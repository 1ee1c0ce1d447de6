"""Median of the program's replica/queue_wait spans in the window: request
accepted by the scheduler until it is admitted to a slot."""
NAME = 'queue_wait_p50_ms'
LAYER = 'scheduler_entry'
UNIT = 'ms'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    waits = [(b - a) * 1e-6 for name, a, b in run.get('spans', [])
             if name == 'replica/queue_wait']
    return ctx.stats.percentile(waits, 50)
