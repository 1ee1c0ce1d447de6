"""Median time to first token on the clients' clock: request due to be sent
until its first streamed token, over the requests sent in the window (some
150 of them); a failed or refused request counts as +inf. At capacity it is
queueing behind the prefills admitted in the same scheduler pass, and it
swings by 8% from run to run (my chip runs, PR 22): recorded, not judged,
until a cell below the knee exists."""
NAME = 'serve_ttft_p50_ms'
LAYER = 'scheduler_entry'
UNIT = 'ms'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    p50 = ctx.stats.percentile(run['samples']['ttft_s'], 50)
    return None if p50 is None else p50 * 1e3
