"""Sum of decode_prefill_seconds over that plus the sum of
decode_step_seconds, over the window: the share of the engine's time that
prompts take from token generation."""
NAME = 'prefill_time_share'
LAYER = 'decode_engine'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    readers = ctx.module('lib', 'readers')
    prefill = readers.histogram(run, 'decode_prefill_seconds')
    step = readers.histogram(run, 'decode_step_seconds')
    if prefill is None or step is None:
        return None
    return 100.0 * prefill[0] / (prefill[0] + step[0])
