"""Median of the program's decode_step_seconds histogram over the window (its
last 512 samples): host wall around one eager lockstep step including the
logits copy. An engine-step time, not a device time."""
NAME = 'decode_step_ms_p50'
LAYER = 'decode_engine'
UNIT = 'ms'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    hist = ctx.module('lib', 'readers').histogram(run, 'decode_step_seconds')
    if hist is None:
        return None
    return ctx.stats.percentile(hist[2], 50) * 1e3
