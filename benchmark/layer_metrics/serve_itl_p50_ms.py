"""Median gap between consecutive streamed tokens of one request on the
clients' clock, pooled over the requests of the window: what a streaming
user feels between two tokens. At capacity it is one scheduler cycle, a
lockstep decode step plus the prefills admitted before it, and the cycles
come in kinds (one prefill, two, ...): the median sits on the border between
two kinds and flipped between 475 and 628 ms over four runs of one code (my
chip runs, PR 22). Recorded, not judged."""
NAME = 'serve_itl_p50_ms'
LAYER = 'decode_engine'
UNIT = 'ms'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    p50 = ctx.stats.percentile(run['samples']['itl_s'], 50)
    return None if p50 is None else p50 * 1e3
