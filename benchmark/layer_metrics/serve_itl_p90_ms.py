"""90th percentile of the gap between consecutive streamed tokens of one
request on the clients' clock. The slots step in lockstep, so a window of
~70 scheduler cycles holds ~70 distinct gaps however many tokens it streams,
and their 90th percentile jumps between the cycles with 3, 4 or 5 prefills
(853 / 1006 / 1179 ms over three runs of one code, my chip runs, PR 22):
recorded, not judged, until the step is short enough for a window to hold
hundreds of cycles."""
NAME = 'serve_itl_p90_ms'
LAYER = 'decode_engine'
UNIT = 'ms'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    p90 = ctx.stats.percentile(run['samples']['itl_s'], 90)
    return None if p90 is None else p90 * 1e3
