"""Median host time of one TrainStep.__call__ (it returns at enqueue), from
the harness's clock around the call. Moves the rate only once it nears the
step time."""
NAME = 'train_dispatch_ms_p50'
LAYER = 'entry'
UNIT = 'ms'
MOVES = 'train_samples_per_s'
RUNNERS = ('train_step',)


def read(run, ctx):
    p50 = ctx.stats.percentile(run['samples']['dispatch_s'], 50)
    return None if p50 is None else p50 * 1e3
