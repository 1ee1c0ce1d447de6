"""Images or sequences finished per second over the window, summed over the
cell's chips: whole steps between two block_until_ready, the window closed
on a step boundary."""
NAME = 'train_samples_per_s'
UNIT = 'samples/s'
RUNNERS = ('train_step',)


def read(run, ctx):
    return run['counts']['samples'] / run['window_s']
