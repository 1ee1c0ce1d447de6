"""Share of the traced slice in which no operation ran on the device: 1 -
union of the device-op intervals of .xplane.pb over the slice, mean over the
cell's chips."""
NAME = 'train_device_idle_share'
LAYER = 'device'
UNIT = '%'
MOVES = 'train_samples_per_s'
RUNNERS = ('train_step',)


def read(run, ctx):
    return ctx.module('lib', 'readers').device_idle_share(run)
