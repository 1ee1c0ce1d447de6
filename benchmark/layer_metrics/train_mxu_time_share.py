"""Share of the device's op time spent in convolution and dot fusions, by the
hlo_category the device trace carries for each op."""
NAME = 'train_mxu_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'train_samples_per_s'
RUNNERS = ('train_step',)


def read(run, ctx):
    return ctx.module('lib', 'readers').mxu_time_share(
        run, ctx.xplane.MXU_CATEGORIES)
