"""Share of the device's op time spent in convolution and dot fusions, by the
hlo_category the device trace carries for each op."""
NAME = 'serve_mxu_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'readers').mxu_time_share(
        run, ctx.xplane.MXU_CATEGORIES)
