"""Share of the traced slice in which no operation ran on the device: 1 -
union of the device-op intervals of .xplane.pb over the slice, mean over the
cell's chips."""
NAME = 'serve_device_idle_share'
LAYER = 'device'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'readers').device_idle_share(run)
