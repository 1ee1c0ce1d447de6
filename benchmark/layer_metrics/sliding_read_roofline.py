"""Roofline share of a sliding layer's one-token read: the least time the
chip could take for the LIVE IN-WINDOW positions the traced slice's steps
attended (flops/<family>.py::sliding_read: each position's K and V rows of
the key/value heads read once, 4,096 B a layer at the published widths, for
all 48 query heads; bytes bind) over the device seconds of the ops under
`kv/sliding_read` in that slice (lib/layer_class_ops.py). The walk reads
whole groups of 128 keys in whole chunks, a ring's first and last group hold
positions outside the window and idle slots read the scratch block: padding
reads below 100%, and nothing can read above."""
NAME = 'sliding_read_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    ops = ctx.module('lib', 'layer_class_ops')
    found = ops.reduce(run, ctx)
    if not found:
        return None
    flops, nbytes = ctx.module('flops', ctx.config['family']).sliding_read(
        ctx.config, found['work']['sliding_positions'])
    return ops.roofline_share(run, ctx, ('kv/sliding_read',), flops, nbytes)
