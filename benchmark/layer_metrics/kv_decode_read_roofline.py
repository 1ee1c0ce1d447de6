"""Roofline share of a full layer's one-token read: the least time the chip
could take for the LIVE positions the traced slice's steps attended
(flops/<family>.py::decode_read: each position's K and V rows of the
key/value heads read once for all query heads; bytes bind) over the device
seconds of the ops under `kv/decode_read` in that slice
(lib/layer_class_ops.py). The walk reads whole groups of 128 keys in whole
chunks and idle slots read the scratch block: padding reads below 100%, and
nothing can read above."""
NAME = 'kv_decode_read_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    ops = ctx.module('lib', 'layer_class_ops')
    found = ops.reduce(run, ctx)
    if not found:
        return None
    flops, nbytes = ctx.module('flops', ctx.config['family']).decode_read(
        ctx.config, found['work']['full_positions'])
    return ops.roofline_share(run, ctx, ('kv/decode_read',), flops, nbytes)
