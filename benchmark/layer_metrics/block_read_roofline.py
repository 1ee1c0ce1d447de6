"""Roofline share of a window model's block read: the least time the chip
could take for the LIVE positions the traced slice's steps attended
(flops/<family>.py::block_read: each position's K and V rows of the
key/value heads read once, 2,048 B a layer at the published widths, for all
32 query heads and all B rows; bytes bind) over the device seconds of the
ops under `kv/block_read` in that slice (lib/block_read_ops.py). The walk
reads whole groups of 128 keys in whole chunks and idle slots read the
scratch block: padding reads below 100%, and nothing can read above."""
NAME = 'block_read_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    ops = ctx.module('lib', 'block_read_ops')
    found = ops.reduce(run, ctx)
    if not found:
        return None
    flops, nbytes = ctx.module('flops', ctx.config['family']).block_read(
        ctx.config, found['positions'])
    return ops.roofline_share(run, ctx, flops, nbytes)
