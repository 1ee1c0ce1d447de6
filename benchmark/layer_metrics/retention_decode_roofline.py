"""Roofline share of a decode step's power retention: the least time the
chip could take for the LIVE slot-layers the traced slice's steps advanced
(flops/<family>.py::decode_update: one read of each state, 34.1 MB at the
published widths, and every query head's read of it; bytes bind) over the
device seconds of the ops under `retention/decode_update` in that slice
(lib/retention_ops.py). A path that rewrites the state every token reads at
most 50%; idle slots (advanced too, on the scratch row) read it lower."""
NAME = 'retention_decode_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    ops = ctx.module('lib', 'retention_ops')
    found = ops.reduce(run, ctx)
    if not found:
        return None
    flops, nbytes = ctx.module('flops', ctx.config['family']).decode_update(
        ctx.config, found['state_updates'])
    return ops.roofline_share(run, ctx, 'retention/decode_update', flops,
                              nbytes)
