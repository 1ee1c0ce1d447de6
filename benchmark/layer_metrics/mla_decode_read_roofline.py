"""Roofline share of the absorbed decode read: the least time the chip could
take for the LIVE cached positions the traced slice's decode steps attended
(flops/<family>.py::decode_read: a latent row read once for all heads, a
score and a weighted sum per head) over the device seconds of the ops under
`mla/decode_read` in that slice (lib/scoped_ops.py). A read that pads every
slot to the longest context reads well below 100%."""
NAME = 'mla_decode_read_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    scoped = ctx.module('lib', 'scoped_ops')
    found = scoped.reduce(run, ctx)
    if not found:
        return None
    flops, nbytes = ctx.module('flops', ctx.config['family']).decode_read(
        ctx.config, found['work']['context_positions'])
    return scoped.roofline_share(run, ctx, 'mla/decode_read', flops, nbytes)
