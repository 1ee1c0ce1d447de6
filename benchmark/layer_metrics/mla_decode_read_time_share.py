"""Share of chip 0's busy seconds in the traced slice spent in the ops of
the absorbed decode read of the latent cache
(`jax.named_scope('mla/decode_read')`: the page gather, the scores over the
latent rows, softmax, the weighted sum; lib/scoped_ops.py)."""
NAME = 'mla_decode_read_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'scoped_ops').time_share(run, ctx,
                                                      'mla/decode_read')
