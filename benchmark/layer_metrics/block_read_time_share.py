"""Share of chip 0's busy seconds in the traced slice spent in the ops of a
window model's block read (`jax.named_scope('kv/block_read')`: the gather of
the live groups' K and V rows, the scores of every slot's B rows, the
running softmax, the weighted sum; lib/block_read_ops.py)."""
NAME = 'block_read_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'block_read_ops').time_share(run, ctx)
