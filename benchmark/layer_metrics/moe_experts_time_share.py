"""Share of chip 0's busy seconds in the traced slice spent in the ops of
the grouped expert feed-forward (`jax.named_scope('moe/experts')`: the sort
of the assignments, the three ragged matmuls, the weighted sum back;
lib/scoped_ops.py)."""
NAME = 'moe_experts_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'scoped_ops').time_share(run, ctx, 'moe/experts')
