"""Share of chip 0's busy seconds in the traced slice spent in the ops of a
sliding layer's one-token read (`jax.named_scope('kv/sliding_read')`: the
gather of the ring's live groups' K and V rows, the scores of a slot's query
heads, the mask by position, the running softmax, the weighted sum;
lib/layer_class_ops.py)."""
NAME = 'sliding_read_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'layer_class_ops').time_share(
        run, ctx, ('kv/sliding_read',))
