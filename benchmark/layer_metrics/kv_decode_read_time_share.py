"""Share of chip 0's busy seconds in the traced slice spent in the ops of a
full layer's one-token read of a model with layer classes
(`jax.named_scope('kv/decode_read')`: the gather of the live groups' K and V
rows of a slot's whole context, the scores of its query heads, the running
softmax, the weighted sum; lib/layer_class_ops.py)."""
NAME = 'kv_decode_read_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'layer_class_ops').time_share(
        run, ctx, ('kv/decode_read',))
