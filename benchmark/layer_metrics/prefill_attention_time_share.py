"""Share of chip 0's busy seconds in the traced slice spent in the ops of the
two prefill attentions of a model with layer classes
(`jax.named_scope('attn/full_prefill')` and `('attn/sliding_prefill')`: the
causal grouped attention over a prompt's own projections, a chunk of query
rows against the chunks of keys its mask lets it see;
lib/layer_class_ops.py)."""
NAME = 'prefill_attention_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    ops = ctx.module('lib', 'layer_class_ops')
    return ops.time_share(run, ctx, ops.PREFILL)
