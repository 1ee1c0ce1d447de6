"""Roofline share of the two prefill attentions: the least time the chip could
take for the prompts the traced slice prefilled, every layer under its own
mask (flops/<family>.py::prefill_attention: max(bytes / bandwidth, masked
FLOPs / peak); FLOPs bind past a few hundred tokens), over the device
seconds of the ops under `attn/full_prefill` and `attn/sliding_prefill` in
that slice (lib/layer_class_ops.py). A rung's padding past the prompt and
the masked part of a chunk pair are computed and not counted: below 100%,
and nothing can read above."""
NAME = 'prefill_attention_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    ops = ctx.module('lib', 'layer_class_ops')
    found = ops.reduce(run, ctx)
    if not found or not found['work']['prompt_lens']:
        return None
    flops, nbytes = ctx.module('flops', ctx.config['family']).prefill_attention(
        ctx.config, found['work']['prompt_lens'])
    return ops.roofline_share(run, ctx, ops.PREFILL, flops, nbytes)
