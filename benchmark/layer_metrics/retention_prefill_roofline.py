"""Roofline share of a prefill's power retention: the least time the chip
could take for the traced slice's prefills, each by its own LIVE prompt
length (flops/<family>.py::prefill_scan: per layer the lesser of the
quadratic form's and the chunked form's FLOPs, no rung padding; FLOPs bind)
over the device seconds of the ops under `retention/prefill_scan` in that
slice (lib/retention_ops.py). A scan over a rung's padding, or state
contractions in several passes, read it below 100%."""
NAME = 'retention_prefill_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    ops = ctx.module('lib', 'retention_ops')
    found = ops.reduce(run, ctx)
    if not found or not found['prompt_lens']:
        return None
    flops, nbytes = ctx.module('flops', ctx.config['family']).prefill_scan(
        ctx.config, found['prompt_lens'])
    return ops.roofline_share(run, ctx, 'retention/prefill_scan', flops,
                              nbytes)
