"""Roofline share of the Mamba-2 mixer in the prefills: the least time the
chip could take for the traced slice's prefills (flops/<family>.py::
ssm_prefill for each prompt's length x Mamba layers, never the rung: the
two projections' 2 x 109.6 M FLOPs a row and the chunked scan's terms;
FLOPs bind) over the device seconds of the ops under `ssm/prefill` in that
slice (lib/ssm_mixer_ops.py). A rung's padding is computed and not
counted: it reads below 100%, and nothing can read above."""
NAME = 'ssm_prefill_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    flops = ctx.module('flops', ctx.config['family'])
    return ctx.module('lib', 'ssm_mixer_ops').roofline_share(
        run, ctx, 'prefill', lambda rows: flops.ssm_prefill(ctx.config, rows))
