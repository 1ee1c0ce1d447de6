"""Roofline share of the Mamba-2 mixer in the decode steps: the least time
the chip could take for the traced slice's steps (flops/<family>.py::
ssm_step for each step's LIVE slots x Mamba layers: the mixer's 219.3 MB of
weights a layer read once, each live slot's 4.32 MB state row read and
written a layer, a bf16 row in and out; bytes bind) over the device seconds
of the ops under `ssm/step` in that slice (lib/ssm_mixer_ops.py). Idle
slots are computed and not counted: they read below 100%, and nothing can
read above."""
NAME = 'ssm_step_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    flops = ctx.module('flops', ctx.config['family'])
    return ctx.module('lib', 'ssm_mixer_ops').roofline_share(
        run, ctx, 'step', lambda rows: flops.ssm_step(ctx.config, rows))
