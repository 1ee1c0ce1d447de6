"""Roofline share of the gated short convolution: the least time the chip
could take for the traced slice's own calls (flops/<family>.py::conv_mixer
for each call's LIVE rows x conv layers: 8 h^2 FLOPs a row a layer and the
taps' few; a call's operator weights read once a layer, a bf16 row in and
out, the float32 state rows read and written; a step is bound by the 33.6 MB
of weights a layer, a prefill by FLOPs, so each call is priced for what
binds it and the times are summed) over the device seconds of the ops under
`conv/prefill` and `conv/step` in that slice (lib/conv_mixer_ops.py). A
rung's padding and idle slots are computed and not counted: padding reads
below 100%, and nothing can read above."""
NAME = 'conv_mixer_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    flops = ctx.module('flops', ctx.config['family'])
    return ctx.module('lib', 'conv_mixer_ops').roofline_share(
        run, ctx, lambda rows, step: flops.conv_mixer(ctx.config, rows, step))
