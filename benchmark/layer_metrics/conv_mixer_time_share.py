"""Share of chip 0's busy seconds in the traced slice spent in the gated
short convolution, the operator whole from `W_in` to `W_out`
(`jax.named_scope('conv/prefill')` and `('conv/step')`: the input
projection h -> 3h, u = B z, the filter's taps over the rows or over the
request's state row, the gate, the state's write, the output projection;
lib/conv_mixer_ops.py)."""
NAME = 'conv_mixer_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'conv_mixer_ops').time_share(run, ctx)
