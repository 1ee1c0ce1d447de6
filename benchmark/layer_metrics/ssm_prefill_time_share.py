"""Share of chip 0's busy seconds in the traced slice spent in the Mamba-2
mixer of the prefills (`jax.named_scope('ssm/prefill')`: the mixer whole
from `W_in` to `W_out` over a prompt's rung, the filter, the chunked scan,
the state's write, the gate and the norm; lib/ssm_mixer_ops.py)."""
NAME = 'ssm_prefill_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'ssm_mixer_ops').time_share(run, ctx, 'prefill')
