"""Share of chip 0's busy seconds in the traced slice spent in the ops of a
decode step's power retention (`jax.named_scope('retention/decode_update')`:
φ of q and k, the gate, the rank-one update of every slot's state where it
lies, the read of the advanced state; lib/retention_ops.py)."""
NAME = 'retention_decode_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'retention_ops').time_share(
        run, ctx, 'retention/decode_update')
