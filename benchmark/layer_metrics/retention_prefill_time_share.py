"""Share of chip 0's busy seconds in the traced slice spent in the ops of a
prefill's power retention (`jax.named_scope('retention/prefill_scan')`: the
chunked scan over the bucket, inside a chunk the quadratic form with the
cumulative gates, across chunks the carried state; lib/retention_ops.py)."""
NAME = 'retention_prefill_time_share'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'retention_ops').time_share(
        run, ctx, 'retention/prefill_scan')
