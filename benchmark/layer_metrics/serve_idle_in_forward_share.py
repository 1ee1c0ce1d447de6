"""Share of chip 0's idle seconds in the traced slice that fall under an
engine/<call>/forward span: the device waiting while the host dispatches the
next per-op kernel. Each idle gap goes to the leaf span of the worker thread
that covers its midpoint (lib/decode_phases.py)."""
NAME = 'serve_idle_in_forward_share'
LAYER = 'device'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    idle = ctx.module('lib', 'decode_phases').idle_by_leaf(run, ctx)
    if not idle:
        return None
    forward = sum(s for name, s in idle.items() if name.endswith('/forward'))
    return 100.0 * forward / sum(idle.values())
