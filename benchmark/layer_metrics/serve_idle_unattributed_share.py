"""Share of chip 0's idle seconds in the traced slice under none of the
worker thread's leaf spans (engine/<call>/<phase>, scheduler/admit,
scheduler/emit): the check that those spans tile the thread
(lib/decode_phases.py)."""
NAME = 'serve_idle_unattributed_share'
LAYER = 'device'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    idle = ctx.module('lib', 'decode_phases').idle_by_leaf(run, ctx)
    if not idle:
        return None
    return 100.0 * idle.get('no span', 0.0) / sum(idle.values())
