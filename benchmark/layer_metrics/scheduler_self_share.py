"""Share of the scheduler worker thread's busy time spent outside engine
calls: (cycle - wait - engine) / (cycle - wait) over the sums of
decode_scheduler_phase_seconds. Admission, token accounting, stream emits,
retiring, and per-request trace bookkeeping where requests are traced."""
NAME = 'scheduler_self_share'
LAYER = 'scheduler'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'decode_phases').scheduler_self_share(run)
