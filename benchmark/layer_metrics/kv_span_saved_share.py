"""What the sliding class gives back: 1 - positions the layers hold of the
live contexts over what they would hold were every layer full (program
counters `decode_kv_positions_held` over
`decode_kv_positions_if_unwindowed`, over the window: per prefill and per
step, context a full layer and min(context, span) a sliding one, summed
over slots and layers). 0 while no context has passed the span; the ring's
engagement reading."""
NAME = 'kv_span_saved_share'
LAYER = 'device'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    counter = ctx.module('lib', 'decode_phases').counter
    held = counter(run, 'decode_kv_positions_held')
    whole = counter(run, 'decode_kv_positions_if_unwindowed')
    if held is None or not whole:
        return None
    return 100.0 * (1.0 - held / whole)
