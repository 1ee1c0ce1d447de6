"""Share of the live tokens' expert assignments that fall on an expert this
model holds (program counter `decode_expert_assignments_held` over
`decode_expert_assignments_total`, over the window): held experts over the
router's width of them, 12.5% at 32 of 256, if the router spreads evenly.
Better lower by convention only: it is the share of a deployment's expert
work that this chip's share of the layer does."""
NAME = 'expert_held_assignment_share'
LAYER = 'decode_engine'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    counter = ctx.module('lib', 'decode_phases').counter
    held = counter(run, 'decode_expert_assignments_held')
    total = counter(run, 'decode_expert_assignments_total')
    if held is None or not total:
        return None
    return 100.0 * held / total
