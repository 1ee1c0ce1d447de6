"""Mean of the program's decode_slot_occupancy histogram over the window:
active slots over all slots, per decode step."""
NAME = 'slot_occupancy_mean'
LAYER = 'scheduler'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    hist = ctx.module('lib', 'readers').histogram(
        run, 'decode_slot_occupancy')
    if hist is None or not hist[1]:
        return None
    return 100.0 * hist[0] / hist[1]
