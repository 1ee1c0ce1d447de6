"""Resident bytes of the recurrent-state cache over the rows it holds, all
layers together (program gauge `state_cache_bytes_in_hbm` over the rows a
request can hold, `state_cache_rows_total`, and the scratch row): what one
slot costs in HBM, whatever its context length."""
NAME = 'state_cache_bytes_per_slot'
LAYER = 'device'
UNIT = 'B'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    counter = ctx.module('lib', 'decode_phases').counter
    resident = counter(run, 'state_cache_bytes_in_hbm')
    rows = counter(run, 'state_cache_rows_total')
    if not resident or not rows:
        return None
    # the gauge counts the rows requests can hold; the arrays hold one more
    return resident / (rows + 1)
