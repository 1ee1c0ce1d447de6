"""Resident bytes of the KV pool over the token positions it holds, all
layers together (program gauge `kv_cache_bytes_in_hbm` over blocks x block
size): what one cached token costs in HBM. K and V rows of every head in
every layer for a per-head cache, one latent row a layer for MLA."""
NAME = 'kv_cache_bytes_per_token'
LAYER = 'device'
UNIT = 'B'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    resident = ctx.module('lib', 'decode_phases').counter(
        run, 'kv_cache_bytes_in_hbm')
    blocks = run['counts'].get('pool_blocks')
    if not resident or not blocks:
        return None
    # the allocator's capacity leaves out the scratch block; the pool holds it
    return resident / ((blocks + 1) * ctx.traffic['engine']['block_size'])
