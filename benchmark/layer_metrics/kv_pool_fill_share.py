"""Share of the KV pool's blocks held by live requests, mean of the samples
the runner takes every half second of the window from the engine's block
allocator (used / capacity). A request holds the blocks of its prompt and
its whole answer from admission on, so this is an upper bound on what is
written. It stands beside serve_peak_hbm_gb: the pool is reserved whole and
counted there whole, and this says how much of it the traffic ever claims."""
NAME = 'kv_pool_fill_share'
LAYER = 'device'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    used = run['samples'].get('pool_blocks_used')
    blocks = run['counts'].get('pool_blocks')
    if not used or not blocks:
        return None
    return 100.0 * sum(used) / len(used) / blocks
