"""Operations and bytes the mathematics of sdar_30b_a3b's two distinctive
kernels needs, for their roofline shares (layer_metrics/moe_experts_roofline
.py, block_read_roofline.py). Counted from the work the program's spans
report (assignments of live tokens, experts given one, live positions a
step's block read attended), never from a padded extent: a kernel that
reads padding reads below 100%, and none can read above.

`config` is the configuration file (published keys at its top level, the
block length under `model`)."""

BF16 = 2


def experts(config, assignments, experts_touched):
    """(FLOPs, bytes) of the grouped expert feed-forward: three matmuls of
    hidden x expert width per assignment; each expert given at least one
    row has its three matrices read once; a bf16 row in and a bf16 row out
    per assignment."""
    h, f = config['hidden_size'], config['moe_intermediate_size']
    return (assignments * 6 * h * f,
            experts_touched * 3 * h * f * BF16 + assignments * 2 * h * BF16)


def block_read(config, positions):
    """(FLOPs, bytes) of a step's block read over `positions` live cached
    positions (summed over slots and layers, the block's own B included): a
    position's K and V rows of the key/value heads read once for all query
    heads and all B rows of the block; per query head and row a score over
    head_dim and a weighted sum over head_dim."""
    d = config['head_dim']
    rows = config['model']['block_length']
    return (positions * config['num_attention_heads'] * rows * 4 * d,
            positions * 2 * config['num_key_value_heads'] * d * BF16)
