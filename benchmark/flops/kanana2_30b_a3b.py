"""Operations and bytes the mathematics of kanana2_30b_a3b's two distinctive
kernels needs, for their roofline shares (layer_metrics/moe_experts_roofline
.py, mla_decode_read_roofline.py). Counted from the work the program's
counters report (assignments of live tokens, experts given one, live
positions attended), never from a padded extent: a kernel that reads padding reads
below 100%, and none can read above.

`config` is the configuration file (published keys at its top level)."""

BF16 = 2


def experts(config, assignments, experts_touched):
    """(FLOPs, bytes) of the grouped expert feed-forward: three matmuls of
    hidden x expert width per assignment; each expert given at least one
    row has its three matrices read once; a bf16 row in and a bf16 row out
    per assignment."""
    h, f = config['hidden_size'], config['moe_intermediate_size']
    return (assignments * 6 * h * f,
            experts_touched * 3 * h * f * BF16 + assignments * 2 * h * BF16)


def decode_read(config, positions):
    """(FLOPs, bytes) of the absorbed decode read over `positions` live
    cached positions (summed over slots and layers): a latent row of
    kv_lora_rank + rope lanes read once for all heads; per head a score
    over the row and a weighted sum over its kv_lora_rank lanes."""
    rank = config['kv_lora_rank']
    width = rank + config['qk_rope_head_dim']
    return (positions * config['num_attention_heads'] * 2 * (width + rank),
            positions * width * BF16)
