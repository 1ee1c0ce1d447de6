"""Operations and bytes the mathematics of lfm2_8b_a1b's distinctive kernels
needs, for their roofline shares (layer_metrics/moe_experts_roofline.py,
conv_mixer_roofline.py; `decode_read` and `prefill_attention` for the
readers the cell will join once their lists may name it). Counted from the
work the program's spans report (assignments of live tokens, experts given
one, live positions a step's reads attended, live rows through the conv
layers, the prompt lengths prefilled), never from a padded extent: a kernel
that computes padding reads below 100%, and none can read above.

`config` is the configuration file (published keys at its top level)."""

BF16, F32 = 2, 4


def _head_dim(config):
    return config['hidden_size'] // config['num_attention_heads']


def layer_counts(config):
    """(attention layers, conv layers) of the configuration."""
    conv = sum(t == 'conv' for t in config['layer_types'])
    return len(config['layer_types']) - conv, conv


def experts(config, assignments, experts_touched):
    """(FLOPs, bytes) of the grouped expert feed-forward: three matmuls of
    hidden x expert width per assignment; each expert given at least one
    row has its three matrices read once; a bf16 row in and a bf16 row out
    per assignment."""
    h, f = config['hidden_size'], config['moe_intermediate_size']
    return (assignments * 6 * h * f,
            experts_touched * 3 * h * f * BF16 + assignments * 2 * h * BF16)


def decode_read(config, positions):
    """(FLOPs, bytes) of the attention layers' one-token read over
    `positions` live cached positions (summed over slots and attention
    layers): a position's K and V rows of the key/value heads read once for
    all query heads; per query head a score and a weighted sum over
    head_dim."""
    d = _head_dim(config)
    return (positions * config['num_attention_heads'] * 4 * d,
            positions * 2 * config['num_key_value_heads'] * d * BF16)


def prefill_attention(config, prompt_lens):
    """(FLOPs, bytes) of the causal grouped prefill attention over prompts
    of `prompt_lens`, every attention layer: per visible (row, key) pair and
    query head a score and a weighted sum over head_dim; per layer the
    prompt's q and output rows of the query heads and its k and v rows of
    the key/value heads cross once, in bf16."""
    d, heads, groups = (_head_dim(config), config['num_attention_heads'],
                        config['num_key_value_heads'])
    attention, _ = layer_counts(config)
    pairs = attention * sum(p * (p + 1) // 2 for p in prompt_lens)
    rows = attention * sum(prompt_lens)
    return (pairs * heads * 4 * d, rows * 2 * (heads + groups) * d * BF16)


def conv_mixer(config, conv_rows, step):
    """(FLOPs, bytes) of ONE engine call's gated short convolutions, the
    operator whole from W_in to W_out, over `conv_rows` = live rows x conv
    layers (a prefill's prompt length, never its rung; a step's live
    slots). Per row and layer: the input projection h -> 3h and the output
    projection h -> h, 8 h^2, and per channel u = B z, L taps' products and
    their sum and the gate, 2 L + 1. Bytes: every conv layer's two
    projections (4 h^2 bf16: 33.6 MB at h = 2,048) and taps read once a
    call; a bf16 row in and a bf16 row out per row and layer; the float32
    state, L - 1 values of u a channel: a step (`step` true) reads and
    writes it for every live row, a prefill writes it once a layer. A step
    is bound by the weights' bytes, a prefill of a few hundred rows or more
    by FLOPs."""
    h, taps = config['hidden_size'], config['conv_L_cache']
    _, layers = layer_counts(config)
    state = (taps - 1) * h * F32
    return (conv_rows * (8 * h * h + (2 * taps + 1) * h),
            layers * (4 * h * h + taps * h) * BF16
            + conv_rows * 2 * h * BF16
            + (conv_rows * 2 * state if step else layers * state))
