"""Operations and bytes the mathematics of trinity_large_preview's
distinctive kernels needs, for their roofline shares
(layer_metrics/moe_experts_roofline.py, sliding_read_roofline.py,
kv_decode_read_roofline.py, prefill_attention_roofline.py). Counted from the
work the program's spans report (assignments of live tokens to the HELD
experts, held experts given one, live positions a step's reads attended by
class of layer, the prompt lengths prefilled), never from a padded extent:
a kernel that reads padding reads below 100%, and none can read above.

`config` is the configuration file (published keys at its top level)."""

BF16 = 2


def experts(config, assignments, experts_touched):
    """(FLOPs, bytes) of the grouped expert feed-forward over the held
    experts: three matmuls of hidden x expert width per assignment to one of
    them; each held expert given at least one row has its three matrices
    read once; a bf16 row in and a bf16 row out per assignment."""
    h, f = config['hidden_size'], config['moe_intermediate_size']
    return (assignments * 6 * h * f,
            experts_touched * 3 * h * f * BF16 + assignments * 2 * h * BF16)


def _read(config, positions):
    """(FLOPs, bytes) of a one-token read over `positions` live cached
    positions (summed over slots and the class's layers): a position's K
    and V rows of the key/value heads read once for all query heads; per
    query head a score over head_dim and a weighted sum over head_dim."""
    d = config['head_dim']
    return (positions * config['num_attention_heads'] * 4 * d,
            positions * 2 * config['num_key_value_heads'] * d * BF16)


def decode_read(config, positions):
    """The full layers' read: `positions` = Σ over slots and full layers of
    the context."""
    return _read(config, positions)


def sliding_read(config, positions):
    """The sliding layers' read: `positions` = Σ over slots and sliding
    layers of min(context, window): the in-window rows alone."""
    return _read(config, positions)


def layer_counts(config):
    """(full layers, sliding layers) of the configuration."""
    sliding = sum(t == 'sliding_attention' for t in config['layer_types'])
    return len(config['layer_types']) - sliding, sliding


def visible_pairs(length, span=0):
    """(row, key) pairs of a prompt of `length` under the causal mask, and
    with `span` only while row - key < span."""
    if not span or length <= span:
        return length * (length + 1) // 2
    return span * (span + 1) // 2 + (length - span) * span


def prefill_attention(config, prompt_lens):
    """(FLOPs, bytes) of the two prefill attentions over prompts of
    `prompt_lens`, every layer under its own mask: per visible (row, key)
    pair and query head a score and a weighted sum over head_dim; per layer
    the prompt's q and output rows of the query heads and its k and v rows
    of the key/value heads cross once, in bf16."""
    d, heads, groups = (config['head_dim'], config['num_attention_heads'],
                        config['num_key_value_heads'])
    full, sliding = layer_counts(config)
    span = config['sliding_window']
    pairs = sum(full * visible_pairs(p) + sliding * visible_pairs(p, span)
                for p in prompt_lens)
    rows = sum(prompt_lens) * (full + sliding)
    return (pairs * heads * 4 * d, rows * 2 * (heads + groups) * d * BF16)
