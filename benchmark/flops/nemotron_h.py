"""Operations and bytes the mathematics of nemotron_h's distinctive kernels
needs, for their roofline shares (layer_metrics/moe_experts_roofline.py,
ssm_step_roofline.py, ssm_prefill_roofline.py). Counted from the work the
program's spans report (assignments of live tokens, experts given one, live
rows through the Mamba layers), never from a padded extent: a kernel that
computes padding reads below 100%, and none can read above.

`config` is the configuration file (published keys at its top level)."""

BF16, F32 = 2, 4


def layer_counts(config):
    """(attention, Mamba, expert) layers of the configuration."""
    pattern = config['hybrid_override_pattern']
    return pattern.count('*'), pattern.count('M'), pattern.count('E')


def experts(config, assignments, experts_touched):
    """(FLOPs, bytes) of the grouped latent expert feed-forward: two
    matmuls of latent x expert width per assignment (relu² has no gate);
    each expert given at least one row has its two matrices read once
    (11.01 MB at 1,024 x 2,688 in bf16); a bf16 latent row in and out per
    assignment."""
    latent, f = config['moe_latent_size'], config['moe_intermediate_size']
    return (assignments * 4 * latent * f,
            experts_touched * 2 * latent * f * BF16
            + assignments * 2 * latent * BF16)


def _mixer(config):
    """(H, P, G, N, K, conv_dim, the mixer's parameters a layer)."""
    h = config['hidden_size']
    heads, p = config['mamba_num_heads'], config['mamba_head_dim']
    g, n, k = (config['n_groups'], config['ssm_state_size'],
               config['conv_kernel'])
    d_inner = heads * p
    conv_dim = d_inner + 2 * g * n
    params = h * (d_inner + conv_dim + heads) + d_inner * h \
        + (k + 1) * conv_dim + d_inner + 3 * heads
    return heads, p, g, n, k, conv_dim, params


def state_row_bytes(config):
    """One request's float32 state a Mamba layer: S and the filter's window
    (4,317,184 B at the published widths)."""
    heads, p, _, n, k, conv_dim, _ = _mixer(config)
    return (heads * p * n + heads * -(-(k - 1) * conv_dim // heads)) * F32


def _per_row(config):
    """FLOPs of one row through one Mamba layer, besides the chunk's terms:
    W_in and W_out (2 x the mixer's 109.6 M weights), the filter and its
    silu, Δ, the decay and the rank-one update of S, its read against C,
    the D term, the gate and the group norm."""
    heads, p, _, n, k, conv_dim, params = _mixer(config)
    return 2 * params + conv_dim * (2 * k + 2) + heads * (4 * p * n + 8) \
        + heads * p * 8


def ssm_step(config, ssm_rows):
    """(FLOPs, bytes) of ONE decode step's Mamba mixers over `ssm_rows` =
    live slots x Mamba layers. Bytes: every Mamba layer's weights read once
    (219.3 MB at the published widths), each live slot's state row read and
    written (8.63 MB a slot-layer), a bf16 row in and out a slot-layer:
    bytes bind."""
    _, layers, _ = layer_counts(config)
    _, _, _, _, _, _, params = _mixer(config)
    h = config['hidden_size']
    return (ssm_rows * _per_row(config),
            layers * params * BF16
            + ssm_rows * (2 * state_row_bytes(config) + 2 * h * BF16))


def ssm_prefill(config, ssm_rows):
    """(FLOPs, bytes) of ONE prefill's Mamba mixers over `ssm_rows` =
    prompt length x Mamba layers (never the rung). Per row besides
    `_per_row`, the chunked form's terms over the chunk before it (half a
    chunk on average, causal): C·B of the group's rows, the decayed weights
    times x, and the start state's read: G·Q·N + H·Q·P + 2·H·P·N FLOPs.
    Bytes: the weights once a layer, a bf16 row in and out a row, the state
    written once a layer: FLOPs bind from a few hundred rows."""
    heads, p, g, n, _, _, params = _mixer(config)
    _, layers, _ = layer_counts(config)
    q, h = config['chunk_size'], config['hidden_size']
    return (ssm_rows * (_per_row(config) + g * q * n + heads * q * p
                        + 2 * heads * p * n),
            layers * (params * BF16 + state_row_bytes(config))
            + ssm_rows * 2 * h * BF16)
