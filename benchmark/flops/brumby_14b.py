"""Operations and bytes the mathematics of brumby_14b's two distinctive
kernels needs, for their roofline shares (layer_metrics/
retention_decode_roofline.py, retention_prefill_roofline.py): the least,
whatever implements it. Counted from the work the program's spans report
(live slot-layers a step advanced, each prefill's own prompt length), never
from a padded extent: a kernel that computes padding reads below 100%, and
none can read above.

`config` is the configuration file (published keys at its top level)."""

F32 = 4


def state_values(config):
    """Values of one key/value head's state: D x (d + 1), D = d(d+1)/2 the
    symmetric degree-2 coordinates of a d-value head, d columns of S and
    one of the normaliser."""
    d = config['head_dim']
    return d * (d + 1) // 2 * (d + 1)


def state_bytes(config):
    """Float32 bytes of one request's state in one layer: every key/value
    head's D x (d + 1)."""
    return config['num_key_value_heads'] * state_values(config) * F32


def decode_update(config, slot_layers):
    """(FLOPs, bytes) of a decode step's retention over `slot_layers` live
    slot-layers: every query head reads its key/value head's state once,
    2·D·(d + 1) a head; the state itself is read once. That it is also
    rewritten every token is one implementation's choice (it then reads at
    most 50%); a path that folds a chunk of tokens at a time may read
    more."""
    return (slot_layers * config['num_attention_heads'] * 2
            * state_values(config),
            slot_layers * state_bytes(config))


def prefill_scan_flops(config, prompt_len):
    """FLOPs of ONE layer's retention over a prompt of `prompt_len` live
    tokens: the lesser of the quadratic form's and the chunked form's.
    Quadratic: every query head, for each of the t(t+1)/2 pairs, a score of
    2d and a weighted sum over [v, 1] of 2(d + 1), and at the end the state
    itself, every key/value head's φ(k) [v, 1]ᵀ, 2·D·(d + 1) a token.
    Chunked, at its cheapest (a chunk of one, the recurrence): per token
    every query head's read of the state and every key/value head's
    update of it, 2·D·(d + 1) each."""
    d, heads, groups = (config['head_dim'], config['num_attention_heads'],
                        config['num_key_value_heads'])
    t = int(prompt_len)
    build = groups * 2 * state_values(config) * t
    quadratic = heads * (t * (t + 1) // 2) * (2 * d + 2 * (d + 1)) + build
    chunked = heads * 2 * state_values(config) * t + build
    return min(quadratic, chunked)


def prefill_scan(config, prompt_lens):
    """(FLOPs, bytes) over a slice's prefill calls, each by its own prompt
    length, all layers; bound by FLOPs (the bytes are the final states
    written once: small beside them)."""
    layers = config['num_hidden_layers']
    return (layers * sum(prefill_scan_flops(config, t) for t in prompt_lens),
            layers * len(prompt_lens) * state_bytes(config))
