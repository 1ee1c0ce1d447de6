"""Plain reference for lfm2_8b_a1b: the decoder of LiquidAI/LFM2-8B-A1B
(`model_type: lfm2_moe`), whole-sequence forward in float32 jax.numpy at
precision "highest". No cache, no state, no kernels, no batching, no
framework code.

    x0 = E[ids]
    a  = operator_norm(x)
    conv layer:  [B | C | z] = a·W_in             (h -> 3h, three chunks of h in this order)
                 u_t = B_t ⊙ z_t
                 c_t = w[0] ⊙ u_{t-2} + w[1] ⊙ u_{t-1} + w[2] ⊙ u_t     (u before position 0 is zero;
                       one L-tap filter a channel, the LAST tap on the newest position: torch Conv1d's
                       cross-correlation order; written for L = conv_L_cache taps)
                 o_t = (C_t ⊙ c_t)·W_out           (no activation: the two gates are products)
    attn layer:  q = RMSNorm_d(a·W_q) heads of d   k = RMSNorm_d(a·W_k) kv heads of d   v = a·W_v
                 q, k = RoPE(q, k) (pairs (2i, 2i+1), the whole head, theta from the config)
                 query head i reads key/value head i // (heads / kv heads); causal; d = hidden / heads
                 o = softmax(q·k / sqrt(d)) v · W_out
    h  = x + o
    m  = ffn_norm(h)
    dense layer (the first num_dense_layers):  f = Dense(m)
    sparse layer:  s = sigmoid(m·W_r) over num_experts; the num_experts_per_tok largest s + b are chosen
                   w_e = s_e / (Σ_chosen s + 1e-6) · routed_scaling_factor      (the UNBIASED scores weigh)
                   f = Σ_{e chosen} w_e · E_e(m)                                (no shared expert)
    y  = h + f
    logits = embedding_norm(y) · Eᵀ                (the final norm sits at the OUTPUT; the head is E)

All norms RMSNorm (eps `norm_eps`), no biases; every feed-forward is gated
(`silu(x·W_gate) ⊙ x·W_up) · W_down`). The convolution is three shifted
products over the sequence (`history`): nothing is carried from row to row.

The operator, the attention, the two norms, the residuals, `embedding_norm`
and the tied head are as the image's `transformers.models.lfm2` has them
(`Lfm2ShortConv.slow_forward`, `Lfm2Attention`, `Lfm2DecoderLayer`,
`Lfm2Model.forward`), and tests/framework/test_hybrid_conv_moe_lm.py holds
`conv_block`, `attention_block` and `decoder_layer` below to those classes
with the same weights. The expert block is the issue's reading of the public
`lfm2_moe`, which the image does not have: NOT held against that source
here. The configuration's `assumed` lists what `config.json` does not carry.

Weights arrive under the program's parameter names, as the program stores
them (bf16 on the chip), and are cast up to float32 where they are used, an
expert or a block of rows at a time, so that the check fits the chip beside
the served model: attention a head and `ROWS` query rows at a time (never a
(T, T) array), the feed-forwards `ROWS` rows at a time. Nothing else is taken
from the program but the experts it reports it chose at the checked
positions, and those are judged, not trusted: `rows` takes `forced` as
reference/kanana2_30b_a3b.py does (its docstring says why), follows a
reported choice only where this file's OWN scores call it a near-tie (every
chosen expert's s + b within `tie_margin` of every expert left out), and
returns the gaps beside the rows.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
# rows a block of the attention and of the feed-forwards holds; a sequence
# longer than this is padded to a multiple of it (`make_rows`)
ROWS = 1024
# what the router's normaliser adds to the chosen scores' sum
NORM_EPSILON = 1e-6


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HIGHEST)


def _norm(x, weight, eps):
    inv = lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * inv * weight.astype(F32)


def _rope(x, pos, theta):
    """x (T, n, d): pairs (2i, 2i+1) turned by pos · theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = (pos.astype(F32)[:, None] * inv_freq)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     -1).reshape(x.shape)


def _by_rows(fn, x):
    """fn over x (T, ...) a block of `ROWS` rows at a time."""
    t = x.shape[0]
    if t <= ROWS:
        return fn(x)
    out = lax.map(fn, x.reshape((t // ROWS, ROWS) + x.shape[1:]))
    return out.reshape((t,) + out.shape[2:])


def history(u, back, prompt_len):
    """u_{t-back} for every row t of u (T, h), zero before position 0: what
    the filter's tap reads ``back`` rows ago. ``prompt_len`` is not read
    here. (The control `state_zero` of tests/benchmark/control_lfm2.py
    replaces this function: a row at or past ``prompt_len`` then reads zero
    for every position before it, what a system would read that lost the
    state its prefill left.)"""
    return jnp.pad(u, ((back, 0), (0, 0)))[:u.shape[0]]


def conv_taps(w):
    """The filter's taps (L, h) as the mathematics reads them, tap j on
    position t - (L - 1) + j. (The control `taps_reversed` returns them in
    the reverse order.)"""
    return w.astype(F32)


def conv_u(p, name, a):
    """(u (T, h), C (T, h)) of a conv layer over its normed input a."""
    h = a.shape[1]
    rows = _by_rows(lambda r: _mm(r, p[name + '.in_proj.weight']), a)
    return rows[:, :h] * rows[:, 2 * h:], rows[:, h:2 * h]


def conv_block(p, name, a, prompt_len=0):
    """The gated short convolution of the normed rows a (T, h), from W_in
    to W_out, as L shifted products over the sequence."""
    u, gate = conv_u(p, name, a)
    taps = conv_taps(p[name + '.taps'])
    n = taps.shape[0]
    conv = sum(taps[j] * history(u, n - 1 - j, prompt_len)
               for j in range(n))
    return _by_rows(lambda r: _mm(r, p[name + '.out_proj.weight']),
                    gate * conv)


def attention_block(p, name, m, a):
    t = a.shape[0]
    heads, groups, d = (m['num_attention_heads'], m['num_key_value_heads'],
                        m['head_dim'])
    pos = jnp.arange(t)
    eps = m['norm_eps']
    q = _norm(_mm(a, p[name + '.q_proj.weight']).reshape(t, heads, d),
              p[name + '.q_norm.weight'], eps)
    k = _norm(_mm(a, p[name + '.k_proj.weight']).reshape(t, groups, d),
              p[name + '.k_norm.weight'], eps)
    q, k = (_rope(z, pos, m['rope_theta']) for z in (q, k))
    v = _mm(a, p[name + '.v_proj.weight']).reshape(t, groups, d)
    rep = heads // groups
    block = min(t, ROWS)

    def head(j):                     # one head, `ROWS` query rows at a time
        kj, vj = k[:, j // rep], v[:, j // rep]

        def rows(i):
            at = i * block + jnp.arange(block)
            scores = jnp.matmul(lax.dynamic_slice_in_dim(q[:, j], i * block,
                                                         block), kj.T,
                                precision=HIGHEST) / math.sqrt(d)
            probs = jax.nn.softmax(
                jnp.where(pos[None, :] <= at[:, None], scores, -jnp.inf), -1)
            return jnp.matmul(probs, vj, precision=HIGHEST)

        return lax.map(rows, jnp.arange(t // block)).reshape(t, d)

    out = lax.map(head, jnp.arange(heads))               # (H, T, d)
    out = out.transpose(1, 0, 2).reshape(t, heads * d)
    return _mm(out, p[name + '.o_proj.weight'])


def _swiglu(x, gate, up, down):
    return _by_rows(lambda r: _mm(jax.nn.silu(_mm(r, gate)) * _mm(r, up),
                                  down), x)


def expert_weights(s, biased, chosen):
    """The weights of the ``chosen`` experts (T, k) before the normaliser:
    their UNBIASED scores s; ``biased`` = s + b chose them and weighs
    nothing. (The control `biased_weights` returns the biased scores.)"""
    return jnp.take_along_axis(s, chosen, -1)


def _experts(p, name, m, h, forced, tie_margin):
    """(the routed sum, gap (T,)). ``forced`` (T, k): the experts a system
    chose for each row, -1 where it reported none. A row's forced choice is
    followed where its gap (the largest s + b left out less the smallest
    chosen) is at most ``tie_margin``; elsewhere, and where none was
    reported, the reference's own top-k stands. The gap returned is the
    forced choice's, or minus the own choice's margin."""
    k, n = m['num_experts_per_tok'], m['num_experts']
    s = jax.nn.sigmoid(_mm(h, p[name + '.router.weight']))
    biased = s + p[name + '.router_bias'].astype(F32)
    _, own = lax.top_k(biased, k)
    given = forced[:, 0] >= 0
    asked = jnp.where(given[:, None], forced, own)
    inside = (asked[:, :, None] == jnp.arange(n)).any(1)            # (T, E)
    gap = jnp.where(inside, -jnp.inf, biased).max(-1) \
        - jnp.where(inside, biased, jnp.inf).min(-1)
    chosen = jnp.where((gap <= tie_margin)[:, None], asked, own)
    w = expert_weights(s, biased, chosen)
    if m['norm_topk_prob']:
        w = w / (w.sum(-1, keepdims=True) + NORM_EPSILON)
    w = w * m['routed_scaling_factor']
    dense = (w[:, :, None] * (chosen[:, :, None] == jnp.arange(n))).sum(1)

    def one(acc, e):
        y = _swiglu(h, p[name + '.experts_gate'][e],
                    p[name + '.experts_up'][e], p[name + '.experts_down'][e])
        return acc + dense[:, e][:, None] * y, None

    routed, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(n))
    return routed, gap


def is_conv(m, i):
    return m['layer_types'][i] == 'conv'


def decoder_layer(p, m, i, x, forced=None, tie_margin=0.0, prompt_len=0):
    """(layer i's output for the rows x (T, h), the experts' gap (T,) or
    None for a dense layer)."""
    eps = m['norm_eps']
    name = f'layers.{i}'
    a = _norm(x, p[name + '.operator_norm.weight'], eps)
    x = x + (conv_block(p, name + '.operator', a, prompt_len)
             if is_conv(m, i)
             else attention_block(p, name + '.operator', m, a))
    h = _norm(x, p[name + '.ffn_norm.weight'], eps)
    if i < m['num_dense_layers']:
        return x + _swiglu(h, p[name + '.ffn.gate.weight'],
                           p[name + '.ffn.up.weight'],
                           p[name + '.ffn.down.weight']), None
    if forced is None:
        forced = jnp.full((x.shape[0], m['num_experts_per_tok']), -1)
    f, gap = _experts(p, name + '.ffn', m, h, forced, tie_margin)
    return x + f, gap


def hidden(p, m, ids, forced, tie_margin, prompt_len=0):
    """(final hidden states (T, h) before the last norm, gaps (expert
    layers, T)) of the sequence ``ids`` (T,); ``forced`` (expert layers, T,
    k) as `_experts` takes it."""
    x = p['embed.weight'][ids].astype(F32)
    gaps = []
    for i in range(m['num_hidden_layers']):
        x, gap = decoder_layer(
            p, m, i, x, None if i < m['num_dense_layers']
            else forced[len(gaps)], tie_margin, prompt_len)
        if gap is not None:
            gaps.append(gap)
    return x, jnp.stack(gaps)


def logits(p, m, x):
    """Rows x (n, h) through the last norm and the tied head, a slice of
    the vocabulary at a time."""
    x = _norm(x, p['embedding_norm.weight'], m['norm_eps'])
    table = p['embed.weight']                              # (V, h)
    pieces = 8 if table.shape[0] % 8 == 0 else 1
    width = table.shape[0] // pieces
    out = lax.map(lambda i: _mm(x, lax.dynamic_slice_in_dim(
        table, i * width, width, 0).T), jnp.arange(pieces))
    return out.transpose(1, 0, 2).reshape(x.shape[0], -1)


def first_conv_state(p, m, ids, prompt_len):
    """What the FIRST conv layer carries after the prompt ``ids[:prompt_len]``
    (ids (T,) padded past it): its last L - 1 values of u, (L - 1, h),
    oldest first, zero where the prompt has not that many tokens. Plain
    products of the layer's own rows; the layers before it (none where the
    model opens with a conv layer) run with the reference's own choices."""
    first = next(i for i in range(m['num_hidden_layers']) if is_conv(m, i))
    x = p['embed.weight'][ids].astype(F32)
    for i in range(first):
        x, _ = decoder_layer(p, m, i, x)
    name = f'layers.{first}'
    u, _ = conv_u(p, name + '.operator',
                  _norm(x, p[name + '.operator_norm.weight'], m['norm_eps']))
    keep = m['conv_L_cache'] - 1
    u = jnp.pad(u, ((keep, 0), (0, 0)))             # row i holds u_{i-keep}
    return lax.dynamic_slice_in_dim(u, prompt_len, keep, 0)


def model_of(config):
    """The sizes this file reads: the published keys sit at the top level of
    the configuration file, under their own names; a head is hidden /
    heads (the family has no `head_dim` key)."""
    model = {**config, **config['model']}
    model['head_dim'] = model['hidden_size'] // model['num_attention_heads']
    return model


def expert_layers(model):
    return model['num_hidden_layers'] - model['num_dense_layers']


def pad_of(tokens):
    """The padded length `make_rows` wants for sequences of up to ``tokens``
    tokens: a whole number of `ROWS` once past one block."""
    return tokens if tokens <= ROWS else -(-tokens // ROWS) * ROWS


def _padded(ids, pad):
    # padded on the host: a slice-update on the device would compile once
    # for every prompt length
    buf = np.zeros((pad,), np.int32)
    buf[:len(ids)] = ids
    return buf


def make_rows(config, pad):
    """rows(params, ids, positions, forced=None, tie_margin=0.0) -> (logits
    rows at `positions` (n, V), their gaps (n, expert layers)), of the
    sequence `ids` padded to `pad` tokens (`pad_of`) so that every length
    shares one compiled program (padding after a position cannot reach it:
    the filter and the mask are causal, and the feed-forwards act on each
    token alone). `forced`: {position: (expert layers, k) expert ids a
    system chose there}, followed where the reference's own scores call the
    choice a near-tie (`_experts`). `positions[0]` is the prompt's last
    row: what `history` is told, and reads only under a control."""
    model = model_of(config)
    if pad != pad_of(pad):
        raise ValueError(f'pad={pad} is no whole number of blocks of {ROWS} '
                         f'rows: use pad_of')
    shape = (expert_layers(model), pad, model['num_experts_per_tok'])

    def run(p, ids, positions, forced, tie_margin):
        x, gaps = hidden(p, model, ids, forced, tie_margin,
                         positions[0] + 1)
        return logits(p, model, x[positions]), gaps[:, positions].T

    fn = jax.jit(run)

    def rows(params, ids, positions, forced=None, tie_margin=0.0):
        asked = np.full(shape, -1, np.int32)
        for position, chosen in (forced or {}).items():
            asked[:, position] = chosen
        return fn(params, _padded(ids, pad),
                  np.asarray(positions, np.int32), asked,
                  np.float32(tie_margin))
    return rows


def make_first_conv_state(config, pad):
    """state(params, prompt) -> `first_conv_state` after ``prompt``, one
    compiled program for every length up to ``pad``."""
    model = model_of(config)
    fn = jax.jit(lambda p, ids, n: first_conv_state(p, model, ids, n))

    def state(params, prompt):
        return fn(params, _padded(prompt, pad), np.int32(len(prompt)))
    return state
