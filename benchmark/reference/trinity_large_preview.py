"""Plain reference for trinity_large_preview: the decoder of
arcee-ai/Trinity-Large-Preview (`model_type: afmoe`), whole-sequence forward
in float32 jax.numpy at precision "highest". No cache, no ring, no kernels,
no batching, no framework code.

    x  = E[ids] · sqrt(hidden)                                (mup_enabled)
    a  = n1(x)
    q  = RMSNorm_d(a·W_q) heads of d      k = RMSNorm_d(a·W_k) kv heads of d
    v  = a·W_v                            g = a·W_g  (one gate value a lane of o)
    sliding layer: q, k = RoPE(q, k) (pairs (2i, 2i+1), the whole head);
                   key j visible to row i  iff  0 <= i - j < sliding_window
    full layer:    no position encoding;  key j visible iff j <= i
    query head i reads key/value head i // (heads / kv heads)
    o  = softmax(q·k / sqrt(d)) v  ⊙  sigmoid(g)
    h  = x + n2(o·W_o)                        (a norm on the branch's OUTPUT)
    m  = n3(h)
    dense layer (the first num_dense_layers):  f = Dense(m)
    sparse layer:  s = sigmoid(m·W_r) over the router's `router_width`
                   experts; the num_experts_per_tok largest s + b are chosen;
                   w_e = s_e / (Σ_chosen s + 1e-20) · route_scale
                   f = Shared(m) + Σ_{e chosen and held} w_e · E_e(m)
    y  = h + n4(f)
    logits = norm(y) · W_head

All norms RMSNorm (eps from the config), no biases; every feed-forward is
gated (`silu(x·W_gate) ⊙ x·W_up) · W_down`).

THE SHARE. The configuration is one chip's share of a layer whose 256
experts are spread over 8 chips: `experts_held` = [first, count] names the
experts whose weights exist here, and the routed sum runs over the chosen
experts that are HELD. What the absent experts would add is left out, here
as in the program, and that partial result goes on to the next layer. The
router keeps its full width and its top-k: an assignment to an absent expert
still takes its place among the chosen and its part of the normaliser.
The logits are over the rows of the vocabulary the file holds.

Written from the equations above, which are the issue's reading of the
public `transformers` implementation of `afmoe`; NOT held against that
source here (no network; the image's `transformers` has no `afmoe`). The
configuration's `assumed` lists what `config.json` does not carry.

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


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HIGHEST)


def _norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight.astype(F32)


def _rope(x, pos, theta):
    """x (T, H, d): pairs (2i, 2i+1) turned by pos · theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = (pos.astype(F32)[:, None] * inv_freq)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     -1).reshape(x.shape)


def layer_span(m, i):
    """The window of layer i, 0 for a full layer. (The control
    `full_reference` of tests/benchmark/control_trinity.py makes this 0
    everywhere: the window ignored.)"""
    return m['sliding_window'] \
        if m['layer_types'][i] == 'sliding_attention' else 0


def layer_rotary(m, i):
    """Whether layer i turns q and k by position: the sliding layers alone.
    (The control `rope_everywhere` makes this true everywhere.)"""
    return m['layer_types'][i] == 'sliding_attention'


def visible(rows, keys, span):
    """(len(rows), len(keys)) bool, [i, j] true where the key at position
    keys[j] is visible to the row at position rows[i]."""
    seen = keys[None, :] <= rows[:, None]
    if span:
        seen = seen & (rows[:, None] - keys[None, :] < span)
    return seen


def _by_rows(fn, x):
    """fn over x (T, ...) a block of `ROWS` rows at a time."""
    t = x.shape[0]
    if t <= ROWS:
        return fn(x)
    out = lax.map(fn, x.reshape((t // ROWS, ROWS) + x.shape[1:]))
    return out.reshape((t,) + out.shape[2:])


def _attention(p, name, m, a, span, rotary):
    t = a.shape[0]
    heads, groups, d = (m['num_attention_heads'], m['num_key_value_heads'],
                        m['head_dim'])
    pos = jnp.arange(t)
    eps = m['rms_norm_eps']
    q = _norm(_mm(a, p[name + '.q_proj.weight']).reshape(t, heads, d),
              p[name + '.q_norm.weight'], eps)
    k = _norm(_mm(a, p[name + '.k_proj.weight']).reshape(t, groups, d),
              p[name + '.k_norm.weight'], eps)
    if rotary:
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
                jnp.where(visible(at, pos, span), scores, -jnp.inf), -1)
            return jnp.matmul(probs, vj, precision=HIGHEST)

        return lax.map(rows, jnp.arange(t // block)).reshape(t, d)

    out = lax.map(head, jnp.arange(heads))               # (H, T, d)
    out = out.transpose(1, 0, 2).reshape(t, heads * d)
    out = out * jax.nn.sigmoid(_mm(a, p[name + '.gate_proj.weight']))
    return _mm(out, p[name + '.o_proj.weight'])


def _swiglu(x, gate, up, down):
    return _by_rows(lambda r: _mm(jax.nn.silu(_mm(r, gate)) * _mm(r, up),
                                  down), x)


def _experts(p, name, m, h, forced, tie_margin):
    """(the held experts' part of the routed sum + the shared expert, gap
    (T,)). ``forced`` (T, k): the experts a system chose for each row, over
    the router's whole width, -1 where it reported none. A row's forced
    choice is followed where its gap (the largest s + b left out less the
    smallest chosen) is at most ``tie_margin``; elsewhere, and where none
    was reported, the reference's own top-k stands. The gap returned is the
    forced choice's, or minus the own choice's margin."""
    k, n = m['num_experts_per_tok'], m['router_width']
    first, count = m['experts_held']
    s = jax.nn.sigmoid(_mm(h, p[name + '.router.weight']))
    biased = s + p[name + '.router_bias'].astype(F32)
    _, own = lax.top_k(biased, k)
    given = forced[:, 0] >= 0
    asked = jnp.where(given[:, None], forced, own)
    inside = (asked[:, :, None] == jnp.arange(n)).any(1)            # (T, E)
    gap = jnp.where(inside, -jnp.inf, biased).max(-1) \
        - jnp.where(inside, biased, jnp.inf).min(-1)
    chosen = jnp.where((gap <= tie_margin)[:, None], asked, own)
    w = jnp.take_along_axis(s, chosen, -1)
    if m['route_norm']:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * m['route_scale']
    # (T, held): a chosen expert's weight where it is held here, else 0
    dense = (w[:, :, None] * (chosen[:, :, None] == first + jnp.arange(count))
             ).sum(1)

    def one(acc, e):
        y = _swiglu(h, p[name + '.experts_gate'][e],
                    p[name + '.experts_up'][e], p[name + '.experts_down'][e])
        return acc + dense[:, e][:, None] * y, None

    routed, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(count))
    shared = _swiglu(h, p[name + '.shared.gate.weight'],
                     p[name + '.shared.up.weight'],
                     p[name + '.shared.down.weight'])
    return routed + shared, gap


def hidden(p, m, ids, forced, tie_margin):
    """(final hidden states (T, h) before the last norm, gaps (expert
    layers, T)) of the sequence ``ids`` (T,); ``forced`` (expert layers, T,
    k) as `_experts` takes it."""
    eps = m['rms_norm_eps']
    x = p['embed.weight'][ids].astype(F32)
    if m['mup_enabled']:
        x = x * math.sqrt(m['hidden_size'])
    gaps = []
    for i in range(m['num_hidden_layers']):
        name = f'layers.{i}'
        branch = _attention(p, name + '.attn', m,
                            _norm(x, p[name + '.norm1.weight'], eps),
                            layer_span(m, i), layer_rotary(m, i))
        x = x + _norm(branch, p[name + '.norm2.weight'], eps)
        h = _norm(x, p[name + '.norm3.weight'], eps)
        if i < m['num_dense_layers']:
            f = _swiglu(h, p[name + '.ffn.gate.weight'],
                        p[name + '.ffn.up.weight'],
                        p[name + '.ffn.down.weight'])
        else:
            f, gap = _experts(p, name + '.ffn', m, h, forced[len(gaps)],
                              tie_margin)
            gaps.append(gap)
        x = x + _norm(f, p[name + '.norm4.weight'], eps)
    return x, jnp.stack(gaps)


def logits(p, m, x):
    """Rows x (n, h) through the last norm and the untied head, a slice of
    the vocabulary at a time."""
    x = _norm(x, p['final_norm.weight'], m['rms_norm_eps'])
    head = p['head.weight']
    pieces = 8 if head.shape[1] % 8 == 0 else 1
    width = head.shape[1] // pieces
    out = lax.map(lambda i: _mm(x, lax.dynamic_slice_in_dim(
        head, i * width, width, 1)), jnp.arange(pieces))  # (pieces, n, V/8)
    return out.transpose(1, 0, 2).reshape(x.shape[0], -1)


def model_of(config):
    """The sizes this file reads: the published keys sit at the top level of
    the configuration file, under their own names, with `experts_held` and
    `router_width` beside them."""
    model = {**config, **config['model']}
    model.setdefault('router_width', model['num_experts'])
    model.setdefault('experts_held', [0, model['num_experts']])
    return model


def expert_layers(model):
    return model['num_hidden_layers'] - model['num_dense_layers']


def pad_of(tokens):
    """The padded length `make_rows` wants for sequences of up to ``tokens``
    tokens: a whole number of `ROWS` once past one block."""
    return tokens if tokens <= ROWS else -(-tokens // ROWS) * ROWS


def make_rows(config, pad):
    """rows(params, ids, positions, forced=None, tie_margin=0.0) -> (logits
    rows at `positions` (n, V), their gaps (n, expert layers)), of the
    sequence `ids` padded to `pad` tokens (`pad_of`) so that every length
    shares one compiled program (padding after a position cannot reach it
    through a causal mask, and the feed-forwards act on each token alone).
    `forced`: {position: (expert layers, k) expert ids a system chose
    there}, followed where the reference's own scores call the choice a
    near-tie (`_experts`)."""
    model = model_of(config)
    if pad != pad_of(pad):
        raise ValueError(f'pad={pad} is no whole number of blocks of {ROWS} '
                         f'rows: use pad_of')
    shape = (expert_layers(model), pad, model['num_experts_per_tok'])

    def run(p, ids, positions, forced, tie_margin):
        x, gaps = hidden(p, model, ids, forced, tie_margin)
        return logits(p, model, x[positions]), gaps[:, positions].T

    fn = jax.jit(run)

    def rows(params, ids, positions, forced=None, tie_margin=0.0):
        # padded on the host: a slice-update on the device would compile
        # once for every prompt length
        buf = np.zeros((pad,), np.int32)
        buf[:len(ids)] = ids
        asked = np.full(shape, -1, np.int32)
        for position, chosen in (forced or {}).items():
            asked[:, position] = chosen
        return fn(params, buf, np.asarray(positions, np.int32), asked,
                  np.float32(tie_margin))
    return rows
