"""Plain reference for brumby_14b: the decoder of manifestai/Brumby-14B-Base
(`model_type: brumby`), whole-sequence forward in float32 jax.numpy at
precision "highest". No state, no chunks, no cache, no kernels, no framework
code: power retention is written in its quadratic (attention) form with the
cumulative gates. Beside the forward, what the first layer's recurrence would
hold after a sequence (`first_state`), as plain sums of outer products: the
check holds the served state to it, which no logits row can see.

    x += Ret(norm1(x)); x += FFN(norm2(x)); logits = final_norm(x) · W_head

All norms RMSNorm (eps from the config), no biases. FFN = down(silu(gate(h))
* up(h)). Ret(h), with g(j) = j // (heads / kv heads) the key/value head of
query head j:

    q_t = rope(rmsnorm_d(h_t W_q))   (heads x d)
    k_t = rope(rmsnorm_d(h_t W_k))   (kv heads x d)
    v_t = h_t W_v                    (kv heads x d)
    γ_t = sigmoid(h_t W_g + b)       (kv heads), float32
    a_{t,i} = (q_t^j · k_i^g)² · Π_{s=i+1..t} γ_s^g        for i <= t
    y_t^j = Σ_i a_{t,i} v_i^g / Σ_i a_{t,i}
    Ret = concat_j(y^j) W_o

RoPE turns the pairs (2i, 2i+1) of the whole head by pos · theta^(-2i/d).
The product of gates is exp of a difference of cumulative log-gates.

Departures from the published model, all of the configuration and not of
this file: random weights from the seed; the gate's constant b
(`model.gate_shift`; 0 is the form assumed for the published model);
`num_hidden_layers` as the configuration cuts it. What `config.json` does
not carry (degree 2, the gate, the normaliser, the per-head norms) is the
configuration's `assumed`.

Weights arrive under the program's parameter names, as the program stores
them (bf16 on the chip), and are cast up to float32 where they are used, a
slice of the feed-forward or of the vocabulary at a time, so that the check
fits beside the resident model. Nothing else is taken from the program.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HIGHEST)


def _norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight.astype(F32)


def _rope(x, pos, theta):
    """x (T, heads, d): pairs (2i, 2i+1) turned by pos · theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None, None] * inv_freq
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     -1).reshape(x.shape)


def _pieces(width):
    return 4 if width % 4 == 0 else 1


def _ffn(p, name, h):
    """down(silu(gate(h)) * up(h)), a quarter of the width at a time."""
    gate, up, down = (p[f'{name}.{w}.weight'] for w in ('gate', 'up',
                                                        'down'))
    pieces = _pieces(gate.shape[1])
    width = gate.shape[1] // pieces

    def one(acc, i):
        cols = lambda w: lax.dynamic_slice_in_dim(w, i * width, width, 1)
        mid = jax.nn.silu(_mm(h, cols(gate))) * _mm(h, cols(up))
        return acc + _mm(mid, lax.dynamic_slice_in_dim(
            down, i * width, width, 0)), None

    out, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(pieces))
    return out


def _keys(p, name, m, h):
    """k (T, G, d), v (T, G, d) and the cumulative log-gates (T, G) of the
    rows h (T, hidden)."""
    t = h.shape[0]
    groups, d = m['num_key_value_heads'], m['head_dim']
    k = _mm(h, p[name + '.k_proj.weight']).reshape(t, groups, d)
    v = _mm(h, p[name + '.v_proj.weight']).reshape(t, groups, d)
    k = _rope(_norm(k, p[name + '.k_norm.weight'], m['rms_norm_eps']),
              jnp.arange(t), m['rope_theta'])
    log_gate = jax.nn.log_sigmoid(
        _mm(h, p[name + '.gate.weight']) + F32(m['gate_shift']))  # (T, G)
    return k, v, jnp.cumsum(log_gate, 0)


def _retention(p, name, m, h):
    t = h.shape[0]
    heads, groups, d = (m['num_attention_heads'], m['num_key_value_heads'],
                        m['head_dim'])
    pos = jnp.arange(t)
    q = _mm(h, p[name + '.q_proj.weight']).reshape(t, heads, d)
    q = _rope(_norm(q, p[name + '.q_norm.weight'], m['rms_norm_eps']), pos,
              m['rope_theta'])
    k, v, cum = _keys(p, name, m, h)
    causal = pos[None, :] <= pos[:, None]

    def head(j):                     # one head at a time: (T, T) weights
        g = j // (heads // groups)
        score = jnp.matmul(q[:, j], k[:, g].T, precision=HIGHEST)
        decay = cum[:, g][:, None] - cum[:, g][None, :]
        a = jnp.where(causal, score * score
                      * jnp.exp(jnp.where(causal, decay, 0.0)), 0.0)
        return jnp.matmul(a, v[:, g], precision=HIGHEST) \
            / a.sum(-1, keepdims=True)

    out = lax.map(head, jnp.arange(heads))                # (H, T, d)
    return _mm(out.transpose(1, 0, 2).reshape(t, heads * d),
               p[name + '.o_proj.weight'])


def first_state(p, m, ids, length):
    """What the FIRST layer's recurrence holds after ``length`` tokens of
    ``ids`` (T,), as quadratic forms (G, d, d, d + 1):

        M[a, b] = Σ_{i < length} Π_{s=i+1..length-1} γ_s · k_{i,a} k_{i,b} · [v_i, 1]

    so that the layer's read is q_tᵀ M[.., :d] q_t / q_tᵀ M[.., d] q_t. No
    φ, no layout: plain outer products, a key/value head at a time. The
    first layer's k, v and gates are functions of the tokens alone."""
    name = 'layers.0'
    k, v, cum = _keys(p, name + '.attn', m, _norm(
        p['embed.weight'][ids].astype(F32), p[name + '.norm1.weight'],
        m['rms_norm_eps']))
    live = jnp.arange(ids.shape[0]) < length
    decay = jnp.where(live[:, None], jnp.exp(jnp.where(
        live[:, None], cum[length - 1][None] - cum, 0.0)), 0.0)   # (T, G)
    v1 = jnp.concatenate([v, jnp.ones(v.shape[:2] + (1,), F32)], -1)

    def head(g):
        kg = k[:, g] * decay[:, g][:, None]
        pairs = (kg[:, :, None] * k[:, g][:, None, :]).reshape(
            ids.shape[0], -1)                             # (T, d·d)
        return jnp.matmul(pairs.T, v1[:, g], precision=HIGHEST)

    d = m['head_dim']
    return lax.map(head, jnp.arange(m['num_key_value_heads'])).reshape(
        -1, d, d, d + 1)


def hidden(p, m, ids):
    """Final hidden states (T, h), before the last norm, of ``ids`` (T,)."""
    x = p['embed.weight'][ids].astype(F32)
    for i in range(m['num_hidden_layers']):
        name = f'layers.{i}'
        x = x + _retention(p, name + '.attn', m, _norm(
            x, p[name + '.norm1.weight'], m['rms_norm_eps']))
        x = x + _ffn(p, name + '.ffn', _norm(
            x, p[name + '.norm2.weight'], m['rms_norm_eps']))
    return x


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
    the configuration file, under their own names; `gate_shift` under
    `model`."""
    return {**config, **config['model']}


def make_rows(config, pad=None):
    """rows(params, tokens, positions) -> logits rows (n, V) at
    ``positions`` of the sequence ``tokens``. With ``pad`` the sequence is
    padded to that many tokens, so that every length shares one compiled
    program: padding after a position cannot reach it through the causal
    weights."""
    model = model_of(config)

    @jax.jit
    def run(p, ids, positions):
        return logits(p, model, hidden(p, model, ids)[positions])

    def rows(params, tokens, positions):
        # padded on the host: a slice-update on the device would compile
        # once for every prompt length
        buf = np.zeros((pad or len(tokens),), np.int32)
        buf[:len(tokens)] = tokens
        return run(params, buf, np.asarray(positions, np.int32))
    return rows


def make_state(config, pad=None):
    """state(params, tokens) -> `first_state` after all of ``tokens``; with
    ``pad`` one compiled program for every length, as `make_rows`."""
    model = model_of(config)
    run = jax.jit(lambda p, ids, length: first_state(p, model, ids, length))

    def state(params, tokens):
        buf = np.zeros((pad or len(tokens),), np.int32)
        buf[:len(tokens)] = tokens
        return run(params, buf, np.int32(len(tokens)))
    return state
