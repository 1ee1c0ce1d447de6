"""Plain reference for nemotron3_super_120b_a12b: the decoder of
nvidia/NVIDIA-Nemotron-3-Super-120B-A12B (`model_type: nemotron_h`),
whole-sequence forward in float32 jax.numpy at precision "highest". No
cache, no chunked scan, no kernels, no batching, no framework code.

    x0 = E[ids]
    every layer:  a = RMSNorm(x);  x = x + Mixer(a), the mixer of its letter
                  in hybrid_override_pattern
    'M' (Mamba-2):
        [z | xBC | dt] = a·W_in         (widths H·P | H·P + 2·G·N | H)
        xBC_t = silu(Σ_{j<K} w[j] ⊙ xBC_{t-(K-1)+j} + b)   (zero before 0; the
                LAST tap on the newest position: torch Conv1d's order)
        [x | B | C] = xBC_t;  x (H, P), B and C (G, N); head h reads group
                h // (H / G)
        Δ = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t,h = exp(Δ_t,h A_h) S_{t-1},h + Δ_t,h x_t,h ⊗ B_t,g   (S_{-1} = 0)
        y_t,h = S_t,h C_t,g + D_h x_t,h          (one row after another)
        o = GroupRMSNorm(y ⊙ silu(z)) · w_norm · W_out   (groups of H·P / G)
    'E' (latent relu² experts):
        s = sigmoid(a·W_r) over the router's experts; the k largest s + b
        are chosen; w_e = s_e / (Σ_chosen s + 1e-20) · routed_scaling_factor
        (the UNBIASED scores weigh);  ℓ = a·W_down (h -> latent)
        o = (Σ_{e chosen, held} w_e · relu(ℓ·W1_e)²·W2_e)·W_up
            + relu(a·W_s1)²·W_s2                       (the shared expert)
    '*' (attention): q = a·W_q heads of d, k = a·W_k, v = a·W_v kv heads of
        d; NO norm, NO rotary turn; query head i reads key/value head
        i // (heads / kv heads); causal; o = softmax(q·k / sqrt(d)) v · W_o
    logits = RMSNorm_f(x)·W_head                      (untied)

All norms RMSNorm (eps `layer_norm_epsilon`), no biases but the filter's.
The experts held are [first, first + count) of the router's (`experts_held`):
the same share as the program's, what the others would add left out alike.

Weights arrive under the program's parameter names, as the program stores
them (bf16 on the chip), and are cast up to float32 where they are used, an
expert or a block of rows at a time, so that the check fits the chip beside
the served model. Nothing else is taken from the program but the experts it
reports it chose, and those are judged, not trusted: `rows` takes `forced`
as reference/kanana2_30b_a3b.py does (its docstring says why), follows a
reported choice only where this file's OWN scores call it a near-tie (every
chosen expert's s + b within `tie_margin` of every expert left out), and
returns the gaps beside the rows.

`first_ssm_state` is what the FIRST Mamba layer carries after a prompt: S
and the filter's last K - 1 rows of xBC. Its input a is taken as the
configuration's stated activation dtype holds it (`model.dtype`: a bf16
row on the chip, float32 at the tests' sizes; rounded to nearest even by
integer arithmetic on the float32 pattern, `stated`, since a convert pair
to bf16 and back is dropped by the chip's compiler): everything from there
on is float32 in the program too (ops/llm_ops.py "Mamba-2", precision), so
the state is held to float32 arithmetic on the same row, and a state kept
in a narrower type, or made of rows rounded to one, shows.
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
NORM_EPSILON = 1e-20


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HIGHEST)


def _norm(x, weight, eps):
    inv = lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * inv * weight.astype(F32)


def _by_rows(fn, x):
    """fn over x (T, ...) a block of `ROWS` rows at a time."""
    t = x.shape[0]
    if t <= ROWS:
        return fn(x)
    out = lax.map(fn, x.reshape((t // ROWS, ROWS) + x.shape[1:]))
    return out.reshape((t,) + out.shape[2:])


def relu2(u):
    """The experts' activation, relu(u)². (The control `relu` of
    tests/benchmark/control_nemotron3.py returns relu(u).)"""
    return jnp.square(jax.nn.relu(u))


def gated_norm(y, z, weight, m):
    """GroupRMSNorm(y ⊙ silu(z)) · w_norm over `n_groups` groups of lanes.
    (The control `norm_whole` norms over all H·P lanes at once.)"""
    g = y * jax.nn.silu(z)
    parts = g.reshape(g.shape[:-1] + (m['n_groups'], -1))
    return _norm(parts, jnp.ones((), F32), m['layer_norm_epsilon']).reshape(
        g.shape) * weight.astype(F32)


def _ssm_rows(p, name, m, a):
    """(z, xBC before the filter, dt) of a Mamba layer's normed rows a."""
    d_inner = m['mamba_num_heads'] * m['mamba_head_dim']
    conv_dim = p[name + '.conv_w'].shape[1]
    proj = _by_rows(lambda r: _mm(r, p[name + '.in_proj.weight']), a)
    return (proj[:, :d_inner], proj[:, d_inner:d_inner + conv_dim],
            proj[:, d_inner + conv_dim:])


def _recurrence(p, name, m, xbc, dt, live):
    """The sequential scan: (y (T, H, P), S (H, P, N) after the last row
    where ``live`` (T,) holds)."""
    heads, d = m['mamba_num_heads'], m['mamba_head_dim']
    g, n = m['n_groups'], m['ssm_state_size']
    t = xbc.shape[0]
    x = xbc[:, :heads * d].reshape(t, heads, d)
    b = xbc[:, heads * d:heads * d + g * n].reshape(t, g, n)
    c = xbc[:, heads * d + g * n:].reshape(t, g, n)
    rep = heads // g
    delta = jax.nn.softplus(dt + p[name + '.dt_bias'].astype(F32))
    a = -jnp.exp(p[name + '.A_log'].astype(F32))

    def row(s, inputs):
        x_t, b_t, c_t, d_t, live_t = inputs
        b_h, c_h = jnp.repeat(b_t, rep, 0), jnp.repeat(c_t, rep, 0)  # (H, N)
        s_new = jnp.exp(d_t * a)[:, None, None] * s \
            + (d_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        y = (s_new * c_h[:, None, :]).sum(-1)
        return jnp.where(live_t, s_new, s), y

    s, y = lax.scan(row, jnp.zeros((heads, d, n), F32),
                    (x, b, c, delta, live))
    return y + p[name + '.D'].astype(F32)[:, None] * x, s


def mamba_block(p, name, m, a):
    """The Mamba-2 mixer of the normed rows a (T, h), from W_in to W_out,
    one row after another."""
    z, raw, dt = _ssm_rows(p, name, m, a)
    taps = p[name + '.conv_w'].astype(F32)
    kernel = taps.shape[0]
    up = jnp.pad(raw, ((kernel - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(taps[j] * up[j:j + raw.shape[0]]
                          for j in range(kernel))
                      + p[name + '.conv_b'].astype(F32))
    y, _ = _recurrence(p, name, m, xbc, dt, jnp.ones(raw.shape[0], bool))
    out = gated_norm(y.reshape(raw.shape[0], -1), z, p[name + '.norm'], m)
    return _by_rows(lambda r: _mm(r, p[name + '.out_proj.weight']), out)


def attention_block(p, name, m, a):
    t = a.shape[0]
    heads, groups, d = (m['num_attention_heads'], m['num_key_value_heads'],
                        m['head_dim'])
    pos = jnp.arange(t)
    q = _mm(a, p[name + '.q_proj.weight']).reshape(t, heads, d)
    k = _mm(a, p[name + '.k_proj.weight']).reshape(t, groups, d)
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


def expert_weights(s, biased, chosen):
    """The weights of the ``chosen`` experts (T, k) before the normaliser:
    their UNBIASED scores s; ``biased`` = s + b chose them and weighs
    nothing. (The control `biased_weights` returns the biased scores.)"""
    return jnp.take_along_axis(s, chosen, -1)


def experts_block(p, name, m, a, forced, tie_margin):
    """(the latent experts' layer of the normed rows a (T, h), gap (T,)).
    ``forced`` (T, k): the experts a system chose for each row, -1 where it
    reported none. A row's forced choice is followed where its gap (the
    largest s + b left out less the smallest chosen) is at most
    ``tie_margin``; elsewhere, and where none was reported, the reference's
    own top-k stands. The gap returned is the forced choice's, or minus the
    own choice's margin."""
    k, width = m['num_experts_per_tok'], m['router_width']
    first, count = m['experts_held']
    s = jax.nn.sigmoid(_mm(a, p[name + '.router.weight']))
    biased = s + p[name + '.router_bias'].astype(F32)
    _, own = lax.top_k(biased, k)
    given = forced[:, 0] >= 0
    asked = jnp.where(given[:, None], forced, own)
    inside = (asked[:, :, None] == jnp.arange(width)).any(1)        # (T, E)
    gap = jnp.where(inside, -jnp.inf, biased).max(-1) \
        - jnp.where(inside, biased, jnp.inf).min(-1)
    chosen = jnp.where((gap <= tie_margin)[:, None], asked, own)
    w = expert_weights(s, biased, chosen)
    if m['norm_topk_prob']:
        w = w / (w.sum(-1, keepdims=True) + NORM_EPSILON)
    w = w * m['routed_scaling_factor']
    dense = (w[:, :, None]
             * (chosen[:, :, None] - first == jnp.arange(count))).sum(1)
    latent = _mm(a, p[name + '.latent_down.weight'])

    def one(acc, e):
        y = _by_rows(lambda r: _mm(relu2(_mm(r, p[name + '.experts_up'][e])),
                                   p[name + '.experts_down'][e]), latent)
        return acc + dense[:, e][:, None] * y, None

    routed, _ = lax.scan(one, jnp.zeros_like(latent), jnp.arange(count))
    shared = _by_rows(
        lambda r: _mm(relu2(_mm(r, p[name + '.shared.up.weight'])),
                      p[name + '.shared.down.weight']), a)
    return _mm(routed, p[name + '.latent_up.weight']) + shared, gap


def kind(m, i):
    return {'M': 'mamba', 'E': 'experts', '*': 'attention'}[
        m['hybrid_override_pattern'][i]]


def decoder_layer(p, m, i, x, forced=None, tie_margin=0.0):
    """(layer i's output for the rows x (T, h), the experts' gap (T,) or
    None for a layer of another kind)."""
    name = f'layers.{i}'
    a = _norm(x, p[name + '.norm.weight'], m['layer_norm_epsilon'])
    mixer = name + '.mixer'
    if kind(m, i) == 'mamba':
        return x + mamba_block(p, mixer, m, a), None
    if kind(m, i) == 'attention':
        return x + attention_block(p, mixer, m, a), None
    if forced is None:
        forced = jnp.full((x.shape[0], m['num_experts_per_tok']), -1)
    f, gap = experts_block(p, mixer, m, a, forced, tie_margin)
    return x + f, gap


def hidden(p, m, ids, forced, tie_margin):
    """(final hidden states (T, h) before the last norm, gaps (expert
    layers, T)) of the sequence ``ids`` (T,); ``forced`` (expert layers, T,
    k) as `experts_block` takes it."""
    x = p['embed.weight'][ids].astype(F32)
    gaps = []
    for i in range(m['num_hidden_layers']):
        x, gap = decoder_layer(
            p, m, i, x, forced[len(gaps)] if kind(m, i) == 'experts'
            else None, tie_margin)
        if gap is not None:
            gaps.append(gap)
    return x, jnp.stack(gaps)


def logits(p, m, x):
    """Rows x (n, h) through the last norm and the head, a slice of the
    vocabulary at a time."""
    x = _norm(x, p['norm_f.weight'], m['layer_norm_epsilon'])
    table = p['head.weight']                               # (h, V)
    pieces = 8 if table.shape[1] % 8 == 0 else 1
    width = table.shape[1] // pieces
    out = lax.map(lambda i: _mm(x, lax.dynamic_slice_in_dim(
        table, i * width, width, 1)), jnp.arange(pieces))
    return out.transpose(1, 0, 2).reshape(x.shape[0], -1)


def stated(x, dtype):
    """x (float32) rounded to ``dtype`` ('bfloat16' or 'float32') and held
    in float32: bf16's round to nearest even done on the bit pattern (finite
    values), which no compiler may drop."""
    if jnp.dtype(dtype) == F32:
        return x
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise ValueError(f'no rounding to {dtype} here')
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(bits, F32)


def first_ssm_state(p, m, ids, prompt_len):
    """What the FIRST Mamba layer carries after the prompt
    ``ids[:prompt_len]`` (ids (T,) padded past it), flat: its S (H, P, N)
    after row prompt_len - 1, then its last K - 1 rows of xBC before the
    filter (K - 1, conv_dim), oldest first, zero where the prompt has not
    that many. The layer's input a is rounded to the stated activation
    dtype (the module docstring); the layers before it (none where the
    model opens with one) run with the reference's own choices."""
    first = next(i for i in range(m['num_hidden_layers'])
                 if kind(m, i) == 'mamba')
    x = p['embed.weight'][ids].astype(F32)
    for i in range(first):
        x, _ = decoder_layer(p, m, i, x)
    name = f'layers.{first}'
    a = _norm(x, p[name + '.norm.weight'], m['layer_norm_epsilon'])
    a = stated(a, m['dtype'])
    mixer = name + '.mixer'
    _, raw, dt = _ssm_rows(p, mixer, m, a)
    taps = p[mixer + '.conv_w'].astype(F32)
    keep = taps.shape[0] - 1
    up = jnp.pad(raw, ((keep, 0), (0, 0)))            # row i holds raw[i-keep]
    xbc = jax.nn.silu(sum(taps[j] * up[j:j + raw.shape[0]]
                          for j in range(keep + 1))
                      + p[mixer + '.conv_b'].astype(F32))
    _, s = _recurrence(p, mixer, m, xbc, dt,
                       jnp.arange(raw.shape[0]) < prompt_len)
    window = lax.dynamic_slice_in_dim(up, prompt_len, keep, 0)
    return jnp.concatenate([s.reshape(-1), window.reshape(-1)])


def model_of(config):
    """The sizes this file reads: the published keys sit at the top level of
    the configuration file, under their own names; the program's own
    (`model`) beside them; the router as wide as `router_width` (the
    published count, where the layer is one chip's share) and the experts
    held (`experts_held`; every one where none is named)."""
    model = {**config, **config['model']}
    count = model['n_routed_experts']
    model['router_width'] = model.get('router_width') or count
    model['experts_held'] = tuple(model.get('experts_held') or (0, count))
    return model


def expert_layers(model):
    return model['hybrid_override_pattern'].count('E')


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
    the filter, the scan and the mask are causal, and the feed-forwards act
    on each token alone). `forced`: {position: (expert layers, k) expert
    ids a system chose there}, followed where the reference's own scores
    call the choice a near-tie (`experts_block`)."""
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
        asked = np.full(shape, -1, np.int32)
        for position, chosen in (forced or {}).items():
            asked[:, position] = chosen
        return fn(params, _padded(ids, pad),
                  np.asarray(positions, np.int32), asked,
                  np.float32(tie_margin))
    return rows


def make_first_conv_state(config, pad):
    """state(params, prompt) -> `first_ssm_state` after ``prompt``, one
    compiled program for every length up to ``pad`` (the name the hybrid
    runner asks for: runners/serve_decode_hybrid.py)."""
    model = model_of(config)
    fn = jax.jit(lambda p, ids, n: first_ssm_state(p, model, ids, n))

    def state(params, prompt):
        return fn(params, _padded(prompt, pad), np.int32(len(prompt)))
    return state
