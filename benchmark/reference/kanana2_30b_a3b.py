"""Plain reference for kanana2_30b_a3b: the decoder of
kakaocorp/kanana-2-30b-a3b-instruct-2601 (`model_type: deepseek_v3`),
whole-sequence forward in float32 jax.numpy at precision "highest". No
cache, no kernels, no batching, no absorbed form, no framework code.

    x += Attn(norm1(x)); x += FFN(norm2(x)); logits = final_norm(x) · W_head

All norms RMSNorm (eps from the config), no biases. Attention is MLA with no
query low-rank: q = h·W_q as heads of [q_nope | q_rope]; [c_kv | k_rope] =
h·W_kva; c = RMSNorm(c_kv); RoPE (interleaved pairs (2i, 2i+1)) on q_rope of
every head and on the one k_rope all heads share; [k_nope_j | v_j] = c·W_kvb
for head j; score_j = (q_nope_j·k_nope_j + q_rope_j·k_rope) / sqrt(nope +
rope), causal softmax, o_j = Σ p·v_j, Attn = concat_j(o_j)·W_o. The first
`first_k_dense_replace` layers have a dense gated feed-forward, the others
`n_routed_experts` gated experts behind a sigmoid router (the
`num_experts_per_tok` largest s + b are chosen, weighted by the unbiased s
normalised over the chosen and scaled by `routed_scaling_factor`; one group)
plus one shared gated feed-forward of `n_shared_experts` expert widths.
Every expert is applied to every token, densely, one expert at a time, and
weighted by zero where it was not chosen.

Departures from the published model, all of the configuration and not of
this file: random weights from the seed; `e_score_correction_bias` (zero in
a fresh checkpoint) drawn N(0, 0.01) so that choosing by s + b and weighting
by s are told apart; `num_hidden_layers` as the configuration cuts it.

Weights arrive under the program's parameter names, as the program stores
them (bf16 on the chip), and are cast up to float32 where they are used, an
expert or a slice at a time, so that the check fits beside the resident
model. Nothing else is taken from the program but the experts it reports
it chose at the checked positions, and those are judged, not trusted (next).

The trap of the comparison: with random weights the choice of the k-th
against the (k+1)-th expert flips on rounding, as an argmax does, and one
flipped expert moves a logits row by far more than any honest tolerance. On
the chip a bf16 pass leaves about 2e-3 on the difference of two scores whose
mean gap at the cut is 1e-2, so a system decides 6-9% of its choices the
other way, one in seven of them not as the plain swap of the k-th and the
(k+1)-th (PERF.md section 6, PR 26): too many ways to list. So `rows` takes
`forced`: for a position, the experts a system reports it chose in every
expert layer. The reference follows such a choice only where ITS OWN scores
call it a near-tie: every chosen expert within `tie_margin` of every expert
left out (`gap` = the largest s + b left out less the smallest chosen; at or
below zero the choice is a top-k of the reference's scores). Where the gap
is wider the reference's own choice stands, and the row then differs by far
more than the tolerance: a router of lower precision, a wrong bias or a
wrong k is not followed. The weights of a followed choice are the
reference's own (its unbiased s over the chosen), and everything downstream
is computed from the followed choice, so the next layer's gap is judged on
the scores the reference gets there. `rows` returns the gaps beside the rows.
"""
import math

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
    """x (T, ..., d): pairs (2i, 2i+1) turned by pos · theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv_freq
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     -1).reshape(x.shape)


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def _attention(p, name, m, h):
    t = h.shape[0]
    heads, nope, rope, vd, rank = (
        m['num_attention_heads'], m['qk_nope_head_dim'],
        m['qk_rope_head_dim'], m['v_head_dim'], m['kv_lora_rank'])
    pos = jnp.arange(t)
    q = _mm(h, p[name + '.q_proj.weight']).reshape(t, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos,
                                          m['rope_theta'])
    kva = _mm(h, p[name + '.kv_a_proj.weight'])
    c = _norm(kva[:, :rank], p[name + '.kv_a_norm.weight'],
              m['rms_norm_eps'])
    k_rope = _rope(kva[:, rank:], pos, m['rope_theta'])
    kv = _mm(c, p[name + '.kv_b_proj.weight']).reshape(t, heads, nope + vd)
    causal = pos[None, :] <= pos[:, None]

    def head(j):                     # one head at a time: (T, T) scores
        scores = (jnp.matmul(q_nope[:, j], kv[:, j, :nope].T,
                             precision=HIGHEST)
                  + jnp.matmul(q_rope[:, j], k_rope.T, precision=HIGHEST)
                  ) / math.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.matmul(probs, kv[:, j, nope:], precision=HIGHEST)

    out = lax.map(head, jnp.arange(heads))               # (H, T, v)
    return _mm(out.transpose(1, 0, 2).reshape(t, heads * vd),
               p[name + '.o_proj.weight'])


def _experts(p, name, m, h, forced, tie_margin):
    """(routed + shared output, gap (T,)). ``forced`` (T, k): the experts a
    system chose for each row, -1 where it reported none. A row's forced
    choice is followed where its gap (the largest s + b left out less the
    smallest chosen) is at most ``tie_margin``; elsewhere, and where none
    was reported, the reference's own top-k stands. The gap returned is the
    forced choice's, or minus the own choice's margin."""
    k, n = m['num_experts_per_tok'], m['n_routed_experts']
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
    if m['norm_topk_prob']:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * m['routed_scaling_factor']
    dense = (w[:, :, None] * (chosen[:, :, None] == jnp.arange(n))
             ).sum(1)                                      # (T, E)

    def one(acc, e):
        y = _swiglu(h, p[name + '.experts_gate'][e],
                    p[name + '.experts_up'][e], p[name + '.experts_down'][e])
        return acc + dense[:, e][:, None] * y, None

    routed, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(n))
    shared = _swiglu(h, p[name + '.shared.gate.weight'],
                     p[name + '.shared.up.weight'],
                     p[name + '.shared.down.weight'])
    return routed + shared, gap


def hidden(p, m, ids, forced, tie_margin):
    """(final hidden states (T, h) before the last norm, gaps (expert
    layers, T)) of the sequence ``ids`` (T,); ``forced`` (expert layers, T,
    k) as `_experts` takes it."""
    x = p['embed.weight'][ids].astype(F32)
    gaps = []
    for i in range(m['num_hidden_layers']):
        name = f'layers.{i}'
        x = x + _attention(p, name + '.attn', m, _norm(
            x, p[name + '.norm1.weight'], m['rms_norm_eps']))
        h = _norm(x, p[name + '.norm2.weight'], m['rms_norm_eps'])
        if i < m['first_k_dense_replace']:
            x = x + _swiglu(h, p[name + '.ffn.gate.weight'],
                            p[name + '.ffn.up.weight'],
                            p[name + '.ffn.down.weight'])
        else:
            y, gap = _experts(p, name + '.ffn', m, h, forced[len(gaps)],
                              tie_margin)
            x = x + y
            gaps.append(gap)
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
    the configuration file, under their own names."""
    return {**config, **config['model']}


def expert_layers(model):
    return model['num_hidden_layers'] - model['first_k_dense_replace']


def make_rows(config, pad):
    """rows(params, ids, positions, forced=None, tie_margin=0.0) -> (logits
    rows at `positions` (n, V), their gaps (n, expert layers)), of the
    sequence `ids` padded to `pad` tokens so that every length shares one
    compiled program (padding after a position cannot reach it through a
    causal mask, and experts act on each token alone). `forced`: {position:
    (expert layers, k) expert ids a system chose there}, followed where the
    reference's own scores call the choice a near-tie (`_experts`)."""
    model = model_of(config)
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
