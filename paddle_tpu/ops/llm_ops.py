"""The modern decoder block as ops (ROADMAP R2): RMSNorm, rotary positions,
the gated feed-forward, a sigmoid router with bias-corrected top-k, a grouped
expert feed-forward that drops no token, and latent (MLA) attention in its
two forms: expanded over a prompt, absorbed over the paged latent cache.

Precision rule, the same in every op: matmuls take their operands as they
are stored (bf16 weights and activations on the served path) and accumulate
in float32; norms, the router's scores, softmax and silu are computed in
float32; an op returns its input's dtype. With float32 operands (the CPU
tests) everything is float32.

The callers name these ops for the device trace (`jax.named_scope` around
the dispatch: `moe/route`, `moe/experts`, `moe/shared` in
models/latent_moe_lm.py, `mla/prefill_attention`, `mla/decode_read` in
serving/decode/kv_cache.py), which benchmark/lib/scoped_ops.py sums device
time by. A scope INSIDE an op's function does not survive: every dispatch is
a jit of its own inside the engine's program, and when XLA inlines it an
instruction keeps the call site's name and only the last part of its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op

_F32 = jnp.float32


def _dot(x, w):
    """x @ w over x's last axis, float32 accumulation, x's dtype out."""
    return jnp.matmul(x, w, preferred_element_type=_F32).astype(x.dtype)


@register_op('rms_norm')
def rms_norm(x, scale, *, epsilon=1e-6):
    """x · rsqrt(mean(x², last axis) + ε) · scale, in float32."""
    x = jnp.asarray(x)
    xf = x.astype(_F32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + epsilon)
    return (xf * inv * jnp.asarray(scale).astype(_F32)).astype(x.dtype)


@register_op('rope')
def rope(x, pos, *, theta=10000.0, nope_dim=0):
    """Rotary positions on interleaved pairs: past the first ``nope_dim``
    lanes of x's last axis (which pass through: the MLA layout
    [nope | rope]), lanes (2i, 2i+1) turn by pos · theta^(-2i / d), d the
    number of rotated lanes.

    x (B, S, D) or (B, S, H, D); pos (B, S) integer positions. The angles
    and the rotation are float32."""
    x = jnp.asarray(x)
    d = x.shape[-1] - int(nope_dim)
    inv_freq = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    ang = jnp.asarray(pos).astype(_F32)[..., None] * inv_freq   # (B, S, d/2)
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    rot = x[..., nope_dim:].astype(_F32)
    even, odd = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    -1).reshape(rot.shape).astype(x.dtype)
    if not nope_dim:
        return out
    return jnp.concatenate([x[..., :nope_dim], out], -1)


@register_op('lm_head')
def lm_head(x, w):
    """Logits of an untied head, x (..., h) · w (h, V), in float32 whatever
    the operands are stored in: the rows go to the host's sampler."""
    return jnp.matmul(jnp.asarray(x), jnp.asarray(w),
                      preferred_element_type=_F32)


@register_op('swiglu_ffn')
def swiglu_ffn(x, w_gate, w_up, w_down):
    """w_down(silu(x · w_gate) ⊙ (x · w_up)); weights (h, f), (h, f),
    (f, h)."""
    x = jnp.asarray(x)
    g = jnp.matmul(x, w_gate, preferred_element_type=_F32)
    u = jnp.matmul(x, w_up, preferred_element_type=_F32)
    return _dot((jax.nn.silu(g) * u).astype(x.dtype), w_down)


@register_op('moe_router', outputs=('Ids', 'Weights'))
def moe_router(x, w_gate, bias, *, top_k, routed_scaling_factor=1.0,
               norm_topk_prob=True):
    """Sigmoid router with a selection bias (`noaux_tc`, one group): scores
    s = sigmoid(x · w_gate) over E experts; the ``top_k`` largest s + bias
    are chosen; their weights are the UNBIASED scores, normalised over the
    chosen (``norm_topk_prob``) and scaled. All float32.

    x (T, h), w_gate (h, E), bias (E,) -> ids (T, k) int32, weights (T, k)
    float32."""
    logits = jnp.matmul(jnp.asarray(x).astype(_F32),
                        jnp.asarray(w_gate).astype(_F32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = lax.top_k(scores + jnp.asarray(bias).astype(_F32),
                       int(top_k))
    weights = jnp.take_along_axis(scores, ids, -1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), weights * routed_scaling_factor


@register_op('moe_experts', outputs=('Out', 'Counts'))
def moe_experts(x, ids, weights, w_gate, w_up, w_down):
    """Σ_k weights[t, k] · E_ids[t, k](x[t]), each expert a gated
    feed-forward, as ONE grouped matmul per projection over the T·k
    assignments sorted by expert (`lax.ragged_dot`: the TPU's ragged-dot
    kernel reads an expert's weights once, for the rows routed to it). No
    capacity: every assignment is computed.

    x (T, h); ids (T, k) int32; weights (T, k) float32; w_gate, w_up
    (E, h, f); w_down (E, f, h). Returns the (T, h) sum and the (E,) int32
    rows each expert was given (they add up to T·k)."""
    x = jnp.asarray(x)
    t, k = ids.shape
    n_experts = w_gate.shape[0]
    flat = ids.reshape(-1)
    counts = (flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)
              ).sum(0, dtype=jnp.int32)
    order = jnp.argsort(flat, stable=True)       # assignments by expert
    rows = x[order // k]                         # (T·k, h)
    g = lax.ragged_dot(rows, w_gate, counts, preferred_element_type=_F32)
    u = lax.ragged_dot(rows, w_up, counts, preferred_element_type=_F32)
    y = lax.ragged_dot((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                       counts, preferred_element_type=_F32)
    y = y[jnp.argsort(order)].reshape(t, k, -1)  # back to token order
    out = (y * weights[..., None]).sum(1)
    return out.astype(x.dtype), counts


def _split_kvb(w_kvb, num_heads, nope, v_dim):
    """kv_b_proj (rank, H·(nope + v)) as W_UK (rank, H, nope) and W_UV
    (rank, H, v)."""
    w = jnp.asarray(w_kvb).reshape(w_kvb.shape[0], num_heads, nope + v_dim)
    return w[..., :nope], w[..., nope:]


# queries of a prompt are attended in chunks of this many rows, each against
# the keys up to its own end: a 4,096-token rung holds at most (heads, 512,
# 4096) scores at a time, not (heads, 4096, 4096), and computes 36 of the 64
# chunk pairs
_PREFILL_QUERY_CHUNK = 512


@register_op('mla_prefill_attention')
def mla_prefill_attention(q, latent, w_kvb, *, qk_nope_dim, v_dim,
                          sm_scale=1.0):
    """Latent attention in its expanded form, causal over one prompt.

    q (B, L, H, nope + rope), rotary part already turned; latent (B, L,
    rank + rope), the rows as they are cached: [c after its norm | k_rope
    after RoPE]; w_kvb (rank, H·(nope + v)). Per head j: [k_nope_j | v_j] =
    c · W_kvb,j, k_j = [k_nope_j | k_rope], softmax(q_j · k_j · sm_scale)
    over keys at or before the query, · v_j. Returns (B, L, H·v)."""
    q, latent = jnp.asarray(q), jnp.asarray(latent)
    b, length, heads, _ = q.shape
    rank = w_kvb.shape[0]
    kv = _dot(latent[..., :rank], w_kvb).reshape(
        b, length, heads, qk_nope_dim + v_dim)
    k_rope = jnp.broadcast_to(latent[:, :, None, rank:],
                              (b, length, heads, latent.shape[-1] - rank))
    k = jnp.concatenate([kv[..., :qk_nope_dim], k_rope], -1)
    v = kv[..., qk_nope_dim:]
    chunk = min(length, _PREFILL_QUERY_CHUNK)
    if length % chunk:
        chunk = length
    key_pos = jnp.arange(length, dtype=jnp.int32)
    out = []
    for start in range(0, length, chunk):
        # a chunk's queries see no key past the chunk's own end: the keys
        # are cut there, and the mask is only the chunk's own triangle
        stop = start + chunk
        s = jnp.einsum('bqhd,bkhd->bhqk', q[:, start:stop], k[:, :stop],
                       preferred_element_type=_F32) * sm_scale
        seen = key_pos[None, :stop] <= key_pos[start:stop, None]
        s = jnp.where(seen[None, None], s, jnp.finfo(_F32).min)
        p = jax.nn.softmax(s, -1).astype(v.dtype)
        out.append(jnp.einsum('bhqk,bkhd->bqhd', p, v[:, :stop],
                              preferred_element_type=_F32).astype(q.dtype))
    return jnp.concatenate(out, 1).reshape(b, length, heads * v_dim)


@register_op('mla_decode_attention')
def mla_decode_attention(q, pages, block_tables, context_lens, w_kvb, *,
                         qk_nope_dim, v_dim, sm_scale=1.0):
    """Latent attention in its absorbed form, over the paged latent cache:
    the same function of the weights as `mla_prefill_attention`, with
    W_UK folded into the query and W_UV applied after the sum, so that the
    cache is read as it is stored and never expanded per head.

    q (S, K, H, nope + rope): K fed tokens per slot (1 in the lockstep
    step), rotary part turned; pages (blocks, block, rank + rope);
    block_tables (S, blocks per slot) int32; context_lens (S,): row j of
    slot s sees positions < context_lens[s] + j (the staircase of
    `paged_attention`); w_kvb (rank, H·(nope + v)). Returns (S, K, H·v).

    q̃_j = q_nope_j · W_UK,jᵀ; score_j = (q̃_j · c + q_rope_j · k_rope) ·
    sm_scale; u_j = Σ p · c; o_j = u_j · W_UV,j. Positions past a slot's
    context are masked to exactly zero probability, so the scratch block's
    and a freed block's stale rows never reach a result."""
    q, pages = jnp.asarray(q), jnp.asarray(pages)
    s, kq, heads, _ = q.shape
    rank = w_kvb.shape[0]
    w_uk, w_uv = _split_kvb(w_kvb, heads, qk_nope_dim, v_dim)
    q_abs = jnp.einsum('skhd,rhd->skhr', q[..., :qk_nope_dim], w_uk,
                       preferred_element_type=_F32).astype(q.dtype)
    query = jnp.concatenate([q_abs, q[..., qk_nope_dim:]], -1)
    tables = jnp.asarray(block_tables, jnp.int32)
    rows = pages[tables].reshape(s, -1, pages.shape[-1])   # (S, T, W)
    rows = rows[..., :query.shape[-1]].astype(q.dtype)
    scores = jnp.einsum('skhw,stw->skht', query, rows,
                        preferred_element_type=_F32) * sm_scale
    extent = jnp.asarray(context_lens, jnp.int32)[:, None] \
        + jnp.arange(kq, dtype=jnp.int32)[None, :]         # (S, K)
    seen = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None, :] \
        < extent[..., None]
    scores = jnp.where(seen[:, :, None, :], scores,
                       jnp.finfo(_F32).min)
    p = jax.nn.softmax(scores, -1).astype(q.dtype)
    u = jnp.einsum('skht,str->skhr', p, rows[..., :rank],
                   preferred_element_type=_F32).astype(q.dtype)
    out = jnp.einsum('skhr,rhd->skhd', u, w_uv,
                     preferred_element_type=_F32).astype(q.dtype)
    return out.reshape(s, kq, heads * v_dim)
