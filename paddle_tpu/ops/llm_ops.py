"""The modern decoder block as ops (ROADMAP R2): RMSNorm, rotary positions,
the gated feed-forward, a router (sigmoid scores with bias-corrected top-k,
or softmax scores) and a grouped expert feed-forward that drops no token
(on a TPU the pallas grouped matmul of ops/pallas_moe.py, the one op here
with a kernel and a predicate beside its call, `experts_kernel_applies`),
latent (MLA) attention in its two forms (expanded over a prompt, absorbed
over the paged latent cache: the lockstep step's read walks the batch's
live groups, ops/nn_ops.py), power retention, the gated short convolution
of a hybrid decoder (a state of L - 1 rows a request), and a
block-diffusion forward's pick (a token and the confidence in it per row).

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
import numpy as np
from jax import lax

from ..core.places import on_tpu
from .nn_ops import _live_group_attention, live_group_list
from .pallas_moe import expert_ffn
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
def lm_head(x, w, *, tied=False):
    """Logits of a head, x (..., h) · w (h, V), in float32 whatever the
    operands are stored in: the rows go to the host's sampler. ``tied``: w
    is the embedding's own array (V, h), contracted over its h as it lies
    (no transposed copy of it exists)."""
    x, w = jnp.asarray(x), jnp.asarray(w)
    if tied:
        return lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())),
                               preferred_element_type=_F32)
    return jnp.matmul(x, w, preferred_element_type=_F32)


@register_op('diffusion_pick', outputs=('Ids', 'Confidence'))
def diffusion_pick(rows, *, mask_token_id=-1):
    """What a denoising forward of a block-diffusion model hands the host of
    its logits ``rows`` (..., V): per row the most likely token and the
    model's confidence in it, the softmax probability of that token, with
    column ``mask_token_id`` (the `MASK` token: an input, never an answer)
    at −∞ in both. Float32 whatever the rows are; ids int32, the first of
    equal maxima."""
    rows = jnp.asarray(rows).astype(_F32)
    if mask_token_id >= 0:
        rows = jnp.where(jnp.arange(rows.shape[-1]) == int(mask_token_id),
                         -jnp.inf, rows)
    top = rows.max(-1, keepdims=True)
    confidence = 1.0 / jnp.exp(rows - top).sum(-1)
    return jnp.argmax(rows, -1).astype(jnp.int32), confidence


@register_op('swiglu_ffn')
def swiglu_ffn(x, w_gate, w_up, w_down):
    """w_down(silu(x · w_gate) ⊙ (x · w_up)); weights (h, f), (h, f),
    (f, h)."""
    x = jnp.asarray(x)
    g = jnp.matmul(x, w_gate, preferred_element_type=_F32)
    u = jnp.matmul(x, w_up, preferred_element_type=_F32)
    return _dot((jax.nn.silu(g) * u).astype(x.dtype), w_down)


@register_op('sigmoid_gate')
def sigmoid_gate(x, gate):
    """x ⊙ sigmoid(gate), lane by lane: the gate on an attention layer's
    output (before its output projection), the sigmoid and the product in
    float32, x's dtype out."""
    x = jnp.asarray(x)
    return (x.astype(_F32) * jax.nn.sigmoid(jnp.asarray(gate).astype(_F32))
            ).astype(x.dtype)


@register_op('moe_router', outputs=('Ids', 'Weights'))
def moe_router(x, w_gate, bias=None, *, top_k, routed_scaling_factor=1.0,
               norm_topk_prob=True, scoring_func='sigmoid',
               norm_epsilon=1e-20):
    """A router's choice of ``top_k`` of E experts and their weights, all
    float32. ``scoring_func``:

    - ``'sigmoid'`` with a selection ``bias`` (`noaux_tc`, one group):
      scores s = sigmoid(x · w_gate); the ``top_k`` largest s + bias are
      chosen; their weights are the UNBIASED scores.
    - ``'softmax'`` (Qwen3-MoE, `sdar_moe`): scores p = softmax(x · w_gate)
      over the E experts; the ``top_k`` largest are chosen (``bias`` None:
      the family has none) and weigh by their p.

    The weights are normalised over the chosen (``norm_topk_prob``: divided
    by their sum + ``norm_epsilon``, 1e-20 in `deepseek_v3`, `sdar_moe` and
    `afmoe`, 1e-6 in `lfm2_moe`) and scaled. Of equal scores the lower
    expert is chosen first.

    x (T, h), w_gate (h, E), bias (E,) or None -> ids (T, k) int32, weights
    (T, k) float32."""
    logits = jnp.matmul(jnp.asarray(x).astype(_F32),
                        jnp.asarray(w_gate).astype(_F32),
                        precision=lax.Precision.HIGHEST)
    if scoring_func == 'softmax':
        scores = jax.nn.softmax(logits, -1)
    elif scoring_func == 'sigmoid':
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f'moe_router: scoring_func={scoring_func!r} is '
                         "neither 'sigmoid' nor 'softmax'")
    biased = scores if bias is None \
        else scores + jnp.asarray(bias).astype(_F32)
    _, ids = lax.top_k(biased, int(top_k))
    weights = jnp.take_along_axis(scores, ids, -1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True)
                             + _F32(norm_epsilon))
    return ids.astype(jnp.int32), weights * routed_scaling_factor


def experts_kernel_applies(x, w_gate):
    """True when `moe_experts` runs the pallas grouped matmul
    (ops/pallas_moe.py) for ``x`` and the experts' weights (arrays or
    ShapeDtypeStructs): a TPU backend, and bf16 or float32 operands of one
    dtype. The repo's one convention (ops/nn_ops.py, "explicit kernel
    dispatch"): where it holds the kernel runs, at every row count (the
    sorted rows are padded to whole tiles), and a Mosaic refusal is an
    error; elsewhere the `lax.ragged_dot` formulation runs because the code
    says so (the CPU tests, the gradients)."""
    return (on_tpu() and x.dtype == w_gate.dtype
            and x.dtype in (jnp.bfloat16, jnp.float32))


@register_op('moe_experts', outputs=('Out', 'Counts'))
def moe_experts(x, ids, weights, w_gate, w_up, w_down, *, experts_held=None):
    """Σ_k weights[t, k] · E_ids[t, k](x[t]), each expert a gated
    feed-forward, as grouped matmuls over the T·k assignments sorted by
    expert. No capacity: every assignment is computed.

    On a TPU (`experts_kernel_applies`) the grouped matmuls are the pallas
    kernel of ops/pallas_moe.py, gate and up in one pass with `silu(g) · u`
    in its epilogue, then down: an expert's weights are read once and whole
    for the rows routed to it. Elsewhere one `lax.ragged_dot` a projection.
    The same precision in both: operands as stored, float32 accumulation,
    `silu(g) · u` in float32 and cast to x's dtype, the weighted sum in
    float32.

    ``experts_held`` = (first, count): the layer holds experts [first,
    first + count) of the router's (one chip's share under expert
    parallelism) and ``w_*`` are theirs alone, E = count. ``ids`` still
    range over all the router's experts: an assignment to an expert held
    elsewhere sorts behind the held ones as nobody's row, is never
    multiplied, and adds nothing here. The result is the held experts' part
    of the sum, and ``Counts`` their rows.

    x (T, h); ids (T, k) int32; weights (T, k) float32; w_gate, w_up
    (E, h, f); w_down (E, f, h). Returns the (T, h) sum and the (E,) int32
    rows each expert was given (they add up to T·k, or to the held
    assignments)."""
    x = jnp.asarray(x)
    t, k = ids.shape
    n_experts = w_gate.shape[0]
    flat = ids.reshape(-1)
    held = None
    if experts_held is not None:
        first, count = (int(n) for n in experts_held)
        if count != n_experts:
            raise ValueError(f'moe_experts: experts_held={experts_held!r} '
                             f'but the weights are of {n_experts} experts')
        flat = flat - first
        held = (flat >= 0) & (flat < count)
        flat = jnp.where(held, flat, count)      # nobody's: behind the held
    counts = (flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)
              ).sum(0, dtype=jnp.int32)
    order = jnp.argsort(flat, stable=True)       # assignments by expert
    if experts_kernel_applies(x, w_gate):
        y = expert_ffn(x, order // k, counts, w_gate, w_up, w_down)
    else:
        rows = x[order // k]                     # (T·k, h)
        g = lax.ragged_dot(rows, w_gate, counts, preferred_element_type=_F32)
        u = lax.ragged_dot(rows, w_up, counts, preferred_element_type=_F32)
        y = lax.ragged_dot((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                           counts, preferred_element_type=_F32)
    y = y[jnp.argsort(order)]                    # back to token order
    if held is not None:
        # a row of nobody is whatever the kernel left there: dropped
        y = jnp.where(held[:, None], y, 0.0)
    y = y.reshape(t, k, -1)
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
def mla_decode_attention(q, pages, block_tables, context_lens, w_kvb,
                         live=None, *, qk_nope_dim, v_dim, sm_scale=1.0):
    """Latent attention in its absorbed form, over the paged latent cache:
    the same function of the weights as `mla_prefill_attention`, with
    W_UK folded into the query and W_UV applied after the sum, so that the
    cache is read as it is stored and never expanded per head.

    q (S, K, H, nope + rope): K fed tokens per slot (1 in the lockstep
    step), rotary part turned; pages (blocks, block, lanes >= rank + rope),
    a row [c | k_rope] and zeros past it; block_tables (S, blocks per slot)
    int32; context_lens (S,): row j of slot s sees positions <
    context_lens[s] + j (the staircase of `paged_attention`); w_kvb (rank,
    H·(nope + v)); ``live`` optional, the :func:`~.nn_ops.live_group_list`
    of these tables and lengths, for a caller that reads many layers
    through them (made here otherwise; the K = 1 read alone takes it).
    Returns (S, K, H·v).

    q̃_j = q_nope_j · W_UK,jᵀ; score_j = (q̃_j · c + q_rope_j · k_rope) ·
    sm_scale; u_j = Σ p · c; o_j = u_j · W_UV,j. Positions past a slot's
    context are masked to exactly zero probability, so the scratch block's
    and a freed block's stale rows never reach a result.

    K = 1 (the lockstep step) is the GROUPED read of ops/nn_ops.py with one
    key/value head: a slot's H absorbed queries [q̃_j | q_rope_j] are the
    rows of one group, the pool's rows are the keys as stored, and their
    first ``rank`` lanes the values. It walks the batch's live groups with
    a running softmax (:func:`~.nn_ops._live_group_attention`: on a TPU the
    pallas kernel of ops/pallas_group_read.py, elsewhere the XLA walk in
    chunks), so its work follows the contexts and no per-slot copy of the
    padded tables exists. The query is padded with zeros to the rows'
    lanes, so that the rows are used as they are stored (a zero lane of the
    query makes a row's pad lanes moot), and the sum is cut to ``rank``
    lanes after the walk. K > 1 (the staircase of a speculative verify or a chunked
    suffix) keeps the dense form, every slot's padded table gathered, as
    `paged_attention`'s (S, K) staircase does: no served cell runs it, and
    the walker's "every row of a slot at one extent" stays true."""
    q, pages = jnp.asarray(q), jnp.asarray(pages)
    s, kq, heads, _ = q.shape
    rank = w_kvb.shape[0]
    w_uk, w_uv = _split_kvb(w_kvb, heads, qk_nope_dim, v_dim)
    q_abs = jnp.einsum('skhd,rhd->skhr', q[..., :qk_nope_dim], w_uk,
                       preferred_element_type=_F32).astype(q.dtype)
    query = jnp.concatenate([q_abs, q[..., qk_nope_dim:]], -1)
    tables = jnp.asarray(block_tables, jnp.int32)
    context_lens = jnp.asarray(context_lens, jnp.int32)
    if kq == 1:
        if live is None:
            live = live_group_list(tables, context_lens, pages.shape[1])
        query = jnp.pad(query, ((0, 0), (0, 0), (0, 0),
                                (0, pages.shape[-1] - query.shape[-1])))
        u = _live_group_attention(
            query.transpose(0, 2, 1, 3), pages, pages, context_lens, live,
            1, sm_scale).transpose(0, 2, 1, 3)[..., :rank]
    else:
        rows = pages[tables].reshape(s, -1, pages.shape[-1])   # (S, T, W)
        rows = rows[..., :query.shape[-1]].astype(q.dtype)
        scores = jnp.einsum('skhw,stw->skht', query, rows,
                            preferred_element_type=_F32) * sm_scale
        extent = context_lens[:, None] \
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


# ---------------------------------------------------------------------------
# power retention (degree 2): linear attention over the symmetric degree-2
# coordinates φ(x), φ(q)·φ(k) = (q·k)², with one sigmoid gate per key/value
# head. Per head the recurrent state is S (D × d) and its normaliser z (D),
# D = d(d+1)/2, both float32:
#
#     S_t = γ_t S_{t−1} + φ(k_t) v_tᵀ      z_t = γ_t z_{t−1} + φ(k_t)
#     y_t = φ(q_t)ᵀ S_t / φ(q_t)ᵀ z_t
#
# A head's state is ONE (P, d) block, d values of v on the lanes: rows 0..D−1
# are S, the next ⌈D/d⌉ hold z row-major (zero past D), and P rounds their
# count up to the 8 sublanes, so that row-major is the TPU's compact layout
# and the normaliser pads no axis from d + 1 to 2d (retention_state_rows).
#
# Precision: the state, the gates' cumulative logs and every sum over the
# state are float32; q, k and v arrive as stored (bf16 on the served path)
# and φ is formed in float32 from them (a product of two bf16 values is
# exact there). Contractions with the float32 state run at _STATE_PRECISION:
# one bf16 pass would round the state's 24 bits to 8 at every read.
# ---------------------------------------------------------------------------

_STATE_PRECISION = lax.Precision.HIGHEST


def retention_state_rows(head_dim):
    """(D, rows of z, P) of one head's state block at ``head_dim`` d."""
    d = int(head_dim)
    if d % 2:
        raise ValueError(f'power retention needs an even head_dim, got {d}')
    big = d * (d + 1) // 2
    z_rows = -(-big // d)
    return big, z_rows, -(-(big + z_rows) // 8) * 8


def _phi(x):
    """The symmetric degree-2 coordinates of x (..., d) in float32, (...,
    d(d+1)/2): lane o·d + a holds c·x_a·x_{(a+o) mod d} for the cyclic
    offsets o = 0 .. d/2 − 1 (c = 1 at o = 0, √2 after it), and the last
    d/2 lanes hold √2·x_a·x_{a+d/2}: every unordered pair once, by rolls of
    the lanes and no gather."""
    x = jnp.asarray(x).astype(_F32)
    d = x.shape[-1]
    half = d // 2
    root2 = jnp.sqrt(_F32(2.0))
    parts = [x * x] + [root2 * x * jnp.roll(x, -o, -1)
                       for o in range(1, half)]
    parts.append(root2 * x[..., :half] * x[..., half:])
    return jnp.concatenate(parts, -1)


def _z_rows(z, n_rows, d):
    """z (..., D) laid row-major in (..., n_rows, d), zero past D: the
    block's rows under S."""
    pad = ((0, 0),) * (z.ndim - 1) + ((0, n_rows * d - z.shape[-1]),)
    return jnp.pad(z, pad).reshape(z.shape[:-1] + (n_rows, d))


def _state_block(s, z, rows):
    """S (G, D, d) and z (G, D) as the (G, P, d) block."""
    big, d = s.shape[1:]
    return jnp.concatenate([s, _z_rows(z, rows - big, d)], 1)


def _state_parts(block):
    """(S (.., D, d), z (.., D)) of a (.., P, d) block."""
    d = block.shape[-1]
    big, z_rows, _ = retention_state_rows(d)
    z = block[..., big:big + z_rows, :]
    return (block[..., :big, :],
            z.reshape(z.shape[:-2] + (z_rows * d,))[..., :big])


def retention_state_forms(block):
    """A (..., P, d) state block as the quadratic forms it stands for,
    (..., d, d, d + 1) float32: M[a, b] = Σ_i decay_i · k_{i,a} k_{i,b} ·
    [v_i, 1], symmetric in (a, b), so that φ(q)ᵀ [S, z] = Σ_{a,b} q_a q_b
    M[a, b]. It names `_phi`'s order of the pairs and the block's layout
    once, for whoever holds a state to one computed otherwise (a test, the
    benchmark's check)."""
    s, z = _state_parts(jnp.asarray(block))
    flat = jnp.concatenate([s, z[..., None]], -1)         # (.., D, d + 1)
    big, d = s.shape[-2:]
    half = d // 2
    lane = np.arange(d)
    a = np.concatenate([np.tile(lane, half), lane[:half]])
    b = np.concatenate([(lane + o) % d for o in range(half)]
                       + [lane[:half] + half])
    where = np.zeros((d, d), np.int32)              # (a, b) -> its lane of φ
    where[a, b] = where[b, a] = np.arange(big)
    flat = flat / jnp.where(a == b, 1.0, jnp.sqrt(_F32(2.0)))[:, None]
    return flat[..., where, :].astype(_F32)


@register_op('retention_gate')
def retention_gate(x, w, *, shift=0.0):
    """log γ = log sigmoid(x · w + shift) in float32, one gate per key/value
    head: x (..., h), w (h, G) -> (..., G)."""
    return jax.nn.log_sigmoid(
        jnp.matmul(jnp.asarray(x), jnp.asarray(w),
                   preferred_element_type=_F32) + _F32(shift))


def _retention_scan(q, k, v, log_gate, last, chunk):
    """One sequence: q (L, G, R, d), k, v (L, G, d), log_gate (L, G); rows
    past ``last`` are padding (gate 1, contribution 0). Returns y (L, G, R,
    d) float32 and the (G, P, d) state after row ``last``.

    A scan over chunks that builds the state a chunk at a time. A chunk's
    outputs are the quadratic form over every key up to t: the state is
    built for the steps that follow and never read here (no φ(q) is formed:
    at d = 128 a read of the state costs a query as much as 8,288 keys)."""
    length, g, rep, d = q.shape
    big, _, rows = retention_state_rows(d)
    chunk = min(int(chunk), length)
    n = -(-length // chunk)
    padded = n * chunk
    live = jnp.arange(padded, dtype=jnp.int32) <= last

    def rows_of(x):
        # a dead row's k and v are zeroed by a select, not by a factor:
        # whatever a padded row holds (a NaN too) then adds exactly nothing
        x = jnp.pad(x, ((0, padded - length),) + ((0, 0),) * (x.ndim - 1))
        return jnp.where(live.reshape((-1,) + (1,) * (x.ndim - 1)), x, 0)

    def chunks(x):
        return x.reshape((n, chunk) + x.shape[1:])

    k_all, v_all = rows_of(k), rows_of(v)
    a_all = rows_of(log_gate.astype(_F32))
    xs = (jnp.arange(n),
          chunks(jnp.pad(q, ((0, padded - length), (0, 0), (0, 0), (0, 0)))),
          chunks(k_all), chunks(v_all), chunks(a_all))
    position = jnp.arange(padded, dtype=jnp.int32)
    cum_all = jnp.cumsum(a_all, 0)                        # (L, G)

    def step(state, x):
        idx, qc, kc, vc, ac = x
        s_prev, z_prev = _state_parts(state)
        b = jnp.cumsum(ac, 0)                             # (C, G), inclusive
        here = idx * chunk + jnp.arange(chunk, dtype=jnp.int32)
        # Σ_i a_{t,i} [v_i, 1] of the chunk's queries over the sequence's
        # keys, a_{t,i} = (q_t·k_i)² exp(cum[t] − cum[i]) for i <= t
        seen = here[:, None] >= position[None, :]
        scores = jnp.einsum('tgrd,igd->grti', qc, k_all,
                            preferred_element_type=_F32)
        cum_q = lax.dynamic_slice_in_dim(cum_all, idx * chunk, chunk)
        diff = cum_q.T[:, :, None] - cum_all.T[:, None, :]  # (G, t, i)
        decay = jnp.where(seen, jnp.exp(jnp.where(seen, diff, 0.0)), 0.0)
        weights = jnp.square(scores) * decay[:, None]     # (G, R, t, i)
        num = jnp.einsum('grti,igd->tgrd', weights.astype(v_all.dtype),
                         v_all, preferred_element_type=_F32)
        den = weights.sum(-1).transpose(2, 0, 1)          # (t, G, R)
        # the next state: S_prev decayed over the chunk, plus the chunk's
        # own φ(k_i) [v_i, 1]ᵀ decayed from i to its end
        phi_k = _phi(kc) * jnp.exp(b[-1][None] - b)[:, :, None]  # (C, G, D)
        s_next = jnp.exp(b[-1])[:, None, None] * s_prev + jnp.einsum(
            'igD,igd->gDd', phi_k, vc.astype(_F32),
            precision=_STATE_PRECISION)
        z_next = jnp.exp(b[-1])[:, None] * z_prev + phi_k.sum(0)
        # a row of padding (q = 0) weighs nothing: its 0/0 reads 0, and its
        # gradient stays finite
        den = jnp.where(den > 0, den, 1.0)
        return _state_block(s_next, z_next, rows), num / den[..., None]

    state, y = lax.scan(step, jnp.zeros((g, rows, d), _F32), xs)
    return y.reshape(padded, g, rep, d)[:length], state


@register_op('power_retention_prefill', outputs=('Out', 'State'))
def power_retention_prefill(q, k, v, log_gate, last=None, *, chunk=256):
    """Power retention over whole sequences, as a scan over chunks that
    builds the state (see above): a_{t,i} = (q_t·k_i)² Π_{s=i+1..t} γ_s.

    q (B, L, H, d); k, v (B, L, G, d), query head j reads key/value head
    j // (H/G); log_gate (B, L, G) float32, log γ; ``last`` () or (B,)
    int32, the index of a sequence's last row (rows past it are a rung's
    padding and never enter the state; None: every row is live). Returns
    (B, L, H·d) in q's dtype and the (B, G, P, d) float32 states after row
    ``last``."""
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    b, length, heads, d = q.shape
    g = k.shape[2]
    if last is None:
        last = length - 1
    last = jnp.broadcast_to(jnp.asarray(last, jnp.int32), (b,))
    y, state = jax.vmap(
        lambda *x: _retention_scan(*x, chunk=chunk))(
            q.reshape(b, length, g, heads // g, d), k, v,
            jnp.asarray(log_gate), last)
    return y.reshape(b, length, heads * d).astype(q.dtype), state


@register_op('power_retention_step', outputs=('Out', 'State'))
def power_retention_step(q, k, v, log_gate, state, rows):
    """One token for each of S slots over their recurrent states: gate,
    rank-one update, read.

    q (S, 1, H, d); k, v (S, 1, G, d); log_gate (S, 1, G); state (rows, G,
    P, d) float32, one row a request and row 0 for idle slots; rows (S,)
    int32, each slot's row. Returns (S, 1, H·d) in q's dtype and the state
    with every slot's row advanced.

    Two walks over the slots, one row at a time, so that the program holds
    no copy of S rows. The first advances each slot's row where it lies:
    S ← γ S + φ(k) vᵀ is one pass that reads the row and writes it back (the
    rank-one term is formed from φ(k) and v as it is added), z alike in its
    few rows. The second reads each advanced row, φ(q)ᵀ S and φ(q)ᵀ z.
    Writing and reading in one walk makes the compiler copy the whole array
    (seen on the CPU backend) or a row at a time (on the TPU)."""
    q, state = jnp.asarray(q), jnp.asarray(state)
    slots, _, heads, d = q.shape
    g = k.shape[2]
    rep = heads // g
    big, _, prow = retention_state_rows(d)
    tail = prow - big                       # the rows of z, and the padding
    rows = jnp.asarray(rows, jnp.int32)
    phi_q = _phi(q[:, 0]).reshape(slots, g, rep, big)
    phi_k = _phi(k[:, 0])                                   # (S, G, D)
    vf = jnp.asarray(v)[:, 0].astype(_F32)                  # (S, G, d)
    gamma = jnp.exp(jnp.asarray(log_gate)[:, 0].astype(_F32))
    z_add = _z_rows(phi_k, tail, d)                         # (S, G, tail, d)

    def advance(i, state):
        row, gm = rows[i], gamma[i][None, :, None, None]
        s_old = lax.dynamic_slice(state, (row, 0, 0, 0), (1, g, big, d))
        state = lax.dynamic_update_slice(
            state, gm * s_old + (phi_k[i][:, :, None]
                                 * vf[i][:, None, :])[None], (row, 0, 0, 0))
        z_old = lax.dynamic_slice(state, (row, 0, big, 0), (1, g, tail, d))
        return lax.dynamic_update_slice(state, gm * z_old + z_add[i][None],
                                        (row, 0, big, 0))

    state = lax.fori_loop(0, slots, advance, state)

    def read(i, carry):
        num, den = carry
        s_now = lax.dynamic_slice(state, (rows[i], 0, 0, 0),
                                  (1, g, big, d))[0]
        z_now = lax.dynamic_slice(state, (rows[i], 0, big, 0),
                                  (1, g, tail, d))[0]
        z_now = z_now.reshape(g, tail * d)[:, :big]
        return (num.at[i].set(jnp.einsum('grD,gDd->grd', phi_q[i], s_now,
                                         precision=_STATE_PRECISION)),
                den.at[i].set(jnp.einsum('grD,gD->gr', phi_q[i], z_now,
                                         precision=_STATE_PRECISION)))

    num, den = lax.fori_loop(
        0, slots, read, (jnp.zeros((slots, g, rep, d), _F32),
                         jnp.zeros((slots, g, rep), _F32)))
    out = (num / den[..., None]).reshape(slots, 1, heads * d)
    return out.astype(q.dtype), state


# ---------------------------------------------------------------------------
# gated short convolution (`lfm2`): a depthwise causal filter of L taps over
# u = B ⊙ z, gated by C, between an input projection h -> 3h = [B | C | z]
# and an output projection (both the caller's: models/hybrid_conv_moe_lm.py):
#
#     u_t = B_t ⊙ z_t      c_t = Σ_{j<L} w[j] ⊙ u_{t-(L-1)+j}      y_t = C_t ⊙ c_t
#
# with u before position 0 zero: torch Conv1d's cross-correlation order, the
# LAST tap on the newest position. No activation: the two gates are products.
# What a request carries from step to step is its last L - 1 values of u,
# oldest first, whatever its context: a state block (1, L - 1, h), h values
# on the lanes.
#
# Precision: the rows arrive as stored (bf16 on the served path); u, the
# filter's products and sum, the gate's product and the state are float32 (a
# product of two bf16 values is exact there); y returns in the rows' dtype.
# ---------------------------------------------------------------------------

def _conv_parts(x, w):
    """(u (.., T, h) float32, C (.., T, h) float32, taps (L, h) float32) of
    rows x (.., T, 3h) = [B | C | z]."""
    x = jnp.asarray(x)
    h = x.shape[-1] // 3
    b, c, z = (x[..., i * h:(i + 1) * h].astype(_F32) for i in range(3))
    return b * z, c, jnp.asarray(w).astype(_F32)


@register_op('short_conv_prefill', outputs=('Out', 'State'))
def short_conv_prefill(x, w, last=None):
    """The gated short convolution over whole sequences (see above).

    x (B, T, 3h), the input projection's rows [B | C | z]; w (L, h), tap j
    on position t - (L - 1) + j; ``last`` () or (B,) int32, the index of a
    sequence's last row (rows past it are a rung's padding: a causal filter
    never lets them reach a row at or before it, and the state is taken at
    ``last``, never at the rung's end; None: every row is live). Returns
    (B, T, h) in x's dtype and the (B, 1, L - 1, h) float32 states after row
    ``last``: u_{last-(L-2)} .. u_{last}, zero where the sequence has not
    that many rows."""
    u, gate, taps = _conv_parts(x, w)
    batch, length, h = u.shape
    n = taps.shape[0]
    if last is None:
        last = length - 1
    last = jnp.broadcast_to(jnp.asarray(last, jnp.int32), (batch,))
    up = jnp.pad(u, ((0, 0), (n - 1, 0), (0, 0)))         # up[i] = u[i-(L-1)]
    conv = sum(taps[j] * up[:, j:j + length] for j in range(n))
    state = jax.vmap(lambda rows, at: lax.dynamic_slice_in_dim(
        rows, at + 1, n - 1, 0))(up, last)
    return (gate * conv).astype(jnp.asarray(x).dtype), state[:, None]


@register_op('short_conv_step', outputs=('Out', 'State'))
def short_conv_step(x, w, state, rows):
    """One token for each of S slots over their conv states: read the
    slot's L - 1 last values of u, filter, shift the new one in.

    x (S, 1, 3h); w (L, h); state (rows, 1, L - 1, h) float32, one row a
    request, oldest value first, and row 0 for idle slots; rows (S,) int32,
    each slot's row. Returns (S, 1, h) in x's dtype and the state with every
    slot's row advanced. The rows are gathered, advanced and scattered back
    whole: a row is 2(L - 1)h·4 bytes (16 KB at h = 2,048), and idle slots
    all write the scratch row, whichever of them lands."""
    u, gate, taps = _conv_parts(x, w)
    state = jnp.asarray(state)
    rows = jnp.asarray(rows, jnp.int32)
    window = jnp.concatenate([state[rows][:, 0], u], 1)   # (S, L, h)
    conv = (taps[None] * window).sum(1, keepdims=True)
    state = state.at[rows].set(window[:, None, 1:])
    return (gate * conv).astype(jnp.asarray(x).dtype), state
