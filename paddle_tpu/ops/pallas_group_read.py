"""The decode step's grouped K/V read as one pallas TPU kernel (ROADMAP S3):
every live row of the batch is copied from the pool into VMEM once and
folded there into its slot's running softmax.

The walk is the one `nn_ops._live_group_walk` makes in XLA, over the
same list (`nn_ops.live_group_list`, `nn_ops.live_ring_group_list`): the
live groups of whole cache blocks, slot-major, and their count ``n_live``.
The XLA walk takes a chunk of 128 groups out of the pool with a gather,
writes that copy back to HBM, lays it out again and reads it in two
einsums. Here the list comes in by scalar prefetch, the pools stay in HBM
(``memory_space=ANY``), and one loop inside the kernel, bounded by
``n_live`` and not by the padded list, visits the groups in order:

- a FOLD is up to `FOLD_GROUPS` consecutive groups of one slot. Every block
  of its groups is copied into one of `BUFFERS` VMEM buffers while the
  folds before it are folded: no branch a block, so the scalar work of a
  copy stays below its transfer;
- a slot's groups are consecutive in the list: its running max, sum and
  accumulator stay in VMEM from its first group to its last, and its output
  is written once, when the walk leaves it;
- masking is by position, as in the XLA walk: a key at position p counts
  iff ``p < context`` and, with ``span``, ``p >= context - span``. A masked
  position gets exactly zero mass, and its value row is zeroed before the
  second matmul, so no stale or NaN row of a buffer or of the scratch block
  reaches a result. A fold whose every position is attended skips the
  masks.

One matmul pair a fold serves all key/value heads. The G heads' R query rows
of a slot are the rows of a block-diagonal ``(G·R, W)`` operand: head g's
rows hold its query in lanes ``[g·D, (g+1)·D)`` and zeros elsewhere. It
meets the fold's rows as stored, ``(F·keys, W)``, and the weighted sum is one
``(G·R, F·keys) x (F·keys, W)`` matmul whose head-g rows keep lanes ``[g·D,
(g+1)·D)``. That costs G times the FLOPs of G per-head matmuls, needs no
lane slice at a head's width (64 lanes in one caller), and stays bound by
HBM bytes at the callers' widths (48 FLOP a byte for 8 heads of 128 over 6
rows, against a v5e's ~240). A matmul's fixed cost, not its FLOPs, is what a
fold pays beyond its bytes, so a fold takes four groups (512 keys at blocks
of 16) and three folds' copies are in flight while one is folded.

The same mathematics as the walk: operands in q's dtype (the rows cast to
it where the pool's differs), float32 accumulation, float32 probabilities
cast to the value rows' dtype for the second matmul. ``v_pages is k_pages``
(a latent pool: one array holds keys and values) is one copy a block, whose
rows serve both matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import on_tpu

_F32 = jnp.float32
# a slot's query rows of a key/value head, padded to whole float32 sublane
# tiles: the block-diagonal operand is G whole tiles of rows
_ROW_TILE = 8
# the running max and sum of a query row, broadcast over one lane tile
_LANES = 128
# groups one fold takes (consecutive groups of one slot: one matmul pair
# over all their positions), and the folds' buffers (the copies of the next
# BUFFERS - 1 folds run while one is folded)
FOLD_GROUPS = 4
BUFFERS = 4


def group_read_kernel_applies(q, k_pages):
    """True when the grouped read (`nn_ops._live_group_attention`) runs
    this kernel for ``q`` and the pool ``k_pages`` (arrays or
    ShapeDtypeStructs): a TPU backend, and bf16 or float32 query and pool
    rows. The repo's one convention (ops/nn_ops.py, "explicit kernel
    dispatch"): where it holds the kernel runs, and a Mosaic refusal is an
    error; elsewhere the XLA walk runs because the code says so (the CPU
    tests). `nn_ops.group_walk_pads` asks it too, for the count of the
    blocks a step's read copies."""
    return (on_tpu() and q.dtype in (jnp.bfloat16, jnp.float32)
            and k_pages.dtype in (jnp.bfloat16, jnp.float32))


def _kernel(ids_ref, slot_ref, pos_ref, n_ref, ctx_ref, q_ref, *refs,
            groups, head_dim, blocks, block, span, scale, shared,
            fold_groups, buffers):
    if shared:
        k_hbm, o_ref, kbuf, sems, m_ref, l_ref, acc_ref, qbd_ref = refs
        pairs = ((k_hbm, kbuf),)
        vbuf = kbuf
    else:
        (k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref,
         qbd_ref) = refs
        pairs = ((k_hbm, kbuf), (v_hbm, vbuf))
    n_entries = slot_ref.shape[0]
    rows, lanes = q_ref.shape[1:]
    keys = blocks * block                     # a group's positions
    width = fold_groups * keys                # a fold's
    n = n_ref[0]
    neg = jnp.finfo(_F32).min

    def at(i):
        """Entry i's scalars, i past the list's end read as its last."""
        return jnp.minimum(i, n_entries - 1)

    def run_of(i):
        """Entries from i on, at most ``fold_groups``, of i's slot: one
        fold's groups (a slot's groups are consecutive in the list)."""
        s, u = slot_ref[at(i)], 1
        for e in range(1, fold_groups):
            u = u + ((i + e < n) & (slot_ref[at(i + e)] == s)
                     & (u == e)).astype(jnp.int32)
        return u

    def transfer(i, u, buf):
        """Start the copies of fold (i, u)'s blocks into buffer ``buf``:
        every block of its groups (a block's rows the slot does not attend
        are masked like any other), a group's blocks unrolled with no
        branch a block. The fold's groups are a loop: unrolled whole, the
        kernel would lower in seconds, which a program that holds it pays in
        every process, its compile cached or not."""
        def group(e, carry):
            @pl.when(e < u)
            def _():
                for j in range(blocks):
                    row = pl.multiple_of(e * keys + j * block, block)
                    for kv, (pages, dst) in enumerate(pairs):
                        pltpu.make_async_copy(
                            pages.at[ids_ref[at(i + e) * blocks + j]],
                            dst.at[buf, pl.ds(row, block)],
                            sems.at[kv, buf]).start()
            return carry

        lax.fori_loop(0, fold_groups, group, 0)

    def arrived(u, buf):
        """Wait for the ``u`` groups' blocks `transfer` started into buffer
        ``buf``: each is one block of rows on the buffer's semaphore."""
        def one(k, carry):
            for kv, (pages, dst) in enumerate(pairs):
                pltpu.make_async_copy(pages.at[0],
                                      dst.at[buf, pl.ds(0, block)],
                                      sems.at[kv, buf]).wait()
            return carry

        lax.fori_loop(0, u * blocks, one, 0)

    def diagonal():
        """(G·R, W) bool: head g's rows hold lanes [g·D, (g+1)·D)."""
        shape = (groups * rows, lanes)
        row = lax.broadcasted_iota(jnp.int32, shape, 0)
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        return functools.reduce(jnp.logical_or, [
            (row >= g * rows) & (row < (g + 1) * rows)
            & (lane >= g * head_dim) & (lane < (g + 1) * head_dim)
            for g in range(groups)])

    def enter(s):
        q = q_ref[s]                                            # (R, W)
        if groups > 1:
            q = jnp.where(diagonal(), jnp.concatenate([q] * groups, 0), 0.0)
        qbd_ref[...] = q.astype(qbd_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, neg, _F32)
        l_ref[...] = jnp.zeros(l_ref.shape, _F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def seen(i, u, shape, axis):
        """Which of a fold's ``width`` positions its slot attends: group e
        < u of the fold holds entry i + e's positions, the others none."""
        idx = lax.broadcasted_iota(jnp.int32, shape, axis)
        context = ctx_ref[slot_ref[at(i)]]
        ok = jnp.zeros(shape, jnp.bool_)
        for e in range(fold_groups):
            mine = (idx >= e * keys) & (idx < (e + 1) * keys) & (e < u)
            pos = idx + (pos_ref[at(i + e)] - e * keys)
            fine = pos < context
            if span:
                fine = fine & (pos >= context - span)
            ok = ok | (mine & fine)
        return ok

    def rows_of(buf_ref, buf):
        got = buf_ref[buf]                                      # (F·T, W)
        return got if got.dtype == qbd_ref.dtype \
            else got.astype(qbd_ref.dtype)

    def fold(i, u, buf, edge):
        """One fold's groups into the slot's running softmax. ``edge``: a
        position of the fold is not attended (past the context, before the
        span, or in a group the fold does not hold): it is masked, and its
        value row zeroed. A fold of whole attended
        groups is taken as it is."""
        scores = lax.dot_general(
            qbd_ref[...], rows_of(kbuf, buf), (((1,), (1,)), ((), ())),
            preferred_element_type=_F32) * scale                # (GR, F·T)
        if edge:
            cols = seen(i, u, (1, width), 1)
            scores = jnp.where(cols, scores, neg)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)                        # (GR, 128)
        p = jnp.exp(scores - m_next[:, :1])
        v = rows_of(vbuf, buf)
        if edge:
            p = jnp.where(cols, p, 0.0)
            v = jnp.where(seen(i, u, (width, 1), 0), v,
                          jnp.zeros((), v.dtype))
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=_F32)

    def leave(s):
        out = acc_ref[...] / l_ref[...][:, :1]                  # (GR, W)
        if groups > 1:
            out = jnp.where(diagonal(), out, 0.0)
            out = functools.reduce(jnp.add, [
                out[g * rows:(g + 1) * rows] for g in range(groups)])
        o_ref[s] = out.astype(o_ref.dtype)

    # a slot the list names no group of reads zeros
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n > 0)
    def _():
        # the folds in flight: (first entry, groups) of the one to fold and
        # of the next ``buffers - 2``, whose copies have started
        ahead = [(jnp.int32(0), run_of(0))]
        for _ in range(buffers - 2):
            i = ahead[-1][0] + ahead[-1][1]
            ahead.append((i, run_of(i)))

        def prefetch(b, fold):
            i, u = fold

            @pl.when(i < n)
            def _():
                transfer(i, u, b)
            return i + u, run_of(i + u)

        lax.fori_loop(0, buffers - 1, prefetch, ahead[0])

        def body(carry):
            t, ahead = carry[0], list(zip(carry[1::2], carry[2::2]))
            i, u = ahead[0]
            nxt = ahead[-1][0] + ahead[-1][1]
            nu = run_of(nxt)

            @pl.when(nxt < n)
            def _():
                transfer(nxt, nu, lax.rem(t + buffers - 1, buffers))

            s = slot_ref[i]

            @pl.when((i == 0) | (slot_ref[jnp.maximum(i - 1, 0)] != s))
            def _():
                enter(s)

            buf = lax.rem(t, buffers)
            arrived(u, buf)
            context = ctx_ref[s]
            inside = (u == fold_groups) & (
                pos_ref[at(i + fold_groups - 1)] + keys <= context)
            if span:
                inside = inside & (pos_ref[i] >= context - span)

            @pl.when(inside)
            def _():
                fold(i, u, buf, False)

            @pl.when(jnp.logical_not(inside))
            def _():
                fold(i, u, buf, True)

            @pl.when((i + u == n) | (slot_ref[at(i + u)] != s))
            def _():
                leave(s)
            flat = [x for pair in ahead[1:] + [(nxt, nu)] for x in pair]
            return (t + 1, *flat)

        lax.while_loop(lambda carry: carry[1] < n, body,
                       (jnp.int32(0), *[x for pair in ahead for x in pair]))


def group_read(q, k_pages, v_pages, context_lens, live, kv_heads, sm_scale,
               span=0, *, interpret=False):
    """`nn_ops._live_group_walk` as the kernel: q (S, H, K, D) against
    the pools' rows (blocks, block, W) of the live groups ``live`` =
    ``(block_ids, slot, first_pos, n_live)``; query head i reads key/value
    head i // (H / ``kv_heads``), every row of a slot sees positions <
    ``context_lens[slot]`` (and, ``span`` > 0, >= context - span). Returns
    (S, H, K, D) in q's dtype. ``interpret``: pallas interpret mode (the
    CPU tests)."""
    s, h, kq, d = q.shape
    g = int(kv_heads)
    r = (h // g) * kq
    rows = -(-r // _ROW_TILE) * _ROW_TILE
    _, block, lanes = k_pages.shape
    block_ids, slot, first_pos, n_live = live
    n_entries, blocks = block_ids.shape
    shared = v_pages is k_pages
    # a slot's queries as the pool lays a row out: row r of head g in lanes
    # [g·D, (g+1)·D), float32 (exact from the stored dtype)
    qc = jnp.pad(q.reshape(s, g, r, d).astype(_F32),
                 ((0, 0), (0, 0), (0, rows - r), (0, 0)))
    qc = jnp.pad(qc.transpose(0, 2, 1, 3).reshape(s, rows, g * d),
                 ((0, 0), (0, 0), (0, lanes - g * d)))
    pools = (k_pages,) if shared else (k_pages, v_pages)
    whole = pl.BlockSpec((s, rows, lanes), lambda i, *_: (0, 0, 0))
    fold_groups, buffers = FOLD_GROUPS, BUFFERS
    width = fold_groups * blocks * block              # a fold's positions
    state = g * rows                                    # rows of the state
    item = k_pages.dtype.itemsize
    vmem = (4 * s * rows * lanes * 4                    # q and out, twice
            + buffers * len(pools) * width * lanes * item
            + state * (lanes * (4 + q.dtype.itemsize) + 2 * _LANES * 4)
            + 4 * state * max(width, lanes) * 4)        # the fold's values
    out = pl.pallas_call(
        functools.partial(_kernel, groups=g, head_dim=d, blocks=blocks,
                          block=block, span=int(span),
                          scale=float(sm_scale), shared=shared,
                          fold_groups=fold_groups, buffers=buffers),
        out_shape=jax.ShapeDtypeStruct((s, rows, lanes), _F32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[whole] + [pl.BlockSpec(memory_space=pl.ANY)
                                for _ in pools],
            out_specs=whole,
            grid=(1,),
            scratch_shapes=[pltpu.VMEM((buffers, width, lanes), p.dtype)
                            for p in pools]
            + [pltpu.SemaphoreType.DMA((len(pools), buffers)),
               pltpu.VMEM((state, _LANES), _F32),
               pltpu.VMEM((state, _LANES), _F32),
               pltpu.VMEM((state, lanes), _F32),
               pltpu.VMEM((state, lanes), q.dtype)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=vmem + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=4 * n_entries * state * lanes * blocks * block,
            transcendentals=n_entries * state * blocks * block,
            bytes_accessed=n_entries * blocks * block * lanes * item
            * len(pools)
            + 2 * s * rows * lanes * 4),
        interpret=interpret,
    )(block_ids.reshape(-1), slot, first_pos,
      jnp.reshape(n_live, (1,)).astype(jnp.int32),
      jnp.asarray(context_lens, jnp.int32), qc, *pools)
    out = out[..., :g * d].reshape(s, rows, g, d)[:, :r]
    return out.transpose(0, 2, 1, 3).reshape(s, h, kq, d).astype(q.dtype)
