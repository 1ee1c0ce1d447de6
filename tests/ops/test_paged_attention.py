"""Op-level contract of ops/nn_ops.py paged_attention /
paged_prefill_attention. The single-query decode read (one formulation: the
walk over the batch's live blocks) is held to a plain float32 numpy softmax
over each slot's live positions, at a stated tolerance, across contexts at
block boundaries, ragged mixes with idle slots, live-block counts around the
chunk size, head sizes, padded rows and pool dtypes; plus clean block reuse
(no stale-cache bleed) and the explicit kernel dispatch of the ops that have
a kernel (predicates, not exception handlers, pick the path)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import nn_ops
from paddle_tpu.ops.nn_ops import (LIVE_BLOCK_CHUNK, flash_kernel_applies,
                                   fused_attention, live_block_list,
                                   paged_attention, paged_prefill_attention)

H, D, BS, MAXBPS = 2, 16, 4, 4
E = MAXBPS * BS          # padded context extent
SCALE = 1.0 / np.sqrt(D)
NEG = -1e9


def whole_seq_reference(q_rows, k_rows, v_rows):
    """The unfused MultiHeadAttention chain at extent E: matmul·α +
    additive causal bias, softmax, matmul — per-row ground truth."""
    q4, k4, v4 = (jnp.asarray(x[None]) for x in (q_rows, k_rows, v_rows))
    s = jnp.matmul(q4, jnp.swapaxes(k4, -1, -2)) \
        * jnp.asarray(SCALE, jnp.float32)
    s = s + jnp.asarray(np.triu(np.full((E, E), NEG, 'float32'),
                                1)[None, None])
    return np.asarray(jnp.matmul(jax.nn.softmax(s, -1), v4))[0]


def rows_of(x):
    """(H, n, D) head-major rows → the pool's (n, H·D) rows of one token."""
    return x.transpose(1, 0, 2).reshape(x.shape[1], -1)


def build_cache(rng, num_blocks, tables_rows):
    """Fill per-slot rows into distinct blocks; returns (pages, tables,
    per-slot row arrays). Pages are (num_blocks, BS, H·D)."""
    k_pages = np.zeros((num_blocks, BS, H * D), 'float32')
    v_pages = np.zeros_like(k_pages)
    tables, k_rows, v_rows = [], [], []
    nxt = 1
    for nb in tables_rows:
        kr = rng.randn(H, E, D).astype('float32')
        vr = rng.randn(H, E, D).astype('float32')
        table = []
        for j in range(nb):
            table.append(nxt)
            k_pages[nxt] = rows_of(kr[:, j * BS:(j + 1) * BS])
            v_pages[nxt] = rows_of(vr[:, j * BS:(j + 1) * BS])
            nxt += 1
        table += [0] * (MAXBPS - nb)
        tables.append(table)
        k_rows.append(kr)
        v_rows.append(vr)
    return k_pages, v_pages, np.asarray(tables, np.int32), k_rows, v_rows


# Tolerance of the decode read against the float32 reference: both sum the
# same float32 products over the same positions, in another order (the read
# per head by a matmul, per block, then across chunks with a rescale), so
# they differ by a few ulp of sums of up to 256 terms of order 1: 1e-7 to
# 5e-7 seen on outputs of scale 1 to 3, and 5e-6 is ten times that. A
# position dropped, added or weighted wrongly moves an output by 1e-2 or
# more; a bf16 product or weight by 2e-3.
TOL = dict(rtol=0, atol=5e-6)


def reference_decode(q, k_pages, v_pages, tables, lens, scale,
                     k_scales=None, v_scales=None):
    """Plain float32 numpy: for each slot and head, softmax over the slot's
    live positions alone, read block by block through its table."""
    s, h, d = q.shape
    bs = k_pages.shape[1]

    def rows(pages, scales, table, c):
        ids = table[:-(-c // bs)]
        x = np.asarray(pages, 'float32')[ids].reshape(-1, pages.shape[-1])
        x = x[:c, :h * d].reshape(c, h, d)
        if scales is not None:
            x = x * scales[ids].reshape(-1, h)[:c, :, None]
        return x.astype('float32')

    out = np.zeros((s, h, d), 'float32')
    for i in range(s):
        c = int(lens[i])
        k = rows(k_pages, k_scales, tables[i], c)
        v = rows(v_pages, v_scales, tables[i], c)
        for j in range(h):
            sc = (k[:, j] @ q[i, j]) * np.float32(scale)
            w = np.exp(sc - sc.max())
            out[i, j] = (w / w.sum()) @ v[:, j]
    return out


def stale_pool(rng, num_blocks, bs, h, d, kv_dtype='f32'):
    """A pool with EVERY row holding finite garbage (free blocks, block
    tails and the scratch block included), rows padded to whole lane tiles
    with garbage too: (k_pages, v_pages, k_scales, v_scales)."""
    lanes = -(-h * d // 128) * 128
    shape = (num_blocks, bs, lanes)
    if kv_dtype == 'int8':
        pages = [rng.randint(-127, 128, shape).astype('int8')
                 for _ in range(2)]
        scales = [rng.uniform(0.002, 0.02, (num_blocks, bs, h))
                  .astype('float32') for _ in range(2)]
        return pages + scales
    dtype = jnp.bfloat16 if kv_dtype == 'bf16' else 'float32'
    return [np.asarray(jnp.asarray(rng.randn(*shape), dtype))
            for _ in range(2)] + [None, None]


def deal_tables(rng, lens, bs, max_blocks, num_blocks):
    """Distinct, shuffled pool blocks for each slot's context; ``lens`` 0 is
    an idle slot as `decode_coords` gives it: the scratch block at context
    1. Returns (tables, context_lens)."""
    free = list(rng.permutation(np.arange(1, num_blocks)))
    tables = np.zeros((len(lens), max_blocks), np.int32)
    for i, c in enumerate(lens):
        for j in range(-(-c // bs)):
            tables[i, j] = free.pop()
    return tables, np.asarray([max(c, 1) for c in lens], np.int32)


def check_decode(lens, h=H, d=D, bs=BS, max_blocks=MAXBPS, kv_dtype='f32',
                 seed=0):
    rng = np.random.RandomState(seed)
    num_blocks = 1 + sum(-(-c // bs) for c in lens) + 3
    k_pages, v_pages, ks, vs = stale_pool(rng, num_blocks, bs, h, d,
                                          kv_dtype)
    tables, ctx = deal_tables(rng, lens, bs, max_blocks, num_blocks)
    q = rng.randn(len(lens), h, d).astype('float32')
    scale = 1.0 / np.sqrt(d)
    out = np.asarray(paged_attention(q, k_pages, v_pages, tables, ctx,
                                     ks, vs, sm_scale=float(scale)))
    assert out.shape == q.shape and out.dtype == np.float32
    assert np.isfinite(out).all()
    np.testing.assert_allclose(
        out, reference_decode(q, k_pages, v_pages, tables, ctx, scale,
                              ks, vs), **TOL)


def test_decode_parity_ragged_mix():
    """Slots with wildly different context lengths in ONE batched call, idle
    slots among them, each match their own float32 reference."""
    check_decode([1, 0, 3, 7, 0, 12, 16])


@pytest.mark.parametrize('c', [1, BS, BS + 1, 2 * BS - 1, 2 * BS, E])
def test_decode_parity_block_boundaries(c):
    """len % block_size ∈ {0, 1, block_size-1}, one token, and the
    full-table case."""
    check_decode([c], seed=c)


def test_decode_every_slot_idle():
    """Each slot reads the scratch block at context 1: finite, and the one
    position's value."""
    check_decode([0, 0, 0, 0, 0])


@pytest.mark.parametrize('n_live', [LIVE_BLOCK_CHUNK - 1, LIVE_BLOCK_CHUNK,
                                    LIVE_BLOCK_CHUNK + 1])
def test_decode_live_blocks_around_the_chunk(n_live):
    """The walk ends inside the first chunk, exactly on it, and one block
    into the second (whose other entries are the list's masked padding); a
    slot's blocks straddle the boundary."""
    max_blocks = 64
    blocks = [64, 64, 63, 1, 62, n_live - 254]       # 6 × 64 = 384 entries
    lens = [b * BS - (i % 3) for i, b in enumerate(blocks)]
    assert sum(-(-c // BS) for c in lens) == n_live
    check_decode(lens, max_blocks=max_blocks, seed=n_live)


@pytest.mark.parametrize('h, d', [(3, 64), (2, 128), (5, 64)],
                         ids=['head_dim64', 'head_dim128', 'row320_in_384'])
def test_decode_head_sizes_and_padded_rows(h, d):
    """head_dim 64 (192 values in 256 lanes), head_dim 128 (until PR 29 the
    stock kernel's), and 5 heads of 64: a 320-wide row in 384 lanes, the
    padding lanes holding garbage that no head may read."""
    check_decode([1, 9, 0, 16, 5], h=h, d=d, seed=h * d)


@pytest.mark.parametrize('kv_dtype', ['f32', 'bf16', 'int8'])
def test_decode_pool_dtypes(kv_dtype):
    """bf16 rows cast, int8 rows scaled per (position, head): the reference
    reads the pool's stored values decoded to float32, so the tolerance is
    the float32 one at every dtype."""
    check_decode([2, 16, 0, 7, 11, 4], h=3, d=64, kv_dtype=kv_dtype, seed=5)


def test_live_block_list_is_the_slot_major_compaction():
    """Against a hand walk of the tables: live entries first, slot-major and
    in sequence order, then scratch entries no context reaches."""
    tables = np.asarray([[7, 3, 9, 0], [0, 0, 0, 0], [5, 2, 0, 0],
                         [8, 1, 4, 6]], np.int32)
    lens = np.asarray([9, 1, 4, 16], np.int32)        # 3, 1, 1, 4 blocks
    block_id, slot, first_pos, n_live = (
        np.asarray(x) for x in live_block_list(tables, lens, BS))
    assert int(n_live) == 9 and block_id.shape == (16,)
    assert block_id[:9].tolist() == [7, 3, 9, 0, 5, 8, 1, 4, 6]
    assert slot[:9].tolist() == [0, 0, 0, 1, 2, 3, 3, 3, 3]
    assert first_pos[:9].tolist() == [0, 4, 8, 0, 0, 0, 4, 8, 12]
    assert not block_id[9:].any() and (first_pos[9:] == E).all()
    assert ((0 <= slot) & (slot < 4)).all()


def test_prefill_parity_rows():
    """paged_prefill_attention rows 0..P-1 equal the whole-sequence rows,
    at a bucket extent SMALLER than the padded context."""
    rng = np.random.RandomState(1)
    k_pages, v_pages, tables, k_rows, v_rows = build_cache(rng, 16, [MAXBPS])
    q_rows = rng.randn(H, E, D).astype('float32')
    Lq = 8                             # bucket < E
    out = np.asarray(paged_prefill_attention(
        q_rows[None, :, :Lq], k_rows[0][None, :, :Lq],
        v_rows[0][None, :, :Lq], k_pages, v_pages, tables[:1],
        sm_scale=float(SCALE)))
    ref = whole_seq_reference(q_rows, k_rows[0], v_rows[0])
    assert np.array_equal(out[0], ref[:, :Lq])


def test_block_reuse_no_stale_bleed():
    """A freed block refilled with garbage, then reused by a new request,
    contributes NOTHING beyond the new context: outputs with clean vs
    garbage pool tails are bitwise identical (masked probabilities are
    exactly zero in the XLA fallback)."""
    rng = np.random.RandomState(2)
    c = 5                              # context: block 0 full + 1 token
    k_rows = rng.randn(H, E, D).astype('float32')
    v_rows = rng.randn(H, E, D).astype('float32')
    q = rng.randn(1, H, D).astype('float32')
    table = np.asarray([[1, 2, 0, 0]], np.int32)
    lens = np.asarray([c], np.int32)

    def run(fill):
        k_pages = np.full((8, BS, H * D), fill, 'float32')
        v_pages = np.full_like(k_pages, fill)
        for j in range(2):
            k_pages[j + 1] = rows_of(k_rows[:, j * BS:(j + 1) * BS])
            v_pages[j + 1] = rows_of(v_rows[:, j * BS:(j + 1) * BS])
        # stale garbage INSIDE the table beyond the context: positions
        # c.. of block 2 keep whatever the previous tenant wrote
        k_pages[2, c - BS:] = fill
        v_pages[2, c - BS:] = fill
        return np.asarray(paged_attention(q, k_pages, v_pages, table, lens,
                                          sm_scale=float(SCALE)))

    clean = run(0.0)
    stale = run(1e6)                   # previous request's leftovers
    assert np.array_equal(clean, stale)


# -- kernel dispatch -------------------------------------------------------

def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_dispatch_on_cpu_selects_xla():
    """Off the chip the flash predicate is false at every shape, including
    the ones the kernel accepts on a TPU."""
    assert not flash_kernel_applies(_sds((1, 8, 128, 64)),
                                    _sds((1, 8, 128, 64)))
    assert not flash_kernel_applies(_sds((8, 12, 512, 64), jnp.bfloat16),
                                    _sds((8, 12, 512, 64), jnp.bfloat16))


def test_dispatch_on_tpu_follows_the_kernels_own_rules(monkeypatch):
    """With a TPU backend the predicate encodes what the stock flash kernel
    and Mosaic accept (established on a v5e, PERF.md section 6, PR 21) — a
    refused shape is XLA by predicate."""
    monkeypatch.setattr(nn_ops, 'on_tpu', lambda: True)
    # flash: both sequence extents whole multiples of the 128-row blocks
    for L, want in ((128, True), (256, True), (512, True), (64, False),
                    (16, False), (1, False), (192, False)):
        q = _sds((1, 8, L, 64))
        assert flash_kernel_applies(q, q) is want, L
    assert flash_kernel_applies(_sds((8, 12, 512, 64), jnp.bfloat16),
                                _sds((8, 12, 512, 64), jnp.bfloat16))
    assert not flash_kernel_applies(_sds((1, 8, 128, 64)),
                                    _sds((1, 8, 64, 64)))     # kv extent
    assert not flash_kernel_applies(_sds((8, 128, 64)), _sds((8, 128, 64)))
    assert not flash_kernel_applies(_sds((1, 8, 128, 64), jnp.float16),
                                    _sds((1, 8, 128, 64), jnp.float16))


def _forbid_kernels(monkeypatch):
    """Any call into a stock pallas kernel fails the test."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    from jax.experimental.pallas.ops.tpu import paged_attention as pa

    def boom(*a, **k):
        raise AssertionError('pallas kernel called')
    monkeypatch.setattr(fa, 'flash_attention', boom)
    monkeypatch.setattr(pa, 'paged_attention', boom)


def test_paged_attention_has_one_single_query_path(monkeypatch):
    """No predicate, attribute or kernel picks the single-query read: on a
    (pretend) TPU, at the head_dim 128 and f32 pool the stock paged kernel
    used to take, it returns what it returns on the CPU and touches no
    pallas kernel."""
    import inspect
    assert not hasattr(nn_ops, 'paged_kernel_applies')
    assert 'pages_per_compute_block' not in inspect.signature(
        paged_attention).parameters
    rng = np.random.RandomState(7)
    lens = [33, 0, 64, 1]
    k_pages, v_pages, _, _ = stale_pool(rng, 16, 16, 2, 128)
    tables, ctx = deal_tables(rng, lens, 16, 4, 16)
    q = rng.randn(len(lens), 2, 128).astype('float32')
    want = np.asarray(paged_attention(q, k_pages, v_pages, tables, ctx,
                                      sm_scale=0.5))
    monkeypatch.setattr(nn_ops, 'on_tpu', lambda: True)
    _forbid_kernels(monkeypatch)
    assert np.array_equal(want, np.asarray(paged_attention(
        q, k_pages, v_pages, tables, ctx, sm_scale=0.5)))
    # and a list made once by the caller reads the same as one made here
    assert np.array_equal(want, np.asarray(paged_attention(
        q, k_pages, v_pages, tables, ctx,
        live=list(live_block_list(tables, ctx, 16)), sm_scale=0.5)))


def test_refused_shapes_take_xla_without_touching_the_kernel(monkeypatch):
    """A shape the flash kernel's own rules refuse never reaches it: with a
    (pretend) TPU backend the ops run the XLA formulation and return what
    they return on the CPU — by predicate, not by exception."""
    rng = np.random.RandomState(3)
    k_pages, v_pages, tables, k_rows, v_rows = build_cache(rng, 16, [MAXBPS])
    q_rows = rng.randn(H, E, D).astype('float32')
    Lq = 8
    args_prefill = (q_rows[None, :, :Lq], k_rows[0][None, :, :Lq],
                    v_rows[0][None, :, :Lq], k_pages, v_pages, tables[:1])
    want_prefill = np.asarray(paged_prefill_attention(
        *args_prefill, sm_scale=float(SCALE)))
    want_fused = np.asarray(fused_attention(
        *args_prefill[:3], sm_scale=float(SCALE), causal=True))
    monkeypatch.setattr(nn_ops, 'on_tpu', lambda: True)
    _forbid_kernels(monkeypatch)
    assert np.array_equal(want_prefill, np.asarray(paged_prefill_attention(
        *args_prefill, sm_scale=float(SCALE))))
    assert np.array_equal(want_fused, np.asarray(fused_attention(
        *args_prefill[:3], sm_scale=float(SCALE), causal=True)))


def test_accepted_shapes_reach_the_kernel_and_refusals_propagate(
        monkeypatch):
    """Where the predicate holds the kernel is called, and whatever it
    raises reaches the caller — no handler turns a refusal into the XLA
    formulation."""
    monkeypatch.setattr(nn_ops, 'on_tpu', lambda: True)
    _forbid_kernels(monkeypatch)
    q = np.zeros((1, 2, 128, 16), 'float32')
    with pytest.raises(AssertionError, match='pallas kernel called'):
        fused_attention(q, q, q, sm_scale=1.0, causal=True)
    pool = np.zeros((8, 4, 2 * 128), 'float32')
    with pytest.raises(AssertionError, match='pallas kernel called'):
        paged_prefill_attention(
            np.zeros((1, 2, 128, 128), 'float32'),
            np.zeros((1, 2, 128, 128), 'float32'),
            np.zeros((1, 2, 128, 128), 'float32'), pool, pool,
            np.zeros((1, 4), np.int32), sm_scale=1.0)
