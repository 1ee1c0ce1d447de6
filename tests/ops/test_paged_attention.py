"""Op-level contract of ops/nn_ops.py paged_attention /
paged_prefill_attention: bitwise parity vs whole-sequence attention at the
same padded key extent, across ragged length mixes and block-boundary
lengths, plus clean block reuse (no stale-cache bleed) and the explicit
kernel dispatch (predicates, not exception handlers, pick the path)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import nn_ops
from paddle_tpu.ops.nn_ops import (flash_kernel_applies, fused_attention,
                                   paged_attention, paged_kernel_applies,
                                   paged_prefill_attention)

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


def test_decode_parity_ragged_mix():
    """Slots with wildly different context lengths in ONE batched call each
    match their own whole-sequence reference row bitwise."""
    rng = np.random.RandomState(0)
    lens = [1, 3, 7, 12, 16]          # ragged, includes min and max context
    k_pages, v_pages, tables, k_rows, v_rows = build_cache(
        rng, 64, [MAXBPS] * len(lens))
    q_rows = [rng.randn(H, E, D).astype('float32') for _ in lens]
    q = np.stack([qr[:, c - 1] for qr, c in zip(q_rows, lens)])
    out = np.asarray(paged_attention(q, k_pages, v_pages, tables,
                                     np.asarray(lens, np.int32),
                                     sm_scale=float(SCALE)))
    for i, c in enumerate(lens):
        ref = whole_seq_reference(q_rows[i], k_rows[i], v_rows[i])
        assert np.array_equal(out[i], ref[:, c - 1]), f'slot {i} (c={c})'


@pytest.mark.parametrize('c', [BS, BS + 1, 2 * BS - 1, 2 * BS, E])
def test_decode_parity_block_boundaries(c):
    """len % block_size ∈ {0, 1, block_size-1} and the full-table case."""
    rng = np.random.RandomState(c)
    k_pages, v_pages, tables, k_rows, v_rows = build_cache(rng, 16, [MAXBPS])
    q_rows = rng.randn(H, E, D).astype('float32')
    q = q_rows[:, c - 1][None]
    out = np.asarray(paged_attention(q, k_pages, v_pages, tables,
                                     np.asarray([c], np.int32),
                                     sm_scale=float(SCALE)))
    ref = whole_seq_reference(q_rows, k_rows[0], v_rows[0])
    assert np.array_equal(out[0], ref[:, c - 1])


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


# chip_smoke's serve geometry: 8 slots, 8 heads of 64, 16-token pages,
# 12 pages per sequence (128-token prompts + 64 new tokens)
_Q64, _POOL64 = _sds((8, 8, 64)), _sds((256, 16, 8 * 64))
_Q128, _POOL128 = _sds((8, 4, 128)), _sds((256, 16, 4 * 128))
_TABLES = _sds((8, 12), jnp.int32)


def test_dispatch_on_cpu_selects_xla():
    """Off the chip every predicate is false at every shape, including the
    ones the kernels accept on a TPU."""
    assert not flash_kernel_applies(_sds((1, 8, 128, 64)),
                                    _sds((1, 8, 128, 64)))
    assert not flash_kernel_applies(_sds((8, 12, 512, 64), jnp.bfloat16),
                                    _sds((8, 12, 512, 64), jnp.bfloat16))
    assert not paged_kernel_applies(_Q128, _POOL128, _TABLES, 4)


def test_dispatch_on_tpu_follows_the_kernels_own_rules(monkeypatch):
    """With a TPU backend the predicates encode what the stock kernels and
    Mosaic accept (established on a v5e, PERF.md "Bring-up") — a refused
    shape is XLA by predicate."""
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
    # paged: single-query, f32 pool, 128-lane head_dim, ppcb | pages/seq
    assert paged_kernel_applies(_Q128, _POOL128, _TABLES, 4)
    assert not paged_kernel_applies(_Q64, _POOL64, _TABLES, 4)  # head_dim 64
    assert not paged_kernel_applies(_Q128, _POOL128, _TABLES, 5)  # 12 % 5
    assert paged_kernel_applies(_Q128, _POOL128, _sds((8, 3), jnp.int32), 4)
    assert not paged_kernel_applies(_Q128, _sds((256, 16, 4 * 128),
                                               jnp.bfloat16), _TABLES, 4)
    # grouped queries: 4 query heads over a row of 2 KV heads, not of 3
    assert paged_kernel_applies(_Q128, _sds((256, 16, 2 * 128)), _TABLES, 4)
    assert not paged_kernel_applies(_Q128, _sds((256, 16, 3 * 128)),
                                    _TABLES, 4)
    assert not paged_kernel_applies(_sds((8, 4, 4, 128)), _POOL128,
                                    _TABLES, 4)       # multi-query verify


def _forbid_kernels(monkeypatch):
    """Any call into a stock pallas kernel fails the test."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    from jax.experimental.pallas.ops.tpu import paged_attention as pa

    def boom(*a, **k):
        raise AssertionError('pallas kernel called')
    monkeypatch.setattr(fa, 'flash_attention', boom)
    monkeypatch.setattr(pa, 'paged_attention', boom)


def test_refused_shapes_take_xla_without_touching_the_kernel(monkeypatch):
    """A shape the kernel's own rules refuse never reaches the kernel: with
    a (pretend) TPU backend the three ops run the XLA formulation and
    return what they return on the CPU — by predicate, not by exception."""
    rng = np.random.RandomState(3)
    k_pages, v_pages, tables, k_rows, v_rows = build_cache(rng, 16, [MAXBPS])
    q_rows = rng.randn(H, E, D).astype('float32')
    Lq = 8
    args_prefill = (q_rows[None, :, :Lq], k_rows[0][None, :, :Lq],
                    v_rows[0][None, :, :Lq], k_pages, v_pages, tables[:1])
    q1 = q_rows[:, 4][None]
    lens = np.asarray([5], np.int32)
    want_prefill = np.asarray(paged_prefill_attention(
        *args_prefill, sm_scale=float(SCALE)))
    want_decode = np.asarray(paged_attention(
        q1, k_pages, v_pages, tables, lens, sm_scale=float(SCALE)))
    want_fused = np.asarray(fused_attention(
        *args_prefill[:3], sm_scale=float(SCALE), causal=True))
    monkeypatch.setattr(nn_ops, 'on_tpu', lambda: True)
    _forbid_kernels(monkeypatch)
    assert np.array_equal(want_prefill, np.asarray(paged_prefill_attention(
        *args_prefill, sm_scale=float(SCALE))))
    assert np.array_equal(want_decode, np.asarray(paged_attention(
        q1, k_pages, v_pages, tables, lens, sm_scale=float(SCALE))))
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
    with pytest.raises(AssertionError, match='pallas kernel called'):
        paged_attention(np.zeros((1, 2, 128), 'float32'), pool, pool,
                        np.zeros((1, 4), np.int32),
                        np.asarray([3], np.int32), sm_scale=1.0)
