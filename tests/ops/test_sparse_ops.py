"""Unit tests for the sparse embedding fast path's building blocks
(paddle_tpu/ops/sparse_ops.py, docs/SPARSE.md): knobs, the nnz bucket
ladder, COO coalescing, the SparseRowsGrad accumulation algebra, the
rows-only update kernels vs their dense counterparts, and the per-row
quantization codec of the sparse push."""
import os

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import sparse_ops as sp
from paddle_tpu.ops.registry import get_op
from paddle_tpu.parallel import quant_collectives as qc


# ---------------------------------------------------------------------------
# knobs (strict parse)
# ---------------------------------------------------------------------------

def test_knob_strict_parse(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_SPARSE_GRAD', '2')
    with pytest.raises(ValueError, match='PADDLE_TPU_SPARSE_GRAD'):
        sp.sparse_grad_enabled()
    monkeypatch.setenv('PADDLE_TPU_SPARSE_GRAD', '0')
    assert sp.sparse_grad_enabled() is False
    monkeypatch.setenv('PADDLE_TPU_SPARSE_NNZ_BUCKET', 'abc')
    with pytest.raises(ValueError, match='PADDLE_TPU_SPARSE_NNZ_BUCKET'):
        sp.bucket_floor()
    monkeypatch.setenv('PADDLE_TPU_SPARSE_NNZ_BUCKET', '0')
    with pytest.raises(ValueError):
        sp.bucket_floor()
    monkeypatch.setenv('PADDLE_TPU_EMBED_OOB', 'warn')
    with pytest.raises(ValueError, match='PADDLE_TPU_EMBED_OOB'):
        sp.oob_policy()
    monkeypatch.setenv('PADDLE_TPU_EMBED_OOB', 'clip')
    assert sp.oob_policy() == 'clip'


def test_nnz_bucket_ladder(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_SPARSE_NNZ_BUCKET', '64')
    assert sp.nnz_bucket(1) == 64
    assert sp.nnz_bucket(64) == 64
    assert sp.nnz_bucket(65) == 128
    assert sp.nnz_bucket(4000) == 4096
    # ladder is powers-of-two multiples of the floor: bounded variants
    rungs = {sp.nnz_bucket(n) for n in range(1, 3000)}
    assert rungs == {64, 128, 256, 512, 1024, 2048, 4096}


# ---------------------------------------------------------------------------
# coalescing
# ---------------------------------------------------------------------------

def test_coalesce_dedups_and_pads():
    ids = jnp.asarray([3, 1, 3, 7, 1, 3], jnp.int32)
    vals = jnp.asarray(np.arange(12, dtype=np.float32).reshape(6, 2))
    rows, out = sp.coalesce_rows(ids, vals, vocab=10, bucket=8)
    rows, out = np.asarray(rows), np.asarray(out)
    assert rows.shape == (8,) and out.shape == (8, 2)
    # unique, sorted, padded with the vocab sentinel
    assert rows[:3].tolist() == [1, 3, 7]
    assert (rows[3:] == 10).all()
    # duplicate rows summed
    dense = np.zeros((10, 2), np.float32)
    np.add.at(dense, np.asarray(ids), np.asarray(vals))
    for r, v in zip(rows, out):
        if r < 10:
            assert np.allclose(v, dense[r])
    assert (out[3:] == 0).all()


def test_coalesce_clips_bad_ids_like_dense_gather():
    ids = jnp.asarray([-5, 99, 2], jnp.int32)   # vocab 10: clip to 0, 9
    vals = jnp.ones((3, 4), jnp.float32)
    rows, out = sp.coalesce_rows(ids, vals, vocab=10, bucket=4)
    rows = np.asarray(rows)
    assert set(rows[rows < 10].tolist()) == {0, 2, 9}


def test_scatter_drops_sentinel_rows():
    rows = jnp.asarray([1, 5, 10, 10], jnp.int32)   # 10 = pad sentinel
    vals = jnp.ones((4, 3), jnp.float32)
    p = jnp.zeros((10, 3), jnp.float32)
    out = np.asarray(sp.sparse_sgd(p, rows, vals, jnp.float32(1.0)))
    assert np.count_nonzero(out) == 6      # rows 1 and 5 only
    assert (out[1] == -1).all() and (out[5] == -1).all()


# ---------------------------------------------------------------------------
# SparseRowsGrad algebra
# ---------------------------------------------------------------------------

def _grad(ids, vals, vocab=20, dim=2, bucket=8):
    rows, out = sp.coalesce_rows(jnp.asarray(ids, jnp.int32),
                                 jnp.asarray(vals, jnp.float32),
                                 vocab, bucket=bucket)
    return sp.SparseRowsGrad(rows, out, vocab, dim)


def test_sparse_grad_add_sparse():
    g1 = _grad([1, 2], np.ones((2, 2)))
    g2 = _grad([2, 3], np.ones((2, 2)))
    s = g1 + g2
    assert isinstance(s, sp.SparseRowsGrad)
    dense = np.asarray(s.densify())
    assert np.allclose(dense[1], 1) and np.allclose(dense[2], 2) \
        and np.allclose(dense[3], 1)
    assert np.count_nonzero(dense) == 6


def test_sparse_grad_add_dense_densifies():
    g = _grad([0, 1], np.ones((2, 2)))
    d = jnp.full((20, 2), 0.5)
    for s in (g + d, d + g):       # __add__ and __radd__
        assert not isinstance(s, sp.SparseRowsGrad)
        s = np.asarray(s)
        assert np.allclose(s[0], 1.5) and np.allclose(s[5], 0.5)


def test_sparse_grad_shape_mismatch_raises():
    with pytest.raises(ValueError, match='cannot accumulate'):
        _grad([1], np.ones((1, 2)), vocab=20) \
            + _grad([1], np.ones((1, 2)), vocab=30)


def test_sparse_grad_is_pytree():
    import jax
    g = _grad([1, 2], np.ones((2, 2)))
    leaves = jax.tree_util.tree_leaves(g)
    assert len(leaves) == 2
    g2 = jax.tree_util.tree_map(lambda x: x, g)
    assert isinstance(g2, sp.SparseRowsGrad)
    assert (g2.vocab, g2.dim) == (20, 2)


# ---------------------------------------------------------------------------
# rows-only updates vs the dense kernels (touched rows identical,
# untouched rows frozen)
# ---------------------------------------------------------------------------

def _coo(ids, vocab, dim, rng):
    vals = rng.randn(len(ids), dim).astype(np.float32)
    dense = np.zeros((vocab, dim), np.float32)
    np.add.at(dense, np.asarray(ids), vals)
    rows, cvals = sp.coalesce_rows(jnp.asarray(ids, jnp.int32),
                                   jnp.asarray(vals), vocab, bucket=8)
    return rows, cvals, dense


def test_sparse_sgd_matches_dense_on_touched_rows():
    rng = np.random.RandomState(0)
    V, D = 12, 4
    p = rng.randn(V, D).astype(np.float32)
    rows, vals, dense_g = _coo([2, 5, 2], V, D, rng)
    ref = np.asarray(get_op('sgd').fn(p, dense_g, 0.1))
    out = np.asarray(sp.sparse_sgd(p, rows, vals, 0.1))
    assert np.allclose(out, ref, atol=1e-6)


def test_sparse_adagrad_matches_dense():
    rng = np.random.RandomState(1)
    V, D = 12, 4
    p = rng.randn(V, D).astype(np.float32)
    m = np.abs(rng.randn(V, D)).astype(np.float32)
    rows, vals, dense_g = _coo([0, 3, 3, 11], V, D, rng)
    ref_p, ref_m = get_op('adagrad').fn(p, dense_g, m, 0.1)
    out_p, out_m = sp.sparse_adagrad(p, rows, vals, m, 0.1)
    # dense adagrad with a zero grad leaves a row unchanged → full parity
    assert np.allclose(np.asarray(out_p), np.asarray(ref_p), atol=1e-6)
    assert np.allclose(np.asarray(out_m), np.asarray(ref_m), atol=1e-6)


def test_sparse_momentum_touched_rows_and_lazy_untouched():
    rng = np.random.RandomState(2)
    V, D = 10, 3
    p = rng.randn(V, D).astype(np.float32)
    vel = rng.randn(V, D).astype(np.float32)
    rows, vals, dense_g = _coo([1, 4], V, D, rng)
    ref_p, ref_v = get_op('momentum').fn(p, dense_g, vel, 0.1, mu=0.9)
    out_p, out_v = sp.sparse_momentum(p, rows, vals, vel, 0.1, mu=0.9)
    for r in (1, 4):
        assert np.allclose(np.asarray(out_p)[r], np.asarray(ref_p)[r],
                           atol=1e-6)
        assert np.allclose(np.asarray(out_v)[r], np.asarray(ref_v)[r],
                           atol=1e-6)
    # LAZY: untouched rows keep param AND velocity frozen (dense decays)
    untouched = [r for r in range(V) if r not in (1, 4)]
    assert np.allclose(np.asarray(out_p)[untouched], p[untouched])
    assert np.allclose(np.asarray(out_v)[untouched], vel[untouched])


def test_sparse_adam_lazy_semantics():
    rng = np.random.RandomState(3)
    V, D = 10, 3
    p = rng.randn(V, D).astype(np.float32)
    m1 = np.zeros((V, D), np.float32)
    m2 = np.zeros((V, D), np.float32)
    b1p = np.full((1,), 0.9, np.float32)
    b2p = np.full((1,), 0.999, np.float32)
    rows, vals, dense_g = _coo([7, 2], V, D, rng)
    ref = get_op('adam').fn(p, dense_g, m1, m2, b1p, b2p, 0.01)
    out = sp.sparse_adam(p, rows, vals, m1, m2, b1p, b2p, 0.01)
    for r in (2, 7):
        assert np.allclose(np.asarray(out[0])[r], np.asarray(ref[0])[r],
                           atol=1e-6)
    # beta powers advance globally, same as dense
    assert np.allclose(np.asarray(out[3]), np.asarray(ref[3]))
    assert np.allclose(np.asarray(out[4]), np.asarray(ref[4]))
    untouched = [r for r in range(V) if r not in (2, 7)]
    assert np.allclose(np.asarray(out[0])[untouched], p[untouched])


# ---------------------------------------------------------------------------
# per-row quantization codec + wire accounting (the sparse push)
# ---------------------------------------------------------------------------

def test_rowwise_quant_roundtrip_bound():
    rng = np.random.RandomState(4)
    v = rng.randn(32, 16).astype(np.float32) * 10
    q, s = qc.rowwise_quantize(jnp.asarray(v))
    rt = np.asarray(qc.rowwise_dequantize(q, s))
    # symmetric int8: error bounded by scale/2 = absmax/254 per row
    bound = np.abs(v).max(axis=1, keepdims=True) / 254.0 + 1e-7
    assert (np.abs(rt - v) <= bound).all()


def test_rowwise_quant_zero_rows_exact():
    v = jnp.zeros((4, 8), jnp.float32)
    q, s = qc.rowwise_quantize(v)
    assert (np.asarray(s) == 0).all()
    assert (np.asarray(qc.rowwise_dequantize(q, s)) == 0).all()


def test_sparse_wire_bytes_arithmetic():
    # 4096 rows × 64 dims, 8 replicas
    f32 = qc.sparse_wire_bytes(4096, 64, 'f32', 8)
    bf16 = qc.sparse_wire_bytes(4096, 64, 'bf16', 8)
    i8 = qc.sparse_wire_bytes(4096, 64, 'int8', 8)
    assert f32 == 4096 * 4 + 4096 * 64 * 4
    assert bf16 == 4096 * 4 + 4096 * 64 * 2
    assert i8 == 4096 * 4 + 4096 * 64 + 4096 * 4
    assert qc.sparse_wire_bytes(4096, 64, 'int8', 1) == 0
    # the acceptance ratios: a 1M x 64 table against its 4096-row push
    dense = qc.wire_bytes(1_000_000 * 64, 'f32', 8)
    assert dense / i8 > 100
    assert f32 / i8 >= 3.5


def test_record_sparse_lookup_metrics():
    from paddle_tpu.observability import registry
    before = sp.sparse_metrics_snapshot()
    sp.record_sparse_lookup(100, 128, dedup_rows=50, table='t0')
    after = sp.sparse_metrics_snapshot()
    assert after['sparse_lookup_ids_total'] - \
        before['sparse_lookup_ids_total'] == 100
    assert after['sparse_grad_rows_total'] - \
        before['sparse_grad_rows_total'] == 128
    g = registry.gauge('sparse_dedup_ratio', '')
    assert g.labels(table='t0').value == pytest.approx(2.0)
