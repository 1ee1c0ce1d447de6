"""End-to-end sparse embedding fast path (docs/SPARSE.md): sparse-vs-
dense parity on both spines (dygraph tape + static executor), the DeepFM
recipe, vocab-sharded tables on a CPU mesh, the quantized sparse push,
OOB-id validation, and the escape hatches."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers as L
import paddle_tpu.dygraph as dygraph
from paddle_tpu.dygraph import Embedding, Linear, to_variable
from paddle_tpu.dygraph.tape import dispatch_op, Tensor
from paddle_tpu.core.random import default_generator
from paddle_tpu.ops import sparse_ops as sp


def _dy_mlp_losses(is_sparse, opt_name, steps=4, vary_ids=True, seed=11):
    """Embedding-MLP dygraph run; returns (losses, final table)."""
    with dygraph.guard():
        default_generator.seed(seed)
        emb = Embedding([60, 8], is_sparse=is_sparse)
        fc = Linear(8, 4)
        params = emb.parameters() + fc.parameters()
        opt = {'sgd': lambda: fluid.optimizer.SGD(0.1,
                                                  parameter_list=params),
               'adam': lambda: fluid.optimizer.Adam(
                   0.01, parameter_list=params),
               'adagrad': lambda: fluid.optimizer.Adagrad(
                   0.05, parameter_list=params),
               'momentum': lambda: fluid.optimizer.MomentumOptimizer(
                   0.05, parameter_list=params)}[opt_name]()
        rng = np.random.RandomState(3)
        losses = []
        for i in range(steps):
            ids = rng.randint(0, 60, (4, 3)) if vary_ids \
                else np.array([[1, 2, 3], [3, 4, 1]])
            x = emb(to_variable(ids.astype(np.int64)))
            y = fc(x)
            loss = dispatch_op('reduce_mean', {'x': y * y}, {})
            loss.backward()
            opt.minimize(loss)
            opt.clear_gradients()
            losses.append(float(loss.numpy()))
        return losses, np.asarray(emb.weight.value)


@pytest.mark.parametrize('opt_name', ['sgd', 'adagrad'])
def test_dygraph_parity_varying_ids(opt_name):
    """SGD/Adagrad: a zero dense gradient is an exact no-op, so rows-only
    updates must reproduce the dense trajectory even when every batch
    touches a different id set."""
    ld, wd = _dy_mlp_losses(False, opt_name)
    ls, ws = _dy_mlp_losses(True, opt_name)
    assert np.allclose(ld, ls, atol=1e-6), (ld, ls)
    assert np.allclose(wd, ws, atol=1e-6)


@pytest.mark.parametrize('opt_name', ['adam', 'momentum'])
def test_dygraph_parity_fixed_ids(opt_name):
    """Adam/momentum carry per-row state that dense updates decay even
    for untouched rows; with a FIXED id set the lazy rows-only update is
    exactly the dense one."""
    ld, wd = _dy_mlp_losses(False, opt_name, vary_ids=False)
    ls, ws = _dy_mlp_losses(True, opt_name, vary_ids=False)
    assert np.allclose(ld, ls, atol=1e-6)
    assert np.allclose(wd, ws, atol=1e-5)


def test_dygraph_grad_is_rows_only():
    with dygraph.guard():
        default_generator.seed(1)
        emb = Embedding([40, 4], is_sparse=True)
        out = emb(to_variable(np.array([[1, 2, 2]], np.int64)))
        loss = dispatch_op('reduce_sum', {'x': out}, {})
        loss.backward()
        g = emb.weight.grad
        assert isinstance(g, sp.SparseRowsGrad)
        assert g.nnz == sp.nnz_bucket(3)
        rows = np.asarray(g.rows)
        assert set(rows[rows < 40].tolist()) == {1, 2}
        # gradient() API densifies for user code
        dense = emb.weight.gradient()
        assert dense.shape == (40, 4)
        assert np.allclose(dense[2], 2.0) and np.allclose(dense[1], 1.0)


def test_dygraph_knob_off_restores_dense(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_SPARSE_GRAD', '0')
    with dygraph.guard():
        default_generator.seed(1)
        emb = Embedding([40, 4], is_sparse=True)
        out = emb(to_variable(np.array([[1, 2]], np.int64)))
        dispatch_op('reduce_sum', {'x': out}, {}).backward()
        assert not isinstance(emb.weight.grad, sp.SparseRowsGrad)


def test_dygraph_padding_idx_rows_get_zero_grad():
    with dygraph.guard():
        default_generator.seed(1)
        emb = Embedding([40, 4], is_sparse=True, padding_idx=2)
        out = emb(to_variable(np.array([[1, 2, 3]], np.int64)))
        dispatch_op('reduce_sum', {'x': out}, {}).backward()
        dense = emb.weight.gradient()
        assert np.allclose(dense[2], 0.0)
        assert np.allclose(dense[1], 1.0) and np.allclose(dense[3], 1.0)


def test_unsupported_sparse_optimizer_raises():
    with dygraph.guard():
        default_generator.seed(1)
        emb = Embedding([40, 4], is_sparse=True)
        opt = fluid.optimizer.AdadeltaOptimizer(
            parameter_list=emb.parameters())
        out = emb(to_variable(np.array([[1]], np.int64)))
        dispatch_op('reduce_sum', {'x': out}, {}).backward()
        with pytest.raises(ValueError, match='sparse'):
            opt.minimize(out)


def test_rows_only_step_equals_dense_scatter_on_a_large_table():
    """One embedding train step as two jitted programs with the table
    donated, repeated on a table far larger than the batch (V=100k, 512 ids
    with duplicates): the dense path scatter-adds a V x D gradient and
    updates all of it, the rows-only path coalesces the per-occurrence
    cotangent and applies it to the touched rows; the tables agree up to
    the f32 order of the duplicate-id sums."""
    vocab, dim, nnz, lr = 100_000, 32, 512, jnp.float32(0.05)
    rng = np.random.RandomState(1)
    w0 = rng.randn(vocab, dim).astype(np.float32)
    ids = jnp.asarray(rng.randint(0, vocab // 100, (nnz,)).astype(np.int32))
    tgt = jnp.asarray(rng.randn(nnz, dim).astype(np.float32))
    bucket = sp.nnz_bucket(nnz)

    def dense_step(w, ids_, tgt_):
        g = jax.grad(lambda w_: jnp.sum(jnp.take(w_, ids_, axis=0) * tgt_))(w)
        return w - lr * g

    def sparse_step(w, ids_, tgt_):
        # the same loss's per-occurrence cotangent is tgt_ itself
        rows, vals = sp.coalesce_rows(ids_, tgt_, vocab, bucket=bucket)
        return sp.sparse_sgd(w, rows, vals, lr)

    tables = []
    for step in (dense_step, sparse_step):
        fn = jax.jit(step, donate_argnums=(0,))
        w = jnp.asarray(w0)
        for _ in range(4):
            w = fn(w, ids, tgt)
        tables.append(np.asarray(w))
    assert len(np.unique(np.asarray(ids))) < nnz        # duplicates summed
    assert not np.array_equal(tables[0], w0)
    assert np.allclose(tables[0], tables[1], atol=1e-4)


# ---------------------------------------------------------------------------
# static spine
# ---------------------------------------------------------------------------

def _static_run(is_sparse, opt_name='sgd', steps=5, deepfm=False, V=200):
    import paddle_tpu.core.scope as sm
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.core import unique_name
    # fresh name counter per run so the sparse and dense builds declare
    # identical var names (the fixture only resets between tests)
    unique_name.generator = unique_name.UniqueNameGenerator()
    default_generator.seed(42)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        if deepfm:
            ids = L.data('ids', [6], dtype='int64')
            vals = L.data('vals', [6], dtype='float32')
            label = L.data('label', [1], dtype='float32')
            w1 = L.embedding(ids, size=[V, 1], is_sparse=is_sparse)
            emb = L.embedding(ids, size=[V, 8], is_sparse=is_sparse)
            v3 = L.unsqueeze(vals, axes=[2])
            first = L.reduce_sum(w1 * v3, dim=1)
            e = emb * v3
            sum_sq = L.square(L.reduce_sum(e, dim=1))
            sq_sum = L.reduce_sum(L.square(e), dim=1)
            second = 0.5 * L.reduce_sum(sum_sq - sq_sum, dim=1,
                                        keep_dim=True)
            deep = L.fc(e, size=16, act='relu')
            logit = L.fc(L.concat([first, second, deep], axis=1), size=1)
            loss = L.reduce_mean(
                L.sigmoid_cross_entropy_with_logits(logit, label))
        else:
            ids = L.data('ids', [5], dtype='int64')
            label = L.data('label', [1], dtype='float32')
            emb = L.embedding(ids, size=[V, 16], is_sparse=is_sparse)
            h = L.fc(emb, size=8, act='relu')
            out = L.fc(h, size=1)
            loss = L.reduce_mean(L.square_error_cost(out, label))
        {'sgd': lambda: fluid.optimizer.SGD(0.1),
         'adagrad': lambda: fluid.optimizer.Adagrad(0.05),
         'adam': lambda: fluid.optimizer.Adam(0.01)}[opt_name]() \
            .minimize(loss)
    exe = fluid.Executor()
    old = sm._global_scope
    sm._global_scope = Scope()
    try:
        exe.run(startup)
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(steps):
            f = {'ids': rng.randint(0, V, (4, 6 if deepfm else 5))
                 .astype(np.int64),
                 'label': rng.rand(4, 1).astype(np.float32)}
            if deepfm:
                f['vals'] = rng.rand(4, 6).astype(np.float32)
            l, = exe.run(main, feed=f, fetch_list=[loss])
            losses.append(float(l))
        tables = {v.name: np.asarray(sm._global_scope.find(v.name))
                  for v in main.all_parameters()
                  if len(v.shape) == 2 and v.shape[0] == V}
        return losses, tables, main
    finally:
        sm._global_scope = old


@pytest.mark.parametrize('V', [200, 2000])
@pytest.mark.parametrize('opt_name', ['sgd', 'adagrad'])
def test_static_parity_embedding_mlp(opt_name, V):
    ld, td, _ = _static_run(False, opt_name, V=V)
    ls, ts, _ = _static_run(True, opt_name, V=V)
    assert np.allclose(ld, ls, atol=1e-5), (ld, ls)
    for name in td:
        assert np.allclose(td[name], ts[name], atol=1e-5)


def test_static_parity_deepfm():
    ld, td, _ = _static_run(False, 'adagrad', deepfm=True)
    ls, ts, main = _static_run(True, 'adagrad', deepfm=True)
    assert np.allclose(ld, ls, atol=1e-5), (ld, ls)
    for name in td:
        assert np.allclose(td[name], ts[name], atol=1e-5)
    # the program really took the sparse path: marker carries the COO
    # outputs and sparse_* update ops exist
    blk = main.global_block()
    types = {op.type for op in blk.ops}
    assert 'sparse_adagrad' in types
    marker = next(op for op in blk.ops if op.type == '__backward__')
    assert len(marker.attrs['sparse_params']) == 2
    assert len(marker.outputs['SparseRows']) == 2


def test_static_dense_reader_falls_back():
    """A table ALSO read by a dense op (weight tying) must keep the
    dense gradient path — sparsifying would drop the dense use's
    contribution."""
    default_generator.seed(7)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('ids', [3], dtype='int64')
        emb = L.embedding(ids, size=[30, 8], is_sparse=True)
        h = L.reduce_sum(emb, dim=1)
        w = main.global_block().var(
            [v.name for v in main.all_parameters()][0])
        tied = L.matmul(h, w, transpose_y=True)     # dense reuse
        loss = L.reduce_mean(tied)
        fluid.optimizer.SGD(0.1).minimize(loss)
    marker = next(op for op in main.global_block().ops
                  if op.type == '__backward__')
    assert not marker.attrs.get('sparse_params')
    assert w.name in marker.attrs['params']


def test_static_metrics_recorded():
    from paddle_tpu.ops.sparse_ops import sparse_metrics_snapshot
    before = sparse_metrics_snapshot()
    _static_run(True, 'sgd', steps=3)
    after = sparse_metrics_snapshot()
    assert after['sparse_lookup_ids_total'] > \
        before['sparse_lookup_ids_total']
    assert after['sparse_grad_rows_total'] > \
        before['sparse_grad_rows_total']


def test_static_knob_off_keeps_dense_marker(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_SPARSE_GRAD', '0')
    _, _, main = _static_run(True, 'sgd', steps=1)
    marker = next(op for op in main.global_block().ops
                  if op.type == '__backward__')
    assert not marker.attrs.get('sparse_params')


def test_gradient_merge_rejects_sparse():
    default_generator.seed(7)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('ids', [3], dtype='int64')
        emb = L.embedding(ids, size=[30, 8], is_sparse=True)
        loss = L.reduce_mean(emb)
        opt = fluid.optimizer.GradientMergeOptimizer(
            fluid.optimizer.SGD(0.1), k_steps=2)
        with pytest.raises(RuntimeError, match='sparse'):
            opt.minimize(loss)


def test_eval_clone_of_sparse_program_runs():
    """clone(for_test=True) drops the marker; the stamped lookup ops must
    run as plain dense gathers outside a sparse trace."""
    import paddle_tpu.core.scope as sm
    from paddle_tpu.core.scope import Scope
    default_generator.seed(5)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('ids', [4], dtype='int64')
        emb = L.embedding(ids, size=[50, 8], is_sparse=True)
        out = L.reduce_sum(emb, dim=[1, 2])
        loss = L.reduce_mean(out)
        fluid.optimizer.SGD(0.1).minimize(loss)
    test_prog = main.clone(for_test=True)
    exe = fluid.Executor()
    old = sm._global_scope
    sm._global_scope = Scope()
    try:
        exe.run(startup)
        f = {'ids': np.array([[1, 2, 3, 4]], np.int64)}
        # eval FIRST: the train step updates the table in place, and the
        # train fetch observes the pre-update forward
        eval_out, = exe.run(test_prog, feed=f, fetch_list=[out])
        train_out, = exe.run(main, feed=dict(
            f, label=np.ones((1, 1), np.float32)), fetch_list=[out])
        assert np.array_equal(train_out, eval_out)
    finally:
        sm._global_scope = old


# ---------------------------------------------------------------------------
# serving validate() OOB satellite
# ---------------------------------------------------------------------------

def test_serving_validate_rejects_oob_ids(tmp_path, monkeypatch):
    import paddle_tpu.core.scope as sm
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.serving import InferenceEngine, InvalidRequest
    default_generator.seed(5)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('ids', [4], dtype='int64')
        emb = L.embedding(ids, size=[50, 8])
        out = L.reduce_sum(emb, dim=[1, 2])
    exe = fluid.Executor()
    old = sm._global_scope
    sm._global_scope = Scope()
    try:
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ['ids'], [out], exe,
                                      main_program=main)
    finally:
        sm._global_scope = old
    eng = InferenceEngine(str(tmp_path), max_batch_size=4)
    assert 'ids' in eng.id_bounds and eng.id_bounds['ids'][0] == 50
    ok, _ = eng.validate({'ids': np.array([[0, 1, 2, 49]], np.int64)})
    assert ok['ids'].shape == (1, 4)
    with pytest.raises(InvalidRequest, match='outside'):
        eng.validate({'ids': np.array([[0, 1, 2, 55]], np.int64)})
    with pytest.raises(InvalidRequest, match='outside'):
        eng.validate({'ids': np.array([[-1, 1, 2, 3]], np.int64)})
    monkeypatch.setenv('PADDLE_TPU_EMBED_OOB', 'clip')   # escape hatch
    ok, _ = eng.validate({'ids': np.array([[0, 1, 2, 55]], np.int64)})
    assert ok['ids'].shape == (1, 4)


def test_executor_full_verify_rejects_oob(monkeypatch):
    import paddle_tpu.core.scope as sm
    from paddle_tpu.core.scope import Scope
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'full')
    default_generator.seed(5)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('ids', [3], dtype='int64')
        emb = L.embedding(ids, size=[20, 4], is_sparse=True)
        loss = L.reduce_mean(emb)
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    old = sm._global_scope
    sm._global_scope = Scope()
    try:
        exe.run(startup)
        exe.run(main, feed={'ids': np.array([[1, 2, 3]], np.int64)},
                fetch_list=[loss])
        with pytest.raises(ValueError, match='outside'):
            exe.run(main, feed={'ids': np.array([[1, 2, 30]], np.int64)},
                    fetch_list=[loss])
        monkeypatch.setenv('PADDLE_TPU_EMBED_OOB', 'clip')
        exe.run(main, feed={'ids': np.array([[1, 2, 30]], np.int64)},
                fetch_list=[loss])
    finally:
        sm._global_scope = old


# ---------------------------------------------------------------------------
# vocab-sharded tables (CPU mesh)
# ---------------------------------------------------------------------------

@pytest.fixture
def mesh8():
    from paddle_tpu.partition import make_mesh
    if len(jax.devices()) < 8:
        pytest.skip('needs 8 devices')
    return make_mesh


def test_sharded_lookup_bitwise(mesh8):
    from paddle_tpu.partition.sparse import VocabShardedTable
    rng = np.random.RandomState(0)
    V, D = 64, 8
    init = rng.randn(V, D).astype(np.float32)
    t = VocabShardedTable(V, D, mesh8({'tp': 4}), axis='tp', init=init)
    for n in (1, 7, 16, 33):
        ids = rng.randint(0, V, (n,)).astype(np.int64)
        assert np.array_equal(np.asarray(t.lookup(ids)), init[ids])
    # 2-D id batches keep their shape
    ids2 = rng.randint(0, V, (3, 5)).astype(np.int64)
    out = np.asarray(t.lookup(ids2))
    assert out.shape == (3, 5, D)
    assert np.array_equal(out, init[ids2])


def test_sharded_push_parity_vs_dense(mesh8):
    from paddle_tpu.partition.sparse import VocabShardedTable
    rng = np.random.RandomState(1)
    V, D = 64, 8
    init = rng.randn(V, D).astype(np.float32)
    ids = rng.randint(0, V, (13,))
    vals = rng.randn(13, D).astype(np.float32)
    rows, cvals = sp.coalesce_rows(jnp.asarray(ids, jnp.int32),
                                   jnp.asarray(vals), V)
    dense = np.zeros((V, D), np.float32)
    r_, v_ = np.asarray(rows), np.asarray(cvals)
    np.add.at(dense, r_[r_ < V], v_[r_ < V])
    t = VocabShardedTable(V, D, mesh8({'tp': 4}), axis='tp', init=init)
    t.sgd_push(rows, cvals, 0.1)
    assert np.allclose(t.full_table(), init - 0.1 * dense, atol=1e-6)


def test_sharded_dp_push_f32_exact_int8_bounded(mesh8):
    from paddle_tpu.partition.sparse import VocabShardedTable
    rng = np.random.RandomState(2)
    V, D = 64, 8
    init = rng.randn(V, D).astype(np.float32)
    mesh = mesh8({'dp': 2, 'tp': 4})
    per_replica = []
    dense = np.zeros((V, D), np.float32)
    for _ in range(2):
        ids = rng.randint(0, V, (8,))
        vals = rng.randn(8, D).astype(np.float32)
        r, v = sp.coalesce_rows(jnp.asarray(ids, jnp.int32),
                                jnp.asarray(vals), V, bucket=8)
        per_replica.append((r, v))
        r_, v_ = np.asarray(r), np.asarray(v)
        np.add.at(dense, r_[r_ < V], v_[r_ < V])
    rows_st = jnp.concatenate([r for r, _ in per_replica])
    vals_st = jnp.concatenate([v for _, v in per_replica])
    ref = init - 0.1 * dense
    t = VocabShardedTable(V, D, mesh, axis='tp', init=init)
    t.sgd_push(rows_st, vals_st, 0.1, dp_axis='dp', comm_dtype='f32')
    assert np.allclose(t.full_table(), ref, atol=1e-6)
    t8 = VocabShardedTable(V, D, mesh, axis='tp', init=init)
    t8.sgd_push(rows_st, vals_st, 0.1, dp_axis='dp', comm_dtype='int8')
    err = np.abs(t8.full_table() - ref).max()
    bound = 0.1 * 2 * np.abs(vals_st).max() / 127.0 + 1e-6
    assert 0 < err <= bound


def test_sharded_table_strict_errors(mesh8):
    from paddle_tpu.partition.sparse import VocabShardedTable
    with pytest.raises(ValueError, match='divisible'):
        VocabShardedTable(63, 4, mesh8({'tp': 4}), axis='tp')
    with pytest.raises(ValueError, match='no axis'):
        VocabShardedTable(64, 4, mesh8({'tp': 4}), axis='fsdp')
