"""Block-quantized collectives (parallel/quant_collectives.py): codec
round-trip error bounds (all-zero / single-element / tail cases), EQuARX
two-phase all-reduce vs the exact psum on the 8-device CPU mesh, the
f32 passthrough's bitwise exactness, comm-dtype strict parsing, the
wired sync points (LocalSGD / geo-SGD / FSDP / dygraph bundles), and the
bytes-on-wire telemetry (the ≥3.5x int8 acceptance at the counter level).
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.core import compat
from paddle_tpu.parallel import quant_collectives as qc
from paddle_tpu.parallel.mesh import make_mesh


@pytest.fixture
def mesh8():
    return make_mesh({'dp': 8})


def _allreduce(X, mesh, comm, block_size=None, op='sum'):
    """Row i of X = device i's local value; returns the replicated result."""
    fn = qc.qallreduce_sum if op == 'sum' else qc.qallreduce_mean

    def body(v):
        return fn(v[0], 'dp', comm_dtype=comm, block_size=block_size)[None]

    return np.asarray(compat.shard_map(
        body, mesh=mesh, in_specs=P('dp'), out_specs=P('dp'))(
        jnp.asarray(X)))


# ---------------------------------------------------------------------------
# strict parsing
# ---------------------------------------------------------------------------

def test_comm_dtype_strict_parse(monkeypatch):
    monkeypatch.delenv(qc.ENV_COMM_DTYPE, raising=False)
    assert qc.resolve_comm_dtype() == 'f32'
    assert qc.resolve_comm_dtype('int8') == 'int8'
    with pytest.raises(ValueError) as e:
        qc.resolve_comm_dtype('int4')
    for name in qc.SUPPORTED_COMM_DTYPES:
        assert name in str(e.value)            # message lists the set
    # env wins over the argument, and parses strictly too
    monkeypatch.setenv(qc.ENV_COMM_DTYPE, 'bf16')
    assert qc.resolve_comm_dtype('int8') == 'bf16'
    monkeypatch.setenv(qc.ENV_COMM_DTYPE, 'fp8')
    with pytest.raises(ValueError, match='PADDLE_TPU_COMM_DTYPE'):
        qc.resolve_comm_dtype()


def test_distributed_strategy_comm_dtype_strict():
    from paddle_tpu.parallel import DistributedStrategy
    s = DistributedStrategy()
    assert s.comm_dtype == 'f32'
    s.comm_dtype = 'int8'
    assert s.comm_dtype == 'int8'
    with pytest.raises(ValueError) as e:
        s.comm_dtype = 'float16'
    assert 'int8' in str(e.value) and 'bf16' in str(e.value)


# ---------------------------------------------------------------------------
# codec round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('size', [1, 17, 255, 256, 257, 4097])
def test_block_roundtrip_error_bound(size):
    """Per-block bound of the symmetric round-to-nearest codec: every
    element's round-trip error <= its block's absmax/254. Sizes cover the
    single-element and non-multiple-of-block-size tails."""
    rng = np.random.RandomState(size)
    bs = 64
    x = (rng.randn(size) * rng.uniform(0.1, 100)).astype('float32')
    q, s = qc.block_quantize(x, block_size=bs)
    rt = np.asarray(qc.block_dequantize(q, s, shape=(size,), block_size=bs))
    padded = -(-size // bs) * bs
    blocks = np.pad(x, (0, padded - size)).reshape(-1, bs)
    bound = np.repeat(np.abs(blocks).max(1) / 254.0, bs)[:size]
    assert np.all(np.abs(rt - x) <= bound * (1 + 1e-6) + 1e-30)


def test_block_roundtrip_exact_cases():
    # all-zero: scale 0 decodes to exact zeros (no 0/0)
    q, s = qc.block_quantize(np.zeros(300, np.float32), block_size=128)
    assert np.all(np.asarray(s) == 0)
    assert np.all(np.asarray(
        qc.block_dequantize(q, s, shape=(300,), block_size=128)) == 0)
    # single element: its own absmax maps to exactly +/-127
    for v in (3.7, -0.001, 1e-20):
        q, s = qc.block_quantize(np.asarray([v], np.float32))
        rt = qc.block_dequantize(q, s, shape=(1,))
        np.testing.assert_allclose(np.asarray(rt), [np.float32(v)],
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# two-phase all-reduce on the 8-device mesh
# ---------------------------------------------------------------------------

def test_qallreduce_f32_passthrough_bitwise(mesh8):
    rng = np.random.RandomState(0)
    X = rng.randn(8, 1000).astype('float32')

    def psum_body(v):
        return lax.psum(v[0], 'dp')[None]

    want = np.asarray(compat.shard_map(
        psum_body, mesh=mesh8, in_specs=P('dp'), out_specs=P('dp'))(
        jnp.asarray(X)))
    got = _allreduce(X, mesh8, 'f32')
    assert np.array_equal(got, want)            # bitwise, not approximate


@pytest.mark.parametrize('size', [1, 130, 1000, 2048])
def test_qallreduce_int8_error_bound(mesh8, size):
    """Error contract: two codec stages around an exact f32 partial sum —
    elementwise error <= sum_i absmax_i/254 + absmax_reduced/254 (using
    the loose global-absmax form of the per-block bound)."""
    rng = np.random.RandomState(size)
    X = (rng.randn(8, size) * rng.uniform(0.5, 5, (8, 1))).astype('float32')
    want = X.sum(0)
    got = _allreduce(X, mesh8, 'int8')
    assert got.shape == (8, size)
    for i in range(8):                           # replicated result
        assert np.array_equal(got[i], got[0])
    bound = (np.abs(X).max(axis=1).sum() + np.abs(want).max()) / 254.0
    err = np.abs(got[0] - want).max()
    assert err <= bound * (1 + 1e-6), (err, bound)
    if size >= 1000:
        assert err / np.abs(want).max() < 0.02   # quality, not just bound


def test_qallreduce_all_zero_and_mean(mesh8):
    Z = np.zeros((8, 513), np.float32)
    assert np.all(_allreduce(Z, mesh8, 'int8') == 0)
    rng = np.random.RandomState(1)
    X = rng.randn(8, 512).astype('float32')
    got = _allreduce(X, mesh8, 'int8', op='mean')
    err = np.abs(got[0] - X.mean(0)).max()
    assert err < np.abs(X.mean(0)).max() * 0.1 + 0.05


def test_qallreduce_bf16(mesh8):
    rng = np.random.RandomState(2)
    X = rng.randn(8, 700).astype('float32')
    got = _allreduce(X, mesh8, 'bf16')
    want = X.sum(0)
    # bf16 has ~8 mantissa bits: relative error ~2^-8 per codec pass
    assert np.abs(got[0] - want).max() <= np.abs(X).max() * 8 * 2 ** -7


def test_qreduce_scatter_matches_psum_scatter(mesh8):
    rng = np.random.RandomState(3)
    X = rng.randn(8, 16, 24).astype('float32')

    def f32_body(v):
        return qc.qreduce_scatter_sum(v[0], 'dp', comm_dtype='f32',
                                      scattered_dimension=1)[None]

    def ref_body(v):
        return lax.psum_scatter(v[0], 'dp', scatter_dimension=1,
                                tiled=True)[None]

    for body in (f32_body,):
        got = np.asarray(compat.shard_map(
            body, mesh=mesh8, in_specs=P('dp'), out_specs=P('dp'))(
            jnp.asarray(X)))
        want = np.asarray(compat.shard_map(
            ref_body, mesh=mesh8, in_specs=P('dp'), out_specs=P('dp'))(
            jnp.asarray(X)))
        assert np.array_equal(got, want)         # exact passthrough

    def int8_body(v):
        return qc.qreduce_scatter_sum(v[0], 'dp', comm_dtype='int8',
                                      scattered_dimension=1)[None]

    got = np.asarray(compat.shard_map(
        int8_body, mesh=mesh8, in_specs=P('dp'), out_specs=P('dp'))(
        jnp.asarray(X)))
    full = X.sum(0)                              # (16, 24)
    for d in range(8):       # device d holds tile d of the scattered dim
        tile = full[:, d * 3:(d + 1) * 3]
        err = np.abs(got[d] - tile).max()
        assert err <= (np.abs(X).max() * 8 / 254.0) * (1 + 1e-6)


def test_qreduce_scatter_indivisible_raises(mesh8):
    def body(v):
        return qc.qreduce_scatter_sum(v[0], 'dp', comm_dtype='int8')[None]

    with pytest.raises(ValueError, match='not divisible'):
        compat.shard_map(body, mesh=mesh8, in_specs=P('dp'),
                         out_specs=P('dp'))(jnp.ones((8, 9, 4)))


# ---------------------------------------------------------------------------
# wired sync points
# ---------------------------------------------------------------------------

def test_fsdp_reduce_scatter_grads():
    from paddle_tpu.parallel.fsdp import (param_shard_bytes,
                                          reduce_scatter_grads)
    mesh = make_mesh({'fsdp': 8})
    rng = np.random.RandomState(0)
    g = {'w1': rng.randn(8, 16, 24).astype('float32'),
         'bias': rng.randn(8, 5).astype('float32')}   # 5: replicated path
    for comm, tol in (('f32', 0.0), ('int8', None)):
        out = reduce_scatter_grads(g, mesh, comm_dtype=comm)
        assert np.asarray(out['w1']).shape == (16, 24)
        assert np.asarray(out['bias']).shape == (5,)
        # the sharded output holds 1/8 of the bytes per device
        assert param_shard_bytes(out['w1']) * 8 == 16 * 24 * 4
        for name in g:
            want = g[name].sum(0)
            err = np.abs(np.asarray(out[name]) - want).max()
            bound = (np.abs(g[name]).max() * 9 / 254.0) * (1 + 1e-6) \
                if tol is None else 0.0
            assert err <= bound, (comm, name, err)


def test_local_sgd_int8_parity(mesh8):
    """LocalSGD with int8 sync tracks the f32 run closely (same data) and
    replicas still converge to one value at sync boundaries."""
    from paddle_tpu.parallel import LocalSGDStep
    rng = np.random.RandomState(0)
    wt = rng.randn(3, 1).astype('float32')
    batches = [rng.randn(16, 3).astype('float32') for _ in range(4)]

    def loss_fn(p, b):
        x, y = b[..., :-1], b[..., -1:]
        return jnp.mean((x @ p['w'] - y) ** 2)

    finals = {}
    for comm in ('f32', 'int8'):
        step = LocalSGDStep(loss_fn, {'w': np.zeros((3, 1), np.float32)},
                            mesh8, k_steps=2, lr=0.05, comm_dtype=comm)
        for x in batches:
            step(np.concatenate([x, x @ wt], -1))
        assert step.replicas_in_sync(rtol=1e-5), comm
        finals[comm] = np.asarray(step.averaged_params()['w'])
    np.testing.assert_allclose(finals['int8'], finals['f32'], atol=0.05)


def test_geo_sgd_int8_parity(mesh8):
    from paddle_tpu.parallel import GeoSGDStep
    rng = np.random.RandomState(1)
    wt = rng.randn(3, 1).astype('float32')
    batches = [rng.randn(16, 3).astype('float32') for _ in range(4)]

    def loss_fn(p, b):
        x, y = b[..., :-1], b[..., -1:]
        return jnp.mean((x @ p['w'] - y) ** 2)

    finals = {}
    for comm in ('f32', 'int8'):
        step = GeoSGDStep(loss_fn, {'w': np.zeros((3, 1), np.float32)},
                          mesh8, need_push_nums=2, lr=0.05, comm_dtype=comm)
        for x in batches:
            step(np.concatenate([x, x @ wt], -1))
        assert step.replicas_in_sync(rtol=1e-4), comm
        finals[comm] = np.asarray(step.base_params()['w'])
    np.testing.assert_allclose(finals['int8'], finals['f32'], atol=0.05)


def test_dygraph_bundle_one_reduce_per_dtype():
    """apply_collective_grads' bundling: ALL grads flatten into one bundle
    per dtype and the reducer runs ONCE per bundle, not per parameter."""
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.nn import Linear
    from paddle_tpu.dygraph.parallel import _allreduce_bundles
    with dygraph.guard():
        model = Linear(6, 4)
        params = list(model.parameters())       # weight + bias
        assert len(params) >= 2
        rng = np.random.RandomState(0)
        wants = []
        for p in params:
            g = rng.randn(*np.shape(p.value)).astype('float32')
            p.grad = jnp.asarray(g)
            wants.append(g)
        calls = []

        def fake_reduce(flat):
            calls.append(int(flat.shape[0]))
            return flat * 2.0

        n_calls = _allreduce_bundles(params, fake_reduce)
        assert n_calls == 1 and len(calls) == 1     # ONE reduce for all
        assert calls[0] == sum(g.size for g in wants)
        for p, g in zip(params, wants):
            np.testing.assert_allclose(np.asarray(p.grad), g * 2, rtol=1e-6)

        # mixed dtypes: one bundle per dtype group
        params[0].grad = jnp.asarray(wants[0], jnp.bfloat16)
        calls.clear()
        assert _allreduce_bundles(params, fake_reduce) == 2
        assert len(calls) == 2


# One rank of a real cross-process dygraph bundle reduce: every rank holds
# its own seeded gradient per parameter; after apply_collective_grads each
# holds the sum over ranks.
_BUNDLE_WORKER = r'''
import json, os, sys
import numpy as np
from paddle_tpu.fleet_runtime import bootstrap
bootstrap()
import jax
import jax.numpy as jnp
from paddle_tpu import dygraph
from paddle_tpu.dygraph.nn import Linear
from paddle_tpu.dygraph.parallel import DataParallel

result_path, comm = sys.argv[1], sys.argv[2]
n, rank = jax.process_count(), jax.process_index()
os.environ['PADDLE_TPU_COMM_DTYPE'] = comm
with dygraph.guard():
    model = Linear(16, 4)
    dp = DataParallel(model)
    rngs = [np.random.RandomState(100 + r) for r in range(n)]
    want = {}
    for p in model.parameters():
        per_rank = [r.randn(*np.shape(p.value)).astype('float32')
                    for r in rngs]
        p.grad = jnp.asarray(per_rank[rank])
        want[id(p)] = np.sum(per_rank, axis=0)
    dp.apply_collective_grads()
    err = max(float(np.abs(np.asarray(p.grad) - want[id(p)]).max())
              for p in model.parameters())
if rank == 0:
    with open(result_path, 'w') as f:
        json.dump({'nproc': n, 'max_err': err}, f)
'''


@pytest.mark.parametrize('comm, bound', [('f32', 0.0), ('int8', 0.5)])
def test_bundled_reduce_across_two_real_processes(tmp_path, comm, bound):
    """dygraph ``DataParallel.apply_collective_grads`` over two REAL
    ``jax.distributed`` CPU processes (gloo): the bundled reduce leaves the
    per-rank gradients' sum on every rank, exactly at f32 and within the
    codec's bound at int8."""
    import json
    from shared_programs import run_fleet_script
    result = tmp_path / 'result.json'
    rcs, output = run_fleet_script(tmp_path, 2, _BUNDLE_WORKER,
                                   [result, comm])
    assert rcs == [0, 0], output
    got = json.loads(result.read_text())
    assert got['nproc'] == 2
    assert got['max_err'] <= bound
    if comm == 'int8':
        assert got['max_err'] > 0            # it really quantized


def test_static_c_allreduce_unbound_axis_is_identity():
    """The graph op lowers to identity outside a shard_map (single-replica
    semantics) — what fleet's inserted sync points do on the GSPMD
    executor — and to a real psum when the axis is bound."""
    from paddle_tpu.ops.registry import get_op
    x = jnp.asarray(np.arange(6.0, dtype=np.float32))
    out = get_op('c_allreduce_sum').fn(x, axis='dp')
    assert np.array_equal(np.asarray(out), np.asarray(x))
    mesh = make_mesh({'dp': 8})
    got = np.asarray(compat.shard_map(
        lambda v: get_op('c_allreduce_sum').fn(v[0], axis='dp')[None],
        mesh=mesh, in_specs=P('dp'), out_specs=P('dp'))(
        jnp.ones((8, 4))))
    assert np.all(got == 8.0)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_collective_telemetry_counters():
    """bytes-on-wire accounting: the int8/f32 ratio at the counter level
    is the >=3.5x acceptance; the error histogram records codec passes."""
    with obs.telemetry_guard(True):
        obs.reset()
        elems = 1 << 20
        qc.record_collective('testpath', elems, 'int8', 8)
        qc.record_collective('testpath', elems, 'f32', 8)
        qc.record_quant_error(
            'testpath', np.random.RandomState(0).randn(4096)
            .astype('float32'), 'int8')
        m = obs.registry.to_dict()
        by_dtype = {s['labels']['dtype']: s['value']
                    for s in m['collective_bytes_on_wire']['samples']}
        assert by_dtype['f32'] / by_dtype['int8'] >= 3.5
        f32eq = sum(s['value']
                    for s in m['collective_bytes_f32_equiv']['samples'])
        assert f32eq == 2 * by_dtype['f32']     # one equiv line per call
        calls = sum(s['value']
                    for s in m['collective_sync_calls']['samples'])
        assert calls == 2
        errs = m['collective_quant_rel_error']['samples']
        assert sum(s['count'] for s in errs) == 1
        assert 0 < max(s['max'] for s in errs) < 0.05
    # axis size 1 moves zero bytes (passthrough is local)
    assert qc.wire_bytes(elems, 'int8', 1) == 0


@pytest.mark.parametrize('comm', ['f32', 'bf16', 'int8'])
def test_gradient_volume_allreduce_wire_bytes_and_error(mesh8, comm):
    """One gradient-volume sync on the 8-device mesh, bytes counted by the
    telemetry: f32 moves the f32-equivalent bytes and is bitwise the exact
    ``lax.psum``; bf16 moves exactly half; int8 (payload + one f32 scale
    per block) at least 3.5x fewer, with a small but nonzero error."""
    elems = 1 << 16
    X = np.random.RandomState(0).randn(8, elems).astype('float32')
    want = np.asarray(compat.shard_map(
        lambda v: lax.psum(v[0], 'dp')[None], mesh=mesh8,
        in_specs=P('dp'), out_specs=P('dp'))(jnp.asarray(X)))[0]
    got = _allreduce(X, mesh8, comm)[0]
    with obs.telemetry_guard(True):
        obs.reset()
        qc.record_collective('testpath', elems, comm, 8)
        m = obs.registry.to_dict()
    wire = sum(s['value'] for s in m['collective_bytes_on_wire']['samples'])
    f32eq = sum(s['value']
                for s in m['collective_bytes_f32_equiv']['samples'])
    rel = np.abs(got - want).max() / np.abs(want).max()
    if comm == 'f32':
        assert f32eq == wire and np.array_equal(got, want)
    elif comm == 'bf16':
        assert f32eq == 2 * wire and 0 < rel < 0.05
    else:
        assert f32eq / wire >= 3.5 and 0 < rel < 0.05


def _mnist_like(rng, n, in_dim=784, classes=10):
    """Prototype-digit corpus (the test_mnist_convergence recipe shape):
    per-class fixed prototypes + pixel noise, learnable by an MLP."""
    protos = rng.randint(0, 256, (classes, in_dim))
    labels = rng.randint(0, classes, n)
    imgs = np.clip(protos[labels] + rng.randint(-80, 80, (n, in_dim)),
                   0, 255).astype(np.float32) / 255.0
    return imgs, labels.astype(np.int32)[:, None]


def _explicit_sync_dp_step(mesh, params, lr, comm_dtype):
    """Jitted data-parallel step: batch sharded over 'dp', params
    replicated, per-shard grads synced with ``qallreduce_mean`` at
    `comm_dtype` (exact pmean at f32)."""
    def loss_fn(p, x, y):
        h = jnp.maximum(x @ p['w1'] + p['b1'], 0.0)
        logp = jax.nn.log_softmax(h @ p['w2'] + p['b2'])
        return -jnp.mean(jnp.take_along_axis(logp, y, axis=1))

    def body(p, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        grads = {k: compat.pcast(
            qc.qallreduce_mean(g, 'dp', comm_dtype=comm_dtype),
            'dp', to='varying') for k, g in grads.items()}
        return ({k: v - lr * grads[k] for k, v in p.items()},
                lax.pmean(loss, 'dp'))

    pspec = {k: P() for k in params}
    return jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=(pspec, P('dp'), P('dp')),
        out_specs=(pspec, P())), donate_argnums=(0,))


def test_int8_gradient_sync_converges_as_f32_does(mesh8):
    """The EQuARX quality claim at test scale: the MNIST-shaped MLP trained
    twice on identical data and init, gradients synced at f32 and at int8;
    both converge and the int8 run ends within 10% of the f32 run's loss
    DECREASE (or 15% of its final value). Red on this image's jax (ROADMAP
    D1) and kept so: ``pcast`` varying→varying is refused, and the step's
    ``P()`` out_specs are no longer inferred replicated."""
    from jax.sharding import NamedSharding
    n, epochs, bs, hidden = 512, 4, 64, 64
    X, Y = _mnist_like(np.random.RandomState(0), n)
    data_sh = NamedSharding(mesh8, P('dp'))
    final, first = {}, None
    for comm in ('f32', 'int8'):
        rng = np.random.RandomState(1)
        params = {
            'w1': jnp.asarray((rng.randn(784, hidden) * (2.0 / 784) ** 0.5)
                              .astype(np.float32)),
            'b1': jnp.zeros(hidden, jnp.float32),
            'w2': jnp.asarray((rng.randn(hidden, 10)
                               * (2.0 / hidden) ** 0.5).astype(np.float32)),
            'b2': jnp.zeros(10, jnp.float32)}
        step = _explicit_sync_dp_step(mesh8, params, 0.1, comm)
        hist = []
        for _ in range(epochs):
            for i in range(0, n - bs + 1, bs):
                xb = jax.device_put(jnp.asarray(X[i:i + bs]), data_sh)
                yb = jax.device_put(jnp.asarray(Y[i:i + bs]), data_sh)
                params, loss = step(params, xb, yb)
                hist.append(float(loss))
        final[comm] = float(np.mean(hist[-4:]))
        first = hist[0] if first is None else first
    assert final['f32'] < 0.5 * first and final['int8'] < 0.5 * first
    tol = max(0.1 * (first - final['f32']), 0.15 * final['f32'])
    assert abs(final['int8'] - final['f32']) <= tol, (final, tol)


def test_local_sgd_records_sync_bytes(mesh8):
    from paddle_tpu.parallel import LocalSGDStep

    def loss_fn(p, b):
        return jnp.mean((b[..., :-1] @ p['w'] - b[..., -1:]) ** 2)

    rng = np.random.RandomState(0)
    with obs.telemetry_guard(True):
        obs.reset()
        step = LocalSGDStep(loss_fn, {'w': np.zeros((3, 1), np.float32)},
                            mesh8, k_steps=2, lr=0.05, comm_dtype='int8')
        for _ in range(4):                       # 2 sync boundaries
            step(rng.randn(16, 4).astype('float32'))
        m = obs.registry.to_dict()
        calls = {s['labels']['path']: s['value']
                 for s in m['collective_sync_calls']['samples']}
        assert calls.get('local_sgd') == 2
