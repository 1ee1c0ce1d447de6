"""Unified SPMD partitioner (paddle_tpu/partition, docs/PARTITIONER.md):
rule-table resolution, strict knob parsing, mesh ownership (the
deprecated ``set_default_mesh`` shim), spec parity vs the retired
per-module plumbing, bitwise parity of the refactored parallel modules
through both entry points, dp×tp / dp×fsdp compositions with the PR 9
quantized+bucketed gradient sync (telemetry asserted), the
sharding-consistency diagnostics corpus, and the partitioner-keyed
checkpoint spec manifest."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, layers, observability as obs, partition
from paddle_tpu.core.scope import Scope
from paddle_tpu.parallel import (DistributedStrategy, GeoSGDStep,
                                 LocalSGDStep, fleet)
from paddle_tpu.parallel import fsdp as F
from paddle_tpu.parallel.mesh import (get_default_mesh, make_mesh,
                                      mesh_guard, set_default_mesh)
from paddle_tpu.parallel.tensor_parallel import (column_parallel_matmul,
                                                 megatron_param_spec,
                                                 mp_allreduce, mp_copy,
                                                 row_parallel_matmul)
from paddle_tpu.partition import (AxisRules, Partitioner, get_partitioner,
                                  parse_axis_rules, parse_mesh_shape)
from paddle_tpu.partition.spmd_step import SpmdTrainStep
from jax.sharding import PartitionSpec as P

_THIS_FILE = os.path.abspath(__file__)


@pytest.fixture(autouse=True)
def _fresh_partitioner():
    partition.reset_partitioner()
    yield
    partition.reset_partitioner()


# ---------------------------------------------------------------------------
# rules + strict parsing
# ---------------------------------------------------------------------------

def test_default_rules_resolution():
    p = Partitioner(mesh_shape={'dp': 8})
    assert p.data_axes() == ('dp',)
    assert p.data_spec(16) == P('dp')
    p = Partitioner(mesh_shape={'dp': 2, 'fsdp': 4})
    assert p.data_axes() == ('dp', 'fsdp')
    assert p.data_spec(16) == P(('dp', 'fsdp'))
    # indivisible batch dim falls back to replicated
    assert p.data_spec(3) == P()
    # unconfigured partitioner replicates everything
    p = Partitioner()
    assert p.mesh is None or p.mesh  # env may configure it
    assert Partitioner(mesh=None).resolve_spec(('batch',)) == P()


def test_rule_table_order_first_match_wins():
    rules = AxisRules((('batch', 'sp'), ('batch', 'dp')))
    assert rules.resolve('batch', {'dp': 8}) == ('dp',)       # sp absent
    assert rules.resolve('batch', {'sp': 4, 'dp': 2}) == ('sp',)
    # divisibility skips to the next rule
    assert rules.resolve('batch', {'sp': 3, 'dp': 2}, dim=8) == ('dp',)


def test_spec_never_reuses_a_mesh_axis():
    p = Partitioner(mesh_shape={'tp': 8})
    rules = AxisRules((('mlp', 'tp'), ('heads', 'tp')))
    spec = rules.spec(('mlp', 'heads'), {'tp': 8})
    assert spec == P('tp')          # second dim loses: axis already taken


def test_axis_rules_strict_parse():
    with pytest.raises(ValueError, match='batch'):
        parse_axis_rules('bogus=dp')
    with pytest.raises(ValueError, match='dp, fsdp, tp, pp, sp'):
        parse_axis_rules('batch=nope')
    assert parse_axis_rules('batch=dp+fsdp,kv=') == \
        (('batch', ('dp', 'fsdp')), ('kv', None))


def test_mesh_shape_strict_parse():
    with pytest.raises(ValueError, match='dp, fsdp, tp, pp, sp'):
        parse_mesh_shape({'gpu': 8})
    with pytest.raises(ValueError, match='>= 1'):
        parse_mesh_shape('dp=0')
    with pytest.raises(ValueError, match='twice'):
        parse_mesh_shape('dp=2,dp=4')
    assert parse_mesh_shape('dp=2, tp=4') == {'dp': 2, 'tp': 4}


def test_distributed_strategy_fields_strict():
    strat = DistributedStrategy()
    with pytest.raises(ValueError, match='DistributedStrategy.mesh_shape'):
        strat.mesh_shape = {'cuda': 8}
    with pytest.raises(ValueError, match='DistributedStrategy.axis_rules'):
        strat.axis_rules = 'embedding=tp'
    strat.mesh_shape = 'dp=2,fsdp=4'
    strat.axis_rules = 'batch=dp,fsdp=fsdp'
    assert strat.mesh_shape == {'dp': 2, 'fsdp': 4}
    assert strat.axis_rules == (('batch', ('dp',)), ('fsdp', ('fsdp',)))


def test_env_knobs(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_MESH', 'dp=2,tp=4')
    monkeypatch.setenv('PADDLE_TPU_AXIS_RULES', 'batch=dp,mlp=tp')
    partition.reset_partitioner()
    p = get_partitioner()
    assert dict(p.mesh.shape) == {'dp': 2, 'tp': 4}
    assert p.data_axes() == ('dp',)
    monkeypatch.setenv('PADDLE_TPU_MESH', 'dp=2,bogus=4')
    partition.reset_partitioner()
    with pytest.raises(ValueError, match='PADDLE_TPU_MESH'):
        get_partitioner()


# ---------------------------------------------------------------------------
# mesh ownership: the deprecated shim + scoped override
# ---------------------------------------------------------------------------

def test_set_default_mesh_deprecated_shim_warns_once(monkeypatch):
    from paddle_tpu.partition import partitioner as pmod
    records = []

    class _Rec:
        def warning(self, msg, *a):
            records.append(msg % a if a else msg)

    monkeypatch.setattr('paddle_tpu.log_helper.get_logger',
                        lambda *a, **k: _Rec())
    pmod._DEPRECATION_WARNED.discard('set_default_mesh')
    mesh = make_mesh({'dp': 8})
    set_default_mesh(mesh)
    assert get_default_mesh() is mesh
    assert get_partitioner().mesh is mesh          # the partitioner owns it
    set_default_mesh(None)
    assert get_default_mesh() is None
    assert len(records) == 1 and 'deprecated' in records[0]
    assert 'set_default_mesh' in pmod._DEPRECATION_WARNED


def test_mesh_guard_scopes_the_owned_mesh():
    mesh = make_mesh({'sp': 8})
    assert get_default_mesh() is None
    with mesh_guard(mesh):
        assert get_default_mesh() is mesh
        assert get_partitioner().mesh is mesh
    assert get_default_mesh() is None


def test_configure_updates_global_in_place():
    p0 = get_partitioner()
    p1 = partition.configure(mesh_shape={'dp': 8})
    assert p1 is p0                                # identity stable
    assert dict(p0.mesh.shape) == {'dp': 8}


# ---------------------------------------------------------------------------
# spec parity vs the retired per-module plumbing
# ---------------------------------------------------------------------------

def test_fsdp_spec_parity_with_module():
    mesh = make_mesh({'fsdp': 8})
    p = Partitioner(mesh=mesh)
    for shape in [(64, 32), (32, 64), (8,), (3, 5), (1,), (16, 16, 4),
                  (24, 7), (7, 24), (8, 8)]:
        assert p.fsdp_spec(shape) == F.fsdp_spec(shape, mesh), shape
        assert p.param_spec('w', shape) == F.fsdp_spec(shape, mesh), shape


def test_megatron_spec_parity_with_module():
    p = Partitioner(mesh_shape={'tp': 8})
    arr = np.zeros((64, 32), np.float32)
    for name in ('l.ffn1.w', 'enc.q_proj.w', 'b.ffn2.w', 'a.out_proj.w',
                 'plain.w'):
        assert tuple(p.param_spec(name, arr.shape)) == \
            tuple(megatron_param_spec(name, arr)), name


def test_optimizer_slots_inherit_param_spec():
    p = Partitioner(mesh_shape={'dp': 2, 'tp': 4})
    w = p.param_spec('fc.ffn1.w_0', (64, 32))
    slot = p.param_spec('fc.ffn1.w_0_velocity_0', (64, 32))
    assert w == slot == P(None, 'tp')


def test_param_spec_composes_tp_and_fsdp():
    p = Partitioner(mesh_shape={'dp': 2, 'tp': 2, 'fsdp': 2})
    assert p.param_spec('x.ffn1.w', (64, 32)) == P(None, 'tp')
    assert p.param_spec('plain.w', (64, 32)) == P('fsdp', None)


# ---------------------------------------------------------------------------
# bitwise parity: refactored modules through both entry points
# ---------------------------------------------------------------------------

def _mse_loss(params, batch):
    return jnp.mean((batch[:, :-1] @ params['w'] - batch[:, -1:]) ** 2)


def _run_local_sgd(step_builder, steps=6):
    rng = np.random.RandomState(0)
    step = step_builder()
    return [float(step(rng.randn(16, 4).astype('float32')))
            for _ in range(steps)]


def test_local_sgd_bitwise_mesh_vs_partitioner():
    w0 = np.zeros((3, 1), np.float32)
    mesh = make_mesh({'dp': 8})
    legacy = _run_local_sgd(lambda: LocalSGDStep(
        _mse_loss, {'w': w0}, mesh, k_steps=2, lr=0.05))
    p = partition.configure(mesh_shape={'dp': 8})
    new = _run_local_sgd(lambda: LocalSGDStep(
        _mse_loss, {'w': w0}, k_steps=2, lr=0.05, partitioner=p))
    assert np.array_equal(legacy, new), (legacy, new)


def test_geo_sgd_bitwise_mesh_vs_partitioner():
    w0 = np.zeros((3, 1), np.float32)
    mesh = make_mesh({'dp': 8})
    legacy = _run_local_sgd(lambda: GeoSGDStep(
        _mse_loss, {'w': w0}, mesh, need_push_nums=2, lr=0.05))
    p = partition.configure(mesh_shape={'dp': 8})
    new = _run_local_sgd(lambda: GeoSGDStep(
        _mse_loss, {'w': w0}, need_push_nums=2, lr=0.05, partitioner=p))
    assert np.array_equal(legacy, new), (legacy, new)


def test_tensor_parallel_bitwise_mesh_vs_partitioner_default():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4, 16).astype('float32'))
    w1 = jnp.asarray(rng.randn(16, 32).astype('float32'))
    w2 = jnp.asarray(rng.randn(32, 16).astype('float32'))
    mesh = make_mesh({'tp': 8})
    y_explicit = row_parallel_matmul(
        column_parallel_matmul(x, w1, mesh=mesh), w2, mesh=mesh)
    partition.configure(mesh=mesh)
    y_default = row_parallel_matmul(
        column_parallel_matmul(x, w1), w2)       # partitioner-owned mesh
    assert np.array_equal(np.asarray(y_explicit), np.asarray(y_default))


def _build_fsdp_program():
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        fluid.framework.manual_seed(5)
        x = layers.data('x', [16], dtype='float32')
        y = layers.data('y', [1], dtype='float32')
        h = layers.fc(x, size=32, act='relu')
        pred = layers.fc(h, size=1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        strat = DistributedStrategy()
        strat.sharding = True
        opt = fleet.distributed_optimizer(
            fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9),
            strat)
        opt.minimize(loss)
    return main, start, loss


def _run_static(main, start, loss, steps=5):
    scope = Scope()
    exe = fluid.Executor()
    exe.run(start, scope=scope)
    rng = np.random.RandomState(1)
    out = []
    for _ in range(steps):
        xv = rng.standard_normal((16, 16)).astype(np.float32)
        yv = xv[:, :1].astype(np.float32)
        l, = exe.run(main, feed={'x': xv, 'y': yv}, fetch_list=[loss],
                     scope=scope)
        out.append(np.asarray(l))
    return np.concatenate([o.ravel() for o in out])


def test_fsdp_static_bitwise_legacy_vs_partitioner_entry():
    """The retired set_default_mesh entry and the partitioner entry
    lower the SAME fsdp program to bit-identical trajectories."""
    main, start, loss = _build_fsdp_program()
    with mesh_guard(make_mesh({'fsdp': 8})):       # legacy entry point
        legacy = _run_static(main, start, loss)
    partition.configure(mesh_shape={'fsdp': 8})    # partitioner entry
    new = _run_static(main, start, loss)
    assert np.array_equal(legacy, new), (legacy, new)


# ---------------------------------------------------------------------------
# compositions: dp×fsdp and dp×tp (ISSUE 11 acceptance)
# ---------------------------------------------------------------------------

def _composition_fixture():
    rng = np.random.RandomState(0)
    W1 = (rng.randn(16, 32) * 0.1).astype('float32')
    W2 = (rng.randn(32, 1) * 0.1).astype('float32')
    b = np.zeros((1,), 'float32')
    X = rng.randn(16, 16).astype('float32')
    batch = np.concatenate([X, X[:, :1]], axis=1)
    return {'ffn1.w': W1, 'ffn2.w': W2, 'b': b}, batch


def _ref_loss(params, bt):
    x, y = bt[:, :-1], bt[:, -1:]
    h = jnp.maximum(x @ params['ffn1.w'], 0.0)
    return jnp.mean(((h @ params['ffn2.w'] + params['b']) - y) ** 2)


def _reference_sgd(loss_fn, params, batch, lr, steps):
    ps = {k: jnp.asarray(v) for k, v in params.items()}
    losses = []
    for _ in range(steps):
        l, g = jax.value_and_grad(loss_fn)(ps, jnp.asarray(batch))
        ps = {k: v - lr * g[k] for k, v in ps.items()}
        losses.append(float(l))
    return losses, ps


def test_spmd_step_dp_fsdp_composition():
    """dp×fsdp with BOTH axes > 1: fc weights train as 1/4 fsdp tiles,
    batch shards over all 8 devices, every gradient sync runs through
    the PR 9 quantized-collective path (counters asserted)."""
    params, batch = _composition_fixture()
    ref_losses, ref_params = _reference_sgd(_ref_loss, params, batch,
                                            0.1, 5)
    p = partition.configure(mesh_shape={'dp': 2, 'fsdp': 4})
    assert all(s > 1 for s in p.mesh.shape.values())
    with obs.telemetry_guard(True):
        obs.reset()
        step = SpmdTrainStep(_ref_loss, params, partitioner=p, lr=0.1)
        assert step.param_kind('ffn1.w') == 'fsdp'
        assert step.param_kind('b') == 'replicated'
        losses = [float(step(batch)) for _ in range(5)]
        m = obs.registry.to_dict()
        calls = sum(s['value']
                    for s in m['collective_sync_calls']['samples']
                    if s['labels'].get('path') == 'spmd_step')
        assert calls == step.sync_calls_per_step * 5
        assert sum(s['value'] for s in
                   m['collective_bytes_on_wire']['samples']
                   if s['labels'].get('path') == 'spmd_step') > 0
    np.testing.assert_allclose(losses, ref_losses, rtol=5e-4, atol=1e-6)
    got = step.materialize()
    for n in params:
        np.testing.assert_allclose(got[n], np.asarray(ref_params[n]),
                                   rtol=5e-4, atol=1e-6)
    # the fsdp tiles really are 1/4 per device along the sharded dim
    w1 = step.sharded_params()['ffn1.w']
    assert w1.addressable_shards[0].data.shape == (16, 8)


def test_spmd_step_dp_tp_composition():
    """dp×tp with BOTH axes > 1: Megatron col+row MLP through the f/g
    conjugate collectives; tp tiles sync over dp only, replicated params
    bucket; trajectory matches the single-device reference."""
    params, batch = _composition_fixture()
    ref_losses, _ = _reference_sgd(_ref_loss, params, batch, 0.1, 5)

    def tp_loss(ps, bt):
        x, y = bt[:, :-1], bt[:, -1:]
        x = mp_copy(x, 'tp')
        h = jnp.maximum(x @ ps['ffn1.w'], 0.0)        # local columns
        part = h @ ps['ffn2.w']                       # partial products
        return jnp.mean(((mp_allreduce(part, 'tp') + ps['b']) - y) ** 2)

    p = partition.configure(mesh_shape={'dp': 2, 'tp': 4})
    with obs.telemetry_guard(True):
        obs.reset()
        step = SpmdTrainStep(tp_loss, params, partitioner=p, lr=0.1)
        assert step.param_kind('ffn1.w') == 'tp'
        assert step.param_kind('ffn2.w') == 'tp'
        assert step.param_kind('b') == 'replicated'
        losses = [float(step(batch)) for _ in range(5)]
        m = obs.registry.to_dict()
        calls = sum(s['value']
                    for s in m['collective_sync_calls']['samples']
                    if s['labels'].get('path') == 'spmd_step')
        assert calls == step.sync_calls_per_step * 5
    np.testing.assert_allclose(losses, ref_losses, rtol=5e-4, atol=1e-6)


def _tp_loss(ps, bt):
    x, y = bt[:, :-1], bt[:, -1:]
    x = mp_copy(x, 'tp')
    h = jnp.maximum(x @ ps['ffn1.w'], 0.0)
    part = h @ ps['ffn2.w']
    return jnp.mean(((mp_allreduce(part, 'tp') + ps['b']) - y) ** 2)


@pytest.mark.parametrize('mesh_shape, loss_fn', [
    ({'dp': 2, 'fsdp': 4}, _ref_loss), ({'dp': 2, 'tp': 4}, _tp_loss)],
    ids=['dp_fsdp', 'dp_tp'])
def test_spmd_step_composition_under_a_partitioner_of_its_own(mesh_shape,
                                                              loss_fn):
    """The two compositions through a ``Partitioner`` object handed to the
    step (the process-global one left unconfigured): four SGD steps stay
    within 1e-3 of the single-device reference, every gradient sync is
    counted, and bucketing keeps the syncs of a step at 6 or fewer.
    Both are red on this image's jax (ROADMAP D1) and kept so, in sight:
    dp×fsdp runs and DIVERGES (0.633 vs 0.773 at step 2), a wrong answer;
    dp×tp stops at the graduated ``shard_map``'s typing of ``psum`` over
    ``('dp', 'tp')``."""
    params, batch = _composition_fixture()
    ref_losses, _ = _reference_sgd(_ref_loss, params, batch, 0.1, 4)
    p = Partitioner(mesh_shape=mesh_shape)
    with obs.telemetry_guard(True):
        obs.reset()
        step = SpmdTrainStep(loss_fn, params, partitioner=p, lr=0.1)
        losses = [float(step(batch)) for _ in range(4)]
        m = obs.registry.to_dict()
    calls = sum(s['value'] for s in m['collective_sync_calls']['samples']
                if s['labels'].get('path') == 'spmd_step')
    assert calls == step.sync_calls_per_step * 4
    assert step.sync_calls_per_step <= 6
    rel = np.abs((np.asarray(losses) - ref_losses) / ref_losses)
    assert rel.max() < 1e-3, (losses, ref_losses)


def test_spmd_step_bucketed_replicated_grads():
    """Many small replicated params coalesce into ONE bucketed sync per
    data axis (the PR 9 bucketing semantics on the functional path)."""
    rng = np.random.RandomState(3)
    params = {f'b{i}': rng.randn(4).astype('float32') for i in range(6)}
    params['w'] = rng.randn(8, 8).astype('float32') * 0.1

    def loss_fn(ps, bt):
        acc = jnp.sum(bt @ ps['w'])
        for i in range(6):
            acc = acc + jnp.sum(ps[f'b{i}'])
        return acc / bt.shape[0]

    p = partition.configure(mesh_shape={'dp': 8})
    step = SpmdTrainStep(loss_fn, params, partitioner=p, lr=0.01)
    # 7 replicated params (w has no fsdp axis on a dp-only mesh), one
    # data axis → exactly ONE bucket → one sync per step
    assert step.sync_calls_per_step == 1
    step(rng.randn(8, 8).astype('float32'))


def test_spmd_step_int8_quantized_sync():
    """comm_dtype=int8 routes the composed gradient sync through the
    EQuARX block-quantized collectives: ~4× fewer bytes on wire, loss
    trajectory within quantization tolerance of f32. Sizes are large
    enough that the 256-elem block scales amortize (small tensors
    EXPAND under int8 — the PR 9 documented caveat)."""
    rng = np.random.RandomState(0)
    params = {'ffn1.w': (rng.randn(32, 512) * 0.1).astype('float32'),
              'ffn2.w': (rng.randn(512, 1) * 0.1).astype('float32'),
              'b': np.zeros((1,), 'float32')}
    X = rng.randn(16, 32).astype('float32')
    batch = np.concatenate([X, X[:, :1]], axis=1)
    ref_losses, _ = _reference_sgd(_ref_loss, params, batch, 0.1, 5)
    p = partition.configure(mesh_shape={'dp': 2, 'fsdp': 4})
    with obs.telemetry_guard(True):
        obs.reset()
        step = SpmdTrainStep(_ref_loss, params, partitioner=p, lr=0.1,
                             comm_dtype='int8')
        losses = [float(step(batch)) for _ in range(5)]
        m = obs.registry.to_dict()
        wire = sum(s['value']
                   for s in m['collective_bytes_on_wire']['samples']
                   if s['labels'].get('path') == 'spmd_step')
        f32eq = sum(s['value']
                    for s in m['collective_bytes_f32_equiv']['samples']
                    if s['labels'].get('path') == 'spmd_step')
        assert f32eq / wire >= 3.0, (wire, f32eq)
        dtypes = {s['labels'].get('dtype')
                  for s in m['collective_sync_calls']['samples']
                  if s['labels'].get('path') == 'spmd_step'}
        assert dtypes == {'int8'}
    np.testing.assert_allclose(losses, ref_losses, rtol=0.05, atol=5e-3)


def test_spmd_step_errors():
    params, batch = _composition_fixture()
    with pytest.raises(ValueError, match='no mesh'):
        SpmdTrainStep(_ref_loss, params)
    p = partition.configure(mesh_shape={'dp': 8})
    step = SpmdTrainStep(_ref_loss, params, partitioner=p)
    with pytest.raises(ValueError, match='divisible'):
        step(np.zeros((13, 17), np.float32))


def test_static_fleet_dp_fsdp_composition():
    """The STATIC path composes too: strategy.mesh_shape builds the
    dp×fsdp mesh at minimize, the Executor places persistables as fsdp
    tiles and shards feeds over both axes; trajectory matches the
    unsharded baseline."""
    from paddle_tpu.compiler import CompiledProgram

    def build(composed):
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            fluid.framework.manual_seed(5)
            x = layers.data('x', [16], dtype='float32')
            y = layers.data('y', [1], dtype='float32')
            h = layers.fc(x, size=32, act='relu')
            pred = layers.fc(h, size=1)
            loss = layers.reduce_mean(layers.square_error_cost(pred, y))
            sgd = fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9)
            if composed:
                strat = DistributedStrategy()
                strat.sharding = True
                strat.mesh_shape = {'dp': 2, 'fsdp': 4}
                fleet.distributed_optimizer(sgd, strat).minimize(loss)
            else:
                sgd.minimize(loss)
        return main, start, loss

    partition.reset_partitioner()
    main, start, loss = build(False)
    base = _run_static(main, start, loss)

    partition.reset_partitioner()
    main, start, loss = build(True)
    assert dict(get_partitioner().mesh.shape) == {'dp': 2, 'fsdp': 4}
    assert getattr(main, '_partition_params', False)
    prog = CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    scope = Scope()
    exe = fluid.Executor()
    exe.run(start, scope=scope)
    rng = np.random.RandomState(1)
    comp = []
    for _ in range(5):
        xv = rng.standard_normal((16, 16)).astype(np.float32)
        yv = xv[:, :1].astype(np.float32)
        l, = exe.run(prog, feed={'x': xv, 'y': yv}, fetch_list=[loss],
                     scope=scope)
        comp.append(float(np.asarray(l).reshape(())))
    np.testing.assert_allclose(comp, base.tolist(), rtol=2e-4, atol=1e-5)
    # a persistable really lives as dp-replicated fsdp tiles
    w = next(p_ for p_ in main.all_parameters()
             if int(np.prod(p_.shape)) >= 32)
    arr = scope.find(w.name)
    assert len(arr.addressable_shards) == 8
    assert F.param_shard_bytes(arr) * 4 == arr.nbytes


# ---------------------------------------------------------------------------
# sharding-consistency diagnostics (seeded-defect corpus)
# ---------------------------------------------------------------------------

def _find(diags, code):
    hits = [d for d in diags if d.code == code]
    assert hits, f'no {code!r} in {[d.format() for d in diags]}'
    return hits[0]


def _assert_site_here(diag):
    assert diag.site is not None, diag.format()
    assert os.path.abspath(diag.site.rsplit(':', 1)[0]) == _THIS_FILE, \
        diag.site


def _stamped_program(specs, mesh_axes):
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('x', [16], dtype='float32')
        h = layers.fc(x, size=30, act='relu')
        h2 = layers.fc(x, size=30)
        out = layers.elementwise_add(h, h2)
    main._partition_specs = specs(main)
    main._partition_mesh_axes = mesh_axes
    return main, out


def test_diag_spec_indivisible():
    main, out = _stamped_program(
        lambda m: {out_name(m): (None, 'fsdp')},     # 30 % 4 != 0
        {'dp': 2, 'fsdp': 4})
    d = _find(analysis.verify_program(main, fetch_names=[out.name]),
              'spec-indivisible')
    assert d.severity == 'error'
    assert d.op_type is not None
    _assert_site_here(d)


def out_name(main):
    """Last fc output var of the stamped corpus program."""
    blk = main.global_block()
    for op in reversed(blk.ops):
        if op.type == 'elementwise_add':
            return op.inputs['x'][0]
    raise AssertionError('corpus program shape changed')


def test_diag_spec_rank_mismatch():
    main, out = _stamped_program(
        lambda m: {out_name(m): (None, None, 'dp')},  # rank-2 var
        {'dp': 2, 'fsdp': 4})
    d = _find(analysis.verify_program(main, fetch_names=[out.name]),
              'spec-rank-mismatch')
    assert d.severity == 'error'
    _assert_site_here(d)


def test_diag_spec_conflict():
    def specs(m):
        blk = m.global_block()
        # the LAST elementwise_add is the explicit h + h2 (fc lowers its
        # bias through earlier adds)
        add = next(op for op in reversed(blk.ops)
                   if op.type == 'elementwise_add')
        xn, yn = add.inputs['x'][0], add.inputs['y'][0]
        return {xn: (None, 'tp'), yn: (None, 'dp')}
    main, out = _stamped_program(specs, {'dp': 2, 'tp': 2})
    d = _find(analysis.verify_program(main, fetch_names=[out.name]),
              'spec-conflict')
    assert d.severity == 'error' and d.op_type == 'elementwise_add'
    _assert_site_here(d)


def test_diag_spec_unknown_axis_and_reuse():
    main, out = _stamped_program(
        lambda m: {out_name(m): ('nope', None)}, {'dp': 2})
    d = _find(analysis.verify_program(main, fetch_names=[out.name]),
              'spec-unknown-axis')
    assert d.severity == 'error'
    main, out = _stamped_program(
        lambda m: {out_name(m): ('dp', 'dp')}, {'dp': 2})
    d = _find(analysis.verify_program(main, fetch_names=[out.name]),
              'spec-axis-reuse')
    assert d.severity == 'error'


def test_partitioner_stamps_are_clean():
    """Specs the partitioner itself resolves never trip its own
    diagnostics (zero-false-positive contract on the fsdp recipe)."""
    partition.configure(mesh_shape={'dp': 2, 'fsdp': 4})
    main, start, loss = _build_fsdp_program()
    assert getattr(main, '_partition_specs', None)
    diags = analysis.verify_program(main, fetch_names=[loss.name])
    bad = [d for d in diags
           if d.code.startswith('spec-') and d.severity == 'error']
    assert bad == [], [d.format() for d in bad]


# ---------------------------------------------------------------------------
# propagation + program specs
# ---------------------------------------------------------------------------

def test_propagation_carries_batch_sharding():
    partition.configure(mesh_shape={'dp': 8})
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('x', [16], dtype='float32')
        h = layers.fc(x, size=32, act='relu')
        out = layers.softmax(h)
    specs = get_partitioner().program_specs(main,
                                            include_activations=True)
    assert specs['x'] == ('dp',)
    assert specs[out.name] == ('dp', None)


def test_propagation_matmul_takes_weight_columns():
    p = Partitioner(mesh_shape={'dp': 2, 'tp': 4})
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('x', [16], dtype='float32')
        h = layers.fc(x, size=32, param_attr=fluid.ParamAttr(
            name='blk.ffn1.w'))
    specs = p.program_specs(main, include_activations=True)
    assert specs['blk.ffn1.w'] == (None, 'tp')
    # fc lowers to mul(+bias): the activation inherits batch rows and
    # the weight's column sharding
    assert specs[h.name] == ('dp', 'tp')


# ---------------------------------------------------------------------------
# checkpoint spec manifest
# ---------------------------------------------------------------------------

def test_checkpoint_manifest_records_partitioner_specs():
    from paddle_tpu.resilience.state import capture_training_state
    partition.configure(mesh_shape={'dp': 2, 'fsdp': 4})
    main, start, loss = _build_fsdp_program()
    scope = Scope()
    exe = fluid.Executor()
    exe.run(start, scope=scope)
    arrays, meta = capture_training_state(program=main, scope=scope,
                                          mode='copy')
    part = meta['partition']
    assert part['mesh_axes'] == {'dp': 2, 'fsdp': 4}
    assert part['axis_rules'][0][0] == 'batch'
    sharded = [n for n, e in part['specs'].items() if any(
        x is not None for x in e)]
    assert any('w_0' in n for n in sharded), part['specs']
    import json
    json.dumps(part)                              # JSON-safe by contract


def test_state_manifest_without_program():
    p = partition.configure(mesh_shape={'dp': 8})
    m = p.state_manifest()
    assert m['mesh_axes'] == {'dp': 8}
    assert 'specs' not in m
