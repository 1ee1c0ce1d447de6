"""Analysis-driven compilation (ISSUE 14): the static cost model
(analysis/cost.py), the peak-HBM memory planner (analysis/plan.py), the
budget-driven auto-remat IR pass (ir/auto_remat.py), bucket autotuning
(PADDLE_TPU_ALLREDUCE_BUCKET_MB=auto), and the RecomputeOptimizer
checkpoint validation satellite.

The two load-bearing claims, asserted here:

- predicted state+feed+fetch bytes match the executor's MEASURED
  accounting within tolerance on every tier-1 verifier recipe;
- auto-remat fits a simulated HBM budget the unplanned program exceeds,
  with losses BITWISE-identical both to the un-rematerialized run and to
  a manual RecomputeOptimizer run over the same checkpoint names.
"""
import functools
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, ir, layers as L
from paddle_tpu import observability as obs
from paddle_tpu.analysis import (VarInfo, all_cost_rules, all_rules,
                                 gradient_bytes, plan_program,
                                 select_checkpoints)
from paddle_tpu.analysis.cost import (dtype_nbytes, info_nbytes, op_cost)
from paddle_tpu.core import unique_name
from paddle_tpu.framework import BACKWARD_OP_TYPE
from paddle_tpu.ir import auto_remat, bucket_allreduce, pipeline_signature

from shared_programs import (build_bert_layer, build_mlp_adam,
                             build_resnet_block)


def _fresh_names():
    unique_name.generator = unique_name.UniqueNameGenerator()
    fluid.framework.manual_seed(0)


# ---------------------------------------------------------------------------
# recipe builders: (main, startup, feed dict, fetch names)
# ---------------------------------------------------------------------------

def _mnist_mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = L.data('img', [64], dtype='float32')
        label = L.data('label', [1], dtype='int64')
        h = L.fc(img, size=32, act='relu')
        h = L.fc(h, size=32, act='relu')
        logits = L.fc(h, size=10)
        loss = L.reduce_mean(L.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.randn(8, 64).astype(np.float32),
            'label': rng.randint(0, 10, (8, 1)).astype(np.int64)}
    return main, startup, feed, [loss.name]


def _fleet_dp():
    from paddle_tpu.parallel import DistributedStrategy, fleet
    fleet.init()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', shape=[32], dtype='float32')
        y = L.data('y', shape=[1], dtype='int64')
        h = L.fc(x, size=32, act='relu')
        h2 = L.fc(h, size=32, act='relu')
        logits = L.fc(h2, size=10)
        loss = L.reduce_mean(L.softmax_with_cross_entropy(logits, y))
        fleet.distributed_optimizer(
            fluid.optimizer.SGD(0.1),
            strategy=DistributedStrategy()).minimize(loss)
    rng = np.random.RandomState(1)
    feed = {'x': rng.randn(8, 32).astype(np.float32),
            'y': rng.randint(0, 10, (8, 1)).astype(np.int64)}
    return main, startup, feed, [loss.name]


def _decode_engine():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('ids', [8], dtype='int64')
        emb = L.embedding(ids, size=[100, 16])
        h = L.fc(emb, size=16, act='tanh')
        logits = L.fc(h, size=100)
        nxt = L.argmax(logits, axis=-1)
    rng = np.random.RandomState(2)
    feed = {'ids': rng.randint(0, 100, (4, 8)).astype(np.int64)}
    return main, startup, feed, [nxt.name]


def _from_builder(builder):
    main, startup, make_feed, fetch = builder()
    feed = make_feed() if callable(make_feed) else make_feed
    return main, startup, feed, [fetch.name]


_RECIPES = {
    'mnist_mlp': _mnist_mlp,
    'mlp_adam': lambda: _from_builder(build_mlp_adam),
    'resnet_block': lambda: _from_builder(build_resnet_block),
    'bert_layer': lambda: _from_builder(build_bert_layer),
    'fleet_dp': _fleet_dp,
    'decode_engine': _decode_engine,
}


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_cost_rule_coverage_matches_infer_registry():
    """Every op type with an inference rule has a cost rule — the same
    coverage contract the infer registry carries, so anything the tier-1
    recipes emit (pre- or post-pipeline) is costed."""
    missing = set(all_rules()) - set(all_cost_rules())
    assert not missing, f'infer rules without cost rules: {sorted(missing)}'
    for t in ('fused_adam', 'fused_momentum', 'fused_sgd',
              'fused_elemwise_add_activation', 'c_allreduce_sum_bucket'):
        assert analysis.has_cost_rule(t), t


def test_cost_rule_coverage_over_recipe_ops():
    for name, build in _RECIPES.items():
        main, _s, _f, _fetch = build()
        for b in main.blocks:
            for op in b.ops:
                if op.type == BACKWARD_OP_TYPE:
                    continue
                assert analysis.has_cost_rule(op.type), \
                    f'{name}: no cost rule for {op.type!r}'


def _one_op_cost(op_type, inputs, attrs, in_slots, out_names=('o',)):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        blk = main.global_block()
        env = {}
        for name, (shape, dtype) in inputs.items():
            blk.create_var(name=name, shape=shape, dtype=dtype)
            env[name] = VarInfo(shape, dtype)
        op = blk.append_op(op_type, inputs=in_slots,
                           outputs={'Out': list(out_names)}, attrs=attrs)
        from paddle_tpu.analysis.infer import infer_op
        res = infer_op(op, env, blk)
        if res:
            for n, info in zip(out_names, [res.get('Out')]):
                env[n] = info if isinstance(info, VarInfo) else info[0]
        return op_cost(op, env, blk)


def test_cost_matmul_flops_2mkn():
    c = _one_op_cost('matmul',
                     {'a': ((8, 16), 'float32'), 'b': ((16, 4), 'float32')},
                     {}, {'x': ['a'], 'y': ['b']})
    assert c.flops == 2 * 8 * 16 * 4
    # bytes: 8×16 + 16×4 read, 8×4 written, all f32
    assert c.bytes_in == (8 * 16 + 16 * 4) * 4
    assert c.bytes_out == 8 * 4 * 4


def test_cost_conv2d_flops():
    c = _one_op_cost('conv2d',
                     {'x': ((2, 3, 8, 8), 'float32'),
                      'w': ((16, 3, 3, 3), 'float32')},
                     {'stride': 1, 'padding': 1},
                     {'x': ['x'], 'weight': ['w']})
    out_elems = 2 * 16 * 8 * 8
    assert c.flops == 2 * 3 * 3 * 3 * out_elems


def test_cost_elementwise_and_movement():
    c = _one_op_cost('elementwise_add',
                     {'a': ((4, 8), 'float32'), 'b': ((4, 8), 'float32')},
                     {}, {'x': ['a'], 'y': ['b']})
    assert c.flops == 32
    c = _one_op_cost('reshape', {'a': ((4, 8), 'float32')},
                     {'shape': [8, 4]}, {'x': ['a']})
    assert c.flops == 0 and c.bytes == 2 * 32 * 4


def test_runtime_byte_widths():
    """int64 prices at 4 bytes — the device computes it as int32 under
    the default x64-off config, and the measured counterpart sums real
    device buffers."""
    assert dtype_nbytes('int64') == 4
    assert dtype_nbytes('bfloat16') == 2
    assert dtype_nbytes('bool') == 1
    assert info_nbytes(VarInfo((4, 2), 'int64')) == 32
    # UNKNOWN dims substitute assume_dim
    assert info_nbytes(VarInfo((-1, 8), 'float32'), assume_dim=16) == 512


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def test_plan_accounting_and_report():
    main, _startup, feed, fetches = _mnist_mlp()
    shapes = {k: v.shape for k, v in feed.items()}
    plan = plan_program(main, fetch_names=fetches, feed_names=sorted(feed),
                        feed_shapes=shapes)
    assert plan.peak_bytes >= plan.accounted_bytes > 0
    assert plan.grad_bytes > 0 and plan.activation_bytes > 0
    assert plan.fwd_flops > 0 and plan.total_flops > plan.fwd_flops
    assert plan.donation_saved_bytes > 0      # params update in place
    assert len(plan.timeline) == len(main.global_block().ops)
    assert not plan.uncosted_ops
    report = '\n'.join(plan.format_report(top=5))
    assert 'predicted peak HBM' in report and 'Top residents' in report
    d = plan.to_dict()
    assert d['peak_hbm_bytes'] == plan.peak_bytes


def test_plan_donation_split():
    """donate=False keeps written state out of the in-place set — the
    plan must price the copy-in/copy-out double buffer."""
    main, _startup, feed, fetches = _mnist_mlp()
    shapes = {k: v.shape for k, v in feed.items()}
    on = plan_program(main, fetch_names=fetches, feed_shapes=shapes,
                      donate=True)
    off = plan_program(main, fetch_names=fetches, feed_shapes=shapes,
                       donate=False)
    assert off.peak_bytes == on.peak_bytes + on.donation_saved_bytes
    assert off.donation_saved_bytes == 0


def test_gradient_bytes_matches_params():
    main, _startup, feed, _f = _mnist_mlp()
    expect = sum(int(np.prod(p.shape)) * 4 for p in main.all_parameters())
    assert gradient_bytes(main) == expect


def test_select_checkpoints_consistent_with_replan():
    main, _startup, feed, fetches = _mnist_mlp()
    shapes = {k: v.shape for k, v in feed.items()}
    base = plan_program(main, fetch_names=fetches, feed_shapes=shapes)
    names, peak = select_checkpoints(main, int(base.peak_bytes * 0.8),
                                     fetch_names=fetches,
                                     feed_shapes=shapes)
    assert names, 'selector found no boundary on a 17-op MLP'
    replanned = plan_program(main, fetch_names=fetches,
                             feed_shapes=shapes, checkpoints=names)
    assert replanned.peak_bytes == peak
    assert peak < base.peak_bytes


@pytest.mark.parametrize('name', sorted(_RECIPES))
def test_predicted_vs_measured_bytes(name):
    """The acceptance bar: the plan's state+feed+fetch prediction matches
    the executor's measured byte accounting within 10% on every tier-1
    verifier recipe (exact for fully-static programs)."""
    main, startup, feed, fetches = _RECIPES[name]()
    with obs.telemetry_guard(True):
        obs.reset()
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=list(fetches))
        d = obs.registry.to_dict()
    predicted = d['program_plan_accounted_bytes']['samples'][0]['value']
    measured = d['program_measured_hbm_bytes']['samples'][0]['value']
    peak = d['program_peak_hbm_bytes']['samples'][0]['value']
    plan_s = d['program_plan_seconds']['samples'][0]
    assert 'program_plan_failures' not in d, d.get('program_plan_failures')
    assert predicted > 0 and measured > 0
    assert abs(measured - predicted) / measured <= 0.10, \
        f'{name}: predicted {predicted} vs measured {measured}'
    assert peak >= predicted
    assert plan_s['count'] >= 1


# ---------------------------------------------------------------------------
# auto-remat
# ---------------------------------------------------------------------------

def _remat_model(manual_ckpt_names=None, depth=6, width=64, bs=16):
    _fresh_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [width], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        h = x
        for _ in range(depth):
            h = L.fc(h, size=width, act='relu')
        pred = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        opt = fluid.optimizer.SGD(0.1)
        if manual_ckpt_names:
            opt = fluid.optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints(list(manual_ckpt_names))
        opt.minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(bs, width).astype(np.float32),
            'y': rng.randn(bs, 1).astype(np.float32)}
    return main, startup, feed, loss


def _run_steps(main, startup, feed, loss, steps=3):
    exe = fluid.Executor()
    exe.run(startup)
    return [exe.run(main, feed=feed, fetch_list=[loss])[0]
            for _ in range(steps)]


@pytest.mark.parametrize('shape', [dict(width=64, bs=16),
                                   dict(width=32, bs=64)],
                         ids=['w64_bs16', 'activation_heavy_w32_bs64'])
def test_auto_remat_fits_budget_bitwise(monkeypatch, shape):
    """The tentpole acceptance: a simulated HBM budget the unplanned
    program exceeds; auto-remat fits it; losses bitwise-identical to the
    un-rematerialized run AND to manual RecomputeOptimizer checkpointing
    over the same names. The second shape is the workload remat exists
    for: wide batch over depth, residuals into the backward dominate."""
    model = functools.partial(_remat_model, **shape)
    monkeypatch.delenv('PADDLE_TPU_HBM_BUDGET_MB', raising=False)
    base = _run_steps(*model())

    main, _s, feed, loss = model()
    shapes = {k: v.shape for k, v in feed.items()}
    kw = dict(fetch_names=[loss.name], feed_names=sorted(feed),
              feed_shapes=shapes)
    no_remat = plan_program(main, **kw)
    _n, floor_peak = select_checkpoints(main, 0, **kw)
    budget = (floor_peak + no_remat.peak_bytes) // 2
    assert no_remat.peak_bytes > budget        # the program OOMs it

    monkeypatch.setenv('PADDLE_TPU_HBM_BUDGET_MB',
                       repr(budget / float(1 << 20)))
    m2, s2, feed2, loss2 = model()
    auto = _run_steps(m2, s2, feed2, loss2)
    opt_prog, ctx = ir.apply_pipeline(m2, fetch_names=[loss2.name],
                                      feed_names=sorted(feed2),
                                      feed_shapes=shapes)
    marker = next(op for op in opt_prog.global_block().ops
                  if op.type == BACKWARD_OP_TYPE)
    chosen = marker.attrs.get('checkpoints')
    assert chosen, 'auto_remat chose no checkpoints'
    assert ctx.stats.get('auto_remat', {}).get('checkpoints') == len(chosen)
    remat_plan = plan_program(opt_prog, **kw)
    assert remat_plan.peak_bytes <= budget, \
        f'{remat_plan.peak_bytes} > budget {budget}'

    monkeypatch.delenv('PADDLE_TPU_HBM_BUDGET_MB')
    manual = _run_steps(*model(manual_ckpt_names=chosen))

    for a, b in zip(auto, base):
        assert np.array_equal(a, b), 'remat changed numerics vs base'
    for a, m in zip(auto, manual):
        assert np.array_equal(a, m), 'auto vs manual checkpoints differ'


def test_auto_remat_respects_manual_checkpoints(monkeypatch):
    main, _s, feed, loss = _remat_model()
    blk = main.global_block()
    marker = next(op for op in blk.ops if op.type == BACKWARD_OP_TYPE)
    manual = [blk.ops[2].output_names()[0]]
    marker.attrs['checkpoints'] = list(manual)
    monkeypatch.setenv('PADDLE_TPU_HBM_BUDGET_MB', '0.0001')
    opt_prog, _ = ir.apply_pipeline(main, fetch_names=[loss.name],
                                    feed_names=sorted(feed))
    m2 = next(op for op in opt_prog.global_block().ops
              if op.type == BACKWARD_OP_TYPE)
    assert m2.attrs.get('checkpoints') == manual


def test_auto_remat_noop_under_budget(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_HBM_BUDGET_MB', '65536')   # 64 GiB
    main, _s, feed, loss = _remat_model()
    opt_prog, ctx = ir.apply_pipeline(main, fetch_names=[loss.name],
                                      feed_names=sorted(feed))
    marker = next(op for op in opt_prog.global_block().ops
                  if op.type == BACKWARD_OP_TYPE)
    assert not marker.attrs.get('checkpoints')
    assert 'auto_remat' not in ctx.stats


def test_hbm_budget_strict_parse(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_HBM_BUDGET_MB', 'lots')
    with pytest.raises(ValueError, match='PADDLE_TPU_HBM_BUDGET_MB'):
        auto_remat.hbm_budget_bytes()
    monkeypatch.setenv('PADDLE_TPU_HBM_BUDGET_MB', '-3')
    with pytest.raises(ValueError, match='> 0'):
        auto_remat.hbm_budget_bytes()
    monkeypatch.setenv('PADDLE_TPU_HBM_BUDGET_MB', '2048')
    assert auto_remat.hbm_budget_bytes() == 2048 << 20
    monkeypatch.delenv('PADDLE_TPU_HBM_BUDGET_MB')
    assert auto_remat.hbm_budget_bytes() is None


def test_pipeline_signature_tags(monkeypatch):
    from paddle_tpu.compiler import BuildStrategy
    monkeypatch.delenv('PADDLE_TPU_HBM_BUDGET_MB', raising=False)
    sig = pipeline_signature()
    assert not any(n.startswith('auto_remat') for n in sig)
    monkeypatch.setenv('PADDLE_TPU_HBM_BUDGET_MB', '1')
    sig = pipeline_signature()
    assert f'auto_remat@{1 << 20}' in sig
    # the bucket tag only counts when its fuse flag is live
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    monkeypatch.setenv('PADDLE_TPU_ALLREDUCE_BUCKET_MB', 'auto')
    assert 'bucket_allreduce@auto' in pipeline_signature(bs)
    monkeypatch.setenv('PADDLE_TPU_ALLREDUCE_BUCKET_MB', '8')
    assert f'bucket_allreduce@{8 << 20}' in pipeline_signature(bs)


# ---------------------------------------------------------------------------
# bucket autotuning
# ---------------------------------------------------------------------------

def test_bucket_cap_auto_arithmetic(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_ALLREDUCE_BUCKET_MB', 'auto')
    # 100 MiB of grads / target 4 buckets = 25 MiB cap
    assert bucket_allreduce.bucket_cap_bytes(grad_bytes=100 << 20) \
        == 25 << 20
    # tiny models floor at 1 MiB (no latency-dominated shattering)
    assert bucket_allreduce.bucket_cap_bytes(grad_bytes=1000) == 1 << 20
    assert bucket_allreduce.bucket_cap_bytes() is None
    assert bucket_allreduce.bucket_cap_is_auto()
    monkeypatch.setenv('PADDLE_TPU_ALLREDUCE_BUCKET_MB', '8')
    assert bucket_allreduce.bucket_cap_bytes(grad_bytes=100 << 20) \
        == 8 << 20
    monkeypatch.setenv('PADDLE_TPU_ALLREDUCE_BUCKET_MB', 'autoo')
    with pytest.raises(ValueError, match="'auto'"):
        bucket_allreduce.bucket_cap_bytes()


def test_bucket_auto_e2e(monkeypatch):
    """=auto forms buckets on the fleet DP recipe (grads ≪ 1 MiB floor →
    one bucket per compatible run) and stays bitwise vs per-grad ops."""
    monkeypatch.delenv('PADDLE_TPU_ALLREDUCE_BUCKET_MB', raising=False)
    from paddle_tpu.compiler import BuildStrategy
    _fresh_names()
    main, startup, feed, fetches = _fleet_dp()
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    monkeypatch.setenv('PADDLE_TPU_ALLREDUCE_BUCKET_MB', 'auto')
    opt_prog, ctx = ir.apply_pipeline(main, fetch_names=fetches,
                                      feed_names=sorted(feed),
                                      build_strategy=bs)
    bucketed = [op for op in opt_prog.global_block().ops
                if op.type == 'c_allreduce_sum_bucket']
    assert bucketed, 'auto cap formed no bucket'
    assert ctx.stats['bucket_allreduce']['buckets'] >= 1
    # bitwise: bucketed (auto cap) vs unbucketed fetches
    exe = fluid.Executor()
    exe.run(startup)
    from paddle_tpu.compiler import CompiledProgram
    on = exe.run(CompiledProgram(main, build_strategy=bs), feed=feed,
                 fetch_list=list(fetches))
    monkeypatch.delenv('PADDLE_TPU_ALLREDUCE_BUCKET_MB')
    _fresh_names()
    main2, startup2, feed2, fetches2 = _fleet_dp()
    exe2 = fluid.Executor()
    exe2.run(startup2)
    off = exe2.run(main2, feed=feed2, fetch_list=list(fetches2))
    for a, b in zip(on, off):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# RecomputeOptimizer validation satellite
# ---------------------------------------------------------------------------

def test_recompute_checkpoints_duplicate_raises():
    opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
    with pytest.raises(ValueError, match=r"duplicate.*\['h'\]"):
        opt._set_checkpoints(['h', 'h'])
    with pytest.raises(ValueError, match='Variables or var names'):
        opt._set_checkpoints([42])
    with pytest.raises(ValueError, match='list/tuple'):
        opt._set_checkpoints('h')


def test_recompute_checkpoints_unknown_name_raises():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [8], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        h = L.fc(x, size=8, act='relu')
        loss = L.reduce_mean(L.square_error_cost(L.fc(h, size=1), y))
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
        opt._set_checkpoints(['no_such_var'])
        with pytest.raises(ValueError, match="no_such_var"):
            opt.minimize(loss)


def test_recompute_checkpoints_valid_still_train():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [8], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        h = L.fc(x, size=8, act='relu')
        loss = L.reduce_mean(L.square_error_cost(L.fc(h, size=1), y))
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
        opt._set_checkpoints([h])
        opt.minimize(loss)
    marker = next(op for op in main.global_block().ops
                  if op.type == BACKWARD_OP_TYPE)
    assert marker.attrs['checkpoints'] == [h.name]
    exe = fluid.Executor()
    exe.run(startup)
    out, = exe.run(main, feed={'x': np.ones((4, 8), np.float32),
                               'y': np.zeros((4, 1), np.float32)},
                   fetch_list=[loss])
    assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# CLIs
# ---------------------------------------------------------------------------

_TOOLS = os.path.join(os.path.dirname(__file__), '..', '..', 'tools')


def test_plan_program_cli_budget_gate(capsys, monkeypatch):
    monkeypatch.syspath_prepend(_TOOLS)
    import plan_program as cli
    rc = cli.main(['--recipe', 'mnist_mlp', '--json', '--budget', '4096'])
    out = capsys.readouterr().out
    assert rc == 0
    import json
    doc = json.loads(out)
    assert doc['fits_budget'] and doc['peak_hbm_bytes'] > 0
    rc = cli.main(['--recipe', 'mnist_mlp', '--budget', '0.001'])
    assert rc == 1


def test_lint_program_plan_flag(capsys, monkeypatch):
    monkeypatch.syspath_prepend(_TOOLS)
    import lint_program as cli
    rc = cli.main(['--recipe', 'mnist_mlp', '--plan'])
    out = capsys.readouterr().out
    assert rc == 0
    assert 'Memory plan' in out and 'predicted peak HBM' in out
