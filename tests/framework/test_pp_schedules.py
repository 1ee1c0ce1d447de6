"""Schedule-aware pipeline lowering (ISSUE 20): 1F1B and interleaved next
to GPipe, driven by the PADDLE_TPU_PP_SCHEDULE / PADDLE_TPU_PP_MICROBATCHES
knobs (strict-parse, env wins over the stamped dist_strategy), the
cost-model auto-cut + budget-driven microbatch solve, the staged planner's
peak-residency prediction, and the lifted pipeline+sparse restriction.

The load-bearing claim: 1F1B is the SAME arithmetic as the GPipe scan —
one backward per microbatch in reverse order against the same
constant-cotangent seed — so its loss/param trajectory must be BITWISE
identical, not merely close. Interleaved reassociates the wave loop, so
it matches at float tolerance."""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis.stage import (plan_staged_program,
                                       solve_microbatches,
                                       solve_stage_cuts,
                                       stage_cut_candidates)
from paddle_tpu.core.scope import global_scope
from paddle_tpu.partition.pipeline import (PP_SCHEDULES, pp_microbatches,
                                           pp_schedule)
from shared_programs import build_bert_layer


def _trajectory(schedule, monkeypatch, steps=5, n_micro=4):
    """Non-uniform 2-stage pipeline (scan lowering) under `schedule`;
    returns (losses, params) after `steps` SGD steps. Fresh unique-name
    generator + scope so the two builds are name-identical."""
    import paddle_tpu.core.scope as sm
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.scope import Scope
    if schedule is None:
        monkeypatch.delenv('PADDLE_TPU_PP_SCHEDULE', raising=False)
    else:
        monkeypatch.setenv('PADDLE_TPU_PP_SCHEDULE', schedule)
    unique_name.generator = unique_name.UniqueNameGenerator()
    monkeypatch.setattr(sm, '_global_scope', Scope())
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        fluid.framework.manual_seed(11)
        x = layers.data('x', [16], dtype='float32')
        y = layers.data('y', [1], dtype='float32')
        h1 = layers.fc(x, size=32, act='tanh')
        h2 = layers.fc(h1, size=8, act='tanh')
        s = layers.reduce_sum(h2, dim=1, keep_dim=True)
        loss = layers.reduce_mean(layers.square_error_cost(s, y))
        fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGD(learning_rate=0.05), cut_list=[h1],
            num_microbatches=n_micro).minimize(loss)
    exe = fluid.Executor()
    exe.run(start)
    rng = np.random.RandomState(0)
    out = []
    for _ in range(steps):
        xv = rng.standard_normal((8, 16)).astype(np.float32)
        l, = exe.run(main, feed={'x': xv, 'y': xv[:, :1]},
                     fetch_list=[loss])
        out.append(np.asarray(l))
    params = {v.name: np.asarray(global_scope().find(v.name))
              for v in main.all_parameters()}
    return out, params


def test_1f1b_bitwise_matches_gpipe_scan(monkeypatch):
    base_l, base_p = _trajectory(None, monkeypatch)       # stamped gpipe
    got_l, got_p = _trajectory('1f1b', monkeypatch)
    for a, b in zip(got_l, base_l):
        assert a.tobytes() == b.tobytes()
    for n in base_p:
        assert got_p[n].tobytes() == base_p[n].tobytes(), n


def test_interleaved_matches_at_tolerance(monkeypatch):
    base_l, base_p = _trajectory(None, monkeypatch)
    got_l, got_p = _trajectory('interleaved', monkeypatch)
    np.testing.assert_allclose(np.ravel(got_l), np.ravel(base_l),
                               rtol=2e-4, atol=1e-5)
    for n in base_p:
        np.testing.assert_allclose(got_p[n], base_p[n],
                                   rtol=2e-4, atol=1e-5)


def test_schedule_knob_strict_parse(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PP_SCHEDULE', 'pipedream')
    with pytest.raises(ValueError) as ei:
        pp_schedule()
    for name in PP_SCHEDULES:
        assert name in str(ei.value)
    monkeypatch.delenv('PADDLE_TPU_PP_SCHEDULE')
    with pytest.raises(ValueError):
        pp_schedule('bogus-default')
    assert pp_schedule('1f1b') == '1f1b'


def test_microbatch_knob_strict_parse(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', 'four')
    with pytest.raises(ValueError, match='positive integer'):
        pp_microbatches()
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', '-2')
    with pytest.raises(ValueError, match='> 0'):
        pp_microbatches()
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', '8')
    assert pp_microbatches(4) == 8          # env wins over the marker


def test_env_overrides_stamped_microbatches(monkeypatch):
    """PADDLE_TPU_PP_MICROBATCHES beats the stamped count at lowering."""
    from paddle_tpu.executor import _pipeline_plan
    from paddle_tpu.framework import BACKWARD_OP_TYPE
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', '2')
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        fluid.framework.manual_seed(1)
        x = layers.data('x', [16], dtype='float32')
        y = layers.data('y', [1], dtype='float32')
        h1 = layers.fc(x, size=8, act='tanh')
        pred = layers.fc(h1, size=1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGD(learning_rate=0.05), cut_list=[h1],
            num_microbatches=4).minimize(loss)
    ops = main.global_block().ops
    bwd = next(i for i, o in enumerate(ops) if o.type == BACKWARD_OP_TYPE)
    state_names = [v.name for v in main.list_vars() if v.persistable]
    plan = _pipeline_plan(main, ops[:bwd], ops[bwd], ['x', 'y'],
                          state_names)
    assert plan['m'] == 2, plan


def test_pipeline_optimizer_arg_validation():
    sgd = fluid.optimizer.SGD(learning_rate=0.05)
    with pytest.raises(ValueError, match='schedule'):
        fluid.optimizer.PipelineOptimizer(sgd, schedule='pipedream')
    with pytest.raises(ValueError, match='num_stages'):
        fluid.optimizer.PipelineOptimizer(sgd, num_stages=1)


def test_auto_cut_and_budget_microbatch_solve(monkeypatch):
    """num_stages + num_microbatches='auto': the optimizer auto-cuts via
    the cost model, stamps m=0, and the executor solves the smallest m
    fitting PADDLE_TPU_HBM_BUDGET_MB at lowering — then runs."""
    from paddle_tpu.executor import _pipeline_plan
    from paddle_tpu.framework import BACKWARD_OP_TYPE
    monkeypatch.setenv('PADDLE_TPU_HBM_BUDGET_MB', '48')
    monkeypatch.delenv('PADDLE_TPU_PP_SCHEDULE', raising=False)
    monkeypatch.delenv('PADDLE_TPU_PP_MICROBATCHES', raising=False)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        fluid.framework.manual_seed(3)
        x = layers.data('x', [256], dtype='float32')
        y = layers.data('y', [1], dtype='float32')
        h = x
        for _ in range(6):
            h = layers.fc(h, size=256, act='tanh')
        s = layers.reduce_sum(h, dim=1, keep_dim=True)
        loss = layers.reduce_mean(layers.square_error_cost(s, y))
        fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGD(learning_rate=0.05), num_stages=2,
            schedule='1f1b', num_microbatches='auto').minimize(loss)
    ops = main.global_block().ops
    bwd = next(i for i, o in enumerate(ops) if o.type == BACKWARD_OP_TYPE)
    marker = ops[bwd]
    pipe = marker.attrs['pipeline']
    assert len(pipe['cut_vars']) == 1       # auto-cut picked a boundary
    assert pipe['num_microbatches'] == 0    # the auto sentinel
    state_names = [v.name for v in main.list_vars() if v.persistable]
    plan = _pipeline_plan(main, ops[:bwd], marker, ['x', 'y'], state_names,
                          fetch_names=(loss.name,),
                          feed_shapes={'x': (64, 256), 'y': (64, 1)})
    assert plan['schedule'] == '1f1b' and plan['m'] >= 2, plan
    exe = fluid.Executor()
    exe.run(start)
    xv = np.random.RandomState(0).standard_normal((64, 256)) \
        .astype(np.float32)
    l, = exe.run(main, feed={'x': xv, 'y': xv[:, :1]}, fetch_list=[loss])
    assert np.isfinite(np.asarray(l)).all()


def test_dist_strategy_pipeline_stamp():
    """DistributedStrategy pp knobs flow through DistributedOptimizer
    into the marker stamp (auto-cut; schedule + m recorded)."""
    from paddle_tpu.framework import BACKWARD_OP_TYPE
    from paddle_tpu.parallel import (DistributedOptimizer,
                                     DistributedStrategy)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        fluid.framework.manual_seed(3)
        x = layers.data('x', [16], dtype='float32')
        y = layers.data('y', [1], dtype='float32')
        h1 = layers.fc(x, size=32, act='tanh')
        h2 = layers.fc(h1, size=16, act='tanh')
        h3 = layers.fc(h2, size=8, act='tanh')
        s = layers.reduce_sum(h3, dim=1, keep_dim=True)
        loss = layers.reduce_mean(layers.square_error_cost(s, y))
        strat = DistributedStrategy()
        strat.pipeline_stages = 2
        strat.pp_schedule = '1f1b'
        strat.pp_microbatches = 4
        DistributedOptimizer(fluid.optimizer.SGD(learning_rate=0.05),
                             strat).minimize(loss)
    marker = next(op for op in reversed(main.global_block().ops)
                  if op.type == BACKWARD_OP_TYPE)
    pipe = marker.attrs['pipeline']
    assert pipe['schedule'] == '1f1b'
    assert pipe['num_microbatches'] == 4
    assert len(pipe['cut_vars']) == 1
    exe = fluid.Executor()
    exe.run(start)
    xv = np.random.RandomState(0).standard_normal((8, 16)) \
        .astype(np.float32)
    l, = exe.run(main, feed={'x': xv, 'y': xv[:, :1]}, fetch_list=[loss])
    assert np.isfinite(np.asarray(l)).all()


def test_dist_strategy_pp_setters_strict():
    from paddle_tpu.parallel import DistributedStrategy
    s = DistributedStrategy()
    with pytest.raises(ValueError):
        s.pp_schedule = 'bogus'
    with pytest.raises(ValueError):
        s.pipeline_stages = 1
    with pytest.raises(ValueError):
        s.pp_microbatches = -1
    s.pp_microbatches = 'auto'              # the sentinel is legal
    with pytest.raises(ValueError, match='pipeline_stages'):
        # schedule without a stage count cannot be stamped
        from paddle_tpu.parallel import DistributedOptimizer
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            x = layers.data('x', [4], dtype='float32')
            y = layers.data('y', [1], dtype='float32')
            pred = layers.fc(x, size=1)
            loss = layers.reduce_mean(layers.square_error_cost(pred, y))
            st = DistributedStrategy()
            st.pp_schedule = '1f1b'
            DistributedOptimizer(fluid.optimizer.SGD(learning_rate=0.1),
                                 st).minimize(loss)


def _sparse_pipeline_losses(pipelined, schedule, monkeypatch):
    """DeepFM-style sparse embedding recipe, optionally pipelined —
    previously `NotImplementedError: pipeline + sparse`."""
    import paddle_tpu.core.scope as sm
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.core.scope import Scope
    if schedule is None:
        monkeypatch.delenv('PADDLE_TPU_PP_SCHEDULE', raising=False)
    else:
        monkeypatch.setenv('PADDLE_TPU_PP_SCHEDULE', schedule)
    unique_name.generator = unique_name.UniqueNameGenerator()
    default_generator.seed(42)
    V = 40
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data('ids', [5], dtype='int64')
        label = layers.data('label', [1], dtype='float32')
        emb = layers.embedding(ids, size=[V, 16], is_sparse=True)
        h = layers.fc(emb, size=8, act='relu')
        h2 = layers.fc(h, size=8, act='relu')
        out = layers.fc(h2, size=1)
        loss = layers.reduce_mean(layers.square_error_cost(out, label))
        sgd = fluid.optimizer.SGD(learning_rate=0.1)
        if pipelined:
            fluid.optimizer.PipelineOptimizer(
                sgd, cut_list=[h], num_microbatches=2).minimize(loss)
        else:
            sgd.minimize(loss)
    exe = fluid.Executor()
    old = sm._global_scope
    sm._global_scope = Scope()
    try:
        exe.run(startup)
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(5):
            f = {'ids': rng.randint(0, V, (4, 5)).astype(np.int64),
                 'label': rng.rand(4, 1).astype(np.float32)}
            l, = exe.run(main, feed=f, fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())[()]))
        params = {v.name: np.asarray(sm._global_scope.find(v.name))
                  for v in main.all_parameters()}
        return losses, params
    finally:
        sm._global_scope = old


@pytest.mark.parametrize('schedule', [None, '1f1b'])
def test_pipeline_sparse_restriction_lifted(schedule, monkeypatch):
    """Sparse embedding + pipeline runs (scan and 1F1B lowering) and
    matches the unpipelined sparse trajectory — the site-surrogate
    slices ride the microbatch scan."""
    lp, tp_ = _sparse_pipeline_losses(True, schedule, monkeypatch)
    ln, tn = _sparse_pipeline_losses(False, None, monkeypatch)
    np.testing.assert_allclose(lp, ln, rtol=2e-4, atol=1e-5)
    for n in tn:
        np.testing.assert_allclose(tp_[n], tn[n], rtol=2e-4, atol=1e-5)


def test_staged_planner_1f1b_peak_below_gpipe():
    """The liveness walk extended to staged programs: on an
    activation-heavy program 1F1B's predicted host peak (one wave of
    residuals) is below GPipe's (all m waves)."""
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        fluid.framework.manual_seed(5)
        x = layers.data('x', [128], dtype='float32')
        y = layers.data('y', [1], dtype='float32')
        h = x
        for _ in range(6):
            h = layers.fc(h, size=128, act='relu')
        pred = layers.fc(h, size=1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    cuts, report = solve_stage_cuts(main, 2, fetch_names=(loss.name,),
                                    feed_names=('x', 'y'), assume_dim=32)
    assert len(cuts) == 1 and report['balance'] < 2.0
    kw = dict(fetch_names=(loss.name,), feed_names=('x', 'y'),
              assume_dim=32)
    g = plan_staged_program(main, cuts, 8, schedule='gpipe', **kw)
    f = plan_staged_program(main, cuts, 8, schedule='1f1b', **kw)
    assert f.host_peak_bytes < g.host_peak_bytes
    # more microbatches shrink the 1F1B peak further, leave GPipe flat
    f16 = plan_staged_program(main, cuts, 16, schedule='1f1b', **kw)
    g16 = plan_staged_program(main, cuts, 16, schedule='gpipe', **kw)
    assert f16.host_peak_bytes < f.host_peak_bytes
    assert abs(g16.host_peak_bytes - g.host_peak_bytes) \
        <= 0.02 * g.host_peak_bytes
    # the budget solve lands on a count whose predicted peak fits
    budget = (f.host_peak_bytes + f16.host_peak_bytes) // 2
    m, peak, fits = solve_microbatches(main, cuts, '1f1b', budget, **kw)
    assert fits and peak <= budget and m == 16
    # auto-cut candidates cover the boundary set the solver used
    cands = stage_cut_candidates(main, **kw)
    assert cuts[0] in cands and len(cands) >= 2


def _compiled_temp_bytes(program, feed, fetch_names, scope):
    """XLA's temp-buffer bytes for the step the executor compiles for
    (program, feed, fetches): the same ``_lower``, donation included."""
    import jax
    from paddle_tpu import ir
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.executor import _lower
    state_names = sorted(v.name for v in program.list_vars()
                         if v.persistable and scope.find(v.name) is not None)
    opt_program, _ = ir.apply_pipeline(
        program, fetch_names=fetch_names, feed_names=list(feed))
    step = _lower(opt_program, list(feed), fetch_names, state_names,
                  feed_shapes={n: v.shape for n, v in feed.items()})
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        {n: scope.find(n) for n in state_names}, {}, feed,
        default_generator.base_key()).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def test_1f1b_holds_one_wave_planned_and_compiled(monkeypatch):
    """An activation-heavy deep MLP auto-cut into 2 stages, GPipe against
    1F1B at the same cut and 4 microbatches through the schedule knob: the
    losses are bitwise (the same arithmetic, the backward reordered), and
    1F1B's peak is not above GPipe's both as the staged planner PREDICTS
    it and as XLA's ``memory_analysis`` of the compiled step MEASURES it,
    so the prediction is held to the compiler."""
    from paddle_tpu.core.random import default_generator
    monkeypatch.delenv('PADDLE_TPU_PP_MICROBATCHES', raising=False)
    monkeypatch.delenv('PADDLE_TPU_HBM_BUDGET_MB', raising=False)
    width, depth, bs = 128, 8, 32
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('pp_x', [width], dtype='float32')
        y = layers.data('pp_y', [1], dtype='float32')
        h = x
        for _ in range(depth):
            h = layers.fc(h, size=width, act='relu')
        loss = layers.reduce_mean(
            layers.square_error_cost(layers.fc(h, size=1), y))
        fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGD(learning_rate=1e-3), num_stages=2,
            num_microbatches=4, schedule='gpipe').minimize(loss)
    stamp = next(op.attrs['pipeline']
                 for op in reversed(main.global_block().ops)
                 if op.attrs.get('pipeline'))
    cuts, m = list(stamp['cut_vars']), int(stamp['num_microbatches'])
    rng = np.random.RandomState(0)
    feeds = [{'pp_x': rng.randn(bs, width).astype(np.float32),
              'pp_y': rng.randn(bs, 1).astype(np.float32)}
             for _ in range(4)]
    losses, predicted, measured = {}, {}, {}
    for sched in ('gpipe', '1f1b'):
        monkeypatch.setenv('PADDLE_TPU_PP_SCHEDULE', sched)
        predicted[sched] = plan_staged_program(
            main, cuts, m, schedule=sched, fetch_names=[loss.name],
            feed_names=['pp_x', 'pp_y'],
            feed_shapes={k: v.shape for k, v in feeds[0].items()}
        ).host_peak_bytes
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            default_generator.seed(42)
            exe = fluid.Executor()
            exe.run(start)
            measured[sched] = _compiled_temp_bytes(main, feeds[0],
                                                   [loss.name], scope)
            losses[sched] = [
                np.asarray(exe.run(main, feed=f, fetch_list=[loss])[0])
                .tobytes() for f in feeds]
    assert losses['1f1b'] == losses['gpipe']
    assert 0 < predicted['1f1b'] <= predicted['gpipe']
    assert 0 < measured['1f1b'] <= measured['gpipe']


def test_auto_cut_within_5_percent_of_the_best_manual_cut():
    """Every manual single cut of the BERT-layer recipe scored through the
    staged planner (max per-stage flops + bytes): the cost-model auto-cut
    lands within 5% of the best of them."""
    program, _startup, make_feed, loss = build_bert_layer()
    kw = dict(fetch_names=[loss.name], feed_names=sorted(make_feed()),
              assume_dim=8)

    def cut_cost(cuts):
        splan = plan_staged_program(program, cuts, 2, schedule='1f1b', **kw)
        return max(r.flops + r.bytes for r in splan.stages)

    cands = stage_cut_candidates(program, **kw)
    assert len(cands) >= 2
    best_manual = min(cut_cost([c]) for c in cands)
    auto_cuts, _report = solve_stage_cuts(program, 2, **kw)
    assert 0 < best_manual <= cut_cost(auto_cuts) <= best_manual * 1.05


def test_parallel_pipeline_shim_delegates():
    """The retired parallel.pipeline.gpipe warns once (through the
    warn_once registry — repo invariant: never print) and delegates to
    partition.pipeline (bitwise — same code, new home)."""
    import jax.numpy as jnp

    from paddle_tpu.parallel import pipeline as shim
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.partition import pipeline as owned
    from paddle_tpu.partition.partitioner import _DEPRECATION_WARNED
    assert shim.gpipe is not owned.gpipe        # wrapper, not alias
    assert shim.stack_stage_params is owned.stack_stage_params
    mesh = make_mesh({'pp': 2})
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(2, 8, 8).astype(np.float32))
    xm = jnp.asarray(rng.randn(4, 2, 8).astype(np.float32))
    _DEPRECATION_WARNED.discard('parallel.pipeline.gpipe')
    old = shim.gpipe(lambda p, h: jnp.tanh(h @ p), W, xm, mesh=mesh)
    assert 'parallel.pipeline.gpipe' in _DEPRECATION_WARNED
    new = owned.gpipe(lambda p, h: jnp.tanh(h @ p), W, xm, mesh=mesh)
    assert np.array_equal(np.asarray(old), np.asarray(new))
