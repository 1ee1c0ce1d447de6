"""Self-healing training (paddle_tpu/resilience/supervisor.py + watchdog.py,
ISSUE 8): divergence detection (non-finite + robust-z spike), the
skip/rollback/escalate policy ladder, AMP overflow-skip benignity,
quarantine records, fault-spec hygiene, and watchdog arm/deadline/breach
mechanics — all in-process (the subprocess recovery story lives in
test_self_healing.py)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers as L
from paddle_tpu import observability, resilience
from paddle_tpu.core.fetch_handle import FetchHandle
from paddle_tpu.resilience import (TrainingDiverged, TrainingSupervisor,
                                   parse_supervisor_spec)
from paddle_tpu.resilience.fault import FaultInjector
from paddle_tpu.resilience.watchdog import Watchdog


def _metric(name):
    d = observability.registry.to_dict().get(name)
    if not d or not d['samples']:
        return 0.0
    return sum(s['value'] for s in d['samples'])


# ---------------------------------------------------------------------------
# spec hygiene (supervisor + fault injector)
# ---------------------------------------------------------------------------

def test_supervisor_spec_parses_policy_and_options():
    assert parse_supervisor_spec('') == (None, {})
    assert parse_supervisor_spec('skip') == ('skip', {})
    policy, opts = parse_supervisor_spec('rollback, window=32 , zmax=6')
    assert policy == 'rollback'
    assert opts == {'window': 32, 'zmax': 6.0}


def test_supervisor_spec_rejects_unknown_policy_and_keys():
    with pytest.raises(ValueError, match='unknown policy'):
        parse_supervisor_spec('rolback')          # typo must not pass
    with pytest.raises(ValueError, match='unknown option'):
        parse_supervisor_spec('skip,zmaxx=8')
    with pytest.raises(ValueError, match='two policies'):
        parse_supervisor_spec('skip,rollback')
    with pytest.raises(ValueError, match='bad value'):
        parse_supervisor_spec('skip,window=many')
    with pytest.raises(ValueError, match='unknown option'):
        TrainingSupervisor(policy='off', not_a_knob=1)
    with pytest.raises(ValueError, match='rollback'):
        TrainingSupervisor(policy='rollback')     # needs a manager


def test_fault_spec_rejects_typos_and_lists_supported_clauses():
    """A typo like kil@step=3 must raise, not silently make a
    fault-injection test vacuous."""
    for bad in ('kil@step=3', 'kill@steps=3', 'nan@loss=1', 'hang@sec=2',
                'garbage'):
        with pytest.raises(ValueError, match='supported'):
            FaultInjector(bad)
    inj = FaultInjector('nan@step=4,spike@step=9,hang@step=2,hang@secs=0.01')
    assert inj.active


def test_fault_loss_injections_fire_once():
    inj = FaultInjector('nan@step=4,spike@step=6')
    assert not inj.wants_loss(3)
    assert inj.wants_loss(4)
    assert np.isnan(inj.on_loss(4, 1.0))
    assert inj.on_loss(4, 1.0) == 1.0             # single-fire
    spiked = inj.on_loss(6, 2.0)
    assert spiked > 1e9
    assert inj.on_loss(6, 2.0) == 2.0


def test_fault_hang_bounded_by_secs():
    import time
    inj = FaultInjector('hang@step=2,hang@secs=0.05')
    t0 = time.monotonic()
    inj.on_step(2)
    assert 0.04 <= time.monotonic() - t0 < 5.0
    t0 = time.monotonic()
    inj.on_step(2)                                # single-fire
    assert time.monotonic() - t0 < 0.04


# ---------------------------------------------------------------------------
# executor-spine training helpers
# ---------------------------------------------------------------------------

def _build_net():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('sx', [4], dtype='float32')
        y = L.data('sy', [1], dtype='float32')
        h = L.fc(x, size=8, act='relu')
        pred = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def _feeds(n, seed=0):
    rng = np.random.RandomState(seed)
    return [{'sx': rng.randn(8, 4).astype(np.float32),
             'sy': rng.randn(8, 1).astype(np.float32)} for _ in range(n)]


def _scope_state(scope, program):
    return {v.name: np.asarray(scope.find(v.name))
            for v in program.list_vars() if v.persistable}


# ---------------------------------------------------------------------------
# detection + skip policy
# ---------------------------------------------------------------------------

def test_nonfinite_detection_skip_drops_the_update(tmp_path):
    """A NaN batch under policy=skip: the update is dropped bitwise (state
    returns to the last healthy boundary), a quarantine record lands, and
    training keeps going with finite losses."""
    fluid.seed(11)
    main, startup, loss = _build_net()
    scope = fluid.Scope()
    qpath = str(tmp_path / 'quarantine.jsonl')
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        sup = TrainingSupervisor(policy='skip', executor=exe, program=main,
                                 scope=scope, quarantine_path=qpath)
        feeds = _feeds(6)
        for step, feed in enumerate(feeds[:3], 1):
            lv, = exe.run(main, feed=feed, fetch_list=[loss])
            assert sup.end_of_step(step, lv,
                                   batch_desc={'i': step}).action == 'ok'
        healthy = _scope_state(scope, main)

        poisoned = dict(feeds[3], sx=feeds[3]['sx'] * np.nan)
        lv, = exe.run(main, feed=poisoned, fetch_list=[loss])
        assert not np.isfinite(lv).all()
        v = sup.end_of_step(4, lv, batch_desc={'i': 4})
        assert v.action == 'skip' and v.reason == 'nonfinite'

        # the poisoned update is GONE: state is bitwise the healthy boundary
        after = _scope_state(scope, main)
        assert set(after) == set(healthy)
        for name in healthy:
            assert np.array_equal(after[name], healthy[name]), name

        # and the loop keeps training with finite losses
        lv, = exe.run(main, feed=feeds[4], fetch_list=[loss])
        assert np.isfinite(lv).all()
        assert sup.end_of_step(5, lv).action == 'ok'

    records = [json.loads(ln) for ln in
               open(qpath).read().strip().splitlines()]
    assert len(records) == 1
    rec = records[0]
    assert rec['step'] == 4 and rec['reason'] == 'nonfinite'
    assert rec['action'] == 'skip' and rec['batch'] == {'i': 4}


def test_spike_detection_uses_robust_zscore(tmp_path):
    """An upward loss excursion past zmax is a spike; the same magnitude
    downward is progress, not divergence."""
    sup = TrainingSupervisor(policy='off', min_history=4, zmax=6.0,
                            quarantine_path=str(tmp_path / 'q.jsonl'))
    for step, x in enumerate([1.0, 1.1, 0.9, 1.05, 0.95], 1):
        assert sup.end_of_step(step, x).action == 'ok'
    down = sup.end_of_step(6, 0.001)              # collapse: fine
    assert down.action == 'ok'
    up = sup.end_of_step(7, 100.0)
    assert up.action == 'record' and up.reason == 'spike'
    assert up.zscore > 6.0
    rec = json.loads(open(tmp_path / 'q.jsonl').read().splitlines()[0])
    assert rec['reason'] == 'spike' and rec['action'] == 'record'
    # the spike was NOT folded into the rolling window: the next normal
    # loss is healthy
    assert sup.end_of_step(8, 1.0).action == 'ok'


def test_check_nan_handle_raise_is_absorbed_into_detection():
    """A FetchHandle armed with check_nan raises FloatingPointError at
    materialization; supervision converts that into a non-finite verdict
    instead of a dead loop."""
    import jax.numpy as jnp
    sup = TrainingSupervisor(policy='off')
    handle = FetchHandle(jnp.asarray(float('nan')), name='loss',
                         check_nan=True)
    v = sup.end_of_step(1, handle)
    assert v.action == 'record' and v.reason == 'nonfinite'


def test_skip_escalates_after_max_consecutive_skips():
    sup = TrainingSupervisor(policy='skip', max_skips=2)
    sup.end_of_step(1, 1.0)                       # healthy: something to
    sup._capture_state = ('scope', {}, None)      # restore (empty is fine)
    assert sup.end_of_step(2, float('nan')).action == 'skip'
    with pytest.raises(TrainingDiverged, match='consecutive'):
        sup.end_of_step(3, float('inf'))


def test_policy_escalate_raises_on_first_detection():
    sup = TrainingSupervisor(policy='escalate')
    assert sup.end_of_step(1, 0.5).action == 'ok'
    with pytest.raises(TrainingDiverged, match='nonfinite'):
        sup.end_of_step(2, float('nan'))


# ---------------------------------------------------------------------------
# rollback + escalation through a real manager
# ---------------------------------------------------------------------------

def _train_with_manager(tmp_path, poison_steps, total=12, **sup_kw):
    fluid.seed(5)
    main, startup, loss = _build_net()
    scope = fluid.Scope()
    events = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        mgr = resilience.CheckpointManager(
            str(tmp_path / 'ck'), every_n_steps=3, keep=2,
            install_signal_handlers=False)
        sup = TrainingSupervisor(policy='rollback', manager=mgr,
                                 executor=exe, program=main, scope=scope,
                                 **sup_kw)
        feeds = _feeds(total + 6, seed=1)
        step, i = 0, 0
        while step < total and i < len(feeds):
            feed = feeds[i]
            i += 1
            if i in poison_steps:
                feed = dict(feed, sx=feed['sx'] * np.nan)
            lv, = exe.run(main, feed=feed, fetch_list=[loss])
            step += 1
            mgr.end_of_step(step, lambda: resilience.capture_training_state(
                executor=exe, program=main, scope=scope), loss=lv)
            v = mgr.last_verdict
            if v is not None and v.action == 'rollback':
                events.append(('rollback', step, v.resume_step))
                step = v.resume_step
            else:
                events.append((step, np.asarray(lv).tobytes().hex()))
        mgr.wait()
        mgr.close()
    return events


@pytest.mark.parametrize('poisoned', [8, 7])
def test_rollback_restores_last_checkpoint_and_run_is_deterministic(
        tmp_path, poisoned):
    """Checkpoints at 3 and 6: a NaN at 8, or at 7 while the step-6 write
    may still be in flight, rolls back to the NEWEST committed one."""
    a = _train_with_manager(tmp_path / 'a', poison_steps={poisoned})
    b = _train_with_manager(tmp_path / 'b', poison_steps={poisoned})
    assert a == b, 'identically-faulted runs diverged'
    rollbacks = [e for e in a if e[0] == 'rollback']
    assert rollbacks == [('rollback', poisoned, 6)]
    # the run completed past the fault with new (forward) data
    assert max(e[0] for e in a if isinstance(e[0], int)) == 12
    q = (tmp_path / 'a' / 'ck' / 'quarantine.jsonl').read_text()
    assert json.loads(q.splitlines()[0])['action'] == 'rollback'


def test_rollback_budget_escalates_to_training_diverged(tmp_path):
    with pytest.raises(TrainingDiverged, match='rollbacks within'):
        _train_with_manager(tmp_path, poison_steps={5, 8, 11},
                            max_rollbacks=2, escalate_window=100)


def test_rollback_before_any_checkpoint_escalates(tmp_path):
    with pytest.raises(TrainingDiverged, match='before any checkpoint'):
        _train_with_manager(tmp_path, poison_steps={2})


def test_skip_boundary_never_checkpoints_the_poisoned_state(tmp_path):
    """A cadence-due boundary with a skip verdict must not save."""
    mgr = resilience.CheckpointManager(str(tmp_path), every_n_steps=2,
                                       keep=5, install_signal_handlers=False)
    sup = TrainingSupervisor(policy='skip', manager=mgr)
    state = {'w': np.ones((4,), np.float32)}
    mgr.end_of_step(1, lambda: (state, {}), loss=1.0)
    mgr.end_of_step(2, lambda: (state, {}), loss=1.0)   # due → saves
    mgr.wait()
    assert len(mgr.all_checkpoints()) == 1
    mgr.end_of_step(3, lambda: (state, {}), loss=1.0)
    mgr.end_of_step(4, lambda: (state, {}), loss=float('nan'))  # due + bad
    assert mgr.last_verdict.action == 'skip'
    mgr.wait()
    assert len(mgr.all_checkpoints()) == 1        # no new checkpoint
    mgr.close()


def test_supervised_healthy_loop_is_bitwise_the_bare_loop(tmp_path):
    """Supervision observes a healthy run and never changes it: the same
    feeds from the same initial state, bare and then supervised (rollback
    policy attached to a manager, watchdog armed with the executor's
    per-run lease and the supervisor's boundary lease), give BITWISE the
    same losses, and every verdict is 'ok'."""
    from paddle_tpu.resilience import watchdog as wdg
    import jax.numpy as jnp
    fluid.seed(5)
    main, startup, loss = _build_net()
    feeds = _feeds(12, seed=3)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        state0 = _scope_state(scope, main)

        bare = [np.asarray(exe.run(main, feed=f, fetch_list=[loss])[0])
                .tobytes() for f in feeds]

        for name, value in state0.items():
            scope.set(name, jnp.asarray(value))
        wdg.enable(floor_s=60.0, abort=False)
        try:
            mgr = resilience.CheckpointManager(
                str(tmp_path), keep=2, install_signal_handlers=False)
            sup = TrainingSupervisor(policy='rollback', manager=mgr,
                                     executor=exe, program=main, scope=scope)
            supervised, verdicts = [], []
            for step, f in enumerate(feeds, 1):
                lv = exe.run(main, feed=f, fetch_list=[loss])[0]
                verdicts.append(sup.end_of_step(step, lv).action)
                supervised.append(np.asarray(lv).tobytes())
            sup.close()
            mgr.close()
        finally:
            wdg.disable()
    assert verdicts == ['ok'] * len(feeds)
    assert supervised == bare


# ---------------------------------------------------------------------------
# AMP benignity
# ---------------------------------------------------------------------------

def test_amp_overflow_skip_is_benign_never_rolled_back():
    """A dygraph AMP overflow-skip step must not count as divergence even
    when the observed loss is non-finite (the optimizer already dropped
    the update by design)."""
    from paddle_tpu import dygraph
    from paddle_tpu.contrib import mixed_precision as mp
    with dygraph.guard():
        layer = dygraph.Linear(2, 1)
        opt = mp.decorate(
            fluid.optimizer.SGD(1e-3, parameter_list=layer.parameters()),
            dtype='float16', decr_every_n_nan_or_inf=1)
        sup = TrainingSupervisor(policy='escalate')
        assert sup.end_of_step(1, 0.5).action == 'ok'
        before = mp.total_overflow_skips()
        x = dygraph.to_variable(np.array([[1e30, 1e30]], 'float32'))
        loss = fluid.layers.reduce_mean(layer(x)) * 1e30
        loss.backward()
        opt.minimize(loss)                        # grads overflow → skip
        layer.clear_gradients()
        assert mp.total_overflow_skips() == before + 1
        # even policy=escalate absorbs it as benign
        v = sup.end_of_step(2, float('inf'))
        assert v.action == 'benign' and v.reason == 'amp_overflow_skip'
        # a later REAL divergence still escalates
        with pytest.raises(TrainingDiverged):
            sup.end_of_step(3, float('nan'))


def test_static_amp_exports_loss_scale_and_skip_counter():
    """Static fp16 path: the in-graph skip counter + loss scale surface
    through overflow_steps()/get_loss_scaling() and the registry export."""
    from paddle_tpu.contrib import mixed_precision as mp
    fluid.seed(3)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('ax', [4], dtype='float32')
        y = L.data('ay', [1], dtype='float32')
        pred = L.fc(x, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        opt = mp.decorate(fluid.optimizer.SGD(learning_rate=1e-3),
                          dtype='float16', init_loss_scaling=2.**15,
                          decr_every_n_nan_or_inf=1)
        opt.minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        assert opt.overflow_steps(scope) == 0
        big = {'ax': np.full((4, 4), 1e4, np.float32),
               'ay': np.zeros((4, 1), np.float32)}
        exe.run(main, feed=big, fetch_list=[loss])
        assert opt.overflow_steps(scope) == 1     # overflow → skipped
        assert opt.get_loss_scaling(scope) < 2.**15   # scale decayed
        export = observability.registry.to_dict()
        assert export['amp_loss_scale']['samples'][0]['value'] == \
            pytest.approx(opt.get_loss_scaling(scope))
        assert _metric('amp_overflow_skipped_steps') >= 1


# ---------------------------------------------------------------------------
# TrainStep spine
# ---------------------------------------------------------------------------

def test_train_step_supervisor_skip_restores_params():
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.dygraph.tape import dispatch_op

    def loss_fn(model, x, y):
        d = dispatch_op('elementwise_sub', {'x': model(x), 'y': y}, {})
        sq = dispatch_op('elementwise_mul', {'x': d, 'y': d}, {})
        return dispatch_op('reduce_mean', {'x': sq}, {})

    with dygraph.guard():
        layer = dygraph.Linear(4, 1)
        opt = fluid.optimizer.SGD(0.1, parameter_list=layer.parameters())
        sup = TrainingSupervisor(policy='skip')
        step = TrainStep(layer, loss_fn, opt, supervisor=sup)
        x = np.random.RandomState(0).randn(8, 4).astype(np.float32)
        y = np.zeros((8, 1), np.float32)
        step(x, y)                                # healthy → captured
        assert sup.last_verdict.action == 'ok'
        healthy = {n: np.asarray(p.value)
                   for n, p in layer.named_parameters()}
        step(x * np.nan, y)                       # poisoned update
        assert sup.last_verdict.action == 'skip'
        for n, p in layer.named_parameters():
            assert np.array_equal(np.asarray(p.value), healthy[n]), n
        # training continues
        step(x, y)
        assert sup.last_verdict.action == 'ok'


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_deadline_tracks_rolling_median():
    wd = Watchdog(floor_s=1.0, factor=10.0, cold_s=300.0, abort=False,
                  poll_s=0.05, dump_dir='/tmp')
    try:
        assert wd.deadline_for('step') == 300.0   # cold: sized for compile
        for _ in range(5):
            wd.observe('step', 0.5)
        assert wd.deadline_for('step') == pytest.approx(5.0)
        for _ in range(10):
            wd.observe('step', 0.01)
        assert wd.deadline_for('step') == 1.0     # floor wins
    finally:
        wd.stop()


def test_watchdog_breach_dumps_stacks_and_counts(tmp_path):
    with observability.telemetry_guard(True):
        wd = Watchdog(floor_s=0.15, cold_s=0.15, abort=False, poll_s=0.03,
                      dump_dir=str(tmp_path))
        try:
            lease = wd.arm('wedged_step')
            import time
            time.sleep(0.5)
            assert lease.breached
            assert len(wd.breaches) == 1
            rec = wd.breaches[0]
            assert rec['name'] == 'wedged_step' and not rec['aborting']
            dump = rec['stack_dump']
            assert os.path.exists(dump)
            text = open(dump).read()
            assert 'Thread' in text or 'File' in text   # real stacks
            assert (tmp_path / 'watchdog_breach.json').exists()
            assert _metric('watchdog_breaches') == 1
            assert _metric('watchdog_stack_dumps') == 1
            # a breached lease fires once, not per poll
            time.sleep(0.1)
            assert len(wd.breaches) == 1
        finally:
            wd.stop()


def test_watchdog_disarm_prevents_breach_and_feeds_history(tmp_path):
    wd = Watchdog(floor_s=0.2, cold_s=0.2, abort=False, poll_s=0.03,
                  dump_dir=str(tmp_path))
    try:
        import time
        for _ in range(3):
            lease = wd.arm('fine_step')
            time.sleep(0.02)
            wd.disarm(lease)
        time.sleep(0.3)                           # idle: no lease armed
        assert not wd.breaches
        assert 0.2 <= wd.deadline_for('fine_step') <= 1.0
    finally:
        wd.stop()


def test_supervisor_holds_train_loop_lease(tmp_path):
    wd = Watchdog(floor_s=5.0, cold_s=5.0, abort=False, poll_s=0.05,
                  dump_dir=str(tmp_path))
    try:
        sup = TrainingSupervisor(policy='off', watchdog=wd)
        sup.end_of_step(1, 1.0)
        assert 'train_loop' in wd._leases
        sup.end_of_step(2, 1.0)
        assert wd._history['train_loop']          # boundary dt observed
        sup.close()
        assert 'train_loop' not in wd._leases
    finally:
        wd.stop()


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_supervisor_metrics_flow_through_registry(tmp_path):
    with observability.telemetry_guard(True):
        sup = TrainingSupervisor(policy='skip',
                                 quarantine_path=str(tmp_path / 'q.jsonl'))
        sup.end_of_step(1, 1.0)
        sup._capture_state = ('scope', {}, None)
        sup.end_of_step(2, float('nan'))
        assert _metric('supervisor_detections') == 1
        assert _metric('supervisor_skipped_updates') == 1
        assert _metric('supervisor_quarantined_batches') == 1
