"""Resilience subsystem (ISSUE 7, docs/RESILIENCE.md): atomic checkpoint
format + torn-file discovery, keep-N retention, IO retry with fault
injection, preemption handling, DataLoader resume cursor, bitwise resume on
both training spines, goodput/lost-work accounting, and the SIGTERM-safe
serving drain. The subprocess `kill -9` crash test lives in
test_crash_resume.py.
"""
import json
import logging
import os
import signal
import time
import urllib.request

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers as L
from paddle_tpu import observability as obs
from paddle_tpu import resilience
from paddle_tpu.core import unique_name
from paddle_tpu.resilience.fault import FaultInjector
from paddle_tpu.resilience.manager import CheckpointManager
from paddle_tpu.resilience.preemption import PreemptionGuard
from paddle_tpu.resilience import snapshot as snap


def _mgr(directory, **kw):
    kw.setdefault('install_signal_handlers', False)
    return CheckpointManager(str(directory), **kw)


# ---------------------------------------------------------------------------
# format: atomic commit, discovery, torn files, retention
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_preserves_values_and_dtypes(tmp_path):
    with _mgr(tmp_path) as mgr:
        arrays = {'scope/w': jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                  'scope/m': jnp.full((3,), 1.5, jnp.bfloat16),
                  'scope/i': np.arange(4, dtype=np.int32)}
        mgr.save(7, arrays, {'note': 'x'})
        mgr.wait()
        got, meta = mgr.restore()
    assert meta['step'] == 7 and meta['note'] == 'x'
    assert np.array_equal(got['scope/w'], np.arange(6).reshape(2, 3))
    assert got['scope/m'].dtype == jnp.bfloat16          # widened + cast back
    assert np.array_equal(got['scope/m'].astype(np.float32), np.full(3, 1.5))
    assert got['scope/i'].dtype == np.int32


def test_latest_skips_torn_payload_with_warning(tmp_path):
    records = []
    h = logging.Handler()
    h.emit = records.append
    logging.getLogger('paddle_tpu.resilience.snapshot').addHandler(h)
    try:
        with _mgr(tmp_path, keep=5) as mgr:
            mgr.save(1, {'w': np.zeros(4)})
            mgr.save(2, {'w': np.ones(4)})
            mgr.wait()
            ck2 = mgr.latest()
            assert ck2.step == 2
            # torn write: truncate the newest payload mid-file
            with open(ck2.payload_path, 'r+b') as f:
                f.truncate(11)
            ck = mgr.latest()
            assert ck is not None and ck.step == 1       # fell back, no crash
        assert any('torn' in r.getMessage() for r in records)
    finally:
        logging.getLogger('paddle_tpu.resilience.snapshot').removeHandler(h)


def test_latest_skips_corrupt_payload_and_orphan_manifest(tmp_path):
    with _mgr(tmp_path, keep=5) as mgr:
        mgr.save(3, {'w': np.zeros(8)})
        mgr.save(4, {'w': np.ones(8)})
        mgr.wait()
        ck4 = mgr.latest()
        # same-size corruption: only the CRC can catch it
        raw = bytearray(open(ck4.payload_path, 'rb').read())
        raw[len(raw) // 2] ^= 0xFF
        with open(ck4.payload_path, 'wb') as f:
            f.write(raw)
        assert mgr.latest().step == 3
        # manifest without payload
        os.unlink(mgr.latest().payload_path)
        assert mgr.latest() is None
    # a payload without a manifest is invisible (not committed)
    snap.atomic_write_bytes(str(tmp_path / 'ckpt-00000009.npz'), b'garbage')
    assert resilience.latest_checkpoint(str(tmp_path)) is None


def test_keep_last_n_retention(tmp_path):
    with _mgr(tmp_path, keep=2) as mgr:
        for s in range(1, 6):
            mgr.save(s, {'w': np.full(4, s, np.float32)})
        mgr.wait()
        steps = [c.step for c in mgr.all_checkpoints()]
    assert steps == [4, 5]
    names = sorted(os.listdir(tmp_path))
    assert not any(n.startswith('ckpt-000000') and n[5:13].isdigit()
                   and int(n[5:13]) < 4 for n in names), names


def test_async_save_overlaps_and_does_not_block(tmp_path):
    """save() with handles must return without materializing: a handle
    whose np.asarray is deliberately slow only blocks the writer thread."""
    class SlowHandle:
        def __init__(self, v, delay):
            self._v, self._delay = v, delay

        def __array__(self, dtype=None, copy=None):
            time.sleep(self._delay)
            return np.asarray(self._v)

    with _mgr(tmp_path) as mgr:
        t0 = time.perf_counter()
        mgr.save(1, {'w': SlowHandle(np.ones(4), 0.3)})
        submit_s = time.perf_counter() - t0
        assert submit_s < 0.1, f'save() stalled {submit_s:.3f}s'
        mgr.wait()
        assert mgr.latest().step == 1


# ---------------------------------------------------------------------------
# fault injection + retry/backoff
# ---------------------------------------------------------------------------

def test_fault_spec_parsing():
    fi = FaultInjector('kill@step=8, io_fail@times=2')
    assert fi.active and fi._kill_step == 8 and fi._io_times == 2
    assert not FaultInjector('').active
    with pytest.raises(ValueError):
        FaultInjector('explode@step=1')
    with pytest.raises(ValueError):
        FaultInjector('kill=3')


def test_io_failures_are_retried_with_backoff(tmp_path):
    with obs.telemetry_guard(True):
        obs.reset()
        mgr = _mgr(tmp_path, retries=3, backoff_s=0.01)
        mgr._fault = FaultInjector('io_fail@times=2')
        mgr.save(5, {'w': np.ones(3)})
        mgr.wait()                                 # no raise: retries won
        assert mgr.latest().step == 5
        m = obs.registry.to_dict()
        assert sum(s['value'] for s in m['checkpoint_retries']['samples']) == 2
        assert sum(s['value']
                   for s in m['fault_injections']['samples']) == 2
        mgr.close()


def test_io_failures_exhausting_retries_surface_on_wait(tmp_path):
    mgr = _mgr(tmp_path, retries=1, backoff_s=0.01)
    mgr._fault = FaultInjector('io_fail@times=5')
    mgr.save(5, {'w': np.ones(3)})
    with pytest.raises(OSError):
        mgr.wait()
    assert mgr.latest() is None                    # nothing half-committed
    mgr.close()


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------

def test_preemption_triggers_final_checkpoint_and_stop(tmp_path):
    with _mgr(tmp_path, every_n_steps=100) as mgr:     # cadence never due
        state = {'w': np.arange(3, dtype=np.float32)}
        assert mgr.end_of_step(1, lambda: (state, {})) is False
        mgr.request_preemption()
        assert mgr.end_of_step(2, lambda: (state, {})) is True
        ck = mgr.latest()
        assert ck is not None and ck.step == 2
        assert ck.meta['preempted'] is True


def test_sigterm_sets_preemption_flag():
    guard = PreemptionGuard().install()
    try:
        assert guard.installed and not guard.requested
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if guard.requested:
                break
            time.sleep(0.01)
        assert guard.requested
    finally:
        guard.uninstall()


def test_fault_kill_hook_runs_at_step_boundary(tmp_path):
    """kill@step must target exactly its step (the real SIGKILL is proven
    in test_crash_resume.py; here we only assert the trigger precision by
    pointing the injector at a step that never comes)."""
    with _mgr(tmp_path, every_n_steps=100) as mgr:
        mgr._fault = FaultInjector('kill@step=999')
        for s in range(1, 5):
            assert mgr.end_of_step(s, lambda: ({}, {})) is False


# ---------------------------------------------------------------------------
# DataLoader cursor
# ---------------------------------------------------------------------------

def _epoch_batches(epoch, n=5):
    rng = np.random.RandomState(50 + epoch)
    return [(rng.randn(2, 4).astype(np.float32),) for _ in range(n)]


def _make_loader():
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = L.data('cur_x', [4], dtype='float32')
        loader = fluid.DataLoader.from_generator(feed_list=[x], capacity=2)
    loader.set_batch_generator(lambda: iter(_epoch_batches(loader.epoch)))
    return loader


def test_loader_cursor_tracks_and_resumes_mid_epoch():
    ref = []
    loader = _make_loader()
    for _ in range(2):
        for b in loader():
            ref.append(np.asarray(b['cur_x']).tobytes())
    assert loader.epoch == 2 and len(ref) == 10

    loader2 = _make_loader()
    seen, cursor = [], None
    it = iter(loader2())
    for i in range(3):
        seen.append(np.asarray(next(it)['cur_x']).tobytes())
    cursor = loader2.state_dict()
    assert cursor == {'epoch': 0, 'batch': 3}

    # "new process": fresh loader, restore the cursor, consume the rest
    loader3 = _make_loader()
    loader3.set_state_dict(cursor)
    for _ in range(2):
        for b in loader3():
            seen.append(np.asarray(b['cur_x']).tobytes())
        if len(seen) >= 10:
            break
    assert seen == ref


def test_loader_cursor_epoch_boundary_resume():
    ref = []
    loader = _make_loader()
    for _ in range(2):
        for b in loader():
            ref.append(np.asarray(b['cur_x']).tobytes())
    # cursor exactly at an exhausted epoch (consumed all, not rolled over)
    loader2 = _make_loader()
    it = iter(loader2())
    got = [np.asarray(next(it)['cur_x']).tobytes() for _ in range(5)]
    cursor = loader2.state_dict()
    assert cursor == {'epoch': 0, 'batch': 5}
    loader3 = _make_loader()
    loader3.set_state_dict(cursor)
    for _ in range(2):
        for b in loader3():
            got.append(np.asarray(b['cur_x']).tobytes())
        if len(got) >= 10:
            break
    assert got == ref


# ---------------------------------------------------------------------------
# bitwise resume: executor spine (in-process; subprocess version with a real
# kill -9 lives in test_crash_resume.py)
# ---------------------------------------------------------------------------

def _build_static():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('rx', [8], dtype='float32')
        y = L.data('ry', [1], dtype='float32')
        h = L.fc(x, size=16, act='relu')
        h = L.dropout(h, dropout_prob=0.3)
        pred = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def _static_batches(epoch, n=6):
    rng = np.random.RandomState(100 + epoch)
    return [(rng.randn(4, 8).astype(np.float32),
             rng.randn(4, 1).astype(np.float32)) for _ in range(n)]


def _run_static(total_steps, ckpt_dir=None, resume=False, every=3, **mgr_kw):
    losses = {}
    with unique_name.guard():
        fluid.seed(1234)
        main, startup, loss = _build_static()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            blk = main.global_block()
            loader = fluid.DataLoader.from_generator(
                feed_list=[blk.var('rx'), blk.var('ry')], capacity=4)
            loader.set_batch_generator(
                lambda: iter(_static_batches(loader.epoch)))
            step, mgr = 0, None
            if ckpt_dir:
                mgr = _mgr(ckpt_dir, every_n_steps=every, keep=2, **mgr_kw)
                if resume:
                    got = mgr.restore()
                    if got is not None:
                        arrays, meta = got
                        resilience.restore_training_state(
                            arrays, meta, executor=exe, program=main,
                            scope=scope, loader=loader)
                        step = meta['step']
            while step < total_steps:
                for batch in loader():
                    lv = exe.run(main, feed=batch, fetch_list=[loss])[0]
                    step += 1
                    losses[step] = np.asarray(lv).tobytes()
                    if mgr is not None:
                        mgr.end_of_step(
                            step,
                            lambda: resilience.capture_training_state(
                                executor=exe, program=main, scope=scope,
                                loader=loader))
                    if step >= total_steps:
                        break
            if mgr is not None:
                mgr.wait()
                mgr.close()
    return losses


def test_executor_spine_bitwise_resume(tmp_path):
    """Adam + dropout + mid-epoch cursor: stop at 7 (checkpoints at 3, 6),
    resume, and the remaining trajectory is BITWISE the uninterrupted one —
    RNG salts, optimizer slots, and the data stream all line up."""
    ref = _run_static(10)
    d = str(tmp_path / 'ck')
    first = _run_static(7, ckpt_dir=d)
    assert all(first[k] == ref[k] for k in first)
    second = _run_static(10, ckpt_dir=d, resume=True)
    assert sorted(second) == [7, 8, 9, 10]          # resumed from step 6
    assert all(second[k] == ref[k] for k in second), \
        'resumed loss trajectory is not bitwise-identical'


@pytest.mark.parametrize('async_save', [True, False],
                         ids=['async', 'blocking'])
def test_checkpointing_observes_and_never_perturbs_the_losses(
        tmp_path, async_save):
    """One loop from one initial state, bare and checkpointing every 3
    steps (the non-blocking donation-protected capture with the background
    writer, and the blocking commit on the calling thread): the losses are
    BITWISE the bare loop's, and the cadence's checkpoints are on disk."""
    ref = _run_static(12)
    d = str(tmp_path / 'ck')
    got = _run_static(12, ckpt_dir=d, every=3, async_save=async_save)
    assert got == ref, 'checkpointing changed the computation'
    with _mgr(d) as mgr:
        assert [c.step for c in mgr.all_checkpoints()] == [9, 12]  # keep=2


def test_executor_snapshot_is_donation_protected_until_materialized():
    """snapshot_persistables registers window protection: the executor must
    not donate a pending handle's buffer (the snapshot's integrity), and
    protection drains once the writer materializes."""
    with unique_name.guard():
        fluid.seed(0)
        main, startup, loss = _build_static()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            # warm the compiled step, then take the point-in-time reference
            x, y = _static_batches(0)[0]
            exe.run(main, feed={'rx': x, 'ry': y}, fetch_list=[loss])
            pre = {n: np.asarray(scope.find(n)) for n in
                   (v.name for v in main.list_vars() if v.persistable)}
            handles = exe.snapshot_persistables(main, scope)
            assert set(exe._window.protected_names()) == set(handles)
            # run a step while the snapshot is pending: donation must skip
            # the protected buffers, so materializing afterwards still
            # yields the PRE-step values (without protection the donated
            # buffers would be invalidated or overwritten in place)
            x2, y2 = _static_batches(0)[1]
            exe.run(main, feed={'rx': x2, 'ry': y2}, fetch_list=[loss])
            mats = {n: np.asarray(h) for n, h in handles.items()}
            for n, v in pre.items():
                assert np.array_equal(mats[n], v), \
                    f'snapshot of {n} was clobbered by the next step'
            # materialized handles drop their protection → donation resumes
            assert exe._window.protected_names() == set()


# ---------------------------------------------------------------------------
# bitwise resume: fused TrainStep spine
# ---------------------------------------------------------------------------

def _make_trainstep():
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.nn import Linear
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.dygraph.tape import dispatch_op
    with unique_name.guard():
        fluid.seed(7)

        class M(dygraph.Layer):
            def __init__(self):
                super().__init__()
                self.l1 = Linear(8, 16, act='relu')
                self.l2 = Linear(16, 1)

            def forward(self, x):
                return self.l2(self.l1(x))

        m = M()
        opt = fluid.optimizer.Adam(learning_rate=1e-2,
                                   parameter_list=list(m.parameters()))

        def loss_fn(layer, x, y):
            d = dispatch_op('elementwise_sub', {'x': layer(x), 'y': y}, {})
            sq = dispatch_op('elementwise_mul', {'x': d, 'y': d}, {})
            return dispatch_op('reduce_mean', {'x': sq}, {})

        return TrainStep(m, loss_fn, opt)


def test_trainstep_bitwise_resume_through_checkpoint(tmp_path):
    from paddle_tpu import dygraph
    rng = np.random.RandomState(0)
    data = [(rng.randn(4, 8).astype('f4'), rng.randn(4, 1).astype('f4'))
            for _ in range(10)]
    with dygraph.guard():
        ts_ref = _make_trainstep()
        ref = [np.asarray(ts_ref(x, y)).tobytes() for x, y in data]

        ts_a = _make_trainstep()
        half = [np.asarray(ts_a(x, y)).tobytes() for x, y in data[:5]]
        assert half == ref[:5]
        with _mgr(tmp_path) as mgr:
            arrays, meta = resilience.capture_training_state(
                train_step=ts_a)
            mgr.save(5, arrays, meta)
            mgr.wait()
            # donation is on by default: the snapshot cloned on-device, so
            # continuing to train must not perturb the checkpoint
            np.asarray(ts_a(*data[5]))
            got, got_meta = mgr.restore()

        ts_b = _make_trainstep()
        resilience.restore_training_state(got, got_meta, train_step=ts_b)
        rest = [np.asarray(ts_b(x, y)).tobytes() for x, y in data[5:]]
    assert rest == ref[5:], \
        'TrainStep resume is not bitwise-identical'


# ---------------------------------------------------------------------------
# goodput / lost-work accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('died_at, every', [(7, 5), (13, 5)])
def test_goodput_books_lost_work_on_restart(tmp_path, died_at, every):
    """A run that checkpoints every K steps and dies at step N restores
    step K*(N//K) and books N mod K lost steps from the heartbeat."""
    restored, lost = every * (died_at // every), died_at % every
    with obs.telemetry_guard(True):
        obs.reset()
        mgr = _mgr(tmp_path, every_n_steps=every, keep=2)
        state = {'w': np.ones(2)}
        for s in range(1, died_at + 1):   # heartbeat runs past the last save
            mgr.end_of_step(s, lambda: (state, {}))
        mgr.wait()
        # "crash": a new manager (new incarnation) restores
        mgr2 = _mgr(tmp_path, every_n_steps=every, keep=2)
        arrays, meta = mgr2.restore()
        assert meta['step'] == restored
        assert mgr2.goodput.lost_steps == lost     # replayed after restore
        assert mgr2.goodput.restarts == 1
        m = obs.registry.to_dict()
        assert sum(s['value'] for s in m['restarts_total']['samples']) == 1
        assert sum(s['value']
                   for s in m['restart_lost_steps']['samples']) == lost
        g = meta['goodput']
        assert g['steps'] == restored and g['productive_s'] >= 0
        mgr.close()
        mgr2.close()


def test_checkpoint_metrics_flow_through_registry(tmp_path):
    with obs.telemetry_guard(True):
        obs.reset()
        with _mgr(tmp_path, every_n_steps=2) as mgr:
            state = {'w': np.ones((64,), np.float32)}
            for s in range(1, 5):
                mgr.end_of_step(s, lambda: (state, {}))
            mgr.wait()
        m = obs.registry.to_dict()
        assert sum(s['value'] for s in m['checkpoint_saves']['samples']) == 2
        assert sum(s['value'] for s in m['checkpoint_bytes']['samples']) > 0
        stall = m['checkpoint_stall_seconds']['samples'][0]
        assert stall['count'] == 2
        assert any(s['value'] == 4 for s in
                   m['checkpoint_last_step']['samples'])
        assert 'goodput_ratio' in m


# ---------------------------------------------------------------------------
# serving: SIGTERM → draining healthz → graceful close, with timeout cap
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def _serving_model(tmp_path_factory):
    d = str(tmp_path_factory.mktemp('srvmodel'))
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = L.data('sx', [4], dtype='float32')
            out = L.fc(x, size=2)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            fluid.io.save_inference_model(d, ['sx'], [out], exe,
                                          main_program=main)
    return d


def test_serving_sigterm_drains_then_stops(_serving_model, monkeypatch):
    from paddle_tpu.serving.engine import InferenceEngine
    from paddle_tpu.serving.server import ServingServer
    eng = InferenceEngine(_serving_model, max_batch_size=2)
    real = eng.run_batch
    monkeypatch.setattr(
        eng, 'run_batch',
        lambda feed, nrows=None: (time.sleep(0.15), real(feed, nrows))[1])
    srv = ServingServer(eng, port=0, batch_timeout_ms=0).start()
    srv.install_signal_handlers()
    try:
        url = f'http://127.0.0.1:{srv.port}'
        assert urllib.request.urlopen(url + '/healthz').status == 200
        futs = [srv.batcher.submit({'sx': [[float(i)] * 4]})
                for i in range(4)]                  # ~0.6s of queued work
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        code = None
        while time.time() < deadline:
            try:
                urllib.request.urlopen(url + '/healthz', timeout=1)
            except urllib.error.HTTPError as e:
                code = e.code
                break
            except OSError:
                break                   # listener already gone: drained fast
            time.sleep(0.02)
        if code is not None:
            assert code == 503          # draining window observed
        for f in futs:                  # graceful: everything admitted runs
            assert len(f.result(10)) == 1
        for _ in range(100):
            if srv.batcher.closed:
                break
            time.sleep(0.05)
        assert srv.batcher.closed
    finally:
        srv.uninstall_signal_handlers()
        srv.shutdown()


def test_serving_drain_timeout_escalates_to_fail_fast(_serving_model,
                                                     monkeypatch):
    from paddle_tpu.serving.batcher import MicroBatcher
    from paddle_tpu.serving.errors import EngineClosed
    from paddle_tpu.serving.engine import InferenceEngine
    from paddle_tpu.serving.server import ServingServer
    eng = InferenceEngine(_serving_model, max_batch_size=1)
    real = eng.run_batch
    monkeypatch.setattr(
        eng, 'run_batch',
        lambda feed, nrows=None: (time.sleep(0.4), real(feed, nrows))[1])
    srv = ServingServer(eng, port=0, batch_timeout_ms=0,
                        queue_depth=64).start()
    futs = [srv.batcher.submit({'sx': [[1.0] * 4]}) for _ in range(8)]
    monkeypatch.setenv('PADDLE_TPU_DRAIN_TIMEOUT_S', '0.5')
    t0 = time.perf_counter()
    srv.shutdown(drain=True)            # ~3.2s of queued work vs 0.5s cap
    elapsed = time.perf_counter() - t0
    assert elapsed < 3.0, f'drain was not capped ({elapsed:.1f}s)'
    outcomes = {'ok': 0, 'closed': 0}
    for f in futs:
        try:
            f.result(5)
            outcomes['ok'] += 1
        except EngineClosed:
            outcomes['closed'] += 1
    assert outcomes['closed'] > 0, outcomes   # tail failed fast, not hung
    assert outcomes['ok'] + outcomes['closed'] == 8
