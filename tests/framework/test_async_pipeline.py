"""Async train-loop pipeline (executor.py + core/fetch_handle.py):
non-blocking FetchHandles, K-steps-in-flight window, snapshot semantics
under donation, zero-copy staged feeds, and the FLAGS_check_nan_inf
interaction. These tests pin the SEMANTICS; the overlap itself is a time,
and a time is measured on the chip (benchmark/)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers as L
from paddle_tpu import observability as obs
from paddle_tpu.compiler import CompiledProgram, ExecutionStrategy
from paddle_tpu.core.fetch_handle import (FetchHandle,
                                          resolve_inflight_steps)


def _mlp_prog(prefix, width=32):
    """MNIST-shaped MLP regression (RNG-free, so parity is bitwise)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data(prefix + 'x', [16], dtype='float32')
        y = L.data(prefix + 'y', [1], dtype='float32')
        h = L.fc(x, size=width, act='relu')
        h = L.fc(h, size=width, act='relu')
        pred = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feeds(prefix, n, bs=8, seed=0):
    rng = np.random.RandomState(seed)
    return [{prefix + 'x': rng.randn(bs, 16).astype(np.float32),
             prefix + 'y': rng.randn(bs, 1).astype(np.float32)}
            for _ in range(n)]


def _loop(main, startup, loss, feeds, fetch_list=None):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        out = [exe.run(main, feed=f, fetch_list=fetch_list or [loss])
               for f in feeds]
    return exe, out


# ---------------------------------------------------------------------------
# mode resolution
# ---------------------------------------------------------------------------

def test_resolve_inflight_env_and_strategy(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_ASYNC', raising=False)
    assert resolve_inflight_steps() == 0
    es = ExecutionStrategy()
    assert es.num_inflight_steps == 1          # sync default
    assert resolve_inflight_steps(es) == 0
    es.num_inflight_steps = 3
    assert resolve_inflight_steps(es) == 3
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '1')
    assert resolve_inflight_steps() == 2       # default double buffer
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '4')
    assert resolve_inflight_steps(es) == 4     # env beats strategy
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '0')
    assert resolve_inflight_steps(es) == 0     # env 0 pins sync


def test_async_env_zero_restores_numpy_results(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '0')
    main, startup, loss = _mlp_prog('az_')
    _, out = _loop(main, startup, loss, _feeds('az_', 2))
    assert all(isinstance(r[0], np.ndarray) for r in out)


# ---------------------------------------------------------------------------
# bitwise parity + window semantics
# ---------------------------------------------------------------------------

def test_sync_async_bitwise_parity(monkeypatch):
    main, startup, loss = _mlp_prog('pa_')
    feeds = _feeds('pa_', 6)
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '0')
    _, sync_out = _loop(main, startup, loss, feeds)
    sync_losses = [r[0] for r in sync_out]
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '2')
    _, async_out = _loop(main, startup, loss, feeds)
    async_losses = [np.asarray(r[0]) for r in async_out]
    for s, a in zip(sync_losses, async_losses):
        assert s.tobytes() == a.tobytes()


def test_one_executor_sync_then_async_behind_a_slow_reader(monkeypatch):
    """The loop the pipeline exists for: a host-bound reader (a sleep per
    batch) feeding one Executor, first sync and then with K=2 steps in
    flight from the same restored state. The pipeline reorders HOST work
    only: every loss is bitwise the sync loop's."""
    import time
    import jax.numpy as jnp
    main, startup, loss = _mlp_prog('sr_')
    feeds = _feeds('sr_', 8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        state0 = {v.name: np.asarray(scope.find(v.name))
                  for v in main.list_vars() if v.persistable}

        def loop(mode):
            monkeypatch.setenv('PADDLE_TPU_ASYNC', mode)
            for name, value in state0.items():
                scope.set(name, jnp.asarray(value))
            out = []
            for f in feeds:
                time.sleep(0.002)                  # the reader's I/O
                out.append(exe.run(main, feed=f, fetch_list=[loss])[0])
            return out

        sync = loop('0')
        pipelined = loop('2')
        assert all(isinstance(h, FetchHandle) for h in pipelined)
        drained = [np.asarray(h).tobytes() for h in pipelined]
    assert drained == [np.asarray(v).tobytes() for v in sync]


def test_inflight_window_never_exceeds_k(monkeypatch):
    k = 2
    monkeypatch.setenv('PADDLE_TPU_ASYNC', str(k))
    main, startup, loss = _mlp_prog('wk_')
    feeds = _feeds('wk_', 8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        handles = []
        for f in feeds:
            h = exe.run(main, feed=f, fetch_list=[loss])[0]
            assert isinstance(h, FetchHandle)
            handles.append(h)
            # observable window bound: dispatch of step N waits for step
            # N-K, so every handle older than the last K is finished
            for old in handles[:-k]:
                assert old.done
            assert len(exe._window) <= k
    # drain is the user's read
    vals = [float(h) for h in handles]
    assert all(np.isfinite(v) for v in vals)


def test_async_uses_fresh_steady_state_each_run(monkeypatch):
    # regression guard: results must come from the run that produced them
    # (no off-by-one in the window) — fetch a deterministic function of
    # the feed alongside the loss
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '2')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('fr_x', [4], dtype='float32')
        out = L.scale(x, scale=2.0)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        handles = []
        feeds = [np.full((2, 4), i, np.float32) for i in range(5)]
        for f in feeds:
            handles.append(exe.run(main, feed={'fr_x': f},
                                   fetch_list=[out])[0])
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(np.asarray(h), feeds[i] * 2.0)


# ---------------------------------------------------------------------------
# snapshot semantics
# ---------------------------------------------------------------------------

def test_handle_snapshot_survives_later_donated_runs(monkeypatch):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('sn_x', [16], dtype='float32')
        y = L.data('sn_y', [1], dtype='float32')
        h = L.fc(x, size=32, act='relu',
                 param_attr=fluid.ParamAttr(name='sn_w0'))
        pred = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    feeds = _feeds('sn_', 5)

    monkeypatch.setenv('PADDLE_TPU_ASYNC', '0')
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ref_w0 = exe.run(main, feed=feeds[0], fetch_list=[loss, 'sn_w0'])[1]

    monkeypatch.setenv('PADDLE_TPU_ASYNC', '2')
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor()
        exe2.run(startup)
        h0 = exe2.run(main, feed=feeds[0], fetch_list=[loss, 'sn_w0'])
        # the pending param fetch pins its name out of donation
        h0[1].block_until_ready()
        assert 'sn_w0' in exe2._window.protected_names()
        # later steps update sn_w0 (and would donate it); mix in sync
        # donated runs too — the pending handle must stay protected
        for i, f in enumerate(feeds[1:]):
            monkeypatch.setenv('PADDLE_TPU_ASYNC', '2' if i % 2 else '0')
            exe2.run(main, feed=f, fetch_list=[loss])
        got = h0[1].numpy()
        assert got.tobytes() == ref_w0.tobytes()
        # materialization releases the protection
        assert 'sn_w0' not in exe2._window.protected_names()


def test_return_numpy_false_handle_snapshot(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_ASYNC', raising=False)
    main, startup, loss = _mlp_prog('rn2_')
    feeds = _feeds('rn2_', 3)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        h = exe.run(main, feed=feeds[0], fetch_list=[loss],
                    return_numpy=False)[0]
        first = np.asarray(h)
        for f in feeds[1:]:
            exe.run(main, feed=f, fetch_list=[loss])
        # cached materialization is stable
        assert np.asarray(h).tobytes() == first.tobytes()


# ---------------------------------------------------------------------------
# knob plumbing: ExecutionStrategy through CompiledProgram
# ---------------------------------------------------------------------------

def test_num_inflight_steps_strategy_drives_async(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_ASYNC', raising=False)
    main, startup, loss = _mlp_prog('es_')
    es = ExecutionStrategy()
    es.num_inflight_steps = 2
    cp = CompiledProgram(main).with_data_parallel(loss_name=loss.name,
                                                 exec_strategy=es)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        r = exe.run(cp, feed=_feeds('es_', 1)[0], fetch_list=[loss])[0]
        assert isinstance(r, FetchHandle)
        assert np.isfinite(float(r))


# ---------------------------------------------------------------------------
# zero-copy staged feeds
# ---------------------------------------------------------------------------

def test_staged_feed_passthrough_no_second_device_put(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_ASYNC', raising=False)
    main, startup, loss = _mlp_prog('st_')
    feeds = _feeds('st_', 4)
    x = main.global_block().var('st_x')
    y = main.global_block().var('st_y')
    loader = fluid.DataLoader.from_generator(feed_list=[x, y], capacity=4)
    loader.set_batch_generator(
        lambda: iter([(f['st_x'], f['st_y']) for f in feeds]))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        with obs.telemetry_guard(True):
            obs.reset()
            for batch in loader():
                exe.run(main, feed=batch, fetch_list=[loss])
            m = obs.registry.to_dict()
    staged = sum(s['value'] for s in m['dataloader_staged_bytes']['samples'])
    passed = sum(s['value']
                 for s in m['executor_feed_passthrough_bytes']['samples'])
    # every byte the producer staged went through without a second
    # device_put (the executor recognized the committed arrays)
    assert staged > 0
    assert passed == staged


def test_numpy_feeds_are_not_counted_as_passthrough():
    main, startup, loss = _mlp_prog('np_')
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        with obs.telemetry_guard(True):
            obs.reset()
            exe.run(main, feed=_feeds('np_', 1)[0], fetch_list=[loss])
            m = obs.registry.to_dict()
    assert 'executor_feed_passthrough_bytes' not in m


# ---------------------------------------------------------------------------
# FLAGS_check_nan_inf under pipelining
# ---------------------------------------------------------------------------

def test_check_nan_inf_moves_to_materialization_in_async(monkeypatch):
    import jax
    from paddle_tpu import debugging
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '2')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('nn_x', [4], dtype='float32')
        out = L.reduce_mean(L.sqrt(x))        # NaN for negative feeds
    debugging.enable_check_nan_inf(True)
    # isolate the fetch-scan path: jax_debug_nans raises from inside the
    # computation and is mode-independent
    jax.config.update('jax_debug_nans', False)
    try:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            with obs.telemetry_guard(True):
                obs.reset()
                h = exe.run(main,
                            feed={'nn_x': np.full((2, 4), -1.0, np.float32)},
                            fetch_list=[out])[0]
                # the run itself does NOT raise (no per-step sync) ...
                assert isinstance(h, FetchHandle)
                # ... the scan fires at the read
                with pytest.raises(FloatingPointError, match='check_nan_inf'):
                    h.numpy()
                m = obs.registry.to_dict()
        nf = sum(s['value'] for s in m['nonfinite_detections']['samples'])
        assert nf >= 1
    finally:
        debugging.enable_check_nan_inf(False)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_async_metrics_recorded(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '2')
    main, startup, loss = _mlp_prog('tm_')
    feeds = _feeds('tm_', 3)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        with obs.telemetry_guard(True):
            obs.reset()
            hs = [exe.run(main, feed=f, fetch_list=[loss])[0]
                  for f in feeds]
            [h.numpy() for h in hs]
            m = obs.registry.to_dict()
    gauge = m['executor_inflight_steps']['samples'][0]['value']
    assert 0 <= gauge <= 2
    hist = m['fetch_materialize_seconds']['samples'][0]
    assert hist['count'] == len(feeds)


# ---------------------------------------------------------------------------
# TrainStep async_fetch
# ---------------------------------------------------------------------------

def _mse(m, x, y):
    from paddle_tpu.dygraph.tape import dispatch_op
    d = dispatch_op('elementwise_sub', {'x': m(x), 'y': y}, {})
    sq = dispatch_op('elementwise_mul', {'x': d, 'y': d}, {})
    return dispatch_op('reduce_mean', {'x': sq}, {})


def test_train_step_async_fetch_parity(monkeypatch):
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.dygraph.nn import Linear
    from paddle_tpu.core.random import seed as set_seed
    monkeypatch.delenv('PADDLE_TPU_ASYNC', raising=False)
    rng = np.random.RandomState(0)
    batches = [(rng.randn(4, 8).astype(np.float32),
                rng.randn(4, 1).astype(np.float32)) for _ in range(4)]

    def run(**kw):
        with dygraph.guard():
            set_seed(7)
            model = Linear(8, 1)
            opt = fluid.optimizer.SGD(0.1,
                                      parameter_list=model.parameters())
            step = TrainStep(model, _mse, opt, **kw)
            return [step(x, y) for x, y in batches]

    sync_losses = [np.asarray(v) for v in run()]
    async_out = run(async_fetch=True, num_inflight_steps=2)
    assert all(isinstance(h, FetchHandle) for h in async_out)
    async_losses = [h.numpy() for h in async_out]
    for s, a in zip(sync_losses, async_losses):
        assert s.tobytes() == a.tobytes()

    # PADDLE_TPU_ASYNC=0 overrides the constructor opt-in
    monkeypatch.setenv('PADDLE_TPU_ASYNC', '0')
    plain = run(async_fetch=True)
    assert not any(isinstance(v, FetchHandle) for v in plain)
