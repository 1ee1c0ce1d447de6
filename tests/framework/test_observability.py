"""Telemetry subsystem (paddle_tpu/observability/, docs/OBSERVABILITY.md):
metrics registry semantics + Prometheus round-trip, chrome-trace span trees,
spine instrumentation (executor phases, donation counts, compile-cache
hit/miss, DataLoader starvation, nonfinite detections), the disabled-path
zero-work guard, and the profiler kernel-cache stats-reset regression."""
import json
import math
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import debugging, dygraph, layers, observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.tracer import StepTracer


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Telemetry off + empty registry/tracer around every test."""
    old = obs._ENABLED
    obs._ENABLED = False
    obs.reset()
    yield
    obs._ENABLED = old
    obs.reset()


def _run_tiny_program(steps=2, feed_x=None):
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('ob_x', shape=[4], dtype='float32')
        y = layers.data('ob_y', shape=[1], dtype='float32')
        loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(start)
    out = None
    for _ in range(steps):
        out, = exe.run(main, feed={
            'ob_x': feed_x if feed_x is not None
            else np.ones((8, 4), 'float32'),
            'ob_y': np.zeros((8, 1), 'float32')}, fetch_list=[loss])
    return out


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter('events', 'help text')
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = reg.gauge('depth')
    g.set(7)
    g.set(3)
    assert g.value == 3
    h = reg.histogram('lat_seconds', bounds=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0, 0.05):
        h.observe(v)
    s = h.labels().sample()
    assert s['buckets'] == [1, 2, 1, 1]       # last bucket = +Inf overflow
    assert s['count'] == 5 and s['min'] == 0.005 and s['max'] == 5.0
    assert abs(s['sum'] - 5.605) < 1e-9
    # same name returns the same metric; kind mismatch is an error
    assert reg.counter('events') is c
    with pytest.raises(TypeError):
        reg.gauge('events')


def test_labeled_series_are_distinct():
    reg = MetricsRegistry()
    c = reg.counter('ops')
    c.labels(op='matmul').inc(3)
    c.labels(op='relu').inc()
    d = reg.to_dict()['ops']
    by_op = {s['labels']['op']: s['value'] for s in d['samples']}
    assert by_op == {'matmul': 3, 'relu': 1}


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter('n')
    h = reg.histogram('h', bounds=(1.0,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.labels().sample()['count'] == 8000


def _parse_prometheus(text):
    """Tiny exposition-format parser: name{labels} value per sample."""
    types, samples = {}, {}
    for line in text.splitlines():
        if line.startswith('# TYPE'):
            _, _, name, kind = line.split()
            types[name] = kind
        elif line and not line.startswith('#'):
            metric, value = line.rsplit(' ', 1)
            samples[metric] = float(value)
    return types, samples


def test_prometheus_exposition_round_trips():
    reg = MetricsRegistry()
    reg.counter('steps', 'steps run').inc(4)
    reg.gauge('queue_depth').labels(loader='a').set(2.5)
    h = reg.histogram('wait_seconds', bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)
    types, samples = _parse_prometheus(reg.prometheus_text())
    assert types['paddle_tpu_steps'] == 'counter'
    assert types['paddle_tpu_queue_depth'] == 'gauge'
    assert types['paddle_tpu_wait_seconds'] == 'histogram'
    assert samples['paddle_tpu_steps'] == 4
    assert samples['paddle_tpu_queue_depth{loader="a"}'] == 2.5
    # histogram buckets are CUMULATIVE; +Inf equals _count
    assert samples['paddle_tpu_wait_seconds_bucket{le="0.1"}'] == 1
    assert samples['paddle_tpu_wait_seconds_bucket{le="1.0"}'] == 2
    assert samples['paddle_tpu_wait_seconds_bucket{le="+Inf"}'] == 3
    assert samples['paddle_tpu_wait_seconds_count'] == 3
    assert abs(samples['paddle_tpu_wait_seconds_sum'] - 50.55) < 1e-9


def test_collectors_run_at_export():
    reg = MetricsRegistry()
    reg.register_collector(lambda: reg.gauge('snap').set(42))
    assert reg.to_dict()['snap']['samples'][0]['value'] == 42


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_chrome_trace_span_tree():
    tr = StepTracer()
    with tr.span('parent', step=1):
        with tr.span('child_a'):
            pass
        with tr.span('child_b'):
            pass
    doc = json.loads(tr.chrome_trace_json())
    events = {e['name']: e for e in doc['traceEvents']}
    assert set(events) == {'parent', 'child_a', 'child_b'}
    p = events['parent']
    assert p['ph'] == 'X' and p['args'] == {'step': 1}
    # tree = [ts, ts+dur] containment on one tid (how Perfetto nests X events)
    for name in ('child_a', 'child_b'):
        c = events[name]
        assert c['tid'] == p['tid']
        assert p['ts'] <= c['ts']
        assert c['ts'] + c['dur'] <= p['ts'] + p['dur'] + 1e-3


def test_tracer_bounds_events():
    tr = StepTracer(max_events=3)
    for i in range(5):
        with tr.span(f's{i}'):
            pass
    assert len(tr) == 3 and tr.dropped == 2
    assert json.loads(tr.chrome_trace_json())['otherData'][
        'dropped_events'] == 2


# ---------------------------------------------------------------------------
# disabled path: zero telemetry work (the ≤3% eager-step budget is met
# structurally — one bool check per dispatch, nothing else runs)
# ---------------------------------------------------------------------------

def test_disabled_dispatch_does_no_telemetry_work(monkeypatch):
    def boom(*a, **k):
        raise AssertionError('telemetry touched while disabled')

    monkeypatch.setattr(obs, 'record_op_dispatch', boom)
    monkeypatch.setattr(obs.tracer, 'span', boom)
    with dygraph.guard():
        t = dygraph.to_variable(np.ones((2, 2), np.float32))
        dygraph.dispatch_op('scale', {'x': t}, {'scale': 2.0})
    assert obs.registry.to_dict().get('tape_dispatch_seconds') is None
    assert len(obs.tracer) == 0


def test_disabled_executor_records_nothing():
    _run_tiny_program(steps=1)
    d = obs.registry.to_dict()
    assert 'executor_steps' not in d
    assert len(obs.tracer) == 0
    assert obs.span('x') is obs.NULL_SPAN      # shared no-op, no allocation


# ---------------------------------------------------------------------------
# spine instrumentation (telemetry on)
# ---------------------------------------------------------------------------

def test_executor_phases_and_counters(tmp_path):
    with obs.telemetry_guard(True, directory=str(tmp_path)):
        _run_tiny_program(steps=2)
        d = obs.registry.to_dict()
        trace = obs.tracer.snapshot()

    def val(name):
        return d[name]['samples'][0]['value']

    assert val('executor_steps') == 2
    assert val('compile_cache_misses') == 1     # program compiled once
    assert val('compile_cache_hits') == 1       # second step reuses it
    assert val('executor_donated_buffers') > 0  # params/slots donated
    assert val('executor_feed_bytes') > 0 and val('executor_fetch_bytes') > 0
    assert d['executor_compile_seconds']['samples'][0]['count'] == 1
    names = [e['name'] for e in trace['traceEvents']]
    for phase in ('executor/run', 'executor/prepare', 'executor/lower',
                  'executor/execute', 'executor/fetch'):
        assert phase in names, names
    # one complete span tree per run (startup + 2 steps), phases nested
    # under executor/run by [ts, ts+dur] containment on the same tid
    runs = [e for e in trace['traceEvents'] if e['name'] == 'executor/run']
    assert len(runs) == 3
    execs = [e for e in trace['traceEvents']
             if e['name'] == 'executor/execute']
    assert len(execs) == 2
    assert all(any(r['ts'] <= e['ts'] and
                   e['ts'] + e['dur'] <= r['ts'] + r['dur'] + 1e-3 and
                   e['tid'] == r['tid']
                   for r in runs)
               for e in execs)
    # per-step structured log got one JSONL record per run
    lines = (tmp_path / 'steps.jsonl').read_text().splitlines()
    recs = [json.loads(ln) for ln in lines]
    assert len(recs) == 2
    assert {'kind', 'step', 'donated', 'execute_s'} <= set(recs[0])


def test_tape_dispatch_histogram_on():
    from paddle_tpu.dygraph.tape import kernel_cache
    kernel_cache.clear()        # cold cache: first dispatch must be a miss
    with obs.telemetry_guard(True):
        with dygraph.guard():
            t = dygraph.to_variable(np.ones((2, 2), np.float32))
            for _ in range(4):
                dygraph.dispatch_op('scale', {'x': t}, {'scale': 2.0})
        d = obs.registry.to_dict()
    samples = d['tape_dispatch_seconds']['samples']
    by_cached = {s['labels']['cached']: s for s in samples
                 if s['labels']['op'] == 'scale'}
    # first dispatch misses the kernel cache, the rest hit
    assert by_cached['false']['count'] >= 1
    assert by_cached['true']['count'] >= 2
    # kernel-cache counters surface as gauges via the export collector
    ek = {s['labels']['stat']: s['value']
          for s in d['eager_kernel_cache']['samples']}
    assert ek['hits'] >= 2 and ek['enabled'] == 1


def test_train_step_spans():
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.dygraph.nn import Linear
    with obs.telemetry_guard(True):
        with dygraph.guard():
            model = Linear(4, 2)
            opt = fluid.optimizer.SGD(0.1,
                                      parameter_list=model.parameters())

            def loss_fn(m, x):
                out = m(x)
                return dygraph.dispatch_op('reduce_mean',
                                           {'x': out * out}, {})

            step = TrainStep(model, loss_fn, opt)
            x = np.ones((3, 4), np.float32)
            step(x)
            step(x)
        names = [e['name'] for e in obs.tracer.snapshot()['traceEvents']]
        d = obs.registry.to_dict()
    assert names.count('train_step/call') == 2
    assert names.count('train_step/build') == 1     # compiled once
    assert 'train_step/execute' in names
    assert d['train_step_calls']['samples'][0]['value'] == 2


def test_dataloader_wait_metrics():
    with obs.telemetry_guard(True):
        loader = fluid.DataLoader.from_generator(capacity=4)

        def gen():
            for i in range(3):
                yield {'lx': np.full((2, 2), i, np.float32)}

        loader.set_batch_generator(gen)
        batches = list(loader)
        d = obs.registry.to_dict()
    assert len(batches) == 3
    assert d['dataloader_batches']['samples'][0]['value'] == 3
    assert d['dataloader_wait_seconds']['samples'][0]['count'] >= 3
    assert 'dataloader_last_wait_seconds' in d
    assert d['dataloader_staged_bytes']['samples'][0]['value'] == 3 * 16


def test_nonfinite_detection_counter_and_span():
    # env-flag style: scan-fetches path (jax_debug_nans stays off)
    old = debugging._check_enabled
    debugging._check_enabled = True
    try:
        with obs.telemetry_guard(True):
            bad = np.full((8, 4), np.nan, 'float32')
            with pytest.raises(FloatingPointError, match='check_nan_inf'):
                _run_tiny_program(steps=1, feed_x=bad)
            d = obs.registry.to_dict()
            names = [e['name'] for e in obs.tracer.snapshot()['traceEvents']]
    finally:
        debugging._check_enabled = old
    assert d['nonfinite_detections']['samples'][0]['value'] >= 1
    assert 'executor/check_nan_inf' in names
    assert 'nonfinite_detected' in names


# ---------------------------------------------------------------------------
# profiler satellites
# ---------------------------------------------------------------------------

def test_reset_stats_keeps_warm_kernels():
    """Regression (ISSUE 2 satellite): resetting the eager kernel-cache
    stats between two back-to-back profiled runs must NOT drop the compiled
    kernels — the second run stays warm (0 misses), with fresh counters."""
    from paddle_tpu.dygraph.tape import kernel_cache
    kernel_cache.clear()
    with dygraph.guard():
        t = dygraph.to_variable(np.ones((2, 2), np.float32))
        for _ in range(3):
            dygraph.dispatch_op('scale', {'x': t}, {'scale': 2.0})
        assert kernel_cache.stats()['misses'] == 1
        profiler.reset_eager_kernel_cache_stats()
        s = kernel_cache.stats()
        assert (s['hits'], s['misses'], s['evictions'], s['bypasses']) \
            == (0, 0, 0, 0)
        assert s['size'] == 1                   # kernels survived the reset
        for _ in range(3):
            dygraph.dispatch_op('scale', {'x': t}, {'scale': 2.0})
        s = kernel_cache.stats()
        assert s['misses'] == 0 and s['hits'] == 3
    kernel_cache.clear()
    s = kernel_cache.stats()
    assert s['size'] == 0 and s['hits'] == 0    # clear() zeroes BOTH


def test_stop_profiler_logs_not_prints(capsys):
    # capture the module logger itself (log_helper handlers hold whatever
    # stderr existed at import — attach our own to be deterministic)
    import io
    import logging
    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    log = logging.getLogger('paddle_tpu.profiler')
    log.addHandler(handler)
    try:
        profiler.reset_profiler()
        profiler.start_profiler(state='CPU')
        with profiler.record_event('obs_region'):
            pass
        profiler.stop_profiler(sorted_key='calls')
    finally:
        log.removeHandler(handler)
    assert 'obs_region' not in capsys.readouterr().out   # print() is gone
    assert 'obs_region' in stream.getvalue()             # logged instead


# ---------------------------------------------------------------------------
# artifacts
# ---------------------------------------------------------------------------

def test_dump_artifacts_and_prom_file(tmp_path):
    with obs.telemetry_guard(True, directory=str(tmp_path)):
        _run_tiny_program(steps=1)
        paths = obs.dump_artifacts()
    doc = json.loads((tmp_path / 'trace.json').read_text())
    assert doc['traceEvents']
    md = json.loads((tmp_path / 'metrics.json').read_text())['metrics']
    assert 'executor_steps' in md
    types, samples = _parse_prometheus((tmp_path / 'metrics.prom')
                                       .read_text())
    assert samples['paddle_tpu_executor_steps'] == 1
    assert set(paths) >= {'metrics', 'prometheus', 'trace'}
    for frac in samples.values():
        assert not math.isnan(frac)
