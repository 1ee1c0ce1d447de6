"""Serving subsystem (paddle_tpu/serving/, ISSUE 4): bucketed-batch engine
parity, micro-batcher robustness (deadlines, backpressure, malformed-request
isolation, graceful drain), and the HTTP front end.

The load-bearing guarantee is BITWISE parity: a request served through the
batcher (coalesced with strangers, padded to a bucket) returns exactly the
bytes single-request Predictor.run returns — for every bucket size and under
concurrency.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, serving
from paddle_tpu.inference import Predictor
from paddle_tpu.serving import (DeadlineExceeded, EngineClosed,
                                InferenceEngine, InvalidRequest, MicroBatcher,
                                Overloaded, ServingError, ServingServer,
                                bucket_ladder)

FEATURES = 8
MAX_BATCH = 8


@pytest.fixture(scope='module')
def saved_model(tmp_path_factory):
    """Tiny MLP saved as an inference model (module-scoped: the serving
    stack reloads it per engine, programs are independent of the default
    program the autouse fixture resets)."""
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('x', shape=[FEATURES], dtype='float32')
        h = layers.fc(x, 32, act='relu')
        out = layers.fc(h, 4, act='softmax')
    exe = fluid.Executor()
    path = str(tmp_path_factory.mktemp('serving') / 'model')
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        fluid.io.save_inference_model(path, ['x'], [out], exe, main)
    return path


@pytest.fixture(scope='module')
def reference(saved_model):
    """(X, per-row single-request Predictor outputs) — the bitwise oracle."""
    pred = Predictor(saved_model)
    X = np.random.RandomState(7).randn(32, FEATURES).astype(np.float32)
    refs = [pred.run([X[i:i + 1]])[0] for i in range(len(X))]
    return X, refs


# ---------------------------------------------------------------------------
# bucket ladder + engine
# ---------------------------------------------------------------------------

def test_bucket_ladder_defaults_and_validation():
    assert bucket_ladder(16) == [1, 2, 4, 8, 16]
    assert bucket_ladder(12) == [1, 2, 4, 8, 12]
    assert bucket_ladder(1) == [1]
    assert bucket_ladder(8, [2, 4, 8]) == [2, 4, 8]
    with pytest.raises(ValueError):
        bucket_ladder(8, [4, 2, 8])        # not increasing
    with pytest.raises(ValueError):
        bucket_ladder(8, [1, 2, 4])        # doesn't end at max
    with pytest.raises(ValueError):
        bucket_ladder(0)


def test_engine_parity_every_bucket(saved_model, reference):
    """run_batch at every bucket size and several padded row counts is
    bitwise-equal to single-request Predictor.run, row by row."""
    X, refs = reference
    eng = InferenceEngine(saved_model, max_batch_size=MAX_BATCH)
    assert eng.buckets == [1, 2, 4, 8]
    for bucket in eng.buckets:
        for nrows in {1, max(1, bucket - 1), bucket}:
            out, = eng.infer({'x': X[:nrows]})
            assert out.shape[0] == nrows
            for i in range(nrows):
                assert np.array_equal(out[i], refs[i][0]), \
                    f'bucket {bucket} rows {nrows} row {i} not bitwise-equal'
    # padded rows really were padded: each nrows ran at its ladder bucket
    assert eng.bucket_for(3) == 4 and eng.bucket_for(8) == 8


def test_engine_warmup_precompiles_all_buckets(saved_model):
    eng = InferenceEngine(saved_model, max_batch_size=MAX_BATCH)
    timings = eng.warmup()
    assert sorted(timings) == eng.buckets == eng.compiled_buckets
    cache_size = len(eng._exe._cache)
    assert cache_size >= len(eng.buckets)
    # traffic at any row count now hits a precompiled bucket: no new compile
    for nrows in (1, 2, 3, 5, 8):
        eng.infer({'x': np.zeros((nrows, FEATURES), np.float32)})
    assert len(eng._exe._cache) == cache_size


def test_engine_validation_rejects_before_device(saved_model):
    eng = InferenceEngine(saved_model, max_batch_size=4)
    ok = np.zeros((1, FEATURES), np.float32)
    with pytest.raises(InvalidRequest):
        eng.validate({'wrong_name': ok})
    with pytest.raises(InvalidRequest):
        eng.validate({'x': ok, 'extra': ok})
    with pytest.raises(InvalidRequest):
        eng.validate({'x': np.zeros((1, FEATURES + 1), np.float32)})
    with pytest.raises(InvalidRequest):
        eng.validate({'x': np.zeros((FEATURES,), np.float32)})  # no batch dim
    with pytest.raises(InvalidRequest):
        eng.validate({'x': [['a'] * FEATURES]})                 # non-numeric
    with pytest.raises(InvalidRequest):
        eng.validate({'x': np.zeros((0, FEATURES), np.float32)})  # empty
    with pytest.raises(InvalidRequest):
        eng.validate({'x': np.zeros((5, FEATURES), np.float32)})  # > max
    # list form maps by feed order; numeric lists cast
    feed, nrows = eng.validate([ok.tolist()])
    assert nrows == 1 and feed['x'].dtype == np.float32


# ---------------------------------------------------------------------------
# micro-batcher: e2e concurrency parity + robustness
# ---------------------------------------------------------------------------

def test_e2e_concurrent_clients_bitwise_parity(saved_model, reference):
    """The acceptance test: many threads, mixed row counts, coalesced into
    shared padded batches — every response bitwise-equals the single-request
    Predictor output for its rows."""
    X, refs = reference
    eng = InferenceEngine(saved_model, max_batch_size=MAX_BATCH)
    eng.warmup()
    results, errors = {}, []

    def client(cid, lo, nrows):
        try:
            for _ in range(5):
                out, = batcher.predict({'x': X[lo:lo + nrows]})
                results[(cid, lo, nrows)] = out
        except Exception as e:          # pragma: no cover - fail loudly
            errors.append(e)

    with MicroBatcher(eng, batch_timeout_ms=2) as batcher:
        threads = []
        for cid in range(12):
            nrows = (cid % 3) + 1       # 1-, 2-, 3-row requests interleaved
            lo = (cid * 2) % (len(X) - nrows)
            threads.append(threading.Thread(target=client,
                                            args=(cid, lo, nrows)))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors
    assert len(results) == 12
    for (cid, lo, nrows), out in results.items():
        for i in range(nrows):
            assert np.array_equal(out[i], refs[lo + i][0]), \
                f'client {cid} row {i} not bitwise-equal to Predictor.run'


def _hist_totals(name):
    """(sum, count) over every sample of one registry histogram."""
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    samples = d['samples'] if d else []
    return (sum(x['sum'] for x in samples),
            sum(x['count'] for x in samples))


def test_closed_loop_single_row_clients_coalesce(saved_model, reference):
    """More closed-loop single-row clients than the row budget: the
    batcher really coalesces (mean rows per device call well past one
    request), every row is accounted in ``serving_batch_rows``, and the
    padding-waste histogram stays a ratio in [0, 1). (That each response
    is bitwise the serial row is the test above.)"""
    X, _ = reference
    eng = InferenceEngine(saved_model, max_batch_size=MAX_BATCH)
    eng.warmup()
    rows0, nb0 = _hist_totals('serving_batch_rows')
    waste0, nw0 = _hist_totals('serving_padding_waste_ratio')
    clients, per_client = 3 * MAX_BATCH, 10
    errors = []

    def client(cid):
        try:
            for i in range(per_client):
                ridx = (cid * per_client + i) % len(X)
                out, = batcher.predict({'x': X[ridx:ridx + 1]})
                assert out.shape == (1, 4)
        except Exception as e:          # pragma: no cover - fail loudly
            errors.append(e)

    with MicroBatcher(eng, batch_timeout_ms=20,
                      queue_depth=4 * clients) as batcher:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    assert not errors
    rows1, nb1 = _hist_totals('serving_batch_rows')
    waste1, nw1 = _hist_totals('serving_padding_waste_ratio')
    assert rows1 - rows0 == clients * per_client    # every row accounted
    assert (rows1 - rows0) / (nb1 - nb0) > 2
    assert nw1 - nw0 == nb1 - nb0
    assert 0 <= (waste1 - waste0) / (nw1 - nw0) < 1


def test_open_loop_arrivals_all_accounted(saved_model, reference):
    """Open-loop submission (arrivals never wait for completions, results
    stamped by ``add_done_callback``): every offered request is either
    answered or rejected with the typed ``Overloaded`` — none fails, none
    is lost."""
    X, _ = reference
    eng = InferenceEngine(saved_model, max_batch_size=MAX_BATCH)
    eng.warmup()
    requests = 200
    done, done_lock = [], threading.Lock()
    rejected, pending = 0, []

    def on_done(fut):
        with done_lock:
            done.append(fut)

    with MicroBatcher(eng, batch_timeout_ms=2,
                      queue_depth=4 * MAX_BATCH) as batcher:
        for i in range(requests):
            ridx = i % len(X)
            try:
                fut = batcher.submit({'x': X[ridx:ridx + 1]})
            except Overloaded:
                rejected += 1
                continue
            fut.add_done_callback(on_done)
            pending.append(fut)
        for fut in pending:
            out, = fut.result(timeout=60)      # a failure raises here
            assert out.shape == (1, 4)
    assert len(pending) > 0
    assert len(done) == len(pending)            # one callback per answer
    assert len(pending) + rejected == requests


class _StubEngine:
    """Duck-typed engine with controllable latency/failure — makes the
    robustness tests deterministic and device-free."""

    def __init__(self, delay_s=0.0, fail=False, max_batch_size=4):
        self.max_batch_size = max_batch_size
        self.delay_s = delay_s
        self.fail = fail
        self.batches = []

    def validate(self, inputs):
        arr = np.asarray(inputs['x'], np.float32)
        if arr.ndim != 2:
            raise InvalidRequest('rank')
        return {'x': arr}, arr.shape[0]

    def run_batch(self, feed, nrows=None):
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail:
            raise RuntimeError('device on fire')
        self.batches.append(nrows)
        return [feed['x'][:nrows] * 2.0]


def test_malformed_request_never_poisons_a_batch():
    """A bad request raises at submit() — co-submitted good requests all
    complete. (Validation happens before enqueue, so there is no batch for
    the bad one to poison.)"""
    eng = _StubEngine()
    with MicroBatcher(eng, batch_timeout_ms=5) as b:
        good = [b.submit({'x': np.full((1, 3), i, np.float32)})
                for i in range(3)]
        with pytest.raises(InvalidRequest):
            b.submit({'x': np.zeros((3,), np.float32)})   # wrong rank
        more = b.submit({'x': np.full((1, 3), 9, np.float32)})
        for i, f in enumerate(good):
            assert np.array_equal(f.result(10)[0], np.full((1, 3), 2.0 * i))
        assert np.array_equal(more.result(10)[0], np.full((1, 3), 18.0))


def test_engine_failure_isolated_to_its_batch():
    """An engine error fails that batch's requests with ServingError; the
    worker survives and serves the next batch."""
    eng = _StubEngine()
    with MicroBatcher(eng, batch_timeout_ms=1) as b:
        eng.fail = True
        f1 = b.submit({'x': np.ones((1, 3), np.float32)})
        with pytest.raises(ServingError, match='device on fire'):
            f1.result(10)
        eng.fail = False
        f2 = b.submit({'x': np.ones((1, 3), np.float32)})
        assert np.array_equal(f2.result(10)[0], np.full((1, 3), 2.0))


def test_overload_typed_rejection_and_counters():
    """queue_depth bounds admission: a burst rejects with Overloaded (typed,
    immediate — no hang), admitted requests still complete, and the
    rejection counter is visible in the Prometheus export."""
    from paddle_tpu.observability import registry
    from paddle_tpu.serving import metrics as sm
    before = sm.requests_rejected_overload.value
    eng = _StubEngine(delay_s=0.05)
    rejected, futures = 0, []
    with MicroBatcher(eng, batch_timeout_ms=1, queue_depth=2) as b:
        for i in range(12):
            try:
                futures.append(b.submit({'x': np.ones((1, 3), np.float32)}))
            except Overloaded as e:
                assert 'retry' in str(e)
                rejected += 1
        for f in futures:
            f.result(30)
    assert rejected > 0 and len(futures) >= 2
    assert sm.requests_rejected_overload.value - before == rejected
    assert 'paddle_tpu_serving_requests_rejected_overload' \
        in registry.prometheus_text()


def test_deadline_expiry_drops_queued_request():
    """A request whose deadline passes while the worker is busy gets
    DeadlineExceeded and never reaches the device."""
    eng = _StubEngine(delay_s=0.15)
    with MicroBatcher(eng, batch_timeout_ms=0) as b:
        blocker = b.submit({'x': np.ones((1, 3), np.float32)})
        time.sleep(0.02)                   # worker is now inside run_batch
        doomed = b.submit({'x': np.ones((1, 3), np.float32)}, timeout_ms=20)
        with pytest.raises(DeadlineExceeded):
            doomed.result(30)
        blocker.result(30)                 # the in-flight one still lands
    assert eng.batches.count(1) == 1       # the doomed row never executed


def test_graceful_drain_completes_queued_requests():
    """close(drain=True) answers everything admitted before shutdown;
    submit() after close raises EngineClosed."""
    eng = _StubEngine(delay_s=0.03)
    b = MicroBatcher(eng, batch_timeout_ms=1, queue_depth=64)
    futures = [b.submit({'x': np.full((1, 3), i, np.float32)})
               for i in range(10)]
    b.close(drain=True)
    assert b.closed and b.pending() == 0
    for i, f in enumerate(futures):
        assert np.array_equal(f.result(1)[0], np.full((1, 3), 2.0 * i))
    with pytest.raises(EngineClosed):
        b.submit({'x': np.ones((1, 3), np.float32)})


def test_close_without_drain_fails_fast():
    eng = _StubEngine(delay_s=0.05)
    b = MicroBatcher(eng, batch_timeout_ms=0, queue_depth=64)
    futures = [b.submit({'x': np.ones((1, 3), np.float32)})
               for i in range(6)]
    b.close(drain=False)
    outcomes = {'ok': 0, 'closed': 0}
    for f in futures:
        try:
            f.result(5)
            outcomes['ok'] += 1
        except EngineClosed:
            outcomes['closed'] += 1
    assert outcomes['closed'] > 0          # queued ones failed fast
    assert outcomes['ok'] + outcomes['closed'] == 6


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------

def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={'Content-Type': 'application/json'})
    return urllib.request.urlopen(req, timeout=30)


def test_http_server_end_to_end(saved_model, reference):
    X, refs = reference
    eng = InferenceEngine(saved_model, max_batch_size=MAX_BATCH)
    with ServingServer(eng, port=0, batch_timeout_ms=1) as srv:
        srv.start()
        url = f'http://127.0.0.1:{srv.port}'

        r = urllib.request.urlopen(url + '/healthz', timeout=30)
        health = json.loads(r.read())
        assert r.status == 200 and health['status'] == 'ok'
        assert health['buckets'] == eng.buckets

        r = _post(url + '/predict', {'inputs': {'x': X[:3].tolist()}})
        body = json.loads(r.read())
        assert r.status == 200 and body['rows'] == 3
        out = np.asarray(body['outputs'][eng.get_output_names()[0]],
                         np.float32)
        # JSON carries exact float32 values (repr round-trip): still bitwise
        for i in range(3):
            assert np.array_equal(out[i], refs[i][0])

        # malformed requests: typed 400s, never a hang
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url + '/predict', {'inputs': {'bogus': [[1.0]]}})
        assert ei.value.code == 400
        assert json.loads(ei.value.read())['error'] == 'InvalidRequest'
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url + '/predict', {'nope': 1})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(url + '/predict', data=b'not json{',
                                       headers={'Content-Type':
                                                'application/json'}),
                timeout=30)
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + '/nowhere', timeout=30)
        assert ei.value.code == 404

        # metrics endpoint: Prometheus text with the serving series
        r = urllib.request.urlopen(url + '/metrics', timeout=30)
        text = r.read().decode()
        assert r.status == 200
        assert 'paddle_tpu_serving_requests_accepted' in text
        assert 'paddle_tpu_serving_http_responses' in text
    assert srv.batcher.closed                  # context exit drained


def test_http_overload_maps_to_429(saved_model):
    eng = InferenceEngine(saved_model, max_batch_size=2)
    srv = ServingServer(eng, port=0, batch_timeout_ms=0, queue_depth=1)
    # deterministic overload: slow the engine down, then overfill the queue
    real_run = eng.run_batch

    def slow_run(feed, nrows=None):
        time.sleep(0.1)
        return real_run(feed, nrows)

    eng.run_batch = slow_run
    srv.start()
    url = f'http://127.0.0.1:{srv.port}/predict'
    payload = {'inputs': {'x': np.zeros((1, FEATURES)).tolist()}}
    codes = []

    def client():
        try:
            codes.append(_post(url, payload).status)
        except urllib.error.HTTPError as e:
            codes.append(e.code)

    threads = [threading.Thread(target=client) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    srv.shutdown()
    assert codes.count(200) >= 1
    assert 429 in codes, codes
    # draining server refuses: healthz already stopped
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        urllib.request.urlopen(f'http://127.0.0.1:{srv.port}/healthz',
                               timeout=2)


def test_listener_holds_a_burst_of_connections_in_its_backlog():
    """A replica's clients connect together (128 at once in the benchmark's
    closed loop). With socketserver's default backlog of 5 the kernel drops
    the SYNs of all but the first few, and a dropped SYN is retried after
    1 s, then 3, 7, 15: here nothing accepts at all (no serve_forever), and
    every connection still completes at once."""
    import socket
    from http.server import BaseHTTPRequestHandler
    from paddle_tpu.serving.server import Listener
    assert Listener.daemon_threads and Listener.request_queue_size >= 1024
    listener = Listener(('127.0.0.1', 0), BaseHTTPRequestHandler)
    socks = []
    try:
        t0 = time.perf_counter()
        for _ in range(200):
            s = socket.socket()
            s.settimeout(0.9)           # below the first SYN retry
            s.connect(listener.server_address)
            socks.append(s)
        assert time.perf_counter() - t0 < 0.9
    finally:
        for s in socks:
            s.close()
        listener.server_close()
