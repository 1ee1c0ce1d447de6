"""Serving-tier router (paddle_tpu/serving/tier/router.py) over in-process
replicas: strict knob parsing, least-loaded dispatch, routed bitwise
parity, breaker-aware draining + half-open probe re-admission, cold-replica
warmup gating, rolling restarts behind drain, mid-stream failover
semantics, GenerationStream result metadata, and the router HTTP front."""
import json
import threading
import time
import urllib.request

import pytest

from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import greedy_generate
from paddle_tpu.serving import (NoReplicaAvailable, Router, RouterServer,
                                ServingServer)
from paddle_tpu.serving.tier import knobs
from paddle_tpu.serving.tier.replica import build_replica_stack, build_tiny_lm
from shared_programs import build_shared_prompt_work


@pytest.fixture(scope='module')
def lm():
    with guard():
        yield build_tiny_lm()


class _InProcReplica:
    """One in-process replica stack + HTTP listener (the real subprocess
    drill lives in test_router_failover.py)."""

    def __init__(self, lm, model_lock, replica_id, warm=True, **kw):
        self.engine, self.scheduler, _ = build_replica_stack(
            model=lm, model_lock=model_lock, replica_id=replica_id, **kw)
        if warm:
            self.engine.warmup()
        self.server = ServingServer(None, port=0,
                                    generator=self.scheduler).start()
        self.url = f'http://127.0.0.1:{self.server.port}'

    def shutdown(self, drain=True):
        self.scheduler.close(drain=drain, timeout=10)
        self.server.shutdown(drain=drain)


@pytest.fixture()
def pair(lm):
    lock = threading.RLock()
    reps = [_InProcReplica(lm, lock, f'rep-{i}') for i in range(2)]
    yield reps
    for r in reps:
        try:
            r.shutdown()
        except Exception:
            pass


def _counter(name):
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    if not d or not d['samples']:
        return 0.0
    return sum(s['value'] for s in d['samples'])


# -- strict knob parse -----------------------------------------------------

def test_router_knob_strict_parse(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_ROUTER_PORT', 'auto')
    with pytest.raises(ValueError, match='PADDLE_TPU_ROUTER_PORT'):
        knobs.parse_int_env(knobs.ENV_ROUTER_PORT, 8180, minimum=0,
                            maximum=65535)
    monkeypatch.setenv('PADDLE_TPU_ROUTER_PORT', '99999')
    with pytest.raises(ValueError, match='<= 65535'):
        knobs.parse_int_env(knobs.ENV_ROUTER_PORT, 8180, minimum=0,
                            maximum=65535)
    monkeypatch.setenv('PADDLE_TPU_ROUTER_HEALTH_POLL_S', 'fast')
    with pytest.raises(ValueError, match='PADDLE_TPU_ROUTER_HEALTH_POLL_S'):
        knobs.parse_float_env(knobs.ENV_ROUTER_HEALTH_POLL_S, 1.0)
    monkeypatch.setenv('PADDLE_TPU_ROUTER_HEALTH_POLL_S', '0')
    with pytest.raises(ValueError, match='> 0'):
        knobs.parse_float_env(knobs.ENV_ROUTER_HEALTH_POLL_S, 1.0)
    monkeypatch.setenv('PADDLE_TPU_ROUTER_REPLICAS', 'localhost')
    with pytest.raises(ValueError, match='PADDLE_TPU_ROUTER_REPLICAS'):
        knobs.parse_replicas_env()
    monkeypatch.setenv('PADDLE_TPU_ROUTER_REPLICAS',
                       'http://a:1,b:2, http://c:3/')
    assert knobs.parse_replicas_env() == \
        ['http://a:1', 'http://b:2', 'http://c:3']


# -- routing ---------------------------------------------------------------

def test_routed_parity_and_result_metadata(lm, pair):
    """Any replica answers any request with the reference bytes, and the
    final event carries replica + restart-safe request identity."""
    with Router([r.url for r in pair], health_poll_s=0.2) as router:
        prompt = [5, 9, 2, 44]
        ref = greedy_generate(lm, prompt, 6,
                              pad_len=pair[0].engine.padded_context)
        finals = [router.generate(prompt, max_new_tokens=6)
                  for _ in range(4)]
        for fin in finals:
            assert fin['tokens'] == ref
            assert fin['replica'] in [r.url for r in pair]
            assert fin['replica_id'] in ('rep-0', 'rep-1')
            assert fin['request_id']
            assert fin['retries'] == 0
        assert len({f['request_id'] for f in finals}) == 4   # unique ids


def _fire_all(work, call, midway=lambda: None):
    """Every request on a thread of its own, none waiting for another, with
    `midway()` run once half of them are started; returns the final events
    in request order (None where one raised) and what was raised."""
    finals, errors = [None] * len(work), []

    def fire(i, prompt, max_new):
        try:
            finals[i] = call(prompt, max_new)
        except Exception as e:          # a drop: the caller asserts none
            errors.append((i, e))

    threads = [threading.Thread(target=fire, args=(i, p, m))
               for i, (p, m) in enumerate(work)]
    for t in threads[:len(threads) // 2]:
        t.start()
    midway()
    for t in threads[len(threads) // 2:]:
        t.start()
    for t in threads:
        t.join(180)
    assert not any(t.is_alive() for t in threads)
    return finals, errors


@pytest.mark.parametrize('replicas', [1, 2])
def test_concurrent_arrivals_complete_bitwise(lm, pair, replicas):
    """The shared-system-prompt mix arriving all at once through the HTTP
    router, against one replica and against two: every request completes
    and every stream is the uncached reference's."""
    work = build_shared_prompt_work(8)
    refs = [greedy_generate(lm, p, m, pad_len=pair[0].engine.padded_context)
            for p, m in work]
    urls = [r.url for r in pair[:replicas]]
    with Router(urls, health_poll_s=5) as router:
        finals, errors = _fire_all(
            work, lambda p, m: router.generate(p, max_new_tokens=m,
                                               timeout=120))
    assert not errors
    assert [f['tokens'] for f in finals] == refs
    assert {f['replica'] for f in finals} <= set(urls)


def test_replica_dying_mid_run_drops_no_nonstreamed_request(lm, pair):
    """One of two replicas stops abruptly with half of the requests in
    flight and the other half not yet sent: a non-streamed generate is
    idempotent (nothing reached the client), so the router retries it on
    the survivor and ALL complete bitwise, zero dropped. (The kill -9
    version over real processes: test_router_failover.py.)"""
    work = build_shared_prompt_work(8)
    refs = [greedy_generate(lm, p, m, pad_len=pair[0].engine.padded_context)
            for p, m in work]
    with Router([r.url for r in pair], health_poll_s=0.3) as router:
        finals, errors = _fire_all(
            work, lambda p, m: router.generate_nonstream(
                p, max_new_tokens=m, timeout=120),
            midway=lambda: pair[0].shutdown(drain=False))   # dies abruptly
    assert not errors, errors
    assert [f['tokens'] for f in finals] == refs


def test_least_loaded_dispatch(lm, pair):
    """With one replica pinned by a long generation, short requests land
    on the idle one."""
    with Router([r.url for r in pair], health_poll_s=10) as router:
        long_s = pair[0].scheduler.submit([3, 5, 7], max_new_tokens=16)
        router.poll_once()            # observe rep-0's busy slot
        fins = [router.generate([9, 2], max_new_tokens=2) for _ in range(3)]
        assert all(f['replica'] == pair[1].url for f in fins)
        long_s.result(120)


def test_cold_replica_not_routed_until_warm(lm):
    """The warmup gate: a cold replica is alive but unroutable; it joins
    the rotation once its ladder + decode step have precompiled."""
    lock = threading.RLock()
    cold = _InProcReplica(lm, lock, 'cold', warm=False)
    try:
        health = json.load(urllib.request.urlopen(cold.url + '/healthz'))
        assert health['status'] == 'ok'
        assert health['warmup'] == {'decode': False, 'done': False}
        with Router([cold.url], health_poll_s=10,
                    connect_timeout=2) as router:
            assert not router.replicas[0].routable()
            with pytest.raises(NoReplicaAvailable):
                router.generate([1, 2], max_new_tokens=2, timeout=0.5)
            cold.engine.warmup()
            router.poll_once()
            assert router.replicas[0].routable()
            assert len(router.generate([1, 2],
                                       max_new_tokens=2)['tokens']) == 2
        health = json.load(urllib.request.urlopen(cold.url + '/healthz'))
        assert health['warmup'] == {'decode': True, 'done': True}
        assert health['replica'] == 'cold'
    finally:
        cold.shutdown()


def test_degraded_replica_drained_then_probe_readmits(lm):
    """Breaker awareness end-to-end: a tripped replica reports degraded and
    is drained; after its cooldown the router routes exactly one probe,
    which closes the breaker and re-admits the replica."""
    lock = threading.RLock()
    rep = _InProcReplica(lm, lock, 'trippy')
    rep.scheduler.breaker.failure_threshold = 2
    rep.scheduler.breaker.reset_after_s = 0.4
    try:
        with Router([rep.url], health_poll_s=10, connect_timeout=2) as router:
            assert router.replicas[0].routable()
            rep.scheduler.breaker.record_failure()
            rep.scheduler.breaker.record_failure()        # trips -> open
            router.poll_once()
            assert not router.replicas[0].routable()      # degraded: drained
            p0 = _counter('router_probes')
            time.sleep(0.5)                               # cooldown elapses
            router.poll_once()
            assert router.replicas[0].half_open
            assert router.replicas[0].routable()          # as the probe
            fin = router.generate([1, 2], max_new_tokens=2)
            assert len(fin['tokens']) == 2
            assert _counter('router_probes') - p0 >= 1
            router.poll_once()
            assert router.replicas[0].healthy             # breaker closed
    finally:
        rep.shutdown()


def test_midstream_failover_kills_only_inflight_stream(lm, pair):
    """An abruptly dying replica errors its in-flight stream; requests
    submitted right after reroute to the survivor with zero drops."""
    with Router([r.url for r in pair], health_poll_s=10) as router:
        gen = router.stream_generate([3, 5, 7], max_new_tokens=16)
        events = gen.events()
        next(events)                              # streaming has begun
        victim = next(r for r in pair if r.url == gen.replica)
        survivor = next(r for r in pair if r.url != gen.replica)
        victim.shutdown(drain=False)              # dies mid-stream
        tail = list(events)
        assert any('error' in e and not e.get('done') for e in tail), tail
        # new requests reroute with zero drops
        ref = greedy_generate(lm, [9, 2], 3,
                              pad_len=pair[0].engine.padded_context)
        fins = [router.generate([9, 2], max_new_tokens=3) for _ in range(4)]
        assert all(f['tokens'] == ref for f in fins)
        assert all(f['replica'] == survivor.url for f in fins)


def test_rolling_restart_behind_drain(lm, pair):
    """Both replicas restart one at a time behind a drain while traffic
    keeps flowing: every request issued during the roll completes."""
    lock = threading.RLock()
    ref_ctx = pair[0].engine.padded_context
    ref = greedy_generate(lm, [5, 9, 2], 3, pad_len=ref_ctx)
    with Router([r.url for r in pair], health_poll_s=0.2) as router:
        results, errors = [], []
        stop = threading.Event()

        def traffic():
            while not stop.is_set():
                try:
                    results.append(
                        router.generate([5, 9, 2], max_new_tokens=3))
                except Exception as e:
                    errors.append(e)
                time.sleep(0.02)

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        by_url = {r.url: r for r in pair}

        def restart(url):
            rep = by_url.pop(url)
            rep.shutdown()
            fresh = _InProcReplica(lm, lock, rep.server.replica_id + '-r2')
            by_url[fresh.url] = fresh
            return fresh.url

        r0 = _counter('router_rolling_restarts')
        router.rolling_restart(restart, drain_timeout=30, warm_timeout=60,
                               poll_interval=0.05)
        stop.set()
        t.join(30)
        pair[:] = list(by_url.values())           # fixture teardown
        assert _counter('router_rolling_restarts') - r0 == 2
        assert not errors, errors
        assert results and all(f['tokens'] == ref for f in results)
        restarted = {f['replica_id'] for f in results}
        assert any(rid.endswith('-r2') for rid in restarted), restarted


# -- HTTP front end --------------------------------------------------------

def test_router_http_e2e(lm, pair):
    ref = greedy_generate(lm, [5, 9, 2, 44], 6,
                          pad_len=pair[0].engine.padded_context)
    with Router([r.url for r in pair], health_poll_s=0.2) as router:
        with RouterServer(router, port=0).start() as rs:
            url = f'http://127.0.0.1:{rs.port}'
            # streaming NDJSON with routing metadata on the done line
            req = urllib.request.Request(
                url + '/generate',
                data=json.dumps({'prompt': [5, 9, 2, 44],
                                 'max_new_tokens': 6}).encode())
            lines = [json.loads(ln) for ln in
                     urllib.request.urlopen(req).read().splitlines()]
            assert [ln['token'] for ln in lines if 'token' in ln] == ref
            done = lines[-1]
            assert done['done'] and done['replica'] in [r.url for r in pair]
            assert done['retries'] == 0 and done['request_id']
            # non-streaming
            req = urllib.request.Request(
                url + '/generate',
                data=json.dumps({'prompt': [5, 9, 2, 44],
                                 'max_new_tokens': 6,
                                 'stream': False}).encode())
            body = json.load(urllib.request.urlopen(req))
            assert body['tokens'] == ref and body['replica']
            # replica 4xx relayed verbatim (bad prompt -> 400)
            req = urllib.request.Request(
                url + '/generate',
                data=json.dumps({'prompt': ['x']}).encode())
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            assert ei.value.code == 400
            # healthz + metrics
            h = json.load(urllib.request.urlopen(url + '/healthz'))
            assert h['status'] == 'ok' and h['routable'] == 2
            prom = urllib.request.urlopen(url + '/metrics').read().decode()
            assert 'paddle_tpu_router_requests' in prom
            assert 'paddle_tpu_router_replicas_routable' in prom


def test_stream_meta_on_generation_stream(lm):
    """Satellite: GenerationStream exposes replica id + restart-safe
    request id directly (scheduler-level, no HTTP)."""
    eng, sched, _ = build_replica_stack(model=lm, replica_id='meta-rep')
    try:
        s1 = sched.submit([1, 2, 3], max_new_tokens=2)
        s2 = sched.submit([1, 2, 3], max_new_tokens=2)
        s1.result(120), s2.result(120)
        assert s1.meta['replica_id'] == s2.meta['replica_id'] == 'meta-rep'
        assert s1.meta['request_id'] != s2.meta['request_id']
        assert len(s1.request_id) == 16
    finally:
        sched.close()


def test_no_replica_available_is_typed(lm):
    """A router whose only replica is unreachable raises the typed
    NoReplicaAvailable (HTTP 503) after its bounded wait."""
    router = Router(['http://127.0.0.1:9'], health_poll_s=10,
                    connect_timeout=0.5, start=False)
    with pytest.raises(NoReplicaAvailable, match='no routable replica'):
        router.generate([1, 2], max_new_tokens=2, timeout=0.6)
    n = _counter('router_no_replica')
    assert n >= 1
    router.close()
