"""E2E distributed-observability drill (ISSUE 17 tentpole): a router and
TWO real replica processes with tracing on — a traced request is forced
through a kill -9 failover, and the merged cross-process timeline must
show the router's retry span plus BOTH replicas' spans under ONE
trace_id with every parent link resolving. Rides the same subprocess
pattern as test_router_failover.py; also drills /metrics/fleet
aggregation semantics against live scrapes, the kill -9 scrape-hardening
contract, the /healthz SLO block, and the sampled-off zero-span A/B."""
import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import greedy_generate
from paddle_tpu.observability import distributed as dobs
from paddle_tpu.observability.trace_context import (ENV_TRACE_DIR,
                                                    ENV_TRACE_SAMPLE)
from paddle_tpu.serving import Router
from paddle_tpu.serving.tier.replica import DEFAULT_SEED, build_tiny_lm
from paddle_tpu.serving.tier.router import RouterServer
from tools.trace_merge import load_span_file, merge_span_files

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_MAX_NEW_CAP = 96          # long decode → wide kill window for the drill
_PAD = -(-(16 + _MAX_NEW_CAP) // 4) * 4
# ttft is only fed by REAL requests (warmup feeds decode_step but never
# emits request tokens), so the vacuous-cold-start check stays clean
_SLO_SPEC = 'ttft.p99<30,ttft.mean<0'


def _spawn_replica(rid, trace_dir):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PADDLE_TPU_TRACE_DIR=trace_dir,
               PADDLE_TPU_TRACE_SAMPLE='1',
               PADDLE_TPU_SLO=_SLO_SPEC)
    env.pop('PADDLE_TPU_TELEMETRY', None)
    return subprocess.Popen(
        [sys.executable, '-m', 'paddle_tpu.serving.tier.replica',
         '--port', '0', '--slots', '2', '--seed', str(DEFAULT_SEED),
         '--max-new-tokens-cap', str(_MAX_NEW_CAP), '--replica-id', rid],
        cwd=_REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)


def _wait_ready(proc):
    deadline = time.monotonic() + 180
    line = ''
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.strip():
            break
        if proc.poll() is not None:
            raise RuntimeError(f'replica died at startup rc={proc.returncode}')
    ready = json.loads(line)
    assert ready['ready'] and ready['pid'] == proc.pid
    return ready


def _counter(name, **labels):
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    if not d:
        return 0.0
    return sum(s['value'] for s in d['samples']
               if all(s['labels'].get(k) == v for k, v in labels.items()))


def _span_file(trace_dir, pid):
    return os.path.join(trace_dir, 'spans-%d.jsonl' % pid)


def _line_count(path):
    try:
        with open(path) as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _sum_counter_from_scrapes(scrapes, family):
    total = 0.0
    for _, text in scrapes:
        fam = dobs.parse_prometheus_text(text).get(family)
        if fam:
            total += sum(v for _, _, v in fam['samples'])
    return total


def test_traced_failover_fleet_metrics_and_scrape_hardening(
        tmp_path, monkeypatch):
    trace_dir = str(tmp_path / 'trace')
    monkeypatch.setenv(ENV_TRACE_DIR, trace_dir)
    monkeypatch.setenv(ENV_TRACE_SAMPLE, '1')
    dobs.reset_distributed()          # recorder must bind to trace_dir

    with guard():
        model = build_tiny_lm()
        short_ref = greedy_generate(model, [9, 2], 4, pad_len=_PAD)
        long_ref = greedy_generate(model, [3, 5, 7], _MAX_NEW_CAP,
                                   pad_len=_PAD)
    assert len(long_ref) == _MAX_NEW_CAP     # no early eos: wide window

    procs = [_spawn_replica('r0', trace_dir), _spawn_replica('r1', trace_dir)]
    router = http_front = None
    try:
        readies = [_wait_ready(p) for p in procs]
        urls = ['http://127.0.0.1:%d' % r['port'] for r in readies]
        by_pid = {p.pid: r['replica_id']
                  for p, r in zip(procs, readies)}
        assert all(r['trace_dir'] == trace_dir for r in readies)

        router = Router(urls, health_poll_s=0.5)
        assert all(r.healthy and r.warmed for r in router.replicas)

        # -- clock handshake: every poll estimated each replica's offset
        for rep in router.replicas:
            assert rep.replica_id in ('r0', 'r1')
            assert rep.clock_offset is not None
            assert abs(rep.clock_offset) < 5.0   # same machine
        assert abs(_counter('trace_clock_offset_seconds',
                            replica='r0')) < 5.0

        # -- /healthz SLO block: vacuously ok before any decode traffic
        for url in urls:
            with urllib.request.urlopen(url + '/healthz', timeout=10) as r:
                body = json.load(r)
            assert body['replica'] in ('r0', 'r1')
            assert body['unix_time'] == pytest.approx(time.time(), abs=30)
            assert body['slo']['ok'] is True
            assert {c['slo'] for c in body['slo']['clauses']} == set(
                _SLO_SPEC.split(','))

        # -- traced traffic: every request returns its trace_id
        fins = [router.generate_nonstream([9, 2], max_new_tokens=4,
                                          timeout=60) for _ in range(4)]
        for fin in fins:
            assert fin['tokens'] == short_ref
            assert len(fin['trace_id']) == 16
        assert len({f['trace_id'] for f in fins}) == 4

        # -- SLO breach: the serving replica's decode_step.mean<0 clause
        # must now burn; its p99<30 clause stays ok
        served_url = fins[0]['replica']
        with urllib.request.urlopen(served_url + '/healthz',
                                    timeout=10) as r:
            slo = json.load(r)['slo']
        assert slo['ok'] is False
        by_clause = {c['slo']: c for c in slo['clauses']}
        assert not by_clause['ttft.mean<0']['ok']
        assert by_clause['ttft.p99<30']['ok']

        # -- /metrics/fleet over HTTP: counters sum, gauges get labels
        http_front = RouterServer(router, port=0).start()
        scrapes = router.scrape_replica_metrics()
        assert [s[0] for s in scrapes] == ['r0', 'r1']
        fleet_url = 'http://127.0.0.1:%d/metrics/fleet' % http_front.port
        with urllib.request.urlopen(fleet_url, timeout=10) as r:
            assert r.status == 200
            fleet_text = r.read().decode()
        fleet = dobs.parse_prometheus_text(fleet_text)
        done = _sum_counter_from_scrapes(scrapes,
                                         'paddle_tpu_decode_requests_completed')
        assert done >= 4.0               # the 4 drill requests landed
        assert sum(v for _, _, v in
                   fleet['paddle_tpu_decode_requests_completed']['samples']) == done
        slots = {labels['replica']: v for _, labels, v in
                 fleet['paddle_tpu_decode_slots_total']['samples']}
        assert slots == {'r0': 2.0, 'r1': 2.0}   # gauge: labeled, not 4

        # -- the tentpole drill: traced request + kill -9 mid-generation
        before = {p.pid: _line_count(_span_file(trace_dir, p.pid))
                  for p in procs}
        result = {}

        def fire():
            result['fin'] = router.generate_nonstream(
                [3, 5, 7], max_new_tokens=_MAX_NEW_CAP, timeout=120)

        th = threading.Thread(target=fire)
        th.start()
        victim = None
        deadline = time.monotonic() + 60
        while victim is None and time.monotonic() < deadline:
            for p in procs:                  # first replica to emit a span
                if _line_count(_span_file(trace_dir, p.pid)) > before[p.pid]:
                    victim = p
                    break
            time.sleep(0.002)
        assert victim is not None, 'no replica span appeared'
        os.kill(victim.pid, signal.SIGKILL)  # the real thing
        th.join(120)

        fin = result['fin']
        assert fin['retries'] >= 1           # the failover actually fired
        assert fin['tokens'] == long_ref     # retried bitwise on survivor
        trace_id = fin['trace_id']
        survivor_id = by_pid[[p for p in procs if p is not victim][0].pid]

        # -- merge all three processes' span files into ONE timeline
        paths = sorted(glob.glob(os.path.join(trace_dir, 'spans-*.jsonl')))
        assert len(paths) == 3               # router (this process) + 2
        chrome, summary = merge_span_files(paths, trace_id=trace_id)
        assert summary['unresolved_parents'] == []   # parent links hold
        assert set(summary['offsets_s']) >= {'router', 'r0', 'r1'}

        spans = [s for p in paths for s in load_span_file(p)['spans']
                 if s['trace_id'] == trace_id]
        assert len(spans) >= 6
        by_name = {}
        for s in spans:
            by_name.setdefault(s['name'], []).append(s)
        assert {s['process'] for s in spans} == {'router', 'r0', 'r1'}
        root = by_name['router/request'][0]
        assert root['parent_span_id'] is None
        retry = by_name['router/retry'][0]
        dispatch = by_name['router/dispatch'][0]
        assert retry['parent_span_id'] == root['span_id']
        assert dispatch['parent_span_id'] == root['span_id']
        assert retry['args']['replica'] != dispatch['args']['replica']
        victim_id = by_pid[victim.pid]
        for s in spans:
            if s['process'] == victim_id:    # victim hangs off the RETRY
                assert s['parent_span_id'] == retry['span_id'], s
            elif s['process'] == survivor_id:  # survivor off the DISPATCH
                assert s['parent_span_id'] == dispatch['span_id'], s
        assert 'replica/prefill' in by_name
        assert any(s['process'] == survivor_id
                   for s in by_name['replica/token'])

        # -- scrape hardening: the kill -9'd replica costs one bounded
        # failure tick, never the fleet scrape
        f0 = _counter('router_scrape_failures', replica=victim_id)
        scrapes = router.scrape_replica_metrics(timeout_s=2.0)
        assert [s[0] for s in scrapes] == [survivor_id]
        assert _counter('router_scrape_failures', replica=victim_id) == f0 + 1
        with urllib.request.urlopen(fleet_url, timeout=15) as r:
            assert r.status == 200
            text = r.read().decode()
        assert 'decode_requests_completed' in text   # survivor's view
    finally:
        if http_front is not None:
            http_front.shutdown()
        if router is not None:
            router.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(30)
        dobs.reset_distributed()


def test_trace_overhead_sampled_off_is_zero_span(tmp_path, monkeypatch):
    """The same serial request sweep through a router and one in-process
    replica with ``PADDLE_TPU_TRACE_SAMPLE=0`` (the production default) and
    then ``=1`` with span records on: a structurally free disabled path —
    ZERO spans recorded with sampling off, spans flowing with it on,
    bitwise-identical tokens either way."""
    from shared_programs import build_shared_prompt_work
    from paddle_tpu.serving import ServingServer
    from paddle_tpu.serving.tier.replica import build_replica_stack
    monkeypatch.delenv(ENV_TRACE_DIR, raising=False)
    monkeypatch.delenv(ENV_TRACE_SAMPLE, raising=False)
    spans, tokens = {}, {}
    with guard():
        model = build_tiny_lm()
        work = build_shared_prompt_work(4)
        pad = -(-(16 + 16) // 4) * 4
        refs = [greedy_generate(model, p, m, pad_len=pad) for p, m in work]
        engine, scheduler, _ = build_replica_stack(
            model=model, replica_id='trace-ab', slots=4, queue_depth=64)
        engine.warmup()
        server = ServingServer(None, port=0, generator=scheduler).start()
        try:
            with Router([f'http://127.0.0.1:{server.port}'],
                        health_poll_s=0.3) as router:
                for mode, env in (('off', {ENV_TRACE_SAMPLE: '0'}),
                                  ('on', {ENV_TRACE_SAMPLE: '1',
                                          ENV_TRACE_DIR: str(tmp_path)})):
                    for k, v in env.items():
                        monkeypatch.setenv(k, v)
                    s0 = _counter('trace_spans_recorded')
                    tokens[mode] = [
                        router.generate(p, max_new_tokens=m,
                                        timeout=120)['tokens']
                        for p, m in work]
                    spans[mode] = _counter('trace_spans_recorded') - s0
        finally:
            dobs.reset_distributed()      # drop the recorder bound to tmp
            scheduler.close(drain=True, timeout=30)
            server.shutdown(drain=True)
    assert spans['off'] == 0                 # disabled path does no work
    assert spans['on'] > 0
    assert tokens['off'] == refs and tokens['on'] == refs
