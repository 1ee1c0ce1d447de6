"""Distributed-observability unit drills (ISSUE 17): trace-context header
round-trips, exact histogram percentiles, windowed series, fleet metric
merge semantics, the SLO monitor, the straggler monitor on synthetic
fleets, and the ``trace_merge --smoke`` tier-1 gate."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import distributed as dobs
from paddle_tpu.observability.trace_context import (
    ENV_TRACE_DIR, ENV_TRACE_SAMPLE, TRACE_HEADER, TraceContext,
    maybe_sample)

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Empty registry/series/recorder and no trace env around each test."""
    for env in (ENV_TRACE_DIR, ENV_TRACE_SAMPLE, dobs.ENV_SLO):
        monkeypatch.delenv(env, raising=False)
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------

def test_trace_context_header_roundtrip():
    root = TraceContext.root()
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.parent_span_id == root.span_id
    assert child.span_id != root.span_id

    headers = child.to_headers()
    assert set(headers) == {TRACE_HEADER}
    back = TraceContext.from_headers(headers)
    assert (back.trace_id, back.span_id, back.sampled) == (
        child.trace_id, child.span_id, True)
    # a replica's spans hang off the id it RECEIVED, not a fresh root
    assert back.child().parent_span_id == child.span_id


@pytest.mark.parametrize('bad', [
    'nonsense', 'aaa-bbb-1', 'g' * 16 + '-' + 'a' * 16 + '-1',
    'a' * 16 + '-' + 'b' * 16 + '-7', 'a' * 16 + '-' + 'b' * 16,
])
def test_trace_context_malformed_header_raises(bad):
    with pytest.raises(ValueError):
        TraceContext.from_header_value(bad)
    assert TraceContext.from_headers({}) is None


def test_maybe_sample_respects_rate_env(monkeypatch):
    monkeypatch.delenv(ENV_TRACE_SAMPLE, raising=False)
    assert maybe_sample() is None            # default: tracing off
    monkeypatch.setenv(ENV_TRACE_SAMPLE, '0')
    assert maybe_sample() is None
    monkeypatch.setenv(ENV_TRACE_SAMPLE, '1')
    ctx = maybe_sample()
    assert ctx is not None and ctx.sampled
    monkeypatch.setenv(ENV_TRACE_SAMPLE, 'lots')
    with pytest.raises(ValueError, match='PADDLE_TPU_TRACE_SAMPLE'):
        maybe_sample()
    monkeypatch.setenv(ENV_TRACE_SAMPLE, '1.5')
    with pytest.raises(ValueError, match='PADDLE_TPU_TRACE_SAMPLE'):
        maybe_sample()


# ---------------------------------------------------------------------------
# exact histogram percentiles (satellite b)
# ---------------------------------------------------------------------------

def test_histogram_percentile_matches_numpy_exactly():
    """The bounded sample ring gives EXACT percentiles (not bucket upper
    bounds) while the ring is not full — numpy 'linear' convention."""
    h = obs.registry.histogram('pct_drill', 'x', bounds=(0.1, 1, 10))
    rng = np.random.RandomState(7)
    values = rng.lognormal(mean=-2.0, sigma=1.0, size=400)
    for v in values:
        h.observe(float(v))
    for q in (0, 25, 50, 90, 99, 100):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(values, q)), rel=1e-12)
    # and the export carries the retained ring for offline analysis
    sample = h.labels().sample()
    assert len(sample['recent']) == 400
    assert sample['recent'] == sorted(sample['recent'])


def test_histogram_percentile_ring_keeps_recent_tail():
    from paddle_tpu.observability.metrics import RECENT_SAMPLES
    h = obs.registry.histogram('pct_ring', 'x', bounds=(1,))
    for _ in range(RECENT_SAMPLES):
        h.observe(1000.0)                    # old regime
    for _ in range(RECENT_SAMPLES):
        h.observe(1.0)                       # new regime displaces it
    assert h.percentile(50) == pytest.approx(1.0)
    assert h.percentile(100) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# windowed series
# ---------------------------------------------------------------------------

def test_windowed_series_percentile_rate_and_mean():
    s = dobs.WindowedSeries('drill', window_s=1.0, windows=4)
    for i in range(101):
        s.observe(float(i), now=100.0 + i * 0.01)   # 101 obs in ~1s
    now = 100.0 + 1.01
    assert s.percentile(50, now=now) == pytest.approx(50.0)
    assert s.percentile(99, now=now) == pytest.approx(99.0)
    assert s.mean(now=now) == pytest.approx(50.0)
    assert s.rate(now=now) == pytest.approx(101 / 1.01, rel=0.02)
    assert s.count(now=now) == 101


def test_windowed_series_slides_old_data_out():
    s = dobs.WindowedSeries('slide', window_s=1.0, windows=2)
    s.observe(100.0, now=10.0)               # will age out: ring holds
    s.observe(1.0, now=20.0)                 # 2 windows + current
    assert s.percentile(99, now=20.5) == pytest.approx(1.0)
    assert s.count(now=20.5) == 1


def test_windowed_series_books_a_steps_tokens_as_that_many_calls_would():
    """`observe(value, times=n)` (a decode step's tokens, booked once a
    step) leaves what n calls at the same stamp leave: count, total, the
    retained samples and their decimation past `max_samples`."""
    one = dobs.WindowedSeries('one', window_s=1.0, windows=2)
    many = dobs.WindowedSeries('many', window_s=1.0, windows=2)
    one._max_samples = many._max_samples = 8
    for step, (value, n) in enumerate([(1.0, 5), (2.0, 7), (3.0, 1)]):
        now = 50.0 + 0.1 * step
        for _ in range(n):
            one.observe(value, now=now)
        many.observe(value, now=now, times=n)
    assert many._cur == one._cur and many._cur['count'] == 13
    assert many.snapshot(now=50.5) == one.snapshot(now=50.5)


def test_series_registry_shared_and_reset():
    dobs.series('shared').observe(3.0)
    assert dobs.series('shared').count() == 1
    snap = dobs.series_snapshot()
    assert snap['shared']['count'] == 1
    dobs.reset_distributed()
    assert dobs.series('shared').count() == 0


# ---------------------------------------------------------------------------
# fleet metric merge semantics (tentpole: cross-host aggregation)
# ---------------------------------------------------------------------------

_SCRAPE_A = """\
# HELP reqs total requests
# TYPE reqs counter
reqs{route="gen"} 3
# TYPE occupancy gauge
occupancy 0.25
# TYPE lat histogram
lat_bucket{le="0.1"} 1
lat_bucket{le="1"} 2
lat_bucket{le="+Inf"} 2
lat_sum 0.6
lat_count 2
"""

_SCRAPE_B = """\
# TYPE reqs counter
reqs{route="gen"} 5
reqs{route="health"} 1
# TYPE occupancy gauge
occupancy 0.75
# TYPE lat histogram
lat_bucket{le="0.1"} 0
lat_bucket{le="1"} 4
lat_bucket{le="+Inf"} 5
lat_sum 7.5
lat_count 5
"""


def _samples(parsed, family):
    return {(name, tuple(sorted(labels.items()))): value
            for name, labels, value in parsed[family]['samples']}


def test_merge_fleet_metrics_counter_gauge_histogram():
    text = dobs.merge_fleet_metrics([('r0', _SCRAPE_A), ('r1', _SCRAPE_B)])
    parsed = dobs.parse_prometheus_text(text)

    # counters: summed per label-set across sources
    reqs = _samples(parsed, 'reqs')
    assert reqs[('reqs', (('route', 'gen'),))] == 8.0
    assert reqs[('reqs', (('route', 'health'),))] == 1.0

    # gauges: never summed — one sample per source, source-labeled
    occ = _samples(parsed, 'occupancy')
    assert occ[('occupancy', (('replica', 'r0'),))] == 0.25
    assert occ[('occupancy', (('replica', 'r1'),))] == 0.75

    # histograms: bucket counts + _sum/_count summed (ladders agree)
    lat = _samples(parsed, 'lat')
    assert lat[('lat_bucket', (('le', '0.1'),))] == 1.0
    assert lat[('lat_bucket', (('le', '1'),))] == 6.0
    assert lat[('lat_bucket', (('le', '+Inf'),))] == 7.0
    assert lat[('lat_count', ())] == 7.0
    assert lat[('lat_sum', ())] == pytest.approx(8.1)


def test_merge_fleet_metrics_ladder_skew_falls_back_to_labeling():
    skewed = _SCRAPE_B.replace('le="0.1"', 'le="0.5"')
    text = dobs.merge_fleet_metrics([('r0', _SCRAPE_A), ('r1', skewed)])
    lat = _samples(dobs.parse_prometheus_text(text), 'lat')
    # no cross-source sums: every bucket line carries its source label
    assert lat[('lat_bucket', (('le', '0.1'), ('replica', 'r0')))] == 1.0
    assert lat[('lat_bucket', (('le', '0.5'), ('replica', 'r1')))] == 0.0
    assert lat[('lat_count', (('replica', 'r1'),))] == 5.0


# ---------------------------------------------------------------------------
# SLO monitor
# ---------------------------------------------------------------------------

def test_slo_spec_parse_and_malformed():
    clauses = dobs.parse_slo_spec('ttft.p99<0.2, tokens.rate>100')
    assert [(c.series, c.agg, c.op, c.bound) for c in clauses] == [
        ('ttft', 'p99', '<', 0.2), ('tokens', 'rate', '>', 100.0)]
    for bad in ('ttft.p99', 'ttft<0.2', 'ttft.p42<0.2', 'ttft.p99<fast'):
        with pytest.raises(ValueError, match='PADDLE_TPU_SLO'):
            dobs.parse_slo_spec(bad)


def test_slo_monitor_burn_counter_and_vacuous_cold_start(monkeypatch):
    monkeypatch.setenv(dobs.ENV_SLO, 'ttft.p99<0.5,ttft.mean>0')
    mon = dobs.SLOMonitor.from_env()
    # cold series: vacuously ok — cold start is not an outage
    verdict = mon.evaluate()
    assert verdict['ok'] and all(c['ok'] for c in verdict['clauses'])

    for _ in range(20):
        dobs.series('ttft').observe(1.0)     # p99=1.0 breaches <0.5
    verdict = mon.evaluate()
    assert not verdict['ok']
    by_slo = {c['slo']: c for c in verdict['clauses']}
    assert not by_slo['ttft.p99<0.5']['ok']
    assert by_slo['ttft.mean>0']['ok']

    reg = obs.registry.to_dict()
    ok = {tuple(sorted(s['labels'].items())): s['value']
          for s in reg['slo_ok']['samples']}
    assert ok[(('slo', 'ttft.p99<0.5'),)] == 0
    assert ok[(('slo', 'ttft.mean>0'),)] == 1
    burns = {tuple(sorted(s['labels'].items())): s['value']
             for s in reg['slo_breaches']['samples']}
    assert burns[(('slo', 'ttft.p99<0.5'),)] == 1
    mon.evaluate()                           # burn counter accumulates
    assert sum(s['value'] for s in obs.registry.to_dict()
               ['slo_breaches']['samples']) == 2


# ---------------------------------------------------------------------------
# straggler monitor (synthetic fleets)
# ---------------------------------------------------------------------------

def test_straggler_monitor_flags_slow_host_and_writes_record(tmp_path):
    mon = dobs.StragglerMonitor(out_dir=str(tmp_path))
    for step in range(4):
        for host in range(3):
            mon.record(host, 0.10 + 0.001 * host)
        mon.record(3, 0.45)                  # one sleeper
    verdict = mon.evaluate(step=4)
    assert verdict['stragglers'] == ['3']
    assert verdict['zscores']['3'] > mon.threshold
    recs = [json.loads(line) for line in
            (tmp_path / 'straggler.jsonl').read_text().splitlines()]
    assert recs and recs[-1]['host'] == '3' and recs[-1]['step'] == 4
    reg = obs.registry.to_dict()
    assert reg['straggler_count']['samples'][0]['value'] == 1
    z = {s['labels']['host']: s['value']
         for s in reg['straggler_zscore']['samples']}
    assert z['3'] > 3.5 > z['0']


def test_straggler_monitor_quiet_on_healthy_jitter(tmp_path):
    mon = dobs.StragglerMonitor(out_dir=str(tmp_path))
    rng = np.random.RandomState(3)
    for step in range(6):
        for host in range(4):
            mon.record(host, 0.1 + float(rng.uniform(-0.004, 0.004)))
    assert mon.evaluate()['stragglers'] == []
    assert not (tmp_path / 'straggler.jsonl').exists()
    # a single host can never be a straggler relative to itself
    solo = dobs.StragglerMonitor()
    solo.record(0, 99.0)
    assert solo.evaluate() == {'stragglers': [], 'zscores': {}}


# ---------------------------------------------------------------------------
# span recorder + merge tool (satellite a)
# ---------------------------------------------------------------------------

def test_span_recorder_streams_jsonl_with_clock_header(
        tmp_path, monkeypatch):
    monkeypatch.setenv(ENV_TRACE_DIR, str(tmp_path))
    dobs.set_process_label('unit-proc')
    root = TraceContext.root()
    dobs.record_span(root, 'unit/root', 1.0, 2.0)
    dobs.record_span(root.child(), 'unit/child', 1.2, 1.8, detail='x')
    dobs.record_clock_offset('peer', 0.25, rtt_s=0.01)
    path = os.path.join(str(tmp_path), 'spans-%d.jsonl' % os.getpid())
    lines = [json.loads(line) for line in open(path)]
    assert 'clock' in lines[0] and lines[0]['clock']['process'] == 'unit-proc'
    spans = [rec['span'] for rec in lines if 'span' in rec]
    assert [s['name'] for s in spans] == ['unit/root', 'unit/child']
    assert spans[1]['parent_span_id'] == root.span_id
    assert spans[1]['args'] == {'detail': 'x'}
    assert spans[1]['dur_s'] == pytest.approx(0.6)
    offs = [rec['offset'] for rec in lines if 'offset' in rec]
    assert offs == [{'process': 'peer', 'offset_s': 0.25, 'rtt_s': 0.01,
                     'unix_time': offs[0]['unix_time']}]

    from tools.trace_merge import merge_span_files
    _, summary = merge_span_files([path])
    assert summary['spans'] == 2
    assert summary['unresolved_parents'] == []


def test_record_span_without_trace_dir_is_inert():
    assert os.environ.get(ENV_TRACE_DIR) is None
    assert dobs.span_recorder() is None
    dobs.record_span(TraceContext.root(), 'noop', 0.0, 1.0)
    dobs.record_clock_offset('peer', 0.1)    # both no-op without the dir


def test_trace_merge_smoke_cli_gate():
    """Tier-1 gate (ISSUE 17 satellite a): the merge tool's self-check —
    two synthetic processes with a known 5s clock skew — must pass."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, 'tools', 'trace_merge.py'),
         '--smoke'],
        cwd=_REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout)
    assert verdict['ok'] and all(verdict['checks'].values())
