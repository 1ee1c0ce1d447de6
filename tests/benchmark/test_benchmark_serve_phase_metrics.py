"""The eight per-layer metrics that read the decode replica's phase
histograms, its logits-copy counter and its per-call spans (PR 24; lib/decode_phases.py): every reader on
a run written by hand, on a program that records none of it (the parent, on
which the driver tries new readers), and on a CPU rehearsal of the tiny
serve cell through a table of this test's own (data/table_tiny_phases.json:
table_tiny.json plus these metrics, the cell under another name so that its
out/ directory is not the one test_benchmark_harness.py writes from another
worker)."""
import json
import math
import os

import pytest

from bench_testlib import BENCH, DATA, load, table

PHASE_TABLE = os.path.join(DATA, 'table_tiny_phases.json')
NEW = ['engine_forward_share', 'engine_device_wait_share',
       'engine_logits_copy_share', 'engine_sample_share',
       'logits_copy_bytes_per_token', 'scheduler_self_share',
       'serve_idle_in_forward_share', 'serve_idle_unattributed_share']
FROM_REGISTRY = NEW[:6]


class Ctx:
    """What a reader asks of the harness's Context."""
    stats = load('lib/stats.py')
    xplane = load('lib/xplane.py')

    def module(self, kind, name):
        return load(f'{kind}/{name}.py')


def _reader(name):
    return load(f'layer_metrics/{name}.py')


def _histogram(samples):
    """The registry's export of a histogram: {labels: [observations]}."""
    return {'type': 'histogram', 'samples': [
        {'labels': dict(labels), 'sum': sum(xs), 'count': len(xs),
         'recent': sorted(xs)} for labels, xs in samples.items()]}


def _counter(value):
    return {'type': 'counter', 'samples': [{'labels': {}, 'value': value}]}


def _registry():
    def call(name, phases):
        return {(('call', name), ('phase', p)): xs
                for p, xs in phases.items()}
    engine = dict(call('prefill', {
        'pack': [0.001] * 2, 'forward': [0.170] * 2,
        'device_wait': [0.002] * 2, 'logits_copy': [0.006] * 2,
        'sample': [0.001] * 2}))
    engine.update(call('step', {
        'pack': [0.002], 'forward': [0.230], 'device_wait': [0.001],
        'logits_copy': [0.004], 'sample': [0.023]}))
    return {
        'decode_engine_phase_seconds': _histogram(engine),
        'decode_scheduler_phase_seconds': _histogram({
            (('phase', 'cycle'),): [0.640, 0.050],
            (('phase', 'admit'),): [0.001, 0.001],
            (('phase', 'engine'),): [0.620],
            (('phase', 'emit'),): [0.004],
            (('phase', 'wait'),): [0.050]}),
        # two prefills at bucket 32 and one step of 4 slots, V = 100,
        # float32: (2 * 32 + 4) * 100 * 4 bytes, for 2 + 4 tokens
        'decode_logits_bytes_copied': _counter(27200.0),
        'decode_tokens_generated': _counter(6.0),
    }


def test_the_engine_shares_are_phase_seconds_over_all_phases():
    run = {'registry': _registry()}
    total = 2 * 0.180 + 0.260
    want = {'engine_forward_share': 2 * 0.170 + 0.230,
            'engine_device_wait_share': 2 * 0.002 + 0.001,
            'engine_logits_copy_share': 2 * 0.006 + 0.004,
            'engine_sample_share': 2 * 0.001 + 0.023}
    got = {n: _reader(n).read(run, Ctx()) for n in want}
    for name, seconds in want.items():
        assert got[name] == pytest.approx(100 * seconds / total)
    pack = 100 * (2 * 0.001 + 0.002) / total
    assert sum(got.values()) + pack == pytest.approx(100.0)


def test_scheduler_self_share_leaves_out_waits_and_engine_calls():
    run = {'registry': _registry()}
    # busy 0.690 - 0.050 = 0.640 s, of which 0.620 s inside the engine
    assert _reader('scheduler_self_share').read(run, Ctx()) \
        == pytest.approx(100 * 0.020 / 0.640)


def test_logits_bytes_per_token_is_the_copy_counter_over_the_tokens():
    run = {'registry': _registry()}
    reader = _reader('logits_copy_bytes_per_token')
    assert reader.read(run, Ctx()) == pytest.approx(27200 / 6)
    # a window in which nothing was emitted, and the parent's registry
    run['registry']['decode_tokens_generated'] = _counter(0.0)
    assert reader.read(run, Ctx()) is None
    del run['registry']['decode_logits_bytes_copied']
    run['registry']['decode_tokens_generated'] = _counter(6.0)
    assert reader.read(run, Ctx()) is None


def _traced_run(obs, spans, gaps):
    """Program spans at chosen perf_counter seconds, and a device trace
    whose clock runs 5 s ahead of perf_counter, with these idle gaps (s)."""
    obs.reset()
    for name, a, b in spans:
        obs.tracer.complete(name, a, b)
    return {'registry': {}, 'trace': {
        'offset_ns': 5e9,
        'chips': [{'gaps': [((a + 5) * 1e9, (b + 5) * 1e9)
                            for a, b in gaps]}]}}


def test_idle_gaps_go_to_the_leaf_span_over_their_midpoint():
    from paddle_tpu import observability as obs
    run = _traced_run(obs, [
        ('scheduler/cycle', 10.0, 11.0),          # not a leaf: owns nothing
        ('scheduler/admit', 10.0, 10.01),
        ('engine/prefill', 10.02, 10.40),
        ('engine/prefill/forward', 10.03, 10.35),
        ('engine/prefill/logits_copy', 10.35, 10.40),
        ('engine/step/forward', 10.50, 10.80),
        ('scheduler/emit', 10.90, 10.95),
    ], gaps=[(10.05, 10.25),      # 0.20 under prefill/forward
             (10.36, 10.38),      # 0.02 under prefill/logits_copy
             (10.55, 10.65),      # 0.10 under step/forward
             (10.91, 10.93),      # 0.02 under scheduler/emit
             (10.96, 11.00)])     # 0.04 under the cycle alone: no leaf
    try:
        forward = _reader('serve_idle_in_forward_share').read(run, Ctx())
        nowhere = _reader('serve_idle_unattributed_share').read(run, Ctx())
    finally:
        obs.reset()
    assert forward == pytest.approx(100 * 0.30 / 0.38)
    assert nowhere == pytest.approx(100 * 0.04 / 0.38)
    assert run['idle_by_leaf']['engine/prefill/logits_copy'] \
        == pytest.approx(0.02)


@pytest.mark.parametrize('run', [
    {}, {'registry': {}}, {'registry': {}, 'trace': None},
    {'registry': {'decode_engine_phase_seconds':
                  {'type': 'histogram', 'samples': []}}}])
def test_readers_find_nothing_in_a_run_without_the_histograms(run):
    for name in NEW:
        assert _reader(name).read(dict(run), Ctx()) is None, name


def test_idle_readers_find_nothing_where_the_program_left_no_engine_span():
    """The parent's traced run: a device trace, replica/* spans only."""
    from paddle_tpu import observability as obs
    run = _traced_run(obs, [('replica/prefill', 10.0, 10.4),
                            ('replica/token', 10.5, 10.8)],
                      gaps=[(10.1, 10.2)])
    try:
        for name in NEW[6:]:
            assert _reader(name).read(run, Ctx()) is None
    finally:
        obs.reset()
    assert 'idle_by_leaf' not in run


def test_the_entries_are_the_last_of_the_table_and_all_for_the_serve_cell():
    entries = table()['per_layer'][-len(NEW):]
    assert [m['name'] for m in entries] == NEW
    for m in entries:
        assert m['workloads'] == ['gpt1_serve_saturated']
        assert m['moves'] == 'serve_tokens_per_s'
        assert m['source'] == ('program_counter' if m['name'] in FROM_REGISTRY
                               else 'device_trace')
        assert os.path.exists(os.path.join(BENCH, 'layer_metrics',
                                           m['name'] + '.py'))


@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_fills_the_histograms_traced_or_not(capsys, trace):
    """The six registry readers read the same from a traced and an untraced
    last_run.json; off a TPU the line carries them as null, and the two
    device-trace readers find no device plane and are left out."""
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', 'tiny_serve_phases', '--seed', '5',
                       '--seconds', '1', '--trace', str(trace)],
                      rehearsal=True, table=PHASE_TABLE)
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and last['correct'] is True, out[-3000:]
    if trace:
        assert set(FROM_REGISTRY) <= set(last['metrics'])
        assert not set(NEW[6:]) & set(last['metrics'])
        assert all(last['metrics'][n]['value'] is None
                   for n in FROM_REGISTRY)
    with open(os.path.join(BENCH, 'out', 'tiny_serve_phases',
                           'last_run.json')) as f:
        run = json.load(f)['run']
    values = {n: _reader(n).read(run, Ctx()) for n in FROM_REGISTRY}
    assert all(v is not None and math.isfinite(v) and v >= 0
               for v in values.values()), values
    shares = [values[n] for n in FROM_REGISTRY[:4]]
    assert 0 < sum(shares) <= 100.0
    assert 0 <= values['scheduler_self_share'] < 100.0
    # a token costs at least its own row of logits (float32)
    with open(os.path.join(os.path.dirname(DATA), 'configs',
                           'tiny_gpt.json')) as f:
        vocab = json.load(f)['model']['vocab_size']
    assert values['logits_copy_bytes_per_token'] >= 4 * vocab
    steps = sum(s['value'] for s in
                run['registry']['decode_steps']['samples'])
    forward = [s for s in
               run['registry']['decode_engine_phase_seconds']['samples']
               if s['labels'] == {'call': 'step', 'phase': 'forward'}]
    assert forward[0]['count'] == steps > 0
