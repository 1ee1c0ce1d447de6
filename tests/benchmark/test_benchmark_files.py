"""BENCHMARK.json against the driver's contract, and every file it names:
each configuration, traffic mix, runner, reader and reference loads, and
every entry of `workloads` resolves. A later PR that adds an entry and its
files is held to the same checks without editing this test."""
import glob
import json
import os
import re

import pytest

from bench_testlib import BENCH, REPO, load, table

NAME = re.compile(r'^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$')
LAYER = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
TABLE = table()
with open(os.path.join(REPO, 'PERF.md')) as _f:
    PERF_MD = _f.read()
CELLS = [w['name'] for w in TABLE['workloads']]


def _find(kind, name, ext):
    for base in TABLE['paths']:
        path = os.path.join(REPO, base, kind, name + ext)
        if os.path.exists(path):
            return path
    raise AssertionError(f'no {kind}/{name}{ext} under {TABLE["paths"]}')


def _applies(metric, cell):
    return 'workloads' not in metric or cell in metric['workloads']


def test_table_has_exactly_the_contracts_keys_and_limits():
    assert set(TABLE) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert os.path.getsize(os.path.join(REPO, 'BENCHMARK.json')) <= 64 * 1024
    assert TABLE['command'][:2] == ['python3', 'benchmark/run.py']
    assert isinstance(TABLE['run_seconds'], int)
    assert 1 <= TABLE['run_seconds'] <= 51
    assert 1 <= len(TABLE['configs']) <= 24
    assert 2 <= len(TABLE['workloads']) <= 24
    assert 1 <= len(TABLE['end_to_end']) <= 16
    assert 1 <= len(TABLE['per_layer']) <= 128
    names = [e['name'] for k in ('configs', 'workloads', 'end_to_end',
                                 'per_layer') for e in TABLE[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names)), 'a name is used twice'
    for entry in TABLE['configs'] + TABLE['workloads']:
        assert len(entry['why']) <= 200, entry['name']
    for path in TABLE['paths']:
        assert os.path.isdir(os.path.join(REPO, path))
        assert re.match(r'^[A-Za-z0-9_.\-/]{1,200}$', path)
    four = sum(w['chips'] == 4 for w in TABLE['workloads'])
    assert four <= max(1, len(TABLE['workloads']) // 4)
    pairs = [(w['config'], w['traffic']) for w in TABLE['workloads']]
    assert len(pairs) == len(set(pairs))
    used = {w['config'] for w in TABLE['workloads']}
    assert used == {c['name'] for c in TABLE['configs']}
    files = [c['file'] for c in TABLE['configs']]
    assert len(files) == len(set(files))


def test_metrics_follow_the_contract():
    e2e = {m['name']: m for m in TABLE['end_to_end']}
    assert e2e['setup_s']['bound'] == 0.1
    for m in TABLE['end_to_end']:
        assert set(m) <= {'name', 'unit', 'better', 'bound', 'source',
                          'workloads'}
        assert 0.01 <= m['bound'] <= 0.1
        assert m['source'] in ('host_clock', 'device_trace')
        assert m['better'] in ('higher', 'lower')
    for m in TABLE['per_layer']:
        assert set(m) <= {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
        assert m['source'] in SOURCES and m['moves'] in e2e
        assert 'bound' not in m
        # a plain name, and the one PERF.md's table of layers uses
        assert LAYER.match(m['layer']), m['layer']
        assert f"`{m['layer']}`" in PERF_MD, m['layer']
        for cell in m.get('workloads', CELLS):
            # reported only where the metric it moves is
            assert cell in CELLS and _applies(e2e[m['moves']], cell)
        if m['name'].endswith('_roofline'):
            assert m['unit'] == '%'
    for m in TABLE['end_to_end'] + TABLE['per_layer']:
        assert set(m.get('workloads', [])) <= set(CELLS)


@pytest.mark.parametrize('cell', CELLS)
def test_cell_resolves_to_files_that_load(cell):
    w = next(w for w in TABLE['workloads'] if w['name'] == cell)
    assert w['chips'] in (1, 4)
    entry = next(c for c in TABLE['configs'] if c['name'] == w['config'])
    assert any(entry['file'].startswith(p + '/') for p in TABLE['paths'])
    assert entry['source'].startswith('http')
    with open(os.path.join(REPO, entry['file'])) as f:
        config = json.load(f)
    assert config['name'] == w['config']
    assert config['reduced'] == entry['reduced']
    for key in ('source', 'runner', 'family', 'model', 'dtype_policy',
                'check', 'assumed', 'departures', 'deployment'):
        assert key in config, key
    with open(_find('traffic', w['traffic'], '.json')) as f:
        traffic = json.load(f)
    assert traffic['runner'] == config['runner']
    assert hasattr(load(os.path.relpath(
        _find('runners', config['runner'], '.py'), BENCH)), 'run')
    for kind in ('programs', 'reference'):
        _find(kind, config['family'], '.py')
    # every cell reports setup_s, another end-to-end metric and a layer's
    e2e = [m for m in TABLE['end_to_end'] if _applies(m, cell)]
    assert 'setup_s' in [m['name'] for m in e2e] and len(e2e) >= 2
    assert any(_applies(m, cell) for m in TABLE['per_layer'])
    mesh = traffic.get('mesh') or {}
    chips = 1
    for n in mesh.values():
        chips *= n
    assert chips == w['chips']


@pytest.mark.parametrize('kind,key', [('end_to_end', 'end_to_end'),
                                      ('per_layer', 'layer_metrics')])
def test_every_metric_has_a_reader_that_says_the_same(kind, key):
    for m in TABLE[kind]:
        if m['name'] == 'setup_s':
            continue            # the harness's own clock, no reader
        reader = load(os.path.relpath(_find(key, m['name'], '.py'), BENCH))
        assert reader.NAME == m['name'] and reader.UNIT == m['unit']
        assert callable(reader.read)
        if kind == 'per_layer':
            assert reader.LAYER == m['layer'] and reader.MOVES == m['moves']


def test_every_data_and_code_file_of_the_benchmark_loads():
    for path in glob.glob(os.path.join(BENCH, '*', '*.json')):
        with open(path) as f:
            json.load(f)
    listed = {m['name'] for k in ('end_to_end', 'per_layer')
              for m in TABLE[k]}
    for path in sorted(glob.glob(os.path.join(BENCH, '*', '*.py'))):
        module = load(os.path.relpath(path, BENCH))
        kind = os.path.basename(os.path.dirname(path))
        if kind in ('end_to_end', 'layer_metrics'):
            assert module.NAME in listed, f'{path} is read by no entry'
        with open(path) as f:
            source = f.read()
        # the yardstick stands alone: nothing of the old bench is imported
        assert not re.search(r'^\s*(import|from)\s+(bench|chip_smoke|tools)\b',
                             source, re.M), path


def test_peaks_carry_their_source_and_know_only_measured_devices():
    with open(os.path.join(BENCH, 'lib', 'peaks.json')) as f:
        peaks = json.load(f)
    assert set(peaks) == {'TPU v5 lite'}
    for entry in peaks.values():
        assert 'Google Cloud documentation' in entry['source']
        assert entry['bf16_flops_per_s'] == 197e12
        assert entry['hbm_bytes_per_s'] == 819e9


def test_flops_are_bench_pys_arithmetic_at_the_published_sizes():
    def config(name):
        with open(os.path.join(BENCH, 'configs', name + '.json')) as f:
            return json.load(f)

    def traffic(name):
        with open(os.path.join(BENCH, 'traffic', name + '.json')) as f:
            return json.load(f)

    # bench.py::bench_bert: seq * (72 L h^2 + 12 L h S + 6 h V)
    h, layers, vocab, seq = 768, 12, 30522, 128
    want = seq * (72.0 * layers * h * h + 12.0 * layers * h * seq
                  + 6.0 * h * vocab)
    got = load('flops/bert_base.py').per_sample(
        config('bert_base'), traffic('pretrain_s128_bs128'))
    assert got == want
    # bench.py's RESNET50_TRAIN_GFLOP_PER_IMG = 12.3 is 3 x 4.09 G
    # multiply-adds taken for FLOPs; at 2 FLOPs each (as its BERT formula
    # counts, and as XLA's cost analysis of the step does: 24.4 G) it is 24.6
    resnet = load('flops/resnet50.py')
    got = resnet.per_sample(config('resnet50'), traffic('train_bs128'))
    assert got == pytest.approx(2 * 12.3e9, rel=5e-3)
    assert resnet.forward_macs(50, 224, 1000) == pytest.approx(4.09e9,
                                                               rel=1e-3)
