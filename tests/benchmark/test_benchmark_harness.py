"""The harness end to end on the CPU, at the tiny sizes of a test-only table
(tests/benchmark/data/table_tiny.json, with test-only configuration and
traffic files beside it): no file under benchmark/ is edited to run them,
which is what lets a later PR add a cell by adding files and one entry.

The in-process entry main(argv, rehearsal=True, table=...) runs the same code
as the command; the command itself must refuse to run off the chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_testlib import REPO, TINY_TABLE, rehearse

RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


@pytest.mark.parametrize('workload,trace', [
    ('tiny_train', 0), ('tiny_train', 1), ('tiny_train_dp4', 0),
    ('tiny_train_dp4', 1), ('tiny_serve', 0), ('tiny_serve', 1)])
def test_rehearsal_prints_the_contract_line(capsys, workload, trace):
    rc, last, out = rehearse(capsys, workload, trace)
    assert rc == 0
    assert RESULT_KEYS <= set(last) <= RESULT_KEYS | {'breakdown'}, last
    assert last['correct'] is True, out[-3000:]
    assert last['attempted'] > 0 and last['failed'] == 0
    assert set(last['device']) >= {'platform', 'kind', 'count',
                                   'memory_peak_bytes'}
    with open(TINY_TABLE) as f:
        table = json.load(f)
    listed = {m['name']: m for m in
              table['per_layer' if trace else 'end_to_end']}
    assert last['metrics'], 'no metric reported'
    for name, metric in last['metrics'].items():
        assert name in listed and metric['unit'] == listed[name]['unit']
        # a rehearsal reports counts; a time or a rate comes from the chip
        assert metric['value'] is None or metric['unit'] == 'count'
    if trace:
        window = [n for n in last['metrics'] if n.endswith('compiles_in_window')]
        assert window and last['metrics'][window[0]]['value'] == 0
    else:
        assert 'setup_s' in last['metrics'] and len(last['metrics']) >= 2
    assert '[info] checks:' in out


def test_same_seed_same_requests():
    from bench_testlib import load
    loadgen = load('lib/loadgen.py')
    with open(os.path.join(REPO, 'benchmark/traffic/closed_c128.json')) as f:
        mix = json.load(f)['load']
    import numpy as np

    def stream(seed, client):
        rng = np.random.default_rng([seed, client])
        return [loadgen.draw_request(rng, mix, seed, k, client)
                for k in range(5)]

    assert stream(7, 3) == stream(7, 3)
    assert stream(7, 3) != stream(8, 3) and stream(7, 3) != stream(7, 4)


def test_the_serve_cell_reports_how_much_of_its_pool_is_held(capsys):
    rc, last, out = rehearse(capsys, 'tiny_serve', 1)
    assert rc == 0 and 'kv_pool_fill_share' in last['metrics']
    assert 'KV pool blocks held by live requests' in out


@pytest.mark.parametrize('samples,blocks,want', [
    ([10, 20, 30], 80, 25.0), ([79] * 4, 79, 100.0), ([], 80, None),
    ([10], None, None)])
def test_pool_fill_is_the_mean_of_the_samples_over_the_capacity(
        samples, blocks, want):
    from bench_testlib import load
    reader = load('layer_metrics/kv_pool_fill_share.py')
    run = {'samples': {'pool_blocks_used': samples},
           'counts': {'pool_blocks': blocks}}
    assert reader.read(run, None) == want


def test_a_profiled_slice_is_reduced_only_after_the_runner_returned():
    """profile() may sit inside a runner's window: it keeps the trace file
    and leaves the seconds of pure-Python reduction to reduce_trace()."""
    from bench_testlib import load
    harness = load('run.py', 'bench_run')
    with open(TINY_TABLE) as f:
        table = json.load(f)
    cell = next(w for w in table['workloads'] if w['name'] == 'tiny_serve')
    ctx = harness.Context(table, cell, seed=1, seconds=1, traced=True,
                          rehearsal=True, t0=0.0)
    real, calls = ctx.xplane, []

    class Recorder:
        mark_name = staticmethod(real.mark_name)

        @staticmethod
        def reduce(path):
            calls.append(path)
            return real.reduce(path)

    ctx.xplane = Recorder
    import jax.numpy as jnp
    with ctx.profile():
        jnp.ones(8).sum().block_until_ready()
    assert calls == [] and ctx.trace is None
    assert ctx.trace_file and os.path.exists(ctx.trace_file)
    ctx.reduce_trace()
    assert calls == [ctx.trace_file]


def _command(cwd, env):
    return subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload', 'resnet50_train',
         '--seed', '0', '--seconds', '1', '--trace', '0'],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_to_run_without_a_tpu():
    done = _command(REPO, dict(os.environ, JAX_PLATFORMS='cpu'))
    assert done.returncode != 0
    assert 'needs a TPU backend' in done.stderr
    assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout


def test_command_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        table = json.load(f)
    shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), tmp_path)
    for path in table['paths']:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns('out', '__pycache__'))
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('PYTHONPATH', None)
    done = _command(str(tmp_path), env)
    assert done.returncode != 0 and done.stdout.strip() == ''


class _FakeJax:
    """What _check_device asks of jax, for machines this sandbox lacks."""

    class _Device:
        platform = 'tpu'

        def __init__(self, kind):
            self.device_kind = kind

    def __init__(self, kind, count):
        self._devices = [self._Device(kind) for _ in range(count)]

    def default_backend(self):
        return 'tpu'

    def devices(self):
        return self._devices


@pytest.mark.parametrize('kind,count,chips,says', [
    ('TPU v5 lite', 1, 4, 'needs 4 chips'),
    ('TPU v9 imaginary', 4, 4, 'not in lib/peaks.json'),
    ('TPU v5 lite', 4, 4, None)])
def test_too_few_chips_or_an_unknown_device_is_an_error(kind, count, chips,
                                                        says):
    from bench_testlib import load
    harness = load('run.py', 'bench_run')

    class Ctx:
        rehearsal = False
        cell = {'name': 'some_cell'}
        peaks = None

        def find(self, kind, name, ext):
            return os.path.join(REPO, 'benchmark', kind, name + ext)

    ctx = Ctx()
    ctx.chips = chips
    if says is None:
        harness._check_device(ctx, _FakeJax(kind, count))
        assert ctx.peaks['bf16_flops_per_s'] == 197e12
    else:
        with pytest.raises(SystemExit) as refused:
            harness._check_device(ctx, _FakeJax(kind, count))
        assert says in str(refused.value)
