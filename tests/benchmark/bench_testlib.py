"""Shared by the tests of the benchmark: where things are, and the harness
loaded by path (benchmark/ is not a package)."""
import importlib.util
import json
import os

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))
BENCH = os.path.join(REPO, 'benchmark')
DATA = os.path.join(os.path.dirname(__file__), 'data')
TINY_TABLE = os.path.join(DATA, 'table_tiny.json')


def load(relative, name=None):
    path = os.path.join(BENCH, relative)
    name = name or 'bench_' + relative.replace('/', '_').replace('.py', '')
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def table():
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        return json.load(f)


def rehearse(capsys, workload, trace=0, seconds=1, seed=3):
    """main(..., rehearsal=True) on the tiny table; (rc, last line, stdout)."""
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', workload, '--seed', str(seed),
                       '--seconds', str(seconds), '--trace', str(trace)],
                      rehearsal=True, table=TINY_TABLE)
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out
