"""The benchmark's one command: one run = one new process = one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration on the device from --seed, checks it against
the plain reference, warms up exactly the shapes the cell uses, measures for
--seconds, and prints one JSON object as the last line of stdout:
{correct, attempted, failed, metrics, device} and, traced, breakdown.
Everything else worth reading goes on earlier `[info]` lines and into
benchmark/out/<workload>/. There is no CPU fallback: without a TPU, with
fewer chips than the cell asks for, or on a device_kind that lib/peaks.json
does not list, the run exits non-zero and prints no result.

The harness is driven by data. It knows no configuration, traffic mix,
runner or metric by name: BENCHMARK.json names them, and each is a file of
its own found under the directories in its "paths" (README.md).

main(argv, rehearsal=True, table=...) is the in-process entry of the tests
in tests/benchmark/: it runs the same code on whatever backend the process
has, at the tiny sizes of a test-only table, and reports every metric that
is not a count as null. No flag and no environment variable reaches it.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()        # set-up is counted from here

import argparse                  # noqa: E402
import contextlib                # noqa: E402
import glob                      # noqa: E402
import importlib.util            # noqa: E402
import json                      # noqa: E402
import math                      # noqa: E402
import os                        # noqa: E402
import shutil                    # noqa: E402
import sys                       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)     # `python3 benchmark/run.py` puts HERE first


# where the readers of each list of BENCHMARK.json live
READERS = {'end_to_end': 'end_to_end', 'per_layer': 'layer_metrics'}


def _load_py(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Context:
    """What a runner and a metric reader get from the harness: the cell, its
    files, the clock the set-up is counted from, and the profiler."""

    def __init__(self, table, cell, seed, seconds, traced, rehearsal, t0):
        self.table, self.cell = table, cell
        self.seed, self.seconds = seed, seconds
        self.traced, self.rehearsal, self.t0 = traced, rehearsal, t0
        self.chips = cell['chips']
        entry = next(c for c in table['configs']
                     if c['name'] == cell['config'])
        with open(os.path.join(ROOT, entry['file'])) as f:
            self.config = json.load(f)
        with open(self.find('traffic', cell['traffic'], '.json')) as f:
            self.traffic = json.load(f)
        self._modules = {}
        self.out_dir = os.path.join(HERE, 'out', cell['name'])
        os.makedirs(self.out_dir, exist_ok=True)
        self.xplane = self.module('lib', 'xplane')
        self.stats = self.module('lib', 'stats')
        self.trace = self.trace_file = self.peaks = None
        self.memory_peak, self.memory_stats = 0, {}
        self.phases, self._phase_from = [], t0

    def find(self, kind, name, ext):
        """<path>/<kind>/<name><ext> under the first of the table's paths
        that holds it."""
        tried = []
        for base in self.table['paths']:
            path = os.path.join(ROOT, base, kind, name + ext)
            if os.path.exists(path):
                return path
            tried.append(path)
        raise FileNotFoundError(f'no {kind} file {name + ext!r}; looked at '
                                + ', '.join(tried))

    def module(self, kind, name):
        if (kind, name) not in self._modules:
            self._modules[kind, name] = _load_py(
                self.find(kind, name, '.py'), f'benchmark_{kind}_{name}')
        return self._modules[kind, name]

    def phase(self, name):
        """Closes one phase of the set-up: its seconds go on an [info] line,
        so that what set-up is made of can be read from any run."""
        now = time.perf_counter()
        self.phases.append((name, round(now - self._phase_from, 2)))
        self._phase_from = now

    def info(self, text):
        print(f'[info] {text}', flush=True)

    @contextlib.contextmanager
    def profile(self):
        """jax.profiler around the body, with this benchmark's begin and end
        marks. Only the trace file is kept here: a runner may be inside its
        window, and reducing a trace is seconds of pure Python that would
        take the interpreter from the code being measured (reduce_trace,
        after the runner has returned). The Python tracer is off: it would
        slow exactly the host code whose gaps are being attributed."""
        import jax
        directory = os.path.join(self.out_dir, 'trace')
        shutil.rmtree(directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False   # categories come without it
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            self._mark('begin')
            yield
            self._mark('end')
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(directory, 'plugins', 'profile', '*',
                                       '*.xplane.pb'))
        self.trace_file = found[0] if found else None

    def reduce_trace(self):
        """self.trace from the profiled slice's file (None where nothing was
        profiled or the trace has no device plane)."""
        if self.trace_file is not None:
            t = time.perf_counter()
            self.trace = self.xplane.reduce(self.trace_file)
            self.info(f'trace {self.trace_file} reduced in '
                      f'{time.perf_counter() - t:.1f} s, after the window')

    def sample_memory(self):
        """Device memory now, on the fullest of the cell's chips: the
        buffers in use plus what the runtime has reserved for the loaded
        program's temporaries (it keeps the two apart, and its own
        peak_bytes_in_use sees only the first). Runners call this while the
        window's work is in flight; the largest reading is kept."""
        import jax
        for d in jax.devices()[:self.chips]:
            stats = d.memory_stats() or {}
            now = stats.get('bytes_in_use', 0) + stats.get('bytes_reserved', 0)
            self.memory_peak = max(self.memory_peak, now,
                                   stats.get('peak_bytes_in_use', 0))
            self.memory_stats = stats

    def _mark(self, label):
        import jax
        name = self.xplane.mark_name(label, time.perf_counter_ns())
        with jax.profiler.TraceAnnotation(name):
            pass


def _applies(metric, cell_name):
    return 'workloads' not in metric or cell_name in metric['workloads']


def _number(value):
    """A metric value for the result line: all digits, null if not finite."""
    if value is None or not math.isfinite(value):
        return None
    return value


def _device(ctx, jax):
    devices = jax.devices()
    ctx.sample_memory()
    return {'platform': devices[0].platform, 'kind': devices[0].device_kind,
            'count': len(devices), 'memory_peak_bytes': ctx.memory_peak}


def _check_device(ctx, jax):
    """No result off the chip: a measured run needs the TPU, the cell's
    chips, and a device whose peaks are written down with their source."""
    with open(ctx.find('lib', 'peaks', '.json')) as f:
        table = json.load(f)
    devices = jax.devices()
    kind = devices[0].device_kind
    if ctx.rehearsal:
        ctx.peaks = table.get(kind)
        return
    if jax.default_backend() != 'tpu':
        sys.exit(f'benchmark: needs a TPU backend, found '
                 f'{jax.default_backend()!r} ({devices[0]}). A measured run '
                 'has no CPU fallback; tests/benchmark/ rehearses the '
                 'harness on the CPU.')
    if len(devices) < ctx.chips:
        sys.exit(f"benchmark: cell {ctx.cell['name']!r} needs {ctx.chips} "
                 f'chips, jax reports {len(devices)}.')
    if kind not in table:
        sys.exit(f'benchmark: device_kind {kind!r} is not in lib/peaks.json '
                 f'({sorted(table)}); add its published peaks with their '
                 'source before measuring on it.')
    ctx.peaks = table[kind]


def main(argv=None, rehearsal=False, table=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = _T0 if not rehearsal else time.perf_counter()

    with open(table or os.path.join(ROOT, 'BENCHMARK.json')) as f:
        table = json.load(f)
    cells = {w['name']: w for w in table['workloads']}
    if args.workload not in cells:
        sys.exit(f'benchmark: no workload {args.workload!r}; the table has '
                 f'{sorted(cells)}')
    ctx = Context(table, cells[args.workload], args.seed, args.seconds,
                  bool(args.trace), rehearsal, t0)

    if not rehearsal:
        # the eager decode path is ~200 per-op programs that each compile in
        # under jax's 1 s persistence floor: without this the second run of
        # a checkout would compile them all again
        os.environ.setdefault('PADDLE_TPU_COMPILE_CACHE_MIN_COMPILE_SECS',
                              '0')
    import jax

    import paddle_tpu  # noqa: F401  (a bare directory fails here, unprinted)
    from paddle_tpu import observability as obs
    from paddle_tpu.core.compile_cache import setup_persistent_cache
    _check_device(ctx, jax)
    ctx.cache_dir = setup_persistent_cache()
    ctx.counter = ctx.module('lib', 'compiles').CompileCounter()
    ctx.phase('start, imports, backend')
    ctx.info(f"cell {ctx.cell['name']} = {ctx.cell['config']} x "
             f"{ctx.cell['traffic']} on {ctx.chips} chip(s), seed "
             f'{ctx.seed}, {ctx.seconds:g} s, trace {int(ctx.traced)}; '
             f'compile cache at {ctx.cache_dir}')

    # telemetry is on only in the traced run, and starts empty: with it and
    # the profiled slice the serve cell is slower than untraced (PERF.md
    # section 5), so no judged number comes from a traced run
    obs.tracer.max_events = max(obs.tracer.max_events, 2_000_000)
    with obs.telemetry_guard(ctx.traced):
        obs.reset()
        run = ctx.module('runners', ctx.config['runner']).run(ctx)
    ctx.reduce_trace()
    run['setup_s'] = run['window_open'] - t0
    run['device'] = _device(ctx, jax)
    run['trace'] = ctx.trace
    run['peaks'] = ctx.peaks

    kind = 'per_layer' if ctx.traced else 'end_to_end'
    e2e = {m['name'] for m in table['end_to_end']
           if _applies(m, ctx.cell['name'])}
    metrics = {}
    for m in table[kind]:
        if not _applies(m, ctx.cell['name']) or \
                m.get('moves', m['name']) not in e2e:
            continue
        if m['name'] == 'setup_s':
            value = run['setup_s']
        else:
            reader = ctx.module(READERS[kind], m['name'])
            value = reader.read(run, ctx) \
                if run['runner'] in reader.RUNNERS else None
        if value is None:
            continue            # nothing to read: the metric is left out
        if rehearsal and m['unit'] != 'count':
            value = None
        metrics[m['name']] = {'value': _number(value), 'unit': m['unit']}

    result = {'correct': bool(run['correct']), 'attempted': run['attempted'],
              'failed': run['failed'], 'metrics': metrics,
              'device': run['device']}
    if ctx.traced and ctx.trace is not None:
        result['device']['busy_s'] = ctx.trace['busy_s']
        result['device']['window_s'] = ctx.trace['slice_s']
        chip = ctx.trace['chips'][0]
        result['breakdown'] = {
            'device_ops': [[f'{sig} x{calls}, e.g. {name}', s]
                           for sig, s, calls, name in chip['ops']],
            'idle_gaps': ctx.xplane.attribute_gaps(
                chip['gaps'], run.get('spans', []), ctx.trace['offset_ns'],
                run.get('span_names', []))}
        for c in ctx.trace['chips']:
            c.pop('gaps')
    ctx.info(f"checks: {json.dumps(run.get('checks', {}))}")
    ctx.info(f"compiles: set-up {run['compiles']['setup']}, window "
             f"{run['compiles']['window']}")
    ctx.info(f'set-up phases, s: {dict(ctx.phases)}')
    ctx.info(f"set-up {run['setup_s']:.1f} s; device {run['device']}; "
             f'memory_stats of the last chip read {ctx.memory_stats}')
    run.pop('spans', None)
    with open(os.path.join(ctx.out_dir, 'last_run.json'), 'w') as f:
        json.dump({'args': vars(args), 'result': result, 'run': run}, f,
                  indent=1, default=str)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
