"""The five per-layer metrics that read the serve host's threads from inside
(PR 34; lib/host_threads.py): the thread-CPU counters beside the phase
histograms, the handler threads' CPU seconds, and the idle seconds under the
worker's own Python with the bookkeeping leaf in the list (read from the
program's spans only where its span buffer dropped none). Every reader on a run written by hand, on a program
that records none of it (the parent, on which the driver tries new readers),
and on traced and untraced CPU rehearsals of two tiny serve cells through a
table and a traffic file of this test's own (data/table_tiny_host_threads
.json, traffic/tiny_closed_threads.json: cells under names of their own, so
that their out/ directories are nobody else's). Entries of BENCHMARK.json
are found by name, never by place."""
import json
import math
import os

import pytest

from bench_testlib import BENCH, DATA, load, table

TABLE = os.path.join(DATA, 'table_tiny_host_threads.json')
NEW = {'worker_on_cpu_share': ('scheduler', 'program_counter', '%'),
       'worker_lock_wait_share': ('scheduler', 'program_counter', '%'),
       'engine_forward_offcpu_share': ('decode_engine', 'program_counter',
                                       '%'),
       'http_handler_cpu_share': ('scheduler_entry', 'program_counter', '%'),
       'serve_idle_in_worker_python_share': ('device', 'device_trace', '%')}
FROM_REGISTRY = ['worker_on_cpu_share', 'worker_lock_wait_share',
                 'engine_forward_offcpu_share', 'http_handler_cpu_share']
SERVE_CELLS = ['gpt1_serve_saturated', 'kanana2_serve_saturated',
               'brumby_serve_saturated', 'sdar_serve_saturated']
COUNTERS = ['decode_engine_phase_cpu_seconds',
            'decode_scheduler_phase_cpu_seconds', 'http_handler_cpu_seconds']


class Ctx:
    """What a reader asks of the harness's Context."""
    stats = load('lib/stats.py')
    xplane = load('lib/xplane.py')

    def __init__(self):
        self.lines = []

    def module(self, kind, name):
        return load(f'{kind}/{name}.py')

    def info(self, text):
        self.lines.append(text)


def _reader(name):
    return load(f'layer_metrics/{name}.py')


def _histogram(samples):
    return {'type': 'histogram', 'samples': [
        {'labels': dict(labels), 'sum': sum(xs), 'count': len(xs),
         'recent': sorted(xs)} for labels, xs in samples.items()]}


def _counter(samples):
    return {'type': 'counter', 'samples': [
        {'labels': dict(labels), 'value': value}
        for labels, value in samples.items()]}


def _registry():
    """Two prefills and one step; a cycle of 0.640 s busy and one of 0.050 s
    waiting. The worker ran 0.400 s of its 0.640 busy seconds; of a
    prefill's 0.170 s forward it ran 0.050 s."""
    def call(name, phases):
        return {(('call', name), ('phase', p)): x for p, x in phases.items()}
    wall = dict(call('prefill', {
        'pack': [0.010] * 2, 'forward': [0.170] * 2,
        'device_wait': [0.002] * 2, 'logits_copy': [0.006] * 2,
        'sample': [0.002] * 2}))
    wall.update(call('step', {
        'pack': [0.020], 'forward': [0.180], 'device_wait': [0.030],
        'logits_copy': [0.004], 'sample': [0.006]}))
    cpu = dict(call('prefill', {
        'pack': 0.012, 'forward': 0.100, 'device_wait': 0.0,
        'logits_copy': 0.010, 'sample': 0.004}))
    cpu.update(call('step', {
        'pack': 0.010, 'forward': 0.160, 'device_wait': 0.001,
        'logits_copy': 0.004, 'sample': 0.005}))
    return {
        'decode_engine_phase_seconds': _histogram(wall),
        'decode_engine_phase_cpu_seconds': _counter(cpu),
        'decode_scheduler_phase_seconds': _histogram({
            (('phase', 'cycle'),): [0.640, 0.050],
            (('phase', 'admit'),): [0.004, 0.001],
            (('phase', 'engine'),): [0.610],
            (('phase', 'emit'),): [0.010, 0.006],
            (('phase', 'book'),): [0.009, 0.001],
            (('phase', 'wait'),): [0.050]}),
        'decode_scheduler_phase_cpu_seconds': _counter({
            (('phase', 'cycle'),): 0.400,
            (('phase', 'admit'),): 0.004,
            (('phase', 'engine'),): 0.380,
            (('phase', 'emit'),): 0.008,
            (('phase', 'book'),): 0.007,
            (('phase', 'wait'),): 0.0005}),
        'http_handler_cpu_seconds': _counter({(): 0.138}),
    }


def test_the_four_registry_readers_on_a_run_written_by_hand():
    run, ctx = {'registry': _registry()}, Ctx()
    busy = 0.690 - 0.050
    got = {n: _reader(n).read(run, ctx) for n in FROM_REGISTRY}
    assert got['worker_on_cpu_share'] == pytest.approx(100 * 0.400 / busy)
    # wall - CPU of pack 0.040 - 0.022, sample 0.010 - 0.009, admit 0.005 -
    # 0.004, emit 0.016 - 0.008, book 0.010 - 0.007: forward's 0.260 s off
    # the CPU are not in it
    assert got['worker_lock_wait_share'] == pytest.approx(
        100 * (0.018 + 0.001 + 0.001 + 0.008 + 0.003) / busy)
    assert got['engine_forward_offcpu_share'] == pytest.approx(
        100 * (0.520 - 0.260) / 0.520)
    # over the seconds the registry covers: the worker's cycles tile them
    assert got['http_handler_cpu_share'] == pytest.approx(100 * 0.138 / 0.690)
    # the reader of the first leaves the table of every phase on an [info]
    # line: the CPU column beside PERF.md's engine time from inside
    assert len(ctx.lines) == 1 and 'engine/forward 0.520/0.260' in ctx.lines[0]
    assert 'scheduler/book 0.010/0.007' in ctx.lines[0]


def _with_cpu(name, labels, value):
    registry = _registry()
    for sample in registry[name]['samples']:
        if sample['labels'] == labels:
            sample['value'] = value
    return registry


def test_a_phase_that_reads_more_cpu_than_wall_is_summed_as_it_reads():
    """A thread cannot run for longer than the time that passed: a phase
    that reads more CPU than wall was charged a neighbour's ticks. Nothing
    is cut off at 0 (that would hide it, and keep what the neighbour lost
    out of the sum): the shares are signed."""
    host = load('lib/host_threads.py')
    registry = _with_cpu('decode_scheduler_phase_cpu_seconds',
                         {'phase': 'emit'}, 0.056)
    assert host.worker_lock_wait_share({'registry': registry}) \
        == pytest.approx(100 * (0.018 + 0.001 + 0.001 - 0.040 + 0.003)
                         / 0.640)
    # the reader of worker_on_cpu_share names such a phase on an [info] line
    # of its own: the run's log says when its sums cannot all be true
    ctx = Ctx()
    _reader('worker_on_cpu_share').read({'registry': registry}, ctx)
    assert len(ctx.lines) == 2 and 'MORE CPU THAN WALL' in ctx.lines[1] \
        and ctx.lines[1].endswith(': scheduler/emit 0.016/0.056')
    registry = _with_cpu('decode_engine_phase_cpu_seconds',
                         {'call': 'step', 'phase': 'forward'}, 0.500)
    forward = host.seconds({'registry': registry},
                           'decode_engine_phase_cpu_seconds',
                           'phase')['forward']
    assert forward > 0.520
    assert host.engine_forward_offcpu_share({'registry': registry}) \
        == pytest.approx(100 * (0.520 - forward) / 0.520) and \
        host.engine_forward_offcpu_share({'registry': registry}) < 0


@pytest.mark.parametrize('wall, cpu, named', [
    (0.500, 0.700, True),       # 70 ticks in 50 ticks of wall: sqrt(70) + 1
    (0.500, 0.560, False),      # 56 in 50: inside the counting noise
    (0.040, 0.130, True),       # a prefill's pack, 13 ticks in 4
    (0.016, 0.030, False),      # one tick too many in a phase of two
    (2.680, 2.760, False),      # 276 in 268
    (0.010, 0.007, False)])
def test_phases_with_more_cpu_than_wall_beyond_a_ticks_noise_are_named(
        wall, cpu, named):
    """More than the root of the count, in ticks of 10 ms, and one tick."""
    host = load('lib/host_threads.py')
    table = {'scheduler/emit': (wall, cpu), 'engine/forward': (0.520, 0.260)}
    assert host.overcounted(table) \
        == ([('scheduler/emit', wall, cpu)] if named else [])


@pytest.mark.parametrize('run', [
    {}, {'registry': {}}, {'registry': {}, 'trace': None},
    {'registry': {'decode_engine_phase_seconds':
                  {'type': 'histogram', 'samples': []}}},
    # the parent's registry: the wall histograms and no CPU counter
    {'registry': {k: v for k, v in _registry().items()
                  if 'cpu' not in k}}])
def test_readers_find_nothing_in_a_run_without_the_counters(run):
    for name in NEW:
        assert _reader(name).read(dict(run), Ctx()) is None, name


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


def test_idle_under_the_workers_python_with_the_bookkeeping_leaf():
    from paddle_tpu import observability as obs
    run = _traced_run(obs, [
        ('scheduler/cycle', 10.0, 11.0),          # not a leaf: owns nothing
        ('scheduler/admit', 10.0, 10.01),
        ('scheduler/book', 10.01, 10.02),
        ('engine/prefill', 10.02, 10.40),
        ('engine/prefill/pack', 10.02, 10.03),
        ('engine/prefill/forward', 10.03, 10.35),
        ('engine/prefill/logits_copy', 10.35, 10.40),
        ('scheduler/book', 10.40, 10.50),
        ('engine/step/forward', 10.50, 10.80),
        ('engine/step/sample', 10.80, 10.90),
        ('scheduler/emit', 10.90, 10.95),
        ('http/generate', 9.0, 12.0),             # another thread's: no leaf
    ], gaps=[(10.005, 10.025),    # 0.02 under book (midpoint 10.015)
             (10.05, 10.25),      # 0.20 under prefill/forward
             (10.36, 10.38),      # 0.02 under prefill/logits_copy
             (10.42, 10.48),      # 0.06 under book
             (10.82, 10.86),      # 0.04 under step/sample
             (10.91, 10.93),      # 0.02 under scheduler/emit
             (10.96, 11.00)])     # 0.04 under the cycle alone: no leaf
    try:
        share = _reader('serve_idle_in_worker_python_share').read(run, Ctx())
    finally:
        obs.reset()
    assert share == pytest.approx(100 * (0.02 + 0.06 + 0.04 + 0.02) / 0.40)
    idle = run['idle_by_host_leaf']
    assert idle['scheduler/book'] == pytest.approx(0.08)
    assert idle['no span'] == pytest.approx(0.04)
    assert idle['engine/prefill/forward'] == pytest.approx(0.20)


def test_idle_reader_finds_nothing_where_the_program_left_no_book_span():
    """The parent's traced run: engine and scheduler leaves that do not tile
    the worker, and no scheduler/book."""
    from paddle_tpu import observability as obs
    run = _traced_run(obs, [('scheduler/admit', 10.0, 10.01),
                            ('engine/step/forward', 10.1, 10.4),
                            ('scheduler/emit', 10.4, 10.5)],
                      gaps=[(10.1, 10.2)])
    try:
        assert _reader('serve_idle_in_worker_python_share').read(
            run, Ctx()) is None
    finally:
        obs.reset()
    assert 'idle_by_host_leaf' not in run


def test_idle_reader_reads_nothing_from_a_span_buffer_that_dropped_events():
    """Spans that cover part of the window are no reading of it: the reader
    says so and leaves the metric out, and the run is refused for it."""
    from paddle_tpu import observability as obs
    run = _traced_run(obs, [('scheduler/book', 10.0, 10.1),
                            ('engine/step/forward', 10.1, 10.4)],
                      gaps=[(10.1, 10.2)])
    bound, obs.tracer.max_events = obs.tracer.max_events, 2
    try:
        obs.tracer.complete('scheduler/emit', 10.4, 10.5)
        ctx = Ctx()
        assert _reader('serve_idle_in_worker_python_share').read(
            run, ctx) is None
        assert ctx.lines == ['span buffer full: 1 events dropped of a bound '
                             'of 2; the spans cover part of the window, no '
                             'idle share is read from them']
    finally:
        obs.tracer.max_events = bound
        obs.reset()
    assert 'idle_by_host_leaf' not in run


@pytest.mark.parametrize('name', sorted(NEW))
def test_the_entry_lists_the_four_serve_cells_and_its_reader_agrees(name):
    entries = [m for m in table()['per_layer'] if m['name'] == name]
    assert len(entries) == 1
    entry = entries[0]
    layer, source, unit = NEW[name]
    assert entry == {'name': name, 'unit': unit, 'better': 'lower',
                     'source': source, 'layer': layer,
                     'moves': 'serve_tokens_per_s',
                     'workloads': SERVE_CELLS}
    reader = _reader(name)
    assert (reader.NAME, reader.LAYER, reader.UNIT, reader.MOVES,
            reader.RUNNERS) == (name, layer, unit, 'serve_tokens_per_s',
                                ('serve_decode',))
    # every cell it lists reports the end-to-end metric it moves
    moved = next(m for m in table()['end_to_end']
                 if m['name'] == 'serve_tokens_per_s')
    assert set(SERVE_CELLS) <= set(moved['workloads'])


def test_the_leaf_list_is_the_old_one_with_the_bookkeeping_leaf():
    host, old = load('lib/host_threads.py'), load('lib/decode_phases.py')
    assert sorted(host.LEAVES) == sorted(old.LEAVES + ['scheduler/book'])
    assert set(host.PYTHON) < set(host.LEAVES)
    assert not [n for n in host.PYTHON
                if n.endswith(('forward', 'device_wait', 'logits_copy'))]


@pytest.mark.parametrize('cell', ['tiny_serve_threads',
                                  'tiny_serve_threads_diffusion'])
@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_reads_the_host_threads_traced_or_not(capsys, cell, trace):
    """An untraced run's last_run.json holds the three new counters in its
    registry, and the four registry reductions read the same from it as from
    a traced one; the traced line carries the readers (null off a TPU); the
    idle reader finds no device plane
    on the CPU and is left out."""
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', cell, '--seed', '7', '--seconds', '1',
                       '--trace', str(trace)], rehearsal=True, table=TABLE)
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and last['correct'] is True and last['failed'] == 0, \
        out[-3000:]
    if trace:
        assert set(FROM_REGISTRY) <= set(last['metrics'])
        assert 'serve_idle_in_worker_python_share' not in last['metrics']
        assert all(last['metrics'][n]['value'] is None for n in FROM_REGISTRY)
        assert 'worker phases, wall s / CPU s: ' in out
    else:
        assert not set(NEW) & set(last['metrics'])
    with open(os.path.join(BENCH, 'out', cell, 'last_run.json')) as f:
        run = json.load(f)['run']
    assert set(COUNTERS) <= set(run['registry'])
    values = {n: _reader(n).read(run, Ctx()) for n in FROM_REGISTRY}
    assert all(v is not None and math.isfinite(v)
               for v in values.values()), values
    assert 0 <= values['worker_on_cpu_share'] <= 105.0
    assert 0 <= values['http_handler_cpu_share']
    # signed sums: a hair under 0 where a phase's CPU was booked late
    assert -5.0 <= values['worker_lock_wait_share'] <= 100.0
    assert -5.0 <= values['engine_forward_offcpu_share'] <= 100.0
    # per phase, CPU within the wall (the kernel may book a thread's CPU
    # tens of microseconds late: a short phase's sum wanders by that)
    phases = load('lib/host_threads.py').phases(run)
    assert {'scheduler/book', 'scheduler/cycle', 'engine/forward',
            'engine/pack'} <= set(phases)
    for name, (wall, cpu) in phases.items():
        assert 0 <= cpu <= 1.05 * wall + 5e-3, (name, wall, cpu)
    # the handlers answered every request once: one increment a request
    handler = run['registry']['http_handler_cpu_seconds']['samples']
    assert len(handler) == 1 and handler[0]['value'] > 0
    # and the leaves tile the cycles in the sums too
    leaves = sum(wall for name, (wall, _) in phases.items()
                 if name.startswith('engine/')) \
        + sum(phases[f'scheduler/{p}'][0]
              for p in ('admit', 'emit', 'book', 'wait'))
    cycles = phases['scheduler/cycle'][0]
    n = next(s['count'] for s in
             run['registry']['decode_scheduler_phase_seconds']['samples']
             if s['labels'] == {'phase': 'cycle'})
    # (the window opens inside a cycle: what that one booked before the
    # registry was emptied is missing from its leaves)
    assert leaves == pytest.approx(cycles, rel=0.05, abs=20e-6 * n)
