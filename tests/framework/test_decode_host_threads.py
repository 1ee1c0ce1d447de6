"""The serve host's threads seen from inside (PR 34): a thread-CPU clock
beside the wall clock at every phase boundary of the decode worker, so that
wall - CPU of a phase is the time the thread was off the CPU in it; the
worker's bookkeeping under a leaf of its own, with which the leaves tile a
cycle; the HTTP handler threads' CPU seconds and span; and a span buffer
that holds a traced window. Bounds are relative to the measured wall: the
file holds beside five other xdist workers."""
import http.client
import importlib.util
import json
import os
import sys
import threading
import time

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
from paddle_tpu.observability.trace_context import TraceContext
from paddle_tpu.observability.tracer import StepTracer
from paddle_tpu.serving import DecodeEngine, DecodeScheduler
from paddle_tpu.serving import metrics as _m
from paddle_tpu.serving.decode import engine as engine_module
from paddle_tpu.serving.server import ServingServer

ENGINE = 'decode_engine_phase_seconds'
ENGINE_CPU = 'decode_engine_phase_cpu_seconds'
SCHED = 'decode_scheduler_phase_seconds'
SCHED_CPU = 'decode_scheduler_phase_cpu_seconds'
PHASES = ('pack', 'forward', 'device_wait', 'logits_copy', 'sample')
REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))


def _host_threads():
    """benchmark/lib/host_threads.py: the reductions the six per-layer
    metrics share (benchmark/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        'bench_lib_host_threads',
        os.path.join(REPO, 'benchmark', 'lib', 'host_threads.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope='module')
def lm():
    with guard():
        model = TransformerLM(CausalLMConfig.tiny())
        model.eval()
        yield model


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    obs.reset()


def make_engine(model, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 16)
    kw.setdefault('max_new_tokens_cap', 16)
    engine = DecodeEngine(model, **kw)
    engine.warmup()
    obs.reset()
    return engine


def _sums(name, label='phase', **labels):
    """{value of `label`: sum} of a histogram or counter, over the children
    carrying `labels`."""
    metric = obs.registry.to_dict().get(name)
    out = {}
    for s in metric['samples'] if metric else []:
        if all(s['labels'].get(k) == v for k, v in labels.items()):
            key = s['labels'].get(label)
            out[key] = out.get(key, 0.0) + s.get('sum', s.get('value'))
    return out


def _counts(name, label='phase'):
    metric = obs.registry.to_dict().get(name)
    return {s['labels'].get(label): s['count'] for s in metric['samples']}


def _spans(prefix=''):
    return [e for e in obs.tracer.snapshot()['traceEvents']
            if e.get('ph') == 'X' and e['name'].startswith(prefix)]


def _generate(engine, n, max_new=6, traced=False):
    with DecodeScheduler(engine) as sched:
        streams = [sched.submit(
            [3 + i, 5, 7 + i], max_new_tokens=max_new,
            trace=TraceContext.root() if traced else None)
            for i in range(n)]
        for s in streams:
            assert len(s.result(120)) == max_new
    return streams


# The kernel may book a thread's CPU time late and pay it out at its next
# tick: on this image thread_time() runs up to ~65 us ahead of perf_counter
# over a stretch of microseconds (and as far behind before it). A single
# stretch's CPU is good to this; sums are unbiased.
CPU_CLOCK_SKEW = 150e-6


def _spin(seconds):
    """Pure Python on the CPU for `seconds` of the calling thread's wall:
    holds the interpreter but for the switches it is asked for."""
    until = time.perf_counter() + seconds
    while time.perf_counter() < until:
        pass


# -- always on: the CPU counters beside the wall histograms ------------------

def test_every_phase_has_cpu_seconds_and_never_more_than_its_wall(lm):
    assert not obs.enabled()
    engine = make_engine(lm)
    began = time.perf_counter()
    _generate(engine, 4, max_new=8)
    life = time.perf_counter() - began
    for call in ('prefill', 'step'):
        wall = _sums(ENGINE, call=call)
        cpu = _sums(ENGINE_CPU, call=call)
        assert set(cpu) == set(wall) == set(PHASES)
        for phase in PHASES:
            calls = 4 if call == 'prefill' else _sums('decode_steps',
                                                      None)[None]
            assert 0 <= cpu[phase] \
                <= 1.01 * wall[phase] + CPU_CLOCK_SKEW * calls, \
                (call, phase, cpu[phase], wall[phase])
    wall, cpu, n = _sums(SCHED), _sums(SCHED_CPU), _counts(SCHED)
    assert set(cpu) == set(wall) >= {'cycle', 'admit', 'engine', 'emit',
                                     'book', 'wait'}
    for phase in wall:
        assert 0 <= cpu[phase] \
            <= 1.01 * wall[phase] + CPU_CLOCK_SKEW * n[phase], phase
    # book is observed once an iteration, as admit and cycle are
    assert n['book'] == n['cycle'] == n['admit']
    # the thread waits off the CPU
    assert cpu['wait'] <= 0.5 * wall['wait']
    # the leaves tile the iterations: what the engine's own phases, admit,
    # emit, book and the waits leave of the cycles is clock reads
    leaves = sum(_sums(ENGINE).values()) + wall['admit'] + wall['emit'] \
        + wall['book'] + wall['wait']
    assert leaves == pytest.approx(wall['cycle'],
                                   rel=0.01, abs=20e-6 * n['cycle'])
    # and the iterations fill the thread's life but for what lies between
    # two of them, an iteration's own observations (the one that finds the
    # scheduler closed is not booked)
    assert life - 0.1 <= wall['cycle'] <= life
    assert len(obs.tracer) == 0


def test_a_sleeping_forward_reads_off_the_cpu_and_a_spinning_pack_on_it(
        lm, monkeypatch):
    """The engine with its program made to sleep (a dispatch that blocks)
    and its step's coordinates made to spin (interpreter work)."""
    engine = make_engine(lm)
    program, coords = engine._program, engine_module.decode_coords

    def sleepy(*args, **kw):
        time.sleep(0.03)
        return program(*args, **kw)

    def spinning(*args, **kw):
        _spin(0.03)
        return coords(*args, **kw)

    monkeypatch.setattr(engine, '_program', sleepy)
    monkeypatch.setattr(engine_module, 'decode_coords', spinning)
    table = engine.reserve_table(5, 8)
    token = engine.prefill([3, 5, 7, 9, 11], table)
    for _ in range(3):
        token = engine.decode_step([token, None, None, None],
                                   [table, None, None, None])[0]
    engine.release_table(table)
    wall, cpu = _sums(ENGINE, call='step'), _sums(ENGINE_CPU, call='step')
    assert wall['forward'] >= 0.09 and wall['pack'] >= 0.09
    # asleep: off the CPU for (nearly) all of the phase's wall
    assert wall['forward'] - cpu['forward'] >= 0.85 * wall['forward']
    # spinning: on it, but for what the machine's other processes take
    assert cpu['pack'] >= 0.5 * wall['pack']
    off = _host_threads().engine_forward_offcpu_share(
        {'registry': obs.registry.to_dict()})
    assert 85.0 <= off <= 100.0
    # the call was made on this thread, and this thread's clock read it
    assert engine.last_call.call == 'step'
    assert 0 < engine.last_call.last_cpu - engine.last_call.start_cpu \
        <= engine.last_call.last - engine.last_call.start + CPU_CLOCK_SKEW


def test_lock_wait_share_rises_beside_a_spinning_python_thread(
        lm, monkeypatch):
    """The control that the metric sees the interpreter lock at all: the
    worker's `pack` made 30 ms of pure Python a step (most of its busy
    time), alone and then beside a thread that never lets go of the
    interpreter unasked. Beside other processes that take the machine's
    cores the reading alone is above 0 too (a thread that waits for a core
    is off the CPU as well), so the rise is held against the room left."""
    engine = make_engine(lm)
    coords = engine_module.decode_coords

    def spinning(*args, **kw):
        _spin(0.03)
        return coords(*args, **kw)

    monkeypatch.setattr(engine_module, 'decode_coords', spinning)
    host = _host_threads()

    def reading():
        obs.reset()
        _generate(engine, 2, max_new=12)
        run = {'registry': obs.registry.to_dict()}
        return host.worker_lock_wait_share(run), \
            host.worker_on_cpu_share(run)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(0.001)     # many switches in a 30 ms pack
    stop = threading.Event()

    def hog():
        while not stop.is_set():
            pass

    try:
        alone, on_cpu_alone = reading()
        hogs = [threading.Thread(target=hog, daemon=True) for _ in range(1)]
        for t in hogs:
            t.start()
        try:
            beside, on_cpu_beside = reading()
        finally:
            stop.set()
            for t in hogs:
                t.join()
    finally:
        sys.setswitchinterval(interval)
    print("LOCKWAIT", alone, beside, on_cpu_alone, on_cpu_beside)
    # (a signed sum: a hair under 0 where a phase's CPU was booked late)
    assert -2.0 <= alone < beside <= 100.0, (alone, beside)
    # the hog takes every other millisecond of the interpreter
    assert beside - alone >= 0.08 * (100.0 - alone), (alone, beside)
    assert on_cpu_beside < on_cpu_alone <= 105.0


# -- telemetry on: the bookkeeping leaf ---------------------------------------

def test_the_leaves_with_book_tile_every_cycle(lm):
    engine = make_engine(lm)
    with obs.telemetry_guard(True):
        _generate(engine, 3, traced=True)
    steps = int(_sums('decode_steps', None)[None])
    cycles = [e for e in _spans('scheduler/cycle')]
    assert cycles and sum(e['args']['admitted'] for e in cycles) == 3
    worker = {e['tid'] for e in cycles}
    assert len(worker) == 1
    leaves = [e for e in _spans('engine/') if e['name'].count('/') == 2] \
        + [e for e in _spans('scheduler/')
           if e['name'] in ('scheduler/admit', 'scheduler/emit',
                            'scheduler/book')]
    assert {e['tid'] for e in leaves} == worker
    books = [e for e in leaves if e['name'] == 'scheduler/book']
    # around every engine call, and an iteration's tail
    assert len(books) >= 2 * (steps + 3)
    # the CPU seconds are read as sums over a window (the counters), never
    # span by span: a leaf carries no arg for them
    assert not any('cpu_us' in e.get('args', {}) for e in leaves + cycles)
    outside = 0.0
    for cycle in cycles:
        lo, hi = cycle['ts'], cycle['ts'] + cycle['dur']
        inside = [e for e in leaves
                  if lo - 1e-3 <= e['ts'] and e['ts'] + e['dur'] <= hi + 1e-3]
        covered = sum(e['dur'] for e in inside)          # us
        assert abs(cycle['dur'] - covered) <= max(0.01 * cycle['dur'], 20.0), \
            (cycle, sorted((e['ts'], e['name'], e['dur']) for e in inside))
        # and no two leaves overlap: they tile, they do not stack
        inside.sort(key=lambda e: e['ts'])
        for a, b in zip(inside, inside[1:]):
            assert a['ts'] + a['dur'] <= b['ts'] + 1e-3, (a, b)
    # a cycle runs from the loop's top to the stamp before its own `book`
    # and `cycle` observations, as before there was a `book`: what lies
    # between two cycles is a `scheduler/book` span under no cycle, which
    # ends where the next cycle (and its admit) begins
    cycles.sort(key=lambda e: e['ts'])
    starts = {round(e['ts'], 3) for e in cycles}
    assert starts == {round(e['ts'], 3) for e in leaves
                      if e['name'] == 'scheduler/admit'}
    between = [e for e in books
               if round(e['ts'] + e['dur'], 3) in starts]
    assert len(between) == len(cycles)
    for a, b in zip(cycles, cycles[1:]):
        gap = b['ts'] - (a['ts'] + a['dur'])
        assert gap > 0, (a, b)           # the cycle's own observations
        # two cycles that follow each other (no idle iteration between,
        # which leaves no span): one book span fills the gap
        filler = [e for e in between
                  if abs(e['ts'] - (a['ts'] + a['dur'])) <= 1e-3
                  and abs(e['ts'] + e['dur'] - b['ts']) <= 1e-3]
        if filler:
            outside += filler[0]['dur']
    assert outside > 0
    # every other leaf lies in a cycle
    assert sum(e['dur'] for e in leaves) - sum(e['dur'] for e in between) \
        == pytest.approx(sum(e['dur'] for e in cycles), rel=0.01,
                         abs=20.0 * len(cycles))
    # the histogram's book is the in-cycle spans' book (one sum an
    # iteration; idle iterations, which leave no span, hold the rest):
    # the stretch between two cycles is in no phase's sum
    assert (sum(e['dur'] for e in books) - sum(e['dur'] for e in between)) \
        * 1e-6 <= _sums(SCHED)['book'] + 1e-9
    # existing phases kept their boundaries: emit begins where a call
    # returned, after the call's own record (a book stretch between)
    emits = [e for e in leaves if e['name'] == 'scheduler/emit']
    assert len(emits) == steps + 3


def test_a_window_models_prefill_has_its_bookkeeping_under_book():
    """A block-diffusion prefill returns no token and has no emit: what
    follows it lay under no leaf before."""
    from paddle_tpu.models.block_diffusion_lm import (
        BlockDiffusionMoEConfig, BlockDiffusionMoELM)
    with guard():
        model = BlockDiffusionMoELM(BlockDiffusionMoEConfig.tiny())
        model.eval()
        engine = DecodeEngine(model, slots=2, block_size=4, max_blocks=64,
                              max_prompt_len=16, max_new_tokens_cap=8,
                              prompt_buckets=[8, 16])
        engine.warmup()
        obs.reset()
        with obs.telemetry_guard(True):
            with DecodeScheduler(engine, denoising_steps=2) as sched:
                stream = sched.submit([3, 5, 7, 9, 11, 13], max_new_tokens=6)
                assert len(stream.result(120)) == 6
    cycles = _spans('scheduler/cycle')
    leaves = [e for e in _spans('engine/') if e['name'].count('/') == 2] \
        + [e for e in _spans('scheduler/')
           if e['name'] in ('scheduler/admit', 'scheduler/emit',
                            'scheduler/book')]
    assert [e for e in leaves if e['name'].startswith('engine/prefill/')]
    # but for the book span that ends where a cycle begins (what lies
    # between two cycles), every leaf lies in a cycle, and they fill it
    starts = {round(e['ts'], 3) for e in cycles}
    inside = [e for e in leaves if e['name'] != 'scheduler/book'
              or round(e['ts'] + e['dur'], 3) not in starts]
    assert len(leaves) - len(inside) == len(cycles)
    assert sum(e['dur'] for e in inside) == pytest.approx(
        sum(e['dur'] for e in cycles), rel=0.01, abs=20.0 * len(cycles))


# -- the handler threads -------------------------------------------------------

def _post(port, body):
    conn = http.client.HTTPConnection('127.0.0.1', port, timeout=60)
    conn.request('POST', '/generate', json.dumps(body),
                 {'Content-Type': 'application/json'})
    reply = conn.getresponse()
    data = reply.read().decode()
    conn.close()
    return reply.status, data


class _Recorder:
    def __init__(self):
        self.incs = []
        self.writer = None          # the stream writer thread's ident

    def inc(self, amount=1.0):
        self.incs.append((threading.get_ident(), amount))

    def handlers(self):
        return [(tid, cpu) for tid, cpu in self.incs if tid != self.writer]

    def settled(self, n):
        """The handler threads' increments, once they are `n` or a second
        has passed: a handler books its CPU after the client has read the
        reply's last byte."""
        until = time.perf_counter() + 1.0
        while len(self.handlers()) < n and time.perf_counter() < until:
            time.sleep(0.005)
        time.sleep(0.02)            # what a per-token increment would use
        return len(self.handlers())


def test_handler_cpu_moves_once_a_request_and_the_writers_once_a_turn(
        lm, monkeypatch):
    """A handler thread books its CPU once a request and sleeps through a
    streamed reply; the stream writer thread, which writes the reply, books
    its own into the same counter once a turn of its loop (a hand-off or a
    tick), on its own clock."""
    engine = make_engine(lm)
    seen = _Recorder()
    monkeypatch.setattr(_m, 'http_handler_cpu_seconds', seen)
    with DecodeScheduler(engine) as sched:
        server = ServingServer(None, host='127.0.0.1', port=0,
                               generator=sched)
        server.start()
        seen.writer = server.stream_writer._thread.ident
        try:
            status, data = _post(server.port, {'prompt': [3, 5, 7],
                                               'max_new_tokens': 2})
            assert status == 200 and data.count('"token"') == 2
            assert seen.settled(1) == 1
            status, data = _post(server.port, {'prompt': [3, 5, 7],
                                               'max_new_tokens': 12})
            assert status == 200 and data.count('"token"') == 12
            assert seen.settled(2) == 2         # 12 tokens, one increment
            status, _ = _post(server.port, {'prompt': [3, 5, 7],
                                            'max_new_tokens': 4,
                                            'stream': False})
            assert status == 200 and seen.settled(3) == 3
            status, _ = _post(server.port, {'prompt': 'no list'})
            assert status == 400 and seen.settled(4) == 4  # a request too
        finally:
            server.shutdown(drain=False)
    me = threading.get_ident()
    assert all(tid != me and 0 < cpu < 5.0 for tid, cpu in seen.handlers())
    writers = [cpu for tid, cpu in seen.incs if tid == seen.writer]
    # the two streamed replies crossed in at most a hand-off a token (one
    # slot was live) and two registrations; the rest are the loop's ticks
    assert 2 <= len(writers) and all(0 <= cpu < 5.0 for cpu in writers)
    assert sum(writers) > 0


def test_http_generate_span_on_the_handlers_own_thread(lm):
    engine = make_engine(lm)
    with obs.telemetry_guard(True):
        with DecodeScheduler(engine) as sched:
            server = ServingServer(None, host='127.0.0.1', port=0,
                                   generator=sched)
            server.start()
            try:
                # two connections at once: two handler threads alive
                # together (a thread's id is free again once it has ended)
                replies = []
                clients = [threading.Thread(
                    target=lambda i=i, n=n: replies.append(_post(
                        server.port, {
                            'prompt': [3, 5, 7 + i], 'max_new_tokens': n,
                            'request_id': f'req-{i}', 'stream': bool(i)})))
                    for i, n in enumerate((12, 12))]
                for c in clients:
                    c.start()
                for c in clients:
                    c.join()
                assert [status for status, _ in replies] == [200, 200]
                status, _ = _post(server.port, {'prompt': 'no list'})
                assert status == 400            # no generation: no span
                # a handler leaves its span after its reply's last byte
                until = time.perf_counter() + 2.0
                while len(_spans('http/generate')) < 2 \
                        and time.perf_counter() < until:
                    time.sleep(0.01)
            finally:
                server.shutdown(drain=False)
        counter = _sums('http_handler_cpu_seconds', None)[None]
    spans = sorted(_spans('http/generate'),
                   key=lambda e: e['args']['request_id'])
    assert [(e['args']['request_id'], e['args']['tokens']) for e in spans] \
        == [('req-0', 12), ('req-1', 12)]
    worker = {e['tid'] for e in _spans('scheduler/cycle')}
    tids = {e['tid'] for e in spans}
    # a connection is a thread: a track of its own beside the worker's
    assert len(tids) == 2 and not tids & worker
    assert threading.get_ident() not in tids
    for e in spans:
        assert 0 < e['args']['cpu_us'] \
            <= 1.01 * e['dur'] + CPU_CLOCK_SKEW * 1e6
    # the counter took the same seconds (and the refused request's)
    assert counter >= sum(e['args']['cpu_us'] for e in spans) * 1e-6 > 0


# -- the buffer ----------------------------------------------------------------

def test_the_default_bound_fills_to_the_last_event_and_then_counts(
        monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_TRACE_MAX_EVENTS', raising=False)
    tracer = StepTracer()
    bound = tracer.max_events
    assert bound == 100_000
    for i in range(bound):
        tracer.complete('engine/step/forward', 1.0 + i, 1.5 + i)
    assert len(tracer) == bound and tracer.dropped == 0
    doc = tracer.snapshot()
    assert len(doc['traceEvents']) == bound
    assert doc['otherData']['dropped_events'] == 0
    tracer.complete('engine/step/forward', 0.0, 1.0)
    tracer.instant('late')
    assert len(tracer) == bound and tracer.dropped == 2
    assert tracer.snapshot()['otherData']['dropped_events'] == 2
    # the env name keeps its meaning, a count of events
    monkeypatch.setenv('PADDLE_TPU_TRACE_MAX_EVENTS', '3')
    small = StepTracer()
    for i in range(5):
        small.complete('x', 0.0, 1.0)
    assert len(small) == 3 and small.dropped == 2
    assert small.snapshot()['otherData']['dropped_events'] == 2


def test_snapshot_gives_the_dict_an_event_gave_before():
    """The chrome-trace dict as `complete`, `span` and `instant` built it
    at recording time before PR 34, keys in the same order."""
    tracer = StepTracer(max_events=100)
    epoch, pid, tid = tracer._epoch, os.getpid(), threading.get_ident()

    class Odd:
        def __str__(self):
            return 'odd'

    tracer.complete('replica/token', epoch + 0.25, epoch + 0.75, index=3,
                    request_id='r', flag=True, none=None, odd=Odd())
    tracer.complete('backwards', epoch + 2.0, epoch + 1.0)
    with pytest.raises(KeyError):
        with tracer.span('outer', step=1) as outer:
            with tracer.span('inner'):
                pass
            raise KeyError('x')
    tracer.instant('nonfinite', op='matmul')
    tracer.instant('bare')
    events = tracer.snapshot()['traceEvents']
    assert events[0] == {
        'name': 'replica/token', 'ph': 'X', 'ts': 0.25 * 1e6,
        'dur': 0.5 * 1e6, 'pid': pid, 'tid': tid,
        'args': {'index': 3, 'request_id': 'r', 'flag': True, 'none': None,
                 'odd': 'odd'}}
    assert list(events[0]) == ['name', 'ph', 'ts', 'dur', 'pid', 'tid',
                               'args']
    assert events[1] == {'name': 'backwards', 'ph': 'X', 'ts': 2.0 * 1e6,
                         'dur': 0.0, 'pid': pid, 'tid': tid}
    inner, outer_ev = events[2], events[3]
    assert inner['name'] == 'inner' and 'args' not in inner
    assert outer_ev['args'] == {'step': 1, 'error': 'KeyError'}
    assert outer_ev['ts'] == (outer.start - epoch) * 1e6
    assert outer_ev['dur'] == pytest.approx(outer.duration * 1e6, abs=1e-6)
    assert outer_ev['ts'] <= inner['ts'] and inner['ts'] + inner['dur'] \
        <= outer_ev['ts'] + outer_ev['dur']
    assert list(events[4]) == ['name', 'ph', 's', 'ts', 'pid', 'tid', 'args']
    assert events[4]['ph'] == 'i' and events[4]['s'] == 't' \
        and events[4]['args'] == {'op': 'matmul'}
    assert list(events[5]) == ['name', 'ph', 's', 'ts', 'pid', 'tid']
    # a snapshot is a copy: the buffer is as it was, and dump() writes it
    assert len(tracer) == 6 and tracer.snapshot()['traceEvents'] == events
    json.dumps(events)
