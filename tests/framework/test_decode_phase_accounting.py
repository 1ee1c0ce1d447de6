"""The decode replica accounted for from inside (PERF.md, PR 24): the
engine's phase histogram and the scheduler's cycle and queue-wait
histograms fill with telemetry off and no traced request; with telemetry on
the same stamps become per-call spans; and a traced request costs the worker
thread one append per step, its spans made in one batch per iteration."""
import functools
import json
import threading
import time

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
from paddle_tpu.observability import distributed as dobs
from paddle_tpu.observability.trace_context import TraceContext
from paddle_tpu.serving import DecodeEngine, DecodeScheduler

ENGINE_HIST = 'decode_engine_phase_seconds'
SCHED_HIST = 'decode_scheduler_phase_seconds'
PHASES = ('pack', 'forward', 'device_wait', 'logits_copy', 'sample')
# what an engine call may spend outside its stamped phases: making its clock
# and, after the last stamp, its bookkeeping (0.10-0.16 ms on a quiet CPU),
# in which a worker beside five other test workers can lose the CPU for a
# scheduler tick
BOOKKEEPING_S = 2e-3


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
    engine.warmup()             # compiles are not a phase worth measuring
    obs.reset()
    return engine


def _samples(name):
    metric = obs.registry.to_dict().get(name)
    return metric['samples'] if metric else []


def _hist(name, **labels):
    """(sum, count) of the histogram children carrying `labels`."""
    picked = [s for s in _samples(name)
              if all(s['labels'].get(k) == v for k, v in labels.items())]
    return sum(s['sum'] for s in picked), sum(s['count'] for s in picked)


def _counter(name):
    return sum(s['value'] for s in _samples(name))


def _spans(prefix):
    return [e for e in obs.tracer.snapshot()['traceEvents']
            if e.get('ph') == 'X' and e['name'].startswith(prefix)]


def _inside(inner, outer):
    return (inner['tid'] == outer['tid']
            and outer['ts'] - 1e-3 <= inner['ts']
            and inner['ts'] + inner['dur'] <= outer['ts'] + outer['dur'] + 1e-3)


def _generate(engine, n, max_new=6, traced=False, **kw):
    """n concurrent greedy generations; returns the finished streams."""
    with DecodeScheduler(engine, **kw) as sched:
        streams = [sched.submit(
            [3 + i, 5, 7 + i], max_new_tokens=max_new,
            trace=TraceContext.root() if traced else None)
            for i in range(n)]
        for s in streams:
            assert len(s.result(120)) == max_new
    return streams


# -- always on: telemetry off, nothing traced --------------------------------

def test_histograms_fill_with_telemetry_off_and_the_tracer_stays_empty(lm):
    assert not obs.enabled()
    engine = make_engine(lm)
    _generate(engine, 3)
    steps = _counter('decode_steps')
    assert steps >= 5
    for phase in PHASES:
        assert _hist(ENGINE_HIST, call='step', phase=phase)[1] == steps
        assert _hist(ENGINE_HIST, call='prefill', phase=phase)[1] == 3
    sched = {s['labels']['phase']: s for s in _samples(SCHED_HIST)}
    assert {'cycle', 'admit', 'engine', 'emit'} <= set(sched)
    assert sched['emit']['count'] == steps + 3      # and the 3 prefills
    assert sched['cycle']['count'] == sched['admit']['count'] >= steps
    # every request's wait for a slot, header or not
    assert _hist('decode_queue_wait_seconds')[1] == 3
    assert _counter('decode_logits_bytes_copied') > 0
    assert len(obs.tracer) == 0
    assert _counter('trace_spans_recorded') == 0


def test_the_idle_worker_books_its_waits(lm):
    engine = make_engine(lm)
    with DecodeScheduler(engine):
        time.sleep(0.2)
    wait_s, waits = _hist(SCHED_HIST, phase='wait')
    cycle_s, cycles = _hist(SCHED_HIST, phase='cycle')
    assert waits >= 2 and cycles >= waits
    assert 0.9 * cycle_s <= wait_s <= cycle_s      # idle: all of it waiting
    assert _hist(SCHED_HIST, phase='engine')[1] == 0


@pytest.mark.parametrize('call', ['prefill', 'step', 'spec_step'])
def test_phases_tile_the_call_and_keep_the_old_histograms_meaning(lm, call):
    engine = make_engine(lm, spec_decode=(call == 'spec_step'), spec_k=3)
    walls, clocks = [], []
    for _ in range(5):
        table = engine.reserve_table(5, 8)
        t0 = time.perf_counter()
        if call == 'prefill':
            engine.prefill([3, 5, 7, 9, 11], table)
            walls.append(time.perf_counter() - t0)
        else:
            token = engine.prefill([3, 5, 7, 9, 11], table)
            tokens = [token] + [None] * 3
            t0 = time.perf_counter()
            if call == 'step':
                engine.decode_step(tokens, [table, None, None, None])
            else:
                engine.spec_step([[token, 4]] + [None] * 3,
                                 [table, None, None, None])
            walls.append(time.perf_counter() - t0)
        clocks.append(engine.last_call)
        engine.release_table(table)
    phase_s = {p: _hist(ENGINE_HIST, call=call, phase=p)[0] for p in PHASES}
    wall = sum(walls)
    # the phases tile each call from its first stamp to its last
    assert sum(phase_s.values()) == pytest.approx(
        sum(c.last - c.start for c in clocks), rel=1e-9)
    # and the wall holds them, less BOOKKEEPING_S a call
    assert 0.98 * wall - len(walls) * BOOKKEEPING_S \
        <= sum(phase_s.values()) <= wall
    inner = phase_s['forward'] + phase_s['device_wait'] \
        + phase_s['logits_copy']
    if call == 'prefill':
        old = _hist('decode_prefill_seconds')[0]
        assert phase_s['sample'] > 0        # outside decode_prefill_seconds
    else:
        if call == 'step':
            inner += phase_s['sample']      # the step's own argmax
        else:
            assert _hist(ENGINE_HIST, call=call, phase='sample')[1] == 0
        old = _hist('decode_step_seconds')[0]
        assert _hist(ENGINE_HIST, call=call, phase='forward')[1] \
            == _counter('decode_steps') == 5
    assert inner == pytest.approx(old, rel=1e-9)


def test_the_prefills_sampler_is_the_sample_phase(lm):
    engine = make_engine(lm)
    table = engine.reserve_table(3, 2)

    def slow(row):
        time.sleep(0.05)
        return int(row.argmax())

    engine.prefill([3, 5, 7], table, sampler=slow)
    engine.release_table(table)
    assert _hist(ENGINE_HIST, call='prefill', phase='sample')[0] >= 0.05
    assert _hist('decode_prefill_seconds')[0] \
        < _hist(ENGINE_HIST, call='prefill')[0] - 0.05


def test_scheduler_engine_phase_is_the_engines_time(lm):
    """cycle - wait - engine is the worker thread's self time: `engine` is
    booked with its cycle (no window edge between the two, PERF.md), and
    agrees with the engine's own histogram."""
    engine = make_engine(lm)
    _generate(engine, 4, max_new=8)
    own = _hist(ENGINE_HIST)[0]
    seen = _hist(SCHED_HIST, phase='engine')[0]
    cycle = _hist(SCHED_HIST, phase='cycle')[0]
    wait = _hist(SCHED_HIST, phase='wait')[0]
    # what the scheduler sees of a call beyond its phases is the engine's
    # bookkeeping after the last stamp, BOOKKEEPING_S a call at most
    calls = 4 + _counter('decode_steps')
    assert own <= seen <= 1.05 * own + calls * BOOKKEEPING_S
    assert seen <= cycle - wait


# -- telemetry on: per-call spans from the same stamps -----------------------

def test_one_engine_span_per_call_inside_its_cycle(lm):
    engine = make_engine(lm)
    with obs.telemetry_guard(True):
        streams = _generate(engine, 3, traced=True)
    steps = int(_counter('decode_steps'))
    step_spans = _spans('engine/step')
    assert len([e for e in step_spans if e['name'] == 'engine/step']) == steps
    for phase in PHASES:
        assert len([e for e in step_spans
                    if e['name'] == 'engine/step/' + phase]) == steps
    prefills = [e for e in _spans('engine/prefill')
                if e['name'] == 'engine/prefill']
    assert len(prefills) == 3
    assert all(e['args']['prompt_len'] == 3 and e['args']['bucket'] >= 3
               for e in prefills)
    cycles = [e for e in _spans('scheduler/') if e['name'] == 'scheduler/cycle']
    assert [e['args']['cycle'] for e in cycles] \
        == list(range(1, len(cycles) + 1))
    assert sum(e['args']['admitted'] for e in cycles) == 3
    # (a `scheduler/book` span that ends where a cycle begins is what lies
    # BETWEEN two cycles, a cycle's own observations: PR 34; under no cycle)
    starts = {round(c['ts'], 3) for c in cycles}
    for e in _spans('engine/') + [e for e in _spans('scheduler/')
                                  if e['name'] != 'scheduler/cycle']:
        if e['name'] == 'scheduler/book' \
                and round(e['ts'] + e['dur'], 3) in starts:
            continue
        assert any(_inside(e, c) for c in cycles), e
    # admit, emit (one per call that returned tokens) and the engine's
    # phases are the leaves, and tile the busy part of a cycle
    leaves = [e for e in _spans('engine/') + _spans('scheduler/')
              if e['name'].count('/') == 2
              or e['name'] in ('scheduler/admit', 'scheduler/emit')]
    assert len([e for e in leaves if e['name'] == 'scheduler/emit']) \
        == steps + 3
    # (what no leaf covers is the worker's own bookkeeping, the span batch
    # above all: ~0.15 ms per cycle, which a tiny model's sub-millisecond
    # program no longer hides, so it is held per cycle and in us, the
    # unit of 'dur')
    uncovered = sum(e['dur'] for e in cycles) - sum(e['dur'] for e in leaves)
    assert uncovered <= max(0.1 * sum(e['dur'] for e in cycles),
                            1e3 * len(cycles))
    # a call's children tile it
    for parent in prefills:
        kids = [e for e in _spans('engine/prefill/') if _inside(e, parent)]
        assert sorted(e['name'].rsplit('/', 1)[1] for e in kids) \
            == sorted(PHASES)
        assert sum(e['dur'] for e in kids) == pytest.approx(parent['dur'],
                                                            abs=1.0)
    # the per-request trace is whole: every token of every request, under
    # its request's trace id, however many requests shared the step
    tokens = _spans('replica/token')
    for s in streams:
        mine = [e for e in tokens if e['args']['trace_id'] == s.trace_id]
        # the first token comes out of the prefill, the rest out of steps
        assert sorted(e['args']['index'] for e in mine) == list(range(1, 6))
        assert all(e['args']['request_id'] == s.request_id for e in mine)
    assert obs.tracer.snapshot()['otherData']['dropped_events'] == 0


def _step_reads(engine, tables, contexts):
    """One lockstep step with slot i at ``contexts[i]`` cached tokens (None:
    idle); (blocks, positions) the counters gained."""
    for t, c in zip(tables, contexts):
        t.context_len = c or 0
    before = (_counter('decode_kv_blocks_read'),
              _counter('decode_context_positions_read'))
    engine.decode_step([1 if c else None for c in contexts],
                       [t if c else None for t, c in zip(tables, contexts)])
    return (_counter('decode_kv_blocks_read') - before[0],
            _counter('decode_context_positions_read') - before[1])


def test_blocks_read_counts_the_live_blocks_walked_in_whole_chunks(lm):
    """`decode_kv_blocks_read` against a hand count, beside
    `decode_context_positions_read`, and both in the `engine/step` span's
    args. 10 slots of 32 blocks: 320 table entries, so a chunk of the
    lockstep read is `LIVE_BLOCK_CHUNK` = 256 blocks."""
    from paddle_tpu.ops.nn_ops import LIVE_BLOCK_CHUNK
    assert LIVE_BLOCK_CHUNK == 256
    engine = make_engine(lm, slots=10, max_blocks=400, max_prompt_len=16,
                         max_new_tokens_cap=112, spec_decode=True, spec_k=2)
    layers = lm.num_cache_layers
    assert engine.slots * engine.pool.max_blocks_per_seq == 320
    tables = [engine.reserve_table(16, 112) for _ in range(10)]
    step = functools.partial(_step_reads, engine, tables)

    with obs.telemetry_guard(True):
        # the step feeds one token: contexts 4, 5 and 12 attend 5, 6 and 13
        # positions in 2, 2 and 4 blocks; seven idle slots read the scratch
        # block: 15 live blocks, one chunk of 256 walked
        assert step([4, 5, 12] + [None] * 7) == (layers * 256,
                                                 layers * (5 + 6 + 13))
        # 7 slots of 32 blocks, one of 30, two idle: 256 live, one chunk
        assert step([124] * 7 + [119] + [None] * 2)[0] == layers * 256
        # the eighth at 31 blocks: 257 live, a block into the second chunk
        assert step([124] * 7 + [123] + [None] * 2)[0] == layers * 512
    spans = [e for e in _spans('engine/step') if e['name'] == 'engine/step']
    assert [e['args']['kv_blocks'] for e in spans] == [
        layers * 256, layers * 256, layers * 512]
    assert spans[0]['args']['context_positions'] == layers * 24
    # the (S, K) step's read gathers every table whole
    for t in tables:
        t.context_len = 4
    before = _counter('decode_kv_blocks_read')
    engine.spec_step([[1, 2]] * 10, tables)
    assert _counter('decode_kv_blocks_read') - before == layers * 320


def test_blocks_read_of_a_latent_pool_counts_the_live_groups_in_whole_chunks(
        monkeypatch):
    """`decode_kv_blocks_read` of a latent engine against a hand count: its
    lockstep read walks the live GROUPS of blocks (32 blocks of 4 tokens a
    group: 128 keys) in whole chunks of groups, and the quotient over
    steps x layers x slots x `max_blocks_per_seq` is how far the walk
    engages (1.0 when the read gathered every table). 6 slots of 96 blocks:
    3 groups a slot, 18 in all, a chunk cut to 4 groups so that a step
    walks several."""
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.latent_moe_lm import LatentMoEConfig, LatentMoELM
    from paddle_tpu.ops import nn_ops
    monkeypatch.setattr(nn_ops, 'LIVE_GROUP_CHUNK', 4)
    with guard():
        default_generator.seed(5)
        model = LatentMoELM(LatentMoEConfig.tiny(
            max_position_embeddings=512))
        model.eval()
        engine = make_engine(model, slots=6, max_blocks=640,
                             max_prompt_len=16, max_new_tokens_cap=368,
                             prompt_buckets=[16], prefix_cache=False)
        layers = model.cfg.num_hidden_layers
        assert engine.layout.kind == 'latent'
        assert engine.pool.max_blocks_per_seq == 96
        assert nn_ops.live_group_chunk(6, 4, 96) == (3, 4)
        tables = [engine.reserve_table(16, 368) for _ in range(6)]
        step = functools.partial(_step_reads, engine, tables)

        with obs.telemetry_guard(True):
            # contexts 4 and 127 attend 5 and 128 positions in one group
            # each, 128 attends 129 in two; three idle slots read the
            # scratch block, a group each: 7 live groups, two chunks of 4,
            # 8 groups of 32 blocks walked
            assert step([4, 127, 128] + [None] * 3) == (
                layers * 8 * 32, layers * (5 + 128 + 129))
            # every slot at 383 of its 384 positions: 18 groups, 5 chunks
            assert step([383] * 6)[0] == layers * 20 * 32
            # one slot alone: its group and five idle ones, 6 groups
            assert step([9] + [None] * 5)[0] == layers * 8 * 32
        spans = [e for e in _spans('engine/step')
                 if e['name'] == 'engine/step']
        assert [e['args']['kv_blocks'] for e in spans] == [
            layers * 256, layers * 640, layers * 256]
        # the quotient: of the first step's padded read (6 x 96 blocks a
        # layer) 256 / 576 is walked
        assert spans[0]['args']['kv_blocks'] / (layers * 6 * 96) \
            == pytest.approx(0.444, abs=1e-3)


def test_traced_requests_cost_the_per_slot_loop_no_child_contexts(
        lm, monkeypatch, tmp_path):
    """Per traced request per step: one append. The spans, with child
    contexts, parents and JSONL lines, are made in one batch per engine
    call, outside the per-slot loop."""
    monkeypatch.setenv('PADDLE_TPU_TRACE_DIR', str(tmp_path))
    dobs.reset_distributed()
    in_batch = threading.local()
    real_flush = DecodeScheduler._record_spans

    def flush(self):
        in_batch.on = True
        try:
            real_flush(self)
        finally:
            in_batch.on = False

    monkeypatch.setattr(DecodeScheduler, '_record_spans', flush)
    calls = []
    real_child = TraceContext.child
    monkeypatch.setattr(
        TraceContext, 'child',
        lambda self: calls.append((threading.current_thread().name,
                                   getattr(in_batch, 'on', False)))
        or real_child(self))
    batches = []
    real_batch = dobs.record_spans
    monkeypatch.setattr(dobs, 'record_spans',
                        lambda batch: batches.append(len(batch))
                        or real_batch(batch))
    engine = make_engine(lm)
    roots = [TraceContext.root() for _ in range(4)]
    try:
        with DecodeScheduler(engine) as sched:
            streams = [sched.submit([3, 5 + i], max_new_tokens=5, trace=r)
                       for i, r in enumerate(roots)]
            for s in streams:
                s.result(120)
        steps = int(_counter('decode_steps'))
        # every child context is the worker's, made inside a batch
        assert calls == [('paddle-tpu-decode-scheduler', True)] * 24
        # queue_wait + prefill + 4 tokens per request, in at most one batch
        # per engine call (4 prefills and the steps) and one at the close
        assert sum(batches) == 4 * 6 == _counter('trace_spans_recorded')
        assert len(batches) <= 4 + steps + 1
        from tools.trace_merge import load_span_file, merge_span_files
        path = dobs.span_recorder().path
        spans = load_span_file(path)['spans']
        # every request's wait, traced or not, from the span's two stamps
        waits = sorted(s['dur_s'] for s in spans
                       if s['name'] == 'replica/queue_wait')
        assert sorted(_samples('decode_queue_wait_seconds')[0]['recent']) \
            == pytest.approx(waits, abs=1e-9)
        # a kill -9 loses at most the last engine call's batch, the last
        # tokens among it: the file cut there still merges, parents whole
        with open(path) as f:
            lines = f.readlines()
        last = batches[-1]
        killed = tmp_path / 'killed'
        killed.mkdir()
        (killed / 'spans-2.jsonl').write_text(
            ''.join(lines[:-last]) + lines[-last][:20])
        (killed / 'spans-1.jsonl').write_text(''.join(
            json.dumps(r) + '\n' for r in [{'clock': {
                'pid': 1, 'process': 'router', 'unix_time': time.time(),
                'perf_counter': time.perf_counter()}}] + [{'span': {
                    'name': 'router/request', 'trace_id': r.trace_id,
                    'span_id': r.span_id, 'parent_span_id': None,
                    'start_unix': time.time(), 'dur_s': 1.0,
                    'process': 'router'}} for r in roots]))
        chrome, summary = merge_span_files(
            sorted(str(p) for p in killed.iterdir()))
        assert summary['spans'] == 4 + 24 - last
        assert summary['unresolved_parents'] == []
    finally:
        dobs.reset_distributed()
    assert len(spans) == 24 and len({s['span_id'] for s in spans}) == 24
    for root in roots:
        mine = [s for s in spans if s['trace_id'] == root.trace_id]
        assert sorted(s['name'] for s in mine) == sorted(
            ['replica/queue_wait', 'replica/prefill']
            + ['replica/token'] * 4)
        assert all(s['parent_span_id'] == root.span_id for s in mine)
        assert all(len(s['span_id']) == 16 for s in mine)
