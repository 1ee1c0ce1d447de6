"""Stateful decode engine (paddle_tpu/serving/decode/): bitwise parity vs
uncached whole-sequence decode, bounded compile counts, continuous-batching
slot admission, KV-block lifecycle, deadlines/backpressure/drain, streaming
HTTP /generate, and the always-on decode_* metrics."""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import (CausalLMConfig, TransformerLM,
                                         greedy_generate)
from paddle_tpu.serving import (DeadlineExceeded, DecodeEngine,
                                DecodeScheduler, EngineClosed,
                                InvalidRequest, Overloaded, OutOfBlocks,
                                ServingServer)
from paddle_tpu.serving.decode.kv_cache import BlockAllocator


@pytest.fixture(scope='module')
def lm():
    with guard():
        model = TransformerLM(CausalLMConfig.tiny())
        model.eval()
        yield model


def make_engine(model, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 16)
    kw.setdefault('max_new_tokens_cap', 16)
    return DecodeEngine(model, **kw)


def _counter(name):
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    if not d or not d['samples']:
        return 0.0
    return sum(s['value'] for s in d['samples'])


# -- parity ----------------------------------------------------------------

def test_streamed_generation_bitwise_equals_uncached(lm):
    """The acceptance bar: ragged concurrent generations through the
    continuous-batching scheduler produce EXACTLY the uncached
    whole-sequence greedy tokens, per request."""
    eng = make_engine(lm)
    rng = np.random.RandomState(0)
    prompts = [list(map(int, rng.randint(3, 100, n)))
               for n in (3, 7, 12, 5, 9, 1, 16)]
    budgets = [10, 4, 16, 7, 12, 16, 2]
    refs = [greedy_generate(lm, p, m, pad_len=eng.padded_context)
            for p, m in zip(prompts, budgets)]
    with DecodeScheduler(eng) as sched:
        streams = [sched.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, budgets)]
        outs = [s.result(120) for s in streams]
    assert outs == refs
    for s in streams:
        assert s.finish_reason == 'length'


def test_eos_stops_generation_early(lm):
    eng = make_engine(lm)
    prompt = [5, 9, 2, 44]
    ref = greedy_generate(lm, prompt, 8, pad_len=eng.padded_context)
    eos = ref[0]                       # greedy will emit it immediately
    with DecodeScheduler(eng) as sched:
        s = sched.submit(prompt, max_new_tokens=8, eos_id=eos)
        assert s.result(60) == [eos]
        assert s.finish_reason == 'stop'


def test_stream_iterates_tokens_incrementally(lm):
    eng = make_engine(lm)
    prompt = [7, 3, 11]
    ref = greedy_generate(lm, prompt, 6, pad_len=eng.padded_context)
    with DecodeScheduler(eng) as sched:
        s = sched.submit(prompt, max_new_tokens=6)
        got = [t for t in s.iter_tokens(timeout=60)]
    assert got == ref
    assert s.tokens == ref and s.done()


# -- compile-count bounds --------------------------------------------------

def test_decode_compile_count_independent_of_generated_length(lm):
    """One prefill program per bucket + one decode-step program: after
    warmup, generations of ANY length and prompt bucket build no further
    engine program (an engine call is one jitted program, so the eager
    kernel cache it used to be counted by sees nothing of it:
    tests/framework/test_decode_fused_programs.py)."""
    eng = make_engine(lm)
    eng.warmup()
    programs = eng.compiled_programs()
    rng = np.random.RandomState(1)
    with DecodeScheduler(eng) as sched:
        outs = [sched.submit(list(map(int, rng.randint(3, 100, n))),
                             max_new_tokens=m).result(120)
                for n, m in ((3, 4), (9, 14), (15, 16), (2, 2), (16, 9))]
    assert all(len(o) for o in outs)
    assert eng.compiled_programs() == programs


def test_prefill_compiles_bounded_by_bucket_ladder(lm):
    """A fresh engine compiles at most len(prompt_buckets) prefill shapes
    plus one decode-step shape — tracked by the decode_prefill_compiles
    counter regardless of how many requests run."""
    eng = make_engine(lm)
    before = _counter('decode_prefill_compiles')
    with DecodeScheduler(eng) as sched:
        for n in (1, 2, 3, 5, 9, 13, 2, 7, 16):
            sched.submit([1] * n, max_new_tokens=2).result(120)
    compiled = _counter('decode_prefill_compiles') - before
    assert 0 < compiled <= len(eng.prompt_buckets)


# -- continuous batching ---------------------------------------------------

def _heavy_tailed_work(requests=12, max_prompt=12, max_new_cap=32):
    """Seeded ragged prompts with heavy-tailed budgets (3 of 4 requests
    short, every 4th near the cap): what wave batching is worst at, one
    long request pinning a drained wave while the other slots idle."""
    rng = np.random.RandomState(0)
    work = []
    for i in range(requests):
        prompt = [int(t) for t in
                  rng.randint(3, 120, int(rng.randint(2, max_prompt + 1)))]
        if i % 4 == 3:
            max_new = int(rng.randint(2 * max_new_cap // 3, max_new_cap + 1))
        else:
            max_new = int(rng.randint(4, max_new_cap // 4))
        work.append((prompt, max_new))
    return work


def _hist_totals(name):
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    samples = d['samples'] if d else []
    return (sum(x['sum'] for x in samples),
            sum(x['count'] for x in samples))


@pytest.mark.parametrize('work, slots, margin', [
    ([([3, 5], 16), ([7, 2], 2), ([9, 9], 2), ([4, 1], 2),
      ([8, 8], 16), ([6, 2], 2), ([5, 5], 2), ([2, 9], 2)], 2, 1.0),
    (_heavy_tailed_work(), 4, 1.3)], ids=['two_long_six_short',
                                          'heavy_tailed'])
def test_continuous_admission_uses_fewer_steps_than_drain(lm, work, slots,
                                                          margin):
    """Admit-into-freed-slots against drain-then-refill on a mixed
    workload: under BOTH policies every stream is the uncached
    whole-sequence reference (policy changes speed, not math), and
    continuous admission takes structurally fewer lockstep steps (by 1.3x
    on the heavy-tailed mix) at a higher mean slot occupancy. Step counts
    are deterministic for a seeded workload."""
    def run(admission):
        eng = make_engine(lm, slots=slots, max_new_tokens_cap=32)
        steps0 = _counter('decode_steps')
        occ0, n0 = _hist_totals('decode_slot_occupancy')
        with DecodeScheduler(eng, queue_depth=len(work) + 1,
                             admission=admission) as sched:
            streams = [sched.submit(p, max_new_tokens=m) for p, m in work]
            outs = [s.result(240) for s in streams]
        occ1, n1 = _hist_totals('decode_slot_occupancy')
        refs = [greedy_generate(lm, p, m, pad_len=eng.padded_context)
                for p, m in work]
        assert outs == refs
        return _counter('decode_steps') - steps0, (occ1 - occ0) / (n1 - n0)

    steps_cont, occupancy_cont = run('continuous')
    steps_drain, occupancy_drain = run('drain')
    assert steps_cont < steps_drain
    assert steps_cont * margin <= steps_drain, (steps_cont, steps_drain)
    assert occupancy_cont > occupancy_drain


def test_short_request_admitted_into_freed_slot_finishes_first(lm):
    """With one slot-hogging long generation and S=2, later short requests
    flow through the second slot and complete while the long one is still
    decoding — the defining continuous-batching observable."""
    eng = make_engine(lm, slots=2)
    with DecodeScheduler(eng) as sched:
        long_s = sched.submit([3, 5, 7], max_new_tokens=16)
        shorts = [sched.submit([9, 2], max_new_tokens=2) for _ in range(3)]
        for s in shorts:
            s.result(120)
        assert not long_s.done(), \
            'short requests should finish while the long one decodes'
        long_s.result(120)


# -- KV-block lifecycle ----------------------------------------------------

def test_block_allocator_free_list_reuse_and_double_free():
    alloc = BlockAllocator(8)
    assert alloc.capacity == 7
    a = alloc.allocate(3)
    b = alloc.allocate(4)
    assert alloc.available == 0 and 0 not in a + b
    with pytest.raises(OutOfBlocks):
        alloc.allocate(1)
    alloc.free(a)
    c = alloc.allocate(3)
    assert sorted(c) == sorted(a)           # free list recycles
    with pytest.raises(ValueError):
        alloc.free(b + b[:1])               # double free detected
    with pytest.raises(ValueError):
        alloc.free([0])                     # scratch is untouchable


def test_blocks_released_at_completion_and_metrics(lm):
    from paddle_tpu.observability import registry
    eng = make_engine(lm)
    assert eng.pool.allocator.used == 0
    with DecodeScheduler(eng) as sched:
        sched.submit([1, 2, 3], max_new_tokens=4).result(120)
        sched.submit([1] * 10, max_new_tokens=8).result(120)
    assert eng.pool.allocator.used == 0, 'completed requests leak blocks'
    d = registry.to_dict()
    for name in ('decode_slots_total', 'decode_cache_blocks_total',
                 'decode_cache_blocks_used', 'decode_tokens_generated',
                 'decode_prefill_seconds', 'decode_step_seconds',
                 'decode_slot_occupancy'):
        assert name in d, f'missing decode metric {name}'


def test_pool_exhaustion_defers_admission_not_failure(lm):
    """A pool that can only hold one request at a time still serves a
    backlog FIFO — OutOfBlocks defers admission until blocks free."""
    # each request reserves ceil((2+14)/4)=4 blocks; pool holds 5 usable
    eng = make_engine(lm, slots=4, max_blocks=6, max_prompt_len=2,
                      max_new_tokens_cap=14, block_size=4)
    with DecodeScheduler(eng) as sched:
        streams = [sched.submit([1, 2], max_new_tokens=14)
                   for _ in range(3)]
        outs = [s.result(240) for s in streams]
    assert all(len(o) == 14 for o in outs)
    assert eng.pool.allocator.used == 0


# -- validation / backpressure / deadlines / shutdown ----------------------

def test_validation_rejects_bad_requests(lm):
    eng = make_engine(lm)
    with DecodeScheduler(eng) as sched:
        with pytest.raises(InvalidRequest):
            sched.submit([], max_new_tokens=4)
        with pytest.raises(InvalidRequest):
            sched.submit([1] * 99, max_new_tokens=4)      # prompt too long
        with pytest.raises(InvalidRequest):
            sched.submit([1, 2], max_new_tokens=0)
        with pytest.raises(InvalidRequest):
            sched.submit([1, 2], max_new_tokens=999)      # over the cap
        with pytest.raises(InvalidRequest):
            sched.submit(['a', 'b'], max_new_tokens=4)


def test_overload_backpressure(lm):
    eng = make_engine(lm, slots=1)
    with DecodeScheduler(eng, queue_depth=1, start=False) as sched:
        sched.submit([1, 2], max_new_tokens=2)            # queued
        with pytest.raises(Overloaded):
            sched.submit([3, 4], max_new_tokens=2)        # queue full
        sched._worker.start()


def test_waiting_deadline_expires(lm):
    eng = make_engine(lm, slots=1)
    with DecodeScheduler(eng) as sched:
        long_s = sched.submit([1, 2, 3], max_new_tokens=16)
        late = sched.submit([4, 5], max_new_tokens=2, timeout_ms=1)
        with pytest.raises(DeadlineExceeded):
            late.result(120)
        assert len(long_s.result(120)) == 16              # unharmed


def test_close_drain_completes_everything(lm):
    eng = make_engine(lm, slots=2)
    sched = DecodeScheduler(eng)
    streams = [sched.submit([1, 2], max_new_tokens=6) for _ in range(5)]
    sched.close(drain=True)
    assert all(len(s.result(1)) == 6 for s in streams)
    with pytest.raises(EngineClosed):
        sched.submit([1], max_new_tokens=2)
    assert eng.pool.allocator.used == 0


def test_close_fail_fast_errors_streams(lm):
    eng = make_engine(lm, slots=1)
    sched = DecodeScheduler(eng)
    streams = [sched.submit([1, 2, 3], max_new_tokens=16)
               for _ in range(3)]
    sched.close(drain=False)
    failures = 0
    for s in streams:
        try:
            s.result(5)
        except EngineClosed:
            failures += 1
    assert failures >= 2, 'waiting/in-flight requests must fail fast'
    assert eng.pool.allocator.used == 0


def test_engine_failure_isolated_to_batch(lm):
    """A decode-step blowup fails the in-flight generations with a typed
    error; the scheduler worker survives and serves the next request."""
    eng = make_engine(lm, slots=2)
    boom = {'armed': False}
    real_step = eng.decode_step

    def flaky_step(tokens, tables):
        if boom['armed']:
            boom['armed'] = False
            raise RuntimeError('injected device failure')
        return real_step(tokens, tables)

    eng.decode_step = flaky_step
    from paddle_tpu.serving.errors import ServingError
    with DecodeScheduler(eng) as sched:
        boom['armed'] = True
        s1 = sched.submit([1, 2], max_new_tokens=4)
        with pytest.raises(ServingError):
            s1.result(120)
        s2 = sched.submit([3, 4], max_new_tokens=3)
        assert len(s2.result(120)) == 3
    assert eng.pool.allocator.used == 0


# -- HTTP front end --------------------------------------------------------

def test_http_generate_streaming_e2e(lm):
    eng = make_engine(lm)
    ref = greedy_generate(lm, [5, 9, 2, 44], 8, pad_len=eng.padded_context)
    sched = DecodeScheduler(eng)
    srv = ServingServer(None, port=0, generator=sched).start()
    url = f'http://127.0.0.1:{srv.port}'
    try:
        # healthz exposes decode state
        health = json.load(urllib.request.urlopen(url + '/healthz'))
        assert health['decode']['slots'] == eng.slots
        # streaming: chunked NDJSON, one line per token + a final summary
        req = urllib.request.Request(
            url + '/generate',
            data=json.dumps({'prompt': [5, 9, 2, 44],
                             'max_new_tokens': 8}).encode())
        lines = [json.loads(ln) for ln in
                 urllib.request.urlopen(req).read().splitlines()]
        toks = [ln['token'] for ln in lines if 'token' in ln]
        assert toks == ref
        assert lines[-1]['done'] is True
        assert lines[-1]['tokens'] == ref
        assert lines[-1]['finish_reason'] == 'length'
        # non-streaming mode
        req = urllib.request.Request(
            url + '/generate',
            data=json.dumps({'prompt': [5, 9, 2, 44], 'max_new_tokens': 8,
                             'stream': False}).encode())
        body = json.load(urllib.request.urlopen(req))
        assert body['tokens'] == ref
        # validation maps to 400
        req = urllib.request.Request(url + '/generate',
                                     data=json.dumps({'prompt': []}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
        # decode metrics are scrape-able without telemetry
        prom = urllib.request.urlopen(url + '/metrics').read().decode()
        assert 'paddle_tpu_decode_tokens_generated' in prom
        assert 'paddle_tpu_decode_slot_occupancy' in prom
    finally:
        srv.shutdown()


def test_http_predict_404_on_decode_only_server(lm):
    eng = make_engine(lm)
    sched = DecodeScheduler(eng)
    srv = ServingServer(None, port=0, generator=sched).start()
    try:
        req = urllib.request.Request(
            f'http://127.0.0.1:{srv.port}/predict',
            data=json.dumps({'inputs': {'x': [[1.0]]}}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 404
    finally:
        srv.shutdown()
