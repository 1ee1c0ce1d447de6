"""Disaggregated prefill/decode (paddle_tpu/serving/tier/disagg.py):
handoff parity vs colocated, the serializable payload seam, failure
isolation, decode-not-stalled behavior, and the PADDLE_TPU_DISAGG knob."""
import time

import numpy as np
import pytest

from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import greedy_generate
from paddle_tpu.serving import DecodeScheduler, ServingError
from paddle_tpu.serving.tier.disagg import (KVPayload, LocalPrefillWorker,
                                            PrefillReplica)
from paddle_tpu.serving.tier.replica import build_replica_stack, build_tiny_lm


@pytest.fixture(scope='module')
def lm():
    with guard():
        yield build_tiny_lm()


def _counter(name):
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    if not d or not d['samples']:
        return 0.0
    return sum(s['value'] for s in d['samples'])


def test_disagg_env_strict_parse(lm, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_DISAGG', 'on')
    with pytest.raises(ValueError, match="'0', '1'"):
        build_replica_stack(model=lm)
    monkeypatch.setenv('PADDLE_TPU_DISAGG', '1')
    eng, sched, worker = build_replica_stack(model=lm)
    try:
        assert worker is not None and sched.disagg is worker
    finally:
        sched.close()
        worker.close()


def test_handoff_parity_vs_colocated_and_reference(lm):
    """The acceptance bar: generations whose prefill ran on a DIFFERENT
    engine (own pool, shipped KV blocks) are bitwise-identical to the
    colocated path and to the uncached whole-sequence reference."""
    prompts = [[7, 3, 11, 5, 9], [2, 44, 8, 13], [1, 2, 3], [9] * 7]
    eng_d, sched_d, worker = build_replica_stack(model=lm, disagg=True)
    refs = [greedy_generate(lm, p, 6, pad_len=eng_d.padded_context)
            for p in prompts]
    h0, b0 = _counter('disagg_handoffs'), _counter('disagg_kv_bytes')
    try:
        outs = [sched_d.submit(p, max_new_tokens=6).result(120)
                for p in prompts]
    finally:
        sched_d.close()
        worker.close()
    assert outs == refs
    assert _counter('disagg_handoffs') - h0 == len(prompts)
    assert _counter('disagg_kv_bytes') - b0 > 0
    eng_c, sched_c, _ = build_replica_stack(model=lm, disagg=False)
    try:
        colocated = [sched_c.submit(p, max_new_tokens=6).result(120)
                     for p in prompts]
    finally:
        sched_c.close()
    assert colocated == outs
    assert eng_d.pool.allocator.used == 0     # handoff requests clean up


def test_payload_wire_roundtrip(lm):
    """to_bytes/from_bytes is the cross-host seam: arrays, context length,
    first token, and block size all survive exactly."""
    eng, sched, worker = build_replica_stack(model=lm, disagg=False)
    sched.close()
    replica = PrefillReplica(eng)
    pay = replica.prefill_to_payload([5, 6, 7, 8, 9], 0)
    assert eng.pool.allocator.used == 0       # prefill pool is scratch
    clone = KVPayload.from_bytes(pay.to_bytes())
    assert clone.context_len == 5
    assert clone.first_token == pay.first_token
    assert clone.block_size == pay.block_size
    assert len(clone.layers) == len(pay.layers) == eng.pool.num_layers
    for (k1, v1), (k2, v2) in zip(pay.layers, clone.layers):
        assert np.array_equal(k1, k2) and np.array_equal(v1, v2)
    assert pay.nbytes > 0


def test_handoff_failure_is_typed_and_isolated(lm):
    """A prefill-replica blowup fails exactly that request with a typed
    ServingError; the decode loop keeps serving the next request."""
    eng, sched, worker = build_replica_stack(model=lm, disagg=True)
    prefill_eng = worker.replicas[0].engine
    real = prefill_eng.prefill
    boom = {'armed': True}

    def flaky(prompt, table):
        if boom['armed']:
            boom['armed'] = False
            raise RuntimeError('injected prefill-replica failure')
        return real(prompt, table)

    prefill_eng.prefill = flaky
    f0 = _counter('disagg_handoff_failures')
    try:
        s1 = sched.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(ServingError):
            s1.result(120)
        s2 = sched.submit([4, 5, 6], max_new_tokens=4)
        assert len(s2.result(120)) == 4
    finally:
        sched.close()
        worker.close()
    assert _counter('disagg_handoff_failures') - f0 == 1
    assert eng.pool.allocator.used == 0


def test_decode_keeps_stepping_while_prefill_pending(lm):
    """The disaggregation point: a slow prefill must not stall the
    lockstep decode loop — an active stream finishes its whole generation
    while the handoff is still in flight."""
    eng, sched, worker = build_replica_stack(model=lm, disagg=True)
    replica = worker.replicas[0]
    real = replica.prefill_to_payload

    def slow(prompt, max_new):
        if len(prompt) > 4:                   # only the long prompt is slow
            time.sleep(2.0)
        return real(prompt, max_new)

    replica.prefill_to_payload = slow
    try:
        fast = sched.submit([1, 2], max_new_tokens=8)
        next(fast.iter_tokens(timeout=60))              # it is decoding
        slow_s = sched.submit([5, 6, 7, 8, 9], max_new_tokens=4)
        assert len(fast.result(120)) == 8
        assert not slow_s.done(), \
            'fast stream must finish while the slow handoff is pending'
        assert len(slow_s.result(120)) == 4
    finally:
        sched.close()
        worker.close()


def test_disagg_with_prefix_cache_skips_handoff_on_hit(lm):
    """Cache hits are served by suffix fill on the decode engine — no
    second handoff for a repeated prompt."""
    eng, sched, worker = build_replica_stack(model=lm, disagg=True,
                                             prefix_cache=True)
    prompt = [7, 3, 11, 5, 9, 2, 44, 8, 13]
    ref = greedy_generate(lm, prompt, 5, pad_len=eng.padded_context)
    h0 = _counter('disagg_handoffs')
    try:
        assert sched.submit(prompt, max_new_tokens=5).result(120) == ref
        assert _counter('disagg_handoffs') - h0 == 1
        assert sched.submit(prompt, max_new_tokens=5).result(120) == ref
        assert _counter('disagg_handoffs') - h0 == 1    # hit: no handoff
    finally:
        sched.close()
        worker.close()
    assert _counter('prefix_cache_hits') > 0


def test_disagg_metrics_exported(lm):
    from paddle_tpu.observability import registry
    eng, sched, worker = build_replica_stack(model=lm, disagg=True)
    try:
        sched.submit([1, 2, 3], max_new_tokens=2).result(120)
    finally:
        sched.close()
        worker.close()
    d = registry.to_dict()
    for name in ('disagg_handoffs', 'disagg_handoff_seconds',
                 'disagg_kv_bytes', 'disagg_pending'):
        assert name in d, f'missing disagg metric {name}'
