"""Speculative decoding + per-request sampling (serving/decode/):
spec-greedy bitwise parity across ragged accept lengths and block-boundary
rollbacks, the PADDLE_TPU_SPEC_DECODE=0 escape hatch, typed sampling
validation (scheduler + HTTP 400 naming the field), and the replay drill —
the same request_id + params through a FRESH subprocess reproduces the
sampled stream bitwise."""
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import (CausalLMConfig, TransformerLM,
                                         greedy_generate, sampled_generate)
from paddle_tpu.serving import (DecodeEngine, DecodeScheduler, InvalidRequest,
                                ServingServer)
from paddle_tpu.serving.decode.drafter import NGramDrafter, build_drafter
from paddle_tpu.serving.decode.sampling import (SamplingParams, TokenSampler,
                                                derive_stream_seed)

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope='module')
def lm():
    with guard():
        model = TransformerLM(CausalLMConfig.tiny())
        model.eval()
        yield model


@pytest.fixture(scope='module')
def seeded_lm():
    """Deterministic weights (the replica seed) — the step-count assertion
    below depends on n-gram acceptance, which depends on the weights."""
    from paddle_tpu.serving.tier.replica import build_tiny_lm
    with guard():
        yield build_tiny_lm()


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


_WORK = [((3, 7, 12, 5), (10, 4, 16, 7)),       # (prompt lens, budgets)
         ((9, 1, 16, 2), (12, 16, 2, 9))]


def _workload(seed=0):
    rng = np.random.RandomState(seed)
    lens, budgets = _WORK[seed % len(_WORK)]
    prompts = [list(map(int, rng.randint(3, 100, n))) for n in lens]
    return list(zip(prompts, budgets))


# -- validation ------------------------------------------------------------

def test_sampling_params_validation_unit():
    assert SamplingParams.validate(None).greedy
    p = SamplingParams.validate({'temperature': 0.7, 'top_k': 5,
                                 'top_p': 0.9, 'seed': 42})
    assert (p.temperature, p.top_k, p.top_p, p.seed) == (0.7, 5, 0.9, 42)
    assert not p.greedy
    assert SamplingParams.validate(p).to_dict() == p.to_dict()
    assert SamplingParams.validate({'top_p': 1.0}).greedy   # boundary ok
    for bad, field in (({'temperature': -0.1}, 'temperature'),
                       ({'temperature': float('inf')}, 'temperature'),
                       ({'temperature': True}, 'temperature'),
                       ({'top_k': -1}, 'top_k'),
                       ({'top_k': 1.5}, 'top_k'),
                       ({'top_p': 0.0}, 'top_p'),
                       ({'top_p': 1.5}, 'top_p'),
                       ({'seed': 'abc'}, 'seed'),
                       ({'typo_knob': 1}, 'typo_knob'),
                       ('not-a-dict', 'SamplingParams')):
        with pytest.raises(InvalidRequest) as ei:
            SamplingParams.validate(bad)
        assert field in str(ei.value), (bad, str(ei.value))


def test_submit_rejects_bad_sampling_and_request_id(lm):
    eng = make_engine(lm)
    before = _counter('decode_requests_rejected_invalid')
    with DecodeScheduler(eng) as sched:
        with pytest.raises(InvalidRequest, match='temperature'):
            sched.submit([1, 2], max_new_tokens=2,
                         sampling={'temperature': -1})
        with pytest.raises(InvalidRequest, match='unknown sampling'):
            sched.submit([1, 2], max_new_tokens=2, sampling={'temp': 0.5})
        with pytest.raises(InvalidRequest, match='request_id'):
            sched.submit([1, 2], max_new_tokens=2, request_id='a\nb')
        with pytest.raises(InvalidRequest, match='request_id'):
            sched.submit([1, 2], max_new_tokens=2, request_id='x' * 200)
    assert _counter('decode_requests_rejected_invalid') - before >= 4


def test_http_400_names_bad_field(lm):
    eng = make_engine(lm)
    sched = DecodeScheduler(eng)
    srv = ServingServer(None, port=0, generator=sched).start()
    url = f'http://127.0.0.1:{srv.port}/generate'

    def post(body):
        req = urllib.request.Request(url, data=json.dumps(body).encode())
        return urllib.request.urlopen(req)

    try:
        for body, field in (({'prompt': [1, 2], 'temperature': -1},
                             'temperature'),
                            ({'prompt': [1, 2], 'top_p': 2.0}, 'top_p'),
                            ({'prompt': [1, 2], 'tempreture': 0.5},
                             'tempreture')):
            with pytest.raises(urllib.error.HTTPError) as ei:
                post(body)
            assert ei.value.code == 400
            msg = json.loads(ei.value.read())['message']
            assert field in msg, (body, msg)
        # a valid sampled request streams, and the same request_id replays
        body = {'prompt': [5, 9, 2], 'max_new_tokens': 6, 'stream': False,
                'temperature': 0.8, 'top_k': 20, 'request_id': 'http-replay'}
        one = json.load(post(body))
        two = json.load(post(body))
        assert one['tokens'] == two['tokens'] and len(one['tokens']) == 6
        assert one['request_id'] == 'http-replay'
    finally:
        srv.shutdown()
        sched.close()


# -- sampling: greedy unchanged, sampled replayable ------------------------

def test_greedy_sampling_params_bitwise_unchanged(lm):
    """temperature=0 (explicit or default) is EXACT argmax — the engine's
    pre-sampling bitwise contract, untouched by the sampling machinery."""
    eng = make_engine(lm)
    prompt = [5, 9, 2, 44]
    ref = greedy_generate(lm, prompt, 8, pad_len=eng.padded_context)
    with DecodeScheduler(eng) as sched:
        plain = sched.submit(prompt, max_new_tokens=8).result(120)
        explicit = sched.submit(prompt, max_new_tokens=8,
                                sampling={'temperature': 0.0},
                                request_id='greedy-ignores-id').result(120)
    assert plain == ref and explicit == ref


def test_sampled_stream_matches_uncached_reference_and_replays(lm):
    """A sampled stream is a pure function of (request_id, params, prompt,
    weights): it equals the uncached whole-sequence sampled_generate
    reference, resubmission replays it bitwise, a different id diverges."""
    eng = make_engine(lm)
    prompt = [7, 3, 11, 60]
    params = {'temperature': 0.8, 'top_k': 24, 'top_p': 0.95}
    rid = 'replay-drill'
    sampler = TokenSampler(SamplingParams.validate(params), rid)
    ref = sampled_generate(lm, prompt, 10, sampler.sample,
                           pad_len=eng.padded_context)
    with DecodeScheduler(eng) as sched:
        s1 = sched.submit(prompt, max_new_tokens=10, sampling=params,
                          request_id=rid)
        got = s1.result(120)
        again = sched.submit(prompt, max_new_tokens=10, sampling=params,
                             request_id=rid).result(120)
        other = sched.submit(prompt, max_new_tokens=10, sampling=params,
                             request_id='another-id').result(120)
    assert got == ref
    assert again == got                       # bitwise replay
    assert other != got                       # the id IS the seed
    assert s1.request_id == rid
    # explicit seed wins over the request_id
    assert derive_stream_seed('x', seed=7) == 7
    assert derive_stream_seed('x') != derive_stream_seed('y')


# -- speculative decoding: parity + perf structure -------------------------

def test_spec_greedy_parity_and_fewer_steps(seeded_lm):
    """The acceptance bar: speculative greedy streams are array_equal to
    non-speculative greedy (which equals the uncached reference), and the
    verify rounds take FEWER decode steps than lockstep on the same
    workload."""
    work = _workload(0) + _workload(1)

    def run(**kw):
        eng = make_engine(seeded_lm, **kw)
        before = _counter('decode_steps')
        with DecodeScheduler(eng) as sched:
            streams = [sched.submit(p, max_new_tokens=m) for p, m in work]
            outs = [s.result(240) for s in streams]
        assert eng.pool.allocator.used == 0
        return outs, _counter('decode_steps') - before

    refs, steps_lockstep = run()
    spec, steps_spec = run(spec_decode=True, spec_k=4)
    assert spec == refs
    assert steps_spec < steps_lockstep, (steps_spec, steps_lockstep)
    assert _counter('decode_spec_rounds') > 0


class _OffsetOracle:
    """Drafts the TRUE greedy continuation shifted by ``off`` token ids:
    off=0 → every draft accepted (full-k rounds), off≠0 → every draft
    rejected (0-accept rounds, a rollback at every block boundary)."""

    def __init__(self, prompt, ref, off=0):
        self.plen, self.ref, self.off = len(prompt), list(ref), int(off)

    def propose(self, history, n):
        i = len(history) - self.plen
        return [(t + self.off) % 128 for t in self.ref[i:i + int(n)]]


def test_spec_ragged_accept_lengths_bitwise(lm):
    """Force the accept-length extremes through oracle drafters: all-k
    accepts, all-0 accepts (every round rolls its tail back, including at
    block boundaries — block_size=4, contexts cross many), and eos retiring
    a request mid-round. Every case must be bitwise greedy."""
    prompt = [3, 5, 7, 11, 13]
    eng0 = make_engine(lm)
    ref = greedy_generate(lm, prompt, 16, pad_len=eng0.padded_context)
    del eng0

    def run(off, **submit_kw):
        eng = make_engine(lm, spec_decode=True, spec_k=4)
        drafter = _OffsetOracle(prompt, ref, off)
        drafted = _counter('decode_spec_draft_tokens')
        accepted = _counter('decode_spec_accepted_tokens')
        with DecodeScheduler(eng, drafter=drafter) as sched:
            out = sched.submit(prompt, max_new_tokens=16,
                               **submit_kw).result(240)
        assert eng.pool.allocator.used == 0
        return (out, _counter('decode_spec_draft_tokens') - drafted,
                _counter('decode_spec_accepted_tokens') - accepted)

    full, drafted, accepted = run(0)
    assert full == ref
    assert drafted > 0 and accepted == drafted    # oracle: full-k accepts
    none, drafted, accepted = run(1)
    assert none == ref
    assert drafted > 0 and accepted == 0          # all rejected, all rolled
    # eos mid-verify-window retires the request before the window ends
    eos = ref[2]
    expect = ref[:ref.index(eos) + 1]             # first occurrence stops it
    eng = make_engine(lm, spec_decode=True, spec_k=4)
    with DecodeScheduler(eng, drafter=_OffsetOracle(prompt, ref)) as sched:
        s = sched.submit(prompt, max_new_tokens=16, eos_id=eos)
        assert s.result(240) == expect
        assert s.finish_reason == 'stop'
    assert eng.pool.allocator.used == 0


def test_spec_sampled_stream_identical_to_lockstep(lm):
    """Sampled slots ride the verify step one token at a time: the stream
    equals the non-speculative sampled stream (same draws, same indexes)
    and still replays from its request_id."""
    prompt = [9, 2, 31]
    params = {'temperature': 1.1, 'top_p': 0.9}

    def run(**kw):
        eng = make_engine(lm, **kw)
        with DecodeScheduler(eng) as sched:
            return sched.submit(prompt, max_new_tokens=8, sampling=params,
                                request_id='spec-sampled').result(240)

    lockstep = run()
    assert run(spec_decode=True) == lockstep
    assert run(spec_decode=True) == lockstep      # replay under spec


def test_spec_warmup_precompiles_verify_shape(lm):
    """warmup() covers the (S, k) verify shape too: spec generations build
    no further engine program afterwards, and ``warmed`` stays False until
    the spec shape has compiled."""
    eng = make_engine(lm, spec_decode=True)
    assert not eng.warmed
    timings = eng.warmup()
    assert eng.warmed and 'spec_step' in timings
    programs = eng.compiled_programs()
    with DecodeScheduler(eng) as sched:
        outs = [sched.submit(p, max_new_tokens=m).result(240)
                for p, m in _workload(0)]
    assert all(len(o) for o in outs)
    assert eng.compiled_programs() == programs


# -- knobs -----------------------------------------------------------------

def test_spec_escape_hatch_env_zero_wins(lm, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_SPEC_DECODE', '0')
    eng = make_engine(lm, spec_decode=True)       # arg says on; env 0 wins
    assert not eng.spec_enabled
    prompt = [5, 9, 2]
    ref = greedy_generate(lm, prompt, 6, pad_len=eng.padded_context)
    with DecodeScheduler(eng) as sched:
        assert sched.drafter is None
        assert sched.submit(prompt, max_new_tokens=6).result(120) == ref


def test_spec_env_knobs(lm, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_SPEC_DECODE', '1')
    monkeypatch.setenv('PADDLE_TPU_SPEC_K', '3')
    eng = make_engine(lm)
    assert eng.spec_enabled and eng.spec_k == 3
    monkeypatch.setenv('PADDLE_TPU_SPEC_DRAFTER', 'off')
    with DecodeScheduler(eng, start=False) as sched:
        assert sched.drafter is None              # knob resolved 'off'
    monkeypatch.setenv('PADDLE_TPU_SPEC_DRAFTER', 'bogus')
    with pytest.raises(ValueError, match='bogus'):
        DecodeScheduler(eng, start=False).close()
    with pytest.raises(ValueError):
        make_engine(lm, spec_decode=True, spec_k=1)


def test_ngram_drafter_and_build(lm):
    d = NGramDrafter()
    #              0  1  2  3  4  5  6
    history = [7, 8, 9, 4, 7, 8, 9]
    assert d.propose(history, 2) == [4, 7]        # longest suffix [7,8,9]
    assert d.propose([1, 2, 3], 4) == []          # no earlier occurrence
    assert d.propose([5], 3) == []                # history too short
    assert d.propose(history, 0) == []
    assert build_drafter('off', 32) is None
    assert isinstance(build_drafter(None, 32), NGramDrafter)
    dm = build_drafter('draft_model', 32, draft_model=lm)
    assert dm.propose([3, 5, 7], 2) == greedy_generate(lm, [3, 5, 7], 2,
                                                       pad_len=32)
    with pytest.raises(InvalidRequest, match='supported'):
        build_drafter('nope', 32)
    sentinel = NGramDrafter()
    assert build_drafter(sentinel, 32) is sentinel   # duck-typed pass-through


# -- replay drill: fresh subprocess ----------------------------------------

def _spawn_replica(*extra):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('PADDLE_TPU_TELEMETRY', None)
    proc = subprocess.Popen(
        [sys.executable, '-m', 'paddle_tpu.serving.tier.replica',
         '--port', '0', '--slots', '2', *extra],
        cwd=_REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    deadline = time.monotonic() + 180
    line = ''
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.strip():
            break
        if proc.poll() is not None:
            raise RuntimeError(f'replica died at startup rc={proc.returncode}')
    ready = json.loads(line)
    assert ready['ready']
    return proc, f"http://127.0.0.1:{ready['port']}"


def test_replay_drill_fresh_subprocess_bitwise():
    """The restart-safety contract end to end: the same request_id + params
    posted to a FRESH replica process — even one running with speculative
    decoding ON — returns the bitwise-identical sampled stream."""
    body = json.dumps({'prompt': [5, 9, 2, 44], 'max_new_tokens': 8,
                       'stream': False, 'temperature': 0.9, 'top_k': 12,
                       'top_p': 0.8, 'request_id': 'drill-1'}).encode()

    def post_once(*extra):
        proc, url = _spawn_replica(*extra)
        try:
            req = urllib.request.Request(url + '/generate', data=body)
            reply = json.load(urllib.request.urlopen(req, timeout=120))
        finally:
            proc.kill()
            proc.wait()
        assert reply['request_id'] == 'drill-1'
        assert len(reply['tokens']) == 8
        return reply['tokens']

    first = post_once()
    again = post_once('--spec-decode', '1')       # fresh pid, spec on
    assert first == again
