"""The `sdar_moe`-family decoder (models/block_diffusion_lm.py), a WINDOW
model that generates by block diffusion, at a small size on the CPU: against
the plain reference of the benchmark (benchmark/reference/sdar_30b_a3b.py,
float32 at precision "highest", imports nothing of paddle_tpu), whole
sequence under the block mask, through the decode engine's paged pool
(prefill, denoising and commit forwards) and through the scheduler (token
streams against the reference's generation loop); the schedule; what crosses
to the host; the softmax router; and what a window model refuses."""
import importlib.util
import os

import jax
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.core.random import default_generator
from paddle_tpu.dygraph import guard
from paddle_tpu.dygraph.tape import Tensor, no_grad_guard
from paddle_tpu.models.block_diffusion_lm import (BlockDiffusionMoEConfig,
                                                  BlockDiffusionMoELM)
from paddle_tpu.ops import llm_ops
from paddle_tpu.serving import metrics as _m
from paddle_tpu.serving.decode import DecodeEngine, DecodeScheduler
from paddle_tpu.serving.decode.diffusion import (denoise_quota,
                                                 unmask_most_confident,
                                                 validate_denoising_steps)
from paddle_tpu.serving.errors import (InvalidRequest,
                                       UnsupportedCacheFeature)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))
B, MASK = 4, 95
# float32 on both sides, another order of summation: a few ulp of a logit
TOLERANCE = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        'reference_sdar', os.path.join(
            REPO, 'benchmark', 'reference', 'sdar_30b_a3b.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _config_file(cfg):
    """The configuration file's shape: published keys at the top level, the
    program's own under `model`."""
    return dict(vars(cfg), model={'block_length': cfg.block_length,
                                  'mask_token_id': cfg.mask_token_id})


@pytest.fixture(scope='module')
def lm():
    with guard():
        default_generator.seed(11)
        model = BlockDiffusionMoELM(BlockDiffusionMoEConfig.tiny())
        model.eval()
        yield model


@pytest.fixture(scope='module')
def params(lm):
    return {n: p.value for n, p in lm.named_parameters()}


@pytest.fixture(scope='module')
def ref_rows(lm):
    return REF.make_rows(_config_file(lm.cfg), 48)


@pytest.fixture(scope='module')
def ref_generate(lm):
    return REF.make_generate(_config_file(lm.cfg), 48)


def _engine(model, **kw):
    kw.setdefault('slots', 3)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 16)
    kw.setdefault('max_new_tokens_cap', 16)
    kw.setdefault('prompt_buckets', [8, 16])
    return DecodeEngine(model, **kw)


def _want(ref_rows, params, tokens, positions):
    with jax.default_matmul_precision('highest'):
        return np.asarray(ref_rows(params, tokens, positions)[0])


def _error(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


# -- the model, whole sequence -------------------------------------------

@pytest.mark.parametrize('length', [4, 12, 16])
def test_whole_sequence_logits_under_the_block_mask(lm, params, ref_rows,
                                                    length):
    ids = np.random.default_rng(length).integers(1, MASK, length)
    with no_grad_guard():
        got = lm(Tensor(ids[None], stop_gradient=True)).value[0]
    want = _want(ref_rows, params, ids.tolist(), list(range(length)))
    assert _error(got, want) < TOLERANCE


def test_a_row_sees_its_whole_block_and_no_later_one(lm):
    """Changing a token changes the rows of its own block and of every
    later one, and no earlier row."""
    ids = np.random.default_rng(0).integers(1, MASK, 12)
    other = ids.copy()
    other[6] = (other[6] + 1) % MASK or 1
    with no_grad_guard():
        a = np.asarray(lm(Tensor(ids[None], stop_gradient=True)).value[0])
        b = np.asarray(lm(Tensor(other[None], stop_gradient=True)).value[0])
    changed = np.abs(a - b).max(-1) > 0
    assert not changed[:4].any() and changed[4:].all()


# -- the configuration ---------------------------------------------------

def test_config_takes_published_keys_and_refuses_what_it_cannot_compute():
    published = dict(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=32, num_hidden_layers=2,
        num_attention_heads=8, num_key_value_heads=2, head_dim=8,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
        rms_norm_eps=1e-6, rope_theta=1e6, max_position_embeddings=64,
        model_type='sdar_moe', max_window_layers=2, decoder_sparse_step=1,
        mlp_only_layers=[], attention_bias=False, hidden_act='silu',
        rope_scaling=None, sliding_window=None, use_sliding_window=False,
        tie_word_embeddings=False)
    cfg = BlockDiffusionMoEConfig.from_published(
        dict(published, runner='x', check={}), block_length=4,
        mask_token_id=95)
    assert (cfg.num_experts, cfg.n_routed_experts, cfg.n_shared_experts,
            cfg.scoring_func) == (8, 8, 0, 'softmax')
    for key, value in [('use_sliding_window', True),
                       ('rope_scaling', {'type': 'yarn'}),
                       ('attention_bias', True),
                       ('tie_word_embeddings', True),
                       ('decoder_sparse_step', 2), ('mlp_only_layers', [0])]:
        with pytest.raises(ValueError, match=key):
            BlockDiffusionMoEConfig(**dict(published, **{key: value}))
    with pytest.raises(ValueError, match='unknown key'):
        BlockDiffusionMoEConfig(**dict(published, n_group=2))
    with pytest.raises(ValueError, match='block_length'):
        BlockDiffusionMoEConfig(**dict(published, block_length=1))
    with pytest.raises(ValueError, match='mask_token_id'):
        BlockDiffusionMoEConfig(**dict(published, mask_token_id=96))
    with pytest.raises(ValueError, match='divide'):
        BlockDiffusionMoEConfig(**dict(published, num_key_value_heads=3))


def test_the_model_says_what_it_caches_and_what_a_step_feeds(lm):
    from paddle_tpu.serving.decode.layout import CacheLayout, LayerCache
    layout = lm.cache_layout()
    assert layout == CacheLayout(
        (LayerCache.kv(2, 8, read='window'),) * 3, window=4)
    assert layout.window == 4 and lm.mask_token_id == MASK
    names = [n for n, _ in lm.named_parameters()]
    # softmax router: no selection bias; no shared expert
    assert not [n for n in names if 'router_bias' in n or 'shared' in n]
    assert layout.step_rows(128) == 512
    assert layout.block_bytes(16, 'bf16') == 3 * 16 * 2 * 128 * 2


# -- the softmax router ---------------------------------------------------

def test_softmax_router_against_its_definition_ties_to_the_lower_expert():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((6, 16)).astype('float32')
    w = rng.standard_normal((16, 8)).astype('float32')
    w[:, 5] = w[:, 2]                       # experts 2 and 5 always tie
    ids, weights = llm_ops.moe_router(x, w, top_k=3, scoring_func='softmax')
    ids, weights = np.asarray(ids), np.asarray(weights)
    logits = x.astype(np.float64) @ w.astype(np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    for t in range(6):
        order = sorted(range(8), key=lambda e: (-np.float32(p[t, e]), e))
        np.testing.assert_array_equal(ids[t], order[:3])
        chosen = p[t, ids[t]]
        np.testing.assert_allclose(weights[t], chosen / chosen.sum(),
                                   rtol=1e-5)
        if 5 in ids[t]:
            assert list(ids[t]).index(2) + 1 == list(ids[t]).index(5)
    raw = np.asarray(llm_ops.moe_router(
        x, w, top_k=3, scoring_func='softmax', norm_topk_prob=False)[1])
    np.testing.assert_allclose(raw, np.take_along_axis(p, ids, -1),
                               rtol=1e-5)
    with pytest.raises(ValueError, match='scoring_func'):
        llm_ops.moe_router(x, w, top_k=3, scoring_func='tanh')


def test_router_of_the_reference_is_the_ops(lm, params):
    h = np.random.default_rng(3).standard_normal((5, 32)).astype('float32')
    model = REF.model_of(_config_file(lm.cfg))
    forced = np.full((5, 2), -1, np.int32)
    ids, weights = llm_ops.moe_router(
        h, params['layers.0.ffn.router.weight'], top_k=2,
        scoring_func='softmax')
    with jax.default_matmul_precision('highest'):
        out, gap = REF._experts(params, 'layers.0.ffn', model, h, forced, 0.)
    routed, _ = llm_ops.moe_experts(
        h, ids, weights, params['layers.0.ffn.experts_gate'],
        params['layers.0.ffn.experts_up'],
        params['layers.0.ffn.experts_down'])
    assert _error(routed, np.asarray(out)) < TOLERANCE
    assert (np.asarray(gap) <= 0).all()


# -- the schedule ---------------------------------------------------------

def test_schedule_unmasks_the_most_confident_ties_to_the_lower_position():
    blocks = np.full((4, 4), MASK, np.int64)
    blocks[1, 0] = 7
    masked = np.ones((4, 4), bool)
    masked[1, 0] = False
    masked[3] = False                        # a committing slot
    ids = np.arange(16).reshape(4, 4) + 20
    conf = np.asarray([[.2, .9, .9, .1], [1., .3, .5, .4],
                       [.5, .5, .5, .5], [.9, .9, .9, .9]], np.float32)
    n = unmask_most_confident(blocks, masked, ids, conf, [2, 1, 3, 0])
    assert n == 6
    np.testing.assert_array_equal(blocks[0], [MASK, 21, 22, MASK])
    np.testing.assert_array_equal(blocks[1], [7, MASK, 26, MASK])
    np.testing.assert_array_equal(blocks[2], [28, 29, 30, MASK])
    np.testing.assert_array_equal(masked.sum(1), [2, 2, 1, 0])
    assert [denoise_quota(m, s) for m, s in
            [(4, 1), (4, 2), (4, 3), (4, 4), (3, 2), (1, 2)]] \
        == [4, 2, 2, 1, 2, 1]
    assert validate_denoising_steps(4, 4) == 4
    for bad in (0, 5, 2.0, True, '2'):
        with pytest.raises(InvalidRequest, match='denoising_steps'):
            validate_denoising_steps(bad, 4)


# -- through the paged pool ------------------------------------------------

def _drive(engine, prompt, blocks_wanted, steps=2):
    """Slot 1 of the engine (0 and 2 idle) through a prefill and
    ``blocks_wanted`` whole blocks; yields per forward (tokens fed, rows of
    the block (B, V), was it a commit)."""
    s = engine.slots
    blocks = np.zeros((s, B), np.int64)
    masked = np.zeros((s, B), bool)
    quota = np.zeros(s, np.int64)
    table = engine.reserve_table(len(prompt), (blocks_wanted + 1) * B)
    tables = [None, table, None]
    engine.prefill(prompt, table)
    whole = table.context_len
    sequence, fixed = list(prompt[:whole]), list(prompt[whole:])
    forwards = []
    for _ in range(blocks_wanted):
        blocks[1, :len(fixed)] = fixed
        blocks[1, len(fixed):] = MASK
        masked[1] = np.arange(B) >= len(fixed)
        quota[1] = denoise_quota(B - len(fixed), steps)
        while True:
            commit = not masked[1].any()
            fed = sequence + blocks[1].tolist()
            base = table.context_len
            _, _, rows = engine.window_step(
                blocks, masked, quota, tables, [False, commit, False],
                return_rows=True)
            forwards.append((fed, np.array(rows[1]), commit))
            assert table.context_len == base + (B if commit else 0)
            if commit:
                break
        sequence, fixed = sequence + blocks[1].tolist(), []
    engine.release_table(table)
    return forwards


@pytest.mark.parametrize('plen', [8, 5, 7, 3])
def test_prefill_denoising_and_commit_through_the_pool(lm, params, ref_rows,
                                                       plen):
    """Every forward's B rows against the reference's whole-sequence
    forward over the very tokens fed: a prompt's tail beside masks over the
    prefill's K/V, partly unmasked blocks, commit forwards, and blocks over
    K/V that commit forwards wrote (a prompt shorter than a block runs no
    prefill at all)."""
    engine = _engine(lm)
    prompt = np.random.default_rng(plen).integers(1, MASK, plen).tolist()
    forwards = _drive(engine, prompt, blocks_wanted=3)
    assert sum(commit for _, _, commit in forwards) == 3
    # the first block opens with the prompt's tail: fewer masks, as many
    # or fewer forwards
    assert len(forwards) == (8 if plen % B == 3 else 9)
    for fed, rows, _ in forwards:
        at = list(range(len(fed) - B, len(fed)))
        assert _error(rows, _want(ref_rows, params, fed, at)) < TOLERANCE


def test_a_provisional_write_is_never_read_after_a_commit_of_other_tokens(
        lm, params, ref_rows):
    """The K/V a denoising forward wrote (of `MASK` inputs) are gone once
    the block commits other tokens: the next block's rows are the
    reference's over the committed tokens, and far from the reference's over
    the denoising forward's inputs."""
    engine = _engine(lm)
    prompt = np.random.default_rng(21).integers(1, MASK, 8).tolist()
    forwards = _drive(engine, prompt, blocks_wanted=2)
    first_inputs = forwards[0][0]               # prompt + [MASK] * 4
    assert first_inputs[-B:] == [MASK] * B
    fed, rows, _ = next(f for f in forwards if len(f[0]) == 16)
    at = list(range(12, 16))
    assert _error(rows, _want(ref_rows, params, fed, at)) < TOLERANCE
    stale = first_inputs + fed[-B:]
    assert _error(rows, _want(ref_rows, params, stale, at)) > 1e-2


def test_ids_and_confidences_cross_and_no_logits_row(lm):
    engine = _engine(lm)
    s = engine.slots
    table = engine.reserve_table(8, 8)
    engine.prefill(list(range(1, 9)), table)
    blocks = np.full((s, B), MASK, np.int64)
    masked = np.ones((s, B), bool)
    with obs.telemetry_guard(True):
        obs.reset()
        before = _m.decode_logits_bytes_copied.value
        ids, conf = engine.window_step(
            blocks, masked, np.full(s, 2), [table, None, None],
            [False] * s)
        copied = _m.decode_logits_bytes_copied.value - before
        span = next(e for e in obs.tracer.snapshot()['traceEvents']
                    if e['name'] == 'engine/step')
        obs.reset()
    engine.release_table(table)
    assert ids.shape == conf.shape == (s, B)
    assert ids.dtype == np.int32 and conf.dtype == np.float32
    assert copied == s * B * 8                  # 4 B an id, 4 a confidence
    args = span['args']
    assert args['rows_fetched'] == 0
    assert (args['window'], args['slot_forwards'], args['commits'],
            args['rows_unmasked']) == (B, 1, 0, 2)
    assert args['context_positions'] == 3 * (8 + B)
    assert args['expert_assignments'] == 3 * B * 2
    assert (ids[0] != MASK).all() and (0 < conf[0]).all() \
        and (conf[0] <= 1).all()
    # the two most confident masked positions took their picks, in place
    taken = ~masked[0]
    assert taken.sum() == 2 and (blocks[0][taken] == ids[0][taken]).all()
    assert set(np.argsort(-conf[0], kind='stable')[:2]) \
        == set(np.flatnonzero(taken))
    assert masked[1:].all() and (blocks[1:] == MASK).all()


def test_one_step_program_and_one_prefill_program_a_rung(lm):
    engine = _engine(lm, prompt_buckets=[2, 8, 16])
    base = engine.compiled_programs()
    engine.warmup()
    assert engine.warmed            # the rung below a block runs no program
    # this geometry's programs: two rungs and the step (other tests'
    # engines of the same geometry share them)
    assert engine.compiled_programs() - base <= 3
    text = engine.lowered().as_text(debug_info=True)
    assert 'kv/block_read' in text and 'diffusion/pick' in text
    assert 'kv/decode_read' not in text


# -- through the scheduler -------------------------------------------------

REQUESTS = [(8, 7), (5, 6), (7, 9), (3, 5), (12, 10), (9, 1), (16, 13)]


@pytest.mark.parametrize('steps', [1, 2, 4])
def test_token_streams_are_the_references_generation_loop(lm, params,
                                                          ref_generate,
                                                          steps):
    """Prompts of P mod 4 in {0, 1, 3} and more, answers that are no
    multiple of 4, seven requests over three slots: slots commit while
    others denoise, admissions land beside live blocks, a slot idles at the
    end."""
    engine = _engine(lm)
    sched = DecodeScheduler(engine, denoising_steps=steps)
    rng = np.random.default_rng(steps)
    prompts = [rng.integers(1, MASK, p).tolist() for p, _ in REQUESTS]
    try:
        streams = [sched.submit(prompt, n)
                   for prompt, (_, n) in zip(prompts, REQUESTS)]
        for stream, prompt, (_, n) in zip(streams, prompts, REQUESTS):
            got = stream.result(120)
            with jax.default_matmul_precision('highest'):
                want = ref_generate(params, prompt, n, steps)
            assert got == want and len(got) == n
            assert stream.finish_reason == 'length'
            assert MASK not in got
    finally:
        sched.close()
    assert engine.pool.allocator.used == 0


def _blocks_timed():
    hist = obs.registry.to_dict().get('decode_block_seconds')
    return sum(s['count'] for s in hist['samples']) if hist else 0


def test_denoising_steps_per_request_counters_and_block_time(lm, params,
                                                             ref_generate):
    engine = _engine(lm)
    sched = DecodeScheduler(engine, denoising_steps=2)
    prompt = list(range(1, 9))
    counters = (_m.decode_diffusion_denoise_forwards,
                _m.decode_diffusion_commit_forwards,
                _m.decode_diffusion_tokens_committed)
    try:
        before = [c.value for c in counters]
        blocks_before = _blocks_timed()
        got = sched.submit(prompt, 8).result(60)       # the default: 2
        assert [c.value - b for c, b in zip(counters, before)] \
            == [4, 2, 8]                    # 2 blocks x (2 denoise + commit)
        assert _blocks_timed() - blocks_before == 2
        before = [c.value for c in counters]
        one = sched.submit(prompt, 6, denoising_steps=1).result(60)
        assert [c.value - b for c, b in zip(counters, before)] \
            == [2, 2, 6]                    # the last block cut at 6
        four = sched.submit(prompt, 8, denoising_steps=4).result(60)
        with jax.default_matmul_precision('highest'):
            assert got == ref_generate(params, prompt, 8, 2)
            assert one == ref_generate(params, prompt, 6, 1)
            assert four == ref_generate(params, prompt, 8, 4)
        for bad in (0, 5, 1.5):
            with pytest.raises(InvalidRequest, match='denoising_steps'):
                sched.submit(prompt, 4, denoising_steps=bad)
        with pytest.raises(InvalidRequest, match='confidence'):
            sched.submit(prompt, 4, sampling={'temperature': 0.7})
        eos = got[2]
        stopped = sched.submit(prompt, 8, eos_id=eos)
        assert stopped.result(60) == got[:got.index(eos) + 1]
        assert stopped.finish_reason == 'stop'
    finally:
        sched.close()


def test_generate_takes_denoising_steps_over_http(lm):
    import http.client
    import json
    from paddle_tpu.serving.server import ServingServer
    engine = _engine(lm)
    sched = DecodeScheduler(engine, denoising_steps=2)
    server = ServingServer(None, host='127.0.0.1', port=0, generator=sched)
    server.start()
    try:
        def post(body):
            conn = http.client.HTTPConnection('127.0.0.1', server.port,
                                              timeout=60)
            conn.request('POST', '/generate', json.dumps(body),
                         {'Content-Type': 'application/json'})
            resp = conn.getresponse()
            out = resp.status, json.loads(resp.read())
            conn.close()
            return out

        body = {'prompt': list(range(1, 9)), 'max_new_tokens': 6,
                'stream': False}
        ok, default = post(body)
        ok4, four = post(dict(body, denoising_steps=4))
        assert ok == ok4 == 200
        assert len(default['tokens']) == len(four['tokens']) == 6
        bad, why = post(dict(body, denoising_steps=9))
        assert bad == 400 and 'denoising_steps' in why['message']
    finally:
        server.shutdown(drain=False)


# -- what a window model refuses, and what it leaves alone ------------------

def test_refusals_are_typed_and_say_why(lm):
    for kw, what in [({'prefix_cache': True}, 'prefix cache'),
                     ({'spec_decode': True}, 'speculative'),
                     ({'kv_dtype': 'int8'}, 'int8')]:
        with pytest.raises(UnsupportedCacheFeature, match=what) as e:
            _engine(lm, **kw)
        assert e.value.kind == 'window'
        assert 'Window models' in str(e.value)
    from paddle_tpu.serving.tier.disagg import PrefillReplica
    with pytest.raises(UnsupportedCacheFeature, match='handoff'):
        PrefillReplica(_engine(lm))
    with pytest.raises(ValueError, match='multiple'):
        _engine(lm, block_size=6)
    engine = _engine(lm)
    with pytest.raises(InvalidRequest, match='sampler'):
        engine.prefill([1, 2, 3, 4], engine.reserve_table(4, 4),
                       sampler=lambda row: 0)


def test_a_model_of_window_one_takes_the_path_it_took():
    """No window state, no diffusion argument, and the sigmoid router's
    dispatch as it was: models of window 1 lower what they lowered."""
    from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
    from paddle_tpu.models.latent_moe_lm import (LatentMoEConfig,
                                                 LatentMoELM)
    with guard():
        default_generator.seed(3)
        gpt = TransformerLM(CausalLMConfig.tiny())
        gpt.eval()
        engine = DecodeEngine(gpt, slots=2, block_size=4, max_blocks=32,
                              max_prompt_len=8, max_new_tokens_cap=8)
        assert engine.window == 1
        with pytest.raises(ValueError, match='window model'):
            DecodeScheduler(engine, denoising_steps=2, start=False)
        sched = DecodeScheduler(engine)
        try:
            assert not hasattr(sched, '_blocks')
            with pytest.raises(InvalidRequest, match='denoising_steps'):
                sched.submit([1, 2, 3], 2, denoising_steps=2)
            assert len(sched.submit([1, 2, 3], 3).result(60)) == 3
        finally:
            sched.close()
        text = engine.lowered().as_text(debug_info=True)
        assert 'kv/decode_read' in text and 'kv/block_read' not in text \
            and 'diffusion/pick' not in text
        moe = LatentMoELM(LatentMoEConfig.tiny())
        routed = moe.layers[1].ffn
        assert 'scoring_func' not in routed._route
        assert routed.router_bias is not None and routed.shared is not None
