"""The deepseek_v3-family decoder (models/latent_moe_lm.py) and its ops
(ops/llm_ops.py) at a small size on the CPU: against the plain reference of
the benchmark (benchmark/reference/kanana2_30b_a3b.py, float32 at precision
"highest", imports nothing of paddle_tpu), whole sequence and through the
decode engine's latent pool; each op against its own definition; the
analysis rules; and what a latent pool refuses."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.random import default_generator
from paddle_tpu.dygraph import guard
from paddle_tpu.dygraph.tape import Tensor, no_grad_guard
from paddle_tpu.models.latent_moe_lm import LatentMoEConfig, LatentMoELM
from paddle_tpu.ops import llm_ops
from paddle_tpu.serving.decode import DecodeEngine
from paddle_tpu.serving.decode.kv_cache import row_lanes
from paddle_tpu.serving.errors import UnsupportedCacheFeature

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))


def _reference():
    spec = importlib.util.spec_from_file_location(
        'reference_kanana2', os.path.join(
            REPO, 'benchmark', 'reference', 'kanana2_30b_a3b.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _config_file(cfg):
    """The configuration file's shape: published keys at the top level."""
    return dict(vars(cfg), model={})


@pytest.fixture(scope='module')
def lm():
    with guard():
        default_generator.seed(7)
        model = LatentMoELM(LatentMoEConfig.tiny())
        model.eval()
        yield model


@pytest.fixture(scope='module')
def params(lm):
    return {n: p.value for n, p in lm.named_parameters()}


def _engine(model, **kw):
    kw.setdefault('slots', 2)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 16)
    kw.setdefault('max_new_tokens_cap', 8)
    kw.setdefault('prompt_buckets', [8, 16])
    kw.setdefault('prefix_cache', False)
    return DecodeEngine(model, **kw)


def _rows(lm, pad=32):
    return REF.make_rows(_config_file(lm.cfg), pad)


def test_whole_sequence_agrees_with_the_reference(lm, params):
    ids = np.random.RandomState(0).randint(1, lm.cfg.vocab_size, (2, 20))
    with no_grad_guard():
        got = lm(Tensor(ids, stop_gradient=True)).numpy()
    assert got.dtype == np.float32
    rows = _rows(lm)
    for b in range(2):
        want = np.asarray(rows(params, ids[b].tolist(), range(20))[0])
        assert np.abs(got[b] - want).max() < 1e-4 * np.abs(want).max()


@pytest.mark.parametrize('kv_dtype,tolerance', [('f32', 1e-5), ('bf16', 2e-2)])
def test_prefill_then_decode_through_the_latent_pool(lm, params, kv_dtype,
                                                     tolerance):
    """Prefill's last row and three decode steps, read through the paged
    latent pool in the absorbed form, against the reference's whole
    forward; two slots at different contexts in one lockstep step."""
    engine = _engine(lm, kv_dtype=kv_dtype)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, lm.cfg.vocab_size, n).tolist() for n in (11, 5)]
    tables, seqs, got = [], [], [[], []]
    for i, prompt in enumerate(prompts):
        table = engine.reserve_table(len(prompt), 4)
        token = engine.prefill(prompt, table, sampler=lambda row, i=i: (
            got[i].append(np.array(row)), int(row.argmax()))[1])
        tables.append(table)
        seqs.append(prompt + [token])
    chosen = [{}, {}]        # per slot, {position: (expert layers, k)}
    for _ in range(3):
        ids, step_rows = engine.decode_step([s[-1] for s in seqs], tables,
                                            return_rows=True)
        routed = np.asarray(engine.last_stats['expert_ids'])
        for i in range(2):
            got[i].append(np.array(step_rows[i]))
            chosen[i][len(seqs[i]) - 1] = routed[:, i]
            seqs[i].append(int(ids[i]))
    rows = _rows(lm)
    for i, prompt in enumerate(prompts):
        n = len(prompt)
        want = np.asarray(rows(params, seqs[i], range(n - 1, n + 3))[0])
        err = max(np.abs(g - w).max() for g, w in zip(got[i], want))
        assert err < tolerance * np.abs(want).max(), (kv_dtype, i, err)
        if kv_dtype == 'f32':
            # the experts the step reports are the reference's own top-k
            gaps = np.asarray(rows(params, seqs[i], sorted(chosen[i]),
                                   chosen[i])[1])
            assert (gaps < 0).all(), gaps
    # one latent array a layer, no head axis; a row of kv_lora_rank + rope
    # values in the next multiple of 128 lanes
    layers, scales = engine.pool.arrays()
    assert len(layers) == lm.cfg.num_hidden_layers and not scales
    assert lm.cfg.latent_row_width == 20 and row_lanes(576) == 640
    for arrs in layers.values():
        assert [a.shape for a in arrs] == [(64, 4, 128)]
    assert engine.pool.row_bytes() == 128 * (4 if kv_dtype == 'f32' else 2)
    assert engine.layout.kind == 'latent'
    for table in tables:
        engine.release_table(table)


def test_the_verify_step_over_the_latent_pool_is_the_lockstep_step(lm):
    """K fed tokens a slot (the speculative verify step) write K latent rows
    and read the staircase, every table gathered dense: row 0 is the
    lockstep step's row, which walks the live groups, to the rounding of
    another order of float32 summation (2.7e-6 seen)."""
    rows = []
    for spec in (True, False):
        engine = _engine(lm, spec_decode=spec, spec_k=3)
        table = engine.reserve_table(6, 8)
        token = engine.prefill([3, 4, 5, 6, 7, 8], table)
        if spec:
            rows.append(np.asarray(engine.spec_step(
                [[token, 4, 5], None], [table, None]))[0, 0])
            assert np.asarray(engine.last_stats['expert_ids']).shape[1] == 6
        else:
            rows.append(engine.decode_step([token, None], [table, None],
                                           return_rows=True)[1][0])
        engine.release_table(table)
    np.testing.assert_allclose(rows[0], rows[1], rtol=2e-5, atol=2e-5)


def test_the_engine_books_routing_and_positions_read(lm):
    from paddle_tpu.observability import registry
    from paddle_tpu.serving import metrics as m
    engine = _engine(lm)
    table = engine.reserve_table(6, 2)
    before = {k: getattr(m, k).value for k in (
        'decode_expert_assignments', 'decode_experts_touched',
        'decode_context_positions_read')}
    token = engine.prefill([3, 4, 5, 6, 7, 8], table)
    engine.decode_step([token, None], [table, None])
    engine.release_table(table)
    cfg = lm.cfg
    moe_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    # live tokens alone: the prompt's 6 rows of the 8-row rung, the one
    # active slot of the step's two (the rest is computed and not counted)
    assert m.decode_expert_assignments.value - before[
        'decode_expert_assignments'] == (6 + 1) * moe_layers \
        * cfg.num_experts_per_tok
    touched = m.decode_experts_touched.value - before[
        'decode_experts_touched']
    k = cfg.num_experts_per_tok
    assert 2 * moe_layers * k <= touched <= (6 + 1) * moe_layers * k
    counts = np.asarray(engine.last_stats['expert_counts'])
    assert counts.shape == (moe_layers, cfg.n_routed_experts)
    assert (counts.sum(1) == k).all() and counts.max() == 1
    assert m.decode_context_positions_read.value - before[
        'decode_context_positions_read'] == 7 * cfg.num_hidden_layers
    hist = registry.to_dict()['decode_expert_load_max_over_mean']
    assert {s['labels']['call'] for s in hist['samples']} >= {'prefill',
                                                               'step'}
    assert m.kv_cache_row_bytes.value == 128 * 4


def test_the_counts_are_copied_before_the_sample_phase(lm, monkeypatch):
    """`sample` is the host's pick and nothing else: the routing counts
    reach the host inside `logits_copy`, with the rows."""
    from paddle_tpu.serving.decode import engine as eng
    seen = []
    account = eng.DecodeEngine._account_experts
    monkeypatch.setattr(
        eng.DecodeEngine, '_account_experts', staticmethod(
            lambda call, counts: (seen.append(type(counts)),
                                  account(call, counts))[1]))
    clocks = []
    fetch = eng._CallClock.fetch
    monkeypatch.setattr(eng._CallClock, 'fetch', lambda self, *a: (
        clocks.append(self), fetch(self, *a))[1])
    engine = _engine(lm)
    table = engine.reserve_table(6, 2)
    token = engine.prefill([3, 4, 5, 6, 7, 8], table)
    engine.decode_step([token, None], [table, None])
    engine.release_table(table)
    assert seen == [np.ndarray, np.ndarray]
    for clock in clocks:
        assert [phase for phase, _ in clock.ends] == [
            'pack', 'forward', 'device_wait', 'logits_copy', 'sample']


def test_absorbed_decode_is_expanded_attention_on_the_same_weights():
    """`mla_decode_attention` over a paged pool holding a sequence's latent
    rows gives, for the last K positions, the rows `mla_prefill_attention`
    gives over the whole sequence."""
    rng = np.random.RandomState(2)
    heads, nope, rope, v, rank, n, block = 3, 8, 4, 6, 16, 13, 4
    q = jnp.asarray(rng.randn(1, n, heads, nope + rope), jnp.float32)
    latent = jnp.asarray(rng.randn(1, n, rank + rope), jnp.float32)
    w_kvb = jnp.asarray(rng.randn(rank, heads * (nope + v)) * 0.3,
                        jnp.float32)
    attrs = dict(qk_nope_dim=nope, v_dim=v, sm_scale=(nope + rope) ** -0.5)
    want = np.asarray(llm_ops.mla_prefill_attention(q, latent, w_kvb,
                                                    **attrs))
    # the sequence's rows in blocks 5, 2, 7, 1 of a pool of stale garbage
    table = [5, 2, 7, 1]
    pages = rng.randn(9, block, rank + rope).astype(np.float32)
    padded = np.zeros((len(table) * block, rank + rope), np.float32)
    padded[:n] = np.asarray(latent[0])
    pages[table] = padded.reshape(len(table), block, -1)
    pages[table[-1], n % block:] = 99.0          # past the context: masked
    for k in (1, 3):
        got = llm_ops.mla_decode_attention(
            q[0, n - k:][None], jnp.asarray(pages),
            np.asarray([table + [0]], np.int32),
            np.asarray([n - k + 1], np.int32), w_kvb, **attrs)
        np.testing.assert_allclose(np.asarray(got)[0], want[0, n - k:],
                                   rtol=2e-5, atol=2e-5)


def _walked_batch(rng, contexts, *, block=16, per_slot=40, lanes=None,
                  dtype=np.float32, heads=3, nope=8, rope=4, v=6, rank=16):
    """A lockstep batch for the walked read. Slot i holds ``contexts[i]``
    cached positions (0: idle, its whole table the scratch block and its
    context 1) in its own blocks, handed out back to front, of a pool
    whose every other value is stale garbage: 99.0 in every dead block,
    in every position past a context inside a live block, and, where
    ``lanes`` > rank + rope, in the pad lanes of the dead blocks (a live
    row's pad lanes are zeros, as the pool's writes leave them). Returns
    the op's inputs, and the expanded form's answer per live slot: the
    last row `mla_prefill_attention` gives over that slot's sequence."""
    width = rank + rope
    lanes = lanes or width
    slots = len(contexts)
    attrs = dict(qk_nope_dim=nope, v_dim=v, sm_scale=(nope + rope) ** -0.5)
    w_kvb = jnp.asarray(rng.randn(rank, heads * (nope + v)) * 0.3, dtype)
    pages = np.full((1 + slots * per_slot, block, lanes), 99.0, np.float32)
    tables = np.zeros((slots, per_slot), np.int32)
    q = rng.randn(slots, 1, heads, nope + rope).astype(np.float32)
    want = {}
    free = list(range(slots * per_slot, 0, -1))
    for i, n in enumerate(contexts):
        if not n:
            continue
        latent = rng.randn(n, width).astype(np.float32)
        for j in range(-(-n // block)):
            tables[i, j] = free.pop(0)
            rows = latent[j * block:(j + 1) * block]
            pages[tables[i, j], :len(rows), :width] = rows
            pages[tables[i, j], :len(rows), width:] = 0.0
        # the expanded form over the rows as the pool holds them
        held = jnp.asarray(latent, dtype)[None]
        qs = jnp.zeros((1, n, heads, nope + rope), dtype).at[0, -1].set(
            jnp.asarray(q[i, 0], dtype))
        want[i] = np.asarray(llm_ops.mla_prefill_attention(
            qs, held, w_kvb, **attrs)[0, -1].astype(jnp.float32))
    inputs = (jnp.asarray(q, dtype), jnp.asarray(pages, dtype), tables,
              np.asarray([n or 1 for n in contexts], np.int32), w_kvb)
    return inputs, attrs, want


def test_the_walked_read_is_the_expanded_form_over_each_slots_live_rows(
        monkeypatch):
    """K = 1 walks the live groups: slots of unequal context (one position,
    a group's edge and one past it, the table's whole width), idle slots on
    the scratch block, 7 x 5 = 35 groups in chunks of 8 (so the walk takes
    several chunks and the last is part padding), stale garbage in every
    dead block and past each context inside a live group. Equal to the
    expanded form to float32's rounding; exactly-zero mass where masked: a
    finite garbage value anywhere dead moves no bit of the result."""
    from paddle_tpu.ops import nn_ops
    monkeypatch.setattr(nn_ops, 'LIVE_GROUP_CHUNK', 8)
    contexts = [1, 0, 128, 129, 640, 0, 333]
    inputs, attrs, want = _walked_batch(np.random.RandomState(11), contexts)
    block_ids, _, _, n_live = nn_ops.live_group_list(inputs[2], inputs[3], 16)
    assert block_ids.shape == (40, 8) and int(n_live) == 1 + 1 + 1 + 2 \
        + 5 + 1 + 3
    got = np.asarray(llm_ops.mla_decode_attention(*inputs, **attrs))
    assert got.shape == (7, 1, 3 * 6) and np.isfinite(got).all()
    for i, row in want.items():
        np.testing.assert_allclose(got[i, 0], row, rtol=2e-5, atol=2e-5)
    # other garbage, the same bits: what is masked has no mass at all (an
    # idle slot reads the scratch block's first row, and nobody its result)
    pages = np.asarray(inputs[1]).copy()
    pages[pages == 99.0] = -7.5
    again = llm_ops.mla_decode_attention(inputs[0], jnp.asarray(pages),
                                         *inputs[2:], **attrs)
    live = sorted(want)
    np.testing.assert_array_equal(np.asarray(again)[live], got[live])


def test_the_walked_read_takes_a_bf16_pool_of_padded_lanes_as_it_lies():
    """The served layout in small: bf16 rows of rank + rope = 20 values in
    32 lanes. The query is padded with zeros to the rows' lanes and a chunk
    is used as taken, so non-zero garbage in the pad lanes of DEAD blocks
    (and in their values) changes nothing, and the result is the expanded
    form's at bf16's rounding."""
    contexts = [37, 0, 200, 16]
    inputs, attrs, want = _walked_batch(
        np.random.RandomState(12), contexts, per_slot=13, lanes=32,
        dtype=jnp.bfloat16)
    assert inputs[1].shape[-1] == 32 and inputs[1].dtype == jnp.bfloat16
    got = llm_ops.mla_decode_attention(*inputs, **attrs)
    assert got.dtype == jnp.bfloat16
    got = np.asarray(got.astype(jnp.float32))
    for i, row in want.items():
        np.testing.assert_allclose(got[i, 0], row, rtol=0.05, atol=0.05)
    pages = np.asarray(inputs[1].astype(jnp.float32)).copy()
    dead = (pages[:, :, 20:] == 99.0).all((1, 2))
    assert dead.sum() == pages.shape[0] - (3 + 13 + 1)
    pages[dead, :, 20:] = 3.25
    again = llm_ops.mla_decode_attention(
        inputs[0], jnp.asarray(pages, jnp.bfloat16), *inputs[2:], **attrs)
    live = sorted(want)
    np.testing.assert_array_equal(
        np.asarray(again.astype(jnp.float32))[live], got[live])


def _count_eqns(jaxpr, pred):
    """Equations of ``jaxpr`` and of every jaxpr nested in it (a `pjit`'s,
    a `while`'s body, a `cond`'s branches) that ``pred`` holds for."""
    found = 0
    for eqn in jaxpr.eqns:
        found += bool(pred(eqn))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    found += _count_eqns(sub, pred)
    return found


def test_given_the_list_the_op_builds_no_second_one(lm):
    """One `live_group_list` a program: the step of a model of three latent
    layers holds ONE cumsum (the list's; nothing else in the step has one)
    and one `while` a layer; the op called alone, with no ``live``, makes
    its own."""
    from paddle_tpu.serving.decode.kv_cache import decode_coords
    engine = _engine(lm)
    table = engine.reserve_table(6, 2)
    engine.prefill([3, 4, 5, 6, 7, 8], table)      # allocates the pool
    engine.release_table(table)
    pool, prog = engine.pool, engine._program
    feed = np.zeros((engine.slots, 1), np.int64)
    layers, scales = pool.arrays()
    step = jax.make_jaxpr(
        lambda pv, *rest: prog.jitted('decode', pool.geometry, pv, {},
                                      layers, scales, *rest))(
        {n: p.value for n, p in prog._params.items()}, feed, feed,
        decode_coords(pool, [None] * engine.slots, [1] * engine.slots),
        None)
    is_cumsum = lambda e: e.primitive.name == 'cumsum'
    is_while = lambda e: e.primitive.name == 'while'
    assert _count_eqns(step.jaxpr, is_cumsum) == 1
    assert _count_eqns(step.jaxpr, is_while) == lm.cfg.num_hidden_layers
    inputs, attrs, _ = _walked_batch(np.random.RandomState(13), [5, 0, 40],
                                     per_slot=4)
    alone = jax.make_jaxpr(
        lambda *a: llm_ops.mla_decode_attention(*a, **attrs))(*inputs)
    assert _count_eqns(alone.jaxpr, is_cumsum) == 1
    assert _count_eqns(alone.jaxpr, is_while) == 1


def test_prefill_attention_in_chunks_is_the_unchunked_one(monkeypatch):
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 16, 2, 12), jnp.float32)
    latent = jnp.asarray(rng.randn(2, 16, 20), jnp.float32)
    w_kvb = jnp.asarray(rng.randn(16, 2 * 14) * 0.3, jnp.float32)
    attrs = dict(qk_nope_dim=8, v_dim=6, sm_scale=0.3)
    whole = llm_ops.mla_prefill_attention(q, latent, w_kvb, **attrs)
    monkeypatch.setattr(llm_ops, '_PREFILL_QUERY_CHUNK', 4)
    np.testing.assert_allclose(
        np.asarray(llm_ops.mla_prefill_attention(q, latent, w_kvb, **attrs)),
        np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_grouped_experts_drop_no_token_under_a_skewed_routing():
    """Against a dense loop over the experts, with most tokens on one
    expert and one expert empty; the counts add up to tokens x k."""
    rng = np.random.RandomState(4)
    t, h, f, e, k = 24, 16, 8, 6, 3
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    gate, up = (jnp.asarray(rng.randn(e, h, f) * 0.3, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.randn(e, f, h) * 0.3, jnp.float32)
    ids = np.stack([rng.permutation(e - 1)[:k] for _ in range(t)])  # no 5
    ids[:18, 0] = 0                                                 # skew
    ids[:18, 1:] = np.stack([1 + rng.permutation(e - 2)[:k - 1]
                             for _ in range(18)])
    weights = jnp.asarray(rng.rand(t, k), jnp.float32)
    out, counts = llm_ops.moe_experts(x, jnp.asarray(ids, jnp.int32),
                                      weights, gate, up, down)
    counts = np.asarray(counts)
    assert counts.sum() == t * k and counts[5] == 0 and counts[0] >= 18
    assert counts.tolist() == np.bincount(ids.ravel(), minlength=e).tolist()
    want = np.zeros((t, h), np.float32)
    for j in range(e):
        y = np.asarray(llm_ops.swiglu_ffn(x, gate[j], up[j], down[j]))
        want += y * (np.asarray(weights) * (ids == j)).sum(1)[:, None]
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


def test_the_router_chooses_by_s_plus_b_and_weighs_by_s():
    x = jnp.eye(4, dtype=jnp.float32)
    logits = np.array([[2.0, 1.0, 0.0, -1.0, -2.0]] * 4, np.float32)
    bias = np.array([0.0, 0.0, 0.0, 0.0, 1.0], np.float32)   # lifts expert 4
    ids, w = llm_ops.moe_router(x, jnp.asarray(logits), bias, top_k=2,
                                routed_scaling_factor=2.448)
    s = 1 / (1 + np.exp(-logits[0]))
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 4]   # s + b: 1.12 > .73
    got = dict(zip(np.asarray(ids)[0].tolist(), np.asarray(w)[0].tolist()))
    for j in (0, 4):        # weights from the unbiased s, over the chosen
        assert got[j] == pytest.approx(2.448 * s[j] / (s[0] + s[4]), rel=1e-6)
    assert np.asarray(ids).dtype == np.int32 and np.asarray(w).dtype \
        == np.float32
    _, plain = llm_ops.moe_router(x, jnp.asarray(logits), bias, top_k=2,
                                  norm_topk_prob=False)
    assert sorted(np.asarray(plain)[0].tolist()) == pytest.approx(
        sorted([s[0], s[4]]), rel=1e-6)


def test_rope_turns_interleaved_pairs_and_leaves_the_nope_lanes():
    rng = np.random.RandomState(5)
    x = rng.randn(1, 3, 2, 10).astype(np.float32)
    pos = np.array([[0, 1, 7]])
    got = np.asarray(llm_ops.rope(x, pos, theta=100.0, nope_dim=6))
    np.testing.assert_array_equal(got[..., :6], x[..., :6])
    np.testing.assert_allclose(got[0, 0], x[0, 0], atol=1e-7)  # position 0
    for i in range(2):                          # pairs (6, 7) and (8, 9)
        ang = 7 * 100.0 ** (-2 * i / 4)
        a, b = x[0, 2, :, 6 + 2 * i], x[0, 2, :, 7 + 2 * i]
        np.testing.assert_allclose(got[0, 2, :, 6 + 2 * i],
                                   a * np.cos(ang) - b * np.sin(ang),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got[0, 2, :, 7 + 2 * i],
                                   a * np.sin(ang) + b * np.cos(ang),
                                   rtol=1e-5, atol=1e-6)
    # the dot product of two turned vectors depends on their distance alone
    q, k = rng.randn(1, 1, 4).astype(np.float32), rng.randn(1, 1, 4).astype(
        np.float32)
    dots = [float((np.asarray(llm_ops.rope(q, [[p]])) * np.asarray(
        llm_ops.rope(k, [[p - 3]]))).sum()) for p in (3, 30)]
    assert dots[0] == pytest.approx(dots[1], rel=1e-4)


def test_the_reference_follows_a_near_tie_and_nothing_wider(lm, params):
    """The check's reference takes the experts a system reports it chose
    and follows them only where its own scores call the choice a near-tie:
    a system that decided one near-tie the other way is far from the
    reference's own row and ON the followed one; a choice wider than
    `tie_margin` is not followed; weights of lower precision are far from
    every followed row."""
    rows = _rows(lm)
    cfg = lm.cfg
    ids = np.random.RandomState(6).randint(1, cfg.vocab_size, 14).tolist()
    position = 13
    engine = _engine(lm)
    table = engine.reserve_table(len(ids), 2)
    engine.prefill(ids, table)
    own = np.asarray(engine.last_stats['expert_ids'])[:, 0]   # (layers, k)
    engine.release_table(table)
    assert own.shape == (cfg.num_hidden_layers - cfg.first_k_dense_replace,
                         cfg.num_experts_per_tok)

    def ask(forced, tie_margin, weights=params):
        row, gaps = rows(weights, ids, [position], forced, tie_margin)
        return np.asarray(row)[0], np.asarray(gaps)[0]

    row, margins = ask(None, 0.0)
    scale = np.abs(row).max()
    # in float32 the system's choice IS the reference's: every gap is minus
    # the reference's own margin, and following it changes nothing
    same, gaps = ask({position: own}, 0.0)
    np.testing.assert_array_equal(same, row)
    np.testing.assert_array_equal(gaps, margins)
    assert (gaps < 0).all()
    # every other choice in the tightest layer, by its gap: the smallest is
    # the swap of the k-th and the (k+1)-th, whose gap is the margin
    layer = int(margins.argmax())
    others = []
    for slot in range(cfg.num_experts_per_tok):
        for expert in set(range(cfg.n_routed_experts)) - set(own[layer]):
            choice = own.copy()
            choice[layer, slot] = expert
            others.append((float(ask({position: choice}, 1.0)[1][layer]),
                           choice))
    others.sort(key=lambda other: other[0])
    (tie, near), (wide, far) = others[0], others[-1]
    assert tie == pytest.approx(-margins[layer], rel=1e-4) and wide > 2 * tie
    followed, gaps = ask({position: near}, tie * 1.01)
    assert gaps[layer] == pytest.approx(tie)
    assert np.abs(followed - row).max() > 1e-2 * scale     # the trap is real
    # a margin below the gap, or a choice far from a tie: the own row
    for choice, tie_margin in ((near, tie * 0.99), (far, tie * 1.01)):
        refused, gaps = ask({position: choice}, tie_margin)
        np.testing.assert_array_equal(refused, row)
        assert gaps[layer] > tie_margin
    # the row also moves with a choice followed at the position before it
    # (a later layer attends the row written there)
    before = {position - 1: (own + 1) % cfg.n_routed_experts}
    assert np.abs(ask(before, 0.0)[0] - row).max() == 0.0
    assert np.abs(ask(before, 9.0)[0] - row).max() > 1e-6 * scale
    # lower precision: far from the own row and from every followed one
    coarse = {n: v.astype(jnp.bfloat16).astype(v.dtype)
              for n, v in params.items()}
    got = ask(None, 0.0, coarse)[0]
    assert min(np.abs(got - r).max() for r in (row, followed)) > 1e-3 * scale


def test_parameters_are_created_and_kept_in_bfloat16():
    with guard():
        default_generator.seed(8)
        model = LatentMoELM(LatentMoEConfig.tiny(dtype='bfloat16'))
        model.eval()
        kept = {n: p.value.dtype for n, p in model.named_parameters()}
        assert {str(d) for n, d in kept.items()
                if not n.endswith('router_bias')} == {'bfloat16'}
        assert all(str(d) == 'float32' for n, d in kept.items()
                   if n.endswith('router_bias'))
        engine = _engine(model, kv_dtype='bf16')
        table = engine.reserve_table(5, 2)
        rows = []
        token = engine.prefill([9, 8, 7, 6, 5], table, sampler=lambda r: (
            rows.append(r), int(r.argmax()))[1])
        engine.decode_step([token, None], [table, None])
        # one program for the rung used and one for the step, as for any model
        assert engine.compiled_programs() == 2
        assert rows[0].dtype == np.float32 and np.isfinite(rows[0]).all()
        assert {str(a.dtype) for arrs in engine.pool.arrays()[0].values()
                for a in arrs} == {'bfloat16'}


@pytest.mark.parametrize('kwargs,named', [
    ({'prefix_cache': True}, 'prefix cache'),
    ({'kv_dtype': 'int8'}, 'int8'),
    ({'prefix_cache': True, 'kv_dtype': 'int8'}, 'int8')])
def test_a_latent_pool_refuses_pair_features_when_the_engine_is_built(
        lm, kwargs, named):
    with pytest.raises(UnsupportedCacheFeature, match=named):
        _engine(lm, **kwargs)


def test_the_replica_stack_refuses_disaggregation_over_a_latent_pool(lm):
    from paddle_tpu.serving.tier.replica import build_replica_stack
    with pytest.raises(UnsupportedCacheFeature, match='handoff'):
        build_replica_stack(model=lm, disagg=True, prefix_cache=False)
    engine, scheduler, worker = build_replica_stack(
        model=lm, slots=2, kv_dtype='bf16', prefix_cache=False,
        disagg=False)
    assert engine.pool.kv_dtype == 'bf16' and worker is None


def test_the_config_refuses_what_the_block_has_no_equations_for():
    with pytest.raises(ValueError, match='q_lora_rank'):
        LatentMoEConfig.tiny(q_lora_rank=1536)
    with pytest.raises(ValueError, match='n_group'):
        LatentMoEConfig.tiny(n_group=8)
    with pytest.raises(ValueError, match='unknown key'):
        LatentMoEConfig.tiny(hc_mult=4)
    cfg = LatentMoEConfig.from_published(
        dict(vars(LatentMoEConfig.tiny()), name='x', runner='y',
             q_lora_rank=None, head_dim=64), dtype='bfloat16')
    assert cfg.dtype == 'bfloat16' and cfg.hidden_size == 32


# -- analysis rules ----------------------------------------------------------

def _infer_and_cost(op_type, inputs, in_slots, out_slots, attrs):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.analysis.cost import op_cost
    from paddle_tpu.analysis.infer import VarInfo, infer_op
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        blk = main.global_block()
        env = {}
        for name, (shape, dtype) in inputs.items():
            blk.create_var(name=name, shape=shape, dtype=dtype)
            env[name] = VarInfo(shape, dtype)
        op = blk.append_op(op_type, inputs=in_slots,
                           outputs={s: [s.lower()] for s in out_slots},
                           attrs=attrs)
        out = infer_op(op, env, blk)
        for slot in out_slots:
            env[slot.lower()] = out[slot]
        return out, op_cost(op, env, blk)


T, H, F, E, K = 6, 16, 8, 4, 2
HEADS, NOPE, ROPE, V, RANK = 2, 8, 4, 6, 12
_MLA = dict(qk_nope_dim=NOPE, v_dim=V, sm_scale=0.3)
RULES = {
    'rms_norm': (dict(x=((T, H), 'bfloat16'), s=((H,), 'bfloat16')),
                 dict(x=['x'], scale=['s']), {}, {'Out': ((T, H), 'bfloat16')},
                 4 * T * H),
    'rope': (dict(x=((1, T, HEADS, NOPE + ROPE), 'float32'),
                  p=((1, T), 'int64')), dict(x=['x'], pos=['p']),
             dict(theta=1e6, nope_dim=NOPE),
             {'Out': ((1, T, HEADS, NOPE + ROPE), 'float32')},
             11 * T * HEADS * (NOPE + ROPE)),
    'lm_head': (dict(x=((T, H), 'bfloat16'), w=((H, 50), 'bfloat16')),
                dict(x=['x'], w=['w']), {}, {'Out': ((T, 50), 'float32')},
                2 * T * H * 50),
    'swiglu_ffn': (dict(x=((T, H), 'float32'), g=((H, F), 'float32'),
                        u=((H, F), 'float32'), d=((F, H), 'float32')),
                   dict(x=['x'], w_gate=['g'], w_up=['u'], w_down=['d']), {},
                   {'Out': ((T, H), 'float32')}, 6 * T * H * F + 9 * T * F),
    'moe_router': (dict(x=((T, H), 'bfloat16'), g=((H, E), 'bfloat16'),
                        b=((E,), 'float32')),
                   dict(x=['x'], w_gate=['g'], bias=['b']), dict(top_k=K),
                   {'Ids': ((T, K), 'int32'), 'Weights': ((T, K), 'float32')},
                   2 * T * H * E + 8 * T * E),
    'moe_experts': (dict(x=((T, H), 'bfloat16'), i=((T, K), 'int32'),
                         w=((T, K), 'float32'), g=((E, H, F), 'bfloat16'),
                         u=((E, H, F), 'bfloat16'), d=((E, F, H), 'bfloat16')),
                    dict(x=['x'], ids=['i'], weights=['w'], w_gate=['g'],
                         w_up=['u'], w_down=['d']), {},
                    {'Out': ((T, H), 'bfloat16'), 'Counts': ((E,), 'int32')},
                    T * K * (6 * H * F + 9 * F + 2 * H)),
    'mla_prefill_attention': (
        dict(q=((1, T, HEADS, NOPE + ROPE), 'bfloat16'),
             l=((1, T, RANK + ROPE), 'bfloat16'),
             w=((RANK, HEADS * (NOPE + V)), 'bfloat16')),
        dict(q=['q'], latent=['l'], w_kvb=['w']), _MLA,
        {'Out': ((1, T, HEADS * V), 'bfloat16')},
        2 * T * RANK * HEADS * (NOPE + V)
        + HEADS * T * T * (2 * (NOPE + ROPE) + 2 * V + 10)),
    'mla_decode_attention': (
        dict(q=((3, 1, HEADS, NOPE + ROPE), 'bfloat16'),
             p=((9, 4, RANK + ROPE), 'bfloat16'), t=((3, 5), 'int32'),
             c=((3,), 'int32'), w=((RANK, HEADS * (NOPE + V)), 'bfloat16')),
        # `live` left out: the op makes the list of live groups itself
        dict(q=['q'], pages=['p'], block_tables=['t'], context_lens=['c'],
             w_kvb=['w'], live=[]), _MLA,
        {'Out': ((3, 1, HEADS * V), 'bfloat16')},
        3 * HEADS * (2 * NOPE * RANK + 2 * RANK * V
                     + 20 * (2 * (RANK + ROPE) + 2 * RANK + 10))),
}


@pytest.mark.parametrize('op_type', sorted(RULES))
def test_every_new_op_has_an_infer_rule_and_a_cost_rule(op_type):
    from paddle_tpu.analysis import has_cost_rule
    from paddle_tpu.analysis.infer import has_rule
    from paddle_tpu.ops.registry import get_op
    inputs, in_slots, attrs, outs, flops = RULES[op_type]
    assert has_rule(op_type) and has_cost_rule(op_type)
    assert set(in_slots) == set(get_op(op_type).input_slots)
    out, cost = _infer_and_cost(op_type, inputs, in_slots, list(outs), attrs)
    for slot, (shape, dtype) in outs.items():
        assert tuple(out[slot].shape) == shape and out[slot].dtype == dtype
    assert cost.flops == flops
    assert cost.bytes_in > 0 and cost.bytes_out > 0
    # the rule and the kernel agree on shapes and dtypes
    rng = np.random.RandomState(0)
    args = []
    for slot in get_op(op_type).input_slots:
        if not in_slots[slot]:
            args.append(None)
            continue
        shape, dtype = inputs[in_slots[slot][0]]
        if dtype.startswith('int'):
            args.append(np.zeros(shape, dtype) if slot != 'context_lens'
                        else np.ones(shape, dtype))
        else:
            args.append(jnp.asarray(rng.randn(*shape), dtype))
    got = get_op(op_type).fn(*args, **attrs)
    got = got if isinstance(got, tuple) else (got,)
    for value, (shape, dtype) in zip(got, outs.values()):
        assert value.shape == shape and str(value.dtype) == dtype


@pytest.mark.parametrize('op_type,change,match', [
    ('swiglu_ffn', dict(g=((H + 1, F), 'float32')), 'contraction'),
    ('moe_router', dict(b=((E + 1,), 'float32')), 'bias'),
    ('mla_decode_attention', dict(p=((9, 4), 'bfloat16')), 'rank 3'),
    ('mla_prefill_attention', dict(w=((RANK, 7), 'bfloat16')), 'w_kvb'),
    ('lm_head', dict(w=((H + 2, 50), 'bfloat16')), 'contraction')])
def test_infer_rules_refuse_shapes_that_cannot_agree(op_type, change, match):
    from paddle_tpu.analysis.infer import InferError
    inputs, in_slots, attrs, outs, _ = RULES[op_type]
    with pytest.raises(InferError, match=match):
        _infer_and_cost(op_type, dict(inputs, **change), in_slots,
                        list(outs), attrs)


# -- the pool-size solve asks the model --------------------------------------

@pytest.mark.parametrize('kind', ['latent', 'kv'])
def test_the_budget_solve_prices_what_the_model_caches(lm, kind):
    from paddle_tpu.serving.decode.layout import (decode_pool_report,
                                                  solve_decode_pool_blocks)
    if kind == 'latent':
        model, per_token = lm, {'f32': 128 * 4, 'bf16': 128 * 2}
        with pytest.raises(ValueError, match='int8'):
            lm.cache_layout().block_bytes(4, 'int8')
    else:
        from paddle_tpu.serving.tier.replica import build_tiny_lm
        with guard():
            model = build_tiny_lm()
        # a K and a V row of 2 heads x 16 in the 128 lanes the pool gives
        # them; int8 with an f32 scale a head
        heads, lanes = 2, row_lanes(2 * 16)
        per_token = {'f32': 2 * lanes * 4, 'bf16': 2 * lanes * 2,
                     'int8': 2 * (lanes + 4 * heads)}
    layers = model.cfg.num_hidden_layers
    state = sum(p.value.nbytes for p in model.parameters())
    for dtype, row in per_token.items():
        assert model.cache_layout().block_bytes(4, dtype) \
            == layers * 4 * row
        blocks = solve_decode_pool_blocks(model, 8, block_size=4,
                                          kv_dtype=dtype)
        assert blocks == ((8 << 20) - state) // (layers * 4 * row)
        report = decode_pool_report(model, 8, block_size=4, kv_dtype=dtype)
        assert report['row_bytes'] == row
        assert report['kv_cache'] == kind
    # what the engine's pool then holds is what the solve priced
    engine = DecodeEngine(model, slots=2, block_size=4, max_blocks=32,
                          max_prompt_len=8, max_new_tokens_cap=4,
                          prefix_cache=False, kv_dtype='bf16')
    engine.warmup()
    assert engine.pool.bytes_in_hbm() == 32 * model.cache_layout(
        ).block_bytes(4, 'bf16')

    class Bare:
        def parameters(self):
            return []

    with pytest.raises(ValueError, match='cache_layout'):
        solve_decode_pool_blocks(Bare(), 8, block_size=4)


def test_the_scopes_reach_the_compiled_programs_op_names(lm):
    """What benchmark/lib/scoped_ops.py sums device time by: after XLA has
    inlined every dispatch's own jit, the matmuls of the experts and of the
    decode read still carry their scope in `op_name` (a scope put inside the
    op's function does not: ops/llm_ops.py)."""
    import re
    from paddle_tpu.serving.decode.engine import _Program
    from paddle_tpu.serving.decode.kv_cache import decode_coords
    engine = _engine(lm)
    table = engine.reserve_table(6, 2)
    table.context_len = 6
    coords = decode_coords(engine.pool, [table, None], [6, 1])
    program = _Program.of(lm)
    pvals = {n: p.value for n, p in lm.named_parameters()}
    lanes = row_lanes(lm.cfg.latent_row_width)
    layers = {i: [jnp.zeros((64, 4, lanes), jnp.float32)]
              for i in range(lm.cfg.num_hidden_layers)}
    text = program.jitted.lower(
        'decode', engine.pool.geometry, pvals, {}, layers, {},
        np.zeros((2, 1), np.int32), np.zeros((2, 1), np.int32), coords,
        None).compile().as_text()
    engine.release_table(table)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    # (the CPU runs the op's ragged_dot formulation, lowered to a masked
    # product: its sort is the witness; compiled for the TPU the experts are
    # two pallas custom calls a layer that carry the scope:
    # test_kv_pool_layout.py::
    # test_compiled_for_the_chip_the_experts_run_the_grouped_kernel)
    for scope, op in (('moe/experts', 'sort'),
                      ('moe/shared', 'dot_general'),
                      ('moe/route', 'top_k'),
                      ('mla/decode_read', 'dot_general')):
        assert any(f'/{scope}/' in n and op in n for n in names), (scope, op)
    assert not any('mla/prefill_attention' in n for n in names)
