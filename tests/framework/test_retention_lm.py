"""The brumby-family decoder (models/retention_lm.py) at a small size on the
CPU: against the plain reference of the benchmark
(benchmark/reference/brumby_14b.py, the quadratic form in float32 at
precision "highest", imports nothing of paddle_tpu), whole sequence and
through the decode engine's state cache, across chunk and rung boundaries
and two chunks' worth of steps; what belongs to whom in the state cache;
what a state cache refuses; the analysis rules and the budget solve."""
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.random import default_generator
from paddle_tpu.dygraph import guard
from paddle_tpu.dygraph.tape import Tensor, no_grad_guard
from paddle_tpu.models.retention_lm import RetentionLM, RetentionLMConfig
from paddle_tpu.ops.llm_ops import retention_state_rows
from paddle_tpu.serving.decode import DecodeEngine
from paddle_tpu.serving.errors import (OutOfBlocks, OutOfStateRows,
                                       UnsupportedCacheFeature)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))


def _reference():
    spec = importlib.util.spec_from_file_location(
        'reference_brumby', os.path.join(
            REPO, 'benchmark', 'reference', 'brumby_14b.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
CHUNK = 4
PROW = retention_state_rows(8)[2]


def _config_file(cfg):
    """The configuration file's shape: published keys at the top level,
    the program's own under `model`."""
    return dict(vars(cfg), model={'gate_shift': cfg.gate_shift})


@pytest.fixture(scope='module')
def lm():
    with guard():
        default_generator.seed(11)
        model = RetentionLM(RetentionLMConfig.tiny())
        model.eval()
        yield model


@pytest.fixture(scope='module')
def params(lm):
    return {n: p.value for n, p in lm.named_parameters()}


@pytest.fixture(scope='module')
def rows(lm):
    return REF.make_rows(_config_file(lm.cfg), 48)


def _engine(model, **kw):
    kw.setdefault('slots', 3)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 16)
    kw.setdefault('max_new_tokens_cap', 12)
    kw.setdefault('prompt_buckets', [4, 8, 16])
    kw.setdefault('prefix_cache', False)
    return DecodeEngine(model, **kw)


def _grab(into):
    def sampler(row):
        into.append(np.array(row))
        return int(row.argmax())
    return sampler


def _close(got, want, tolerance=1e-4):
    return np.abs(got - want).max() < tolerance * np.abs(want).max()


def test_the_tiny_preset_has_grouped_heads_and_gates_near_one(lm):
    cfg = lm.cfg
    assert cfg.num_attention_heads // cfg.num_key_value_heads == 3
    assert cfg.prefill_chunk == CHUNK and cfg.gate_shift == 3.0
    names = {n for n, _ in lm.named_parameters()}
    assert {'layers.0.attn.q_norm.weight', 'layers.0.attn.k_norm.weight',
            'layers.0.attn.gate.weight', 'layers.2.ffn.down.weight',
            'head.weight', 'embed.weight'} <= names
    from paddle_tpu.serving.decode.layout import CacheLayout, LayerCache
    assert lm.cache_layout() == CacheLayout(
        (LayerCache.state((2, PROW, 8), 'retention'),) * 3)


def test_whole_sequence_agrees_with_the_reference(lm, params, rows):
    ids = np.random.RandomState(0).randint(1, lm.cfg.vocab_size, (2, 21))
    with no_grad_guard():
        got = lm(Tensor(ids, stop_gradient=True)).numpy()
    assert got.dtype == np.float32 and got.shape == (2, 21, 96)
    for b in range(2):
        assert _close(got[b], np.asarray(rows(params, ids[b].tolist(),
                                              range(21))))


def test_the_reference_tells_a_gate_shift_and_a_group_apart(lm, params):
    """The comparison has teeth: a reference at another gate constant, or
    one that reads the wrong key/value head, is far from the model."""
    ids = np.random.RandomState(2).randint(1, lm.cfg.vocab_size, 21)
    with no_grad_guard():
        got = lm(Tensor(ids[None], stop_gradient=True)).numpy()[0]
    shifted = dict(_config_file(lm.cfg), model={'gate_shift': 0.0})
    want = np.asarray(REF.make_rows(shifted)(params, ids.tolist(),
                                             range(21)))
    assert not _close(got, want, 1e-2)
    swapped = dict(params)
    k = np.asarray(params['layers.0.attn.k_proj.weight'])
    swapped['layers.0.attn.k_proj.weight'] = jnp.asarray(
        np.concatenate([k[:, 8:], k[:, :8]], 1))
    want = np.asarray(REF.make_rows(_config_file(lm.cfg))(
        swapped, ids.tolist(), range(21)))
    assert not _close(got, want, 1e-2)


@pytest.mark.parametrize('lengths', [(3, 7, 13), (4, 8, 16), (5, 10, 11)])
def test_prefill_then_decode_through_the_state_cache(lm, params, rows,
                                                     lengths):
    """Prompts that are no multiple of the chunk (and some that are), on
    every rung, in neighbouring slots; then nine lockstep steps, more than
    two chunks' worth: every row is the reference's whole forward over the
    prompt and the system's own tokens."""
    engine = _engine(lm)
    rng = np.random.RandomState(sum(lengths))
    prompts = [rng.randint(1, lm.cfg.vocab_size, n).tolist()
               for n in lengths]
    tables, seqs, got = [], [], [[] for _ in prompts]
    for i, prompt in enumerate(prompts):
        table = engine.reserve_table(len(prompt), 10)
        token = engine.prefill(prompt, table, sampler=_grab(got[i]))
        tables.append(table)
        seqs.append(prompt + [token])
    assert sorted(t.state_row for t in tables) == [1, 2, 3]
    steps = 2 * CHUNK + 1
    for _ in range(steps):
        ids, step_rows = engine.decode_step([s[-1] for s in seqs], tables,
                                            return_rows=True)
        for i in range(len(seqs)):
            got[i].append(np.array(step_rows[i]))
            seqs[i].append(int(ids[i]))
    for i, prompt in enumerate(prompts):
        n = len(prompt)
        want = np.asarray(rows(params, seqs[i], range(n - 1, n + steps)))
        for j, (g, w) in enumerate(zip(got[i], want)):
            assert _close(g, w), (lengths, i, j)
    # one float32 array a layer over slots + 1 rows; no K/V rows anywhere
    layers, scales = engine.pool.arrays()
    assert len(layers) == lm.cfg.num_hidden_layers and not scales
    for arrs in layers.values():
        assert [(a.shape, str(a.dtype)) for a in arrs] == [
            ((4, 2, PROW, 8), 'float32')]
    assert engine.layout.kind == 'state'
    assert engine.layout.state_layers == 3
    assert engine.layout.row_layers == 0
    assert engine.pool.bytes_in_hbm() == 0 and engine.pool.row_bytes() == 0
    assert engine.pool.state_bytes_in_hbm() == 3 * 4 * 2 * PROW * 8 * 4
    for table in tables:
        engine.release_table(table)


def test_neighbours_idle_slots_and_a_reused_row_change_nothing(lm, params,
                                                               rows):
    """A request's rows are the same alone as between two neighbours that
    come and go; the row a finished request gave back serves the next one
    clean; idle slots advance the scratch row alone."""
    rng = np.random.RandomState(9)
    prompt = rng.randint(1, lm.cfg.vocab_size, 6).tolist()
    others = [rng.randint(1, lm.cfg.vocab_size, n).tolist() for n in (9, 3)]

    def alone():
        engine = _engine(lm)
        table, got = engine.reserve_table(6, 8), []
        seq = [engine.prefill(prompt, table, sampler=_grab(got))]
        for _ in range(6):
            ids, step_rows = engine.decode_step(
                [None, seq[-1], None], [None, table, None], return_rows=True)
            got.append(np.array(step_rows[1]))
            seq.append(int(ids[1]))
        return engine, seq, got

    engine, seq, want = alone()
    assert all(_close(g, w) for g, w in zip(want, np.asarray(rows(
        params, prompt + seq, range(5, 12)))))
    scratch = [np.asarray(arrs[0])[0] for arrs in
               engine.pool.arrays()[0].values()]
    assert all(np.abs(s).max() > 0 for s in scratch)    # idle slots wrote it

    engine = _engine(lm)
    left = engine.reserve_table(9, 8)
    mine = engine.reserve_table(6, 8)
    right = engine.reserve_table(3, 8)
    assert (left.state_row, mine.state_row, right.state_row) == (1, 2, 3)
    got = []
    feed = [engine.prefill(others[0], left),
            engine.prefill(prompt, mine, sampler=_grab(got)),
            engine.prefill(others[1], right)]
    tables = [left, mine, right]
    for step in range(6):
        if step == 2:            # the left neighbour finishes: slot idle
            engine.release_table(left)
            tables[0], feed[0] = None, None
        if step == 4:            # another request takes its row and slot
            again = engine.reserve_table(3, 8)
            assert again.state_row == 1
            tables[0], feed[0] = again, engine.prefill(others[1], again)
        ids, step_rows = engine.decode_step(feed, tables, return_rows=True)
        got.append(np.array(step_rows[1]))
        feed = [None if t is None else int(i) for t, i in zip(tables, ids)]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5 * np.abs(w).max())
    # the reused row holds the new request's state and nothing of the old:
    # its two steps read as that prompt's alone do
    fresh = _engine(lm)
    table, first = fresh.reserve_table(3, 8), []
    token = fresh.prefill(others[1], table, sampler=_grab(first))
    _, step_rows = fresh.decode_step([token, None, None],
                                     [table, None, None], return_rows=True)
    reused = _engine(lm)
    old = reused.reserve_table(9, 8)
    assert old.state_row == 1
    reused.prefill(others[0], old)
    reused.release_table(old)
    table2 = reused.reserve_table(3, 8)
    assert table2.state_row == 1          # the row the old request held
    token2 = reused.prefill(others[1], table2)
    _, step_rows2 = reused.decode_step([token2, None, None],
                                       [table2, None, None],
                                       return_rows=True)
    assert token2 == token
    np.testing.assert_array_equal(step_rows2[0], step_rows[0])


def test_a_rungs_padding_never_enters_the_state(lm):
    """The same prompt on a wider rung (more padded rows) leaves the same
    state row and the same rows."""
    prompt = [5, 9, 2, 44, 17]
    seen = []
    for buckets in ([8, 16], [16]):
        engine = _engine(lm, prompt_buckets=buckets)
        table, got = engine.reserve_table(5, 4), []
        token = engine.prefill(prompt, table, sampler=_grab(got))
        _, step_rows = engine.decode_step(
            [token, None, None], [table, None, None], return_rows=True)
        state = [np.asarray(arrs[0])[table.state_row]
                 for arrs in engine.pool.arrays()[0].values()]
        seen.append((got[0], np.array(step_rows[0]), state))
    for a, b in zip(seen[0][:2], seen[1][:2]):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.abs(a).max())
    for a, b in zip(seen[0][2], seen[1][2]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_admission_waits_for_a_state_row_as_for_blocks(lm):
    engine = _engine(lm, slots=2)
    first = engine.reserve_table(4, 4)
    second = engine.reserve_table(4, 4)
    used = engine.pool.allocator.used
    with pytest.raises(OutOfStateRows) as caught:
        engine.reserve_table(4, 4)
    assert isinstance(caught.value, OutOfBlocks)       # the scheduler waits
    assert engine.pool.allocator.used == used          # and nothing leaked
    engine.release_table(first)
    third = engine.reserve_table(4, 4)
    assert third.state_row == 1           # the row the first gave back
    # out of blocks with a row free: the row goes back
    small = _engine(lm, slots=3, max_blocks=15)
    held = [small.reserve_table(16, 12), small.reserve_table(16, 12)]
    with pytest.raises(OutOfBlocks):
        small.reserve_table(16, 12)
    assert small.pool.state_rows.used == 2
    for table in held + [second, third]:
        (small if table in held else engine).release_table(table)
    assert engine.pool.state_rows.used == 0


def test_the_scheduler_serves_requests_through_the_state_cache(lm, params,
                                                               rows):
    """More requests than slots through build_replica_stack and the
    scheduler: every answer is the greedy continuation the reference
    gives."""
    from paddle_tpu.serving.tier.replica import build_replica_stack
    engine, scheduler, _ = build_replica_stack(
        model=lm, slots=2, block_size=4, max_blocks=64, max_prompt_len=16,
        max_new_tokens_cap=8, prompt_buckets=[8, 16], prefix_cache=False,
        disagg=False, spec_decode=False)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, lm.cfg.vocab_size, n).tolist()
               for n in (5, 12, 7, 9, 3)]
    try:
        streams = [scheduler.submit(p, max_new_tokens=5) for p in prompts]
        answers = [s.result(timeout=120) for s in streams]
    finally:
        scheduler.close()
    for prompt, answer in zip(prompts, answers):
        assert len(answer) == 5
        seq = list(prompt)
        for token in answer:
            want = np.asarray(rows(params, seq, [len(seq) - 1]))[0]
            # greedy, and no near-tie in these draws
            assert int(want.argmax()) == token
            seq.append(token)
    assert engine.pool.state_rows.used == 0
    assert engine.pool.allocator.used == 0


@pytest.mark.parametrize('asked,named', [
    (dict(prefix_cache=True), 'prefix cache'),
    (dict(spec_decode=True), 'speculative'),
    (dict(kv_dtype='bf16'), 'kv_dtype=bf16'),
    (dict(kv_dtype='int8'), 'kv_dtype=int8')])
def test_a_state_cache_refuses_what_it_cannot_hold(lm, asked, named):
    with pytest.raises(UnsupportedCacheFeature, match=named) as caught:
        _engine(lm, **asked)
    assert 'state cache' in str(caught.value)
    assert 'Recurrent state' in str(caught.value)
    assert caught.value.kind == 'state'


def test_the_handoff_is_refused_where_its_prefill_role_is_built(lm):
    from paddle_tpu.serving.tier.disagg import PrefillReplica
    from paddle_tpu.serving.tier.replica import build_replica_stack
    with pytest.raises(UnsupportedCacheFeature, match='handoff') as caught:
        PrefillReplica(_engine(lm, slots=1))
    assert 'state cache' in str(caught.value)
    with pytest.raises(UnsupportedCacheFeature, match='handoff'):
        build_replica_stack(model=lm, slots=2, block_size=4, max_blocks=64,
                            prefix_cache=False, disagg=True)


def test_a_window_of_tokens_is_refused_by_the_state_layer(lm):
    engine = _engine(lm)
    table = engine.reserve_table(4, 8)
    token = engine.prefill([3, 4, 5, 6], table)
    with pytest.raises(UnsupportedCacheFeature, match='window'):
        engine.spec_step([[token, 4], None, None], [table, None, None])


def test_no_engine_program_moves_or_copies_a_state_array(lm):
    engine = _engine(lm)
    table = engine.reserve_table(5, 4)
    engine.prefill([3, 5, 7, 9, 11], table)          # allocates the states
    layers, _ = engine.pool.arrays()
    for bucket in (None, 8):
        lowered = engine.lowered(bucket)
        # donation held: every state argument is aliased to a result
        assert lowered.as_text().count('tf.aliasing_output') == len(layers)
        assert engine.pool_moves(bucket) == []
    # one executable for the step, whichever rows the slots hold
    engine.decode_step([1, None, None], [table, None, None])
    programs = engine.compiled_programs()
    engine.decode_step([1, 2, None], [table, engine.reserve_table(3, 3),
                                      None])
    assert engine.compiled_programs() == programs


def test_the_engine_books_state_updates_and_tokens_folded(lm):
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import metrics as m
    engine = _engine(lm)
    names = ('decode_state_updates', 'decode_state_tokens_folded',
             'decode_context_positions_read', 'decode_kv_blocks_read')
    with obs.telemetry_guard(True):
        obs.reset()
        before = {k: getattr(m, k).value for k in names}
        a, b = engine.reserve_table(6, 2), engine.reserve_table(3, 2)
        feed = [engine.prefill([3, 4, 5, 6, 7, 8], a), None,
                engine.prefill([9, 8, 7], b)]
        engine.decode_step(feed, [a, None, b])
        events = obs.tracer.snapshot()['traceEvents']
        after = {k: getattr(m, k).value - before[k] for k in names}
        gauges = {k: getattr(m, k).value for k in (
            'state_cache_bytes_in_hbm', 'state_cache_rows_total',
            'state_cache_rows_used', 'kv_cache_row_bytes')}
        obs.reset()
    # live tokens and live slots alone: 6 + 3 prompt tokens of two 8-row
    # rungs, two of three slots, over three state layers
    assert after == {'decode_state_updates': 2 * 3,
                     'decode_state_tokens_folded': (6 + 3) * 3,
                     'decode_context_positions_read': 0,
                     'decode_kv_blocks_read': 0}
    assert engine._blocks_walked([7, 1, 4]) == 0
    spans = {e['name']: e.get('args') or {} for e in events
             if e.get('ph') == 'X' and e['name'] in ('engine/prefill',
                                                     'engine/step')}
    assert spans['engine/prefill']['state_tokens_folded'] == 3 * 3
    assert spans['engine/prefill']['prompt_len'] == 3
    assert spans['engine/step']['state_updates'] == 6
    assert spans['engine/step']['kv_blocks'] == 0
    assert gauges == {'state_cache_bytes_in_hbm': 3 * 4 * 2 * PROW * 8 * 4,
                      'state_cache_rows_total': 3,
                      'state_cache_rows_used': 2, 'kv_cache_row_bytes': 0}
    engine.release_table(a)
    assert m.state_cache_rows_used.value == 1
    engine.release_table(b)


def test_from_published_takes_the_catalog_keys_and_refuses_the_rest():
    published = {
        'attention_bias': False, 'head_dim': 128, 'hidden_act': 'silu',
        'hidden_size': 5120, 'intermediate_size': 17408,
        'max_position_embeddings': 32768, 'max_window_layers': 40,
        'model_type': 'brumby', 'num_attention_heads': 40,
        'num_hidden_layers': 40, 'num_key_value_heads': 8,
        'rms_norm_eps': 1e-06, 'rope_scaling': None, 'rope_theta': 1000000,
        'sliding_window': None, 'tie_word_embeddings': False,
        'use_sliding_window': False, 'vocab_size': 151936}
    cfg = RetentionLMConfig.from_published(
        dict(published, name='x', check={}), gate_shift=6.0,
        dtype='bfloat16')
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (5120, 40, 8, 128)
    assert cfg.gate_shift == 6.0 and cfg.prefill_chunk == 256
    for key, value in (('sliding_window', 4096), ('rope_scaling', {'f': 2}),
                       ('tie_word_embeddings', True),
                       ('attention_bias', True)):
        with pytest.raises(ValueError, match=key):
            RetentionLMConfig.from_published(dict(published, **{key: value}))
    with pytest.raises(ValueError, match='divide'):
        RetentionLMConfig.from_published(dict(published,
                                              num_key_value_heads=7))


def test_parameters_are_kept_in_the_dtype_asked_for():
    with guard():
        model = RetentionLM(RetentionLMConfig.tiny(dtype='bfloat16',
                                                   num_hidden_layers=1))
        assert {str(p.value.dtype) for p in model.parameters()} == {
            'bfloat16'}
        ids = np.arange(1, 9)[None]
        with no_grad_guard():
            out = model(Tensor(ids, stop_gradient=True)).numpy()
        assert out.dtype == np.float32 and np.isfinite(out).all()


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


T, S, H, G, D = 16, 4, 6, 2, 8
BIG = D * (D + 1) // 2
_QKV = dict(q=['q'], k=['k'], v=['v'], log_gate=['a'])
RULES = {
    'retention_gate': (
        dict(x=((1, T, 32), 'bfloat16'), w=((32, G), 'bfloat16')),
        dict(x=['x'], w=['w']), dict(shift=6.0),
        {'Out': ((1, T, G), 'float32')}, 2 * T * 32 * G + 16 * T * G),
    'power_retention_prefill': (
        dict(q=((1, T, H, D), 'bfloat16'), k=((1, T, G, D), 'bfloat16'),
             v=((1, T, G, D), 'bfloat16'), a=((1, T, G), 'float32'),
             n=((), 'int32')),
        dict(_QKV, last=['n']), dict(chunk=4),
        {'Out': ((1, T, H * D), 'bfloat16'),
         'State': ((1, G, PROW, D), 'float32')},
        # every query over the 16 keys of the padded sequence
        T * (H * T * (2 * 17 + 11) + G * (2 * BIG + 2 * BIG * 9))),
    'power_retention_step': (
        dict(q=((S, 1, H, D), 'bfloat16'), k=((S, 1, G, D), 'bfloat16'),
             v=((S, 1, G, D), 'bfloat16'), a=((S, 1, G), 'float32'),
             s=((S + 1, G, PROW, D), 'float32'), r=((S,), 'int32')),
        dict(_QKV, state=['s'], rows=['r']), {},
        {'Out': ((S, 1, H * D), 'bfloat16'),
         'State': ((S + 1, G, PROW, D), 'float32')},
        S * ((H + G) * 2 * BIG + H * 2 * BIG * 9 + G * 3 * BIG * 9)),
}


@pytest.mark.parametrize('case', sorted(RULES))
def test_every_new_op_has_an_infer_rule_and_a_cost_rule(case):
    from paddle_tpu.analysis import has_cost_rule
    from paddle_tpu.analysis.infer import has_rule
    from paddle_tpu.ops.registry import get_op
    inputs, in_slots, attrs, outs, flops = RULES[case]
    op_type = case.split()[0]
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
        shape, dtype = inputs[in_slots[slot][0]]
        if dtype.startswith('int'):
            args.append(np.zeros(shape, dtype) + (3 if slot == 'last'
                                                  else 0))
        else:
            args.append(jnp.asarray(rng.randn(*shape), dtype))
    got = get_op(op_type).fn(*args, **attrs)
    got = got if isinstance(got, tuple) else (got,)
    for value, (shape, dtype) in zip(got, outs.values()):
        assert value.shape == shape and str(value.dtype) == dtype


@pytest.mark.parametrize('op_type,change,match', [
    ('retention_gate', dict(w=((31, G), 'bfloat16')), 'contraction'),
    ('power_retention_step', dict(k=((S, 1, 4, D), 'bfloat16')), 'disagree'),
    ('power_retention_step', dict(s=((S + 1, G, PROW + 8, D), 'float32')),
     'block'),
    ('power_retention_step', dict(r=((S + 1,), 'int32')), 'rows'),
    ('power_retention_prefill', dict(q=((1, T, 5, D), 'bfloat16')),
     'divide'),
    ('power_retention_prefill', dict(a=((1, T, 3), 'float32')), 'log_gate')])
def test_infer_rules_refuse_shapes_that_cannot_agree(op_type, change, match):
    from paddle_tpu.analysis.infer import InferError
    inputs, in_slots, attrs, outs, _ = RULES[op_type]
    with pytest.raises(InferError, match=match):
        _infer_and_cost(op_type, dict(inputs, **change), in_slots,
                        list(outs), attrs)


# -- the budget solve prices a state row -------------------------------------

def test_the_budget_solve_prices_a_state_row_per_slot(lm):
    from paddle_tpu.serving.decode.layout import (decode_pool_report,
                                                  solve_decode_pool_blocks,
                                                  solve_decode_state_slots)
    row = 3 * 2 * PROW * 8 * 4                 # layers x heads x P x d x 4
    assert lm.cache_layout().state_row_bytes() == row
    state = sum(int(p.value.nbytes) for p in lm.parameters())
    # slots + 1 rows fit the budget beside the weights
    budget_mb = -(-(state + 5 * row) // (1 << 20))
    slots = solve_decode_state_slots(lm, budget_mb)
    assert (slots + 1) * row <= (budget_mb << 20) - state < (slots + 2) * row
    assert slots >= 4
    with pytest.raises(ValueError, match='does not cover'):
        solve_decode_state_slots(lm, 0.001)
    # blocks book lengths and hold no memory: the block solve gives the
    # floor it was asked for and prices a block at nothing
    assert solve_decode_pool_blocks(lm, budget_mb, block_size=4,
                                    min_blocks=9) == 9
    doc = decode_pool_report(lm, budget_mb, block_size=4, min_blocks=9)
    assert doc['kv_cache'] == 'state'
    assert doc['block_bytes'] == 0 and doc['row_bytes'] == 0
    assert doc['state_row_bytes'] == row and doc['state_slots'] == slots
    with pytest.raises(ValueError, match='float32'):
        decode_pool_report(lm, budget_mb, block_size=4, kv_dtype='bf16')
    # a model whose cache is rows has no state row to price
    from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
    with guard():
        gpt = TransformerLM(CausalLMConfig.tiny())
    with pytest.raises(ValueError, match='state'):
        gpt.cache_layout().state_row_bytes()
