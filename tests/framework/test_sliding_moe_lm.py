"""The `afmoe` decoder (models/sliding_moe_lm.py) and the layer classes of
the K/V pool (serving/decode/kv_cache.py "Layer classes"): the system against
the plain reference (benchmark/reference/trinity_large_preview.py) on seeded
weights, whole-sequence and through the decode engine with a span of 8 and
blocks of 4, so that the ring comes round many times; the ring's
bookkeeping; the shares of an expert layer adding up to the uncut layer; and
what a sliding class refuses."""
import importlib.util
import os

import jax
import numpy as np
import pytest

from paddle_tpu import dygraph
from paddle_tpu.models.latent_moe_lm import LatentMoEConfig, RoutedExperts
from paddle_tpu.models.sliding_moe_lm import (SlidingMoEConfig, SlidingMoELM,
                                              span_mask_bias)
from paddle_tpu.serving.decode.engine import (SLIDING_SPARE_BLOCKS,
                                              DecodeEngine)
from paddle_tpu.serving.decode.kv_cache import (BlockTable, KVCachePool,
                                                decode_coords,
                                                prefill_coords)
from paddle_tpu.serving.decode.layout import (model_state_bytes,
                                              solve_decode_pool_blocks)
from paddle_tpu.serving.errors import OutOfBlocks, UnsupportedCacheFeature

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))
TOLERANCE = 2e-5        # of a row's largest logit: float32, another order
SPAN, BLOCK = 8, 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        'reference_trinity', os.path.join(
            REPO, 'benchmark', 'reference', 'trinity_large_preview.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _reference()


def _config(cfg):
    """The configuration-file form of a `SlidingMoEConfig`, as the
    reference reads it."""
    keys = ('vocab_size', 'hidden_size', 'intermediate_size',
            'moe_intermediate_size', 'num_hidden_layers', 'num_dense_layers',
            'num_attention_heads', 'num_key_value_heads', 'head_dim',
            'num_experts', 'num_experts_per_tok', 'sliding_window',
            'route_norm', 'route_scale', 'mup_enabled', 'rms_norm_eps',
            'rope_theta', 'router_width')
    config = {k: getattr(cfg, k) for k in keys}
    config['layer_types'] = list(cfg.layer_types)
    config['experts_held'] = list(cfg.experts_held or (0, cfg.num_experts))
    config['model'] = {}
    return config


def _model(seed=0, peaked=False, **overrides):
    from paddle_tpu.core.random import default_generator
    default_generator.seed(seed)
    model = SlidingMoELM(SlidingMoEConfig.tiny(**overrides))
    model.eval()
    if peaked:
        # queries 6x larger: attention is peaked, so that one key more or
        # less at a window's edge moves a row by far more than TOLERANCE
        for name, p in model.named_parameters():
            if name.endswith('q_proj.weight'):
                p.value = p.value * 6.0
    return model


def _params(model):
    return {n: p.value for n, p in model.named_parameters()}


def _want(model, ids, positions):
    rows = REFERENCE.make_rows(_config(model.cfg), REFERENCE.pad_of(len(ids)))
    with jax.default_matmul_precision('highest'):
        return np.asarray(rows(_params(model), ids, positions)[0])


def _worst(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def _engine(model, slots=3, **kw):
    kw.setdefault('max_blocks', slots * 14 + 8)
    return DecodeEngine(model, slots=slots, block_size=BLOCK,
                        max_prompt_len=32, max_new_tokens_cap=24,
                        prompt_buckets=[8, 16, 32], prefix_cache=False, **kw)


@pytest.mark.parametrize('held', [None, (0, 8), (2, 4)])
def test_whole_sequence_logits_equal_the_reference(held):
    with dygraph.guard():
        extra = {} if held is None else dict(
            experts_held=held, num_experts=held[1], router_width=8)
        model = _model(1, **extra)
        ids = np.random.RandomState(3).randint(1, 96, 29)
        got = model(dygraph.to_variable(ids[None])).numpy()[0]
    assert _worst(got, _want(model, ids.tolist(), list(range(29)))) \
        < TOLERANCE


def test_the_mask_bias_is_the_references_visible():
    for span in (0, 1, 5):
        bias = np.asarray(span_mask_bias(9, span))
        at = np.arange(9)
        assert ((bias == 0) == np.asarray(REFERENCE.visible(at, at, span))
                ).all()


@pytest.mark.parametrize('prompt_len', [3, 7, 8, 9, 21, 32])
def test_prefill_and_decode_through_both_classes_equal_the_reference(
        prompt_len):
    """A prompt shorter than, equal to and longer than the span, decoded
    for 14 steps through the ring (3 blocks of 4: it comes round every 12
    positions) with peaked attention: every row against the reference's
    whole-sequence forward over the system's own tokens."""
    with dygraph.guard():
        model = _model(2, peaked=True)
        eng = _engine(model)
        assert (eng.layout.span, eng.pool.ring) == (SPAN, SPAN // BLOCK + 1)
        rng = np.random.RandomState(prompt_len)
        prompt = rng.randint(1, 96, prompt_len).tolist()
        rows = []
        table = eng.reserve_table(prompt_len, 15)
        assert len(table.ring) == min(-(-(prompt_len + 15) // BLOCK), 3)
        fed = [eng.prefill(prompt, table, sampler=lambda r: (
            rows.append(np.array(r)), int(r.argmax()))[1])]
        for _ in range(14):
            picks, step = eng.decode_step([fed[-1], None, None],
                                          [table, None, None],
                                          return_rows=True)
            rows.append(step[0])
            fed.append(int(picks[0]))
        eng.release_table(table)
        ids = prompt + fed[:-1]
        want = _want(model, ids, list(range(prompt_len - 1, len(ids))))
    assert len(rows) == 15
    assert max(_worst(g, w) for g, w in zip(rows, want)) < TOLERANCE


def test_ragged_slots_decode_across_the_edge_together():
    """Three slots at contexts below, at and past the span step in
    lockstep; each slot's rows equal its own whole-sequence forward."""
    with dygraph.guard():
        model = _model(4, peaked=True)
        eng = _engine(model)
        rng = np.random.RandomState(11)
        prompts = [rng.randint(1, 96, n).tolist() for n in (2, 7, 30)]
        tables = [eng.reserve_table(len(p), 12) for p in prompts]
        fed = [[eng.prefill(p, t)] for p, t in zip(prompts, tables)]
        rows = [[] for _ in prompts]
        for _ in range(11):
            picks, step = eng.decode_step([f[-1] for f in fed], tables,
                                          return_rows=True)
            for s in range(3):
                rows[s].append(step[s])
                fed[s].append(int(picks[s]))
        for s, (p, t) in enumerate(zip(prompts, tables)):
            eng.release_table(t)
            ids = p + fed[s][:-1]
            want = _want(model, ids, list(range(len(p), len(ids))))
            assert max(_worst(g, w) for g, w in zip(rows[s], want)) \
                < TOLERANCE, s
        assert eng.pool.allocator.used == eng.pool.sliding.used == 0


def test_a_window_one_block_short_shows(monkeypatch):
    """The system with a span of 4 where the reference keeps 8: the rows
    past the shorter window are far outside the tolerance (the control
    `span_short` of tests/benchmark/control_trinity.py, at this size)."""
    monkeypatch.setattr(
        SlidingMoEConfig, 'span', lambda self, layer: (
            self.sliding_window - BLOCK) * (
                self.layer_types[layer] == 'sliding_attention'))
    with dygraph.guard():
        model = _model(2, peaked=True)
        eng = _engine(model)
        assert eng.layout.span == SPAN - BLOCK
        prompt = np.random.RandomState(5).randint(1, 96, 20).tolist()
        rows = []
        table = eng.reserve_table(20, 2)
        eng.prefill(prompt, table, sampler=lambda r: (
            rows.append(np.array(r)), int(r.argmax()))[1])
        want = _want(model, prompt, [19])
    assert _worst(rows[0], want[0]) > 100 * TOLERANCE


def test_a_ring_block_reused_after_retirement_reads_clean():
    """The second request is handed the first one's ring and table blocks,
    full of the first one's rows: its logits are those of a fresh pool."""
    with dygraph.guard():
        model = _model(6)
        rng = np.random.RandomState(2)
        first = rng.randint(1, 96, 31).tolist()
        second = rng.randint(1, 96, 10).tolist()

        def serve(eng, prompt, steps):
            rows = []
            table = eng.reserve_table(len(prompt), steps + 1)
            held = (list(table.blocks), list(table.ring))
            tok = eng.prefill(prompt, table)
            for _ in range(steps):
                picks, step = eng.decode_step([tok, None, None],
                                              [table, None, None],
                                              return_rows=True)
                rows.append(step[0])
                tok = int(picks[0])
            eng.release_table(table)
            return np.stack(rows), held

        used = _engine(model)
        _, held_first = serve(used, first, 12)
        after, held_second = serve(used, second, 8)
        fresh, _ = serve(_engine(model), second, 8)
        # the free lists hand the same blocks out again
        assert set(held_second[1]) <= set(held_first[1])
        assert set(held_second[0]) <= set(held_first[0])
    assert np.abs(after - fresh).max() < 1e-5 * np.abs(fresh).max()


def test_both_free_lists_return_to_full_after_every_request():
    with dygraph.guard():
        model = _model(7)
        eng = _engine(model)
        pool = eng.pool
        full, sliding = pool.allocator.capacity, pool.sliding.capacity
        assert sliding == 3 * 3 + SLIDING_SPARE_BLOCKS - 1
        for prompt_len, budget in ((3, 2), (8, 24), (32, 24), (1, 1)):
            table = eng.reserve_table(prompt_len, budget)
            blocks = -(-(prompt_len + budget) // BLOCK)
            assert (pool.allocator.used, pool.sliding.used) == (
                blocks, min(blocks, 3))
            eng.prefill([5] * prompt_len, table)
            eng.release_table(table)
            assert (pool.allocator.available, pool.sliding.available) == (
                full, sliding)
            assert table.blocks == [] and table.ring == []


@pytest.mark.parametrize('exhausted', ['full', 'sliding'])
def test_out_of_blocks_says_which_class_and_keeps_nothing(exhausted):
    pool = KVCachePool(block_size=4, num_blocks=7 if exhausted == 'full'
                       else 40, max_blocks_per_seq=8, span=8,
                       sliding_blocks=5)
    first = pool.new_table(16)            # 4 blocks, a ring of 3
    with pytest.raises(OutOfBlocks) as e:
        pool.new_table(16)
    assert e.value.layer_class == exhausted and exhausted in str(e.value)
    assert (pool.allocator.used, pool.sliding.used) == (4, 3)
    pool.free_table(first)
    assert pool.allocator.used == pool.sliding.used == 0
    # a pool of one class says none
    with pytest.raises(OutOfBlocks) as e:
        KVCachePool(block_size=4, num_blocks=3,
                    max_blocks_per_seq=8).new_table(16)
    assert e.value.layer_class is None and 'class' not in str(e.value)


def test_the_coordinates_place_every_position_in_its_ring_block():
    pool = KVCachePool(block_size=4, num_blocks=40, max_blocks_per_seq=10,
                       span=8, sliding_blocks=12)
    table = BlockTable([11, 12, 13, 14, 15, 16, 17, 18], 4,
                       ring=[21, 22, 23])
    table.context_len = 22                # blocks 0..5 hold the prompt
    coords = prefill_coords(pool, table, 32)
    # of the bucket's 8 blocks the last ring's worth that hold the prompt
    # (3, 4, 5) go to ring blocks 3 % 3, 4 % 3, 5 % 3; the others to scratch
    assert coords['sliding_write_ids'].tolist() == [0, 0, 0, 21, 22, 23,
                                                    0, 0]
    assert coords['write_ids'].tolist() == [11, 12, 13, 14, 15, 16, 0, 0]
    assert coords['sliding_tables'].tolist() == [[21, 22, 23]]
    short = BlockTable([31, 32], 4, ring=[24, 25])     # never past the span
    short.context_len = 6
    assert prefill_coords(pool, short, 8)['sliding_write_ids'].tolist() \
        == [24, 25]
    got = decode_coords(pool, [table, None, short], [23, 1, 7])
    # position 22 in block 5 -> ring block 5 % 3; position 6 in block 1
    assert got['sliding_write_ids'].tolist() == [23, 0, 25]
    assert got['sliding_write_offs'].tolist() == [2, 0, 2]
    assert got['sliding_tables'].tolist() == [[21, 22, 23], [0, 0, 0],
                                              [24, 25, 0]]
    assert got['write_ids'].tolist() == [16, 0, 32]
    with pytest.raises(UnsupportedCacheFeature, match='window'):
        decode_coords(pool, [table], [23], fed_counts=[2], window=2)


def test_the_pool_is_sized_per_class_from_the_spec():
    with dygraph.guard():
        model = _model(8)
        layout = model.cache_layout()
        assert layout.kind == 'kv' and tuple(
            layer.span for layer in layout.layers) == (8, 8, 8, 0)
        assert layout.window == 1
        eng = _engine(model, slots=5, max_blocks=99)
        assert eng.layout == layout and eng.window == 1
        pool = eng.pool
        assert pool.geometry == (4, 99, 14, 'f32', 0, 8,
                                 5 * 3 + SLIDING_SPARE_BLOCKS)
        eng.prefill([1, 2, 3], eng.reserve_table(3, 1))
        layers, _ = pool.arrays()
        assert [layers[i][0].shape[0] for i in range(4)] == [23, 23, 23, 99]
        assert (layout.full_layers, layout.sliding_layers, layout.span) \
            == (1, 3, 8)
        row = layout.token_bytes()
        assert row == 2 * 128 * 4
        assert layout.context_bytes(5) == row * 5 * 4
        assert layout.context_bytes(40) == row * (40 + 3 * 8)
        assert layout.block_bytes(4) == row * 4
        assert layout.sliding_class_bytes(5, 4) == \
            3 * 23 * 4 * row == sum(int(a.nbytes) for i in range(3)
                                    for a in layers[i])
        assert pool.bytes_in_hbm() == 3 * 23 * 4 * row + 99 * 4 * row
        with pytest.raises(ValueError, match='slots'):
            solve_decode_pool_blocks(model, 64, 4)
        state = model_state_bytes(model)
        budget_mb = (state + 3 * 23 * 4 * row + 50 * 4 * row) // 2 ** 20 + 1
        blocks = solve_decode_pool_blocks(model, budget_mb, 4, slots=5)
        assert 50 <= blocks < 50 + 2 ** 20 // (4 * row) + 1


def test_what_a_sliding_class_refuses():
    with dygraph.guard():
        model = _model(9)
        for kw, feature in ((dict(prefix_cache=True), 'prefix cache'),
                            (dict(spec_decode=True), 'speculative'),
                            (dict(kv_dtype='int8'), 'int8')):
            args = dict(slots=2, block_size=4, max_blocks=40,
                        max_prompt_len=16, max_new_tokens_cap=8,
                        prefix_cache=False)
            args.update(kw)
            with pytest.raises(UnsupportedCacheFeature) as e:
                DecodeEngine(model, **args)
            assert e.value.kind == 'sliding' and feature in str(e.value)
            assert 'Layer classes' in str(e.value)
        from paddle_tpu.serving.tier.disagg import PrefillReplica
        with pytest.raises(UnsupportedCacheFeature, match='handoff'):
            PrefillReplica(_engine(model))


@pytest.mark.parametrize('bad,match', [
    (dict(rope_scaling={'type': 'yarn'}), 'rope_scaling'),
    (dict(n_group=2), 'n_group'), (dict(topk_group=2), 'topk_group'),
    (dict(score_func='softmax'), 'score_func'),
    (dict(tie_word_embeddings=True), 'tie_word_embeddings'),
    (dict(layer_types=['sliding_attention', 'chunked_attention',
                       'sliding_attention', 'full_attention']),
     'chunked_attention'),
    (dict(layer_types=['full_attention']), 'layer_types'),
    (dict(experts_held=(6, 4), num_experts=4, router_width=8),
     'experts_held'),
    (dict(router_width=16), 'experts_held'),
    (dict(unheard_of=1), 'unknown key')])
def test_the_configuration_refuses_what_it_has_no_equations_for(bad, match):
    with pytest.raises(ValueError, match=match):
        SlidingMoEConfig.tiny(**bad)


def test_the_published_keys_build_the_cut_configuration():
    import json
    with open(os.path.join(REPO, 'benchmark', 'configs',
                           'trinity_large_preview.json')) as f:
        config = json.load(f)
    cfg = SlidingMoEConfig.from_published(config, **config['model'])
    assert (cfg.num_experts, cfg.router_width, cfg.experts_held) == (
        32, 256, (0, 32))
    assert [cfg.span(i) for i in range(5)] == [4096] * 4 + [0]
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.hidden_size) == (48, 8, 128, 3072)
    assert cfg.n_routed_experts == 256 and cfg.routed_scaling_factor == 2.448


# -- the shares of an expert layer --------------------------------------------

def _experts_layer(cfg, seed):
    from paddle_tpu.core.random import default_generator
    default_generator.seed(seed)
    return RoutedExperts(cfg)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts over 4 chips of 2: the routed parts of the four shares
    plus the shared expert ONCE equal the uncut reference's layer, and each
    share equals the reference given that share."""
    with dygraph.guard():
        whole_cfg = SlidingMoEConfig.tiny()
        whole = _experts_layer(whole_cfg, 3)
        x = np.random.RandomState(1).randn(2, 9, 32).astype(np.float32)
        p = {'ffn.' + n: v.value for n, v in whole.named_parameters()}
        m = dict(_config(whole_cfg))
        flat = x.reshape(-1, 32)
        none = np.full((18, 2), -1, np.int32)
        with jax.default_matmul_precision('highest'):
            want, _ = REFERENCE._experts(p, 'ffn', m, flat, none, 0.0)
            shared = np.asarray(REFERENCE._swiglu(
                flat, p['ffn.shared.gate.weight'], p['ffn.shared.up.weight'],
                p['ffn.shared.down.weight']))
        want = np.asarray(want)
        total = np.zeros_like(want)
        for first in range(0, 8, 2):
            cfg = SlidingMoEConfig.tiny(experts_held=(first, 2),
                                        num_experts=2, router_width=8)
            share = _experts_layer(cfg, 3)
            for name, param in share.named_parameters():
                value = dict(whole.named_parameters())[name].value
                param.value = value[first:first + 2] \
                    if name.startswith('experts_') else value
            got = share(dygraph.to_variable(x)).numpy().reshape(-1, 32)
            with jax.default_matmul_precision('highest'):
                mine, _ = REFERENCE._experts(
                    {k: (v[first:first + 2] if 'experts_' in k else v)
                     for k, v in p.items()}, 'ffn',
                    dict(m, experts_held=[first, 2]), flat, none, 0.0)
            assert _worst(got, np.asarray(mine)) < TOLERANCE, first
            total += got - shared
        assert _worst(total + shared, want) < TOLERANCE
        # and the uncut layer is the program's own, every expert held
        assert _worst(whole(dygraph.to_variable(x)).numpy().reshape(-1, 32),
                      want) < TOLERANCE


@pytest.mark.parametrize('scoring', ['sigmoid', 'softmax'])
def test_holding_every_expert_leaves_routed_experts_bit_for_bit(scoring):
    """kanana2's (sigmoid, a bias, shared experts) and sdar's (softmax, no
    bias, no shared expert) layer with `experts_held` of everything: the
    same bits, and the same counts noted."""
    with dygraph.guard():
        cfg = LatentMoEConfig.tiny()
        cfg.scoring_func = scoring
        if scoring == 'softmax':
            cfg.n_shared_experts = 0
        plain = _experts_layer(cfg, 5)
        cfg.experts_held = (0, cfg.n_routed_experts)
        held = _experts_layer(cfg, 5)
        x = dygraph.to_variable(
            np.random.RandomState(2).randn(3, 7, 32).astype(np.float32))
        assert held.held == (0, 8) and plain.held is None
        for (n0, p0), (n1, p1) in zip(plain.named_parameters(),
                                      held.named_parameters()):
            assert n0 == n1 and np.array_equal(p0.numpy(), p1.numpy())
        assert np.array_equal(plain(x).numpy(), held(x).numpy())


def test_the_engine_counts_the_held_share_of_the_assignments():
    from paddle_tpu import observability as obs
    with dygraph.guard():
        model = _model(3, experts_held=(2, 4), num_experts=4,
                       router_width=8)
        eng = _engine(model)
        obs.reset()
        table = eng.reserve_table(20, 4)
        tok = eng.prefill(list(range(1, 21)), table)
        work = dict(eng.last_call.work)
        eng.decode_step([tok, None, None], [table, None, None])
        step = dict(eng.last_call.work)
        eng.release_table(table)
        counts = np.asarray(eng.last_stats['expert_counts'])
    # 3 expert layers, top-2: 20 live rows, then 1
    assert counts.shape == (3, 4)
    assert work['assignments_total'] == 20 * 2 * 3
    assert step['assignments_total'] == 1 * 2 * 3
    assert 0 < work['assignments_held'] == work['expert_assignments'] \
        < work['assignments_total']
    assert work['experts_touched'] <= 12
    # positions by class: 3 sliding layers hold min(context, 8), 1 full all
    assert (work['full_positions'], work['sliding_positions']) == (20, 24)
    assert (step['full_positions'], step['sliding_positions']) == (21, 24)
    registry = obs.registry.to_dict()
    value = lambda n: registry[n]['samples'][0]['value']
    assert value('decode_expert_assignments_total') == 126
    assert value('decode_expert_assignments_held') == \
        work['assignments_held'] + step['assignments_held']
    assert value('decode_kv_positions_held') == 44 + 45
    assert value('decode_kv_positions_if_unwindowed') == 80 + 84
    assert value('decode_full_blocks_held') == 0
    assert value('decode_sliding_blocks_held') == 0


# -- analysis rules -----------------------------------------------------------

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


_PAGES = dict(kp=((40, 4, 128), 'float32'), vp=((40, 4, 128), 'float32'),
              bt=((3, 10), 'int32'), cl=((3,), 'int32'))
_READ = (dict(q=((3, 6, 8), 'float32'), **_PAGES),
         dict(q=['q'], k_pages=['kp'], v_pages=['vp'], block_tables=['bt'],
              context_lens=['cl']))
_PREFILL = (dict(q=((1, 6, 32, 8), 'float32'), k=((1, 2, 32, 8), 'float32'),
                 v=((1, 2, 32, 8), 'float32'), **_PAGES),
            dict(q=['q'], k=['k'], v=['v'], k_pages=['kp'], v_pages=['vp'],
                 block_tables=['bt']))
_PER_PAIR = 4 * 8 + 8 + 2          # QK, PV, a transcendental and two more
RULES = {
    'sigmoid_gate': (dict(x=((5, 16), 'bfloat16'), g=((5, 16), 'bfloat16')),
                     dict(x=['x'], gate=['g']), {},
                     ((5, 16), 'bfloat16'), 9 * 5 * 16),
    # the grouped read: the padded table; its sliding form: the span
    'paged_attention': (*_READ, dict(kv_heads=2), ((3, 6, 8), 'float32'),
                        3 * 6 * 40 * _PER_PAIR),
    'paged_attention/span': (*_READ, dict(kv_heads=2, span=8),
                             ((3, 6, 8), 'float32'), 3 * 6 * 8 * _PER_PAIR),
    # the causal grouped prefill: half the rung's square; sliding: the span
    'paged_prefill_attention': (*_PREFILL, dict(kv_heads=2),
                                ((1, 6, 32, 8), 'float32'),
                                6 * 32 * 16 * _PER_PAIR),
    'paged_prefill_attention/span': (*_PREFILL, dict(kv_heads=2, span=4),
                                     ((1, 6, 32, 8), 'float32'),
                                     6 * 32 * 4 * _PER_PAIR),
}


@pytest.mark.parametrize('case', sorted(RULES))
def test_every_new_op_and_attribute_has_its_rules(case):
    from paddle_tpu.analysis import has_cost_rule
    from paddle_tpu.analysis.infer import has_rule
    op_type = case.split('/')[0]
    inputs, in_slots, attrs, (shape, dtype), flops = RULES[case]
    assert has_rule(op_type) and has_cost_rule(op_type)
    out, cost = _infer_and_cost(op_type, inputs, in_slots, ['Out'], attrs)
    assert (tuple(out['Out'].shape), out['Out'].dtype) == (shape, dtype)
    assert cost.flops == flops
    assert cost.bytes_in > 0 and cost.bytes_out > 0


def test_moe_experts_rules_take_a_held_range():
    inputs = dict(x=((6, 16), 'bfloat16'), i=((6, 2), 'int32'),
                  w=((6, 2), 'float32'), g=((4, 16, 8), 'bfloat16'),
                  u=((4, 16, 8), 'bfloat16'), d=((4, 8, 16), 'bfloat16'))
    slots = dict(x=['x'], ids=['i'], weights=['w'], w_gate=['g'],
                 w_up=['u'], w_down=['d'])
    out, cost = _infer_and_cost('moe_experts', inputs, slots,
                                ['Out', 'Counts'],
                                {'experts_held': (4, 4)})
    assert tuple(out['Counts'].shape) == (4,)
    plain = _infer_and_cost('moe_experts', inputs, slots, ['Out', 'Counts'],
                            {})[1]
    assert cost.flops == plain.flops > 0        # the static bound


@pytest.mark.parametrize('op_type,case,attrs,match', [
    ('paged_attention', _READ, dict(span=8), 'needs kv_heads'),
    ('paged_attention', _READ, dict(kv_heads=4), 'do not divide'),
    ('paged_attention', _READ, dict(kv_heads=2, span=-1), 'negative'),
    ('paged_prefill_attention', _PREFILL, dict(kv_heads=3), 'k holds'),
    ('paged_prefill_attention', _PREFILL, dict(kv_heads=2, block_len=4),
     'not both'),
    ('paged_prefill_attention', _PREFILL, dict(span=4), 'needs kv_heads')])
def test_infer_rules_refuse_attributes_that_cannot_agree(op_type, case,
                                                         attrs, match):
    from paddle_tpu.analysis.infer import InferError
    with pytest.raises(InferError, match=match):
        _infer_and_cost(op_type, *case, ['Out'], attrs)
    inputs = dict(x=((6, 16), 'bfloat16'), i=((6, 2), 'int32'),
                  w=((6, 2), 'float32'), g=((4, 16, 8), 'bfloat16'),
                  u=((4, 16, 8), 'bfloat16'), d=((4, 8, 16), 'bfloat16'))
    with pytest.raises(InferError, match='experts_held'):
        _infer_and_cost('moe_experts', inputs, dict(
            x=['x'], ids=['i'], weights=['w'], w_gate=['g'], w_up=['u'],
            w_down=['d']), ['Out', 'Counts'], {'experts_held': (0, 8)})
