"""Mixed-kind accounting and refusals of a HYBRID model's cache
(serving/decode/kv_cache.py "Hybrid models"): the pool is sized over the row
layers and the state rows over the state layers; admission takes blocks AND
a row, all or nothing; one step books both kinds' counters and the span
carries both; both sets of gauges stand in one registry; the prefix cache,
speculation and the handoff are refused naming the state layers, int8 rows
by the grouped reads; the budget solve prices a hybrid request. A model of
state layers alone (retention) and one of row layers alone keep what they
had: their own files test them, unedited."""
import numpy as np
import pytest

from paddle_tpu import dygraph
from paddle_tpu.models.hybrid_conv_moe_lm import (HybridConvMoEConfig,
                                                  HybridConvMoELM)
from paddle_tpu.serving.decode.engine import DecodeEngine
from paddle_tpu.serving.decode.kv_cache import KVCachePool
from paddle_tpu.serving.decode.layout import (CacheLayout, LayerCache,
                                              decode_pool_report,
                                              kv_row_bytes,
                                              solve_decode_pool_blocks)
from paddle_tpu.serving.errors import (OutOfBlocks, OutOfStateRows,
                                       UnsupportedCacheFeature)

H, CONV, ATTN = 32, 3, 1          # tiny(): conv, attention, conv, conv
STATE_ROW = CONV * 2 * H * 4      # a request's float32 (u_{t-1}, u_t) a layer


@pytest.fixture(scope='module')
def lm():
    from paddle_tpu.core.random import default_generator
    with dygraph.guard():
        default_generator.seed(11)
        model = HybridConvMoELM(HybridConvMoEConfig.tiny())
        model.eval()
        yield model


def _engine(lm, slots=3, **kw):
    kw.setdefault('max_blocks', slots * 14 + 8)
    kw.setdefault('prefix_cache', False)
    return DecodeEngine(lm, slots=slots, block_size=4, max_prompt_len=32,
                        max_new_tokens_cap=24, prompt_buckets=[8, 16, 32],
                        **kw)


def test_layer_kinds_reads_both_forms_of_a_spec(lm):
    """A layout says each layer's kind; its own ``kind`` is its row
    layers', or 'state' where it has none."""
    states = CacheLayout((LayerCache.state((2, 3, 4), 'retention'),) * 3)
    assert (states.kind, states.row_layers, states.state_layers) \
        == ('state', 0, 3)
    latent = CacheLayout((LayerCache.latent(8),) * 2)
    assert (latent.kind, latent.row_layers, latent.reads) \
        == ('latent', 2, (('groups', 2),))
    assert CacheLayout((LayerCache.kv(2, 8),)).reads == (('blocks', 1),)
    layout = lm.cache_layout()
    assert tuple(layer.kind for layer in layout.layers) == (
        'state', 'kv', 'state', 'state')
    assert layout.kind == 'kv' and layout.reads == (('groups', ATTN),)


@pytest.mark.parametrize('kv_dtype', ['f32', 'bf16'])
def test_blocks_are_sized_over_row_layers_and_rows_over_state_layers(
        lm, kv_dtype):
    """Four layers, ONE pool: the attention layer alone holds [k, v] of the
    pool's depth at ``kv_dtype``; each conv layer one float32 array of
    slots + 1 rows, whatever ``kv_dtype``."""
    engine = _engine(lm, kv_dtype=kv_dtype)
    layout = engine.layout
    assert (layout.kind, layout.state_layers, layout.row_layers,
            layout.conv_layers) == ('kv', CONV, ATTN, CONV)
    assert layout.classes and (layout.full_layers, layout.span) == (ATTN, 0)
    table = engine.reserve_table(5, 3)
    engine.prefill([3, 4, 5, 6, 7], table)
    pool = engine.pool
    layers, _ = pool.arrays()
    assert pool.state_rows.num_rows == engine.slots + 1
    assert pool.state_bytes_in_hbm() + pool.bytes_in_hbm() == sum(
        int(a.nbytes) for arrs in layers.values() for a in arrs)
    width = {'f32': 'float32', 'bf16': 'bfloat16'}[kv_dtype]
    assert [(a.shape, str(a.dtype)) for a in layers[1]] == [
        ((pool.num_blocks, 4, 128), width)] * 2         # 2 heads of 8: a tile
    for layer in (0, 2, 3):
        assert [(a.shape, str(a.dtype)) for a in layers[layer]] == [
            ((engine.slots + 1, 1, 2, H), 'float32')]
    assert pool.bytes_in_hbm() == 2 * pool.num_blocks * 4 * 128 * (
        4 if kv_dtype == 'f32' else 2)
    assert pool.state_bytes_in_hbm() == (engine.slots + 1) * STATE_ROW
    engine.release_table(table)


def test_a_table_is_given_blocks_and_a_row_and_returns_both(lm):
    engine = _engine(lm)
    pool = engine.pool
    table = engine.reserve_table(9, 5)
    assert len(table.blocks) == 4 and table.state_row == 1
    assert (pool.allocator.used, pool.state_rows.used) == (4, 1)
    engine.release_table(table)
    assert (pool.allocator.used, pool.state_rows.used) == (0, 0)
    assert table.blocks == [] and table.state_row == 0


def test_admission_fails_cleanly_by_whichever_runs_out(lm):
    """Rows run out with more live tables than slots; blocks with a pool
    too shallow. Either way nothing of what was taken is kept."""
    engine = _engine(lm, slots=2)
    pool = engine.pool
    held = [engine.reserve_table(3, 1), engine.reserve_table(3, 1)]
    with pytest.raises(OutOfStateRows) as caught:
        engine.reserve_table(3, 1)
    assert isinstance(caught.value, OutOfBlocks)        # the WAIT signal
    assert (pool.allocator.used, pool.state_rows.used) == (2, 2)
    for table in held:
        engine.release_table(table)
    small = KVCachePool(block_size=4, num_blocks=4, max_blocks_per_seq=8,
                        state_rows=3)
    first = small.new_table(8)                           # 2 of 3 blocks
    with pytest.raises(OutOfBlocks) as caught:
        small.new_table(8)
    assert not isinstance(caught.value, OutOfStateRows)
    assert (small.allocator.used, small.state_rows.used) == (2, 1)
    small.free_table(first)
    assert (small.allocator.used, small.state_rows.used) == (0, 0)


def test_one_step_books_both_kinds_and_the_spans_carry_both(lm):
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import metrics as m
    engine = _engine(lm)
    names = ('decode_state_updates', 'decode_state_tokens_folded',
             'decode_context_positions_read', 'decode_kv_blocks_read',
             'decode_conv_rows')
    with obs.telemetry_guard(True):
        obs.reset()
        before = {k: getattr(m, k).value for k in names}
        a, b = engine.reserve_table(6, 2), engine.reserve_table(3, 2)
        feed = [engine.prefill([3, 4, 5, 6, 7, 8], a), None,
                engine.prefill([9, 8, 7], b)]
        folded = m.decode_conv_rows.value - before['decode_conv_rows']
        engine.decode_step(feed, [a, None, b])
        events = obs.tracer.snapshot()['traceEvents']
        after = {k: getattr(m, k).value - before[k] for k in names}
        gauges = {k: getattr(m, k).value for k in (
            'state_cache_bytes_in_hbm', 'state_cache_rows_total',
            'state_cache_rows_used', 'kv_cache_bytes_in_hbm',
            'kv_cache_row_bytes', 'decode_cache_blocks_used')}
        registry = obs.registry.to_dict()
        obs.reset()
    # the attention layer's live groups: the one read of the step
    walked = engine._blocks_walked([7, 1, 4])
    assert walked > 0
    # live tokens and live slots alone: 6 + 3 prompt tokens of two 8-row
    # rungs, two of three slots; each counter over ITS layers
    assert folded == (6 + 3) * CONV
    assert after == {'decode_state_updates': 2 * CONV,
                     'decode_state_tokens_folded': (6 + 3) * CONV,
                     'decode_context_positions_read': (7 + 4) * ATTN,
                     'decode_kv_blocks_read': walked,
                     'decode_conv_rows': (6 + 3 + 2) * CONV}
    spans = {e['name']: e.get('args') or {} for e in events
             if e.get('ph') == 'X' and e['name'] in ('engine/prefill',
                                                     'engine/step')}
    assert spans['engine/prefill']['prompt_len'] == 3
    assert spans['engine/prefill']['rung'] == 8 \
        == spans['engine/prefill']['bucket']
    assert spans['engine/prefill']['conv_rows'] == 3 * CONV
    assert spans['engine/prefill']['state_tokens_folded'] == 3 * CONV
    step = spans['engine/step']
    assert (step['state_updates'], step['conv_rows']) == (2 * CONV,) * 2
    assert step['kv_blocks'] == walked > 0
    assert step['context_positions'] == (7 + 4) * ATTN
    # both sets of gauges in one /metrics
    pool = engine.pool
    assert gauges == {
        'state_cache_bytes_in_hbm': (engine.slots + 1) * STATE_ROW,
        'state_cache_rows_total': 3, 'state_cache_rows_used': 2,
        'kv_cache_bytes_in_hbm': pool.bytes_in_hbm(),
        'kv_cache_row_bytes': 2 * kv_row_bytes(2, 8, 'f32'),
        'decode_cache_blocks_used': 2 + 2}
    for name in ('state_cache_bytes_in_hbm', 'state_cache_rows_total',
                 'kv_cache_bytes_in_hbm', 'decode_conv_rows_total',
                 'decode_state_updates', 'decode_kv_blocks_read'):
        assert name in registry, name
    engine.release_table(a)
    assert m.state_cache_rows_used.value == 1
    engine.release_table(b)


@pytest.mark.parametrize('asked,named,kind', [
    (dict(prefix_cache=True), 'prefix cache', 'state'),
    (dict(spec_decode=True), 'speculative', 'state'),
    (dict(kv_dtype='int8'), 'kv_dtype=int8', 'grouped')])
def test_what_a_hybrid_refuses_and_why(lm, asked, named, kind):
    with pytest.raises(UnsupportedCacheFeature, match=named) as caught:
        _engine(lm, **asked)
    assert caught.value.kind == kind
    if kind == 'state':
        assert 'state layers' in str(caught.value)
        assert 'Recurrent state' in str(caught.value)
    else:
        assert 'Layer classes' in str(caught.value)


def test_the_row_layers_take_bf16_beside_float32_states(lm):
    """`kv_dtype` is the row layers': bf16 rows are no refusal here, as they
    are for a model of state layers alone."""
    engine = _engine(lm, kv_dtype='bf16')
    assert engine.pool.kv_dtype == 'bf16'


def test_the_handoff_is_refused_naming_the_state_layers(lm):
    from paddle_tpu.serving.tier.disagg import PrefillReplica
    from paddle_tpu.serving.tier.replica import build_replica_stack
    with pytest.raises(UnsupportedCacheFeature, match='handoff') as caught:
        PrefillReplica(_engine(lm, slots=1))
    assert caught.value.kind == 'state'
    assert 'state layers' in str(caught.value)
    with pytest.raises(UnsupportedCacheFeature, match='handoff'):
        build_replica_stack(model=lm, slots=2, block_size=4, max_blocks=64,
                            prefix_cache=False, disagg=True)


def test_a_window_of_tokens_is_refused_by_the_state_layer(lm):
    engine = _engine(lm)
    table = engine.reserve_table(4, 8)
    token = engine.prefill([3, 4, 5, 6], table)
    with pytest.raises(UnsupportedCacheFeature, match='window'):
        engine.spec_step([[token, 4], None, None], [table, None, None])
    engine.release_table(table)


def test_the_plan_prices_a_hybrid_request(lm):
    """A request costs a state row a state layer plus rows a row layer; a
    budget buys blocks for the row layers alone, after the weights and the
    slots' state rows."""
    row = 2 * kv_row_bytes(2, 8, 'bf16')          # K and V, a 128-lane tile
    layout = lm.cache_layout()
    assert (layout.row_layers, layout.state_layers) == (ATTN, CONV)
    assert (layout.full_layers, layout.sliding_layers, layout.span) \
        == (ATTN, 0, 0)
    assert layout.token_bytes('bf16') == row == 512
    assert layout.state_row_bytes() == STATE_ROW
    assert layout.block_bytes(4, 'bf16') == ATTN * 4 * row
    assert layout.context_bytes(100, 'bf16') == ATTN * 100 * row
    assert layout.request_bytes(100, 'bf16') \
        == ATTN * 100 * row + STATE_ROW
    weights = sum(int(p.value.nbytes) for p in lm.parameters())
    with pytest.raises(ValueError, match='slots'):
        solve_decode_pool_blocks(lm, 1, block_size=4, kv_dtype='bf16')
    blocks = solve_decode_pool_blocks(lm, 1, block_size=4, kv_dtype='bf16',
                                      slots=3)
    assert blocks == ((1 << 20) - weights - 4 * STATE_ROW) // (4 * row)
    doc = decode_pool_report(lm, 1, block_size=4, kv_dtype='bf16', slots=3)
    assert doc['num_blocks'] == blocks and doc['state_row_bytes'] == STATE_ROW
    assert doc['block_bytes'] == 4 * row and 'state_slots' not in doc


def test_the_budget_knob_sizes_a_hybrid_engine(lm, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_DECODE_HBM_MB', '1')
    monkeypatch.delenv('PADDLE_TPU_DECODE_MAX_BLOCKS', raising=False)
    engine = DecodeEngine(lm, slots=3, block_size=4, max_prompt_len=32,
                          max_new_tokens_cap=24, prompt_buckets=[32],
                          prefix_cache=False, kv_dtype='bf16')
    assert engine.pool.num_blocks == solve_decode_pool_blocks(
        lm, 1, block_size=4, kv_dtype='bf16', min_blocks=15, slots=3)


def test_the_published_cell_is_priced_from_the_configuration_file():
    """The cell's own numbers from the model's layout at the published
    widths, shapes alone: 6,144 B a token over the 3 attention layers,
    163,840 B of state a request over the 10 conv layers, 3.52 GB of pool
    and 21 MB of state rows at 128 slots."""
    import json
    import os
    import jax
    from paddle_tpu.core.random import default_generator
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), '..',
                                        '..', 'benchmark'))
    with open(os.path.join(root, 'configs', 'lfm2_8b_a1b.json')) as f:
        config = json.load(f)
    with open(os.path.join(root, 'traffic',
                           'closed_c128_ctx4k_v65k.json')) as f:
        engine = json.load(f)['engine']
    made = {}

    def init(key):
        with default_generator.bind_base(key):
            made['model'] = HybridConvMoELM(
                HybridConvMoEConfig.from_published(config,
                                                   **config['model']))
        return {n: p.value for n, p in made['model'].named_parameters()}

    with dygraph.guard():
        default_generator.seed(3)
        shapes = jax.eval_shape(init, default_generator.base_key())
    model = made['model']
    parameters = sum(int(np.prod(s.shape)) for s in shapes.values())
    assert round(parameters / 1e9, 3) == 4.606
    assert {str(s.dtype) for n, s in shapes.items()
            if 'router_bias' not in n} == {'bfloat16'}
    layout = model.cache_layout()
    assert (layout.row_layers, layout.state_layers) == (3, 10)
    assert 3 * layout.token_bytes('bf16') == 6144
    assert layout.state_row_bytes() == 10 * 2 * 2048 * 4
    pool = engine['max_blocks'] * layout.block_bytes(engine['block_size'],
                                                     'bf16')
    states = layout.state_rows(engine['slots']) * layout.state_row_bytes()
    assert round(pool / 1e9, 2) == 3.52 and round(states / 1e6) == 21
    assert layout.request_bytes(4480, 'bf16') \
        == 4480 * 6144 + 163840
