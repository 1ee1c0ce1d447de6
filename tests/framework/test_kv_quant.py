"""Quantized KV cache (PADDLE_TPU_KV_DTYPE): strict knob parsing, the
f32-is-bitwise / int8-match-rate quality contract, int8 interaction with
speculative-decode rollback and the disaggregated handoff wire format, and
the planner-backed pool sizing solve (PADDLE_TPU_DECODE_HBM_MB vs the
closed form, with the explicit MAX_BLOCKS overrides winning)."""
import numpy as np
import pytest

from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import greedy_generate
from paddle_tpu.serving import DecodeEngine, DecodeScheduler
from paddle_tpu.serving.tier.disagg import KVPayload, PrefillReplica
from paddle_tpu.serving.tier.replica import build_replica_stack, build_tiny_lm


@pytest.fixture(scope='module')
def lm():
    with guard():
        yield build_tiny_lm()


def make_engine(model, **kw):
    kw.setdefault('slots', 2)
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


_WORK = [([7, 3, 11, 5, 9], 8), ([2, 44, 8, 13], 6), ([9] * 7, 10),
         ([1, 2, 3], 5)]


def _run(engine, work=_WORK):
    with DecodeScheduler(engine, queue_depth=len(work) + 1) as sched:
        streams = [sched.submit(p, max_new_tokens=m) for p, m in work]
        return [s.result(240) for s in streams]


# -- strict knob parsing ---------------------------------------------------

def test_kv_dtype_env_strict_parse(lm, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_KV_DTYPE', 'fp8')
    with pytest.raises(ValueError, match='PADDLE_TPU_KV_DTYPE') as e:
        make_engine(lm)
    assert 'int8' in str(e.value)                 # names the supported set
    for env, storage in (('f32', 'float32'), ('bf16', 'bfloat16'),
                         ('int8', 'int8')):
        monkeypatch.setenv('PADDLE_TPU_KV_DTYPE', env)
        eng = make_engine(lm)
        assert eng.pool.kv_dtype == env
        assert eng.pool.dtype == storage
    # an explicit argument wins over the env knob
    monkeypatch.setenv('PADDLE_TPU_KV_DTYPE', 'f32')
    assert make_engine(lm, kv_dtype='int8').pool.kv_dtype == 'int8'


def test_decode_hbm_mb_env_strict_parse(lm, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_DECODE_HBM_MB', 'lots')
    with pytest.raises(ValueError, match='PADDLE_TPU_DECODE_HBM_MB'):
        make_engine(lm, max_blocks=None)
    monkeypatch.setenv('PADDLE_TPU_DECODE_HBM_MB', '0')
    with pytest.raises(ValueError, match='integers >= 1'):
        make_engine(lm, max_blocks=None)


def test_prefix_cache_host_mb_env_strict_parse(lm, monkeypatch):
    from paddle_tpu.serving import PrefixCache
    eng = make_engine(lm)
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE_HOST_MB', 'big')
    with pytest.raises(ValueError, match='PADDLE_TPU_PREFIX_CACHE_HOST_MB'):
        PrefixCache(eng.pool)
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE_HOST_MB', '-1')
    with pytest.raises(ValueError, match='integers >= 0'):
        PrefixCache(eng.pool)
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE_HOST_MB', '2')
    assert PrefixCache(eng.pool).host_bytes == 0  # configured, still empty


# -- quality contract ------------------------------------------------------

def test_f32_pool_bitwise_and_untouched(lm):
    """f32 storage is the pre-quantization path exactly: generations match
    the whole-sequence reference bitwise, the pool dtype is float32, no
    scale arrays exist, and _encode_rows passes values through UNTOUCHED
    (object identity — the no-cast, no-copy guarantee)."""
    eng = make_engine(lm)
    refs = [greedy_generate(lm, p, m, pad_len=eng.padded_context)
            for p, m in _WORK]
    assert _run(eng) == refs
    assert eng.pool.dtype == 'float32'
    assert all(eng.pool.scales(layer) is None
               for layer in range(eng.pool.num_layers))
    import jax.numpy as jnp
    vals = jnp.ones((2, 3, 8), jnp.float32)
    enc, sc = eng.pool._encode_rows(vals)
    assert enc is vals and sc is None


@pytest.mark.parametrize('dtype', ['bf16', 'int8'])
def test_quantized_greedy_match_rate(lm, dtype):
    """Lossy storage keeps the greedy trajectory: ≥ 0.99 token-level match
    against the f32 reference (docs/SERVING.md quality contract). Length
    divergence counts against the rate."""
    eng = make_engine(lm, kv_dtype=dtype)
    refs = [greedy_generate(lm, p, m, pad_len=eng.padded_context)
            for p, m in _WORK]
    outs = _run(eng)
    matched = sum(sum(a == b for a, b in zip(o, r))
                  for o, r in zip(outs, refs))
    total = sum(len(r) for r in refs)
    assert matched / total >= 0.99, (outs, refs)
    if dtype == 'int8':
        assert all(eng.pool.scales(layer) is not None
                   for layer in range(eng.pool.num_layers))
    assert eng.pool.bytes_in_hbm() > 0


@pytest.fixture(scope='module')
def lm_head_dim_32():
    """f32 rows of 128 B against int8 rows of 32 + 4 B (payload + one f32
    scale): the 3.56x pool ratio; the tiny model's head_dim 16 would
    understate it (3.2x)."""
    from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
    with guard():
        model = TransformerLM(CausalLMConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=64,
            max_position_embeddings=128))
        model.eval()
        yield model


def _ragged_work(requests=8, seed=0):
    rng = np.random.RandomState(seed)
    return [([int(t) for t in rng.randint(3, 120, int(rng.randint(2, 13)))],
             int(rng.randint(4, 25))) for _ in range(requests)]


def test_pool_bytes_per_dtype_and_what_they_buy(lm_head_dim_32):
    """At head_dim 32, after the same ragged greedy workload: f32 storage
    is bitwise the uncached reference and bf16/int8 match it at >= 0.99 of
    tokens; the MEASURED int8 pool is >= 3.5x smaller than the f32 one and
    the bf16 one exactly half; at one HBM budget the planner solves more
    slots per chip for the smaller rows, and a host tier extends every
    dtype's effective cache."""
    from paddle_tpu.serving.decode.layout import solve_decode_pool_blocks
    model, work = lm_head_dim_32, _ragged_work()
    pool_bytes, slots_per_chip = {}, {}
    refs = None
    for dtype in ('f32', 'bf16', 'int8'):
        eng = make_engine(model, slots=4, block_size=8, max_blocks=256,
                          max_new_tokens_cap=48, kv_dtype=dtype)
        if refs is None:
            refs = [greedy_generate(model, p, m, pad_len=eng.padded_context)
                    for p, m in work]
        outs = _run(eng, work)
        matched = sum(sum(a == b for a, b in zip(o, r))
                      for o, r in zip(outs, refs))
        assert matched / sum(len(r) for r in refs) >= 0.99, dtype
        if dtype == 'f32':
            assert outs == refs
        pool_bytes[dtype] = eng.pool.bytes_in_hbm()
        blocks = solve_decode_pool_blocks(model, 1024, block_size=8,
                                          kv_dtype=dtype)
        slots_per_chip[dtype] = blocks // eng.pool.max_blocks_per_seq
        host_blocks = (512 << 20) // model.cache_layout().block_bytes(
            8, dtype)
        assert host_blocks > 0                 # the tier adds to `blocks`
    assert pool_bytes['f32'] / pool_bytes['int8'] >= 3.5, pool_bytes
    assert pool_bytes['bf16'] * 2 == pool_bytes['f32']
    assert (slots_per_chip['int8'] > slots_per_chip['bf16']
            > slots_per_chip['f32'] > 0), slots_per_chip


def test_int8_spec_decode_rollback_parity(lm):
    """Speculative verify + rollback over an int8 pool: the (S, k) verify
    rows read DEQUANTIZED keys, the rollback re-quantizes the accepted
    window — the trajectory must equal the int8 LOCKSTEP engine's (the
    spec machinery may not add quantization error on top)."""
    lockstep = _run(make_engine(lm, kv_dtype='int8'))
    r0 = _counter('decode_spec_rounds')
    spec = _run(make_engine(lm, kv_dtype='int8', spec_decode=True))
    assert spec == lockstep
    assert _counter('decode_spec_rounds') > r0   # spec path actually ran


def test_int8_disagg_handoff_parity(lm, monkeypatch):
    """Disaggregated prefill at int8: the payload ships the QUANTIZED pages
    + scales, the decode pool scatters them byte-exactly — generations
    equal the colocated int8 engine's."""
    monkeypatch.setenv('PADDLE_TPU_KV_DTYPE', 'int8')
    eng_d, sched_d, worker = build_replica_stack(model=lm, disagg=True)
    try:
        assert eng_d.pool.kv_dtype == 'int8'
        outs = [sched_d.submit(p, max_new_tokens=m).result(240)
                for p, m in _WORK]
    finally:
        sched_d.close()
        worker.close()
    eng_c, sched_c, _ = build_replica_stack(model=lm, disagg=False)
    try:
        colocated = [sched_c.submit(p, max_new_tokens=m).result(240)
                     for p, m in _WORK]
    finally:
        sched_c.close()
    assert outs == colocated
    assert eng_d.pool.allocator.used == 0


def test_int8_payload_wire_roundtrip(lm):
    eng = make_engine(lm, kv_dtype='int8')
    pay = PrefillReplica(eng).prefill_to_payload([5, 6, 7, 8, 9], 0)
    assert pay.kv_dtype == 'int8' and pay.scales is not None
    clone = KVPayload.from_bytes(pay.to_bytes())
    assert clone.kv_dtype == 'int8'
    for (k1, v1), (k2, v2) in zip(pay.layers, clone.layers):
        assert k2.dtype == np.int8 and v2.dtype == np.int8
        assert np.array_equal(k1, k2) and np.array_equal(v1, v2)
    for (ks1, vs1), (ks2, vs2) in zip(pay.scales, clone.scales):
        assert ks2.dtype == np.float32
        assert np.array_equal(ks1, ks2) and np.array_equal(vs1, vs2)
    # int8 payload + f32 scales beat the f32 bytes they replace
    f32 = PrefillReplica(make_engine(lm)).prefill_to_payload(
        [5, 6, 7, 8, 9], 0)
    assert pay.nbytes < f32.nbytes / 2


def test_legacy_three_int_meta_parses_as_f32():
    """Pre-quantization senders wrote meta = [ctx, first, bs]: the reader
    must accept it as an f32 payload with no scales (rolling-upgrade
    compatibility of the cross-host seam)."""
    import io
    arrays = {'meta': np.asarray([5, 42, 4], np.int64),
              'k0': np.zeros((2, 2, 4, 8), np.float32),
              'v0': np.zeros((2, 2, 4, 8), np.float32)}
    buf = io.BytesIO()
    np.savez(buf, **arrays)  # lint: allow-io (in-memory BytesIO)
    pay = KVPayload.from_bytes(buf.getvalue())
    assert pay.kv_dtype == 'f32' and pay.scales is None
    assert pay.context_len == 5 and pay.first_token == 42
    assert pay.block_size == 4


# -- planner-backed pool sizing --------------------------------------------

def test_budget_solve_matches_closed_form(lm):
    from paddle_tpu.serving.decode.layout import (decode_pool_report,
                                                  solve_decode_pool_blocks)
    state = sum(getattr(p, 'value', p).nbytes for p in lm.parameters())
    for dtype in ('f32', 'bf16', 'int8'):
        block_bytes = lm.cache_layout().block_bytes(4, dtype)
        closed = ((8 << 20) - state) // block_bytes
        solved = solve_decode_pool_blocks(lm, 8, block_size=4,
                                          kv_dtype=dtype)
        assert abs(solved - closed) <= 1, (dtype, solved, closed)
        rep = decode_pool_report(lm, 8, block_size=4, kv_dtype=dtype)
        assert rep['num_blocks'] == solved
        assert rep['num_blocks'] * rep['block_bytes'] <= (8 << 20) - state
    # int8 rows are head_dim + 4 scale bytes -> strictly more blocks
    assert (solve_decode_pool_blocks(lm, 8, block_size=4, kv_dtype='int8')
            > solve_decode_pool_blocks(lm, 8, block_size=4, kv_dtype='f32'))


def test_budget_sizes_engine_pool(lm, monkeypatch):
    from paddle_tpu.serving.decode.layout import solve_decode_pool_blocks
    monkeypatch.setenv('PADDLE_TPU_DECODE_HBM_MB', '8')
    eng = make_engine(lm, max_blocks=None)
    expect = solve_decode_pool_blocks(
        lm, 8, block_size=4, kv_dtype='f32',
        min_blocks=eng.pool.max_blocks_per_seq + 1)
    assert eng.pool.num_blocks == expect


def test_explicit_max_blocks_wins_over_budget(lm, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_DECODE_HBM_MB', '8')
    assert make_engine(lm, max_blocks=50).pool.num_blocks == 50
    monkeypatch.setenv('PADDLE_TPU_DECODE_MAX_BLOCKS', '77')
    assert make_engine(lm, max_blocks=None).pool.num_blocks == 77


def test_budget_smaller_than_state_raises(lm):
    from paddle_tpu.serving.decode.layout import solve_decode_pool_blocks
    with pytest.raises(ValueError, match='model state'):
        solve_decode_pool_blocks(lm, 0, block_size=4)


# -- analysis wiring -------------------------------------------------------

def _paged_op_cost(inputs, in_slots, op_type='paged_attention'):
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
                           outputs={'Out': ['o']}, attrs={})
        env['o'] = infer_op(op, env, blk)['Out']
        return op_cost(op, env, blk)


def test_paged_attention_cost_prices_quantized_pool():
    """The generic byte model prices an int8 pool as 1 B/elem payload plus
    4 B/row scales — the pool-bytes delta vs f32 is exactly the storage
    saving (3.56x at head_dim 32), and the scale slots must be typed f32
    rank 3 (NB, BS, H) matching the pages' (NB, BS, H·D) blocks and
    splitting their rows into heads (InferError otherwise)."""
    from paddle_tpu.analysis.infer import InferError
    H, NB, BS, D, S, nbs = 2, 8, 16, 32, 3, 4
    base = {'q': ((S, H, D), 'float32'),
            'kp': ((NB, BS, H * D), 'float32'),
            'vp': ((NB, BS, H * D), 'float32'),
            'bt': ((S, nbs), 'int32'), 'cl': ((S,), 'int32')}
    slots = {'q': ['q'], 'k_pages': ['kp'], 'v_pages': ['vp'],
             'block_tables': ['bt'], 'context_lens': ['cl']}
    c32 = _paged_op_cost(base, slots)
    t_pad = nbs * BS
    assert c32.flops == S * H * t_pad * (4 * D + 8 + 2)

    q8 = dict(base, kp=((NB, BS, H * D), 'int8'),
              vp=((NB, BS, H * D), 'int8'),
              ks=((NB, BS, H), 'float32'), vs=((NB, BS, H), 'float32'))
    s8 = dict(slots, k_scales=['ks'], v_scales=['vs'])
    c8 = _paged_op_cost(q8, s8)
    assert c8.flops == c32.flops + 2 * S * H * t_pad * D  # dequant term
    pool_f32 = 2 * H * NB * BS * D * 4
    pool_i8 = 2 * H * NB * BS * (D + 4)                   # 1 B/elem + 4 B/row
    assert c32.bytes_in - c8.bytes_in == pool_f32 - pool_i8

    for bad in ({'ks': ((NB, BS, H), 'int32')},           # wrong dtype
                {'ks': ((NB, BS), 'float32')},            # wrong rank
                {'ks': ((NB + 1, BS, H), 'float32')},     # other blocks
                {'ks': ((NB, BS, H + 1), 'float32')}):    # no whole heads
        with pytest.raises(InferError, match='k_scales'):
            _paged_op_cost(dict(q8, **bad), s8)
