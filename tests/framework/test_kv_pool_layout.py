"""The K/V pool's storage layout (serving/decode/kv_cache.py): every pool
array is rows of one token, (num_blocks, block_size, n_heads·head_dim in
whole 128-lane tiles), so the paged writes and the reads work on it as it
lies, and the lockstep step's read holds no array over a slot's whole padded
context; the wire format (read_blocks / write_whole_blocks) stays head-major
and unpadded. Each case at every storage dtype."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import dygraph
from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
from paddle_tpu.ops.nn_ops import LIVE_BLOCK_CHUNK, _gather_pages
from paddle_tpu.serving.decode.engine import (DecodeEngine, _arrays_spanning,
                                              _moves_of_size)
from paddle_tpu.serving.decode.kv_cache import (KV_PAYLOAD_DTYPES,
                                                KVCachePool, row_lanes)
from paddle_tpu.serving.decode.layout import kv_row_bytes

KV_DTYPES = ['f32', 'bf16', 'int8']
H, D, BS, NB = 3, 8, 4, 11          # a row of 24 values: padded to 128 lanes


def encode(rows, kv_dtype):
    """numpy's own codec: f32 rows (..., D) → (payload, scales or None)."""
    if kv_dtype == 'f32':
        return rows, None
    if kv_dtype == 'bf16':
        return rows.astype(jnp.bfloat16), None
    scale = np.abs(rows).max(-1) / np.float32(127.0)
    inv = np.where(scale > 0, np.float32(1.0) / np.where(scale > 0, scale, 1),
                   np.float32(0.0)).astype('float32')
    q = np.clip(np.rint(rows * inv[..., None]), -127, 127).astype('int8')
    return q, scale.astype('float32')


class WirePool:
    """A plain numpy model of one layer of the pool, kept in the WIRE
    format: payload (H, NB, BS, D) at the storage dtype, scales (H, NB, BS)."""

    def __init__(self, kv_dtype):
        self.kv_dtype = kv_dtype
        self.k = np.zeros((H, NB, BS, D), KV_PAYLOAD_DTYPES[kv_dtype])
        self.v = np.zeros_like(self.k)
        self.ks = np.zeros((H, NB, BS), 'float32')
        self.vs = np.zeros_like(self.ks)

    def write(self, block, offset, k_row, v_row):
        """One token's (H, D) K and V rows."""
        for pay, sc, row in ((self.k, self.ks, k_row), (self.v, self.vs,
                                                        v_row)):
            q, s = encode(row, self.kv_dtype)
            pay[:, block, offset] = q
            if s is not None:
                sc[:, block, offset] = s


def filled_pools(kv_dtype, seed=0):
    """A pool written through write_prefill (7 tokens into blocks 5, 2 and
    the rung's tail into scratch) and write_tokens (3 more tokens, one of
    them a padded lane on the scratch block), beside its numpy model."""
    rng = np.random.RandomState(seed)
    pool = KVCachePool(block_size=BS, num_blocks=NB, max_blocks_per_seq=4,
                       kv_dtype=kv_dtype)
    model = WirePool(kv_dtype)
    L = 10                       # a bucket of 10 rows: 3 blocks, 2 real
    k = rng.randn(H, L, D).astype('float32')
    v = rng.randn(H, L, D).astype('float32')
    ids = [5, 2, 0]
    pool.write_prefill(0, np.asarray(ids, np.int32), k, v)
    for pos in range(12):        # padded to whole blocks with zeros
        row = (k[:, pos], v[:, pos]) if pos < L else (np.zeros((H, D), 'f4'),
                                                      np.zeros((H, D), 'f4'))
        model.write(ids[pos // BS], pos % BS, *row)
    tk = rng.randn(H, 3, D).astype('float32')
    tv = rng.randn(H, 3, D).astype('float32')
    at = [(2, 3), (9, 0), (0, 0)]
    pool.write_tokens(0, np.asarray([b for b, _ in at], np.int32),
                      np.asarray([o for _, o in at], np.int32), tk, tv)
    for i, (b, o) in enumerate(at):
        model.write(b, o, tk[:, i], tv[:, i])
    return pool, model


@pytest.mark.parametrize('kv_dtype', KV_DTYPES)
def test_pool_arrays_are_rows_of_one_token(kv_dtype):
    pool, _ = filled_pools(kv_dtype)
    layers, scales = pool.arrays()
    assert row_lanes(H * D) == 128 and row_lanes(768) == 768 \
        and row_lanes(320) == 384
    assert [a.shape for a in layers[0]] == [(NB, BS, 128)] * 2
    assert {str(a.dtype) for a in layers[0]} == {KV_PAYLOAD_DTYPES[kv_dtype]}
    assert pool.heads == {0: (H, D)}
    if kv_dtype == 'int8':
        assert [a.shape for a in scales[0]] == [(NB, BS, H)] * 2
    else:
        assert scales == {}
    # the lanes past the row's values stay zero, and the sizing solve
    # prices what is allocated
    for a in layers[0]:
        assert not np.asarray(a)[..., H * D:].any()
    assert pool.bytes_in_hbm() == NB * BS * 2 * kv_row_bytes(H, D, kv_dtype)
    assert pool.row_bytes() == 2 * kv_row_bytes(H, D, kv_dtype)


@pytest.mark.parametrize('kv_dtype', KV_DTYPES)
def test_writes_then_wire_reads_equal_the_numpy_model(kv_dtype):
    pool, model = filled_pools(kv_dtype)
    blocks = [2, 5, 9, 0, 7]          # written, scratch, never written
    k, v = pool.read_blocks(0, blocks)
    assert k.shape == v.shape == (H, len(blocks), BS, D)
    assert k.dtype == v.dtype == model.k.dtype and k.flags.c_contiguous
    assert np.array_equal(k, model.k[:, blocks])
    assert np.array_equal(v, model.v[:, blocks])
    sc = pool.read_block_scales(0, blocks)
    if kv_dtype != 'int8':
        assert sc is None
        return
    assert sc[0].shape == sc[1].shape == (H, len(blocks), BS)
    assert np.array_equal(sc[0], model.ks[:, blocks])
    assert np.array_equal(sc[1], model.vs[:, blocks])


@pytest.mark.parametrize('kv_dtype', KV_DTYPES)
def test_read_blocks_into_a_second_pool_is_byte_exact(kv_dtype):
    pool, _ = filled_pools(kv_dtype, seed=1)
    src, dst = [5, 2, 9], [1, 8, 3]
    k, v = pool.read_blocks(0, src)
    sc = pool.read_block_scales(0, src) or (None, None)
    other = KVCachePool(block_size=BS, num_blocks=NB, max_blocks_per_seq=4,
                        kv_dtype=kv_dtype)
    other.write_whole_blocks(0, dst, k, v, k_scale=sc[0], v_scale=sc[1])
    assert other.heads == {0: (H, D)}
    k2, v2 = other.read_blocks(0, dst)
    assert k2.tobytes() == k.tobytes() and v2.tobytes() == v.tobytes()
    if kv_dtype == 'int8':
        sc2 = other.read_block_scales(0, dst)
        assert sc2[0].tobytes() == sc[0].tobytes()
        assert sc2[1].tobytes() == sc[1].tobytes()
    # and the device arrays themselves: the moved blocks are the same bytes
    for a, b in zip(pool.arrays()[0][0], other.arrays()[0][0]):
        assert np.array_equal(np.asarray(a)[src], np.asarray(b)[dst])
    untouched = [i for i in range(NB) if i not in dst]
    assert not np.asarray(other.arrays()[0][0][0])[untouched].any()


@pytest.mark.parametrize('kv_dtype', KV_DTYPES)
def test_gather_pages_equals_the_numpy_block_walk(kv_dtype):
    rng = np.random.RandomState(3)
    rows = rng.randn(NB, BS, H, D).astype('float32')
    payload, scales = encode(rows, kv_dtype)
    tables = np.asarray([[4, 1, 0], [7, 0, 0], [3, 10, 6]], np.int32)
    pages = np.zeros((NB, BS, row_lanes(H * D)), payload.dtype)
    pages[..., :H * D] = payload.reshape(NB, BS, H * D)
    got = np.asarray(_gather_pages(jnp.asarray(pages), jnp.asarray(tables),
                                   len(tables), H, D, scales))
    # a row that fills its lanes (no padding) reads the same
    assert np.array_equal(got, np.asarray(_gather_pages(
        jnp.asarray(pages[..., :H * D]), jnp.asarray(tables), len(tables),
        H, D, scales)))
    assert got.dtype == np.float32 and got.shape == (3, H, 3 * BS, D)
    for s, table in enumerate(tables):
        t = 0
        for block in table:             # the walk: block by block, row by row
            for off in range(BS):
                for h in range(H):
                    want = payload[block, off, h].astype('float32')
                    if scales is not None:
                        want = want * scales[block, off, h]
                    assert np.array_equal(got[s, h, t], want), (s, h, t)
                t += 1


@pytest.fixture(scope='module')
def lm():
    with dygraph.guard():
        np.random.seed(0)
        model = TransformerLM(CausalLMConfig.tiny())
        model.eval()
        yield model


def _engine(lm, kv_dtype):
    # 9 slots of 31 blocks: 279 table entries, more than one chunk of the
    # step's read, and 9 × 124 = 1,116 positions, a product no other run of
    # the tiny model's dimensions has; 337 blocks: no activation has a pool
    # array's size
    eng = DecodeEngine(lm, slots=9, block_size=4, max_blocks=337,
                       max_prompt_len=64, max_new_tokens_cap=60,
                       prefix_cache=False, kv_dtype=kv_dtype)
    assert eng.slots * eng.pool.max_blocks_per_seq > LIVE_BLOCK_CHUNK
    table = eng.reserve_table(5, 60)
    eng.prefill([3, 5, 7, 9, 11], table)         # allocates the pool
    return eng, table


@pytest.mark.parametrize('kv_dtype', KV_DTYPES)
def test_engine_programs_alias_the_pool_and_never_move_it(lm, kv_dtype):
    eng, table = _engine(lm, kv_dtype)
    layers, scales = eng.pool.arrays()
    arrays = [a for arrs in list(layers.values()) + list(scales.values())
              for a in arrs]
    assert len(arrays) == lm.num_cache_layers * (4 if kv_dtype == 'int8'
                                                 else 2)
    for bucket in (None, 8):
        lowered = eng.lowered(bucket)
        # donation held: every pool argument is aliased to a result
        assert lowered.as_text().count('tf.aliasing_output') == len(arrays)
        assert eng.pool_moves(bucket) == []
    # the step's read builds no array over every slot's padded context ...
    assert eng.step_context_arrays() == []
    # ... and is ONE executable however many live blocks a step walks: 11
    # blocks of one chunk here, two chunks' worth there
    others = [eng.reserve_table(64, 60) for _ in range(8)]
    for t in others:
        t.context_len = 100
    eng.decode_step([1] + [None] * 8, [table] + [None] * 8)
    programs = eng.compiled_programs()
    eng.decode_step([1] * 9, [table] + others)
    assert eng.compiled_programs() == programs


def test_the_detector_sees_the_dense_reads_the_step_had():
    """Result types of the GPT-1 cell's step as the chip ran it before the
    walk over live blocks (ledger, PR 28: 128 slots × 32 blocks of 16), and
    what the step holds now."""
    text = '''
  %fusion.1 = f32[128,512,12,64]{3,1,2,0:T(8,128)} fusion(%copy.3), kind=kLoop
  %fusion.2 = (f32[4096,16,768]{2,1,0:T(8,128)}, f32[4096,16,768]{2,1,0:T(8,128)}) fusion(%p.1, %p.2), kind=kLoop
  %fusion.3 = f32[128,512,12]{1,2,0:T(8,128)} fusion(%a, %b), kind=kLoop
  %fusion.4 = f32[4104,16,768]{2,1,0:T(8,128)} fusion(%p.1, %p.2), kind=kCustom
  %fusion.5 = f32[256,16,768]{2,1,0:T(8,128)} fusion(%p.1, %ids), kind=kCustom
  %fusion.6 = f32[4096,12]{1,0:T(8,128)} fusion(%x, %y), kind=kOutput
  %fusion.7 = s32[4096]{0:T(1024)} fusion(%t, %l), kind=kLoop
  %dot.8 = f32[128,40478]{1,0:T(8,128)} fusion(%h, %w), kind=kOutput
'''
    found = _arrays_spanning(text, 128 * 512)
    assert [line.split(' = ')[0] for line in found] == [
        '%fusion.1', '%fusion.2', '%fusion.3']


def test_the_detector_sees_the_copies_the_head_major_pool_had():
    """Lines of the programs compiled for the v5e before this layout (PERF.md
    section 6, PR 27), and lines that are not the pool's."""
    pool = 12 * 4104 * 16 * 64
    text = '''
  %copy.63 = f32[12,4104,16,64]{3,2,1,0:T(8,128)} copy(%layers_0__0_.1), sharding={replicated}
  %copy.64 = bf16[12,4104,16,64]{3,2,0,1:T(8,128)(2,1)} copy(%fusion.26), metadata={op_name="jit(run)/jit(_scatter_blocks)/scatter"}
  %copy-start.2 = (f32[4104,16,768]{2,1,0:T(8,128)}, f32[4104,16,768]{2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(%fusion.39)
  %transpose.1 = f32[4104,16,12,64]{3,2,1,0} transpose(%p), dimensions={1,2,0,3}
  %scatter.5 = f32[4104,16,768]{2,1,0:T(8,128)} scatter(%a, %b, %c), to_apply=%region
  %copy.9 = f32[128,12,64]{2,1,0} copy(%x)
  ROOT %fusion.3 = f32[4104,16,768]{2,1,0:T(8,128)} fusion(%p.1, %p.2), kind=kLoop
'''
    found = _moves_of_size(text, {pool})
    assert [line.split(' = ')[0] for line in found] == [
        '%copy.63', '%copy.64', '%copy-start.2', '%transpose.1']


@pytest.fixture(scope='module')
def v5e():
    """One chip of a described (not attached) v5e: its compiler is
    installed here and lays arrays out as the chip's does, which the CPU's
    cannot show. Described inside the fixture, by the one worker that runs
    this file."""
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:              # no TPU compiler on this machine
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # an executable for a described chip is written to the persistent cache
    # and cannot be read back without one: keep these out of it
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize('kv_dtype', ['f32', 'bf16'])
def test_compiled_for_the_chip_no_program_moves_the_pool(v5e, kv_dtype):
    """At GPT-1's row (12 heads of 64: 768 = 6 × 128 lanes) the TPU's
    compact layout of a pool argument is the row-major one the scatter and
    the gather use: the step and a prefill rung hold no pool-sized copy,
    where the head-major pool had 8 and 12 at this size (compiled here: no
    chip, no time)."""
    cfg = CausalLMConfig(vocab_size=512, hidden_size=768,
                         num_hidden_layers=2, num_attention_heads=12,
                         intermediate_size=1024, max_position_embeddings=64)
    with dygraph.guard():
        model = TransformerLM(cfg)
        model.eval()
        # 24 slots of 11 blocks: 264 table entries, more than one chunk
        eng = DecodeEngine(model, slots=24, block_size=16, max_blocks=521,
                           max_prompt_len=32, max_new_tokens_cap=144,
                           prefix_cache=False, kv_dtype=kv_dtype)
        assert eng.slots * eng.pool.max_blocks_per_seq > LIVE_BLOCK_CHUNK
        eng.prefill([3, 5, 7], eng.reserve_table(3, 2))
        pool = eng.pool.arrays()[0][0][0]
        assert pool.shape == (521, 16, 768)
        for bucket in (None, 32):
            text = eng.lowered(bucket, v5e).compile().as_text()
            # the pool's arguments lie row-major, and nothing moves them:
            # not the scatters, and not the step's loop over the live
            # blocks' chunks
            assert f'[521,16,768]{{2,1,0' in text
            assert _moves_of_size(text, {pool.size}) == []
            if bucket is None:
                # a chunk of rows as stored, and no per-slot dense context
                assert '[256,16,768]' in text
                assert _arrays_spanning(
                    text, eng.slots * eng.padded_context) == []


def test_compiled_for_the_chip_no_program_moves_a_state_array(v5e):
    """A retention layer's state at the published head size (8 key/value
    heads of 128: a row is 8 blocks of (8328, 128) float32, 128 values on
    the lanes) lies row-major in the step and in a prefill rung compiled
    for the chip, is updated where it lies, and no program holds a copy of
    the array or of all S rows (compiled here: no chip, no time). Shapes
    alone: the parameters and the states are described, not made."""
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.retention_lm import RetentionLM, RetentionLMConfig
    from paddle_tpu.serving.decode.kv_cache import (BlockTable,
                                                    prefill_coords)
    made = {}

    def init(key):
        with default_generator.bind_base(key):
            made['model'] = RetentionLM(RetentionLMConfig(
                vocab_size=512, hidden_size=256, intermediate_size=512,
                num_hidden_layers=2, num_attention_heads=40,
                num_key_value_heads=8, head_dim=128, rope_theta=1e6,
                gate_shift=6.0, dtype='bfloat16'))
        return {n: p.value for n, p in made['model'].named_parameters()}

    with dygraph.guard():
        default_generator.seed(3)
        shapes = jax.eval_shape(init, default_generator.base_key())
        model = made['model']
        model.eval()
        for name, p in model.named_parameters():
            p.value = shapes[name]
        eng = DecodeEngine(model, slots=6, block_size=16, max_blocks=64,
                           max_prompt_len=256, max_new_tokens_cap=64,
                           prompt_buckets=[256], prefix_cache=False)
        pool, prog = eng.pool, eng._program
        out = jax.eval_shape(
            lambda pv, *rest: prog.jitted('prefill', pool.geometry, pv, {},
                                          {}, {}, *rest),
            {n: p.value for n, p in prog._params.items()},
            np.zeros((1, 256), np.int64), None,
            prefill_coords(pool, BlockTable([], 16), 256), np.int32(0))
        pool.adopt({k: list(v) for k, v in out[3].items()}, {})
        state = pool.arrays()[0][0][0]
        assert state.shape == (7, 8, 8328, 128)
        assert (eng.layout.state_layers, eng.layout.row_layers) == (2, 0)
        assert len(pool.arrays()[0]) == 2
        for bucket in (None, 256):
            text = eng.lowered(bucket, v5e).compile().as_text()
            assert 'f32[7,8,8328,128]{3,2,1,0' in text
            assert _moves_of_size(text, {int(np.prod(state.shape))}) == []
            # no gathered copy of the S slots' rows either
            assert 'f32[6,8,8328,128]' not in text
            assert 'f32[6,8,8256,128]' not in text


def _latent_engine_of_shapes(sizes, rung, **engine):
    """(model, engine) of a bf16 `LatentMoELM.tiny` with ``sizes`` over a
    bf16 pool of blocks of 16, with one prefill rung ``rung``: parameters
    and pool are shapes alone (`jax.eval_shape` of the constructor and of
    the rung, which tells the pool its arrays), for programs that are
    compiled and never run. Call under `dygraph.guard()`."""
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.latent_moe_lm import LatentMoEConfig, LatentMoELM
    from paddle_tpu.serving.decode.kv_cache import (BlockTable,
                                                    prefill_coords)
    made = {}

    def init(key):
        with default_generator.bind_base(key):
            made['model'] = LatentMoELM(LatentMoEConfig.tiny(
                dtype='bfloat16', **sizes))
        return {n: p.value for n, p in made['model'].named_parameters()}

    default_generator.seed(3)
    shapes = jax.eval_shape(init, default_generator.base_key())
    model = made['model']
    model.eval()
    for name, p in model.named_parameters():
        p.value = shapes[name]
    eng = DecodeEngine(model, block_size=16, max_prompt_len=rung,
                       prompt_buckets=[rung], prefix_cache=False,
                       kv_dtype='bf16', **engine)
    pool, prog = eng.pool, eng._program
    out = jax.eval_shape(
        lambda pv, *rest: prog.jitted('prefill', pool.geometry, pv, {}, {},
                                      {}, *rest),
        {n: p.value for n, p in prog._params.items()},
        np.zeros((1, rung), np.int64), None,
        prefill_coords(pool, BlockTable([], 16), rung), np.int32(0))
    pool.adopt({k: list(v) for k, v in out[3].items()}, {})
    return model, eng


def test_compiled_for_the_chip_the_experts_run_the_grouped_kernel(
        v5e, monkeypatch):
    """A routed model's step and a prefill rung, compiled for the chip with
    `moe_experts`'s predicate holding as it does there: no `ragged-dot`
    custom call is left, and each expert layer holds the kernel's two
    Mosaic custom calls (gate and up in one pass, then down:
    ops/pallas_moe.py) with the caller's scope `moe/experts` in their
    `op_name`, which benchmark/lib/scoped_ops.py sums device time by. The
    step's 4 × 2 assignments and the rung's 128 × 2 are padded to whole
    row tiles (compiled here: no chip, no time). Shapes alone."""
    from paddle_tpu.ops import llm_ops
    from paddle_tpu.ops.pallas_moe import kernel_op_names
    monkeypatch.setattr(llm_ops, 'on_tpu', lambda: True)
    with dygraph.guard():
        model, eng = _latent_engine_of_shapes(
            dict(hidden_size=256, moe_intermediate_size=128,
                 max_position_embeddings=256), 128, slots=4, max_blocks=64,
            max_new_tokens_cap=64)
        expert_layers = model.cfg.num_hidden_layers \
            - model.cfg.first_k_dense_replace
        for bucket in (None, 128):
            text = eng.lowered(bucket, v5e).compile().as_text()
            assert 'ragged-dot' not in text and 'ragged_dot' not in text
            kernels = kernel_op_names(text)
            assert len(kernels) == 2 * expert_layers, kernels
            assert all('/moe/experts/' in name for name in kernels), kernels


def test_compiled_for_the_chip_the_latent_read_walks_the_live_groups(v5e):
    """A latent engine whose tables hold more than one chunk of groups (16
    slots x 9 groups of 8 blocks: 144 > 128), its step compiled for the
    chip: no array over every slot's padded context (the dense
    `pages[tables]` copy of 16 x 1,152 rows and the scores over it are
    gone), no move of the pool, and one `while` a layer under the caller's
    scope `mla/decode_read`, which benchmark/lib/scoped_ops.py sums device
    time by (compiled here: no chip, no time). Shapes alone."""
    from paddle_tpu.ops.nn_ops import live_group_chunk
    with dygraph.guard():
        model, eng = _latent_engine_of_shapes(
            dict(max_position_embeddings=2048), 1024, slots=16,
            max_blocks=1160, max_new_tokens_cap=128)
        pool = eng.pool
        per_slot, chunk = live_group_chunk(16, 16, pool.max_blocks_per_seq)
        assert (per_slot, chunk) == (9, 128) and 16 * per_slot > chunk
        rows = pool.arrays()[0][0][0]
        assert rows.shape == (1160, 16, 128)
        text = eng.lowered(None, v5e).compile().as_text()
        # what `step_context_arrays` and `pool_moves` look for, in the one
        # compile
        assert _arrays_spanning(text, 16 * eng.padded_context) == []
        assert _moves_of_size(text, {int(rows.size)}) == []
        walks = [line for line in text.splitlines()
                 if ' while(' in line and 'op_name=' in line]
        assert len(walks) == model.cfg.num_hidden_layers, walks
        assert all('/mla/decode_read/' in line.split('op_name="')[1]
                   .split('"')[0] for line in walks), walks


def test_compiled_for_the_chip_a_model_with_layer_classes(v5e, monkeypatch):
    """A model with a sliding and a full class of layer and an expert of the
    published 3,072 x 3,072 (18.9 MB a matrix: three width blocks of 6 MiB),
    its step and a prefill rung compiled for the chip: Mosaic takes the
    kernel's width axis (two custom calls an expert layer, under
    `moe/experts`) and the splash-attention kernel of the causal grouped
    prefill, no program moves either class's arrays, and the step and
    the rung hold the scopes the benchmark sums device time by (compiled
    here: no chip, no time). Shapes alone."""
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.sliding_moe_lm import (SlidingMoEConfig,
                                                  SlidingMoELM)
    from paddle_tpu.ops import llm_ops, nn_ops
    from paddle_tpu.ops.pallas_moe import kernel_op_names, width_block
    from paddle_tpu.serving.decode.kv_cache import (BlockTable,
                                                    prefill_coords)
    monkeypatch.setattr(llm_ops, 'on_tpu', lambda: True)
    monkeypatch.setattr(nn_ops, 'on_tpu', lambda: True)
    assert width_block(3072, 3072, 2) == 1024
    made = {}

    def init(key):
        with default_generator.bind_base(key):
            made['model'] = SlidingMoELM(SlidingMoEConfig.tiny(
                vocab_size=512, hidden_size=3072, intermediate_size=256,
                moe_intermediate_size=3072, num_hidden_layers=3,
                layer_types=['sliding_attention', 'sliding_attention',
                             'full_attention'],
                num_attention_heads=48, num_key_value_heads=8, head_dim=128,
                sliding_window=4096, num_experts=2, router_width=8,
                experts_held=(2, 2), max_position_embeddings=2048,
                dtype='bfloat16'))
        return {n: p.value for n, p in made['model'].named_parameters()}

    with dygraph.guard():
        default_generator.seed(3)
        shapes = jax.eval_shape(init, default_generator.base_key())
        model = made['model']
        model.eval()
        for name, p in model.named_parameters():
            p.value = shapes[name]
        # both classes' arrays past what the compiler would prefetch whole
        # into VMEM (135 and 147 MB; an array of a few MB it does, as a
        # copy-start that is no relayout)
        eng = DecodeEngine(model, slots=16, block_size=16, max_blocks=4500,
                           max_prompt_len=1024, max_new_tokens_cap=512,
                           prompt_buckets=[1024], prefix_cache=False,
                           kv_dtype='bf16')
        pool, prog = eng.pool, eng._program
        assert (pool.ring, pool.sliding_blocks) == (257, 16 * 257 + 8)
        out = jax.eval_shape(
            lambda pv, *rest: prog.jitted('prefill', pool.geometry, pv, {},
                                          {}, {}, *rest),
            {n: p.value for n, p in prog._params.items()},
            np.zeros((1, 1024), np.int64), None,
            prefill_coords(pool, BlockTable([], 16), 1024), np.int32(0))
        pool.adopt({k: list(v) for k, v in out[3].items()}, {})
        layers, _ = pool.arrays()
        assert [layers[i][0].shape for i in range(3)] == [
            (4120, 16, 1024), (4120, 16, 1024), (4500, 16, 1024)]
        for bucket in (None, 1024):
            text = eng.lowered(bucket, v5e).compile().as_text()
            assert 'ragged-dot' not in text and 'ragged_dot' not in text
            kernels = kernel_op_names(text)
            # a rung's three attentions are the splash kernel, whose call
            # the compiler leaves without an op_name (benchmark/lib/
            # layer_class_ops.py finds it by its own name)
            assert kernels.count('') == (0 if bucket is None else 3)
            assert text.count('_splash_attention_') >= kernels.count('')
            kernels = [name for name in kernels if name]
            assert len(kernels) == 2 * 2, kernels
            assert all('/moe/experts/' in name for name in kernels), kernels
            assert eng.pool_moves(bucket, v5e) == []
            scopes = ('kv/sliding_read', 'kv/decode_read') if bucket is None \
                else ('attn/sliding_prefill', 'attn/full_prefill')
            assert all(f'/{s}/' in text for s in scopes + ('attn/gate',))


def test_compiled_for_the_chip_a_hybrid_of_state_and_row_layers(v5e,
                                                                monkeypatch):
    """A model of gated short-convolution layers beside grouped-head
    attention layers at the published widths of `lfm2_8b_a1b` (hidden 2,048,
    32 query heads over 8 key/value heads of 64: a K row of 512 lanes;
    experts of 2,048 x 1,792, 7.34 MB a matrix: two width blocks of 896
    columns for gate and up, two of 1,024 for down), its step and a prefill
    rung compiled for the chip: Mosaic takes the expert kernel at the new
    shape (two custom calls an expert layer, under `moe/experts`) and the
    splash-attention kernel at heads of 64, no program copies or transposes
    an array of the K/V pool's or the state rows' size (a state array of a
    few hundred KB the compiler may prefetch whole, a copy-start that is no
    relayout), and the programs hold the scopes the benchmark sums device
    time by (compiled here: no chip, no time). Shapes alone."""
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.hybrid_conv_moe_lm import (HybridConvMoEConfig,
                                                      HybridConvMoELM)
    from paddle_tpu.ops import llm_ops, nn_ops
    from paddle_tpu.ops.pallas_moe import kernel_op_names, width_block
    from paddle_tpu.serving.decode.kv_cache import (BlockTable,
                                                    prefill_coords)
    monkeypatch.setattr(llm_ops, 'on_tpu', lambda: True)
    monkeypatch.setattr(nn_ops, 'on_tpu', lambda: True)
    assert width_block(2048, 1792, 2) == 896
    assert width_block(1792, 2048, 2) == 1024
    made = {}

    def init(key):
        with default_generator.bind_base(key):
            made['model'] = HybridConvMoELM(HybridConvMoEConfig.tiny(
                vocab_size=512, hidden_size=2048, intermediate_size=256,
                moe_intermediate_size=1792, num_hidden_layers=3,
                layer_types=['conv', 'full_attention', 'conv'],
                num_attention_heads=32, num_key_value_heads=8,
                num_experts=4, num_experts_per_tok=2,
                max_position_embeddings=2048, dtype='bfloat16'))
        return {n: p.value for n, p in made['model'].named_parameters()}

    with dygraph.guard():
        default_generator.seed(3)
        shapes = jax.eval_shape(init, default_generator.base_key())
        model = made['model']
        model.eval()
        for name, p in model.named_parameters():
            p.value = shapes[name]
        eng = DecodeEngine(model, slots=16, block_size=16, max_blocks=9000,
                           max_prompt_len=512, max_new_tokens_cap=512,
                           prompt_buckets=[512], prefix_cache=False,
                           kv_dtype='bf16')
        pool, prog = eng.pool, eng._program
        out = jax.eval_shape(
            lambda pv, *rest: prog.jitted('prefill', pool.geometry, pv, {},
                                          {}, {}, *rest),
            {n: p.value for n, p in prog._params.items()},
            np.zeros((1, 512), np.int64), None,
            prefill_coords(pool, BlockTable([], 16), 512), np.int32(0))
        pool.adopt({k: list(v) for k, v in out[3].items()}, {})
        layers, _ = pool.arrays()
        # a row of 8 heads of 64 is 4 whole tiles; a state row is float32
        # whatever the pool's kv_dtype
        assert [[(a.shape, str(a.dtype)) for a in layers[i]]
                for i in range(3)] == [
            [((17, 1, 2, 2048), 'float32')],
            [((9000, 16, 512), 'bfloat16')] * 2,
            [((17, 1, 2, 2048), 'float32')]]
        for bucket in (None, 512):
            text = eng.lowered(bucket, v5e).compile().as_text()
            assert 'ragged-dot' not in text and 'ragged_dot' not in text
            kernels = kernel_op_names(text)
            # the rung's one attention is the splash kernel, whose call the
            # compiler leaves without an op_name
            assert kernels.count('') == (0 if bucket is None else 1)
            assert text.count('_splash_attention_') >= kernels.count('')
            kernels = [name for name in kernels if name]
            assert len(kernels) == 2 * 2, kernels
            assert all('/moe/experts/' in name for name in kernels), kernels
            moves = [line for line in eng.pool_moves(bucket, v5e)
                     if 'copy-start' not in line]
            assert moves == []
            scopes = ('conv/step', 'kv/decode_read') if bucket is None \
                else ('conv/prefill', 'attn/full_prefill')
            assert all(f'/{s}/' in text for s in scopes + ('moe/experts',))
            other = ('conv/prefill',) if bucket is None else ('conv/step',)
            assert not any(f'/{s}/' in text for s in other)


# the four callers of the grouped read at their published widths: (query
# heads, key/value heads, query rows a slot, head_dim, span, the caller's
# scope); the latent read's rows are rank 512 + rope 64 in 640 lanes
GROUP_READS = {
    'full_read': (48, 8, 1, 128, 0, 'kv/decode_read'),
    'sliding_read': (48, 8, 1, 128, 4096, 'kv/sliding_read'),
    'block_read': (32, 4, 4, 128, 0, 'kv/block_read'),
    'latent_read': (32, 1, 1, 192, 0, 'mla/decode_read'),
    'heads_of_64': (32, 8, 1, 64, 0, 'kv/decode_read'),
}


@pytest.mark.parametrize('name', sorted(GROUP_READS))
def test_compiled_for_the_chip_a_grouped_read_is_one_kernel_under_its_scope(
        v5e, monkeypatch, name):
    """Each caller of the grouped read over bf16 pools, compiled for the chip
    with the kernel's predicate holding as it does there
    (ops/pallas_group_read.py) and the op reached as a model's forward
    reaches it, a jit of its own under the caller's scope: one Mosaic custom
    call with the scope in its `op_name`, which the benchmark sums device
    time by, and no `while` (the XLA walk's loop). 16 slots of 1,056-block
    tables (a 257-block ring for the sliding read) at the published widths
    (compiled here: no chip, no time). Shapes alone."""
    from paddle_tpu.ops import llm_ops, nn_ops
    from paddle_tpu.ops.pallas_moe import kernel_op_names
    heads, groups, rows, head_dim, span, scope = GROUP_READS[name]
    monkeypatch.setattr(nn_ops, 'group_read_kernel_applies',
                        lambda q, pages: True)
    slots, width = 16, (span // 16 + 1 if span else 1056)
    lanes = 640 if name == 'latent_read' else groups * head_dim
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=v5e)
    pages = sds((1 + slots * width, 16, lanes), jnp.bfloat16)
    tables = sds((slots, width), jnp.int32)
    ctx = sds((slots,), jnp.int32)

    def read(q, k, v, tables, ctx):
        if name == 'latent_read':
            w_kvb = jnp.zeros((512, heads * 256), q.dtype)
            return llm_ops.mla_decode_attention(
                q, k, tables, ctx, w_kvb, qk_nope_dim=128, v_dim=128)
        if name == 'block_read':
            return nn_ops.paged_attention(q, k, v, tables, ctx,
                                          block_window=True, kv_heads=groups)
        return nn_ops.paged_attention(q, k, v, tables, ctx, kv_heads=groups,
                                      span=span)
    op = jax.jit(read)

    def scoped(*args):
        with jax.named_scope(scope):
            return op(*args)
    q = sds((slots, 1, heads, head_dim) if name == 'latent_read'
            else (slots, heads, rows, head_dim) if rows > 1
            else (slots, heads, head_dim), jnp.bfloat16)
    text = jax.jit(scoped).lower(q, pages, pages, tables, ctx).compile() \
        .as_text()
    names = kernel_op_names(text)
    assert len(names) == 1 and f'/{scope}/' in names[0], names
    assert not [line for line in text.splitlines() if ' while(' in line]


def test_compiled_for_the_chip_a_hybrid_of_ssm_expert_and_row_layers(
        v5e, monkeypatch):
    """A `nemotron_h` model (Mamba-2 layers, latent relu² experts, grouped
    attention) at the published Mamba head size and state width (heads of
    64, 128 state values, a filter of 4 taps, chunk 128) and a latent expert
    of 128 x 256: its step and a prefill rung compiled for the chip. Mosaic
    takes the expert kernel's relu² epilogue (two custom calls an expert
    layer, under `moe/experts`); the step walks the slots a row at a time
    and holds no array of the state's size (its temporaries are below one
    state array; a gather and scatter of the batch's rows held 4.13 GB at
    the cell's size, several arrays' worth); no program moves the pool; the
    scopes the benchmark sums device time by are there (compiled here: no
    chip, no time). Shapes alone."""
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.hybrid_ssm_moe_lm import (HybridSSMMoEConfig,
                                                     HybridSSMMoELM)
    from paddle_tpu.ops import llm_ops, nn_ops, pallas_group_read
    from paddle_tpu.ops.pallas_moe import kernel_op_names
    from paddle_tpu.serving.decode.kv_cache import (BlockTable,
                                                    prefill_coords)
    for module in (llm_ops, nn_ops, pallas_group_read):
        monkeypatch.setattr(module, 'on_tpu', lambda: True)
    made = {}

    def init(key):
        with default_generator.bind_base(key):
            made['model'] = HybridSSMMoELM(HybridSSMMoEConfig.tiny(
                vocab_size=512, hidden_size=256, head_dim=128,
                num_attention_heads=2, num_key_value_heads=1,
                mamba_num_heads=8, mamba_head_dim=64, n_groups=2,
                ssm_state_size=128, chunk_size=128, moe_latent_size=128,
                moe_intermediate_size=256,
                moe_shared_expert_intermediate_size=512,
                max_position_embeddings=1024, dtype='bfloat16'))
        return {n: p.value for n, p in made['model'].named_parameters()}

    with dygraph.guard():
        default_generator.seed(3)
        shapes = jax.eval_shape(init, default_generator.base_key())
        model = made['model']
        model.eval()
        for name, p in model.named_parameters():
            p.value = shapes[name]
        eng = DecodeEngine(model, slots=32, block_size=16, max_blocks=2056,
                           max_prompt_len=512, max_new_tokens_cap=512,
                           prompt_buckets=[512], prefix_cache=False,
                           kv_dtype='bf16')
        pool, prog = eng.pool, eng._program
        out = jax.eval_shape(
            lambda pv, *rest: prog.jitted('prefill', pool.geometry, pv, {},
                                          {}, {}, *rest),
            {n: p.value for n, p in prog._params.items()},
            np.zeros((1, 512), np.int64), None,
            prefill_coords(pool, BlockTable([], 16), 512), np.int32(0))
        pool.adopt({k: list(v) for k, v in out[3].items()}, {})
        layers, _ = pool.arrays()
        # 8 heads of 64 x 128 state values and 3 x 1,024 window values, the
        # heads on the lanes: (8,576, 8) float32 a row
        state = ((33, 64 * 128 + 3 * 1024 // 8, 8), 'float32')
        assert [[(a.shape, str(a.dtype)) for a in layers[i]]
                for i in range(3)] == [
            [state], [state], [((2056, 16, 128), 'bfloat16')] * 2]
        state_bytes = int(np.prod(state[0])) * 4
        for bucket in (None, 512):
            compiled = eng.lowered(bucket, v5e).compile()
            text = compiled.as_text()
            assert 'ragged-dot' not in text and 'ragged_dot' not in text
            kernels = [name for name in kernel_op_names(text) if name]
            experts = [name for name in kernels if '/moe/experts/' in name]
            assert len(experts) == 2 * 2, kernels
            # (a pool of 8 MB the compiler may prefetch whole at this size:
            # a copy-start that is no relayout)
            assert [line for line in eng.pool_moves(bucket, v5e)
                    if 'copy-start' not in line] == []
            scopes = ('ssm/step', 'kv/decode_read') if bucket is None \
                else ('ssm/prefill', 'attn/full_prefill')
            assert all(f'/{s}/' in text
                       for s in scopes + ('moe/experts', 'moe/latent'))
            other = 'ssm/prefill' if bucket is None else 'ssm/step'
            assert f'/{other}/' not in text
            if bucket is None:
                temp = compiled.memory_analysis().temp_size_in_bytes
                assert temp < state_bytes, (temp, state_bytes)
