"""The grouped read as a pallas TPU kernel (ops/pallas_group_read.py), run
here in pallas interpret mode at the four callers' geometries, cut to a few
slots: a full layer's and a sliding layer's one-token read (8 key/value heads
of 128, 6 query rows each), the block read of a window model (4 heads, 8 × 4
rows each), the latent read (one head of 640 lanes, keys and values one
array) and a grouped read at heads of 64. Against the XLA walk
(`nn_ops._live_group_walk`) and a float64 softmax of the stored rows, over
slots of unequal context, idle slots on the scratch block and a list whose
live entries end at and across a chunk's edge; garbage in the dead rows
moves no bit; the ops reach the kernel where its predicate holds; and the
engine counts the blocks the kernel copies. Compiled for the chip:
tests/framework/test_kv_pool_layout.py; run on it: chip_smoke.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import dygraph
from paddle_tpu.ops import llm_ops, nn_ops, pallas_group_read
from paddle_tpu.ops.pallas_group_read import group_read
from paddle_tpu.serving.decode import engine as engine_module
from paddle_tpu.serving.decode.engine import DecodeEngine

BS = 16
SCALE = 0.088
# (query heads, key/value heads, rows a slot, head_dim, span, one array for
# keys and values, the caller's scope)
GEOMETRY = {
    'full_read': (48, 8, 1, 128, 0, False, 'kv/decode_read'),
    'sliding_read': (48, 8, 1, 128, 256, False, 'kv/sliding_read'),
    'block_read': (32, 4, 4, 128, 0, False, 'kv/block_read'),
    'latent_read': (32, 1, 1, 640, 0, True, 'mla/decode_read'),
    'heads_of_64': (32, 8, 1, 64, 0, False, 'kv/decode_read'),
}
# 1 position, a group's edge and one past it, the whole table (24 blocks),
# and an idle slot (its table the scratch block, context 1)
CONTEXTS = [1, 128, 129, 384, 1]
MAX_BLOCKS = 24
# of the output's largest value: float32 differs from the float64 softmax
# by the order of a float32 sum; bf16 by the probabilities' rounding to bf16
# for the second matmul (2^-9 a weight) and the result's
TOLERANCE = {'float32': 2e-5, 'bfloat16': 2e-2}


def _case(name, dtype, contexts=CONTEXTS, max_blocks=MAX_BLOCKS, seed=0):
    """(q, k_pages, v_pages, context_lens, live, tables, ring) of a decode
    batch at geometry ``name``: slot i at ``contexts[i]``, the last slot idle
    on the scratch block. A sliding layer's slots hold rings of span / 16 +
    1 blocks, the others tables of ``max_blocks``."""
    h, g, kq, d, span, shared, _ = GEOMETRY[name]
    rng = np.random.RandomState(seed + len(name))
    s = len(contexts)
    lanes = -(-g * d // 128) * 128
    width = span // BS + 1 if span else max_blocks
    blocks = 1 + s * width + 3
    tables = 1 + rng.permutation(blocks - 1)[:s * width].reshape(s, width)
    tables[-1] = 0
    tables = tables.astype(np.int32)
    k = jnp.asarray(rng.randn(blocks, BS, lanes), dtype)
    v = k if shared else jnp.asarray(rng.randn(blocks, BS, lanes), dtype)
    q = jnp.asarray(rng.randn(s, h, kq, d), dtype)
    ctx = jnp.asarray(contexts, jnp.int32)
    if span:
        live = nn_ops.live_ring_group_list(tables, ctx, BS, span)
    else:
        live = nn_ops.live_group_list(tables, ctx, BS)
    return q, k, v, ctx, tuple(live), tables, span


def _attended(tables, contexts, span, shape):
    """(blocks, block) bool: the pool rows some slot attends."""
    seen = np.zeros(shape, bool)
    for row, c in zip(tables, contexts):
        for p in range(max(c - span, 0) if span else 0, c):
            seen[row[(p // BS) % len(row)], p % BS] = True
    return seen


def _dense(q, k, v, tables, contexts, g, span):
    """softmax(q·k)·v in float64 over each slot's attended positions, from
    the rows as stored: q (S, H, K, D), query head i on key/value head
    i // (H/G), a row's first G·D lanes its heads."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    s, h, kq, d = q.shape
    out = np.zeros(q.shape)
    for i, (row, c) in enumerate(zip(tables, contexts)):
        at = np.arange(max(c - span, 0) if span else 0, c)
        blocks = row[(at // BS) % len(row)]
        keys = k[blocks, at % BS, :g * d].reshape(-1, g, d)
        values = v[blocks, at % BS, :g * d].reshape(-1, g, d)
        for j in range(h):
            head = j // (h // g)
            scores = q[i, j] @ keys[:, head].T * SCALE          # (K, T)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            out[i, j] = (p / p.sum(-1, keepdims=True)) @ values[:, head]
    return out


def _kernel(q, k, v, ctx, live, g, span):
    return group_read(q, k, v, ctx, live, g, SCALE, span, interpret=True)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('name', sorted(GEOMETRY))
def test_the_kernel_equals_the_walk_and_the_float64_softmax(name, dtype):
    q, k, v, ctx, live, tables, span = _case(name, dtype)
    g = GEOMETRY[name][1]
    got = np.asarray(_kernel(q, k, v, ctx, live, g, span), np.float32)
    walk = np.asarray(nn_ops._live_group_walk(q, k, v, ctx, live, g, SCALE,
                                              span), np.float32)
    want = _dense(q, k, v, tables, CONTEXTS, g, span)
    assert got.shape == q.shape and np.isfinite(got).all()
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= TOLERANCE[dtype] * scale
    assert np.abs(got - walk).max() <= TOLERANCE[dtype] * scale


@pytest.mark.parametrize('name', sorted(GEOMETRY))
def test_garbage_in_the_rows_no_slot_attends_moves_no_bit(name):
    """Every pool row no slot attends (past a context inside its last group,
    a ring's rows outside the span, the scratch block's rows past the first,
    blocks no table names) is drawn again, then made NaN and inf: the
    kernel's result is the same to the bit, for the masked rows get exactly
    zero mass and their values are zeroed."""
    q, k, v, ctx, live, tables, span = _case(name, 'float32')
    h, g, _, _, _, shared, _ = GEOMETRY[name]
    seen = _attended(tables, CONTEXTS, span, k.shape[:2])[..., None]
    want = np.asarray(_kernel(q, k, v, ctx, live, g, span))
    rng = np.random.RandomState(1)
    for garbage in (rng.randn(*k.shape) * 1e3, np.full(k.shape, np.nan),
                    np.full(k.shape, np.inf)):
        k2 = jnp.asarray(np.where(seen, k, garbage), jnp.float32)
        v2 = k2 if shared else jnp.asarray(np.where(seen, v, -garbage),
                                           jnp.float32)
        got = np.asarray(_kernel(q, k2, v2, ctx, live, g, span))
        assert np.array_equal(got, want)


@pytest.mark.parametrize('contexts,n_live', [
    ([128, 1, 1], 3), ([256, 1, 1], 4), ([256, 129, 1], 5),
    ([256, 256, 129, 1], 7)])
def test_live_entries_ending_at_and_across_a_chunk_edge(monkeypatch,
                                                        contexts, n_live):
    """A chunk of the walk cut to 4 groups, tables of 2 groups a slot (the
    last slot idle): the list is 8 entries, its live ones end below, at and
    one past the first chunk's edge, and one short of the second's; the
    kernel visits the live entries alone and reads what the walk and the
    float64 softmax read."""
    monkeypatch.setattr(nn_ops, 'LIVE_GROUP_CHUNK', 4)
    q, k, v, ctx, live, tables, _ = _case('heads_of_64', 'float32',
                                          contexts, max_blocks=16)
    assert live[0].shape == (8, 8) and int(live[3]) == n_live
    got = np.asarray(_kernel(q, k, v, ctx, live, 8, 0))
    want = _dense(q, k, v, tables, contexts, 8, 0)
    walk = np.asarray(nn_ops._live_group_walk(q, k, v, ctx, live, 8, SCALE))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= TOLERANCE['float32'] * scale
    assert np.abs(got - walk).max() <= TOLERANCE['float32'] * scale


def test_the_predicate_is_the_chip_and_a_float_pool():
    """Off the chip the walk; on it (pretended: the kernel module's own
    `on_tpu`) bf16 and float32 queries and pools, nothing else. The tests
    that pretend the chip for the expert kernel patch `nn_ops.on_tpu` and
    `llm_ops.on_tpu`: neither reaches this predicate, so the programs they
    compile keep the walk."""
    sds = jax.ShapeDtypeStruct
    bf16, f32 = sds((2, 4, 1, 8), jnp.bfloat16), sds((2, 4, 1, 8), jnp.float32)
    assert not pallas_group_read.group_read_kernel_applies(bf16, bf16)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(nn_ops, 'on_tpu', lambda: True)
        mp.setattr(llm_ops, 'on_tpu', lambda: True)
        assert not pallas_group_read.group_read_kernel_applies(bf16, bf16)
        mp.setattr(pallas_group_read, 'on_tpu', lambda: True)
        for q, pool in ((bf16, bf16), (f32, f32), (f32, bf16)):
            assert pallas_group_read.group_read_kernel_applies(q, pool)
        f16 = sds((2, 4, 1, 8), jnp.float16)
        int8 = sds((2, 4, 1, 8), jnp.int8)
        assert not pallas_group_read.group_read_kernel_applies(f16, bf16)
        assert not pallas_group_read.group_read_kernel_applies(bf16, int8)


def _op_call(name, q, k, v, ctx, tables):
    """The op a caller dispatches for geometry ``name``, on the same rows."""
    h, g, kq, d, span, shared, _ = GEOMETRY[name]
    if name == 'latent_read':
        # the published latent widths: rank 512 + rope 64 in 640 lanes,
        # heads of 128 nope and 128 v; the query's first 192 lanes
        w_kvb = jax.random.normal(jax.random.PRNGKey(2), (512, h * 256),
                                  q.dtype) * 0.05
        return llm_ops.mla_decode_attention(
            q[..., :192].transpose(0, 2, 1, 3), k, tables, ctx, w_kvb,
            qk_nope_dim=128, v_dim=128, sm_scale=SCALE)
    if name == 'block_read':
        return nn_ops.paged_attention(q, k, v, tables, ctx, sm_scale=SCALE,
                                      block_window=True, kv_heads=g)
    return nn_ops.paged_attention(q[:, :, 0], k, v, tables, ctx,
                                  sm_scale=SCALE, kv_heads=g, span=span)


@pytest.mark.parametrize('name', sorted(GEOMETRY))
def test_the_ops_reach_the_kernel_where_its_predicate_holds(monkeypatch,
                                                            name):
    """`paged_attention`'s three grouped branches and the latent read's
    K = 1 form call the kernel where `group_read_kernel_applies` holds
    (patched true here, the kernel in interpret mode), and return what the
    walk returns where it does not."""
    q, k, v, ctx, _, tables, _ = _case(name, 'float32')
    want = np.asarray(_op_call(name, q, k, v, ctx, tables))
    calls = []

    def kernel(*args):
        calls.append(args)
        return group_read(*args, interpret=True)
    monkeypatch.setattr(nn_ops, 'group_read_kernel_applies',
                        lambda q, pages: True)
    monkeypatch.setattr(nn_ops, 'group_read', kernel)
    got = np.asarray(_op_call(name, q, k, v, ctx, tables))
    assert len(calls) == 1
    assert np.abs(got - want).max() <= TOLERANCE['float32'] * np.abs(
        want).max()


def _engine(model, **kw):
    return DecodeEngine(model, max_prompt_len=16, prompt_buckets=[16],
                        prefix_cache=False, **kw)


def _model(kind):
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.block_diffusion_lm import (BlockDiffusionMoEConfig,
                                                      BlockDiffusionMoELM)
    from paddle_tpu.models.hybrid_conv_moe_lm import (HybridConvMoEConfig,
                                                      HybridConvMoELM)
    from paddle_tpu.models.latent_moe_lm import LatentMoEConfig, LatentMoELM
    from paddle_tpu.models.sliding_moe_lm import (SlidingMoEConfig,
                                                  SlidingMoELM)
    default_generator.seed(5)
    model = {'latent': lambda: LatentMoELM(LatentMoEConfig.tiny(
                 max_position_embeddings=512)),
             'window': lambda: BlockDiffusionMoELM(
                 BlockDiffusionMoEConfig.tiny()),
             'sliding': lambda: SlidingMoELM(SlidingMoEConfig.tiny()),
             'hybrid': lambda: HybridConvMoELM(HybridConvMoEConfig.tiny()),
             }[kind]()
    model.eval()
    return model


# contexts of a step's slots, an idle slot's 1 among them
WALKED = [1, 4, 5, 32, 33, 127, 128, 129]


@pytest.mark.parametrize('kind', ['latent', 'window', 'sliding', 'hybrid'])
def test_the_engine_counts_the_blocks_the_kernel_copies(monkeypatch, kind):
    """Where the kernel runs (its predicate patched true in ops/nn_ops.py,
    which an engine asks of its pool's dtype as it is built)
    `_blocks_walked` is, over each read's layers, the live groups times the
    blocks a group holds, by hand, with no rounding to whole chunks: blocks
    of 4 and tables of 32 blocks make a group 32 blocks (128 keys), of
    which the contexts hold 9; a sliding layer's ring of 3 blocks makes its
    group 3 blocks (12 keys), of which the window of 8 touches 9. Off the
    chip it is the walk's whole chunks of groups, above that count."""
    with dygraph.guard():
        model = _model(kind)

        def engine():
            return _engine(model, slots=len(WALKED), block_size=4,
                           max_blocks=len(WALKED) * 32 + 8,
                           max_new_tokens_cap=112)
        walking = engine()
        assert walking.pool.max_blocks_per_seq == 32
        walk = walking._blocks_walked(WALKED)
        monkeypatch.setattr(nn_ops, 'group_read_kernel_applies',
                            lambda q, pages: True)
        copied = engine()._blocks_walked(WALKED)
    full, sliding = walking.layout.full_layers, walking.layout.sliding_layers
    assert (full, sliding) == {'latent': (3, 0), 'window': (3, 0),
                               'sliding': (1, 3), 'hybrid': (1, 0)}[kind]
    groups = sum(-(-c // 128) for c in WALKED)
    assert groups == 1 + 1 + 1 + 1 + 1 + 1 + 1 + 2
    # a sliding layer attends [max(0, c - 8), c): groups of 12 keys
    # (c - 1) // 12 through max(c - 8, 0) // 12
    window = sum((c - 1) // 12 - max(c - 8, 0) // 12 + 1 for c in WALKED)
    assert window == 1 + 1 + 1 + 1 + 1 + 2 + 1 + 1
    assert copied == full * 9 * 32 + sliding * 9 * 3
    # the walk: chunks of min(128, 8 slots x 1 group) = 8 groups, and of
    # min(128, 8 slots x 2 groups) = 16 a ring
    assert walk == full * 16 * 32 + sliding * 16 * 3


def test_a_latent_step_books_the_kernels_blocks(monkeypatch):
    """`decode_kv_blocks_read` and the `engine/step` span's `kv_blocks` of a
    latent engine's step where the kernel runs: layers × the live groups of
    32 blocks (128 keys) its slots' contexts hold, 1 + 1 + 2 + 1 for
    contexts 5, 128, 129 (the token fed included) and an idle slot's 1,
    where the walk would read a whole chunk of 8."""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import metrics as m
    # the engine counts as the kernel would copy; its read stays the walk
    monkeypatch.setattr(engine_module, 'group_walk_pads', lambda dtype: False)
    with dygraph.guard():
        model = _model('latent')
        engine = _engine(model, slots=4, block_size=4, max_blocks=4 * 64 + 8,
                         max_new_tokens_cap=240)
        layers = model.cfg.num_hidden_layers
        tables = [engine.reserve_table(16, 240) for _ in range(3)]
        for t, c in zip(tables, (4, 127, 128)):
            t.context_len = c
        with obs.telemetry_guard(True):
            obs.reset()
            before = m.decode_kv_blocks_read.value
            engine.decode_step([1, 1, 1, None], tables + [None])
            booked = m.decode_kv_blocks_read.value - before
            spans = [e for e in obs.tracer.snapshot()['traceEvents']
                     if e.get('ph') == 'X' and e['name'] == 'engine/step']
            obs.reset()
    assert booked == layers * (1 + 1 + 2 + 1) * 32
    assert [e['args']['kv_blocks'] for e in spans] == [booked]
