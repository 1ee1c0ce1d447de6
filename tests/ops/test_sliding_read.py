"""The ops of a model with layer classes (ops/nn_ops.py, ops/llm_ops.py,
ops/pallas_moe.py): the grouped one-token read against repeated heads, its
sliding form over a ring against a dense masked softmax, the causal grouped
prefill with and without a span against the same, `moe_experts` over a held
range, the gate, and the pallas kernel's width axis in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import llm_ops, nn_ops, pallas_moe
from paddle_tpu.ops.pallas_moe import ROW_TILE as TM

BS = 4


def _dense(q, keys, values, lo, hi, scale):
    """softmax(q·k)·v over positions [lo, hi) in float64: q (H, D), keys and
    values (T, G, D), query head i on key/value head i // (H/G)."""
    h, g = q.shape[0], keys.shape[1]
    out = np.zeros(q.shape, np.float64)
    for i in range(h):
        k = keys[lo:hi, i // (h // g)].astype(np.float64)
        v = values[lo:hi, i // (h // g)].astype(np.float64)
        s = k @ q[i].astype(np.float64) * scale
        p = np.exp(s - s.max())
        out[i] = (p / p.sum()) @ v
    return out


def _pool(rng, blocks, g, d):
    w = -(-g * d // 128) * 128
    k = np.zeros((blocks, BS, w), np.float32)
    v = np.zeros_like(k)
    k[..., :g * d] = rng.randn(blocks, BS, g * d)
    v[..., :g * d] = rng.randn(blocks, BS, g * d)
    return k, v


@pytest.mark.parametrize('heads,groups', [(4, 4), (6, 2), (8, 1)])
def test_the_grouped_one_token_read_equals_repeated_heads(heads, groups):
    """q's H heads over a pool of G: the same numbers as the pool's rows
    repeated to H heads and read head for head, whatever the contexts."""
    rng = np.random.RandomState(heads)
    d, slots, mb = 8, 3, 5
    k_pages, v_pages = _pool(rng, 1 + slots * mb, groups, d)
    tables = 1 + np.arange(slots * mb, dtype=np.int32).reshape(slots, mb)
    contexts = np.asarray([1, 9, 20], np.int32)
    q = rng.randn(slots, heads, d).astype(np.float32)
    got = np.asarray(nn_ops.paged_attention(
        q, k_pages, v_pages, tables, contexts, sm_scale=0.5,
        kv_heads=groups))
    assert got.shape == q.shape
    for s in range(slots):
        rows = lambda pages: pages[tables[s]].reshape(mb * BS, -1)[
            :, :groups * d].reshape(-1, groups, d)
        want = _dense(q[s], rows(k_pages), rows(v_pages), 0, contexts[s],
                      0.5)
        assert np.abs(got[s] - want).max() < 1e-5
    if heads == groups:
        # and the pool-of-q's-heads read of today gives the same
        plain = np.asarray(nn_ops.paged_attention(
            q, k_pages, v_pages, tables, contexts, sm_scale=0.5))
        assert np.abs(got - plain).max() < 1e-5


@pytest.mark.parametrize('span', [8, 12])
@pytest.mark.parametrize('contexts', [[1, 5, 8], [9, 12, 13], [17, 40, 64],
                                      [100, 3, 33]])
def test_the_sliding_read_masks_by_position_over_a_ring(span, contexts):
    """Each slot's keys live in a ring of span / block + 1 blocks, position
    p in ring block (p // block) mod ring; what the ring has overwritten and
    what it has not yet written lie in live groups and must get zero mass:
    the rows are made so large there that any leak shows."""
    rng = np.random.RandomState(span + sum(contexts))
    heads, groups, d = 4, 2, 8
    ring = span // BS + 1
    slots = len(contexts)
    k_pages, v_pages = _pool(rng, 1 + slots * ring, groups, d)
    tables = 1 + np.arange(slots * ring, dtype=np.int32).reshape(slots, ring)
    keys = rng.randn(slots, max(contexts), groups, d).astype(np.float32)
    values = rng.randn(*keys.shape).astype(np.float32)
    for s, c in enumerate(contexts):
        # written in order, as prefill and decode do: later wins
        k_pages[tables[s]] = 50.0
        v_pages[tables[s]] = 1e4
        for p in range(c):
            block, off = tables[s][p // BS % ring], p % BS
            k_pages[block, off, :groups * d] = keys[s, p].reshape(-1)
            v_pages[block, off, :groups * d] = values[s, p].reshape(-1)
    q = rng.randn(slots, heads, d).astype(np.float32)
    got = np.asarray(nn_ops.paged_attention(
        q, k_pages, v_pages, tables, np.asarray(contexts, np.int32),
        sm_scale=0.7, kv_heads=groups, span=span))
    for s, c in enumerate(contexts):
        want = _dense(q[s], keys[s], values[s], max(0, c - span), c, 0.7)
        assert np.abs(got[s] - want).max() < 1e-4, (s, c)


def test_live_ring_groups_name_the_rings_blocks_by_position():
    tables = np.asarray([[3, 4, 5], [6, 7, 8]], np.int32)
    ids, slot, first, n = (np.asarray(a) for a in nn_ops.live_ring_group_list(
        tables, np.asarray([30, 2], np.int32), BS, 8))
    # a group is the whole ring of 3 blocks (12 keys); slot 0 at context 30
    # sees positions 22..29: groups 1 and 2; slot 1 group 0
    assert n == 3 and list(slot[:3]) == [0, 0, 1]
    assert list(first[:3]) == [12, 24, 0]
    # group j's blocks are (3j + i) mod 3 of the slot's ring
    assert ids[0].tolist() == [3, 4, 5] and ids[2].tolist() == [6, 7, 8]
    assert (ids[3:] == 0).all() and (first[3:] > 10 ** 6).all()


@pytest.mark.parametrize('span', [0, 5, 16, 64])
@pytest.mark.parametrize('length', [16, 64])
def test_the_causal_grouped_prefill_equals_a_dense_softmax(
        monkeypatch, length, span):
    """In chunks of 8 query rows against chunks of 16 keys, so that a
    64-row rung folds several chunk pairs and skips those its mask
    empties."""
    monkeypatch.setattr(nn_ops, '_CAUSAL_PREFILL_QUERY_CHUNK', 8)
    monkeypatch.setattr(nn_ops, '_CAUSAL_PREFILL_KEY_CHUNK', 16)
    rng = np.random.RandomState(length + span)
    heads, groups, d = 6, 2, 8
    q = rng.randn(1, heads, length, d).astype(np.float32)
    k = rng.randn(1, groups, length, d).astype(np.float32)
    v = rng.randn(1, groups, length, d).astype(np.float32)
    got = np.asarray(nn_ops.paged_prefill_attention(
        q, k, v, None, None, None, sm_scale=0.4, kv_heads=groups, span=span))
    assert got.shape == q.shape
    keys, values = k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2)
    for i in (0, 1, 7, 8, 15, length - 1):
        want = _dense(q[0, :, i], keys, values,
                      max(0, i - span + 1) if span else 0, i + 1, 0.4)
        assert np.abs(got[0, :, i] - want).max() < 1e-5, i


@pytest.mark.parametrize('span', [0, 100, 256])
def test_the_splash_kernel_computes_the_same_prefill(monkeypatch, span):
    """The chip's path (the stock pallas splash-attention kernel under a
    causal or a local mask, a key/value head's query heads a call) in
    interpret mode against the XLA formulation; off the chip the predicate
    chooses the latter."""
    monkeypatch.setattr(nn_ops, '_SPLASH_BLOCK', 128)
    rng = np.random.RandomState(span)
    q = jnp.asarray(rng.randn(1, 6, 512, 128), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 512, 128), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 512, 128), jnp.float32)
    assert not nn_ops.causal_kernel_applies(q)
    got = np.asarray(nn_ops._splash_prefill_attention(q, k, v, span, 0.09,
                                                      interpret=True))
    want = np.asarray(nn_ops._causal_prefill_attention(q, k, v, span, 0.09))
    assert got.shape == want.shape == (1, 6, 512, 128)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_an_edge_off_by_one_shows():
    """Row i of a sliding layer sees exactly the keys j with 0 <= i - j <
    span: with a key of overwhelming score just outside, a span longer by
    one reads another answer."""
    rng = np.random.RandomState(0)
    length, span = 16, 5
    q = np.ones((1, 2, length, 4), np.float32)
    k = rng.randn(1, 1, length, 4).astype(np.float32) * 0.1
    v = rng.randn(1, 1, length, 4).astype(np.float32)
    k[0, 0, 3] = 20.0                    # position 3: row 7 sees it, row 8 not
    out = lambda s: np.asarray(nn_ops.paged_prefill_attention(
        q, k, v, None, None, None, sm_scale=1.0, kv_heads=1, span=s))
    exact, longer = out(span), out(span + 1)
    assert np.abs(exact[0, :, 7] - v[0, 0, 3]).max() < 1e-3
    assert np.abs(exact[0, :, 8] - v[0, 0, 3]).max() > 0.1
    assert np.abs(longer[0, :, 8] - v[0, 0, 3]).max() < 1e-3


def test_attributes_the_ops_refuse():
    q = np.zeros((2, 4, 8), np.float32)
    pages = np.zeros((4, BS, 128), np.float32)
    tables = np.zeros((2, 3), np.int32)
    with pytest.raises(ValueError, match='span'):
        nn_ops.paged_attention(q, pages, pages, tables, np.ones(2, np.int32),
                               span=8)
    with pytest.raises(ValueError, match='int8'):
        nn_ops.paged_attention(q, pages, pages, tables, np.ones(2, np.int32),
                               k_scales=np.zeros((4, BS, 2), np.float32),
                               v_scales=np.zeros((4, BS, 2), np.float32),
                               kv_heads=2)
    q4 = np.zeros((1, 4, 8, 8), np.float32)
    with pytest.raises(ValueError, match='kv_heads'):
        nn_ops.paged_prefill_attention(q4, q4[:, :2], q4[:, :2], None, None,
                                       None, kv_heads=4)


# -- experts over a held range ------------------------------------------------

def _expert_inputs(rng, tokens, width, k, h, f, held):
    x = rng.randn(tokens, h).astype(np.float32)
    ids = np.stack([rng.permutation(width)[:k] for _ in range(tokens)]
                   ).astype(np.int32)
    weights = rng.rand(tokens, k).astype(np.float32)
    w = [rng.randn(held, *shape).astype(np.float32) * 0.3
         for shape in ((h, f), (h, f), (f, h))]
    return x, ids, weights, w


@pytest.mark.parametrize('first,count', [(0, 4), (4, 4), (2, 3), (7, 1)])
def test_a_held_range_gives_its_experts_part_and_no_more(first, count):
    rng = np.random.RandomState(first * 10 + count)
    x, ids, weights, w = _expert_inputs(rng, 13, 8, 3, 16, 8, count)
    out, counts = llm_ops.moe_experts(x, ids, weights, *w,
                                      experts_held=(first, count))
    want = np.zeros_like(x, dtype=np.float64)
    rows = np.zeros(count, np.int64)
    for t in range(13):
        for j in range(3):
            e = ids[t, j] - first
            if 0 <= e < count:
                g, u = x[t] @ w[0][e], x[t] @ w[1][e]
                want[t] += weights[t, j] * ((g / (1 + np.exp(-g)) * u)
                                            @ w[2][e])
                rows[e] += 1
    assert np.asarray(counts).tolist() == rows.tolist()
    assert np.abs(np.asarray(out) - want).max() < 1e-4 * max(
        1.0, np.abs(want).max())


def test_holding_every_expert_is_the_op_of_today_bit_for_bit():
    rng = np.random.RandomState(5)
    x, ids, weights, w = _expert_inputs(rng, 21, 8, 2, 16, 8, 8)
    plain, c0 = llm_ops.moe_experts(x, ids, weights, *w)
    held, c1 = llm_ops.moe_experts(x, ids, weights, *w, experts_held=(0, 8))
    assert np.array_equal(np.asarray(plain), np.asarray(held))
    assert np.array_equal(np.asarray(c0), np.asarray(c1))
    with pytest.raises(ValueError, match='experts_held'):
        llm_ops.moe_experts(x, ids, weights, *w, experts_held=(0, 4))


def test_the_gate_is_a_sigmoid_in_float32():
    rng = np.random.RandomState(1)
    x = rng.randn(3, 5, 8).astype(np.float32)
    g = rng.randn(3, 5, 8).astype(np.float32) * 4
    got = np.asarray(llm_ops.sigmoid_gate(x, g))
    assert np.abs(got - x / (1 + np.exp(-g))).max() < 1e-6
    low = llm_ops.sigmoid_gate(jnp.asarray(x, jnp.bfloat16),
                               jnp.asarray(g, jnp.bfloat16))
    assert low.dtype == jnp.bfloat16


# -- the pallas kernel's width axis, in interpret mode -------------------------

@pytest.mark.parametrize('h,f,limit,blocks', [
    (128, 512, 128 * 128 * 4, (4, 1)),        # gate/up in 4, down whole
    (256, 256, 256 * 128 * 4, (2, 2)),        # both passes in 2
    (128, 384, 128 * 128 * 4, (3, 1))])
def test_a_wide_expert_streams_in_width_blocks(h, f, limit, blocks):
    """An expert wider than one block is cut along its output width, the
    width outermost in the grid: the same numbers as `lax.ragged_dot`, with
    held experts' padding rows beside."""
    assert (f // pallas_moe.width_block(h, f, 4, limit),
            h // pallas_moe.width_block(f, h, 4, limit)) == blocks
    rng = np.random.RandomState(h + f)
    counts = np.asarray([0, 5, TM + 3, 0, 40], np.int32)
    m, tokens = int(counts.sum()), 31
    x = jnp.asarray(rng.randn(tokens, h), jnp.float32)
    w = [jnp.asarray(rng.randn(len(counts), *s) * 0.2, jnp.float32)
         for s in ((h, f), (h, f), (f, h))]
    source = jnp.asarray(rng.randint(0, tokens, m + 9), jnp.int32)
    got = np.asarray(pallas_moe.expert_ffn(
        x, source, jnp.asarray(counts), *w, interpret=True,
        block_bytes=limit))[:m]
    rows = x[source[:m]]
    g = jax.lax.ragged_dot(rows, w[0], jnp.asarray(counts))
    u = jax.lax.ragged_dot(rows, w[1], jnp.asarray(counts))
    want = np.asarray(jax.lax.ragged_dot(jax.nn.silu(g) * u, w[2],
                                         jnp.asarray(counts)))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_the_routed_cells_sizes_keep_their_one_block_a_visit():
    """At kanana2's and sdar's 2,048 x 768 in bf16 a block is the whole
    matrix in both passes (the grid is the visits, as before the width
    axis); at 3,072 x 3,072 a block is 1,024 columns, 6 MiB."""
    assert pallas_moe.width_block(2048, 768, 2) == 768
    assert pallas_moe.width_block(768, 2048, 2) == 2048
    assert pallas_moe.width_block(3072, 3072, 2) == 1024
    assert 3072 * 1024 * 2 == pallas_moe.WEIGHT_BLOCK_BYTES
    rng = np.random.RandomState(0)
    counts = np.asarray([3, 0, 7], np.int32)
    x = jnp.asarray(rng.randn(10, 2048), jnp.bfloat16)
    w = [jnp.asarray(rng.randn(3, *s) * 0.05, jnp.bfloat16)
         for s in ((2048, 768), (2048, 768), (768, 2048))]
    source = jnp.asarray(rng.randint(0, 10, 10), jnp.int32)
    got = np.asarray(pallas_moe.expert_ffn(x, source, jnp.asarray(counts),
                                           *w, interpret=True))[:10]
    rows = x[source]
    f32 = jnp.float32
    g = jax.lax.ragged_dot(rows, w[0], jnp.asarray(counts),
                           preferred_element_type=f32)
    u = jax.lax.ragged_dot(rows, w[1], jnp.asarray(counts),
                           preferred_element_type=f32)
    want = np.asarray(jax.lax.ragged_dot(
        (jax.nn.silu(g) * u).astype(jnp.bfloat16), w[2], jnp.asarray(counts),
        preferred_element_type=f32))
    assert np.abs(got - want).max() <= 1e-2 * np.abs(want).max()
