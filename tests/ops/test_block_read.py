"""The ops a window model (block diffusion) adds to the paged reads
(ops/nn_ops.py): `paged_attention`'s block read (every row of a slot's K
fed rows at the same extent, query heads grouped over the pool's key/value
heads, a walk over the live groups), `paged_prefill_attention`'s block mask,
and `diffusion_pick` (ops/llm_ops.py), each against a plain dense form."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import llm_ops, nn_ops

BS, MB, NB = 4, 6, 40            # a table spans 24 positions


def _dense(q, k_rows, v_rows, extent, scale):
    """q (H, K, D) against rows (T, G, D): every row sees positions <
    extent; query head i reads key/value head i // (H/G)."""
    h, g = q.shape[0], k_rows.shape[1]
    out = np.zeros_like(q, dtype=np.float64)
    for i in range(h):
        k, v = k_rows[:extent, i // (h // g)], v_rows[:extent, i // (h // g)]
        s = q[i].astype(np.float64) @ k.T.astype(np.float64) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[i] = (p / p.sum(-1, keepdims=True)) @ v.astype(np.float64)
    return out


def _pool(rng, g, d, lanes=None):
    """K and V pools of rows of g·d values in `lanes` lanes, and per-slot
    tables over distinct blocks (block 0 is scratch)."""
    lanes = lanes or g * d
    k = rng.standard_normal((NB, BS, lanes)).astype('float32')
    v = rng.standard_normal((NB, BS, lanes)).astype('float32')
    return k, v


def _rows_of(pages, table, g, d):
    return pages[table].reshape(-1, pages.shape[-1])[:, :g * d].reshape(
        -1, g, d)


@pytest.mark.parametrize('heads,groups,lanes', [(8, 2, None), (4, 4, None),
                                                (6, 3, 128)])
def test_block_read_is_dense_attention_at_one_extent_a_slot(heads, groups,
                                                            lanes):
    """Ragged contexts (one slot of a single block, one that fills its
    table, an idle one on the scratch block): all K rows of a slot see the
    same extent, the K just written included, and grouped heads read their
    key/value head."""
    rng = np.random.default_rng(3)
    d, kq, s = 8, 4, 4
    k_pages, v_pages = _pool(rng, groups, d, lanes)
    tables = np.zeros((s, MB), np.int32)
    tables[0] = [7, 3, 9, 1, 12, 30]
    tables[1] = [5, 0, 0, 0, 0, 0]
    tables[3] = [21, 22, 23, 24, 25, 26]
    extents = np.asarray([14, 4, 1, 24], np.int32)       # slot 2 idle
    q = rng.standard_normal((s, heads, kq, d)).astype('float32')
    got = np.asarray(nn_ops.paged_attention(
        q, k_pages, v_pages, tables, extents, sm_scale=0.3,
        block_window=True, kv_heads=groups))
    assert got.shape == q.shape
    for slot in (0, 1, 3):
        want = _dense(q[slot], _rows_of(k_pages, tables[slot], groups, d),
                      _rows_of(v_pages, tables[slot], groups, d),
                      int(extents[slot]), 0.3)
        np.testing.assert_allclose(got[slot], want, rtol=2e-5, atol=2e-6)
    assert np.isfinite(got[2]).all()


def test_grouped_pool_reads_as_a_pool_of_repeated_heads():
    """4 key/value heads under 8 query heads give what a pool that holds
    every query head's own copy of its key/value head gives."""
    rng = np.random.default_rng(5)
    d, heads, groups, s = 8, 8, 4, 2
    k_pages, v_pages = _pool(rng, groups, d)
    rep = heads // groups

    def repeated(pages):
        return np.repeat(pages.reshape(NB, BS, groups, d), rep, 2).reshape(
            NB, BS, heads * d)

    tables = np.asarray([[2, 4, 6, 8, 10, 12], [3, 5, 0, 0, 0, 0]], np.int32)
    extents = np.asarray([22, 7], np.int32)
    q = rng.standard_normal((s, heads, 4, d)).astype('float32')
    grouped = nn_ops.paged_attention(q, k_pages, v_pages, tables, extents,
                                     block_window=True, kv_heads=groups)
    full = nn_ops.paged_attention(q, repeated(k_pages), repeated(v_pages),
                                  tables, extents, block_window=True,
                                  kv_heads=heads)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(full),
                               rtol=1e-5, atol=1e-6)


def test_stale_rows_past_the_extent_have_no_mass():
    """What lies in a live group past a slot's extent (a denoising
    forward's provisional rows, a freed block's; any K, any finite V)
    reaches nothing: its mass is exactly zero."""
    rng = np.random.default_rng(9)
    k_pages, v_pages = _pool(rng, 2, 8)
    tables = np.asarray([[4, 5, 6, 0, 0, 0]], np.int32)
    q = rng.standard_normal((1, 4, 4, 8)).astype('float32')
    args = dict(block_window=True, kv_heads=2)
    before = np.asarray(nn_ops.paged_attention(
        q, k_pages, v_pages, tables, np.asarray([6], np.int32), **args))
    k_pages[5, 2:], v_pages[5, 2:] = np.nan, 1e30
    k_pages[6], v_pages[6], k_pages[0] = -1e30, -1e30, np.nan
    after = np.asarray(nn_ops.paged_attention(
        q, k_pages, v_pages, tables, np.asarray([6], np.int32), **args))
    np.testing.assert_array_equal(before, after)


def test_live_group_list_walks_only_live_groups():
    tables = np.arange(1, 1 + 3 * 70, dtype=np.int32).reshape(3, 70)
    lens = np.asarray([130, 1, 1100], np.int32)
    ids, slot, first, n_live = (np.asarray(x) for x in
                                nn_ops.live_group_list(tables, lens, 16))
    assert nn_ops.live_group_blocks(16, 70) == 8 and ids.shape[1] == 8
    assert int(n_live) == 2 + 1 + 9          # ceil(len / 128) groups a slot
    np.testing.assert_array_equal(slot[:12], [0, 0, 1] + [2] * 9)
    np.testing.assert_array_equal(first[:4], [0, 128, 0, 0])
    np.testing.assert_array_equal(ids[1], tables[0, 8:16])
    # a table's last group is ragged: its tail names the scratch block
    np.testing.assert_array_equal(ids[11], list(tables[2, 64:70]) + [0, 0])
    assert (ids[int(n_live):] == 0).all()
    assert (first[int(n_live):] == 70 * 16).all()
    assert nn_ops.live_group_chunk(3, 16, 70) == (9, 27)
    assert nn_ops.live_group_chunk(128, 16, 160) == (20, 128)


def test_int8_pool_has_no_block_read():
    k = np.zeros((4, 4, 16), np.int8)
    sc = np.zeros((4, 4, 2), np.float32)
    with pytest.raises(ValueError, match='int8'):
        nn_ops.paged_attention(
            np.zeros((1, 2, 4, 8), np.float32), k, k,
            np.zeros((1, 2), np.int32), np.ones(1, np.int32), sc, sc,
            block_window=True, kv_heads=2)


@pytest.mark.parametrize('length,block_len', [(12, 4), (10, 4), (8, 2)])
def test_prefill_block_mask_is_causal_across_blocks_and_full_inside(
        length, block_len):
    rng = np.random.default_rng(1)
    heads, groups, d = 4, 2, 8
    q = rng.standard_normal((1, heads, length, d)).astype('float32')
    k = rng.standard_normal((1, groups, length, d)).astype('float32')
    v = rng.standard_normal((1, groups, length, d)).astype('float32')
    pages = np.zeros((2, 4, groups * d), np.float32)     # never read
    got = np.asarray(nn_ops.paged_prefill_attention(
        q, k, v, pages, pages, np.zeros((1, 1), np.int32), sm_scale=0.5,
        block_len=block_len))
    for row in range(length):
        extent = min((row // block_len + 1) * block_len, length)
        want = _dense(q[0, :, row:row + 1], k[0].transpose(1, 0, 2),
                      v[0].transpose(1, 0, 2), extent, 0.5)
        np.testing.assert_allclose(got[0, :, row:row + 1], want, rtol=2e-5,
                                   atol=2e-6)


def test_prefill_without_block_len_is_the_causal_read_it_was():
    rng = np.random.default_rng(2)
    q, k, v = (rng.standard_normal((1, 2, 4, 8)).astype('float32')
               for _ in range(3))
    pages_k = np.zeros((2, 4, 16), np.float32)
    pages_v = np.zeros((2, 4, 16), np.float32)
    pages_k[1] = k[0].transpose(1, 0, 2).reshape(4, 16)
    pages_v[1] = v[0].transpose(1, 0, 2).reshape(4, 16)
    got = np.asarray(nn_ops.paged_prefill_attention(
        q, k, v, pages_k, pages_v, np.asarray([[1]], np.int32)))
    for row in range(4):
        want = _dense(q[0, :, row:row + 1], k[0].transpose(1, 0, 2),
                      v[0].transpose(1, 0, 2), row + 1, 1.0)
        np.testing.assert_allclose(got[0, :, row:row + 1], want, rtol=2e-5,
                                   atol=2e-6)


def test_diffusion_pick_is_argmax_and_its_softmax_probability_without_mask():
    rng = np.random.default_rng(4)
    rows = rng.standard_normal((3, 4, 50)).astype('float32') * 3
    rows[0, 0, 7] = 100.0                      # MASK would win: left out
    rows[1, 2, 11] = rows[1, 2, 30] = 50.0     # equal maxima: the first
    ids, conf = (np.asarray(x) for x in llm_ops.diffusion_pick(
        jnp.asarray(rows), mask_token_id=7))
    assert ids.dtype == np.int32 and conf.dtype == np.float32
    plain = rows.astype(np.float64).copy()
    plain[..., 7] = -np.inf
    e = np.exp(plain - plain.max(-1, keepdims=True))
    np.testing.assert_array_equal(ids, plain.argmax(-1))
    np.testing.assert_allclose(conf, (e / e.sum(-1, keepdims=True)).max(-1),
                               rtol=1e-5)
    assert ids[0, 0] != 7 and ids[1, 2] == 11
    assert conf[1, 2] == pytest.approx(0.5, rel=1e-4)
    # no MASK id given: nothing is left out
    ids, _ = llm_ops.diffusion_pick(jnp.asarray(rows))
    assert int(ids[0, 0]) == 7
