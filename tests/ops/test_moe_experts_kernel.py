"""The routed experts' grouped matmul as a pallas kernel (ops/pallas_moe.py),
run here in pallas interpret mode: against a per-expert float32 numpy loop,
at the group sizes that meet a tile's edges, and against the op's
`lax.ragged_dot` formulation on the same inputs. Compiled for the chip:
tests/framework/test_kv_pool_layout.py; run on it: chip_smoke.py."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import llm_ops, pallas_moe
from paddle_tpu.ops.pallas_moe import ROW_TILE as TM

H, F = 32, 16
# of the output's largest value: float32 operands differ by the order of a
# float32 sum; bf16 operands by the hidden activations' rounding to bf16
# (2^-9 a value, 16 of them summed) where the loop keeps them in float32
TOLERANCE = {'float32': 2e-5, 'bfloat16': 1e-2}

GROUPS = {
    'sizes_0_1_tm-1_tm_tm+1': [0, 1, TM - 1, TM, TM + 1, 0, 3],
    'every_row_to_one_expert': [0, 0, 2 * TM + 44, 0],
    'rows_no_multiple_of_tm': [17, 0, 90, 5, 61, 27],
    'a_tile_shared_by_every_expert': [6] * 16,
    'whole_tiles_each': [TM, 2 * TM, TM],
    'empty_first_and_last': [0, TM + 1, 0, 0, TM - 1, 0],
}


def _weights(rng, e, dtype):
    return tuple(jnp.asarray(rng.randn(*shape) * 0.3, dtype)
                 for shape in ((e, H, F), (e, H, F), (e, F, H)))


def _loop(x, source, owner, gate, up, down):
    """Assignment by assignment in float32 numpy: what `expert_ffn` owes."""
    x, gate, up, down = (np.asarray(a, np.float32)
                         for a in (x, gate, up, down))
    out = np.zeros((len(source), x.shape[1]), np.float32)
    for i, (row, e) in enumerate(zip(source, owner)):
        g, u = x[row] @ gate[e], x[row] @ up[e]
        out[i] = (g / (1.0 + np.exp(-g)) * u) @ down[e]
    return out


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('case', sorted(GROUPS))
def test_the_kernel_equals_a_per_expert_loop(case, dtype):
    counts = np.asarray(GROUPS[case], np.int32)
    rng = np.random.RandomState(len(case))
    m, tokens = int(counts.sum()), 23
    x = jnp.asarray(rng.randn(tokens, H), dtype)
    gate, up, down = _weights(rng, len(counts), dtype)
    source = rng.randint(0, tokens, m).astype(np.int32)
    owner = np.repeat(np.arange(len(counts)), counts)
    got = np.asarray(pallas_moe.expert_ffn(
        x, jnp.asarray(source), jnp.asarray(counts), gate, up, down,
        interpret=True))
    assert got.dtype == np.float32 and got.shape == (-(-m // TM) * TM, H)
    want = _loop(x, source, owner, gate, up, down)
    assert np.isfinite(got[:m]).all()
    assert np.abs(got[:m] - want).max() <= TOLERANCE[dtype] * np.abs(
        want).max()


@pytest.mark.parametrize('case', sorted(GROUPS))
def test_an_experts_block_is_fetched_once_and_every_tile_is_visited(case):
    """The visits in the order the grid takes them: experts in order, each
    one's tiles consecutive (pallas fetches a block again only when its
    index changes), every pair shares a row, and together they cover every
    row once."""
    counts = np.asarray(GROUPS[case], np.int32)
    m = -(-int(counts.sum()) // TM) * TM
    (offsets, group_ids, tile_ids), n = pallas_moe.group_visits(
        jnp.asarray(counts), m)
    offsets, group_ids, tile_ids, n = (np.asarray(a) for a in (
        offsets, group_ids, tile_ids, n))
    assert group_ids.shape == tile_ids.shape == (m // TM + len(counts) - 1,)
    assert offsets.tolist() == [0] + np.cumsum(counts).tolist()
    group_ids, tile_ids = group_ids[:n], tile_ids[:n]
    assert (np.diff(group_ids) >= 0).all() and (np.diff(tile_ids) >= 0).all()
    assert sorted(set(group_ids)) == np.flatnonzero(counts).tolist()
    assert sorted(set(tile_ids)) == list(range(m // TM))
    covered = np.zeros(m, np.int32)
    for g, t in zip(group_ids, tile_ids):
        lo, hi = max(offsets[g], t * TM), min(offsets[g + 1], (t + 1) * TM)
        assert lo < hi
        covered[lo:hi] += 1
    assert covered[:counts.sum()].tolist() == [1] * int(counts.sum())
    assert not covered[counts.sum():].any()


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('top_k', [6, 8])
def test_the_op_through_the_kernel_equals_its_ragged_dot_formulation(
        monkeypatch, top_k, dtype):
    """`moe_experts` with the predicate made to hold (the kernel in
    interpret mode) against the same op as it runs on the CPU, same inputs:
    the same counts, which add up to T·k, and the same sum."""
    rng = np.random.RandomState(top_k)
    tokens, e = 37, 12            # 222 or 296 assignments: no whole tiles
    x = jnp.asarray(rng.randn(tokens, H), dtype)
    gate, up, down = _weights(rng, e, dtype)
    ids = np.stack([rng.permutation(e - 1)[:top_k] for _ in range(tokens)])
    ids[ids == 4] = e - 1                        # expert 4 is given no row
    ids = jnp.asarray(ids, jnp.int32)
    weights = jnp.asarray(rng.rand(tokens, top_k), jnp.float32)
    assert not llm_ops.experts_kernel_applies(x, gate)
    want, want_counts = llm_ops.moe_experts(x, ids, weights, gate, up, down)
    monkeypatch.setattr(llm_ops, 'on_tpu', lambda: True)
    monkeypatch.setattr(llm_ops, 'expert_ffn', functools.partial(
        pallas_moe.expert_ffn, interpret=True))
    assert llm_ops.experts_kernel_applies(x, gate)
    got, counts = llm_ops.moe_experts(x, ids, weights, gate, up, down)
    assert got.dtype == x.dtype and got.shape == (tokens, H)
    counts = np.asarray(counts)
    assert counts.tolist() == np.asarray(want_counts).tolist()
    assert counts.sum() == tokens * top_k and counts[4] == 0
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= TOLERANCE[dtype] * np.abs(want).max()


def test_the_predicate_is_the_backend_and_the_dtypes(monkeypatch):
    def sds(dtype):
        return jax.ShapeDtypeStruct((4, H), dtype)
    monkeypatch.setattr(llm_ops, 'on_tpu', lambda: True)
    assert llm_ops.experts_kernel_applies(sds(jnp.bfloat16),
                                          sds(jnp.bfloat16))
    assert llm_ops.experts_kernel_applies(sds(jnp.float32), sds(jnp.float32))
    assert not llm_ops.experts_kernel_applies(sds(jnp.float32),
                                              sds(jnp.bfloat16))
    assert not llm_ops.experts_kernel_applies(sds(jnp.float16),
                                              sds(jnp.float16))
    monkeypatch.setattr(llm_ops, 'on_tpu', lambda: False)
    assert not llm_ops.experts_kernel_applies(sds(jnp.bfloat16),
                                              sds(jnp.bfloat16))
