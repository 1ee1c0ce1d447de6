"""The routed experts' feed-forward as a pallas TPU grouped matmul (ROADMAP
S10): rows sorted by expert against per-expert weights, each expert's block
read from HBM once and whole.

The plan is megablox's (`jax.experimental.pallas.ops.tpu.megablox.gmm`): the
sorted rows are cut into tiles of `ROW_TILE`; the grid visits every (row tile,
expert) pair that shares a row, in row order, so a tile that holds rows of
several experts is visited once for each and an expert whose rows span
several tiles is visited once a tile. A visit multiplies the whole tile by
the expert's block and stores the rows that are the expert's. Pallas fetches
a block again only when its index changes from one visit to the next: an
expert's weights cross once, however many tiles its rows span, and a tile's
rows once, however many experts share it.

What is this repo's own:

- a block spans the expert's whole CONTRACTION, (h, ·) or (f, ·): no
  contraction split, so no accumulator. Where the matrix is at most
  `WEIGHT_BLOCK_BYTES` the block is the whole matrix and the grid is the
  visits alone: one DMA of 3.1 MB a weight where the published sizes are
  2,048 × 768 in bf16. A wider expert (3,072 × 3,072: 18.9 MB a matrix) is
  cut along its OUTPUT width into blocks of at most that size, and the
  width is the grid's OUTER axis: for each width block the visits are
  walked whole, so an expert's weights still cross once, an output tile is
  still revisited only by consecutive visits, and what crosses again is
  the rows, once a width block;
- gate and up run in ONE pass over the rows (`expert_ffn`): two weight
  blocks a visit, `silu(g) · u` in float32 in the kernel, a result in the
  rows' dtype: the two float32 (m, f) intermediates never reach HBM;
- the visits are worked out once a call and shared by both passes.

A visit costs the MXU the same for 1 row as for 128 (it loads each 128 × 128
weight tile whatever streams through it), about half of what the block's DMA
costs: HBM binds at every row count, and a larger tile only adds rows that
belong to no visit's expert (m + (E − 1) · tile rows are multiplied in all).
On the chip 256 was no better at any of the cells' row counts and 512 much
worse (PERF.md §6, PR 33): the tile is a constant.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32

# rows a visit multiplies: the MXU's own tile on a v5e
ROW_TILE = 128
# the most one weight block holds: the DMA of a 12 MB block was the limit
# found at PR 33 (PERF.md section 6); 6 MiB is 3,072 × 1,024 in bf16, and
# two such blocks a visit (gate and up), double-buffered, are 25 MB of VMEM
WEIGHT_BLOCK_BYTES = 6 << 20


def group_visits(counts, m):
    """The (row tile, expert) pairs of ``m`` sorted rows (a multiple of
    `ROW_TILE`), of which expert e owns ``counts[e]`` (rows past their sum are
    padding and nobody's): int32 ``offsets`` (E + 1,), and per visit the
    expert ``group_ids`` and the tile ``tile_ids``, both (m / tile + E − 1,),
    the most there can be, of which the first ``n_visits`` (a traced
    scalar) are real. Every tile holds a real row, so each is visited."""
    counts = jnp.asarray(counts, jnp.int32)
    n_experts = counts.shape[0]
    tm = ROW_TILE
    most = m // tm + n_experts - 1
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    tiles = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    visit0 = jnp.cumsum(tiles) - tiles          # an expert's first visit
    group_ids = jnp.repeat(jnp.arange(n_experts, dtype=jnp.int32), tiles,
                           total_repeat_length=most)
    tile_ids = first[group_ids] + jnp.arange(most, dtype=jnp.int32) \
        - visit0[group_ids]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    # (the entries past the real visits name no tile: kept inside the array)
    return (offsets, group_ids, jnp.minimum(tile_ids, m // tm - 1)), \
        tiles.sum()


def width_block(k, n, itemsize, limit=None):
    """Columns of an expert's (k, n) matrix one block holds: all n where the
    matrix is within ``limit`` bytes (`WEIGHT_BLOCK_BYTES`), else the
    largest multiple of 128 that divides n and keeps the block within it
    (128 where none does)."""
    limit = WEIGHT_BLOCK_BYTES if limit is None else int(limit)
    if k * n * itemsize <= limit:
        return n
    fits = [w for w in range(128, n, 128)
            if n % w == 0 and k * w * itemsize <= limit]
    return max(fits) if fits else (128 if n % 128 == 0 else n)


def _visit(visit_axis, offsets, group_ids, tile_ids, x_ref, *refs):
    """One visit: the tile times the expert's block(s), float32
    accumulation; with two blocks `silu(x · w0) · (x · w1)` in float32. The
    expert's rows are stored, the tile's other rows stay as they are."""
    *w_refs, o_ref = refs
    visit = pl.program_id(visit_axis)
    group = group_ids[visit]
    x = x_ref[...]
    y = [jnp.dot(x, w[...], preferred_element_type=_F32) for w in w_refs]
    y = y[0] if len(y) == 1 else jax.nn.silu(y[0]) * y[1]
    row = tile_ids[visit] * ROW_TILE + lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)
    mine = (row >= offsets[group]) & (row < offsets[group + 1])
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def grouped_matmul(rows, weights, visits, *, out_dtype, interpret=False,
                   block_bytes=None):
    """rows (m, k) sorted by expert × each of ``weights`` (E, k, n), over
    ``visits`` = `group_visits(counts, m)`: (m, n) in ``out_dtype``. One
    weight: the product. Two: `silu(rows · w0) ⊙ (rows · w1)`. A row of no
    expert (the padding) comes back undefined. ``block_bytes`` (the tests')
    stands in for `WEIGHT_BLOCK_BYTES`."""
    (offsets, group_ids, tile_ids), n_visits = visits
    m, k = rows.shape
    n = weights[0].shape[2]
    tm = ROW_TILE
    tn = width_block(k, n, max(w.dtype.itemsize for w in weights),
                     block_bytes)

    if tn == n:
        # the whole matrix a block: the grid is the visits
        grid, visit_axis = (n_visits,), 0

        def tile(visit, offsets, group_ids, tile_ids):
            return tile_ids[visit], 0

        def block(visit, offsets, group_ids, tile_ids):
            return group_ids[visit], 0, 0

        out_tile = tile
    else:
        # the width outermost: every visit for one block of columns, then
        # the next block
        grid, visit_axis = (n // tn, n_visits), 1

        def tile(j, visit, offsets, group_ids, tile_ids):
            return tile_ids[visit], 0

        def block(j, visit, offsets, group_ids, tile_ids):
            return group_ids[visit], 0, j

        def out_tile(j, visit, offsets, group_ids, tile_ids):
            return tile_ids[visit], j

    out_item = jnp.dtype(out_dtype).itemsize
    resident = (tm * k * rows.dtype.itemsize + tm * tn * out_item
                + sum(k * tn * w.dtype.itemsize for w in weights))
    return pl.pallas_call(
        functools.partial(_visit, visit_axis),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, k), tile)]
            + [pl.BlockSpec((None, k, tn), block) for _ in weights],
            out_specs=pl.BlockSpec((tm, tn), out_tile),
            grid=grid),
        # an output tile is revisited by consecutive visits: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',) * len(grid),
            # every block double-buffered, the float32 products beside them
            vmem_limit_bytes=2 * resident + len(weights) * tm * tn * 4
            + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(weights),
            transcendentals=m * n * (len(weights) - 1),
            bytes_accessed=(n // tn) * m * k * rows.dtype.itemsize
            + m * n * out_item
            + sum(w.size * w.dtype.itemsize for w in weights)),
        interpret=interpret,
    )(offsets, group_ids, tile_ids, rows, *weights)


def expert_ffn(x, source, counts, w_gate, w_up, w_down, *, interpret=False,
               block_bytes=None):
    """w_down,e(silu(x_s · w_gate,e) ⊙ (x_s · w_up,e)) for the assignments
    sorted by expert: ``source`` (m,) the row of x (T, h) behind each,
    ``counts`` (E,) how many each expert owns. Returns (≥ m, h) float32:
    the m results in the order of ``source``, then the padding to whole
    tiles, undefined. bf16 or float32 operands as stored, float32
    accumulation, the hidden (m, f) in x's dtype."""
    m = source.shape[0]
    padded = -(-m // ROW_TILE) * ROW_TILE
    rows = x[jnp.pad(source, (0, padded - m))]
    visits = group_visits(counts, padded)
    hidden = grouped_matmul(rows, (w_gate, w_up), visits, out_dtype=x.dtype,
                            interpret=interpret, block_bytes=block_bytes)
    return grouped_matmul(hidden, (w_down,), visits, out_dtype=_F32,
                          interpret=interpret, block_bytes=block_bytes)


def kernel_op_names(hlo_text):
    """The `op_name` of every Mosaic custom call in a compiled program's
    text: what says the kernel is there and under which scope a profiler
    trace will book it (chip_smoke.py, tests/framework/
    test_kv_pool_layout.py). '' for a call the compiler left without one
    (the stock splash-attention kernel's, ops/nn_ops.py)."""
    names = (re.search(r'op_name="([^"]*)"', line)
             for line in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line)
    return [found.group(1) if found else '' for found in names]
