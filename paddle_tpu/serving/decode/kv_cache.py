"""Paged KV-cache pool: fixed-size blocks, free-list allocator, per-request
block tables (docs/SERVING.md "Stateful decode").

Every pool array is rows of ONE token: a layer's K and V are each
(num_blocks, block_size, row_lanes(n_heads·head_dim)), a latent (MLA) layer's
one array (num_blocks, block_size, row_lanes(width)), int8 row scales
(num_blocks, block_size, n_heads). A token's row of all heads is contiguous
and a whole number of the TPU's 128-lane tiles (GPT-1: 768 = 6 × 128; any
other width is padded up to one, :func:`row_lanes`), so row-major is the
compact layout the compiler gives a program's arguments and results, and the
paged scatter and the reads (the lockstep step's walk over the live blocks,
the page gather) take its rows as they lie: no engine program relayouts the
pool. The head-major (n_heads, num_blocks,
block_size, head_dim) of the stock pallas paged kernel put head_dim 64 under
the 128 lanes; the compiler then made the block axis minor and every engine
program copied every layer's whole pool into the scatter's layout and back,
48 copies of 201 MB a call at GPT-1 size (PERF.md section 6, PR 27).
Head-major survives as the WIRE format alone: `read_blocks` /
`read_block_scales` return and `write_whole_blocks` takes
(n_heads, nb, block_size, head_dim) / (n_heads, nb, block_size) host arrays,
transposed at the boundary over the nb blocks moved, never over the pool.

Why paged: a contiguous per-request KV buffer must be sized for the WORST
CASE length at admission, so short requests strand memory and long ones
fragment it. Blocks fix both — a request holds exactly
``ceil(context / block_size)`` blocks (plus its reservation), the free list
recycles them the moment a slot finishes, and the attention ops read
through the block table so the cache never moves.

Sizing happens ONCE at engine start (`PADDLE_TPU_DECODE_{SLOTS,BLOCK_SIZE,
MAX_BLOCKS}`); per-layer arrays allocate lazily, before the engine's first
call (head count / head dim are discovered from an abstract trace of the
model's K projections, so the pool needs no model config duplicated into
it) or by the first whole-block injection.

Block 0 is the **scratch block**: never allocated, the padding target for
inactive decode slots and short block tables. Writes to it are harmless
(masked by context lengths — and masked probabilities are *exactly* zero,
so stale block contents can never bleed between requests;
tests/ops/test_paged_attention.py proves reuse-after-free is clean).

Functional updates: jax arrays are immutable, so writes are scatters over a
DONATED pool array — XLA updates in place instead of copying the pool per
token (the same donation lever as PR 1's executor). There are two writers.
An engine call (engine.py) is ONE jitted program: the pool's arrays are its
donated arguments, `write_prefill` / `write_tokens` run inside its trace on
a pool built over the traced arrays (:meth:`KVCachePool.over`), and the
engine adopts the arrays the program returns; every write coordinate is a
traced array (:func:`prefill_coords`, :func:`decode_coords`), so a program
serves every request of its shape. Between engine calls `write_whole_blocks`
(handoff, reinjection) scatters eagerly through the small jitted kernels
below.

Recurrent state (docs/SERVING.md "Recurrent state"): a retention layer
(models/retention_lm.py) caches no row per token but ONE fixed-size float32
state per request, whatever its context: a state layer is one array
(state_rows, G, P, d), a row a request, ``state_rows = slots + 1`` with row 0
the scratch row of idle slots. A row is G blocks of (P, d) with d values on
the lanes (ops/llm_ops.py::retention_state_rows), so row-major is the compact
layout here too. It is not paged: a table carries its row
(`BlockTable.state_row`, taken and returned with the table by a free list of
its own, :class:`StateRows`), a prefill overwrites the whole row and every
step advances it in place, inside the same donated programs. The block
allocator still books the request's lengths; no HBM stands behind a block of
a model whose every layer is a state layer.

Hybrid models (docs/SERVING.md "Hybrid models"): state layers and row
layers in ONE model (models/hybrid_conv_moe_lm.py: gated short convolutions
beside grouped-head attention). Nothing above changes: a table is given
its blocks AND a row at admission (`new_table`, all or nothing) and returns
both; the pool's depth is bought for the row layers alone, ``state_rows``
is ``slots + 1`` wherever ANY layer is a state layer; the row layers hold
``kv_dtype`` rows beside the float32 state rows; a state of any block shape
goes through `CacheContext.attend_state` with the two ops that make and
advance it handed in.

Layer classes (docs/SERVING.md "Layer classes"): a model may bound what a
layer's queries see to the last ``span`` positions (sliding attention). Such
a layer gives back what lies behind its span: the K/V layers of a pool are
of two CLASSES under this one manager. The FULL class is everything above: a
table that grows with the context. The SLIDING class holds per request a
RING of ``ring`` = ceil(span / block_size) + 1 blocks (`BlockTable.ring`,
`layout.ring_blocks`): position p lives in ring block ``(p // block_size)
mod ring``, a block is overwritten in place when the ring comes round, and
the request never holds more however long it grows. Each class has its own
free list (`KVCachePool.allocator`, `KVCachePool.sliding`), its own depth of
array (``num_blocks``, ``sliding_blocks``), its own scratch block 0 and its
own columns in :func:`prefill_coords` / :func:`decode_coords`
(``sliding_tables``, ``sliding_write_ids``, ``sliding_write_offs``). Which
layer is of which class the model says as it attends
(`CacheContext.attend(span=)`) and in its layout (layout.py). A row that
has left the span is never read: the reads mask by a key's POSITION,
rebuilt from its place in the ring and the context length. The ring is
taken WHOLE at admission, as many blocks as the request's prompt and budget
can ever touch (at most ``ring``), like the full class's reservation and
for its reason: a generation never dies of a missing block
mid-flight, and the free list's count is what admission can promise.

Quantized storage (``kv_dtype``, docs/SERVING.md "Tiered KV cache"): the
pools hold payload at ``f32`` (exact, the default), ``bf16`` (half the
bytes; decode reads cast back to f32 — an exact roundtrip for every
representable value), or ``int8``
(quarter the bytes: one symmetric int8 row + one f32 scale per
(head, position) row via quant_collectives.rowwise_quantize — the PR 9/15
sparse-push codec; KV rows and embedding rows are the same shape problem).
Quantization happens AT THE WRITE (prefill block scatter, decode token
scatter, speculative window, whole-block handoff injection) and
dequantization AT THE READ inside `paged_attention` /
`paged_prefill_attention`, on what the read took from the pool (a chunk of
live blocks, a gathered table) — so the resident pool never exists at f32.
The scratch-block masking contract survives every dtype: scales init to
0.0, so an unwritten int8 row dequantizes to exact zeros, and masked
probabilities are exactly zero regardless.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import numpy as np

from ..errors import (InvalidRequest, OutOfBlocks, OutOfStateRows,
                      UnsupportedCacheFeature)
from .layout import KV_PAYLOAD_DTYPES, ring_blocks, row_lanes

__all__ = ['BlockAllocator', 'BlockTable', 'KVCachePool', 'CacheContext',
           'StateRows', 'prefill_coords', 'decode_coords', 'DEFAULT_SLOTS',
           'DEFAULT_BLOCK_SIZE', 'DEFAULT_MAX_BLOCKS', 'SCRATCH_BLOCK',
           'KV_PAYLOAD_DTYPES', 'KV_DTYPE_CODES', 'row_lanes']

DEFAULT_SLOTS = int(os.environ.get('PADDLE_TPU_DECODE_SLOTS', '8'))
DEFAULT_BLOCK_SIZE = int(os.environ.get('PADDLE_TPU_DECODE_BLOCK_SIZE', '16'))
DEFAULT_MAX_BLOCKS = int(os.environ.get('PADDLE_TPU_DECODE_MAX_BLOCKS',
                                        '256'))

SCRATCH_BLOCK = 0

# stable small-int codes: the kv_cache_dtype gauge and the disagg KVPayload
# wire meta both speak these (0 is also what a legacy 3-int meta implies)
KV_DTYPE_CODES = {'f32': 0, 'bf16': 1, 'int8': 2}


def _to_lanes(rows, lanes):
    """``rows`` (..., W) zero-padded on the last axis to the pool's
    ``lanes``; as they are where W fills them."""
    import jax.numpy as jnp
    pad = lanes - rows.shape[-1]
    if not pad:
        return rows
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, pad),))


# the one pair of writes, for every pool array (K, V, latent rows, scales):
# each is (NB, BS, W) rows of one token, W its own width

@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_blocks(pages, block_ids, vals):
    """pages (NB, BS, W) ← vals (nb, BS, W) at block_ids (nb,)."""
    return pages.at[block_ids].set(vals)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_tokens(pages, block_ids, offsets, vals):
    """pages (NB, BS, W) ← vals (S, W) at (block_ids, offsets) (S,)."""
    return pages.at[block_ids, offsets].set(vals)


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_row(states, row, block):
    """states (R, *block) ← block at row () int32, whole."""
    return jax.lax.dynamic_update_index_in_dim(states, block, row, 0)


class StateRows:
    """Free list of the state layers' rows (one row of EVERY state layer
    belongs to one request). Row 0, the scratch row of idle slots, is never
    handed out; a row is owned by one table and shared by nobody."""

    def __init__(self, num_rows):
        self.num_rows = int(num_rows)
        self._free = list(range(self.num_rows - 1, 0, -1))    # pop() -> 1..
        self._lock = threading.Lock()

    @property
    def capacity(self):
        return max(self.num_rows - 1, 0)

    @property
    def used(self):
        with self._lock:
            return self.capacity - len(self._free)

    def take(self):
        with self._lock:
            if not self._free:
                raise OutOfStateRows(self.capacity)
            return self._free.pop()

    def give_back(self, row):
        row = int(row)
        with self._lock:
            if not 0 < row < self.num_rows or row in self._free:
                raise ValueError(f'state row {row} is not in use')
            self._free.append(row)


class BlockAllocator:
    """Refcounted free-list block allocator. Block 0 (scratch) is never
    handed out.

    Refcounts are what make prefix sharing (serving/tier/prefix_cache.py)
    safe: a block holding a shared system-prompt's K/V is referenced by
    every live request reading it PLUS the cache's own residency reference,
    and only returns to the free list when the LAST reference releases it.
    ``allocate`` hands blocks out at refcount 1; ``free``/``release`` are
    the same operation (decrement, recycle at zero) so pre-sharing callers
    keep their exact semantics."""

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError(f'need >= 2 blocks (1 scratch), got '
                             f'{num_blocks}')
        self.num_blocks = int(num_blocks)
        self._free = list(range(self.num_blocks - 1, 0, -1))  # pop() -> 1..
        self._refs = {}               # live block id -> refcount >= 1
        self._lock = threading.Lock()

    @property
    def capacity(self):
        return self.num_blocks - 1

    @property
    def available(self):
        with self._lock:
            return len(self._free)

    @property
    def used(self):
        return self.capacity - self.available

    def refcount(self, block_id):
        """Live references on ``block_id`` (0 = on the free list)."""
        with self._lock:
            return self._refs.get(int(block_id), 0)

    def allocate(self, n):
        """n block ids at refcount 1, or raise :class:`OutOfBlocks`
        (nothing allocated)."""
        n = int(n)
        with self._lock:
            if n > len(self._free):
                raise OutOfBlocks(n, len(self._free))
            ids = [self._free.pop() for _ in range(n)]
            for b in ids:
                self._refs[b] = 1
            return ids

    def retain(self, block_ids):
        """Add one reference per block (sharing an already-live block)."""
        with self._lock:
            for b in block_ids:
                b = int(b)
                if b not in self._refs:
                    raise ValueError(f'retain of non-live block {b}')
                self._refs[b] += 1

    def release(self, block_ids):
        """Drop one reference per block; blocks reaching zero return to the
        free list. Releasing a non-live block raises (double-free)."""
        with self._lock:
            for b in block_ids:
                b = int(b)
                if b == SCRATCH_BLOCK:
                    raise ValueError('freeing the scratch block')
                if b not in self._refs:
                    raise ValueError(f'double free of block {b}')
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    del self._refs[b]
                    self._free.append(b)

    # exclusive ownership (refcount 1) makes free == release; kept as the
    # name the pre-sharing callers (engine/scheduler/tests) use
    free = release


class BlockTable:
    """One request's cache blocks, in sequence order. ``context_len`` is the
    number of cached tokens (prompt + generated so far)."""

    __slots__ = ('blocks', 'block_size', 'context_len', 'cached_len',
                 'state_row', 'ring')

    def __init__(self, blocks, block_size, cached_len=0, state_row=0,
                 ring=()):
        self.blocks = list(blocks)
        # the request's blocks of the SLIDING class (none where the model
        # has no such layer): position p in ring[(p // block_size) % R], R
        # the pool's ring length
        self.ring = list(ring)
        # the request's row of the state layers (0: none, the scratch row)
        self.state_row = int(state_row)
        self.block_size = int(block_size)
        self.context_len = 0
        # tokens at the FRONT of the table already filled by shared
        # prefix-cache blocks (always a whole-block multiple); this request
        # must never write positions < cached_len — they belong to every
        # other request sharing those blocks
        self.cached_len = int(cached_len)

    @property
    def capacity_tokens(self):
        return len(self.blocks) * self.block_size

    def slot_for(self, position):
        """(block_id, offset) holding token ``position``."""
        if position >= self.capacity_tokens:
            raise IndexError(
                f'position {position} beyond the table\'s '
                f'{self.capacity_tokens} reserved token slots')
        return (self.blocks[position // self.block_size],
                position % self.block_size)

    def padded(self, max_blocks_per_seq):
        """Block ids padded to the engine-wide table width with scratch."""
        if len(self.blocks) > max_blocks_per_seq:
            raise ValueError(
                f'{len(self.blocks)} blocks exceed max_blocks_per_seq='
                f'{max_blocks_per_seq}')
        return self.blocks + [SCRATCH_BLOCK] * (max_blocks_per_seq
                                                - len(self.blocks))


class KVCachePool:
    """Per-layer paged K/V arrays + the shared allocator.

    ``max_blocks_per_seq`` fixes the batched block-table width — and with
    it ``padded_context = max_blocks_per_seq * block_size``, the most a
    slot can hold and the key extent of the reads that gather a table whole
    (prefill rungs below 128, the (S, K) step over a K/V or a latent pool);
    the lockstep step, over a K/V pool and over a latent one, reads what is
    live alone (see ops/nn_ops.py). ``layout`` (layout.py, the model's)
    says which layers keep a state a request.
    """

    def __init__(self, block_size=None, num_blocks=None,
                 max_blocks_per_seq=None, kv_dtype=None, state_rows=0,
                 span=0, sliding_blocks=0, layout=None):
        self.block_size = int(block_size or DEFAULT_BLOCK_SIZE)
        self.num_blocks = int(num_blocks or DEFAULT_MAX_BLOCKS)
        self.max_blocks_per_seq = int(max_blocks_per_seq or 8)
        kv_dtype = kv_dtype or 'f32'
        if kv_dtype not in KV_PAYLOAD_DTYPES:
            raise ValueError(
                f'kv_dtype={kv_dtype!r} is not supported; supported values: '
                + ', '.join(repr(c) for c in KV_PAYLOAD_DTYPES))
        self.kv_dtype = kv_dtype
        self.dtype = KV_PAYLOAD_DTYPES[kv_dtype]
        self.allocator = BlockAllocator(self.num_blocks)
        # the SLIDING class (span 0: the model has no such layer): what a
        # layer of it lets a query see, the ring a request holds of it, the
        # depth of its arrays and its own free list
        self.span = int(span)
        self.ring = ring_blocks(self.span, self.block_size)
        self.sliding_blocks = int(sliding_blocks)
        self.sliding = BlockAllocator(self.sliding_blocks) if self.span \
            else None
        # rows of the state layers' arrays (0: the model has none), row 0
        # scratch, and who holds which
        self.state_rows = StateRows(state_rows)
        # the layers that keep a state, as the model's layout says (none for
        # a pool built without one): the bytes of rows and of states apart
        self._state_layers = frozenset(
            i for i, layer in enumerate(layout.layers)
            if layer.kind == 'state') if layout else frozenset()
        # layer idx -> [k_pages, v_pages], each (NB, BS, lanes of H·D), or
        # for a latent (MLA) layer -> [rows] of (NB, BS, lanes of W): one
        # row a token; for a state layer -> [states] of (state rows,
        # *block) float32, a block of three axes: one row a request
        self._layers = {}
        self._scales = {}          # int8 only: layer -> [k_scales, v_scales]
        # layer idx -> (H, D), how a K/V row splits into heads: what the
        # wire format needs and the arrays' shapes do not say
        self.heads = {}

    @property
    def padded_context(self):
        return self.max_blocks_per_seq * self.block_size

    @property
    def num_layers(self):
        return len(self._layers)

    @property
    def geometry(self):
        """Everything of the pool an engine program's trace depends on, as
        the constructor's arguments: hashable, so it can key a compiled
        program, and engines of equal geometry share executables."""
        return (self.block_size, self.num_blocks, self.max_blocks_per_seq,
                self.kv_dtype, self.state_rows.num_rows, self.span,
                self.sliding_blocks)

    @classmethod
    def over(cls, geometry, layers, scales):
        """A pool of ``geometry`` over the given per-layer arrays: what the
        inside of an engine program writes and reads. The arrays are the
        program's traced arguments; the program returns :meth:`arrays` and
        the engine's own pool :meth:`adopt` s them, so no tracer ever
        reaches a pool that outlives the trace. Its allocator is unused."""
        pool = cls(*geometry)
        pool.adopt(layers, scales)
        return pool

    def arrays(self):
        """(layers, scales): ``{layer: [k, v]}`` payload arrays and, for
        int8 pools, their row scales (``{}`` otherwise). The pytrees an
        engine program takes donated and returns."""
        return self._layers, self._scales

    def adopt(self, layers, scales):
        """Take the arrays an engine program returned (the donated ones it
        was given are deleted by then). jit hands out fresh containers, so
        they are kept as they come."""
        self._layers, self._scales = layers, scales

    def allocate(self, layers, scales, heads):
        """Zeroed arrays of the shapes and dtypes an abstract trace of the
        model's first engine call returned for them (``{layer: [struct]}``
        as :meth:`arrays` gives them), and the ``heads`` the traced pool
        noted. Under that trace `ensure_layer` decided every shape from
        what the model wrote; this makes them real, so the pool learns what
        the model caches with no model config duplicated into it."""
        import jax.numpy as jnp
        for store, structs in ((self._layers, layers),
                               (self._scales, scales)):
            for layer, arrs in structs.items():
                store[layer] = [jnp.zeros(a.shape, a.dtype) for a in arrs]
        self.heads.update(heads)

    def _arrays_of(self, states):
        """The arrays of the state layers, or of the row layers."""
        return [arrs for layer, arrs in self._layers.items()
                if (layer in self._state_layers) == states]

    def row_bytes(self):
        """Resident bytes of one token's cached rows in one layer (a
        layer's arrays over the positions they hold): the
        kv_cache_row_bytes gauge. 0 where no layer caches rows."""
        positions = sum(arrs[0].shape[0] * self.block_size
                        for arrs in self._arrays_of(states=False))
        return self.bytes_in_hbm() // positions if positions else 0

    def new_table(self, total_tokens):
        """Allocate a table holding ``total_tokens`` (prompt + budget), and
        with state layers its state row. Raises OutOfBlocks (OutOfStateRows
        is one) when the pool cannot cover it right now: nothing is kept."""
        nb = -(-int(total_tokens) // self.block_size)
        if nb > self.max_blocks_per_seq:
            raise InvalidRequest(
                f'{total_tokens} tokens need {nb} blocks > '
                f'max_blocks_per_seq={self.max_blocks_per_seq}')
        row = self.state_rows.take() if self.state_rows.num_rows else 0
        blocks = ring = ()
        try:
            blocks = self.allocator.allocate(nb)
            if self.span:
                ring = self.sliding.allocate(min(nb, self.ring))
        except OutOfBlocks as e:
            # nothing is kept: what the other class gave goes back
            if blocks:
                self.allocator.free(blocks)
            if row:
                self.state_rows.give_back(row)
            if self.span and not isinstance(e, OutOfStateRows):
                raise OutOfBlocks(e.requested, e.available,
                                  'sliding' if blocks else 'full') from None
            raise
        return BlockTable(blocks, self.block_size, state_row=row, ring=ring)

    def free_table(self, table):
        if table.blocks:
            self.allocator.free(table.blocks)
            table.blocks = []
        if table.ring:
            self.sliding.free(table.ring)
            table.ring = []
        if table.state_row:
            self.state_rows.give_back(table.state_row)
            table.state_row = 0

    def ensure_layer(self, layer, n_heads, head_dim, sliding=False):
        """The layer's arrays, made on first use: the one place that decides
        their shapes. [k, v] of (NB, BS, row_lanes(n_heads·head_dim)), a
        token's row of all heads contiguous (the module docstring says
        why), NB the depth of the layer's class (``sliding``: the sliding
        class's); with ``n_heads`` None a latent (MLA) layer, ONE array of
        rows (NB, BS, row_lanes(head_dim))."""
        if n_heads is not None:
            self.heads[layer] = (int(n_heads), int(head_dim))
        if sliding and not self.span:
            raise ValueError(
                'a sliding layer over a pool built with span=0: the '
                'model\'s cache_layout() must name its span')
        if layer not in self._layers:
            import jax.numpy as jnp
            if n_heads is None:
                self._layers[layer] = [jnp.zeros(
                    (self.num_blocks, self.block_size,
                     row_lanes(head_dim)), self.dtype)]
                return self._layers[layer]
            rows = (self.sliding_blocks if sliding else self.num_blocks,
                    self.block_size)
            shape = rows + (row_lanes(n_heads * head_dim),)
            self._layers[layer] = [jnp.zeros(shape, self.dtype),
                                   jnp.zeros(shape, self.dtype)]
            if self.kv_dtype == 'int8':
                # one f32 scale per (position, head) row, in the payload's
                # order; zero-init means unwritten rows (incl. the scratch
                # block) dequantize to exact zeros — the masking contract
                # at a new dtype
                self._scales[layer] = [jnp.zeros(rows + (n_heads,), 'float32'),
                                       jnp.zeros(rows + (n_heads,), 'float32')]
        return self._layers[layer]

    def pages(self, layer):
        return self._layers[layer]

    def scales(self, layer):
        """int8 pools: [k_scales, v_scales] each (NB, BS, H) f32; ``None``
        for f32/bf16 pools (payload is self-describing)."""
        return self._scales.get(layer)

    def _encode_rows(self, vals):
        """f32 rows (..., H, D) → (payload at the storage dtype, row scales
        (..., H) f32 or ``None``). The f32 branch returns its input object
        untouched — the default path must stay bitwise-identical."""
        if self.kv_dtype == 'f32':
            return vals, None
        import jax.numpy as jnp
        if self.kv_dtype == 'bf16':
            return jnp.asarray(vals).astype(jnp.bfloat16), None
        from ...parallel.quant_collectives import rowwise_quantize
        return rowwise_quantize(vals)

    def bytes_in_hbm(self):
        """Resident bytes of the layers that cache rows: payload arrays
        plus (int8) their scale arrays — the kv_cache_bytes_in_hbm gauge."""
        return sum(int(a.nbytes) for arrs in self._arrays_of(states=False)
                   + list(self._scales.values()) for a in arrs)

    def state_bytes_in_hbm(self):
        """Resident bytes of the state layers, every row of every layer —
        the state_cache_bytes_in_hbm gauge."""
        return sum(int(arrs[0].nbytes)
                   for arrs in self._arrays_of(states=True))

    # -- state layers: one row a request, whole ----------------------------
    def ensure_state(self, layer, block_shape):
        """The state layer's one array, (state rows, *block_shape) float32
        zeros, made on first use; ``block_shape`` (three axes: a retention
        layer's (G, P, d), a short convolution's (1, L - 1, h)) is what the
        layer's op returns for one request."""
        if layer not in self._layers:
            import jax.numpy as jnp
            if not self.state_rows.num_rows:
                raise ValueError(
                    'a state layer over a pool built with state_rows=0: '
                    'the model\'s cache_layout() must name its state '
                    'layers')
            self._layers[layer] = [jnp.zeros(
                (self.state_rows.num_rows,) + tuple(block_shape),
                'float32')]
        return self._layers[layer]

    def write_state(self, layer, row, block):
        """A prefill's final state (one block) over the whole of ``row``:
        a row reused after a free carries nothing over."""
        states = self.ensure_state(layer, block.shape)
        states[0] = _put_row(states[0], row, block.astype('float32'))

    def write_prefill(self, layer, block_ids, k, v, sliding=False):
        """Write the prompt's K/V rows. ``k``/``v``: (H, L, D) — the bucket-
        padded projections; ``block_ids``: (ceil(L/bs),) int32 from
        :func:`prefill_coords`, the table's first ``ceil(context/bs)`` blocks
        and the scratch block for the rest (tail rows inside the last block
        are masked garbage until decode overwrites them).

        Every shape here is a function of the BUCKET length L alone, and the
        prompt's length reaches the scatter only as the values of
        ``block_ids``: one prefill program per rung serves every prompt on
        it. Were the block count taken from the prompt length, each new
        ``ceil(context/bs)`` would compile again on the first request that
        has it — after warm-up, where chip_smoke.py counts none."""
        import jax.numpy as jnp
        h, L, d = k.shape
        self.ensure_layer(layer, h, d, sliding)
        nb = -(-L // self.block_size)
        target = nb * self.block_size
        if L < target:
            pad = ((0, 0), (0, target - L), (0, 0))
            k = jnp.pad(k, pad)
            v = jnp.pad(v, pad)
        self._write(layer, _scatter_blocks,
                    (jnp.asarray(block_ids, jnp.int32),),
                    (nb, self.block_size), k, v)

    def write_tokens(self, layer, block_ids, offsets, k, v, sliding=False):
        """One decode step's K/V: ``k``/``v`` (H, S, D) written at
        (block_ids[s], offsets[s]) per slot. Inactive slots point at the
        scratch block."""
        import jax.numpy as jnp
        h, s, d = k.shape
        self.ensure_layer(layer, h, d, sliding)
        self._write(layer, _scatter_tokens,
                    (jnp.asarray(block_ids, jnp.int32),
                     jnp.asarray(offsets, jnp.int32)), (s,), k, v)

    def _write(self, layer, scatter, at, lead, k, v):
        """Encode ``k`` / ``v`` (H, n, D) at the storage dtype and scatter
        them at ``at`` as rows of one token, (*lead, H·D) with ``lead`` the
        n tokens as the scatter takes them: only the new tokens are
        transposed, the pool is written as it lies."""
        pages, sc = self._layers[layer], self._scales.get(layer)
        for i, x in enumerate((k, v)):
            rows, scales = self._encode_rows(x.transpose(1, 0, 2))
            pages[i] = scatter(pages[i], *at, _to_lanes(
                rows.reshape(*lead, -1), pages[i].shape[-1]))
            if scales is not None:
                sc[i] = scatter(sc[i], *at, scales.reshape(*lead, -1))

    # -- latent (MLA) layers: one array of rows, no head axis --------------
    def _latent_rows(self, pages, rows):
        """``rows`` (n, W) at the pool's dtype and lane width."""
        return _to_lanes(rows.astype(self.dtype), pages[0].shape[-1])

    def write_prefill_latent(self, layer, block_ids, rows):
        """The prompt's latent rows, ``rows`` (L, W) bucket-padded, into the
        blocks ``block_ids`` (ceil(L/bs),) of :func:`prefill_coords`: shapes
        depend on the rung alone, as in :meth:`write_prefill`."""
        import jax.numpy as jnp
        length, width = rows.shape
        pages = self.ensure_layer(layer, None, width)
        nb = -(-length // self.block_size)
        target = nb * self.block_size
        if length < target:
            rows = jnp.pad(rows, ((0, target - length), (0, 0)))
        pages[0] = _scatter_blocks(
            pages[0], jnp.asarray(block_ids, jnp.int32),
            self._latent_rows(pages, rows).reshape(nb, self.block_size, -1))

    def write_tokens_latent(self, layer, block_ids, offsets, rows):
        """One decode step's latent rows, ``rows`` (S·K, W) slot-major, at
        (block_ids[i], offsets[i])."""
        import jax.numpy as jnp
        pages = self.ensure_layer(layer, None, rows.shape[-1])
        pages[0] = _scatter_tokens(
            pages[0], jnp.asarray(block_ids, jnp.int32),
            jnp.asarray(offsets, jnp.int32), self._latent_rows(pages, rows))

    # -- whole-block transfer (serving/tier/disagg.py handoff) -------------
    # The wire format is head-major, (H, nb, BS, D) payload and (H, nb, BS)
    # scales, as it was when the pool lay so: payloads cross versions and
    # the host tier keeps them. It meets the pool's rows of one token by a
    # transposition over the nb blocks moved.
    @staticmethod
    def _wire(pages, block_ids, h, x):
        """Blocks ``block_ids`` of one pool array, whose rows begin with
        ``x`` values for each of ``h`` heads (payload: the head's D, then
        the lanes' padding; scales: 1), as the host array (H, nb, BS, x)."""
        got = np.asarray(pages[np.asarray(block_ids, np.int32)])
        nb, bs, _ = got.shape
        return np.ascontiguousarray(
            got[..., :h * x].reshape(nb, bs, h, x).transpose(2, 0, 1, 3))

    def read_blocks(self, layer, block_ids):
        """Gather whole blocks as host arrays: ``(k, v)`` each
        (H, nb, block_size, D). The disaggregation payload format — a
        prefill replica reads its finished blocks out, a decode replica
        writes them into its own pool ids."""
        k_pages, v_pages = self._layers[layer]
        h, d = self.heads[layer]
        return (self._wire(k_pages, block_ids, h, d),
                self._wire(v_pages, block_ids, h, d))

    def read_block_scales(self, layer, block_ids):
        """int8 pools: gather the blocks' row scales as host arrays
        ``(k_scales, v_scales)`` each (H, nb, block_size) f32 — shipped
        beside :meth:`read_blocks` payloads so a same-dtype receiver can
        scatter them back byte-exact. ``None`` for f32/bf16 pools."""
        if layer not in self._scales:
            return None
        ks, vs = self._scales[layer]
        h, _ = self.heads[layer]
        return (self._wire(ks, block_ids, h, 1)[..., 0],
                self._wire(vs, block_ids, h, 1)[..., 0])

    def write_whole_blocks(self, layer, block_ids, k, v,
                           k_scale=None, v_scale=None):
        """Scatter whole blocks (the :meth:`read_blocks` shapes) into this
        pool at ``block_ids`` — the receiving half of a KV handoff or a
        host-tier reinjection.

        Dtype conversion matrix: payload already at this pool's storage
        dtype (int8 arriving WITH its scales) scatters directly —
        byte-exact, which is what makes same-dtype disagg handoff and
        spill→reinject bitwise; otherwise the incoming rows are decoded to
        f32 (using ``k_scale``/``v_scale`` when the sender was int8) and
        re-encoded at this pool's dtype."""
        h, nb, bs, d = k.shape
        if bs != self.block_size:
            raise InvalidRequest(
                f'handoff block_size {bs} != pool block_size '
                f'{self.block_size}')
        pages = self.ensure_layer(layer, h, d)
        sc = self._scales.get(layer)
        ids = np.asarray(block_ids, np.int32)
        import jax.numpy as jnp
        same = (jnp.dtype(k.dtype) == jnp.dtype(self.dtype)
                and (self.kv_dtype != 'int8' or k_scale is not None))
        for i, (x, xs) in enumerate(((k, k_scale), (v, v_scale))):
            # (H, nb, BS, D) -> the pool's (nb, BS, H, D), scales alike
            x = jnp.asarray(x).transpose(1, 2, 0, 3)
            if xs is not None:
                xs = jnp.asarray(xs).transpose(1, 2, 0)
            if not same:
                if xs is not None:       # sender was int8: decode first
                    from ...parallel.quant_collectives import (
                        rowwise_dequantize)
                    x = rowwise_dequantize(x, xs)
                x, xs = self._encode_rows(x.astype(jnp.float32))
            pages[i] = _scatter_blocks(pages[i], ids, _to_lanes(
                x.reshape(nb, bs, h * d), pages[i].shape[-1]))
            if sc is not None:
                sc[i] = _scatter_blocks(sc[i], ids, xs)


def prefill_coords(pool, table, bucket):
    """What a prefill program reads of one request, as host arrays whose
    shapes depend on the rung and the pool's geometry alone:
    ``block_tables`` (1, max_blocks_per_seq), the table padded with scratch,
    and ``write_ids`` (ceil(bucket/bs),), the blocks `write_prefill` scatters
    the bucket-padded K/V into: the table's first ``ceil(context/bs)``
    blocks, then the scratch block for the rows past the prompt."""
    bs = pool.block_size
    nb = -(-int(bucket) // bs)
    nb_w = min(-(-table.context_len // bs), len(table.blocks), nb)
    coords = {'block_tables': np.asarray(
                  [table.padded(pool.max_blocks_per_seq)], np.int32),
              'write_ids': np.asarray(
                  table.blocks[:nb_w] + [SCRATCH_BLOCK] * (nb - nb_w),
                  np.int32)}
    if pool.state_rows.num_rows:
        # (1,): the request's row of the state layers
        coords['state_rows'] = np.asarray([table.state_row], np.int32)
    if pool.span:
        # the sliding class's columns: the request's ring padded with that
        # class's scratch block, and of the bucket's blocks the last
        # ``ring`` that hold the prompt, each into its place in the ring;
        # a block the prompt has already left behind goes to scratch
        last = -(-table.context_len // bs) - 1        # the prompt's last
        ids = [SCRATCH_BLOCK] * nb
        if table.ring:
            for b in range(max(0, last - pool.ring + 1), min(last, nb - 1)
                           + 1):
                ids[b] = table.ring[b % pool.ring]
        coords['sliding_tables'] = np.asarray([_padded_ring(pool, table)],
                                              np.int32)
        coords['sliding_write_ids'] = np.asarray(ids, np.int32)
    return coords


def _padded_ring(pool, table):
    """The table's ring padded to the pool's ring length with the sliding
    class's scratch block (a request that can never pass the span holds
    fewer blocks than a whole ring); None: an idle slot's."""
    ring = [] if table is None else table.ring
    return ring + [SCRATCH_BLOCK] * (pool.ring - len(ring))


def decode_coords(pool, tables, context_lens, fed_counts=None, window=1,
                  block=False):
    """What a decode program reads of the S slots, as host arrays whose
    shapes depend on S, ``window`` and the pool's geometry alone:
    ``block_tables`` (S, max_blocks_per_seq), ``context_lens`` (S,) and the
    flattened, slot-major write coordinates ``write_ids`` / ``write_offs``
    (S·window,). ``tables[s] is None`` is an inactive slot: it reads and
    writes the scratch block.

    ``window`` K > 1 is the (S, K) step (speculative verify, chunked suffix
    fill): slot s feeds ``fed_counts[s]`` ≤ K real tokens at positions
    context_lens[s]-1 .. context_lens[s]-1+f-1, and its K-f padded lanes
    write to the scratch block (harmless by the masking contract above).
    ``context_lens[s]`` stays the extent of fed ROW 0; `paged_attention`'s
    multi-query form gives row j the causal staircase extent
    context_lens + j.

    ``block`` is a window model's step (block diffusion): every live slot
    feeds one whole block of ``window`` tokens, and ``context_lens[s]`` is
    the extent EVERY row of it sees, the block included: the block is
    written at positions context_lens[s]-window .. context_lens[s]-1."""
    if fed_counts is None:
        fed_counts = [1 if t is not None else 0 for t in tables]
    ids, offs, padded = [], [], []
    for t, c, f in zip(tables, context_lens, fed_counts):
        if t is None:                       # inactive slot
            ids.extend([SCRATCH_BLOCK] * window)
            offs.extend([0] * window)
            padded.append([SCRATCH_BLOCK] * pool.max_blocks_per_seq)
            continue
        # first token written this step
        base = int(c) - (window if block else 1)
        for j in range(window):
            if j < int(f):
                b, o = t.slot_for(base + j)
            else:                  # padded lane: scratch write
                b, o = SCRATCH_BLOCK, 0
            ids.append(b)
            offs.append(o)
        padded.append(t.padded(pool.max_blocks_per_seq))
    coords = {'block_tables': np.asarray(padded, np.int32),
              'write_ids': np.asarray(ids, np.int32),
              'write_offs': np.asarray(offs, np.int32),
              'context_lens': np.asarray(
                  [max(int(c), 1) for c in context_lens], np.int32)}
    if pool.state_rows.num_rows:
        # (S,): each slot's row of the state layers, an idle slot's the
        # scratch row
        coords['state_rows'] = np.asarray(
            [0 if t is None else t.state_row for t in tables], np.int32)
    if pool.span:
        if window != 1:
            raise UnsupportedCacheFeature(
                ['a decode window of more than one token (speculation, '
                 'chunked suffix fill, a window model)'], 'sliding')
        # the sliding class's columns: every slot's ring, and the fed
        # token's place in it
        s_ids, s_offs = [], []
        for t, c in zip(tables, context_lens):
            if t is None:
                s_ids.append(SCRATCH_BLOCK)
                s_offs.append(0)
                continue
            p = int(c) - 1
            s_ids.append(t.ring[p // pool.block_size % pool.ring])
            s_offs.append(p % pool.block_size)
        coords['sliding_tables'] = np.asarray(
            [_padded_ring(pool, t) for t in tables], np.int32)
        coords['sliding_write_ids'] = np.asarray(s_ids, np.int32)
        coords['sliding_write_offs'] = np.asarray(s_offs, np.int32)
    return coords


class CacheContext:
    """The duck-typed ``cache=`` object MultiHeadAttention calls into
    (models/bert.py). One context per model forward; each attention layer's
    ``attend(q, k, v, sm_scale=)`` call consumes the next layer index. It
    lives inside an engine program's trace: ``pool`` is a
    :meth:`KVCachePool.over` the program's arrays and ``coords`` are the
    traced arrays of :func:`prefill_coords` / :func:`decode_coords`.

    mode='prefill': q/k/v are (1, H, Lb, D) for one bucket-padded prompt —
    K/V are written into the request's blocks, attention runs causal over
    the paged view (`paged_prefill_attention`).

    mode='decode': q/k/v are (S, H, K, D). K = 1 is the lockstep step, one
    token per slot — K/V land at each slot's next position, attention walks
    the batch's live blocks (`paged_attention` over :meth:`live_blocks`) at
    fixed shape.
    K > 1 is the multi-token window :func:`decode_coords` describes.

    A window model (block diffusion, models/block_diffusion_lm.py) passes
    ``attend(..., block_len=B)``: k/v hold the model's key/value heads (G ≤
    q's H; a pool row holds G heads), a prefill attends under the block
    mask, and a decode step's K = B rows of a slot are one block, all at
    the extent ``context_lens`` says (`paged_attention` with
    ``block_window`` over :meth:`live_groups`). What such a step writes is
    provisional: it is kept only if the engine moves the table's
    ``context_len`` past it (engine.py::window_step), and the next forward
    of the block writes the same positions again.
    """

    def __init__(self, pool, mode, coords, last=None):
        self.pool = pool
        self.mode = mode
        self.coords = coords
        # prefill: index of the prompt's last row, the one the host reads
        # (traced); the rows past it are the rung's padding
        self.last = last
        self._layer = 0
        self._live = None          # `live_blocks`, once a layer asked
        self._groups = None        # `live_groups`, once a layer asked
        self._ring_groups = None   # `live_ring_groups`, once a layer asked
        self.stats = {}            # name -> [what a layer noted], `note`

    def note(self, name, value):
        """Keep ``value`` (a small traced array) under ``name`` for the
        host: the engine's program returns every name's values stacked in
        the order noted, beside the rows, and reads nothing of them."""
        self.stats.setdefault(name, []).append(value)

    def live_rows(self, n):
        """(n,) bool over the call's ``n`` tokens, flattened as the model
        feeds them: True where a row is a request's token. The others are a
        rung's padding past the prompt, idle slots and a window's padded
        lanes, which all write to the scratch block."""
        import jax.numpy as jnp
        if self.mode == 'prefill':
            return jnp.arange(n, dtype=jnp.int32) <= self.last
        return jnp.asarray(self.coords['write_ids']) != SCRATCH_BLOCK

    def live_blocks(self):
        """The decode batch's live blocks as they lie in the pool,
        ``[block_id, slot, first_pos, n_live]``
        (ops/nn_ops.py::live_block_list), made on the device from this
        program's block tables and context lengths the first time a layer
        asks and shared by every layer after it: a read that walks it does
        work in proportion to the contexts, not to the tables' padded
        width. A program none of whose layers asks (a latent pool's, which
        asks for :meth:`live_groups`; the (S, K) step's) holds no such
        op."""
        if self._live is None:
            from ...ops.nn_ops import live_block_list
            c = self.coords
            self._live = list(live_block_list(
                c['block_tables'], c['context_lens'], self.pool.block_size))
        return self._live

    def live_groups(self):
        """:meth:`live_blocks` for the reads that walk GROUPS of whole
        blocks (ops/nn_ops.py::live_group_list): a window model's block
        read, a full layer's grouped read, a latent pool's lockstep read.
        Made once a program and shared by its layers."""
        if self._groups is None:
            from ...ops.nn_ops import live_group_list
            c = self.coords
            self._groups = list(live_group_list(
                c['block_tables'], c['context_lens'], self.pool.block_size))
        return self._groups

    def live_ring_groups(self):
        """:meth:`live_groups` of the SLIDING class: the groups of each
        slot's ring that hold a position inside its span
        (ops/nn_ops.py::live_ring_group_list), made once a program and
        shared by its sliding layers."""
        if self._ring_groups is None:
            from ...ops.nn_ops import live_ring_group_list
            c = self.coords
            self._ring_groups = list(live_ring_group_list(
                c['sliding_tables'], c['context_lens'],
                self.pool.block_size, self.pool.span))
        return self._ring_groups

    def _scale_inputs(self, layer):
        """Extra dispatch inputs for int8 pools ({} otherwise — the f32/bf16
        dispatch must stay slot-for-slot what it was before quantization)."""
        sc = self.pool.scales(layer)
        if sc is None:
            return {}
        return {'k_scales': sc[0], 'v_scales': sc[1]}

    def attend_latent(self, inputs, attrs):
        """A latent (MLA) layer's attention through the pool. ``inputs``:
        q (B, L, H, D), the tokens' own ``latent`` rows (B, L, W) as they
        are to be cached, and ``w_kvb``; ``attrs`` those of the two ops
        (ops/llm_ops.py). Prefill writes the prompt's rows and attends in
        the expanded form over the prompt itself; decode writes each slot's
        K fed rows and reads the pool in the absorbed form: the lockstep
        step (K = 1) walks :meth:`live_groups`, one list a program shared
        by its layers; the (S, K) step gathers every table and takes no
        list."""
        from ...dygraph.tape import dispatch_op
        layer = self._layer
        self._layer += 1
        c = self.coords
        rows = inputs['latent'].value
        if self.mode == 'prefill':
            self.pool.write_prefill_latent(layer, c['write_ids'], rows[0])
            with jax.named_scope('mla/prefill_attention'):
                return dispatch_op('mla_prefill_attention', inputs, attrs)
        self.pool.write_tokens_latent(
            layer, c['write_ids'], c['write_offs'],
            rows.reshape(-1, rows.shape[-1]))
        # the scope names the read's device ops in a profiler trace (the
        # read of the live groups, on a TPU one pallas custom call, and the
        # absorbing matmuls before and after it; in the first layer the
        # list of live groups too)
        with jax.named_scope('mla/decode_read'):
            return dispatch_op('mla_decode_attention', {
                'q': inputs['q'], 'pages': self.pool.pages(layer)[0],
                'block_tables': c['block_tables'],
                'context_lens': c['context_lens'],
                'w_kvb': inputs['w_kvb'],
                'live': self.live_groups() if rows.shape[1] == 1 else None},
                attrs)

    def attend_state(self, ops, inputs, attrs, block_shape, scopes=None,
                     step_attrs=None):
        """A STATE layer through its row, whatever the state is: ``ops`` =
        (prefill op, step op) are handed in (ops/llm_ops.py), each returning
        (out, state). The prefill op takes ``inputs`` and ``last`` under
        ``attrs`` and returns the (1, *block_shape) state after the
        prompt's TRUE last row (rows past ``last`` are the rung's padding
        and never enter it), which is written over the request's whole
        row: a row taken again carries nothing of its last request. The
        step op takes ``inputs``, the layer's array ``state`` (state rows,
        *block_shape) and each slot's ``rows`` (one fed token a slot) under
        ``step_attrs`` (None: none), and returns the array with every
        slot's row advanced, idle slots on the scratch row. ``scopes``
        (prefill, step) name the two ops' device ops in a profiler trace;
        None where the caller's own scope does."""
        from ...dygraph.tape import dispatch_op
        layer = self._layer
        self._layer += 1
        rows = self.coords['state_rows']
        prefill = self.mode == 'prefill'
        scope = jax.named_scope(scopes[0 if prefill else 1]) if scopes \
            else contextlib.nullcontext()
        if prefill:
            with scope:
                out, state = dispatch_op(
                    ops[0], dict(inputs, last=self.last), attrs)
            self.pool.write_state(layer, rows[0], state.value[0])
            return out
        if self.coords['write_ids'].shape[0] != rows.shape[0]:
            raise UnsupportedCacheFeature(
                ['a decode window of more than one token (speculation, '
                 'chunked suffix fill)'], 'state')
        states = self.pool.ensure_state(layer, block_shape)
        with scope:
            out, state = dispatch_op(
                ops[1], dict(inputs, state=states[0], rows=rows),
                step_attrs or {})
        states[0] = state.value
        return out

    def attend_retention(self, inputs, attrs):
        """A power-retention layer through its recurrent state
        (:meth:`attend_state` with the two retention ops). ``inputs``: q (B,
        L, H, d), k and v (B, L, G, d), ``log_gate`` (B, L, G); ``attrs``
        those of `power_retention_prefill` (ops/llm_ops.py). Prefill scans
        the bucket and writes the final state over the request's whole row;
        a decode step advances every slot's row in place and reads it."""
        from ...ops.llm_ops import retention_state_rows
        _, _, groups, d = inputs['k'].shape
        return self.attend_state(
            ('power_retention_prefill', 'power_retention_step'), inputs,
            attrs, (groups, retention_state_rows(d)[2], d),
            ('retention/prefill_scan', 'retention/decode_update'))

    def attend(self, q, k, v, sm_scale=1.0, block_len=0, span=None):
        """``span`` (None: a model of one class of layer, whose key/value
        heads are its query heads' or a window model's) is the layer's
        class in a model that has two: 0 a FULL layer, S > 0 a SLIDING
        layer whose row i sees keys j with 0 <= i - j < S (the pool's
        ``span``). Such a layer's k/v hold the model's key/value heads (G ≤
        q's H), a prefill attends the raw projections causally
        (`paged_prefill_attention` with ``kv_heads``) and a decode step
        reads the live groups of the class's blocks (`paged_attention`
        with ``kv_heads``, scopes `kv/decode_read` / `kv/sliding_read`)."""
        from ...dygraph.tape import Tensor, dispatch_op
        if span is not None:
            return self._attend_class(q, k, v, sm_scale, int(span))
        layer = self._layer
        self._layer += 1
        c = self.coords
        kv = k.value if isinstance(k, Tensor) else k
        vv = v.value if isinstance(v, Tensor) else v
        if self.mode == 'prefill':
            # (1, H, L, D) -> (H, L, D): the prompt's projections
            self.pool.write_prefill(layer, c['write_ids'], kv[0], vv[0])
            k_pages, v_pages = self.pool.pages(layer)
            inputs = {'q': q, 'k': k, 'v': v, 'k_pages': k_pages,
                      'v_pages': v_pages, 'block_tables': c['block_tables']}
            inputs.update(self._scale_inputs(layer))
            attrs = {'sm_scale': float(sm_scale)}
            if block_len:
                attrs['block_len'] = int(block_len)
            return dispatch_op('paged_prefill_attention', inputs, attrs)
        s, h, k_w, d = kv.shape
        # (S, H, K, D) -> (H, S·K, D), slot-major, matching the flattened
        # write coordinates
        self.pool.write_tokens(
            layer, c['write_ids'], c['write_offs'],
            kv.transpose(1, 0, 2, 3).reshape(h, s * k_w, d),
            vv.transpose(1, 0, 2, 3).reshape(h, s * k_w, d))
        k_pages, v_pages = self.pool.pages(layer)
        inputs = {'k_pages': k_pages, 'v_pages': v_pages,
                  'block_tables': c['block_tables'],
                  'context_lens': c['context_lens']}
        inputs.update(self._scale_inputs(layer))
        attrs = {'sm_scale': float(sm_scale)}
        if block_len:
            if k_w != block_len:
                raise ValueError(f'a window model steps whole blocks of '
                                 f'{block_len} rows a slot, got {k_w}')
            # the scope names the block read's device ops in a trace
            with jax.named_scope('kv/block_read'):
                return dispatch_op(
                    'paged_attention',
                    dict(inputs, q=q, live=self.live_groups()),
                    dict(attrs, block_window=True, kv_heads=int(h)))
        if k_w > 1:
            # q stays rank-4 for the multi-query paged_attention read
            return dispatch_op('paged_attention', dict(inputs, q=q), attrs)
        q3 = dispatch_op('reshape', {'x': q}, {'shape': [s, h, d]})
        # the scope names the read's device ops in a profiler trace
        with jax.named_scope('kv/decode_read'):
            out = dispatch_op(
                'paged_attention',
                dict(inputs, q=q3, live=self.live_blocks()), attrs)
        return dispatch_op('reshape', {'x': out}, {'shape': [s, h, 1, d]})

    def _attend_class(self, q, k, v, sm_scale, span):
        """`attend` for a layer that names its class (``span``)."""
        from ...dygraph.tape import Tensor, dispatch_op
        layer = self._layer
        self._layer += 1
        c = self.coords
        sliding = span > 0
        if sliding and span != self.pool.span:
            raise ValueError(f'a sliding layer of span {span} over a pool '
                             f'whose sliding class spans {self.pool.span}')
        kv = k.value if isinstance(k, Tensor) else k
        vv = v.value if isinstance(v, Tensor) else v
        groups = int(kv.shape[1])
        tables = c['sliding_tables' if sliding else 'block_tables']
        ids = c['sliding_write_ids' if sliding else 'write_ids']
        attrs = {'sm_scale': float(sm_scale), 'kv_heads': groups,
                 'span': span}
        if self.mode == 'prefill':
            self.pool.write_prefill(layer, ids, kv[0], vv[0], sliding)
            k_pages, v_pages = self.pool.pages(layer)
            # the scopes name the two prefill attentions' device ops
            with jax.named_scope('attn/sliding_prefill' if sliding
                                 else 'attn/full_prefill'):
                return dispatch_op('paged_prefill_attention', {
                    'q': q, 'k': k, 'v': v, 'k_pages': k_pages,
                    'v_pages': v_pages, 'block_tables': tables}, attrs)
        s, _, k_w, d = kv.shape
        if k_w != 1:
            raise UnsupportedCacheFeature(
                ['a decode window of more than one token (speculation, '
                 'chunked suffix fill)'], 'sliding')
        offs = c['sliding_write_offs' if sliding else 'write_offs']
        self.pool.write_tokens(layer, ids, offs, kv[:, :, 0].transpose(1, 0, 2),
                               vv[:, :, 0].transpose(1, 0, 2), sliding)
        k_pages, v_pages = self.pool.pages(layer)
        h = q.shape[1]
        q3 = dispatch_op('reshape', {'x': q}, {'shape': [s, h, d]})
        live = self.live_ring_groups() if sliding else self.live_groups()
        with jax.named_scope('kv/sliding_read' if sliding
                             else 'kv/decode_read'):
            out = dispatch_op('paged_attention', {
                'q': q3, 'k_pages': k_pages, 'v_pages': v_pages,
                'block_tables': tables, 'context_lens': c['context_lens'],
                'live': live}, attrs)
        return dispatch_op('reshape', {'x': out}, {'shape': [s, h, 1, d]})
