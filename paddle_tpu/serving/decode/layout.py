"""What a decode model caches, one layer at a time (docs/SERVING.md "Cache
layout"): the one object the engine, the pool, the pool-size solve, the
handoff and the counters ask.

A model says it once, in ``model.cache_layout()``: a :class:`CacheLayout` of
one :class:`LayerCache` per layer that caches something, in the order its
layers reach the pool, and the rows a slot feeds a step (``window``). A layer
caches ``kv`` (a K and a V row a token of (heads, head_dim)), ``latent`` (one
row a token of (width,), MLA) or ``state`` (one float32 block a REQUEST,
advanced by ``op``). A row layer's ``span`` is 0 (full) or S (sliding: the
last S positions in a ring of blocks a request), and its ``read`` is what a
decode step walks (ops/nn_ops.py): ``blocks`` (its own heads over
`live_block_list`), ``groups`` (a key/value head's group of query heads, or
a latent row, over `live_group_list`), ``ring`` (`live_ring_group_list`) or
``window`` (a window model's block of rows a slot, over the live groups).
Everything else is derived here, once: counts by kind, class and read, the
ring, what a token, a block, a state row, a request and the sliding class
cost, and what each layer cannot serve (:meth:`CacheLayout.refuse`).
"""
from __future__ import annotations

import collections
import dataclasses
import math

from ..errors import UnsupportedCacheFeature

__all__ = ['CacheLayout', 'LayerCache', 'SLIDING_SPARE_BLOCKS',
           'ring_blocks', 'refusal_message', 'model_state_bytes',
           'solve_decode_pool_blocks', 'solve_decode_state_slots',
           'decode_pool_report', 'KV_PAYLOAD_DTYPES', 'kv_row_bytes',
           'row_lanes']

# blocks the sliding class's arrays hold beyond every slot's ring: the
# class's scratch block and a few spare, as a K/V pool sized by its slots
# holds (`slots × blocks a slot + 8`)
SLIDING_SPARE_BLOCKS = 8

# storage payload width per element, by kv_dtype; int8 additionally carries
# one f32 scale per (position, head) row
KV_PAYLOAD_DTYPES = {'f32': 'float32', 'bf16': 'bfloat16', 'int8': 'int8'}
_KV_PAYLOAD_BYTES = {'f32': 4, 'bf16': 2, 'int8': 1}


def row_lanes(width):
    """Lanes a token's row of ``width`` values takes in the pool: the next
    multiple of the TPU's 128. At 576 or 320 lanes the compiler lays a
    (blocks, block, W) array out with the BLOCK axis minor and every engine
    program copies each layer's pool into the scatter's layout and back
    (PERF.md section 6, PRs 26, 27); at 640 or 384 row-major is the compact
    layout, the writes are in place, and the bytes are those the tiles of
    the unpadded array would take anyway."""
    return -(-int(width) // 128) * 128


def kv_row_bytes(heads, head_dim, kv_dtype):
    """Bytes ONE token's row takes in one pool array (its K, its V, or its
    latent row with ``heads`` 1) at ``kv_dtype``, as allocated: the payload
    of all heads in :func:`row_lanes` lanes + (int8 only) one f32 scale a
    head."""
    if kv_dtype not in _KV_PAYLOAD_BYTES:
        raise ValueError(
            f'kv_dtype={kv_dtype!r} is not supported; supported values: '
            + ', '.join(repr(c) for c in KV_PAYLOAD_DTYPES))
    return (row_lanes(int(heads) * int(head_dim))
            * _KV_PAYLOAD_BYTES[kv_dtype]
            + (4 * int(heads) if kv_dtype == 'int8' else 0))


def ring_blocks(span, block_size):
    """Blocks of a sliding layer's ring, every block a span can touch:
    ceil(span / block) + 1 (0 without a sliding layer)."""
    return -(-int(span) // int(block_size)) + 1 if span else 0


# The one refusal table: for each kind a layer is refused as
# (`LayerCache.refused_as`), the cache it names, why, the docs/SERVING.md
# section that says more, and the features it refuses. `refuse` asks the
# kinds in this order and raises for the first that refuses what was asked.
_PREFIX = 'the prefix cache (and its spill and reinject)'
_SPEC = 'speculative decoding (its (S, K) verify step)'
_REFUSALS = {
    'latent': ('a latent KV cache',
               'they read and write [k, v] pairs of per-head rows',
               'Latent pool', ('prefix_cache', 'int8', 'handoff')),
    'state': ('a model with state layers (its state cache)',
              'a state layer holds one float32 recurrent state per request, '
              'advanced in place: no row per token to share, hand off, '
              'quantize or roll back', 'Recurrent state',
              ('prefix_cache', 'spec_decode', 'kv_dtype', 'handoff')),
    'sliding': ('a KV cache with a sliding class of layer',
                'a sliding layer keeps a ring of blocks a request and '
                'overwrites it in place, and these have no path over a ring '
                'yet', 'Layer classes',
                ('prefix_cache', 'spec_decode', 'int8', 'window', 'handoff')),
    'grouped': ('a KV cache whose model names its layers\' classes',
                'a model that names its layers\' classes reads its rows a '
                'key/value head\'s group of query heads at a time, and these '
                'have no path through those reads yet', 'Layer classes',
                ('prefix_cache', 'spec_decode', 'int8', 'window')),
    'window': ('a window model\'s KV cache',
               'a window model reads its rows under the block mask and keeps '
               'a block\'s rows only at its commit forward, and these have '
               'no path under that mask yet', 'Window models',
               ('prefix_cache', 'spec_decode', 'int8', 'handoff'))}


def refusal_message(features, kind):
    """What `UnsupportedCacheFeature` says of ``features`` refused by a
    cache of ``kind``."""
    what, why, section, _ = _REFUSALS[kind]
    return (f'{", ".join(features)} cannot be used with {what}: {why} '
            f'(docs/SERVING.md "{section}")')


@dataclasses.dataclass(frozen=True)
class LayerCache:
    """What ONE layer caches and how a decode step reads it (the module
    docstring); made by :meth:`kv`, :meth:`latent` and :meth:`state`."""

    kind: str               # 'kv' | 'latent' | 'state'
    shape: tuple            # (heads, head_dim) | (width,) | the state block
    span: int = 0           # a sliding layer's span; 0 a full layer
    read: str = ''          # 'blocks' | 'groups' | 'ring' | 'window'
    op: str = ''            # what advances a state: 'retention' |
    #                         'short_conv' | 'ssm'

    @classmethod
    def kv(cls, heads, head_dim, read='blocks', span=0):
        if (read == 'ring') != bool(span):
            raise ValueError(f'a K/V layer is read by the ring walk exactly '
                             f'when it is sliding: read={read!r}, span={span}')
        return cls('kv', (int(heads), int(head_dim)), int(span), read)

    @classmethod
    def latent(cls, width):
        return cls('latent', (int(width),), read='groups')

    @classmethod
    def state(cls, block, op):
        return cls('state', tuple(int(n) for n in block), op=op)

    @property
    def refused_as(self):
        """The kind of the refusal table this layer is asked as (None: the
        plain pool of per-head rows, which serves every feature)."""
        if self.kind != 'kv':
            return self.kind
        return {'ring': 'sliding', 'groups': 'grouped',
                'window': 'window'}.get(self.read)


def _derived():
    return dataclasses.field(init=False, compare=False, repr=False)


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """A model's :class:`LayerCache` s in pool order, and ``window``: the
    rows a slot feeds a lockstep step (B > 1 for a window model, block
    diffusion, whose step is `DecodeEngine.window_step`). The counts below
    are made once, as it is built."""

    layers: tuple
    window: int = 1
    row_layers: int = _derived()        # a row a token: K/V or latent
    state_layers: int = _derived()      # one state a request
    conv_layers: int = _derived()       # state layers a short conv advances
    ssm_layers: int = _derived()        # state layers a Mamba-2 scan advances
    kind: str = _derived()              # the row layers', or 'state'
    span: int = _derived()              # the sliding class's; 0 none
    sliding_layers: int = _derived()
    full_layers: int = _derived()       # row layers not sliding
    # the K/V layers go through the grouped one-token reads, full and
    # sliding (docs/SERVING.md "Layer classes"): positions, blocks and
    # gauges are booked by class
    classes: bool = _derived()
    # ((read, row layers that take it), ...): what a step's blocks go by
    reads: tuple = _derived()
    group_reads: bool = _derived()      # some read walks groups of blocks

    def __post_init__(self):
        rows = [layer for layer in self.layers if layer.kind != 'state']
        spans = sorted({layer.span for layer in rows if layer.span})
        if len(spans) > 1:
            raise ValueError(
                f'the model\'s sliding layers span {spans}: the pool holds '
                f'one sliding class, of one span')
        reads = collections.Counter(layer.read for layer in rows)
        derived = dict(
            row_layers=len(rows), state_layers=len(self.layers) - len(rows),
            conv_layers=sum(layer.op == 'short_conv'
                            for layer in self.layers),
            ssm_layers=sum(layer.op == 'ssm' for layer in self.layers),
            kind=rows[0].kind if rows else 'state',
            span=spans[0] if spans else 0,
            sliding_layers=sum(bool(layer.span) for layer in rows),
            full_layers=sum(not layer.span for layer in rows),
            classes=any(layer.refused_as in ('grouped', 'sliding')
                        for layer in rows),
            reads=tuple(sorted(reads.items())),
            group_reads=any(read != 'blocks' for read in reads))
        for name, value in derived.items():
            object.__setattr__(self, name, value)

    # -- geometry for a pool -------------------------------------------------
    def ring(self, block_size):
        return ring_blocks(self.span, block_size)

    def sliding_blocks(self, slots, block_size):
        """Depth of the sliding class's arrays, DERIVED, never asked for: a
        ring a slot and the spare, since a slot never holds more of it."""
        return (int(slots) * self.ring(block_size) + SLIDING_SPARE_BLOCKS
                if self.span else 0)

    def state_rows(self, slots):
        """Rows of a state layer's array: a slot's each and the scratch row
        of idle slots (0 without a state layer)."""
        return int(slots) + 1 if self.state_layers else 0

    def step_rows(self, slots):
        """Rows a lockstep step feeds the model: what its matmuls, its
        router and its head are priced over."""
        return int(slots) * self.window

    # -- what it costs -------------------------------------------------------
    def token_bytes(self, kv_dtype='f32'):
        """HBM bytes ONE token costs in ONE row layer: a K and a V row of
        its heads, or one latent row, in the lanes the pool gives them (int8
        rows carry an f32 scale a head; a latent row has no int8 form). 0
        where every layer keeps a state, priced a slot instead."""
        if self.kind == 'state':
            if kv_dtype != 'f32':
                raise ValueError('a state cache is float32: it has no '
                                 f'kv_dtype={kv_dtype} form')
            return 0
        row = next(layer for layer in self.layers if layer.kind != 'state')
        if row.kind == 'latent':
            if kv_dtype == 'int8':
                raise ValueError('a latent KV cache has no int8 rows')
            return kv_row_bytes(1, row.shape[0], kv_dtype)
        return 2 * kv_row_bytes(*row.shape, kv_dtype)

    def block_bytes(self, block_size, kv_dtype='f32'):
        """HBM bytes ONE block costs across the FULL class's layers: the
        block count a budget buys is theirs."""
        return self.full_layers * int(block_size) * self.token_bytes(
            kv_dtype)

    def state_row_bytes(self):
        """HBM bytes ONE request's float32 state row costs across every
        state layer, whatever its context."""
        if not self.state_layers:
            raise ValueError(
                f'a {self.kind} cache holds rows per token, no state row: '
                f'price it by its block bytes')
        return sum(math.prod(layer.shape) * 4 for layer in self.layers
                   if layer.kind == 'state')

    def context_bytes(self, context, kv_dtype='f32'):
        """HBM bytes of ONE context's rows in every row layer: ``context``
        positions a full layer, ``min(context, span)`` a sliding one."""
        return self.token_bytes(kv_dtype) * (
            self.full_layers * int(context)
            + self.sliding_layers * min(int(context), self.span))

    def request_bytes(self, context, kv_dtype='f32'):
        """HBM bytes ONE request of ``context`` positions holds: its rows
        and, with state layers, its state row."""
        return (self.context_bytes(context, kv_dtype) if self.row_layers
                else 0) + (self.state_row_bytes() if self.state_layers else 0)

    def sliding_class_bytes(self, slots, block_size, kv_dtype='f32'):
        """HBM bytes of the sliding class's arrays: a fixed cost beside the
        weights, whatever the budget."""
        return (self.sliding_layers * self.sliding_blocks(slots, block_size)
                * int(block_size) * self.token_bytes(kv_dtype))

    # -- what it refuses -----------------------------------------------------
    def refuse(self, prefix_cache=False, spec_decode=False, kv_dtype='f32',
               handoff=False):
        """Raise `UnsupportedCacheFeature` for the first kind of this
        layout's layers, in the table's order, that cannot serve what is
        asked: the prefix cache, speculative decoding, ``kv_dtype`` (a state
        is float32 alone; int8 rows are the plain pool's), a window model's
        block step over the grouped reads, or the disaggregated handoff.
        Asked where the engine and the prefill role are built."""
        asked = {'prefix_cache': (_PREFIX, prefix_cache),
                 'spec_decode': (_SPEC, spec_decode),
                 'int8': ('kv_dtype=int8', kv_dtype == 'int8'),
                 'kv_dtype': (f'kv_dtype={kv_dtype}',
                              kv_dtype != 'f32' and not self.row_layers),
                 'window': ('a window model\'s block step', self.window > 1),
                 'handoff': ('the disaggregated handoff', handoff)}
        kinds = {layer.refused_as for layer in self.layers}
        for kind, (_, _, _, refused) in _REFUSALS.items():
            features = [asked[f][0] for f in refused if asked[f][1]]
            if kind in kinds and features:
                raise UnsupportedCacheFeature(features, kind)


# -- the pool-size solve ------------------------------------------------------

def model_state_bytes(model):
    """Σ parameter bytes of a dygraph model, at runtime widths."""
    return sum(int(getattr(getattr(p, 'value', p), 'nbytes', 0))
               for p in model.parameters())


def _layout(model):
    if not hasattr(model, 'cache_layout'):
        # a budget solve over unknown geometry would size the pool wrong
        raise ValueError(
            'decode-pool budget solve needs model.cache_layout() (the '
            'models/causal_lm.py, latent_moe_lm.py and retention_lm.py '
            'contract); pass an explicit max_blocks / '
            'PADDLE_TPU_DECODE_MAX_BLOCKS for models without it')
    return model.cache_layout()


def solve_decode_state_slots(model, hbm_mb):
    """Slots a budget covers for a state cache: (budget − model state) //
    the bytes of one state row, less the scratch row of idle slots. Raises
    when the budget does not cover the weights and one slot."""
    budget = int(float(hbm_mb) * (1 << 20))
    state = model_state_bytes(model)
    row = _layout(model).state_row_bytes()
    rows = (budget - state) // row
    if rows < 2:
        raise ValueError(
            f'a budget of {hbm_mb} MiB ({budget} bytes) does not cover the '
            f'model state ({state} bytes) and two state rows of {row} bytes '
            f'(a slot and the scratch row)')
    return int(rows) - 1


def solve_decode_pool_blocks(model, hbm_mb, block_size, kv_dtype='f32',
                             min_blocks=2, slots=None):
    """The ``PADDLE_TPU_DECODE_HBM_MB`` budget solve: blocks = (budget −
    model state) // a block's bytes, floored at ``min_blocks`` (the engine
    passes max_blocks_per_seq + 1 so an empty pool always covers one
    maximal request). Raises when the budget does not cover the model's
    resident state: a silent floor would hide that the budget is fiction.
    The sliding class's arrays (a ring a slot) and a hybrid's state rows
    (``slots + 1``) come off the budget first, so both need ``slots``; the
    blocks bought are the full class's."""
    layout = _layout(model)
    budget = int(hbm_mb) << 20
    state = model_state_bytes(model)
    if layout.row_layers and layout.state_layers:
        if slots is None:
            raise ValueError(
                'a model with state layers beside row layers is sized per '
                'kind: solve_decode_pool_blocks needs slots (a state row a '
                'slot and the scratch row)')
        state += layout.state_rows(slots) * layout.state_row_bytes()
    if layout.sliding_layers:
        if slots is None:
            raise ValueError(
                'a model with a sliding class of layer is sized per class: '
                'solve_decode_pool_blocks needs slots (the sliding class '
                'holds a ring a slot)')
        state += layout.sliding_class_bytes(slots, block_size, kv_dtype)
    block_bytes = layout.block_bytes(block_size, kv_dtype)
    if budget <= state:
        raise ValueError(
            f'PADDLE_TPU_DECODE_HBM_MB={hbm_mb} ({budget} bytes) does not '
            f'cover the model state ({state} bytes); nothing left for the '
            f'KV pool')
    if not block_bytes:
        # a state cache: blocks book lengths and no HBM stands behind them
        return int(min_blocks)
    return max(int(min_blocks), (budget - state) // block_bytes)


def decode_pool_report(model, hbm_mb, block_size, kv_dtype='f32',
                       min_blocks=2, slots=None):
    """The solve itemized for tools/plan_program.py: every term of the
    closed form beside the block count (``slots`` as the solve takes it)."""
    layout = _layout(model)
    block_bytes = layout.block_bytes(block_size, kv_dtype)
    blocks = solve_decode_pool_blocks(model, hbm_mb, block_size, kv_dtype,
                                      min_blocks, slots)
    extra = {}
    if layout.state_layers:
        extra['state_row_bytes'] = layout.state_row_bytes()
        if not layout.row_layers:
            extra['state_slots'] = solve_decode_state_slots(model, hbm_mb)
    return {**extra, 'budget_mb': int(hbm_mb), 'kv_dtype': kv_dtype,
            'block_size': int(block_size),
            'model_state_bytes': model_state_bytes(model),
            'kv_layers': len(layout.layers), 'kv_cache': layout.kind,
            'step_rows_per_slot': layout.window,
            'row_bytes': layout.token_bytes(kv_dtype),
            'block_bytes': block_bytes, 'num_blocks': int(blocks),
            'pool_bytes': int(blocks) * block_bytes}
