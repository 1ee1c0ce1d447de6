"""Radix prefix cache over the paged KV pool: shared prompt prefixes resolve
to already-filled, refcounted cache blocks, so prefill runs only on the
uncached suffix (docs/SERVING.md "Serving tier"; kernel-side blueprint:
"Ragged Paged Attention", PAPERS.md arxiv 2604.15464).

Why a trie keyed at BLOCK granularity: K/V rows for position ``p`` depend on
the whole token prefix ``[0..p]`` (attention mixes every earlier position
into layer-1+ activations), so cached K/V is only reusable for a prompt that
matches the ENTIRE prefix leading to it. A radix trie whose edges are
``block_size``-token chunks encodes exactly that: the node reached by
walking a prompt's whole-block chunks holds a block id whose K/V content is
valid for ANY prompt sharing that prefix — and block granularity means a hit
plugs straight into the request's :class:`~..decode.kv_cache.BlockTable`
with zero copying.

Parity design (the load-bearing PR 6 contract): the uncached suffix is NOT
run through a second prefill formulation — the scheduler feeds the
remaining prompt tokens through the SAME lockstep ``(S, 1)`` decode step
used for generation (chunked prefill), whose logits rows are held to the
whole-sequence forward at ``padded_context`` (engine.py: equal token
streams, rows within a tolerance). A cached-hit generation emits the cold
generation's tokens, and the parity suite
(tests/framework/test_prefix_cache.py) asserts it; the cached blocks
themselves are byte-for-byte what the cold request wrote.

Host spill tier (docs/SERVING.md "Tiered KV cache"): with
``PADDLE_TPU_PREFIX_CACHE_HOST_MB`` > 0, an idle block that would be
evicted is instead SPILLED — serialized to host RAM as a one-block
:class:`~.disagg.KVPayload` (the npz wire format; same bytes a cross-host
handoff would ship) while its trie node stays in place with ``block=None``.
A later radix hit walking through spilled nodes reinjects them: blocks are
reallocated and the whole reinjected run lands with ONE scatter per layer
(``KVCachePool.write_whole_blocks``), so the working set the cache can
serve is host-RAM-sized, not HBM-sized. The host tier is an LRU bounded by
the byte cap; overflowing entries are dropped for real (with their fully-
spilled subtrees). Spilled-subtree invariant: a spilled node never has a
resident descendant — spill victims have none, and both ``match`` (via
reinjection) and ``insert`` (via promotion from the publishing request's
identical private copy) restore residency top-down along any path they
walk.

Invariants:

- only WHOLE blocks of prompt tokens are published (a block also holding
  generated or padded rows is request-private and never enters the trie);
- the last prompt token is never served from cache (``match`` caps at
  ``(P - 1) // block_size`` blocks): at least one token must be fed through
  the model to produce the first generated token's logits;
- refcounts (``kv_cache.BlockAllocator``): a resident block carries the
  cache's own reference plus one per live request sharing it. Spill/evict
  is LRU over **refcount-idle** nodes with no resident children, so
  interior nodes never orphan reachable resident blocks; it triggers on
  pool pressure (an allocation that would otherwise raise OutOfBlocks) and
  on the ``PADDLE_TPU_PREFIX_CACHE_MAX_BLOCKS`` cap at publish — counted
  apart as ``prefix_cache_evictions{cause=pressure|cap}``. Nodes on the
  walk that triggered the pressure are excluded from victim selection (an
  eviction there would detach the path being built and leak its blocks).

Metrics (always-on, docs/OBSERVABILITY.md): ``prefix_cache_hits/misses``,
``prefix_cache_tokens_saved`` (prefill-compute-saved),
``prefix_cache_blocks_resident``, ``prefix_cache_inserted_blocks``,
``prefix_cache_evicted_blocks``, ``prefix_cache_evictions{cause}``, and the
spill tier's ``kv_cache_{bytes_spilled,spill_count,reinject_count,
reinject_seconds}``.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

from .. import metrics as _m
from ..errors import InvalidRequest, OutOfBlocks
from ..decode.kv_cache import BlockTable
from .knobs import (ENV_PREFIX_CACHE_HOST_MB, ENV_PREFIX_CACHE_MAX_BLOCKS,
                    parse_int_env)

__all__ = ['PrefixCache']


class _Node:
    __slots__ = ('block', 'children', 'parent', 'chunk', 'last_use')

    def __init__(self, block, parent=None, chunk=None):
        self.block = block            # pool block id; None = spilled (or root)
        self.children = {}            # chunk tuple -> _Node
        self.parent = parent
        self.chunk = chunk            # this node's edge key in parent
        self.last_use = 0


class _HostTier:
    """Byte-bounded LRU of spilled one-block payloads, keyed by trie node.
    Overflow returns the DROPPED nodes so the cache can unlink their
    (fully-spilled) subtrees — a payload the LRU let go of must not leave a
    dangling trie path that ``match`` would try to reinject."""

    def __init__(self, cap_bytes):
        self.cap = int(cap_bytes)
        self.bytes = 0
        self._entries = collections.OrderedDict()   # _Node -> payload bytes

    def __len__(self):
        return len(self._entries)

    def __contains__(self, node):
        return node in self._entries

    def put(self, node, blob):
        self._entries[node] = blob
        self._entries.move_to_end(node)
        self.bytes += len(blob)
        dropped = []
        while self.bytes > self.cap and self._entries:
            n, b = self._entries.popitem(last=False)
            self.bytes -= len(b)
            dropped.append(n)
        return dropped

    def pop(self, node):
        blob = self._entries.pop(node)
        self.bytes -= len(blob)
        return blob

    def touch(self, node):
        if node in self._entries:
            self._entries.move_to_end(node)


class PrefixCache:
    """Token-trie prefix cache bound to one :class:`KVCachePool`.

    The intended owner is a :class:`~..decode.engine.DecodeEngine` (enable
    with ``DecodeEngine(prefix_cache=True)`` or ``PADDLE_TPU_PREFIX_CACHE=1``);
    all calls arrive on the scheduler worker thread, but a lock keeps
    direct multi-threaded engine use correct.

    ``max_blocks``: resident-block cap (0 = uncapped, bounded only by pool
    pressure); defaults from ``PADDLE_TPU_PREFIX_CACHE_MAX_BLOCKS``.
    ``host_mb``: host spill-tier byte cap (0 = no spill tier, idle blocks
    under pressure are dropped as before); defaults from
    ``PADDLE_TPU_PREFIX_CACHE_HOST_MB``.
    """

    def __init__(self, pool, max_blocks=None, host_mb=None):
        self.pool = pool
        self.block_size = pool.block_size
        self.max_blocks = (parse_int_env(ENV_PREFIX_CACHE_MAX_BLOCKS, 0,
                                         minimum=0)
                           if max_blocks is None else int(max_blocks))
        host_mb = (parse_int_env(ENV_PREFIX_CACHE_HOST_MB, 0, minimum=0)
                   if host_mb is None else int(host_mb))
        self._host = _HostTier(host_mb << 20) if host_mb else None
        self._root = _Node(None)
        self._resident = 0
        self._clock = itertools.count(1)
        self._lock = threading.RLock()

    # -- introspection -----------------------------------------------------
    @property
    def resident_blocks(self):
        return self._resident

    @property
    def spilled_blocks(self):
        """Blocks currently living in the host tier (0 when it is off)."""
        return len(self._host) if self._host is not None else 0

    @property
    def host_bytes(self):
        return self._host.bytes if self._host is not None else 0

    def resident_block_ids(self):
        with self._lock:
            out = []
            stack = list(self._root.children.values())
            while stack:
                n = stack.pop()
                if n.block is not None:
                    out.append(n.block)
                stack.extend(n.children.values())
            return out

    # -- lookup ------------------------------------------------------------
    def match(self, prompt):
        """Longest cached whole-block prefix of ``prompt``, RETAINED for the
        caller (one reference per block). Returns the block-id list; at
        most ``(len(prompt) - 1) // block_size`` blocks so at least one
        prompt token is always left to feed. Spilled nodes on the hit path
        are reinjected from the host tier (the path truncates at the first
        spilled node the pool cannot make room for)."""
        bs = self.block_size
        usable = max(len(prompt) - 1, 0) // bs
        with self._lock:
            node, path = self._root, []
            for i in range(usable):
                child = node.children.get(tuple(prompt[i * bs:(i + 1) * bs]))
                if child is None:
                    break
                path.append(child)
                node = child
            path = self._reinject_path(path)
            blocks = [n.block for n in path]
            # stamp the whole hit path as one recency unit (leaf-first LRU
            # then naturally evicts deepest, least-shared nodes first)
            tick = next(self._clock)
            n = path[-1] if path else self._root
            while n is not None and n is not self._root:
                n.last_use = tick
                n = n.parent
            if blocks:
                self.pool.allocator.retain(blocks)
        if blocks:
            _m.prefix_cache_hits.inc()
            _m.prefix_cache_tokens_saved.inc(len(blocks) * bs)
        else:
            _m.prefix_cache_misses.inc()
        return blocks

    def _reinject_path(self, path):
        """Restore residency for spilled nodes on a hit path: allocate a
        block each (spilling/evicting NON-path idles under pressure), then
        scatter all reinjected payloads with one ``write_whole_blocks``
        per layer. Returns the (possibly truncated) usable path."""
        if not any(n.block is None for n in path):
            return path
        from .disagg import KVPayload
        t0 = time.perf_counter()
        exclude = set(map(id, path))
        pending = []                       # [node, new block id, payload]
        try:
            for idx, n in enumerate(path):
                if n.block is not None:
                    continue
                # ``exclude`` shields path nodes from VICTIM selection
                # only: a pressure spill below can still overflow the
                # host LRU and drop a later path node (this one
                # included) — so check membership before allocating and
                # after.
                bid = None
                if n in self._host:
                    try:
                        bid = self._allocate_evicting(1, exclude=exclude)[0]
                    except OutOfBlocks:
                        bid = None
                    if bid is not None and n not in self._host:
                        self.pool.allocator.release([bid])
                        bid = None
                if bid is None:
                    # truncate here; the still-spilled tail was just
                    # matched (hot), so refresh its host-LRU recency
                    for m in path[idx:]:
                        if m.block is None and m in self._host:
                            self._host.touch(m)
                    path = path[:idx]
                    break
                pending.append([n, bid, None])
                pending[-1][2] = KVPayload.from_bytes(self._host.pop(n))
            if not pending:
                return path
            import numpy as np
            ids = [bid for _, bid, _ in pending]
            n_layers = max(len(p.layers) for _, _, p in pending)
            for layer in range(n_layers):
                k = np.concatenate([p.layers[layer][0]
                                    for _, _, p in pending], axis=1)
                v = np.concatenate([p.layers[layer][1]
                                    for _, _, p in pending], axis=1)
                ks = vs = None
                if pending[0][2].scales is not None:
                    ks = np.concatenate(
                        [p.scales[layer][0] for _, _, p in pending], axis=1)
                    vs = np.concatenate(
                        [p.scales[layer][1] for _, _, p in pending], axis=1)
                self.pool.write_whole_blocks(layer, ids, k, v,
                                             k_scale=ks, v_scale=vs)
        except BaseException:
            # the pending payloads are already popped from the host
            # tier: return their blocks to the pool and drop the now-
            # irrecoverable nodes so a later match cannot dangle on them
            self.pool.allocator.release([bid for _, bid, _ in pending])
            for m, _, _ in pending:
                self._drop_spilled(m)
            raise
        for n, bid, _ in pending:
            # the fresh allocation's refcount 1 becomes the cache's own
            # residency reference (mirror of insert's retain)
            n.block = bid
            self._resident += 1
        _m.prefix_cache_blocks_resident.set(self._resident)
        _m.kv_cache_reinject_count.inc(len(pending))
        _m.kv_cache_reinject_seconds.observe(time.perf_counter() - t0)
        return path

    # -- admission ---------------------------------------------------------
    def acquire_table(self, prompt, total_tokens):
        """Build a request's :class:`BlockTable` for ``total_tokens``
        (prompt + generation budget): shared cached-prefix blocks first,
        freshly allocated blocks for the rest. Pool pressure spills (or
        evicts) idle cached blocks before giving up (the re-raised
        OutOfBlocks is the scheduler's FIFO-wait signal, unchanged)."""
        bs = self.block_size
        nb = -(-int(total_tokens) // bs)
        if nb > self.pool.max_blocks_per_seq:
            raise InvalidRequest(
                f'{total_tokens} tokens need {nb} blocks > '
                f'max_blocks_per_seq={self.pool.max_blocks_per_seq}')
        with self._lock:
            shared = self.match(prompt) if prompt else []
            try:
                fresh = self._allocate_evicting(nb - len(shared))
            except OutOfBlocks:
                if shared:
                    self.pool.allocator.release(shared)
                raise
        return BlockTable(shared + fresh, bs,
                          cached_len=len(shared) * bs)

    def _allocate_evicting(self, n, exclude=frozenset()):
        while True:
            try:
                return self.pool.allocator.allocate(n)
            except OutOfBlocks:
                if not self._spill_or_evict_one(exclude=exclude,
                                                cause='pressure'):
                    raise

    # -- publication -------------------------------------------------------
    def insert(self, prompt, table):
        """Publish ``table``'s whole-prompt blocks into the trie. Blocks
        already cached along the path are skipped (the request keeps its
        private copy in its table — content is identical by construction);
        new nodes retain their block so it survives the request. A SPILLED
        node on the path is promoted back to residency from the request's
        private copy (same content, zero deserialization). The
        ``PADDLE_TPU_PREFIX_CACHE_MAX_BLOCKS`` cap is enforced here too
        (cause=``cap``), with the walked path excluded from victim
        selection — evicting a node this very walk stands on would attach
        the new child to a detached subtree and leak its block."""
        bs = self.block_size
        full = len(prompt) // bs
        tick = next(self._clock)
        with self._lock:
            node = self._root
            walked = {id(self._root)}
            for i in range(full):
                chunk = tuple(int(t) for t in prompt[i * bs:(i + 1) * bs])
                child = node.children.get(chunk)
                needs_block = child is None or child.block is None
                if needs_block:
                    if self.max_blocks and self._resident >= self.max_blocks:
                        if not self._spill_or_evict_one(exclude=walked,
                                                        cause='cap'):
                            break     # cap reached, nothing idle to move
                    bid = table.blocks[i]
                    self.pool.allocator.retain([bid])
                    if child is None:
                        child = _Node(bid, parent=node, chunk=chunk)
                        node.children[chunk] = child
                    else:             # promote the spilled node in place
                        child.block = bid
                        if self._host is not None and child in self._host:
                            self._host.pop(child)
                    self._resident += 1
                    _m.prefix_cache_inserted_blocks.inc()
                child.last_use = tick
                walked.add(id(child))
                node = child
            _m.prefix_cache_blocks_resident.set(self._resident)

    # -- spill / eviction --------------------------------------------------
    def _spill_or_evict_one(self, exclude=frozenset(), cause='pressure',
                            allow_spill=True):
        """Move the least-recently-used idle node (block refcount == 1, no
        resident children — the spilled-subtree invariant keeps deeper
        descendants non-resident too) out of HBM: into the host tier when
        it is configured and ``allow_spill``, else dropped. ``exclude``
        holds ``id()``s of nodes the caller's walk depends on. Returns
        False when nothing is movable."""
        victim = None
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if (n.block is not None and id(n) not in exclude
                    and all(c.block is None for c in n.children.values())
                    and self.pool.allocator.refcount(n.block) == 1):
                if victim is None or n.last_use < victim.last_use:
                    victim = n
        if victim is None:
            return False
        bid = victim.block
        if self._host is not None and allow_spill:
            self._spill(victim)         # sets victim.block = None
        else:
            self._unlink(victim)
        self.pool.allocator.release([bid])
        self._resident -= 1
        _m.prefix_cache_evicted_blocks.inc()
        _m.prefix_cache_evictions.labels(cause=cause).inc()
        _m.prefix_cache_blocks_resident.set(self._resident)
        return True

    def _evict_one(self, exclude=frozenset(), cause='pressure'):
        """Pre-spill name, kept for callers/tests that poke the eviction
        machinery directly: move one idle block out of HBM (into the host
        tier when configured)."""
        return self._spill_or_evict_one(exclude=exclude, cause=cause)

    def _spill(self, node):
        """Serialize ``node``'s single block to the host tier as a
        one-block :class:`~.disagg.KVPayload` (the npz wire bytes a
        cross-host handoff would ship) and leave the node in place with
        ``block=None``. The block itself is released by the caller."""
        from .disagg import KVPayload
        pool = self.pool
        bid = node.block
        layers, scales, any_scales = [], [], False
        for layer in range(pool.num_layers):
            layers.append(pool.read_blocks(layer, [bid]))
            sc = pool.read_block_scales(layer, [bid])
            scales.append(sc)
            any_scales = any_scales or sc is not None
        payload = KVPayload(layers, self.block_size, 0, self.block_size,
                            kv_dtype=pool.kv_dtype,
                            scales=scales if any_scales else None)
        blob = payload.to_bytes()
        node.block = None
        for dropped in self._host.put(node, blob):
            # the LRU let this payload go — its trie path (fully spilled
            # by the invariant) must go with it or match would dangle
            self._drop_spilled(dropped)
        _m.kv_cache_spill_count.inc()
        _m.kv_cache_bytes_spilled.inc(len(blob))

    def _unlink(self, node):
        """Remove ``node`` from the trie. Its children are all spilled
        (victim selection guarantees no resident ones) and become
        unreachable — drop them from the host tier with it."""
        if node.parent is not None:
            del node.parent.children[node.chunk]
        for child in list(node.children.values()):
            self._drop_spilled(child)

    def _drop_spilled(self, node):
        """Discard a spilled node and its (spilled) subtree entirely."""
        if node.parent is not None and node.parent.children.get(
                node.chunk) is node:
            del node.parent.children[node.chunk]
        stack = [node]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if self._host is not None and n in self._host:
                self._host.pop(n)

    def evict_idle(self):
        """Drop every currently-idle cached block for real — no spilling
        (tests / shutdown want the pool AND host tier shrinking)."""
        with self._lock:
            n = 0
            while self._spill_or_evict_one(allow_spill=False):
                n += 1
            # fully-spilled subtrees have no resident node for the loop to
            # unlink through — drop them outright so host RAM drains too
            if self._host is not None:
                stack = [self._root]
                while stack:
                    node = stack.pop()
                    for child in list(node.children.values()):
                        if child.block is None:
                            self._drop_spilled(child)
                        else:
                            stack.append(child)
            return n
