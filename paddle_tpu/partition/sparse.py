"""Vocab-sharded embedding tables over the partitioner's mesh.

A V×D table too big for one device's HBM shards its VOCAB dim over a
mesh axis (the ``vocab`` logical-axis rule, rules.py): device i owns
rows [i·V/p, (i+1)·V/p). The access pattern is the classic
parameter-server exchange, expressed as XLA collectives (docs/SPARSE.md
"Vocab sharding"):

- **lookup** — every device takes an equal slice of the id batch,
  routes each id to its owner shard with an ``all_to_all``, the owner
  gathers locally, a second ``all_to_all`` returns the rows, and an
  ``all_gather`` re-replicates the output batch (ids → owners → rows
  back: O(nnz·D) wire bytes, never O(V·D)).
- **gradient push** — the padded-COO gradient pair is (optionally)
  gathered across a data axis through the PR 9 quantized codec
  (``quant_collectives.sparse_allgather``: int8 rows + per-row f32
  scales), then every shard scatter-applies ONLY its owned rows — the
  out-of-bounds drop does the routing.

Single-process CPU meshes (tests) and real TPU meshes share this code;
parity vs an unsharded dense table is asserted in
tests/framework/test_sparse_embedding.py.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import compat
from ..parallel import quant_collectives as qc
from ..ops import sparse_ops as sp

__all__ = ['VocabShardedTable', 'sharded_lookup', 'shard_owned_apply']


def _axis_size_of(mesh: Mesh, axis: str) -> int:
    if axis not in mesh.shape:
        raise ValueError(
            f"mesh {dict(mesh.shape)} has no axis {axis!r}")
    return int(mesh.shape[axis])


def sharded_lookup(w_local, ids, axis: str, vocab: int):
    """Inside shard_map (``axis`` bound, ``w_local`` = this device's
    (V/p, D) shard, ``ids`` replicated): the all-to-all exchange above.
    Returns the replicated (nnz, D) rows."""
    n = lax.psum(1, axis)
    me = lax.axis_index(axis)
    shard = vocab // n
    ids = ids.reshape(-1).astype(jnp.int32)
    nnz = ids.shape[0]
    chunk = -(-nnz // n)
    padded = chunk * n
    if padded != nnz:
        # sentinel pad: owner formula maps `vocab` to shard n (nobody),
        # so pad lanes ride along as masked zeros
        ids = jnp.concatenate(
            [ids, jnp.full((padded - nnz,), vocab, jnp.int32)])
    # my slice of the id batch
    my_ids = lax.dynamic_slice_in_dim(ids, me * chunk, chunk)
    owner = jnp.clip(my_ids // shard, 0, n)          # vocab → n (pad)
    # request buffer: lane (k, j) asks peer k for my j-th id iff k owns it
    want = owner[None, :] == jnp.arange(n)[:, None]          # (n, chunk)
    req = jnp.where(want, my_ids[None, :], vocab)            # vocab = "no"
    got = lax.all_to_all(req, axis, split_axis=0, concat_axis=0)
    # serve: gather my owned rows for every request lane
    local = jnp.clip(got - me * shard, 0, w_local.shape[0] - 1)
    rows = jnp.take(w_local, local, axis=0)                  # (n, chunk, D)
    rows = jnp.where(((got >= me * shard)
                      & (got < (me + 1) * shard))[..., None], rows, 0.0)
    back = lax.all_to_all(rows, axis, split_axis=0, concat_axis=0)
    # exactly one peer answered each of my lanes (the owner)
    mine = jnp.sum(back * want[..., None].astype(rows.dtype), axis=0)
    out = lax.all_gather(mine, axis).reshape(padded, -1)
    return out[:nnz]


def shard_owned_apply(w_local, rows, vals, axis: str, vocab: int, update):
    """Scatter-apply a replicated COO gradient to this device's shard:
    rows re-base to the local window and everything out of window drops
    (XLA scatter semantics do the routing). ``update(w_local, local_rows,
    vals)`` is the rows-only optimizer formula."""
    n = lax.psum(1, axis)
    me = lax.axis_index(axis)
    shard = vocab // n
    rows = jnp.asarray(rows).astype(jnp.int32)
    owned = (rows >= me * shard) & (rows < (me + 1) * shard)
    # out-of-window rows → index V/p (dropped by mode='drop')
    local_rows = jnp.where(owned, rows - me * shard, w_local.shape[0])
    return update(w_local, local_rows, jnp.asarray(vals))


class VocabShardedTable:
    """A (vocab, dim) embedding table sharded over ``axis`` of ``mesh``.

    ``lookup(ids)`` returns replicated rows for any replicated id batch;
    ``sgd_push(rows, vals, lr, dp_axis=, comm_dtype=)`` applies a padded
    COO gradient, optionally gathering it across a data axis through the
    quantized sparse push first. ``full_table()`` reassembles the dense
    table (tests / checkpoint export)."""

    def __init__(self, vocab, dim, mesh: Mesh, axis: str = 'tp',
                 init=None, dtype=jnp.float32):
        self.vocab, self.dim = int(vocab), int(dim)
        self.mesh, self.axis = mesh, axis
        n = _axis_size_of(mesh, axis)
        if self.vocab % n:
            raise ValueError(
                f"vocab {self.vocab} is not divisible by mesh axis "
                f"{axis!r} size {n}")
        self.shard_rows = self.vocab // n
        if init is None:
            init = np.zeros((self.vocab, self.dim), np.float32)
        init = np.asarray(init, np.float32)
        if init.shape != (self.vocab, self.dim):
            raise ValueError(
                f"init shape {init.shape} != ({self.vocab}, {self.dim})")
        self._sharding = NamedSharding(mesh, P(axis, None))
        self.weight = jax.device_put(jnp.asarray(init, dtype),
                                     self._sharding)
        self._lookup_fn = None
        self._push_fns = {}

    # -- lookup ---------------------------------------------------------
    def lookup(self, ids):
        """(…,) int ids → (…, dim) rows (replicated)."""
        ids = jnp.asarray(ids)
        shape = ids.shape
        if self._lookup_fn is None:
            mesh, axis, vocab = self.mesh, self.axis, self.vocab

            def fn(w, flat_ids):
                body = compat.shard_map(
                    lambda wl, i: sharded_lookup(wl, i, axis, vocab),
                    mesh=mesh, in_specs=(P(axis, None), P()),
                    out_specs=P(), check_vma=False)
                return body(w, flat_ids)
            from ..core.compile_cache import setup_persistent_cache
            setup_persistent_cache()
            self._lookup_fn = jax.jit(fn)
        out = self._lookup_fn(self.weight, ids.reshape(-1))
        return out.reshape(shape + (self.dim,))

    # -- gradient push --------------------------------------------------
    def sgd_push(self, rows, vals, lr, dp_axis=None, comm_dtype=None):
        """Rows-only SGD over the shards. With ``dp_axis`` the COO pair
        is per-replica: replicas exchange entries via the quantized
        sparse push (int8 rows + f32 scales at ``comm_dtype='int8'``)
        and every shard applies the global gradient — duplicate rows
        across replicas sum in the scatter-add, which is the gradient
        reduction."""
        comm = qc.resolve_comm_dtype(comm_dtype)
        key = (dp_axis, comm)
        fn = self._push_fns.get(key)
        if fn is None:
            mesh, axis, vocab = self.mesh, self.axis, self.vocab

            def body(wl, r, v, step_lr):
                if dp_axis is not None:
                    r, v = qc.sparse_allgather(r, v, dp_axis, comm)

                def apply(w_shard, local_rows, vv):
                    return w_shard.at[local_rows].add(
                        -step_lr.astype(w_shard.dtype)
                        * vv.astype(w_shard.dtype), mode='drop')
                return shard_owned_apply(wl, r, v, axis, vocab, apply)

            in_specs = (P(axis, None),
                        P(dp_axis) if dp_axis else P(),
                        P(dp_axis, None) if dp_axis else P(),
                        P())
            fn = jax.jit(compat.shard_map(
                body, mesh=mesh, in_specs=in_specs,
                out_specs=P(axis, None), check_vma=False))
            self._push_fns[key] = fn
        n_dp = _axis_size_of(self.mesh, dp_axis) if dp_axis else 1
        qc.record_sparse_collective(
            'sharded_push', int(np.shape(rows)[0]), self.dim, comm,
            n_dp, self.vocab * self.dim)
        self.weight = fn(self.weight, jnp.asarray(rows, jnp.int32),
                         jnp.asarray(vals), jnp.asarray(lr, jnp.float32))
        return self.weight

    # -- utilities ------------------------------------------------------
    def full_table(self):
        """Dense (vocab, dim) host copy (parity tests, export)."""
        rep = jax.device_put(self.weight, NamedSharding(self.mesh, P()))
        return np.asarray(rep)
