"""The unified SPMD Partitioner: ONE owned device mesh, logical axis
rules, and a PartitionSpec answer for every tensor a Program touches.

Before this subsystem each ``parallel/`` module hand-rolled its own mesh
and sharding plumbing, so DP×TP×FSDP could not compose (ROADMAP item 1).
Now a single :class:`Partitioner` (the T5X pattern — SNIPPETS.md
[1]–[3]) owns:

- the **device mesh**, built once from a ``DistributedStrategy`` /
  ``PADDLE_TPU_MESH`` env topology (hybrid ICI×DCN through
  ``device_mesh.make_hybrid_mesh`` when a DCN shape is given; plain
  CPU-mesh fallback for tests);
- the **logical axis rules** (rules.AxisRules) mapping logical names
  (``batch``/``embed``/``mlp``/``heads``/``kv``/``fsdp``…) onto mesh
  axes through an ordered first-match table;
- **spec resolution** for every persistable and activation of a Program
  — zero tracing, driven by the PR 10 ``analysis/infer.py`` VarInfo
  shapes (propagation.py) — which the Executor consults when lowering
  and the resilience layer records per checkpoint.

The process-global instance is the successor of the old
``parallel.mesh`` module globals: ``get_partitioner()`` /
``configure()`` replace ``set_default_mesh`` (now a deprecated shim).
"""
from __future__ import annotations

import contextlib
import logging
import os
from typing import Dict, Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import device_mesh
from .rules import (AxisRules, largest_divisible_dim, parse_axis_rules,
                    parse_mesh_shape)

__all__ = ['Partitioner', 'get_partitioner', 'set_partitioner', 'configure',
           'reset_partitioner', 'mesh_scope', 'state_spec_fn',
           'spec_entries', 'entries_to_json', 'ENV_AXIS_RULES']

ENV_AXIS_RULES = 'PADDLE_TPU_AXIS_RULES'

# Megatron parameter-name markers (ref: the c_allreduce-after-row-matmul
# fleet model-parallel mode): up-projections / QKV shard their OUTPUT
# features (logical 'mlp'), down-projections their INPUT features.
COLUMN_PARALLEL_MARKERS = ('ffn1', 'q_proj', 'k_proj', 'v_proj', '.q.',
                           '.k.', '.v.')
ROW_PARALLEL_MARKERS = ('ffn2', 'out_proj', '.out.')


def spec_entries(spec) -> tuple:
    """PartitionSpec → plain tuple of entries (None | str | tuple) — the
    stampable/JSON-able form checks.py and checkpoints consume."""
    return tuple(tuple(e) if isinstance(e, (tuple, list)) else e
                 for e in tuple(spec))


def entries_to_json(entries):
    return [list(e) if isinstance(e, tuple) else e for e in entries]


class Partitioner:
    """Owns the device mesh and the logical-axis rule table; resolves a
    PartitionSpec / NamedSharding for any tensor by name, shape, or
    logical axes. Thread-unsafe by design (one per process, like the
    Executor's compile cache)."""

    def __init__(self, mesh: Optional[Mesh] = None, mesh_shape=None,
                 dcn_mesh_shape=None, axis_rules=None, devices=None,
                 use_cpu_jit=False):
        # mesh precedence: explicit Mesh > mesh_shape (+DCN hybrid) >
        # PADDLE_TPU_MESH env > unconfigured (None — single-device /
        # replicated semantics, what tests get by default)
        mesh_shape = parse_mesh_shape(mesh_shape)
        dcn_mesh_shape = parse_mesh_shape(dcn_mesh_shape,
                                          source='dcn_mesh_shape')
        if mesh is None and mesh_shape is not None:
            if dcn_mesh_shape:
                mesh = device_mesh.make_hybrid_mesh(
                    mesh_shape, dcn_mesh_shape, devices)
            else:
                mesh = device_mesh.make_mesh(mesh_shape, devices)
        if mesh is None:
            mesh = device_mesh.mesh_from_env()
        self._mesh = mesh
        env_rules = os.environ.get(ENV_AXIS_RULES)
        if env_rules:
            axis_rules = parse_axis_rules(env_rules, source=ENV_AXIS_RULES)
        self._rules = (axis_rules if isinstance(axis_rules, AxisRules)
                       else AxisRules(axis_rules))
        self._use_cpu_jit = bool(use_cpu_jit)

    # -- mesh ownership --------------------------------------------------

    @property
    def mesh(self) -> Optional[Mesh]:
        return self._mesh

    @property
    def rules(self) -> AxisRules:
        return self._rules

    def set_mesh(self, mesh: Optional[Mesh]):
        self._mesh = mesh

    def axis_sizes(self) -> Dict[str, int]:
        return dict(self._mesh.shape) if self._mesh is not None else {}

    def axis_size(self, axis) -> int:
        if self._mesh is None or axis is None:
            return 1
        sizes = self._mesh.shape
        if isinstance(axis, (tuple, list)):
            return int(np.prod([sizes.get(a, 1) for a in axis]))
        return int(sizes.get(axis, 1))

    def describe(self) -> str:
        if self._mesh is None:
            return 'Partitioner(mesh=None)'
        shape = ', '.join(f'{a}={s}' for a, s in self._mesh.shape.items())
        return f'Partitioner(mesh={{{shape}}}, rules={len(self._rules.rules)})'

    # -- logical resolution ----------------------------------------------

    def mesh_axes_for(self, logical, dim=None, taken=()):
        """Mesh axes (tuple) the logical axis resolves to in the owned
        mesh, or None (replicated / unconfigured)."""
        if self._mesh is None:
            return None
        return self._rules.resolve(logical, dict(self._mesh.shape),
                                   taken=taken, dim=dim)

    def resolve_spec(self, logical_axes, shape=None) -> PartitionSpec:
        """Logical spec (one logical name or None per dim) →
        PartitionSpec under the owned mesh + rules."""
        if self._mesh is None:
            return PartitionSpec()
        return self._rules.spec(logical_axes, dict(self._mesh.shape),
                                shape=shape)

    def sharding(self, spec) -> Optional[NamedSharding]:
        if self._mesh is None:
            return None
        if not isinstance(spec, PartitionSpec):
            spec = PartitionSpec(*spec)
        return NamedSharding(self._mesh, spec)

    # -- canonical specs -------------------------------------------------

    def data_axes(self) -> tuple:
        """Mesh axes the 'batch' logical axis shards over (the gradient
        sync axes), () when unconfigured."""
        return self.mesh_axes_for('batch') or ()

    def data_spec(self, batch_dim=None) -> PartitionSpec:
        axes = self.mesh_axes_for('batch', dim=batch_dim)
        if not axes:
            return PartitionSpec()
        return PartitionSpec(axes[0] if len(axes) == 1 else axes)

    def data_sharding(self, batch_dim=None) -> Optional[NamedSharding]:
        """Sharding for a batch tensor: leading dim over the data axes,
        rest replicated; None when unconfigured."""
        if self._mesh is None:
            return None
        spec = self.data_spec(batch_dim)
        if not tuple(spec):
            return None
        return NamedSharding(self._mesh, spec)

    def replicated(self) -> Optional[NamedSharding]:
        if self._mesh is None:
            return None
        return NamedSharding(self._mesh, PartitionSpec())

    def fsdp_spec(self, shape, axis=None) -> PartitionSpec:
        """ZeRO placement: the LARGEST dim divisible by the fsdp axis
        size shards, everything else replicates (parallel/fsdp.py
        semantics, now rule-table-driven)."""
        axes = ((axis,) if axis is not None
                else self.mesh_axes_for('fsdp'))
        if not axes or self._mesh is None \
                or axes[0] not in self._mesh.shape:
            return PartitionSpec()
        ax = axes[0]
        p = self._mesh.shape[ax]
        if p <= 1:
            return PartitionSpec()
        best = largest_divisible_dim(shape, p)
        if best is None:
            return PartitionSpec()
        entries = [None] * len(shape)
        entries[best] = ax
        return PartitionSpec(*entries)

    def param_spec(self, name, shape, fsdp_axis=None) -> PartitionSpec:
        """Spec for a parameter/optimizer-slot by name + shape: Megatron
        markers map 2-D projections onto the tensor axes (logical
        'embed'×'mlp'), anything else falls back to the fsdp rule (or
        replicated). Optimizer slots inherit their parameter's spec
        because slot names embed the parameter name."""
        name = name or ''
        if len(shape) == 2:
            tp = self.mesh_axes_for('mlp', dim=None)
            if tp:
                ax = tp[0]
                if any(m in name for m in COLUMN_PARALLEL_MARKERS) \
                        and _divides(shape[1], self.axis_size(ax)):
                    return PartitionSpec(None, ax)
                if any(m in name for m in ROW_PARALLEL_MARKERS) \
                        and _divides(shape[0], self.axis_size(ax)):
                    return PartitionSpec(ax, None)
        return self.fsdp_spec(shape, axis=fsdp_axis)

    def param_sharding(self, name, shape,
                       fsdp_axis=None) -> Optional[NamedSharding]:
        if self._mesh is None:
            return None
        return NamedSharding(self._mesh,
                             self.param_spec(name, shape,
                                             fsdp_axis=fsdp_axis))

    # -- program-level resolution (zero tracing) -------------------------

    def program_specs(self, program, include_activations=False,
                      fsdp_axis=None) -> Dict[str, tuple]:
        """Spec entries for every persistable (and, optionally, every
        activation via sharding propagation over the op registry) of a
        Program — shapes come from the declared VarInfos / the PR 10
        static inference engine, never from tracing."""
        from ..analysis.infer import declared_info
        out: Dict[str, tuple] = {}
        for v in program.list_vars():
            info = declared_info(v)
            shape = info.display_shape() or ()
            if v.persistable:
                spec = self.param_spec(v.name, tuple(shape),
                                       fsdp_axis=fsdp_axis)
            elif v.is_data:
                spec = self.data_spec(
                    shape[0] if shape and isinstance(shape[0], int)
                    and shape[0] > 0 else None)
            else:
                continue
            out[v.name] = spec_entries(spec)
        if include_activations:
            from .propagation import propagate_specs
            out = propagate_specs(program, self, seed=out)
        return out

    def stamp_program(self, program, include_activations=True,
                      fsdp_axis=None) -> Dict[str, tuple]:
        """Attach ``_partition_specs`` / ``_partition_mesh_axes`` to the
        program so analysis/checks.py runs the sharding-consistency
        diagnostics on it (and IR passes re-verify them per rewrite)."""
        specs = self.program_specs(program,
                                   include_activations=include_activations,
                                   fsdp_axis=fsdp_axis)
        program._partition_specs = specs
        program._partition_mesh_axes = self.axis_sizes()
        return specs

    # -- pjit-style lowering ---------------------------------------------

    def partition(self, fn, in_shardings=None, out_shardings=None,
                  static_argnums=(), donate_argnums=()):
        """pjit-style partitioned compile of ``fn`` under the owned mesh
        (SNIPPETS.md [1] ``pjit_with_cpu_fallback``): with
        ``use_cpu_jit`` (or no mesh) the sharding annotations drop and a
        plain ``jax.jit`` runs — the CPU test fallback. Donation
        interops with the PR 1 machinery (donate_argnums passes
        through)."""
        from ..core.compile_cache import setup_persistent_cache
        setup_persistent_cache()
        from ..core.places import on_tpu
        if self._mesh is None or (self._use_cpu_jit and not on_tpu()):
            return jax.jit(fn, static_argnums=static_argnums,
                           donate_argnums=donate_argnums)
        to_shard = lambda s: (jax.tree_util.tree_map(
            lambda x: self.sharding(x) if isinstance(x, PartitionSpec)
            else x, s, is_leaf=lambda x: isinstance(x, PartitionSpec))
            if s is not None else None)
        kw = {}
        if in_shardings is not None:
            kw['in_shardings'] = to_shard(in_shardings)
        if out_shardings is not None:
            kw['out_shardings'] = to_shard(out_shardings)
        return jax.jit(fn, static_argnums=static_argnums,
                       donate_argnums=donate_argnums, **kw)

    def shard_map(self, body, in_specs, out_specs):
        """compat.shard_map over the owned mesh — the explicit-SPMD
        surface the functional train steps lower through."""
        if self._mesh is None:
            raise ValueError(
                'Partitioner.shard_map: no mesh configured (pass '
                'mesh_shape to configure()/fleet.init, or set '
                'PADDLE_TPU_MESH)')
        from ..core import compat
        return compat.shard_map(body, mesh=self._mesh, in_specs=in_specs,
                                out_specs=out_specs)

    def replica_put(self, value, axis):
        """Broadcast ``value`` to (axis_size, *shape) and place it
        sharded over ``axis`` — the divergent-replica layout local/geo
        SGD carry (one stacked row per device)."""
        import jax.numpy as jnp
        n = self.axis_size(axis)
        arr = jnp.asarray(value)
        spec = PartitionSpec(axis, *([None] * arr.ndim))
        return jax.device_put(jnp.broadcast_to(arr, (n,) + arr.shape),
                              NamedSharding(self._mesh, spec))

    # -- checkpoint manifest ---------------------------------------------

    def state_manifest(self, program=None, fsdp_axis=None) -> dict:
        """JSON-safe record of mesh topology + rules (+ per-persistable
        specs when a program is given) — written into every checkpoint
        manifest so a restore can re-shard state onto a DIFFERENT mesh
        (the prerequisite for sharded per-host save/load, ROADMAP 2)."""
        m = {'mesh_axes': self.axis_sizes(),
             'axis_rules': self._rules.to_json()}
        if program is not None:
            m['specs'] = {
                name: entries_to_json(entries)
                for name, entries in self.program_specs(
                    program, fsdp_axis=fsdp_axis).items()}
        return m


def _divides(dim, size):
    return isinstance(dim, int) and dim > 0 and size > 0 \
        and dim % size == 0


# ---------------------------------------------------------------------------
# the process-global instance (successor of parallel.mesh's module globals)
# ---------------------------------------------------------------------------

_GLOBAL: Optional[Partitioner] = None


def get_partitioner() -> Partitioner:
    """The process partitioner; lazily built unconfigured (mesh from
    ``PADDLE_TPU_MESH`` when set, else None)."""
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = Partitioner()
    return _GLOBAL


def set_partitioner(p: Optional[Partitioner]):
    global _GLOBAL
    _GLOBAL = p


def reset_partitioner():
    set_partitioner(None)


def configure(mesh=None, mesh_shape=None, dcn_mesh_shape=None,
              axis_rules=None, devices=None, use_cpu_jit=False
              ) -> Partitioner:
    """Build + install the process partitioner (fleet.init's mesh
    bring-up calls this). Strict parse on mesh_shape/axis_rules. The
    global instance is updated IN PLACE when one exists, so scoped
    overrides (mesh_scope) that captured it restore correctly."""
    global _GLOBAL
    p = Partitioner(mesh=mesh, mesh_shape=mesh_shape,
                    dcn_mesh_shape=dcn_mesh_shape, axis_rules=axis_rules,
                    devices=devices, use_cpu_jit=use_cpu_jit)
    if _GLOBAL is None:
        _GLOBAL = p
    else:
        _GLOBAL._mesh = p._mesh
        _GLOBAL._rules = p._rules
        _GLOBAL._use_cpu_jit = p._use_cpu_jit
    return _GLOBAL


@contextlib.contextmanager
def mesh_scope(mesh: Optional[Mesh]):
    """Temporarily swap the partitioner's owned mesh (the mesh_guard
    successor — tests and scoped bring-up use it)."""
    p = get_partitioner()
    old = p.mesh
    p.set_mesh(mesh)
    try:
        yield mesh
    finally:
        p.set_mesh(old)


def state_spec_fn(program):
    """(name, shape) → NamedSharding resolver for a program's persistable
    state, or None when the program is not partitioned / no mesh is
    configured. The Executor consults this once per (program, scope) when
    lowering (executor.py): ``_fsdp_axis``-stamped programs keep the
    legacy pure-fsdp placement bitwise; ``_partition_params`` programs
    get the full rule-table resolution (tp + fsdp composition)."""
    p = get_partitioner()
    mesh = p.mesh
    if mesh is None:
        return None
    fsdp_axis = getattr(program, '_fsdp_axis', None)
    partitioned = getattr(program, '_partition_params', False)
    if partitioned:
        return lambda name, shape: NamedSharding(
            mesh, p.param_spec(name, tuple(shape), fsdp_axis=fsdp_axis))
    if fsdp_axis is None or fsdp_axis not in mesh.shape:
        return None
    return lambda name, shape: NamedSharding(
        mesh, p.fsdp_spec(tuple(shape), axis=fsdp_axis))


_DEPRECATION_WARNED = set()


def warn_once(key, message):
    """One-per-process deprecation warning through log_helper (repo
    invariant: never print)."""
    if key in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(key)
    from ..log_helper import get_logger
    get_logger(__name__, logging.WARNING).warning(message)
