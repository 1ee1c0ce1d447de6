"""Dygraph autograd: Tensor (VarBase) + tape of jax.vjp nodes.

Parity with the reference imperative engine
(/root/reference/paddle/fluid/imperative/tracer.cc + gradient accumulation in
imperative/layer.cc), redesigned for XLA: every eager op call runs the SAME
registered jax functional the static graph uses, capturing its vjp; backward()
walks the tape in reverse topological order. Under `jit.to_static` the tape
records through tracers, so the whole step can still fuse into one XLA program.

Hot path: repeated eager dispatches reuse jitted kernels from an LRU cache
(see _EagerKernelCache below) instead of re-tracing per call.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import OrderedDict
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..core import unique_name
from ..core.dtypes import convert_dtype, to_jax_dtype
from ..core.random import default_generator
from ..ops.registry import get_op

# THREAD-LOCAL grad switch (default on). A process-global flag let a
# serving/decode worker thread's no_grad_guard() — every engine step
# runs under one — disable tape recording for EVERY thread: a training
# loop on the main thread would intermittently build tensors with no
# grad history while a scheduler thread was mid-step, and backward()
# raised. Per-thread state keeps each guard scoped to its own thread
# (regression: tests/dygraph/test_tape.py).
_grad_state = threading.local()
_tensor_watchers = []


def grad_enabled():
    """Whether op dispatch on THIS thread records grad history."""
    return getattr(_grad_state, 'enabled', True)


# ---------------------------------------------------------------------------
# Eager per-op jitted-kernel cache.
#
# The reference avoids Python dispatch overhead with ~1,500 LoC of C++ Tracer
# (imperative/tracer.cc); the TPU analogue is to make the SECOND eager call of
# an op signature free: each dispatch is keyed by (op_type, input avals, arg
# structure, attrs) and reuses a jitted kernel — one XLA executable for the
# forward (returning the vjp residuals as a Partial pytree) plus one for the
# backward — instead of re-tracing jax.vjp through the functional every call.
# LRU-bounded; PADDLE_TPU_EAGER_CACHE=0 is the escape hatch; statistics are
# exposed through profiler.eager_kernel_cache_stats().
# ---------------------------------------------------------------------------

class _Unhashable(Exception):
    pass


def _attr_sig(v):
    """Canonical hashable form of an op attr value, or raise _Unhashable
    (arrays, closures, initializer objects → bypass the cache). Scalars are
    tagged with their type: True and 1 hash equal in Python but may mean
    different things to an op body."""
    if isinstance(v, (str, bytes, int, float, bool, type(None))):
        return (type(v).__name__, v)
    if isinstance(v, (np.bool_, np.integer)):
        return ('int', int(v))
    if isinstance(v, np.floating):
        return ('float', float(v))
    if isinstance(v, (list, tuple)):
        return tuple(_attr_sig(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _attr_sig(x)) for k, x in v.items()))
    raise _Unhashable


_BLOCKED = object()   # negative-cache sentinel: this key cannot be jitted


class _EagerKernelCache:
    """LRU of per-op-signature jitted kernels for the dygraph hot path."""

    def __init__(self, maxsize=None):
        if maxsize is None:
            maxsize = int(os.environ.get('PADDLE_TPU_EAGER_CACHE_SIZE',
                                         '1024'))
        self.maxsize = max(int(maxsize), 1)
        self.enabled = os.environ.get('PADDLE_TPU_EAGER_CACHE', '1') != '0'
        self._entries = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.bypasses = 0     # unhashable attrs or untraceable op bodies

    def stats(self):
        return {'enabled': self.enabled, 'size': len(self._entries),
                'maxsize': self.maxsize, 'hits': self.hits,
                'misses': self.misses, 'evictions': self.evictions,
                'bypasses': self.bypasses}

    def clear(self):
        self._entries.clear()
        self.reset_stats()

    def reset_stats(self):
        """Zero the counters but KEEP the compiled kernels — a profiled
        re-run over a warm cache must report fresh hit/miss numbers without
        paying the recompiles that clear() would force."""
        self.hits = self.misses = self.evictions = self.bypasses = 0

    def get(self, key):
        e = self._entries.get(key)
        if e is not None and e is not _BLOCKED:
            self._entries.move_to_end(key)
            self.hits += 1
        return e

    def put(self, key, entry):
        self.misses += 1
        self._entries[key] = entry
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self.evictions += 1

    def block(self, key):
        """This signature failed to trace under jit (e.g. value-dependent
        Python control flow in the op body) — never try again."""
        self._entries[key] = _BLOCKED
        self.bypasses += 1


kernel_cache = _EagerKernelCache()


def kernel_cache_stats():
    return kernel_cache.stats()


def _collect_kernel_cache_gauges():
    """At-export snapshot of the kernel-cache counters into the telemetry
    registry — the cache's own hot path stays untouched."""
    s = kernel_cache.stats()
    g = _obs.registry.gauge(
        'eager_kernel_cache',
        'dygraph per-op jitted-kernel cache state (stat label selects '
        'hits/misses/evictions/bypasses/size/maxsize/enabled)')
    for k in ('size', 'maxsize', 'hits', 'misses', 'evictions', 'bypasses'):
        g.labels(stat=k).set(s[k])
    g.labels(stat='enabled').set(1.0 if s['enabled'] else 0.0)


_obs.registry.register_collector(_collect_kernel_cache_gauges)


@contextlib.contextmanager
def watch_tensors(collector: list):
    """Record every Tensor that flows into an op while active (used by
    `to_static` to discover which Parameters/buffers a traced function
    actually reads, so only those become inputs of the compiled program)."""
    _tensor_watchers.append(collector)
    try:
        yield
    finally:
        _tensor_watchers.pop()


@contextlib.contextmanager
def no_grad_guard():
    old = grad_enabled()
    _grad_state.enabled = False
    try:
        yield
    finally:
        _grad_state.enabled = old


def no_grad(fn=None):
    if fn is None:
        return no_grad_guard()
    import functools

    @functools.wraps(fn)
    def wrapper(*a, **k):
        with no_grad_guard():
            return fn(*a, **k)
    return wrapper


class Node:
    __slots__ = ('vjp_fn', 'inputs', 'n_outputs', 'out_avals', 'op_type',
                 'call_fn')

    def __init__(self, vjp_fn, inputs, n_outputs, out_avals, op_type,
                 call_fn=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs          # list[Tensor] in vjp arg order
        self.n_outputs = n_outputs
        self.out_avals = out_avals    # [(shape, dtype)] per output
        self.op_type = op_type
        # pure primal replay `call_fn(*input_values) -> op result` — lets
        # grad(create_graph=True) rebuild the forward as a jax function and
        # differentiate it to any order (ref: imperative/partial_grad_engine)
        self.call_fn = call_fn


class Tensor:
    """VarBase parity: eager tensor with autograd metadata."""

    def __init__(self, value, name=None, stop_gradient=False,
                 persistable=False, dtype=None):
        if dtype is not None:
            value = jnp.asarray(value, to_jax_dtype(dtype))
        else:
            value = jnp.asarray(value)
        self.value = value
        self.name = name or unique_name.generate('tensor')
        self.stop_gradient = stop_gradient
        self.persistable = persistable
        self.grad = None
        self._node: Optional[Node] = None
        self._out_index = 0

    # ---- info ----
    @property
    def shape(self):
        return tuple(self.value.shape)

    @property
    def dtype(self):
        return convert_dtype(self.value.dtype)

    @property
    def ndim(self):
        return self.value.ndim

    def numpy(self):
        return np.asarray(self.value)

    def __array__(self, dtype=None, copy=None):
        # without this, np.asarray falls back to the sequence protocol and
        # dispatches one traced slice op PER ELEMENT (minutes for a matrix)
        if copy is False:
            # device memory cannot be exposed as a writable host view
            raise ValueError(
                "converting a paddle_tpu Tensor to numpy always copies "
                "from device memory; np.asarray(t, copy=False) cannot "
                "return a view")
        a = np.asarray(self.value)
        if dtype is not None:
            a = a.astype(dtype)
        return np.array(a, copy=True) if copy else a

    def item(self):
        return self.value.item()

    def __len__(self):
        return self.value.shape[0]

    def __repr__(self):
        return f"Tensor(name={self.name}, shape={self.shape}, " \
               f"dtype={self.dtype}, stop_gradient={self.stop_gradient})\n" \
               f"{self.value}"

    # ---- autograd ----
    def backward(self, retain_graph=False, backward_strategy=None):
        run_backward(self, retain_graph=retain_graph)

    def gradient(self):
        if self.grad is None:
            return None
        from ..ops.sparse_ops import SparseRowsGrad
        if isinstance(self.grad, SparseRowsGrad):
            # API parity: user code reads a dense (V, D) gradient even
            # when the tape carried rows-only COO
            return np.asarray(self.grad.densify())
        return np.asarray(self.grad)

    def clear_gradient(self):
        self.grad = None

    def detach(self):
        t = Tensor(self.value, stop_gradient=True)
        return t

    def set_value(self, value):
        v = value.value if isinstance(value, Tensor) else jnp.asarray(value)
        self.value = v.astype(self.value.dtype)

    def astype(self, dtype):
        return dispatch_op('cast', {'x': self}, {'dtype': convert_dtype(dtype)})

    # math dunders are attached by monkey_patch_tensor() below


class Parameter(Tensor):
    def __init__(self, value, name=None, trainable=True, regularizer=None,
                 **kw):
        super().__init__(value, name=name, stop_gradient=not trainable,
                         persistable=True)
        self.trainable = trainable
        self.regularizer = regularizer
        self.optimize_attr = {'learning_rate': kw.get('learning_rate', 1.0)}


def to_tensor_value(x):
    return x.value if isinstance(x, Tensor) else jnp.asarray(x)


def dispatch_op(op_type, inputs, attrs):
    """Run a registered op eagerly, recording the tape. `inputs` is
    slot → Tensor | [Tensor] | None, matching the op's positional slots.

    Telemetry shim: with PADDLE_TPU_TELEMETRY off this is one bool check +
    one extra call frame on top of the real dispatch (_dispatch_op_impl);
    with it on, each dispatch lands one sample in the per-op latency
    histogram, labeled by whether the kernel cache served it."""
    if not _obs._ENABLED:
        return _dispatch_op_impl(op_type, inputs, attrs)
    hits0 = kernel_cache.hits
    t0 = time.perf_counter()
    try:
        with _obs.tracer.span('tape/' + op_type):
            return _dispatch_op_impl(op_type, inputs, attrs)
    finally:
        _obs.record_op_dispatch(op_type, time.perf_counter() - t0,
                                cached=kernel_cache.hits > hits0)


def _dispatch_op_impl(op_type, inputs, attrs):
    if op_type == 'lookup_table' and attrs.get('is_sparse'):
        out = _try_sparse_lookup(inputs, attrs)
        if out is not None:
            return out
    opdef = get_op(op_type)
    flat_tensors = []   # tensors participating in vjp
    arg_spec = []       # per-slot: ('single', idx) | ('list', [idx]) | ('const', v)
    for slot in opdef.input_slots:
        v = inputs.get(slot)
        if v is None:
            arg_spec.append(('const', None))
        elif isinstance(v, (list, tuple)):
            idxs = []
            for item in v:
                t = item if isinstance(item, Tensor) else Tensor(item, stop_gradient=True)
                idxs.append(len(flat_tensors))
                flat_tensors.append(t)
            arg_spec.append(('list', idxs))
        else:
            t = v if isinstance(v, Tensor) else Tensor(v, stop_gradient=True)
            arg_spec.append(('single', len(flat_tensors)))
            flat_tensors.append(t)

    if _tensor_watchers:
        for w in _tensor_watchers:
            w.extend(flat_tensors)

    attrs = dict(attrs)
    rng = None
    if opdef.needs_rng:
        rng = attrs.pop('key', None)
        if rng is None:
            rng = default_generator.next_key()

    def call_with(vals, key):
        kw = attrs if key is None else dict(attrs, key=key)
        args = []
        for kind, ref in arg_spec:
            if kind == 'const':
                args.append(ref)
            elif kind == 'single':
                args.append(vals[ref])
            else:
                args.append([vals[i] for i in ref])
        return opdef.fn(*args, **kw)

    def call(*vals):
        return call_with(vals, rng)

    vals = [t.value for t in flat_tensors]
    needs_grad = grad_enabled() and any(
        not t.stop_gradient and jnp.issubdtype(t.value.dtype, jnp.inexact)
        for t in flat_tensors)

    if kernel_cache.enabled:
        out = _cached_dispatch(op_type, opdef, arg_spec, attrs, call_with,
                               call, vals, rng, needs_grad, flat_tensors)
        if out is not _BLOCKED:
            return out

    if not needs_grad:
        result = call(*vals)
        return _wrap_outputs(opdef, result, node=None)

    result, vjp_fn = jax.vjp(call, *vals)
    flat_res = _flatten_result(opdef, result)
    node = Node(vjp_fn, flat_tensors, len(flat_res),
                [(r.shape, r.dtype) for r in flat_res], op_type,
                call_fn=call)
    return _wrap_outputs(opdef, result, node)


def _try_sparse_lookup(inputs, attrs):
    """Rows-only gradient path of ``lookup_table(is_sparse=True)``
    (docs/SPARSE.md): the eager forward is the plain dense gather; the
    tape node's hand-written vjp emits a padded-COO
    :class:`~paddle_tpu.ops.sparse_ops.SparseRowsGrad` — coalesced at a
    bucket-ladder rung — instead of letting jax.vjp scatter-add a dense
    V×D table gradient. Returns None (→ the generic dense dispatch) when
    the path does not apply: knob off, no-grad mode, frozen table,
    or under a to_static trace (the static path owns sparse there)."""
    from ..ops import sparse_ops
    w, ids = inputs.get('w'), inputs.get('ids')
    if not (isinstance(w, Tensor) and not w.stop_gradient
            and grad_enabled() and not _tensor_watchers
            and jnp.issubdtype(w.value.dtype, jnp.inexact)
            and sparse_ops.sparse_grad_enabled()):
        return None
    ids_val = ids.value if isinstance(ids, Tensor) else jnp.asarray(ids)
    if isinstance(w.value, jax.core.Tracer) \
            or isinstance(ids_val, jax.core.Tracer):
        return None
    opdef = get_op('lookup_table')
    padding_idx = attrs.get('padding_idx', -1)
    kernel_attrs = {k: v for k, v in attrs.items()
                    if k in ('padding_idx', 'is_sparse', 'is_distributed')}
    out_val = opdef.fn(w.value, ids_val, **kernel_attrs)
    vocab, dim = int(w.value.shape[0]), int(w.value.shape[1])
    flat_ids = sparse_ops.flatten_ids(ids_val)
    nnz = int(flat_ids.shape[0])
    bucket = sparse_ops.nnz_bucket(nnz)

    def vjp_fn(ct):
        ct = jnp.asarray(ct).reshape(nnz, dim)
        vals = ct
        if padding_idx is not None and padding_idx >= 0:
            # padded positions were zeroed independent of w: no gradient
            vals = jnp.where((flat_ids == padding_idx)[:, None], 0.0, vals)
        rows, coalesced = sparse_ops.coalesce_rows(flat_ids, vals, vocab,
                                                   bucket=bucket)
        dedup = None
        try:
            dedup = int(np.unique(np.asarray(flat_ids)).shape[0])
        except Exception:
            pass
        sparse_ops.record_sparse_lookup(nnz, bucket, dedup_rows=dedup,
                                        table=w.name)
        return (sparse_ops.SparseRowsGrad(rows, coalesced, vocab, dim),)

    node = Node(vjp_fn, [w], 1, [(out_val.shape, out_val.dtype)],
                'lookup_table',
                call_fn=lambda wv: opdef.fn(wv, ids_val, **kernel_attrs))
    return _wrap_outputs(opdef, out_val, node)


def _cached_dispatch(op_type, opdef, arg_spec, attrs, call_with, call, vals,
                     rng, needs_grad, flat_tensors):
    """Dispatch through the per-op jitted-kernel cache. Returns the wrapped
    outputs, or the _BLOCKED sentinel when this op must take the plain
    (re-traced) path: unhashable attrs, or a body jit cannot stage out."""
    try:
        spec_sig = tuple((kind, len(ref)) if kind == 'list' else (kind,)
                         for kind, ref in arg_spec)
        aval_sig = tuple(
            (v.shape, str(v.dtype), bool(getattr(v, 'weak_type', False)))
            for v in vals)
        key = (op_type, needs_grad, spec_sig, aval_sig, _attr_sig(attrs))
    except _Unhashable:
        kernel_cache.bypasses += 1
        return _BLOCKED

    entry = kernel_cache.get(key)
    if entry is _BLOCKED:
        return _BLOCKED
    if entry is None:
        # every eager kernel compiles through the persistent cross-process
        # XLA cache, same as Executor steps (lint_codebase.py invariant)
        from ..core.compile_cache import setup_persistent_cache
        setup_persistent_cache()
        if needs_grad:
            # fwd returns (primal outs, vjp residuals as a Partial pytree);
            # bwd re-applies that Partial under jit, so a repeated backward
            # through the same op signature is also a cache hit
            fwd = jax.jit(lambda vs, k: jax.vjp(
                lambda *v: call_with(v, k), *vs))
            bwd = jax.jit(lambda vf, ct: vf(ct))
        else:
            fwd = jax.jit(call_with)
            bwd = None
        entry = (fwd, bwd)

    try:
        if needs_grad:
            result, vjp_partial = entry[0](tuple(vals), rng)
        else:
            result = entry[0](tuple(vals), rng)
    except Exception:
        # e.g. value-dependent Python branching in the op body: fall back to
        # the eager path (a genuine user error re-raises there with an
        # untraced stack) and never retry this signature
        kernel_cache.block(key)
        return _BLOCKED

    if key not in kernel_cache._entries:
        kernel_cache.put(key, entry)

    if not needs_grad:
        return _wrap_outputs(opdef, result, node=None)

    bwd = entry[1]
    flat_res = _flatten_result(opdef, result)
    node = Node(lambda ct: bwd(vjp_partial, ct), flat_tensors, len(flat_res),
                [(r.shape, r.dtype) for r in flat_res], op_type,
                call_fn=call)
    return _wrap_outputs(opdef, result, node)


def _flatten_result(opdef, result):
    if len(opdef.output_slots) == 1:
        return list(result) if isinstance(result, (list, tuple)) else [result]
    flat = []
    for r in result:
        flat.extend(r if isinstance(r, (list, tuple)) else [r])
    return flat


def _wrap_outputs(opdef, result, node):
    def mk(val, idx):
        t = Tensor(val, stop_gradient=(node is None))
        t._node = node
        t._out_index = idx
        return t

    if len(opdef.output_slots) == 1:
        if isinstance(result, (list, tuple)):
            return [mk(v, i) for i, v in enumerate(result)]
        return mk(result, 0)
    outs = []
    idx = 0
    for r in result:
        if isinstance(r, (list, tuple)):
            outs.append([mk(v, idx + j) for j, v in enumerate(r)])
            idx += len(r)
        else:
            outs.append(mk(r, idx))
            idx += 1
    return tuple(outs)


def run_backward(loss: Tensor, retain_graph=False):
    """Reverse-topological tape walk (ref: imperative/engine.cc).
    With retain_graph=False (default, ref parity) the walked nodes' vjp
    residuals are released afterwards; a second backward() through them
    raises instead of silently re-accumulating."""
    if loss._node is None:
        raise RuntimeError("backward() on a tensor with no grad history")
    topo = []
    seen = set()

    def dfs(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for t in node.inputs:
            if t._node is not None:
                dfs(t._node)
        topo.append(node)

    dfs(loss._node)
    if any(n.vjp_fn is None for n in topo):
        raise RuntimeError(
            "trying to run backward() through a graph that has already been "
            "freed; pass retain_graph=True to the first backward() if you "
            "need to backward through it again")

    cotangents = {}  # id(node) → [array or None per output]

    def seed_ct(node, idx, val):
        lst = cotangents.setdefault(id(node), [None] * node.n_outputs)
        lst[idx] = val if lst[idx] is None else lst[idx] + val

    seed_ct(loss._node, loss._out_index,
            jnp.ones(loss.shape, to_jax_dtype(loss.dtype)))

    for node in reversed(topo):
        cts = cotangents.pop(id(node), None)
        if cts is None:
            continue
        full = []
        for i, (shape, dtype) in enumerate(node.out_avals):
            if cts[i] is not None:
                full.append(cts[i])
            else:
                full.append(jnp.zeros(shape, dtype))
        # rebuild the vjp cotangent structure (mirror of the primal output)
        ct_struct = _rebuild_ct(node, full)
        in_cts = node.vjp_fn(ct_struct)
        for t, g in zip(node.inputs, in_cts):
            if t.stop_gradient or not jnp.issubdtype(t.value.dtype, jnp.inexact):
                continue
            if type(g).__name__ == 'float0' or (hasattr(g, 'dtype') and
                                                g.dtype == jax.dtypes.float0):
                continue
            if t._node is not None:
                seed_ct(t._node, t._out_index, g)
            else:
                t.grad = g if t.grad is None else t.grad + g
        # leaf accumulation also for tensors that have nodes but are params?
        # params are leaves (no node), handled above.
    # intermediate tensors keep no .grad (matches ref default)
    if not retain_graph:
        for n in topo:
            n.vjp_fn = None          # release residual buffers (ref parity)


def _rebuild_ct(node, flat):
    """Reshape flat cotangent list back into the op's output structure."""
    if node.op_type == 'grad':
        # a grad(create_graph=True) node wraps jax.vjp(grad_fn, ...) where
        # grad_fn always returns a TUPLE of cotangents (even for a single
        # input), so its vjp demands a tuple — never a bare array
        return tuple(flat)
    try:
        opdef = get_op(node.op_type)
    except KeyError:
        return flat[0] if node.n_outputs == 1 else tuple(flat)
    if len(opdef.output_slots) == 1:
        if node.n_outputs == 1:
            return flat[0]
        return flat  # variadic single-slot (e.g. split) → list
    return tuple(flat)


def _node_flat_result(node, result):
    try:
        opdef = get_op(node.op_type)
    except KeyError:
        return list(result) if isinstance(result, (list, tuple)) else [result]
    return _flatten_result(opdef, result)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """Partial gradients d(outputs)/d(inputs) (ref: imperative/
    partial_grad_engine.cc via fluid.dygraph.grad).

    create_graph=True returns Tensors that carry grad history, enabling
    double-backward: the recorded subgraph between `inputs` and `outputs` is
    replayed as a pure jax function (each tape Node keeps its primal
    `call_fn`) and differentiated with jax.vjp — the grads' own node holds
    the vjp of THAT gradient function, so any order composes.

    `retain_graph` is accepted for API parity but has no effect: this engine
    replays primals instead of consuming vjp residuals, so grad() never
    frees the tape (a later backward()/grad() through the same graph always
    works)."""
    outputs = [outputs] if isinstance(outputs, Tensor) else list(outputs)
    inputs = [inputs] if isinstance(inputs, Tensor) else list(inputs)
    if not outputs or not inputs:
        raise ValueError("grad(): outputs and inputs must be non-empty")
    for o in outputs:
        if o._node is None:
            raise RuntimeError(f"grad(): output {o.name} has no grad history")

    # collect the ancestor subgraph, stopping at `inputs`
    input_pos = {id(t): i for i, t in enumerate(inputs)}
    topo, seen = [], set()

    def dfs(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for t in node.inputs:
            if id(t) not in input_pos and t._node is not None:
                dfs(t._node)
        topo.append(node)

    for o in outputs:
        dfs(o._node)
    for n in topo:
        if n.call_fn is None:
            raise RuntimeError(
                f"grad(): op '{n.op_type}' on the path has no replayable "
                f"primal (e.g. a to_static fused node); use backward() or "
                f"compute this gradient inside the traced function")

    node_order = {id(n): i for i, n in enumerate(topo)}

    # unused-input detection (ref: allow_unused in partial_grad_engine):
    # an input participates iff some node in the ancestor subgraph reads it
    used = set()
    for n in topo:
        for t in n.inputs:
            if id(t) in input_pos:
                used.add(id(t))
    used |= {id(o) for o in outputs if id(o) in input_pos}
    for i, t in enumerate(inputs):
        if id(t) not in used and not allow_unused:
            raise ValueError(
                f"grad(): input {i} ({t.name}) is not reachable from "
                f"outputs; set allow_unused=True to get None for it")

    nogv_ids = set()
    if no_grad_vars:
        ngv = [no_grad_vars] if isinstance(no_grad_vars, Tensor) \
            else list(no_grad_vars)
        nogv_ids = {id(t) for t in ngv}

    def replay(*in_vals):
        produced = {}

        def val(t):
            if id(t) in input_pos:
                v = in_vals[input_pos[id(t)]]
            elif t._node is not None and id(t._node) in node_order:
                v = produced[(id(t._node), t._out_index)]
            else:
                v = t.value
            if id(t) in nogv_ids:
                v = jax.lax.stop_gradient(v)
            return v

        for node in topo:
            res = node.call_fn(*[val(t) for t in node.inputs])
            for i, v in enumerate(_node_flat_result(node, res)):
                produced[(id(node), i)] = v
        return tuple(val(o) for o in outputs)

    in_vals = tuple(t.value for t in inputs)
    if grad_outputs is None:
        cts = tuple(jnp.ones(o.shape, to_jax_dtype(o.dtype)) for o in outputs)
    else:
        gos = [grad_outputs] if isinstance(grad_outputs, Tensor) \
            else list(grad_outputs)
        cts = tuple(g.value if isinstance(g, Tensor) else jnp.asarray(g)
                    for g in gos)

    def grad_fn(*iv):
        _, vjp_fn = jax.vjp(replay, *iv)
        return vjp_fn(cts)    # replay always returns a tuple

    if not create_graph:
        gvals = grad_fn(*in_vals)
        return [None if id(t) not in used and allow_unused
                else Tensor(g, stop_gradient=True)
                for t, g in zip(inputs, gvals)]

    gvals, vjp2 = jax.vjp(grad_fn, *in_vals)
    node = Node(vjp2, inputs, len(gvals),
                [(g.shape, g.dtype) for g in gvals], 'grad',
                call_fn=grad_fn)
    outs = []
    for i, g in enumerate(gvals):
        if id(inputs[i]) not in used and allow_unused:
            outs.append(None)
            continue
        t = Tensor(g)
        t._node = node
        t._out_index = i
        outs.append(t)
    return outs


def monkey_patch_tensor():
    T = Tensor

    def _coerce(other):
        return other if isinstance(other, Tensor) else Tensor(other, stop_gradient=True)

    def binop(op_type, reverse=False):
        def impl(self, other):
            other = _coerce(other)
            x, y = (other, self) if reverse else (self, other)
            return dispatch_op(op_type, {'x': x, 'y': y}, {})
        return impl

    T.__add__ = binop('elementwise_add')
    T.__radd__ = binop('elementwise_add', True)
    T.__sub__ = binop('elementwise_sub')
    T.__rsub__ = binop('elementwise_sub', True)
    T.__mul__ = binop('elementwise_mul')
    T.__rmul__ = binop('elementwise_mul', True)
    T.__truediv__ = binop('elementwise_div')
    T.__rtruediv__ = binop('elementwise_div', True)
    T.__pow__ = binop('elementwise_pow')
    T.__mod__ = binop('elementwise_mod')
    T.__floordiv__ = binop('elementwise_floordiv')
    T.__matmul__ = lambda self, other: dispatch_op(
        'matmul', {'x': self, 'y': _coerce(other)}, {})
    T.__neg__ = lambda self: dispatch_op('scale', {'x': self}, {'scale': -1.0})
    T.__eq__ = binop('equal')
    T.__ne__ = binop('not_equal')
    T.__lt__ = binop('less_than')
    T.__le__ = binop('less_equal')
    T.__gt__ = binop('greater_than')
    T.__ge__ = binop('greater_equal')
    T.__hash__ = lambda self: id(self)

    def _getitem(self, idx):
        if _tensor_watchers:
            for w in _tensor_watchers:
                w.append(self)
        if isinstance(idx, Tensor):
            idx = idx.value
        if (self.stop_gradient or not grad_enabled()
                or not jnp.issubdtype(self.value.dtype, jnp.inexact)):
            return Tensor(self.value[idx], stop_gradient=True)
        getter = lambda v: v[idx]  # noqa: E731
        out, vjp_fn = jax.vjp(getter, self.value)
        node = Node(vjp_fn, [self], 1, [(out.shape, out.dtype)],
                    '__getitem__', call_fn=getter)
        t = Tensor(out)
        t._node = node
        return t

    T.__getitem__ = _getitem


monkey_patch_tensor()
