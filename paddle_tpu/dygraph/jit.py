"""dygraph.jit: to_static / TracedLayer / fused training steps.

Parity with reference python/paddle/fluid/dygraph/jit.py +
dygraph_to_static/: where the reference translates Python AST to a static
Program, the TPU design traces the SAME eager code with jax tracers (the tape
runs the identical registered functionals), producing one fused XLA
computation. `TrainStep` additionally folds grad + optimizer update into that
single program — the production training path used by the benchmarks.
"""
from __future__ import annotations

import contextlib
import functools
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..resilience import watchdog as _watchdog
from .tape import Tensor, Parameter, no_grad_guard
from .layers import Layer


@contextlib.contextmanager
def _bind(tensors: dict, values: dict):
    """Temporarily swap Tensor.value for traced values; restore after."""
    saved = {n: t.value for n, t in tensors.items()}
    try:
        for n, t in tensors.items():
            if n in values:
                t.value = values[n]
        yield
    finally:
        for n, t in tensors.items():
            t.value = saved[n]


def _tensorize(args):
    return [a if isinstance(a, Tensor) else Tensor(a, stop_gradient=True)
            for a in args]


def _devalue(out):
    if isinstance(out, Tensor):
        return out.value
    if isinstance(out, (list, tuple)):
        return type(out)(_devalue(o) for o in out)
    return out


def functionalize(layer: Layer):
    """layer → (apply_fn, params, buffers) where
    apply_fn(params, buffers, *arg_arrays) -> (outputs, new_buffers) is pure."""
    params = dict(layer.named_parameters())
    buffers = dict(layer.named_buffers())

    def apply_fn(param_vals, buffer_vals, *args):
        with _bind(params, param_vals), _bind(buffers, buffer_vals):
            with no_grad_guard():
                out = layer(*_tensorize(args))
            new_buffers = {n: b.value for n, b in buffers.items()}
        return _devalue(out), new_buffers

    return apply_fn, {n: p.value for n, p in params.items()}, \
        {n: b.value for n, b in buffers.items()}


class TracedLayer:
    """ref: dygraph/jit.py:TracedLayer — here a jitted functional closure."""

    def __init__(self, layer, apply_fn, params, buffers):
        self._layer = layer
        self._apply = jax.jit(apply_fn)
        self._params = params
        self._buffers = buffers

    @staticmethod
    def trace(layer, inputs):
        apply_fn, params, buffers = functionalize(layer)
        traced = TracedLayer(layer, apply_fn, params, buffers)
        out = traced(*inputs)
        return out, traced

    def __call__(self, *args):
        vals = [a.value if isinstance(a, Tensor) else jnp.asarray(a)
                for a in args]
        out, _ = self._apply(self._params, self._buffers, *vals)
        if isinstance(out, (list, tuple)):
            return type(out)(Tensor(o, stop_gradient=True) for o in out)
        return Tensor(out, stop_gradient=True)

    def save_inference_model(self, dirname, feed=None, fetch=None):
        from ..io import _save_jit_model
        _save_jit_model(dirname, self._layer, self._params, self._buffers)


class InputSpec:
    """Declared input signature for `to_static` (paddle.static.InputSpec
    parity). `shape` entries of None mean "any size" — the concrete size is
    taken from the first call (each distinct size compiles once)."""

    def __init__(self, shape, dtype='float32', name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name


class ProgramTranslator:
    """ref: dygraph_to_static/program_translator.py:ProgramTranslator —
    process-wide switch; `enable(False)` makes every StaticFunction fall back
    to plain eager execution (the reference's escape hatch)."""

    _instance = None
    enabled = True

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def enable(self, flag: bool):
        ProgramTranslator.enabled = bool(flag)

    _fn_cache = {}

    def get_output(self, fn, *args, **kwargs):
        if isinstance(fn, StaticFunction):
            sf = fn
        else:
            sf = ProgramTranslator._fn_cache.get(fn)
            if sf is None:
                sf = ProgramTranslator._fn_cache.setdefault(
                    fn, StaticFunction(fn))
        return sf(*args, **kwargs)


def _find_layers(fn, instance, args, kwargs):
    """Layers whose parameters the traced program must treat as inputs: the
    bound instance, Layer positional/kw args, Layers captured in the
    function's closure cells, and Layers reachable from the function's module
    globals (one container level deep). The reference discovers these via AST
    rewrite + the program cache; here object inspection suffices."""
    layers = []
    seen = set()

    def add(obj, depth=0):
        if isinstance(obj, Layer):
            if id(obj) not in seen:
                seen.add(id(obj))
                layers.append(obj)
        elif depth < 1:
            if isinstance(obj, (list, tuple)):
                for v in obj:
                    add(v, depth + 1)
            elif isinstance(obj, dict):
                for v in obj.values():
                    add(v, depth + 1)

    add(instance)
    for a in args:
        add(a)
    for a in kwargs.values():
        add(a)
    raw = getattr(fn, '__wrapped__', fn)
    for cell in (getattr(raw, '__closure__', None) or ()):
        try:
            add(cell.cell_contents)
        except ValueError:
            pass
    for v in getattr(raw, '__globals__', {}).values():
        add(v)
    return layers


def _is_array_like(x):
    return isinstance(x, (Tensor, np.ndarray, jnp.ndarray)) or (
        hasattr(x, 'shape') and hasattr(x, 'dtype'))


class StaticFunction:
    """Real dygraph→static translation (ref: dygraph_to_static/
    program_translator.py:StaticFunction). Instead of AST-rewriting Python to
    a fluid Program, the eager function is traced with jax tracers — the tape
    dispatches the same registered functionals either way — producing ONE
    fused XLA program per input signature, cached by (shapes, dtypes, static
    args, grad mode). Gradients flow: the whole compiled forward becomes a
    single tape node whose vjp is itself a cached jitted XLA program."""

    def __init__(self, fn, input_spec=None):
        functools.update_wrapper(self, fn)
        self._fn = fn
        self._input_spec = input_spec
        self._instance = None
        self._cache = {}
        # per-instance caches keyed by the instance object itself via
        # weakref: id() reuse after GC can't resurrect a stale entry whose
        # closure captures a dead instance's parameters, and entries die
        # with their instance instead of leaking
        self._instance_caches = weakref.WeakKeyDictionary()
        # shared mutable cell: bound copies made by __get__ must increment
        # the same counter the descriptor exposes
        self._stats = {'compiles': 0}
        self._is_declarative = True

    @property
    def _compile_count(self):
        return self._stats['compiles']

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = StaticFunction.__new__(StaticFunction)
        bound.__dict__ = dict(self.__dict__)
        bound._instance = instance
        return bound

    # -- signature handling --------------------------------------------------
    def _split_args(self, args, kwargs):
        """→ (arr_vals, rebuild, sig). Array-likes become traced inputs;
        everything else (python scalars, strings, None, Layers) is static and
        partakes in the cache key."""
        spec = self._input_spec
        arr_vals, slots = [], []
        sig = []

        def classify(x, spec_i=None):
            if _is_array_like(x):
                v = x.value if isinstance(x, Tensor) else jnp.asarray(x)
                if spec_i is not None and spec_i.dtype is not None:
                    from ..core.dtypes import (to_jax_dtype,
                                               check_int32_bounds)
                    if str(spec_i.dtype) == 'int64' and \
                            not hasattr(v, 'aval'):
                        check_int32_bounds(np.asarray(v), 'InputSpec')
                    v = v.astype(to_jax_dtype(spec_i.dtype))
                arr_vals.append(v)
                slots.append(None)
                sig.append(('arr', v.shape, str(v.dtype)))
            else:
                slots.append(x)
                sig.append(('static', repr(x)))

        for i, a in enumerate(args):
            s = spec[i] if spec is not None and i < len(spec) else None
            classify(a, s)
        kw_keys = sorted(kwargs)
        for k in kw_keys:
            sig.append(('kw', k))
            classify(kwargs[k])

        def rebuild(traced_vals):
            it = iter(traced_vals)
            vals = [next(it) if s is None else s for s in slots]
            pos = vals[:len(args)]
            kw = dict(zip(kw_keys, vals[len(args):]))
            return pos, kw

        return arr_vals, rebuild, tuple(sig)

    def _compile(self, layers, arr_vals, rebuild, grad_flag, args_need_grad):
        from ..core.random import default_generator
        from .tape import watch_tensors
        all_params, all_buffers = {}, {}
        for li, layer in enumerate(layers):
            for n, p in layer.named_parameters():
                all_params[f'{li}.{n}'] = p
            for n, b in layer.named_buffers():
                all_buffers[f'{li}.{n}'] = b
        fn = self._fn
        # hold the instance only weakly: cache entries live in a
        # WeakKeyDictionary keyed by the instance, so a strong capture here
        # would pin the key and the entry could never be collected
        inst_ref = (weakref.ref(self._instance)
                    if self._instance is not None else None)

        def make_run(params, buffers, pnames, bnames):
            def run(pvals, bvals, key, arr):
                pts = {n: params[n] for n in pnames}
                bts = {n: buffers[n] for n in bnames}
                with _bind(pts, dict(zip(pnames, pvals))), \
                        _bind(bts, dict(zip(bnames, bvals))), \
                        default_generator.bind_base(key), no_grad_guard():
                    pos, kw = rebuild(_tensorize_keep(arr))
                    if inst_ref is not None:
                        out = fn(inst_ref(), *pos, **kw)
                    else:
                        out = fn(*pos, **kw)
                    new_b = [buffers[n].value for n in bnames]
                flat, treedef = jax.tree_util.tree_flatten(_devalue(out))
                return flat, treedef, new_b
            return run

        # Discovery pass (abstract, no FLOPs): bind every candidate
        # param/buffer to protect it from tracer leaks, watch which tensors
        # the function actually reads, and capture the output structure.
        touched = []
        k0 = default_generator.base_key()
        run_all = make_run(all_params, all_buffers,
                           list(all_params), list(all_buffers))
        with watch_tensors(touched):
            jax.eval_shape(lambda p, b, k, a: run_all(p, b, k, a)[0],
                           [p.value for p in all_params.values()],
                           [b.value for b in all_buffers.values()],
                           k0, tuple(arr_vals))
        touched_ids = {id(t) for t in touched}
        params = {n: p for n, p in all_params.items() if id(p) in touched_ids}
        # keep every buffer of any layer the trace actually used (buffer
        # writes don't flow through dispatch, so reads alone can't prove
        # a buffer is untouched)
        used_layers = set()
        for li, layer in enumerate(layers):
            names = [n for n in list(all_params) + list(all_buffers)
                     if n.startswith(f'{li}.')]
            tensors = [all_params.get(n) or all_buffers.get(n) for n in names]
            if any(id(t) in touched_ids for t in tensors):
                used_layers.add(li)
        buffers = {n: b for n, b in all_buffers.items()
                   if int(n.split('.', 1)[0]) in used_layers}
        pnames = list(params)
        bnames = list(buffers)

        treedef_box = []
        run = make_run(params, buffers, pnames, bnames)

        def run_flat(pvals, bvals, key, arr):
            flat, treedef, new_b = run(pvals, bvals, key, arr)
            if not treedef_box:
                treedef_box.append(treedef)
            return tuple(flat), new_b

        shapes = jax.eval_shape(run_flat,
                                [params[n].value for n in pnames],
                                [buffers[n].value for n in bnames],
                                k0, tuple(arr_vals))
        n_out = len(shapes[0])
        treedef = treedef_box.pop()

        needs_grad = grad_flag and (
            args_need_grad or
            any(getattr(p, 'trainable', False) for p in params.values()))
        if not needs_grad:
            infer = jax.jit(run_flat)
            return ('infer', infer, pnames, bnames, treedef, n_out,
                    params, buffers)

        def fwd_fn(pvals, bvals, key, arr):
            def g(pv, a):
                flat, new_b = run_flat(pv, bvals, key, a)
                out = flat[0] if n_out == 1 else tuple(flat)
                return out, new_b
            out, vjp_fn, new_b = jax.vjp(g, pvals, tuple(arr), has_aux=True)
            flat = [out] if n_out == 1 else list(out)
            return flat, new_b, vjp_fn

        fwd = jax.jit(fwd_fn)
        bwd = jax.jit(lambda vf, ct: vf(ct))
        return ('grad', (fwd, bwd), pnames, bnames, treedef, n_out,
                params, buffers)

    # -- call ----------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if not ProgramTranslator.enabled:
            if self._instance is not None:
                return self._fn(self._instance, *args, **kwargs)
            return self._fn(*args, **kwargs)
        from ..core.random import default_generator
        arr_vals, rebuild, sig = self._split_args(args, kwargs)

        ordered_args = list(args) + [kwargs[k] for k in sorted(kwargs)]
        grad_flag = _grad_enabled()
        arg_req = tuple(isinstance(a, Tensor) and not a.stop_gradient
                        for a in ordered_args)
        # The entry stores the param/buffer Tensor objects it bound at
        # compile time, so cache hits skip layer discovery entirely.
        # (Rebinding a module global to a NEW Layer instance mid-training is
        # not retraced — same staleness semantics as the reference's program
        # cache, which also keys on function identity + input spec.)
        if self._instance is None:
            cache = self._cache
        else:
            cache = self._instance_caches.get(self._instance)
            if cache is None:
                cache = self._instance_caches.setdefault(self._instance, {})
        key = (sig, grad_flag, arg_req)
        entry = cache.get(key)
        if entry is None:
            layers = _find_layers(self._fn, self._instance, args, kwargs)
            entry = self._compile(layers, arr_vals, rebuild, grad_flag,
                                  any(arg_req))
            cache[key] = entry
            self._stats['compiles'] += 1  # one trace+compile per signature
        mode, compiled, pnames, bnames, treedef, n_out, params, buffers = entry
        pvals = [params[n].value for n in pnames]
        bvals = [buffers[n].value for n in bnames]
        rng = default_generator.next_key()

        if mode == 'infer':
            flat, new_b = compiled(pvals, bvals, rng, tuple(arr_vals))
            for n, v in zip(bnames, new_b):
                buffers[n].value = v
            outs = [Tensor(v, stop_gradient=True) for v in flat]
            return jax.tree_util.tree_unflatten(treedef, outs)

        fwd, bwd = compiled
        flat, new_b, vjp_fn = fwd(pvals, bvals, rng, tuple(arr_vals))
        for n, v in zip(bnames, new_b):
            buffers[n].value = v

        param_tensors = [params[n] for n in pnames]

        from .tape import Node

        def node_vjp(ct):
            p_cts, a_cts = bwd(vjp_fn, ct)
            by_val = list(p_cts) + list(a_cts)
            # map cotangents back to node.inputs order (params then arr args)
            return by_val

        # Tensors corresponding to traced arr inputs, in arr order
        arr_tensors = [a if isinstance(a, Tensor)
                       else Tensor(a, stop_gradient=True)
                       for a in ordered_args if _is_array_like(a)]
        node_inputs = param_tensors + arr_tensors
        node = Node(node_vjp, node_inputs, n_out,
                    [(v.shape, v.dtype) for v in flat], 'to_static')
        outs = []
        for i, v in enumerate(flat):
            t = Tensor(v)
            t._node = node
            t._out_index = i
            outs.append(t)
        return jax.tree_util.tree_unflatten(treedef, outs)


def _tensorize_keep(vals):
    return [Tensor(v, stop_gradient=True) for v in vals]


def _grad_enabled():
    from . import tape
    return tape.grad_enabled()


def declarative(fn=None, input_spec=None):
    """@declarative / @to_static: trace the eager function into a cached
    jitted XLA program (see StaticFunction)."""
    if fn is None:
        return lambda f: StaticFunction(f, input_spec=input_spec)
    return StaticFunction(fn, input_spec=input_spec)


to_static = declarative


class TrainStep:
    """Fully-fused training step: forward + vjp + optimizer update in ONE
    jitted XLA program with donated state (the TPU analogue of the reference
    ParallelExecutor fast path). Use:

        step = TrainStep(model, loss_fn, optimizer)
        loss = step(x_batch, y_batch)          # numpy/jax arrays in

    With `async_fetch=True` the call returns a
    :class:`~paddle_tpu.core.fetch_handle.FetchHandle` instead of the raw
    loss array and keeps up to `num_inflight_steps` (default 2) dispatched
    steps outstanding — `float(handle)` / `np.asarray(handle)` is the sync
    point, so logging the loss every k steps stops serializing the loop.
    `PADDLE_TPU_ASYNC=0` forces the synchronous behavior regardless.

    async_fetch composes with donation asymmetrically: `donate=True` (the
    default) updates params in place, which makes dispatch N+1 wait for
    step N to finish producing the donated buffers — host-side batch prep
    still overlaps the running step, but the dispatch window is
    effectively 1 deep. Pass `donate=False` for a true K-deep window at
    the cost of the double-buffer transient (2× param HBM).
    """

    def __init__(self, layer: Layer, loss_fn, optimizer, data_sharding=None,
                 remat=False, donate=True, amp_dtype=None, accum_steps=1,
                 async_fetch=False, num_inflight_steps=None, supervisor=None):
        from ..core.compile_cache import setup_persistent_cache
        setup_persistent_cache()   # second process reuses the compiled step
        self._layer = layer
        self._params = dict(layer.named_parameters())
        self._buffers = dict(layer.named_buffers())
        self._opt = optimizer
        self._loss_fn = loss_fn
        self._remat = remat
        self._data_sharding = data_sharding
        # donate=True (default): params/buffers/optimizer slots are donated
        # into the jitted step (jax donate_argnums) so XLA writes the update
        # in place — live HBM stays 1× params instead of 2×. The pre-step
        # buffers are invalidated (deleted-buffer semantics, asserted by the
        # donation-safety tests); donate=False keeps them valid.
        self._donate = bool(donate)
        # amp_dtype (e.g. jnp.bfloat16): params stay fp32 master weights;
        # the forward sees a low-precision cast, grads/updates are fp32 —
        # param dtypes are stable across steps so the step compiles once.
        self._amp_dtype = amp_dtype
        # accum_steps > 1: gradient merge (ref GradientMergeOptimizer,
        # optimizer.py:3870 semantics) — grads accumulate across k calls,
        # the optimizer applies once on the k-step mean, inside the same
        # jitted program via lax.cond so the step still compiles once.
        self._accum_steps = int(accum_steps)
        self._acc = None
        self._jitted = None
        self._slots = None
        self._step = 0
        # async_fetch: non-blocking loss handles + a bounded K-in-flight
        # dispatch window (executor-style pipelining for the fused step;
        # the loss output buffer is never donated, so a pending handle is
        # inherently snapshot-safe here). PADDLE_TPU_ASYNC=0 pins sync; a
        # numeric PADDLE_TPU_ASYNC sets the default window depth.
        from ..core.fetch_handle import (InflightWindow,
                                         resolve_inflight_steps)
        if async_fetch:
            self._async_k = resolve_inflight_steps(
                default=int(num_inflight_steps) if num_inflight_steps else 2)
        else:
            self._async_k = 0
        self._window = InflightWindow() if self._async_k else None
        # supervisor (resilience/supervisor.py): every call's loss is judged
        # at this boundary — a skip verdict restores the pre-step snapshot
        # via set_state, a rollback verdict surfaces on supervisor
        # .last_verdict; escalations raise TrainingDiverged out of the call.
        self._supervisor = supervisor
        if supervisor is not None and supervisor._train_step is None:
            supervisor._train_step = self

    def _build(self):
        layer = self._layer
        params = self._params
        buffers = self._buffers
        loss_fn = self._loss_fn
        opt = self._opt
        slot_names = opt._slot_names
        hypers = opt._hypers()
        has_lr = opt._has_lr_input
        from ..ops.registry import get_op
        update_fn = get_op(opt._op_type).fn
        clip = opt._grad_clip
        base_reg = opt.regularization
        regs = {n: (getattr(p, 'regularizer', None) or base_reg)
                for n, p in params.items()}
        # a dict, not a set: its order (the layer's construction order) is
        # the order the update ops are traced in, and a set of strings
        # iterates differently in every process — each cold process then
        # lowered a different HLO and the persistent compile cache never hit
        # for the step (seen on the chip: ResNet-50 recompiled for 50 s with
        # its own executable already on disk)
        trainable = {n: None for n, p in params.items() if p.trainable}

        amp_dtype = self._amp_dtype

        def forward(pvals, bvals, batch):
            if amp_dtype is not None:
                # params cast to the compute dtype; fp32 FEEDS meet the
                # low-precision weights at conv/matmul, which harmonize the
                # activation onto the weight dtype (ops/nn_ops.py) — labels
                # and loss targets are never touched
                pvals = {n: (v.astype(amp_dtype)
                             if jnp.issubdtype(v.dtype, jnp.floating) else v)
                         for n, v in pvals.items()}
            with _bind(params, pvals), _bind(buffers, bvals):
                with no_grad_guard():
                    loss = loss_fn(layer, *_tensorize(batch))
                new_b = {n: b.value for n, b in buffers.items()}
            lv = loss.value if isinstance(loss, Tensor) else loss
            return jnp.sum(lv), new_b

        if self._remat:
            forward = jax.checkpoint(forward, static_argnums=())

        def apply_update(train_p, grads, slots, lr):
            for n in grads:
                if regs[n] is not None:
                    grads[n] = regs[n].apply(train_p[n], grads[n])
            if clip is not None:
                grads = clip.apply_tree(grads)
            new_tp = {}
            new_slots = {}
            for n in trainable:
                args = [train_p[n], grads[n]] + \
                    [slots[n][s] for s in slot_names]
                if has_lr:
                    args.append(lr)
                res = update_fn(*args, **hypers)
                res = res if isinstance(res, tuple) else (res,)
                # pin param/slot dtypes across steps: bf16 params meeting
                # fp32 hypers/slots would otherwise promote the update to
                # fp32, which breaks donated-buffer reuse (shape/dtype must
                # match the donated input) and, under accum_steps>1, the
                # lax.cond branch signatures
                new_tp[n] = res[0].astype(train_p[n].dtype)
                new_slots[n] = {
                    s: r.astype(slots[n][s].dtype)
                    for s, r in zip(slot_names, res[1:])}
            return new_tp, new_slots

        accum_steps = self._accum_steps
        if accum_steps <= 1:
            def step(pvals, bvals, slots, lr, batch):
                train_p = {n: pvals[n] for n in trainable}
                frozen_p = {n: v for n, v in pvals.items()
                            if n not in trainable}

                def f(tp):
                    return forward({**frozen_p, **tp}, bvals, batch)

                (loss, new_b), grads = \
                    jax.value_and_grad(f, has_aux=True)(train_p)
                new_tp, new_slots = apply_update(train_p, grads, slots, lr)
                return {**frozen_p, **new_tp}, new_b, new_slots, loss

            return jax.jit(step, donate_argnums=(0, 1, 2)
                           if self._donate else ())

        def step(pvals, bvals, slots, acc, count, lr, batch):
            # gradient merge: accumulate, and on every k-th call apply the
            # optimizer on the k-step MEAN (regularizer/clip act on the
            # merged grad, matching ref GradientMergeOptimizer which scales
            # by 1/k before the inner optimizer runs)
            train_p = {n: pvals[n] for n in trainable}
            frozen_p = {n: v for n, v in pvals.items() if n not in trainable}

            def f(tp):
                return forward({**frozen_p, **tp}, bvals, batch)

            (loss, new_b), grads = jax.value_and_grad(f, has_aux=True)(train_p)
            acc = jax.tree_util.tree_map(lambda a, g: a + g, acc, grads)
            do_apply = (count + 1) % accum_steps == 0

            def on_apply(operand):
                acc, slots = operand
                mean_g = {n: a / accum_steps for n, a in acc.items()}
                new_tp, new_slots = apply_update(dict(train_p), mean_g,
                                                 slots, lr)
                zero = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return new_tp, new_slots, zero

            def on_skip(operand):
                acc, slots = operand
                return dict(train_p), slots, acc

            new_tp, new_slots, new_acc = jax.lax.cond(
                do_apply, on_apply, on_skip, (acc, slots))
            return ({**frozen_p, **new_tp}, new_b, new_slots, new_acc,
                    count + 1, loss)

        return jax.jit(step, donate_argnums=(0, 1, 2, 3)
                       if self._donate else ())

    def state(self):
        return ({n: p.value for n, p in self._params.items()},
                {n: b.value for n, b in self._buffers.items()})

    def _replicate_state(self):
        """GSPMD path, before the first dispatch: commit every parameter,
        buffer, optimizer slot and gradient-merge accumulator that still
        sits where its initialiser left it (one device) to the batch's mesh,
        replicated. Without this the
        first step is compiled for single-device state and hands back state
        replicated over the mesh, so the second step compiles the whole
        program a second time for the changed input sharding (seen on the
        four-chip host, chip_smoke.py train_dp4). State the caller already
        laid out over the mesh (tensor-parallel shardings) is left alone."""
        mesh = getattr(self._data_sharding, 'mesh', None)
        if mesh is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec
        replicated = NamedSharding(mesh, PartitionSpec())
        devices = set(mesh.devices.flat)

        def place(v):
            if set(v.devices()) == devices:
                return v
            return jax.device_put(v, replicated)

        for holder in (*self._params.values(), *self._buffers.values()):
            holder.value = place(holder.value)
        self._slots = jax.tree_util.tree_map(place, self._slots)
        if self._acc is not None:
            self._acc = jax.tree_util.tree_map(place, self._acc)
            self._count = place(self._count)

    # -- checkpoint/resume (paddle_tpu/resilience/) --------------------
    def snapshot(self):
        """Non-blocking point-in-time capture for async checkpointing:
        → ({flat_key: FetchHandle}, meta). With donation on, the fused
        step donates its WHOLE state pytree every call — per-name
        protection is impossible — so each array is first cloned on-device
        (async dispatch, no host sync) and the handle wraps the clone; the
        checkpoint writer materializes D2H in the background while
        subsequent steps donate the originals freely."""
        from ..core.fetch_handle import FetchHandle

        def wrap(key, v):
            if self._donate and hasattr(v, 'block_until_ready'):
                v = jnp.copy(v)
            return FetchHandle(v, name=key)

        arrays = {}
        for n, p in self._params.items():
            arrays[f'param/{n}'] = wrap(f'param/{n}', p.value)
        for n, b in self._buffers.items():
            arrays[f'buffer/{n}'] = wrap(f'buffer/{n}', b.value)
        for n, slots in (self._slots or {}).items():
            for s, v in slots.items():
                arrays[f'slot/{s}/{n}'] = wrap(f'slot/{s}/{n}', v)
        if self._acc is not None:
            for n, v in self._acc.items():
                arrays[f'acc/{n}'] = wrap(f'acc/{n}', v)
            arrays['accum_count'] = wrap('accum_count', self._count)
        meta = {'step': self._step, 'accum_steps': self._accum_steps}
        lr = self._opt._learning_rate
        if hasattr(lr, 'step_num'):
            meta['lr_step_num'] = int(lr.step_num)
        return arrays, meta

    def set_state(self, arrays, meta=None):
        """Restore a :meth:`snapshot`. Call before or after the first step
        — restored optimizer slots/accumulators survive the lazy build.
        Unrecognized keys (e.g. ``scope/``-prefixed executor state in a
        combined capture) are ignored."""
        meta = dict(meta or {})
        slots = {}
        acc = {}
        for key, arr in arrays.items():
            if key.startswith('param/'):
                n = key[len('param/'):]
                if n in self._params:
                    self._params[n].value = jnp.asarray(arr)
            elif key.startswith('buffer/'):
                n = key[len('buffer/'):]
                if n in self._buffers:
                    self._buffers[n].value = jnp.asarray(arr)
            elif key.startswith('slot/'):
                _, s, n = key.split('/', 2)
                slots.setdefault(n, {})[s] = jnp.asarray(arr)
            elif key.startswith('acc/'):
                acc[key[len('acc/'):]] = jnp.asarray(arr)
            elif key == 'accum_count':
                self._count = jnp.asarray(arr, jnp.int32)
        if slots:
            self._slots = slots
        if acc:
            self._acc = acc
        if 'step' in meta:
            self._step = int(meta['step'])
        lr = self._opt._learning_rate
        if 'lr_step_num' in meta and hasattr(lr, 'step_num'):
            lr.step_num = meta['lr_step_num']

    def __call__(self, *batch):
        # hang watchdog lease over the fused dispatch (free when no process
        # watchdog is armed; see resilience/watchdog.py)
        lease = _watchdog.arm_step('train_step')
        try:
            if not _obs._ENABLED:
                loss = self._call_impl(batch)
            else:
                # span tree per fused step: build (first call only) +
                # execute nest under train_step/call; one steps.jsonl
                # record per call
                with _obs.span('train_step/call', step=self._step + 1):
                    loss = self._call_impl(batch)
                _obs.inc('train_step_calls',
                         help='fused TrainStep invocations')
                _obs.log_step(kind='train_step', step=self._step,
                              accum_steps=self._accum_steps,
                              donate=self._donate)
        finally:
            _watchdog.disarm(lease)
        if self._supervisor is not None:
            self._supervisor.end_of_step(self._step, loss)
        return loss

    def _call_impl(self, batch):
        first_call = self._jitted is None
        if first_call:
            with _obs.span('train_step/build'):
                self._jitted = self._build()
        if self._slots is None:
            # skipped when set_state() restored checkpointed slots before
            # the first call — a resumed step must continue the restored
            # optimizer trajectory, not a fresh one
            self._slots = {
                n: {s: jnp.full(shp, fill, jnp.float32)
                    for s, (shp, fill) in
                    self._opt._slot_init(list(p.shape), p.dtype).items()}
                for n, p in self._params.items() if p.trainable}
        if self._accum_steps > 1 and self._acc is None:
            # accumulators carry the GRADIENT dtype (== param dtype; fp32
            # masters under amp): a hardcoded fp32 accumulator would promote
            # `acc + grad` for bf16 params and the two lax.cond branches
            # would disagree on dtypes (ADVICE r5)
            self._acc = {n: jnp.zeros_like(p.value)
                         for n, p in self._params.items() if p.trainable}
            self._count = jnp.int32(0)
        batch_vals = []
        for b in batch:
            arr = b.value if isinstance(b, Tensor) else jnp.asarray(b)
            if self._data_sharding is not None:
                arr = jax.device_put(arr, self._data_sharding)
            batch_vals.append(arr)
        if first_call and self._data_sharding is not None:
            self._replicate_state()
        pvals, bvals = self.state()
        if self._window is not None:
            # K-in-flight window: block on the oldest pending loss handle
            # only when the window is full, so this dispatch overlaps the
            # device still executing earlier steps
            self._window.admit(self._async_k)
        with _obs.span('train_step/execute'):
            if self._accum_steps > 1:
                new_p, new_b, self._slots, self._acc, self._count, loss = \
                    self._jitted(pvals, bvals, self._slots, self._acc,
                                 self._count,
                                 jnp.float32(self._opt._current_lr()),
                                 tuple(batch_vals))
            else:
                new_p, new_b, self._slots, loss = self._jitted(
                    pvals, bvals, self._slots,
                    jnp.float32(self._opt._current_lr()),
                    tuple(batch_vals))
        for n, p in self._params.items():
            p.value = new_p[n]
        for n, b in self._buffers.items():
            b.value = new_b[n]
        self._step += 1
        if hasattr(self._opt._learning_rate, 'step'):
            self._opt._learning_rate.step()
        if self._window is not None:
            from ..core.fetch_handle import FetchHandle
            from ..debugging import check_nan_inf_enabled
            handle = FetchHandle(loss, name='loss',
                                 check_nan=check_nan_inf_enabled())
            self._window.push([handle])
            return handle
        return loss
