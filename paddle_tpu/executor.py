"""Executor: lowers a Program to ONE pure jax function and runs it jitted.

Parity with reference python/paddle/fluid/executor.py + the C++ executor
(/root/reference/paddle/fluid/framework/executor.cc). The TPU redesign (see
BASELINE.json north star): instead of per-op kernel dispatch, the whole
Program becomes `step(donated_state, kept_state, feeds, key) ->
(new_state, fetches)`, compiled through an XLA compile cache keyed by
(program version, feed shapes) and backed by the persistent cross-process
compilation cache (core/compile_cache.py). Parameter/optimizer-state buffers
are DONATED into the step (XLA updates them in place — no transient 2×
parameter HBM) unless fetch-aliased, buffer-shared, or opted out
(PADDLE_TPU_DONATE=0 / BuildStrategy.enable_inplace=False). Backward
markers lower to jax.value_and_grad; optimizer ops run inside the same fused
step; persistable writes return functionally and are stored back to the Scope.
"""
from __future__ import annotations

import os
from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from . import observability as _obs
from .core.compile_cache import record_program_cache
from .core.dtypes import to_jax_dtype
from .core.fetch_handle import (FetchHandle, InflightWindow,
                                resolve_inflight_steps)
from .core.places import _get_paddle_place
from .core.scope import global_scope
from .core.random import default_generator
from .framework import (BACKWARD_OP_TYPE, Program, Variable,
                        default_main_program)
from .ops.registry import NON_KERNEL_ATTRS, get_op
from .resilience import watchdog as _watchdog


def _fleet_spmd_mesh():
    """The partitioner's mesh when this is a REAL multi-host run whose
    mesh spans every process — the condition under which the executor
    must lower against GLOBAL arrays (feeds assembled from per-host
    shards, state placed once fleet-wide) so XLA derives the cross-host
    collectives. None single-process (the normal path, zero change)."""
    if jax.process_count() <= 1:
        return None
    from .partition import get_partitioner
    mesh = get_partitioner().mesh
    if mesh is None or mesh.devices.size != jax.device_count():
        return None
    return mesh


def _globalize_state(value, mesh, sharding):
    """Host-local state value (every host holds the identical/full value,
    by seed determinism or by restore) → global jax.Array under
    `sharding`. Already-global arrays — anything whose sharding spans
    the whole mesh, e.g. every warm step's own outputs (which come back
    as GSPMD shardings, not NamedShardings — attribute equality would
    re-place 1× state bytes per step) — pass through untouched."""
    sh = getattr(value, 'sharding', None)
    if sh is not None and len(sh.device_set) == mesh.devices.size:
        return value
    host_val = np.asarray(value)
    return jax.make_array_from_callback(
        host_val.shape, sharding, lambda idx: host_val[idx])


def _globalize_feed(value, mesh, spec):
    """Per-host feed rows → ONE global batch array sharded per `spec`
    (each host contributed its own process_index-strided slice — the
    DataLoader's fleet sharding). Feeds with no batch spec must be
    identical on every host and replicate."""
    from jax.experimental import multihost_utils
    return multihost_utils.host_local_array_to_global_array(
        np.asarray(value), mesh, spec)


class _OpRunner:
    """Executes one IR op given a name→value resolver. Shared by the jit
    lowering and the eager startup path."""

    @staticmethod
    def run(op, read, write, key):
        if op.type in _CONTROL_FLOW_OPS:
            _CONTROL_FLOW_OPS[op.type](op, read, write, key)
            return
        if op.type == '__init__':
            attrs = op.attrs
            out = attrs['initializer'].compute(attrs['shape'], attrs['dtype'],
                                               key=key)
            write(op.outputs['Out'][0], out)
            return
        if op.type == '__constant__':
            write(op.outputs['Out'][0], jnp.asarray(op.attrs['value']))
            return
        opdef = get_op(op.type)
        args = []
        for slot in opdef.input_slots:
            names = op.inputs.get(slot, [])
            if not names:
                args.append(None)
            elif slot in opdef.variadic:
                args.append([read(n) for n in names])
            else:
                args.append(read(names[0]))
        attrs = {k: v for k, v in op.attrs.items()
                 if k not in NON_KERNEL_ATTRS}
        if opdef.needs_rng:
            attrs['key'] = key
        amp = getattr(op.block.program, '_amp_config', None)
        if amp is not None:
            args = _amp_cast_args(op.type, args, amp)
        result = opdef.fn(*args, **attrs)
        if opdef.atomic_output:
            write(op.outputs['Out'][0], result)
            return
        results = [result] if len(opdef.output_slots) == 1 else list(result)
        for slot, res in zip(opdef.output_slots, results):
            names = op.outputs.get(slot, [])
            if not names:
                continue
            res_list = res if isinstance(res, (list, tuple)) else [res]
            if len(names) == 1 and len(res_list) == 1:
                write(names[0], res_list[0])
            else:
                for n, r in zip(names, res_list):
                    write(n, r)


def _amp_cast_args(op_type, args, amp):
    """Static AMP graph rewrite (ref: python/paddle/fluid/contrib/
    mixed_precision/fp16_utils.py:156 rewrite_program): white-list ops
    consume low-precision inputs (MXU dtype), black-list ops are pinned to
    fp32. Casts are inserted at trace time, so the lowered HLO carries them;
    master parameters stay fp32 in the state. jax.vjp differentiates through
    the casts, so grads come back fp32."""
    if op_type in amp['white']:
        target = amp['dtype']
    elif op_type in amp['black']:
        target = jnp.float32
    else:
        return args

    def cast(a):
        if a is None:
            return a
        if isinstance(a, (list, tuple)):
            return [cast(x) for x in a]
        a = jnp.asarray(a)
        if jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(target)
        return a

    return [cast(a) for a in args]


# ---------------------------------------------------------------------------
# structured control flow: sub-Block ops → XLA control-flow primitives.
# The TPU replacement for the reference's conditional_block/while interpreter
# ops (paddle/fluid/operators/controlflow/) — branches/bodies stay INSIDE the
# one compiled program (lax.cond / lax.while_loop / lax.switch / lax.scan).
# ---------------------------------------------------------------------------


def _run_block(block, read, write, key):
    """Run a sub-Block's ops over a local env chained onto the outer `read`."""
    for i, op in enumerate(block.ops):
        _OpRunner.run(op, read, write,
                      jax.random.fold_in(key, i) if _op_needs_key(op)
                      else None)


def _chained_env(overrides, outer_read):
    local = dict(overrides)

    def read(name):
        if name in local:
            return local[name]
        return outer_read(name)

    return local, read


def _as_bool(x):
    return jnp.reshape(jnp.asarray(x), ()).astype(bool)


def _run_cond(op, read, write, key):
    program = op.block.program
    pred = _as_bool(read(op.inputs['Cond'][0]))
    writes = op.attrs.get('writes', [])

    def branch(blk_idx, out_names):
        blk = program.block(blk_idx)

        def f(_):
            local, read2 = _chained_env({}, read)
            _run_block(blk, read2, local.__setitem__, key)
            # parent-var writes merge out of the branch; an untouched var
            # passes through its outer value so both branches line up
            return tuple(read2(n) for n in list(out_names) + writes)

        return f

    res = jax.lax.cond(pred,
                       branch(op.attrs['true_block'], op.attrs['true_outs']),
                       branch(op.attrs['false_block'], op.attrs['false_outs']),
                       None)
    for n, v in zip(op.outputs['Out'], res):
        write(n, v)


def _run_switch(op, read, write, key):
    program = op.block.program
    idx_val = jnp.reshape(jnp.asarray(read(op.inputs['Index'][0])),
                          ()).astype(jnp.int32)
    keys = op.attrs['keys']
    writes = op.attrs.get('writes', [])
    # map branch_index value → position in blocks list; unmatched → default
    pos = jnp.asarray(len(keys), jnp.int32)  # default branch position
    for i, k in enumerate(keys):
        pos = jnp.where(idx_val == k, jnp.asarray(i, jnp.int32), pos)

    def branch(blk_idx, out_names):
        blk = program.block(blk_idx)

        def f(_):
            local, read2 = _chained_env({}, read)
            _run_block(blk, read2, local.__setitem__, key)
            return tuple(read2(n) for n in list(out_names) + writes)

        return f

    branches = [branch(b, outs) for b, outs in
                zip(op.attrs['blocks'], op.attrs['branch_outs'])]
    res = jax.lax.switch(pos, branches, None)
    for n, v in zip(op.outputs['Out'], res):
        write(n, v)


def _run_while(op, read, write, key):
    program = op.block.program
    carry_names = op.attrs['loop_vars'] + op.attrs.get('writes', [])
    cond_blk = program.block(op.attrs['cond_block'])
    body_blk = program.block(op.attrs['body_block'])
    out_names = op.attrs['body_outs'] + op.attrs.get('writes', [])
    carry0 = (jnp.zeros((), jnp.int32),) + tuple(
        jnp.asarray(read(n)) for n in carry_names)

    def run_blk(blk, it, carry, names):
        local, read2 = _chained_env(dict(zip(carry_names, carry)), read)
        _run_block(blk, read2, local.__setitem__, jax.random.fold_in(key, it))
        return tuple(read2(n) for n in names)

    def cond_fun(c):
        return _as_bool(run_blk(cond_blk, c[0], c[1:],
                                [op.attrs['cond_out']])[0])

    def body_fun(c):
        new = run_blk(body_blk, c[0], c[1:], out_names)
        return (c[0] + 1,) + tuple(
            _check_carry(v, c0, n)
            for v, c0, n in zip(new, c[1:], carry_names))

    max_trips = op.attrs.get('max_trip_count')
    if max_trips is not None:
        # Reverse-differentiable lowering (ref WhileGradOp parity,
        # /root/reference/paddle/fluid/operators/controlflow/while_op.cc:154):
        # XLA's while has no reverse-mode rule, so with a static trip bound
        # the loop becomes a lax.scan of `max_trip_count` masked steps — an
        # inactive step keeps the previous carry via jnp.where (select is
        # differentiable; the dead branch's cotangent is zeroed).
        def scan_step(c, _):
            active = cond_fun(c)
            new = body_fun(c)
            kept = tuple(
                jnp.where(active, nv, cv) for nv, cv in zip(new, c))
            return kept, None
        res, _ = jax.lax.scan(scan_step, carry0, None, length=int(max_trips))
    else:
        res = jax.lax.while_loop(cond_fun, body_fun, carry0)
    for n, v in zip(op.outputs['Out'], res[1:]):
        write(n, v)


def _check_carry(new, init, name):
    """Loop carries must keep shape+dtype; raise instead of silently casting
    (a silent cast floors float updates into int carries)."""
    new = jnp.asarray(new)
    if new.shape != init.shape or new.dtype != init.dtype:
        raise TypeError(
            f"while loop carry '{name}' changed from "
            f"{init.shape}/{init.dtype} to {new.shape}/{new.dtype}; loop "
            f"variables must keep a fixed shape and dtype across iterations")
    return new


def _run_while_legacy(op, read, write, key):
    program = op.block.program
    body_blk = program.block(op.attrs['body_block'])
    carry_names = op.attrs['carry']
    carry0 = (jnp.zeros((), jnp.int32),) + tuple(
        jnp.asarray(read(n)) for n in carry_names)

    def cond_fun(c):
        return _as_bool(c[1])

    def body_fun(c):
        local, read2 = _chained_env(dict(zip(carry_names, c[1:])), read)
        _run_block(body_blk, read2, local.__setitem__,
                   jax.random.fold_in(key, c[0]))
        return (c[0] + 1,) + tuple(
            _check_carry(read2(n), c0, n)
            for n, c0 in zip(carry_names, c[1:]))

    res = jax.lax.while_loop(cond_fun, body_fun, carry0)
    for n, v in zip(carry_names, res[1:]):
        write(n, v)


def _run_scan(op, read, write, key):
    program = op.block.program
    blk = program.block(op.attrs['block'])
    slice_names = op.attrs['slice_names']
    pre_names = op.attrs['pre_names']
    new_names = op.attrs['new_names']
    out_names = op.attrs['out_names']
    xs = tuple(read(n) for n in op.inputs.get('X', []))
    init = tuple(read(n) for n in op.inputs.get('Init', []))

    def scan_fn(carry, x_t):
        it, mems = carry
        overrides = dict(zip(pre_names, mems))
        overrides.update(zip(slice_names, x_t))
        local, read2 = _chained_env(overrides, read)
        _run_block(blk, read2, local.__setitem__, jax.random.fold_in(key, it))
        new_mems = tuple(read2(n) for n in new_names)
        outs = tuple(read2(n) for n in out_names)
        return (it + 1, new_mems), outs

    _, ys = jax.lax.scan(scan_fn, (jnp.zeros((), jnp.int32), init), xs)
    for n, v in zip(op.outputs['Out'], ys):
        write(n, v)


def _run_create_array(op, read, write, key):
    write(op.outputs['Out'][0], [])


_CONTROL_FLOW_OPS = {
    '__create_array__': _run_create_array,
    '__cond__': _run_cond,
    '__switch__': _run_switch,
    '__while__': _run_while,
    '__while_legacy__': _run_while_legacy,
    '__scan__': _run_scan,
}


def _op_needs_key(op):
    """Whether tracing this op must fold a PRNG key. Eagerly folding for
    EVERY op left 3 dead equations (random_wrap/fold_in/unwrap) per non-RNG
    op in the jaxpr — pure trace+compile bloat. Skipping the fold cannot
    change numerics: fold_in(k, salt) depends only on (k, salt), never on
    which other ops folded."""
    t = op.type
    if t in ('__constant__', '__create_array__'):
        return False
    if t in _CONTROL_FLOW_OPS or t == '__init__':
        return True          # sub-blocks may contain RNG consumers
    from .ops.registry import has_op
    return has_op(t) and get_op(t).needs_rng


def _op_read_names(op):
    """All var names an op may read, including reads made by its sub-blocks
    (control-flow branches chain onto the outer env, so their reads are not
    declared in op.inputs) AND reads the control-flow machinery itself
    performs: cond/switch merge their `writes` vars out of every branch,
    reading the OUTER value for a branch that leaves one untouched
    (_run_cond/_run_switch), and while loops seed their carry from the
    outer env (_run_while/_run_while_legacy). Omitting these made DCE drop
    the producer of a cond `writes` var nothing else read — the program
    then died at trace time with a bare KeyError (found by the PR 10
    static verifier; regression: test_program_verifier.py)."""
    names = set(op.input_names())
    for attr in ('writes', 'loop_vars', 'carry'):
        v = op.attrs.get(attr)
        if isinstance(v, (list, tuple)):
            names.update(x for x in v if isinstance(x, str))
    program = op.block.program
    sub_blocks = []
    for attr in ('true_block', 'false_block', 'cond_block', 'body_block',
                 'block'):
        if attr in op.attrs:
            sub_blocks.append(op.attrs[attr])
    sub_blocks.extend(op.attrs.get('blocks', []))
    for bi in sub_blocks:
        for o in program.block(bi).ops:
            names |= _op_read_names(o)
    return names


def _pipeline_plan(program, fwd_ops, marker, feed_names, state_names,
                   fetch_names=(), feed_shapes=None):
    """Static analysis for PipelineOptimizer lowering (ref optimizer.py:3405):
    split the forward at the cut vars into stages + a loss tail. If the
    schedule is 'gpipe' and the stages are isomorphic (same op/attr
    sequence, same param shapes, single chained activation) and the default
    mesh has a matching 'pp' axis, the step runs the real SPMD GPipe
    schedule (partition/pipeline.gpipe); otherwise it lowers to a
    microbatched lax.scan whose gradient structure follows the schedule —
    gpipe numerics via scan-transpose, 1F1B/interleaved via per-microbatch
    (per-wave) backward inside the scan (sched_fwd_grad)."""
    pipe = marker.attrs.get('pipeline')
    if not pipe or not pipe.get('cut_vars'):
        return None
    cut_vars = list(pipe['cut_vars'])
    n_stages = len(cut_vars) + 1
    # knob resolution: env wins over the marker attr (which carries the
    # PipelineOptimizer/DistributedStrategy value) — strict-parse both
    from .partition.pipeline import pp_microbatches, pp_schedule
    schedule = pp_schedule(pipe.get('schedule')) or 'gpipe'
    m_attr = int(pipe.get('num_microbatches') or 0)
    m = pp_microbatches(m_attr if m_attr > 0 else None)
    if m is None:
        # auto (0-sentinel): smallest count whose predicted staged peak
        # fits PADDLE_TPU_HBM_BUDGET_MB — the auto_remat consumption
        # pattern; no budget (or an unplannable cut — the lowering falls
        # back regardless) → one microbatch per stage
        from .ir.auto_remat import hbm_budget_bytes
        budget = hbm_budget_bytes()
        m = n_stages
        if budget is not None:
            from .analysis.stage import solve_microbatches
            try:
                m, _peak, _fits = solve_microbatches(
                    program, cut_vars, schedule, budget,
                    fetch_names=fetch_names, feed_names=feed_names,
                    feed_shapes=feed_shapes)
            except Exception:
                pass
    # microbatch-combine rule for the loss: mean-reduced losses average
    # across microbatches, sum-reduced losses add — anything else cannot be
    # reassembled exactly from per-microbatch values (scan_fwd raises)
    loss_producer = next((o.type for o in reversed(fwd_ops)
                          if marker.attrs['loss'] in o.output_names()), None)
    combine = ('mean' if loss_producer in ('mean', 'reduce_mean')
               else 'sum' if loss_producer in ('reduce_sum', 'sum')
               else None)
    fallback = {'mode': 'scan', 'm': m, 'combine': combine,
                'schedule': schedule, 'n_stages': n_stages}
    if schedule != 'gpipe':
        # 1F1B/interleaved restructure the backward — they always lower
        # through the schedule-structured scan, never the SPMD gpipe mode
        return fallback
    producer = {}
    for i, op in enumerate(fwd_ops):
        for n in op.output_names():
            producer[n] = i
    if any(c not in producer for c in cut_vars):
        return fallback
    bounds = [producer[c] + 1 for c in cut_vars]
    if bounds != sorted(bounds) or len(set(bounds)) != len(bounds):
        return fallback
    stages, prev = [], 0
    for b in bounds:
        stages.append((prev, b))
        prev = b
    tail = (prev, len(fwd_ops))
    param_set = set(marker.attrs['params'])
    state_set = set(state_names)

    def op_sig(op):
        # op_device annotations must not break stage isomorphism — per-stage
        # device_guard is the canonical fluid PipelineOptimizer idiom
        attrs = tuple(sorted((k, repr(v)) for k, v in op.attrs.items()
                             if k not in NON_KERNEL_ATTRS))
        return (op.type, attrs)

    template_sig = [op_sig(o) for o in fwd_ops[stages[0][0]:stages[0][1]]]
    if any([op_sig(o) for o in fwd_ops[lo:hi]] != template_sig
           for lo, hi in stages[1:]):
        return fallback

    def stage_params(lo, hi):
        seen = []
        for op in fwd_ops[lo:hi]:
            for n in op.input_names():
                if n in param_set and n not in seen:
                    seen.append(n)
        return seen

    spn = [stage_params(lo, hi) for lo, hi in stages]
    if any(len(s) != len(spn[0]) for s in spn):
        return fallback
    blk = program.global_block()
    for s in spn[1:]:
        for a, b in zip(spn[0], s):
            if tuple(blk.var(a).shape or ()) != tuple(blk.var(b).shape or ()):
                return fallback

    def external_reads(lo, hi):
        produced, reads = set(), []
        for op in fwd_ops[lo:hi]:
            for n in _op_read_names(op):
                if (n not in produced and n not in param_set
                        and n not in reads):
                    reads.append(n)
            produced |= set(op.output_names())
        return reads

    ext = [external_reads(lo, hi) for lo, hi in stages]
    # stage 0 consumes exactly one feed; stage i consumes only cut i-1; no
    # stage reads mutable state (BN stats etc. would break the template map)
    if (len(ext[0]) != 1 or ext[0][0] not in feed_names
            or any(e != [cut_vars[i - 1]] for i, e in enumerate(ext)
                   if i > 0)
            or any(n in state_set for e in ext for n in e)):
        return fallback
    # gpipe_fwd materializes ONLY the final cut activation (stage-internal
    # vars and earlier cuts live inside the shard_map): the loss tail must
    # read nothing else, and fetches must be reachable — otherwise scan mode
    tail_outs = set()
    for o in fwd_ops[tail[0]:tail[1]]:
        tail_outs |= set(o.output_names())
    reachable = (tail_outs | {cut_vars[-1]} | set(feed_names)
                 | set(state_names))
    if any(f not in reachable for f in fetch_names):
        return fallback
    tail_reads = external_reads(*tail)
    if any(n not in reachable and n not in param_set for n in tail_reads):
        return fallback
    from .parallel.mesh import get_default_mesh
    mesh = get_default_mesh()
    if mesh is None or 'pp' not in mesh.shape or \
            mesh.shape['pp'] != len(stages):
        return fallback
    return {'mode': 'gpipe', 'm': m, 'stages': stages, 'tail': tail,
            'spn': spn, 'x_name': ext[0][0], 'out_name': cut_vars[0],
            'cut_out': cut_vars[-1], 'mesh': mesh,
            'schedule': 'gpipe', 'n_stages': len(stages)}


def _remat_segments(fwd_ops, checkpoints):
    """Split the forward op list at checkpoint-producing ops. Returns a list
    of (lo, hi) index ranges; each range becomes one jax.checkpoint segment
    (RecomputeOptimizer parity, ref python/paddle/fluid/optimizer.py:3705)."""
    ckpt = set(checkpoints)
    bounds = sorted({i + 1 for i, o in enumerate(fwd_ops)
                     if set(o.output_names()) & ckpt})
    segs, prev = [], 0
    for b in bounds:
        if b > prev:
            segs.append((prev, b))
            prev = b
    if prev < len(fwd_ops):
        segs.append((prev, len(fwd_ops)))
    return segs


def _lower(program: Program, feed_names, fetch_names, state_names,
           feed_shapes=None):
    """Build the pure step function for `program`.

    The step takes the training state SPLIT in two dicts so the caller can
    donate the hot one: `step(dstate, kstate, feeds, key)`. `dstate` holds
    parameters/optimizer slots whose HBM XLA may reuse in place
    (jit donate_argnums=(0,)); `kstate` holds state that must survive the
    call — fetch-aliased persistables and anything sharing a buffer with
    another argument. The split is the caller's choice; the lowering only
    sees the union."""
    ops = list(program.global_block().ops)
    bwd_idx = next((i for i, op in enumerate(ops)
                    if op.type == BACKWARD_OP_TYPE), None)
    state_set = frozenset(state_names)

    # ---- static backward-plan analysis (trace-independent) ----
    if bwd_idx is not None:
        marker = ops[bwd_idx]
        loss_name = marker.attrs['loss']
        param_names = marker.attrs['params']
        checkpoints = list(marker.attrs.get('checkpoints') or [])
        fwd_ops = ops[:bwd_idx]
        # rows-only embedding gradients (docs/SPARSE.md): per-site
        # surrogate params expose the per-occurrence cotangents, the
        # post-backward coalesce writes the padded-COO pair the
        # sparse_* update ops consume
        sparse_params = list(marker.attrs.get('sparse_params') or [])
        sparse_sites = [tuple(s) for s in
                        (marker.attrs.get('sparse_sites') or [])]
        sparse_rows_names = dict(zip(sparse_params,
                                     marker.outputs.get('SparseRows', [])))
        sparse_vals_names = dict(zip(sparse_params,
                                     marker.outputs.get('SparseVals', [])))
        pplan = _pipeline_plan(program, fwd_ops, marker, feed_names,
                               state_names, fetch_names, feed_shapes)
        if pplan is not None and sparse_params \
                and pplan['mode'] == 'gpipe':
            # the scan lowerings split the per-site surrogates per
            # microbatch (docs/SPARSE.md); only the SPMD gpipe mode —
            # whose stages live inside a shard_map the surrogate context
            # cannot cross — still rejects the composition
            raise NotImplementedError(
                'sparse embedding gradients are not composable with the '
                'SPMD gpipe pipeline mode; use the scan lowering '
                '(non-isomorphic stages or PADDLE_TPU_PP_SCHEDULE=1f1b) '
                'or set PADDLE_TPU_SPARSE_GRAD=0')
        loss_var_shape = None
        blk0 = program.global_block()
        if blk0.has_var(loss_name):
            shp = blk0.var(loss_name).shape
            if shp is not None and int(np.prod(shp or (1,))) == 1:
                loss_var_shape = tuple(shp)
        if pplan is not None:
            checkpoints = []       # pipeline owns the memory schedule
        segs = (_remat_segments(fwd_ops, checkpoints)
                if checkpoints else [(0, len(fwd_ops))])
        # names each segment boundary must carry forward: reads of later
        # ops + loss/fetches/state-writes. Everything else is dropped at
        # the boundary so jax.checkpoint only saves the live set and
        # remats the rest during the backward pass.
        live_after = []
        downstream = (set().union(*(_op_read_names(o)
                                    for o in ops[bwd_idx + 1:]))
                      if bwd_idx + 1 < len(ops) else set())
        downstream |= {loss_name, *fetch_names, *state_set, *checkpoints}
        # the coalesce after the backward reads every sparse site's ids
        downstream |= {ids_name for _, _, ids_name in sparse_sites}
        for _, hi in segs:
            live = set(downstream)
            for o in fwd_ops[hi:]:
                live |= _op_read_names(o)
            live_after.append(live)
        # state vars written during the forward (BN stats etc.) — the scan
        # fallback threads them through the microbatch loop carry
        written_state = [n for n in state_names
                        if any(n in o.output_names() for o in fwd_ops)]

    def step(dstate, kstate, feeds, base_key):
        state = {**dstate, **kstate}
        env: Dict[str, object] = dict(feeds)

        def make_read(*stores):
            def read(name):
                for s in stores:
                    if name in s:
                        return s[name]
                raise KeyError(
                    f"variable '{name}' has no value: not a feed, not in "
                    f"scope (did you run the startup program?)")
            return read

        def run_seq(op_list, offset, read, write, key=None):
            k = base_key if key is None else key
            for i, op in enumerate(op_list):
                # pass-pipeline-stamped ops carry their pre-rewrite position
                # (ir/pass_base.py): the RNG stream is position-independent,
                # so pass-on and pass-off programs stay bit-identical
                if _op_needs_key(op):
                    salt = op.attrs.get('_rng_salt')
                    kk = jax.random.fold_in(
                        k, offset + i if salt is None else salt)
                else:
                    kk = None
                try:
                    _OpRunner.run(op, read, write, kk)
                except Exception as e:
                    _annotate_trace_error(e, op, offset + i)
                    raise

        def _annotate_trace_error(e, op, pos):
            # trace-time failures name the op and — with construction-site
            # capture on (PADDLE_TPU_VERIFY ≠ off) — the model line that
            # built it, so the error points at user code, not the lowering
            site = getattr(op, '_site', None)
            note = (f"[while lowering op '{op.type}' (op #{pos})"
                    + (f" built at {site}" if site else '') + ']')
            if hasattr(e, 'add_note'):              # Python ≥3.11
                e.add_note(note)
            elif e.args and isinstance(e.args[0], str) \
                    and note not in e.args[0]:
                # 3.10 fallback: fold the note into the message (guarded
                # against double-annotation by nested run_seq frames)
                e.args = (f'{e.args[0]} {note}',) + e.args[1:]

        if bwd_idx is None:
            run_seq(ops, 0, make_read(env, state), env.__setitem__)
        else:
            # diff targets come from state (parameters) or from the feeds
            # (fluid.gradients w.r.t. data inputs, ref backward.py:1672)
            params = {}
            for n in param_names:
                if n in state_set:
                    params[n] = state[n]
                elif n in feeds:
                    params[n] = feeds[n]
                else:
                    raise KeyError(
                        f"gradient target '{n}' is neither a persistable "
                        f"parameter nor a fed variable")
            # one zero (nnz, D) surrogate per sparse lookup site: its
            # gradient is the per-occurrence row cotangent (the table
            # itself stays a constant — no dense V×D scatter ever exists)
            site_vals = {}
            site_keys = [s[0] for s in sparse_sites]
            for site_key, pname, ids_name in sparse_sites:
                if ids_name not in feeds:
                    raise KeyError(
                        f"sparse lookup site {site_key!r}: ids var "
                        f"{ids_name!r} is not fed this run; feed it or set "
                        f"PADDLE_TPU_SPARSE_GRAD=0")
                shp = tuple(feeds[ids_name].shape)
                if len(shp) >= 2 and shp[-1] == 1:
                    shp = shp[:-1]
                nnz = int(np.prod(shp)) if shp else 1
                table = state[pname]
                params[site_key] = jnp.zeros((nnz, int(table.shape[1])),
                                             table.dtype)

            def make_segment(lo, hi):
                def seg(e_in, pvals):
                    e = dict(e_in)
                    run_seq(fwd_ops[lo:hi], lo, make_read(e, pvals, state),
                            e.__setitem__)
                    return e
                return seg

            def plain_fwd(pvals):
                if site_keys:
                    # publish this trace's surrogate tracers for the
                    # lookup kernels (ops/sparse_ops.site_value); the
                    # dict stays bound through the whole value_and_grad
                    # call so checkpointed-segment replays re-read it
                    site_vals.update({k: pvals[k] for k in site_keys})
                e = {k: pvals.get(k, v) for k, v in feeds.items()}
                for (lo, hi), live in zip(segs, live_after):
                    seg = make_segment(lo, hi)
                    if checkpoints:
                        seg = jax.checkpoint(seg)
                    e = seg(e, pvals)
                    if checkpoints:
                        e = {n: v for n, v in e.items() if n in live}
                loss = e[loss_name]
                return jnp.sum(loss), e

            def gpipe_fwd(pvals):
                """Real SPMD GPipe: stage params stacked over 'pp', scan +
                ppermute schedule (partition/pipeline.gpipe), loss tail on
                the reassembled full batch."""
                from .partition.pipeline import gpipe
                e = {k: pvals.get(k, v) for k, v in feeds.items()}
                spn = pplan['spn']

                def getp(n):
                    return pvals[n] if n in pvals else state[n]

                stacked = {t: jnp.stack([getp(s[j]) for s in spn])
                           for j, t in enumerate(spn[0])}
                lo0, hi0 = pplan['stages'][0]
                x = e[pplan['x_name']]
                mm = pplan['m']
                if x.shape[0] % mm != 0:
                    raise ValueError(
                        f"pipeline: batch {x.shape[0]} not divisible by "
                        f"num_microbatches {mm}")
                xm = x.reshape((mm, x.shape[0] // mm) + x.shape[1:])

                def stage_fn(pstage, xs):
                    e2 = {pplan['x_name']: xs}
                    read2 = make_read(e2, pstage, state)
                    # per-stage RNG stream (microbatches within a stage
                    # share one — documented dropout caveat of gpipe mode)
                    ks = jax.random.fold_in(
                        base_key, jax.lax.axis_index('pp') + 1)
                    for i, op in enumerate(fwd_ops[lo0:hi0]):
                        if _op_needs_key(op):
                            salt = op.attrs.get('_rng_salt')
                            kk = jax.random.fold_in(
                                ks, lo0 + i if salt is None else salt)
                        else:
                            kk = None
                        _OpRunner.run(op, read2, e2.__setitem__, kk)
                    return e2[pplan['out_name']]

                ym = gpipe(stage_fn, stacked, xm, mesh=pplan['mesh'])
                e[pplan['cut_out']] = ym.reshape(
                    (ym.shape[0] * ym.shape[1],) + ym.shape[2:])
                tlo, thi = pplan['tail']
                run_seq(fwd_ops[tlo:thi], tlo, make_read(e, pvals, state),
                        e.__setitem__)
                return jnp.sum(e[loss_name]), e

            def micro_split(pvals):
                """Shared scan-mode prologue: batch-major feeds and the
                per-site sparse surrogates split (m, batch/m, ...);
                scalars pass through. Microbatch i's lookup occurrences
                are the contiguous surrogate row block i (ids are
                batch-major, so flatten order is block-contiguous)."""
                mm = pplan['m']
                if pplan['combine'] is None:
                    raise ValueError(
                        "pipeline microbatching requires a mean- or "
                        "sum-reduced scalar loss (loss producer must be "
                        "mean/reduce_mean/reduce_sum); restructure the loss "
                        "or remove cut_list")
                fv = {k: pvals.get(k, v) for k, v in feeds.items()}
                dims = {v.shape[0] for v in fv.values()
                        if getattr(v, 'ndim', 0) >= 1}
                if len(dims) != 1:
                    raise ValueError(
                        f"pipeline microbatching requires all batch-major "
                        f"feeds to share one leading dim; got {sorted(dims)}")
                batch = dims.pop() if dims else 0
                if batch == 0 or batch % mm != 0:
                    raise ValueError(
                        f"pipeline: batch {batch} not divisible by "
                        f"num_microbatches {mm}")
                mb = batch // mm
                split, rest = {}, {}
                for kf, v in fv.items():
                    if getattr(v, 'ndim', 0) >= 1:
                        split[kf] = v.reshape((mm, mb) + v.shape[1:])
                    else:
                        rest[kf] = v
                site_split = {}
                for k in site_keys:
                    v = pvals[k]
                    if v.shape[0] % mm != 0:
                        raise ValueError(
                            f"pipeline+sparse: lookup site {k!r} has "
                            f"{v.shape[0]} id occurrences, not divisible "
                            f"by num_microbatches {mm}")
                    site_split[k] = v.reshape(
                        (mm, v.shape[0] // mm) + v.shape[1:])
                return fv, split, rest, site_split, mb, mm

            def micro_fetch_names():
                # fetches of forward intermediates: collected per microbatch
                # and reassembled after the scan (grad fetches are bound
                # after fwd by the marker, so only fwd-produced names count)
                fwd_produced = {n for o in fwd_ops
                                for n in o.output_names()}
                return [n for n in fetch_names
                        if n in fwd_produced and n not in state_set
                        and n != loss_name]

            def micro_stitch(e, micro_fetch, ys, mm, mb):
                for n, v in zip(micro_fetch, ys):
                    if v.ndim >= 2 and v.shape[1] == mb:
                        # batch-major intermediate: stitch microbatches back
                        e[n] = v.reshape((mm * mb,) + v.shape[2:])
                    else:
                        # per-microbatch scalar/metric: average (exact for
                        # mean-type metrics over equal microbatches)
                        e[n] = jnp.mean(v, axis=0)

            def scan_fwd(pvals):
                """GPipe-numerics fallback: microbatched lax.scan with loss
                (and grad, via autodiff of the scan) accumulation; state
                writes thread through the carry in microbatch order."""
                fv, split, rest, site_split, mb, mm = micro_split(pvals)
                sw0 = {n: state[n] for n in written_state}
                micro_fetch = micro_fetch_names()

                def body(carry, xs):
                    loss_acc, sw = carry
                    mb_idx, xslices, ssl = xs
                    if site_keys:
                        # rebind the site surrogates to this trace's
                        # per-microbatch slices (grads flow back through
                        # the scan transpose into pvals[site])
                        site_vals.update(ssl)
                    e = dict(rest)
                    e.update(xslices)
                    e.update(sw)
                    run_seq(fwd_ops, 0, make_read(e, pvals, state),
                            e.__setitem__,
                            key=jax.random.fold_in(base_key, 7919 + mb_idx))
                    new_sw = {n: e[n] for n in written_state}
                    outs = tuple(jnp.asarray(e[n]) for n in micro_fetch)
                    return (loss_acc + jnp.sum(e[loss_name]), new_sw), outs

                (loss_tot, sw_fin), ys = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32), sw0),
                    (jnp.arange(mm), split, site_split))
                loss = loss_tot / mm if pplan['combine'] == 'mean' \
                    else loss_tot
                e = dict(fv)          # all feeds stay fetchable
                e.update(sw_fin)
                e[loss_name] = (jnp.reshape(loss, loss_var_shape)
                                if loss_var_shape is not None else loss)
                micro_stitch(e, micro_fetch, ys, mm, mb)
                return jnp.reshape(loss, ()), e

            def sched_fwd_grad(pvals):
                """Schedule-structured gradients for 1F1B/interleaved: the
                backward runs per microbatch (1F1B) or per wave
                (interleaved) INSIDE the scan, so only one wave of
                residuals is ever live — the staged planner's
                ``host_peak_bytes`` prediction, visible to XLA as a
                smaller temp arena than the gpipe scan-transpose.

                1F1B runs its scan in reverse: jax's scan transpose
                accumulates constant-operand cotangents from the last
                microbatch down, so reverse per-microbatch accumulation
                reproduces the gpipe schedule's float association exactly
                — bitwise grad parity on the same cut. The per-microbatch
                cotangent seed is ``loss_sum / m`` (the same literal
                division the transpose injects). With forward-written
                state (BN stats) the scan must run forward; parity then
                holds to tolerance, not bitwise. Returns ``(env, grads)``
                — the backward is internal, no outer value_and_grad."""
                fv, split, rest, site_split, mb, mm = micro_split(pvals)
                sw0 = {n: state[n] for n in written_state}
                micro_fetch = micro_fetch_names()
                dense = {n: pvals[n] for n in param_names}
                combine = pplan['combine']

                def mb_fwd(pv, sv, xslices, sw, mb_idx):
                    if site_keys:
                        site_vals.update(sv)
                    e = dict(rest)
                    e.update(xslices)
                    e.update(sw)
                    run_seq(fwd_ops, 0, make_read(e, pv, state),
                            e.__setitem__,
                            key=jax.random.fold_in(base_key, 7919 + mb_idx))
                    lsum = jnp.sum(e[loss_name])
                    seed = lsum / mm if combine == 'mean' else lsum
                    new_sw = {n: e[n] for n in written_state}
                    outs = tuple(jnp.asarray(e[n]) for n in micro_fetch)
                    return seed, (lsum, new_sw, outs)

                gacc0 = {n: jnp.zeros_like(v) for n, v in dense.items()}
                if pplan['schedule'] == '1f1b':
                    def body(carry, xs):
                        gacc, sw = carry
                        mb_idx, xslices, ssl = xs
                        (_, (lsum, new_sw, outs)), (gd, gs) = \
                            jax.value_and_grad(
                                mb_fwd, argnums=(0, 1), has_aux=True)(
                                dense, ssl, xslices, sw, mb_idx)
                        gacc = {n: gacc[n] + gd[n] for n in gacc}
                        return (gacc, new_sw), (lsum, outs, gs)

                    (gacc, sw_fin), (lsums, ys, gsite) = jax.lax.scan(
                        body, (gacc0, sw0),
                        (jnp.arange(mm), split, site_split),
                        reverse=not written_state)
                else:                                       # interleaved
                    from .analysis.stage import wave_size
                    w = wave_size('interleaved', pplan['n_stages'], mm)
                    nw = mm // w
                    wsplit = {k: v.reshape((nw, w) + v.shape[1:])
                              for k, v in split.items()}
                    wsite = {k: v.reshape((nw, w) + v.shape[1:])
                             for k, v in site_split.items()}
                    widx = jnp.arange(mm).reshape(nw, w)

                    def wave_fwd(pv, sv, wslices, sw, idxs):
                        def inner(c, ixs):
                            sacc, sw_i = c
                            mb_idx, xsl, ssl = ixs
                            seed, (lsum, new_sw, outs) = mb_fwd(
                                pv, ssl, xsl, sw_i, mb_idx)
                            return (sacc + seed, new_sw), (lsum, outs)

                        (seed_tot, sw_out), (lsums, outs) = jax.lax.scan(
                            inner, (jnp.zeros((), jnp.float32), sw),
                            (idxs, wslices, sv))
                        return seed_tot, (lsums, sw_out, outs)

                    def body(carry, xs):
                        gacc, sw = carry
                        idxs, wslices, wsl = xs
                        (_, (lsums, sw_out, outs)), (gd, gs) = \
                            jax.value_and_grad(
                                wave_fwd, argnums=(0, 1), has_aux=True)(
                                dense, wsl, wslices, sw, idxs)
                        gacc = {n: gacc[n] + gd[n] for n in gacc}
                        return (gacc, sw_out), (lsums, outs, gs)

                    (gacc, sw_fin), (lsums, ys, gsite) = jax.lax.scan(
                        body, (gacc0, sw0), (widx, wsplit, wsite))
                    lsums = lsums.reshape((mm,))
                    ys = tuple(v.reshape((mm,) + v.shape[2:]) for v in ys)
                    gsite = {k: v.reshape((mm,) + v.shape[2:])
                             for k, v in gsite.items()}
                # loss assembled in FORWARD microbatch order — the same
                # float association as scan_fwd's carry accumulation
                loss_acc = jnp.zeros((), jnp.float32)
                for i in range(mm):
                    loss_acc = loss_acc + lsums[i]
                loss = loss_acc / mm if combine == 'mean' else loss_acc
                e = dict(fv)
                e.update(sw_fin)
                e[loss_name] = (jnp.reshape(loss, loss_var_shape)
                                if loss_var_shape is not None else loss)
                micro_stitch(e, micro_fetch, ys, mm, mb)
                grads = dict(gacc)
                for k in site_keys:
                    g = gsite[k]
                    grads[k] = g.reshape((-1,) + g.shape[2:])
                return e, grads

            from .ops import sparse_ops as _sp
            if pplan is not None and pplan['mode'] == 'scan' \
                    and pplan['schedule'] != 'gpipe':
                # 1F1B/interleaved own their backward (per-microbatch /
                # per-wave value_and_grad inside the scan)
                with _sp.site_context(site_vals):
                    env, grads = sched_fwd_grad(params)
            else:
                if pplan is None:
                    fwd = plain_fwd
                elif pplan['mode'] == 'gpipe':
                    fwd = gpipe_fwd
                else:
                    fwd = scan_fwd
                with _sp.site_context(site_vals):
                    (_, env), grads = jax.value_and_grad(
                        fwd, has_aux=True)(params)
            for n, gname in zip(param_names, marker.outputs['Grads']):
                env[gname] = grads[n]
            if sparse_sites:
                # coalesce per-occurrence cotangents into the padded-COO
                # pair (@GRAD@ROWS/@GRAD@VALS) the sparse_* updates read
                per_param = {}
                for site_key, pname, ids_name in sparse_sites:
                    per_param.setdefault(pname, []).append(
                        (site_key, ids_name))
                for pname, psites in per_param.items():
                    table = state[pname]
                    dim = int(table.shape[1])
                    ids_cat = jnp.concatenate(
                        [_sp.flatten_ids(feeds[i]) for _, i in psites])
                    vals_cat = jnp.concatenate(
                        [grads[k].reshape(-1, dim) for k, _ in psites])
                    rows, vals = _sp.coalesce_rows(ids_cat, vals_cat,
                                                   int(table.shape[0]))
                    env[sparse_rows_names[pname]] = rows
                    env[sparse_vals_names[pname]] = vals
            run_seq(ops[bwd_idx + 1:], bwd_idx + 1,
                    make_read(env, state), env.__setitem__)

        # ALL state passes through (donated inputs alias unwritten outputs —
        # otherwise the scope would keep handles to donated buffers)
        new_state = {n: env.get(n, state[n]) for n in state_set}
        read = make_read(env, state)
        fetches = [read(n) for n in fetch_names]
        return new_state, fetches

    return step


def _dataset_logger():
    """INFO logger for *_from_dataset fetch reporting (repo invariant:
    framework code never print()s — tools/lint_codebase.py enforces it)."""
    import logging
    from .log_helper import get_logger
    return get_logger(__name__, logging.INFO, fmt='%(message)s')


def _default_len_feeds(block, feed_vals):
    """Plain-array feeds to lod_level>0 vars: the companion '@LEN' var
    defaults to full lengths (every row spans the padded time dim) so
    non-ragged feeds keep the pre-LoDTensor semantics."""
    for name in list(feed_vals):
        ln = name + '@LEN'
        if (not name.endswith('@LEN') and ln not in feed_vals
                and block.has_var(ln) and block.var(ln).is_data):
            arr = feed_vals[name]
            if getattr(arr, 'ndim', 0) >= 2:
                feed_vals[ln] = jnp.full((arr.shape[0],), arr.shape[1],
                                         jnp.int32)


class Executor:
    """fluid.Executor parity. `place` is accepted for compat; execution always
    targets the default XLA backend."""

    def __init__(self, place=None):
        self.place = _get_paddle_place(place)
        self._cache = {}
        self._step_counter = 0
        self._partition_placed = set()
        self._lookup_meta_cache = {}
        # async pipeline bookkeeping: dispatched steps whose FetchHandles
        # are still pending (K-in-flight window + donation protection)
        self._window = InflightWindow()
        # persistent cross-process XLA compile cache underneath the
        # in-process program+shape jit cache (core/compile_cache.py)
        from .core.compile_cache import setup_persistent_cache
        setup_persistent_cache()

    # ------------------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True, feed_var_name='feed',
            fetch_var_name='fetch'):
        """Run `program` once. Fetch results come back three ways:

        - default (synchronous): numpy arrays, one blocking D2H per fetch —
          the exact pre-pipeline behavior (`PADDLE_TPU_ASYNC=0` pins this);
        - `return_numpy=False`: :class:`FetchHandle` s backed by on-device
          arrays — `np.asarray(handle)` materializes on read, with snapshot
          semantics (later steps cannot donate-over a pending handle);
        - async mode (`PADDLE_TPU_ASYNC=1`/`K`, or
          `ExecutionStrategy.num_inflight_steps > 1` on a CompiledProgram):
          always returns FetchHandles and keeps up to K dispatched steps
          outstanding, blocking on the oldest handle only when the window
          is full — host feed prep and dispatch of step N+1 overlap device
          execution of step N (semantics pinned by
          tests/framework/test_async_pipeline.py).
        """
        # hang watchdog (resilience/watchdog.py, PADDLE_TPU_WATCHDOG): a
        # wedged device step breaches the 'executor_step' lease — deadline
        # tracks this executor's own rolling-median run time (the first,
        # compiling run gets the larger cold deadline). Free when no
        # process watchdog is armed.
        lease = _watchdog.arm_step('executor_step')
        try:
            if not _obs._ENABLED:
                return self._run_impl(program, feed, fetch_list, scope,
                                      return_numpy)
            # telemetry on: every run is one span tree — prepare / lower /
            # execute / fetch phases nest under executor/run (trace.json),
            # the phase durations + donation/byte counts land in the metrics
            # registry and one steps.jsonl record (docs/OBSERVABILITY.md)
            with _obs.span('executor/run', step=self._step_counter + 1):
                return self._run_impl(program, feed, fetch_list, scope,
                                      return_numpy)
        finally:
            _watchdog.disarm(lease)

    def _lookup_feed_meta(self, program):
        """Per-program map of embedding lookups fed directly from data
        vars: [(ids_name, vocab, table_name, is_sparse_site)]. Cached per
        (program id, version) — one op scan, not one per run."""
        key = (program._id, program._version)
        meta = self._lookup_meta_cache.get(key)
        if meta is None:
            meta = []
            blk = program.global_block()
            for op in blk.ops:
                if op.type != 'lookup_table':
                    continue
                ids = (op.inputs.get('ids') or [None])[0]
                w = (op.inputs.get('w') or [None])[0]
                if not (ids and w and blk.has_var(ids) and blk.has_var(w)
                        and getattr(blk.var(ids), 'is_data', False)):
                    continue
                shape = blk.var(w).shape or ()
                if not shape or not isinstance(shape[0], int) \
                        or shape[0] <= 0:
                    continue
                meta.append((ids, int(shape[0]), w,
                             op.attrs.get('_sparse_site') is not None))
            self._lookup_meta_cache[key] = meta
        return meta

    def _embedding_feed_checks(self, program, block, feed):
        """Two per-run hooks over embedding-id feeds (docs/SPARSE.md):

        - ``PADDLE_TPU_VERIFY=full`` + ``PADDLE_TPU_EMBED_OOB=error``:
          host-side dtype/range validation — an out-of-range id would
          silently clip to row V-1 on device and train the wrong row.
          ``PADDLE_TPU_EMBED_OOB=clip`` is the legacy escape hatch.
        - always-on ``sparse_*`` metrics for rows-only-gradient tables
          (host-resident feeds only; staged device arrays are counted at
          coalesce by their bucket instead of forcing a D2H sync).
        """
        meta = self._lookup_feed_meta(program)
        if not meta:
            return
        from .core.lod import LoDTensor
        from . import analysis
        from .ops import sparse_ops as _sp
        check_range = analysis.verify_level() == 'full' \
            and _sp.oob_policy() == 'error'
        for ids_name, vocab, table, is_sparse_site in meta:
            value = feed.get(ids_name)
            if value is None:
                continue
            if isinstance(value, LoDTensor):
                value = value.data
            if isinstance(value, jax.Array):
                continue      # staged feed: no host copy without a sync
            arr = np.asarray(value)
            if check_range:
                if not np.issubdtype(arr.dtype, np.integer):
                    raise ValueError(
                        f"feed {ids_name!r} indexes embedding table "
                        f"{table!r} but has dtype {arr.dtype} (expected "
                        f"an integer id dtype)")
                if arr.size and (arr.min() < 0 or arr.max() >= vocab):
                    raise ValueError(
                        f"feed {ids_name!r} holds ids outside [0, {vocab}) "
                        f"for embedding table {table!r} (min {arr.min()}, "
                        f"max {arr.max()}); on device they would silently "
                        f"clip to row {vocab - 1} and train the wrong row. "
                        f"Set PADDLE_TPU_EMBED_OOB=clip for the legacy "
                        f"clipping behavior.")
            if is_sparse_site and arr.size:
                _sp.record_sparse_lookup(
                    arr.size, _sp.nnz_bucket(arr.size),
                    dedup_rows=int(np.unique(arr).size), table=table)

    def _run_impl(self, program, feed, fetch_list, scope, return_numpy):
        from .compiler import CompiledProgram
        sharding = None
        build_strategy = None
        exec_strategy = None
        donate = os.environ.get('PADDLE_TPU_DONATE', '1') != '0'
        if isinstance(program, CompiledProgram):
            sharding = program._data_sharding
            bs = build_strategy = program._build_strategy
            exec_strategy = program._exec_strategy
            # fluid memory knobs map onto donation: enable_inplace=False or
            # memory_optimize=False opts the whole program out of buffer reuse
            if bs is not None and (bs.enable_inplace is False
                                   or bs.memory_optimize is False):
                donate = False
            program = program._program
        # K > 0: pipelined loop with up to K dispatched steps outstanding.
        # Pipelining turns donation OFF for the dispatched steps: donating a
        # buffer that is still being produced by the PREVIOUS in-flight step
        # makes the runtime block the dispatch until the producer finishes
        # (measured: the whole overlap win disappears on the CPU PJRT
        # client), and K-deep double buffering fundamentally needs the old
        # and new state live at once. The cost is the classic double-buffer
        # transient (2× pipelined-state HBM); a CPU finding, ROADMAP S7.
        inflight_k = resolve_inflight_steps(exec_strategy)
        use_handles = bool(inflight_k) or not return_numpy
        if inflight_k:
            donate = False
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in fetch_list]

        block = program.global_block()
        if any(op.type == '__init__' for op in block.ops):
            with _obs.span('executor/startup'):
                self._run_startup(program, scope)
            return []

        prep_span = _obs.span('executor/prepare')
        prep_span.__enter__()
        # persistable vars = training state
        state_names = sorted(v.name for v in program.list_vars()
                             if v.persistable)
        # partitioned state placement (paddle_tpu/partition): programs a
        # fleet strategy stamped (`_fsdp_axis` legacy pure-fsdp, or
        # `_partition_params` full rule-table resolution — tp Megatron
        # specs + fsdp tiles on one mesh) get their persistables
        # device_put with the partitioner-resolved NamedShardings, the
        # pjit-style in_shardings of the jitted step. Place once per
        # (program, scope): step outputs keep the sharding, so
        # re-placing every run would only add host-side dispatch cost.
        # program._id is a never-recycled counter (unlike id())
        spec_fn = None
        part_key = (program._id, id(scope))
        if part_key not in self._partition_placed:
            from .partition import state_spec_fn
            spec_fn = state_spec_fn(program)
            if spec_fn is not None:
                self._partition_placed.add(part_key)
        # multi-host fleet (fleet_runtime/): state must live as GLOBAL
        # arrays on the process-spanning mesh — partitioner-resolved
        # shardings (fsdp tiles, tp tiles) or replicated — so the jitted
        # step is one SPMD program over all hosts and XLA emits the
        # cross-host gradient reduction the c_allreduce sync points
        # describe. The guard per value is one attribute check; already-
        # global step outputs pass straight through on warm steps.
        fleet_mesh = _fleet_spmd_mesh()
        fleet_spec_fn = None
        if fleet_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from .partition import state_spec_fn as _state_spec_fn
            fleet_spec_fn = _state_spec_fn(program) or (
                lambda n, s: NamedSharding(fleet_mesh, PartitionSpec()))
        state = {}
        for n in state_names:
            val = scope.find(n)
            if val is None:
                raise RuntimeError(
                    f"persistable var '{n}' is uninitialized; run the startup "
                    f"program first (exe.run(fluid.default_startup_program()))")
            if fleet_mesh is not None and hasattr(val, 'shape'):
                val = _globalize_state(val, fleet_mesh,
                                       fleet_spec_fn(n, val.shape))
            elif spec_fn is not None and hasattr(val, 'shape'):
                val = jax.device_put(val, spec_fn(n, val.shape))
            state[n] = val

        from .core.lod import LoDTensor
        feed_vals = {}
        passthrough_bytes = 0
        if fleet_mesh is not None:
            # fleet feeds: every host contributes its local rows, the
            # step consumes ONE global batch (docs/DISTRIBUTED.md). Data
            # vars shard their leading dim over the partitioner's data
            # axes; everything else must be host-identical and
            # replicates. LoD feeds have no row-aligned global form.
            from .partition import get_partitioner
            from jax.sharding import PartitionSpec
            part = get_partitioner()
            data_spec = part.data_spec()
            for name, value in feed.items():
                if isinstance(value, LoDTensor):
                    raise NotImplementedError(
                        f'feed {name!r}: LoDTensor feeds are not '
                        f'supported on a multi-host fleet (shard the '
                        f'reader and pad to dense)')
                dtype = block.var(name).dtype if block.has_var(name) \
                    else None
                if dtype == 'int64':
                    from .core.dtypes import check_int32_bounds
                    check_int32_bounds(np.asarray(value), name)
                target = to_jax_dtype(dtype) if dtype else None
                host_val = np.asarray(value)
                if target is not None:
                    host_val = host_val.astype(target, copy=False)
                is_data = block.has_var(name) and \
                    getattr(block.var(name), 'is_data', False)
                spec = (data_spec if is_data and host_val.ndim
                        else PartitionSpec())
                feed_vals[name] = _globalize_feed(host_val, fleet_mesh,
                                                  spec)
        else:
            for name, value in feed.items():
                if isinstance(value, LoDTensor):
                    # ragged feed: bind the padded data plus the companion
                    # length var that data(lod_level>0) declared
                    if block.has_var(name + '@LEN'):
                        from .core.dtypes import check_int32_bounds
                        feed_vals[name + '@LEN'] = jnp.asarray(
                            check_int32_bounds(value.lengths, name + '@LEN'))
                    value = value.data
                dtype = block.var(name).dtype if block.has_var(name) \
                    else None
                target = to_jax_dtype(dtype) if dtype else None
                if (isinstance(value, jax.Array)
                        and not isinstance(value, jax.core.Tracer)
                        and (target is None or value.dtype == target)
                        and (sharding is None
                             or value.sharding == sharding)):
                    # zero-copy staged feed: the DataLoader producer thread
                    # already committed this batch to the device (reader.py
                    # device_put) — and ran the int64 bounds check
                    # host-side at staging — so re-converting here would
                    # only put H2D (and, for int64, a device→host bounds
                    # scan = a full sync) back on the critical path
                    passthrough_bytes += getattr(value, 'nbytes', 0)
                    feed_vals[name] = value
                    continue
                if dtype == 'int64':
                    # int64 computes as int32 on device (core/dtypes.py); a
                    # feed that would wrap must fail loudly, not silently
                    from .core.dtypes import check_int32_bounds
                    check_int32_bounds(value, name)
                arr = jnp.asarray(value, target)
                if sharding is not None:
                    arr = jax.device_put(arr, sharding)
                feed_vals[name] = arr
        if _obs._ENABLED and passthrough_bytes:
            _obs.inc('executor_feed_passthrough_bytes', passthrough_bytes,
                     help='feed bytes recognized as already device-committed '
                          'and passed through without a second device_put')
        _default_len_feeds(block, feed_vals)
        self._embedding_feed_checks(program, block, feed)
        prep_span.__exit__(None, None, None)

        from . import ir
        feed_sig = tuple(sorted((n, v.shape, str(v.dtype))
                                for n, v in feed_vals.items()))
        # the pp knobs restructure the lowering (schedule/microbatch
        # count), so a knob flip must re-lower, not hit the cache
        key = (id(program), program._version, feed_sig, tuple(fetch_names),
               tuple(state_names), donate,
               ir.pipeline_signature(build_strategy),
               os.environ.get('PADDLE_TPU_PP_SCHEDULE', ''),
               os.environ.get('PADDLE_TPU_PP_MICROBATCHES', ''))
        fn = self._cache.get(key)
        compiled_now = fn is None
        record_program_cache(hit=not compiled_now)
        lower_span = _obs.span('executor/lower', program=program._id)
        if fn is None:
            with lower_span:
                # pre-lowering validation (PADDLE_TPU_VERIFY=full): the
                # static verifier rejects malformed programs HERE, with the
                # op and its Python construction site, instead of deep in
                # the XLA trace. Runs per compile-cache miss, never per step.
                from . import analysis
                if analysis.verify_level() == 'full':
                    analysis.assert_verified(
                        program, fetch_names=fetch_names,
                        feed_names=list(feed_vals), stage='pre-lower')
                # program-level IR passes rewrite a CLONE before the trace
                # (op fusion / DCE / constant folding — paddle_tpu/ir/);
                # their runtime lands inside executor/lower and therefore in
                # executor_compile_seconds, same as the trace they shrink
                opt_program, _ = ir.apply_pipeline(
                    program, fetch_names=fetch_names,
                    feed_names=list(feed_vals),
                    build_strategy=build_strategy,
                    feed_shapes={n: tuple(v.shape) for n, v in
                                 feed_vals.items()
                                 if hasattr(v, 'shape')})
                # static memory plan (paddle_tpu/analysis/plan.py): peak
                # HBM predicted from the VarInfos before the trace runs —
                # milliseconds, zero tracing, once per compile-cache miss
                self._plan_telemetry(opt_program, fetch_names, feed_vals,
                                     donate)
                step = _lower(opt_program, list(feed_vals), fetch_names,
                              state_names,
                              feed_shapes={n: tuple(v.shape)
                                           for n, v in feed_vals.items()
                                           if hasattr(v, 'shape')})
                fn = jax.jit(step, donate_argnums=(0,))
            self._cache[key] = fn

        # Donation guards: a fetch-aliased persistable must survive the call
        # (the caller observes its pre-step buffer), and a buffer shared
        # between two state names — or with a feed — may be donated at most
        # once. A persistable fetched by a still-PENDING FetchHandle from an
        # earlier async step is protected too: donating it would overwrite
        # the handle's snapshot in place. Everything else (params, optimizer
        # slots, BN stats) is donated so XLA updates it in place instead of
        # doubling live HBM.
        fetch_set = frozenset(fetch_names)
        pending_protected = self._window.protected_names()
        seen_ids = {id(v) for v in feed_vals.values()}
        dstate, kstate = {}, {}
        for n in state_names:
            v = state[n]
            if (donate and n not in fetch_set and n not in pending_protected
                    and id(v) not in seen_ids):
                dstate[n] = v
                seen_ids.add(id(v))
            else:
                kstate[n] = v

        self._step_counter += 1
        base_key = jax.random.fold_in(default_generator.base_key(),
                                      self._step_counter)
        from .debugging import check_nan_inf_enabled
        check_nan = check_nan_inf_enabled() and bool(fetch_names)
        if inflight_k:
            # bounded in-flight window: block on the OLDEST dispatched
            # step only when K are already outstanding, so this step's
            # dispatch (and the next step's host feed prep) overlap the
            # device executing steps N..N-K+1
            self._window.admit(inflight_k)
        # execute = host-side dispatch of the jitted step (on a cache miss
        # this includes trace + XLA compile); fetch = scope write-back plus
        # the device→host transfer that synchronizes with the computation
        exec_span = _obs.span('executor/execute', compile=compiled_now)
        try:
            with exec_span:
                new_state, fetches = fn(dstate, kstate, feed_vals, base_key)
        except FloatingPointError:
            # jax_debug_nans (enable_check_nan_inf) raised inside the step:
            # record the detection so a NaN storm is a telemetry series,
            # not only the first traceback
            _obs.inc('nonfinite_detections', 1,
                     help='fetched variables containing NaN/Inf '
                          '(FLAGS_check_nan_inf)')
            _obs.instant('nonfinite_detected', source='jax_debug_nans')
            raise
        fetch_span = _obs.span('executor/fetch')
        with fetch_span:
            for n, v in new_state.items():
                scope.set(n, v)
            if use_handles:
                # non-blocking fetches: hand back FetchHandles over the
                # still-on-device arrays; np.asarray(handle) is the sync
                # point. The window entry records which persistables the
                # handles alias so later donation can't corrupt them, and
                # (with FLAGS_check_nan_inf) the non-finite scan moves to
                # materialization time instead of re-serializing the loop.
                result = [FetchHandle(f, name=n, check_nan=check_nan)
                          for n, f in zip(fetch_names, fetches)]
                self._window.push(result,
                                  protected=fetch_set & frozenset(state_names))
            else:
                result = [np.asarray(f) for f in fetches]

        if check_nan and not use_handles:
            # FLAGS_check_nan_inf parity on the fused step: scan the fetched
            # host values; detections land in telemetry (counter + instant
            # trace marker) BEFORE the raise so a NaN storm is visible in
            # the artifacts, not only in the first traceback
            with _obs.span('executor/check_nan_inf'):
                self._check_fetches_finite(fetch_names, fetches)

        if _obs._ENABLED:
            _obs.inc('executor_steps',
                     help='completed Executor.run training/eval steps')
            _obs.inc('executor_donated_buffers', len(dstate),
                     help='state buffers donated into the step (in-place '
                          'XLA update)')
            _obs.inc('executor_kept_buffers', len(kstate),
                     help='state buffers excluded from donation '
                          '(fetch-aliased or buffer-shared)')
            feed_bytes = sum(getattr(v, 'nbytes', 0)
                             for v in feed_vals.values())
            fetch_bytes = sum(getattr(f, 'nbytes', 0) for f in result)
            _obs.inc('executor_feed_bytes', feed_bytes,
                     help='bytes fed into Executor.run')
            _obs.inc('executor_fetch_bytes', fetch_bytes,
                     help='bytes fetched out of Executor.run')
            # measured counterpart of program_plan_accounted_bytes: the
            # same state+feed+fetch accounting from the LIVE buffers
            state_bytes = sum(getattr(v, 'nbytes', 0)
                              for v in new_state.values())
            _obs.set_gauge('program_measured_hbm_bytes',
                           state_bytes + feed_bytes + fetch_bytes,
                           help='measured state+feed+fetch bytes of the '
                                'last step (predicted-vs-measured delta '
                                'in tools/telemetry_report.py)')
            if compiled_now:
                _obs.observe(
                    'executor_compile_seconds',
                    lower_span.duration + exec_span.duration,
                    help='lower + first-execution (trace/XLA-compile) time '
                         'per program+shape cache miss')
            _obs.log_step(
                kind='executor', step=self._step_counter,
                compiled=compiled_now, donated=len(dstate),
                kept=len(kstate), feed_bytes=feed_bytes,
                fetch_bytes=fetch_bytes,
                prepare_s=round(prep_span.duration, 6),
                lower_s=round(lower_span.duration, 6),
                execute_s=round(exec_span.duration, 6),
                fetch_s=round(fetch_span.duration, 6))
        return result

    @staticmethod
    def _plan_telemetry(program, fetch_names, feed_vals, donate):
        """Record the static memory plan for a freshly-lowered program:
        ``program_plan_seconds`` + predicted peak/accounted gauges
        (docs/OBSERVABILITY.md "Memory plan"). Telemetry-gated and
        failure-isolated — a planning bug must never break lowering."""
        if not _obs._ENABLED:
            return
        import time
        from .analysis.plan import plan_program
        t0 = time.perf_counter()
        try:
            plan = plan_program(
                program, fetch_names=fetch_names,
                feed_shapes={n: tuple(v.shape)
                             for n, v in feed_vals.items()
                             if hasattr(v, 'shape')},
                donate=donate)
        except Exception:
            _obs.inc('program_plan_failures', 1,
                     help='memory-plan attempts that raised (planning is '
                          'best-effort; lowering proceeds)')
            return
        _obs.observe('program_plan_seconds',
                     time.perf_counter() - t0,
                     help='wall time per static memory-plan computation '
                          '(once per program+shape compile-cache miss)')
        _obs.set_gauge('program_peak_hbm_bytes', plan.peak_bytes,
                       help='predicted peak HBM of the last lowered '
                            'program (analysis/plan.py)')
        _obs.set_gauge('program_plan_accounted_bytes',
                       plan.accounted_bytes,
                       help='predicted state+feed+fetch bytes — the '
                            'subset program_measured_hbm_bytes measures')

    @staticmethod
    def _check_fetches_finite(fetch_names, fetches):
        """Count + raise on non-finite fetched values (FLAGS_check_nan_inf).
        The counter increments even when telemetry is disabled-at-env — it
        is a no-op then — so enabling both shows NaN storms as a
        `nonfinite_detections` series instead of a lone traceback."""
        from .debugging import check_numerics
        bad = {}
        for n, f in zip(fetch_names, fetches):
            arr = np.asarray(f)
            if arr.dtype.kind == 'f' and not np.isfinite(arr).all():
                bad[n] = arr
        if bad:
            _obs.inc('nonfinite_detections', len(bad),
                     help='fetched variables containing NaN/Inf '
                          '(FLAGS_check_nan_inf)')
            _obs.instant('nonfinite_detected', variables=','.join(bad))
            check_numerics(bad, 'fetches')

    # ------------------------------------------------------------------
    def snapshot_persistables(self, program=None, scope=None):
        """Zero-copy, non-blocking snapshot of the program's persistable
        state for async checkpointing (paddle_tpu/resilience/): each value
        is wrapped in a :class:`FetchHandle` registered as
        donation-PROTECTED on this executor's inflight window — subsequent
        `run` calls keep those exact buffers out of the donated set (they
        run copy-in/copy-out for that state) until the checkpoint writer
        materializes the handles, at which point donation resumes. The
        step loop therefore never waits on checkpoint D2H.

        Note the protected set changes the donated/kept pytree split, so
        the first run after a snapshot (and the first run after the
        handles drain) each hit their own step-cache entry — two compiled
        variants total, both reused across checkpoints."""
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        handles = {}
        for v in program.list_vars():
            if not v.persistable:
                continue
            val = scope.find(v.name)
            if val is None:
                raise RuntimeError(
                    f"snapshot_persistables: '{v.name}' is uninitialized; "
                    f"run the startup program first")
            handles[v.name] = FetchHandle(val, name=v.name)
        self._window.protect(handles.values())
        return handles

    # ------------------------------------------------------------------
    def _run_from_dataset(self, program, dataset, scope, debug, fetch_list,
                          fetch_info, print_period, fetch_handler):
        if dataset is None:
            raise RuntimeError('dataset is required for *_from_dataset')
        if not dataset.use_vars:
            raise RuntimeError('dataset.set_use_var was never called')
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        fetch_list = fetch_list or []
        fetch_info = fetch_info or [getattr(f, 'name', f) for f in fetch_list]
        monitor = None
        if fetch_handler is not None:
            from .trainer_factory import FetchHandlerMonitor
            monitor = FetchHandlerMonitor(scope, fetch_handler)
            monitor.start()
        try:
            for step, batch in enumerate(dataset._batches()):
                fetches = self.run(program, feed=batch,
                                   fetch_list=fetch_list, scope=scope)
                if (debug or fetch_list) and step % print_period == 0:
                    msg = ', '.join(
                        f'{info}={np.asarray(val).ravel()[:4]}'
                        for info, val in zip(fetch_info, fetches))
                    if msg:
                        _dataset_logger().info('step %d: %s', step, msg)
        finally:
            if monitor is not None:
                monitor.stop()

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        """ref executor.py:train_from_dataset — one pass over a
        fluid.dataset (QueueDataset/InMemoryDataset), running the jitted
        step per batch. `thread` is accepted for parity: host-side parsing
        threads are not the TPU bottleneck (the step is one XLA program)."""
        self._run_from_dataset(program, dataset, scope, debug, fetch_list,
                               fetch_info, print_period, fetch_handler)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        """ref executor.py:infer_from_dataset — same loop; the program
        decides whether backward/update ops exist."""
        self._run_from_dataset(program, dataset, scope, debug, fetch_list,
                               fetch_info, print_period, fetch_handler)

    # ------------------------------------------------------------------
    def lower_to_callable(self, program, feed, fetch_list, scope=None):
        """(program, example feed dict, fetch_list) → (fn, arg_vals): a pure
        jittable fn over the feed arrays with the scope's parameters closed
        over as constants — the export surface for StableHLO (inference.py)."""
        scope = scope if scope is not None else global_scope()
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in fetch_list]
        from .core.lod import LoDTensor
        feed = dict(feed)
        block0 = program.global_block()
        for n in list(feed):
            if isinstance(feed[n], LoDTensor):
                if block0.has_var(n + '@LEN'):
                    feed[n + '@LEN'] = feed[n].lengths
                feed[n] = feed[n].data
        for n in list(feed):
            ln = n + '@LEN'
            if (not n.endswith('@LEN') and ln not in feed
                    and block0.has_var(ln) and block0.var(ln).is_data):
                arr = np.asarray(feed[n])
                if arr.ndim >= 2:
                    feed[ln] = np.full((arr.shape[0],), arr.shape[1],
                                       np.int32)
        feed_names = sorted(feed)
        state_names = sorted(v.name for v in program.list_vars()
                             if v.persistable)
        state = {}
        for n in state_names:
            val = scope.find(n)
            if val is None:
                raise RuntimeError(f"persistable var '{n}' is uninitialized")
            state[n] = jnp.asarray(val)
        step = _lower(program, feed_names, fetch_names, state_names,
                      feed_shapes={n: tuple(np.asarray(feed[n]).shape)
                                   for n in feed_names})
        base_key = default_generator.base_key()

        def fn(*feed_arrays):
            feed_vals = dict(zip(feed_names, feed_arrays))
            # export path: nothing is donated (state is closed over as
            # constants and must stay readable across calls)
            _, fetches = step({}, dict(state), feed_vals, base_key)
            return fetches

        block = program.global_block()
        arg_vals = []
        for n in feed_names:
            dtype = block.var(n).dtype if block.has_var(n) else None
            arg_vals.append(jnp.asarray(feed[n],
                                        to_jax_dtype(dtype) if dtype
                                        else None))
        return fn, arg_vals

    # ------------------------------------------------------------------
    def _run_startup(self, program, scope):
        """Run an init program eagerly (once-per-training cost; not jitted)."""
        self._step_counter += 1
        base_key = jax.random.fold_in(default_generator.base_key(),
                                      self._step_counter)
        env = {}

        def read(name):
            if name in env:
                return env[name]
            v = scope.find(name)
            if v is None:
                raise KeyError(f"startup: uninitialized input '{name}'")
            return v

        for i, op in enumerate(program.global_block().ops):
            _OpRunner.run(op, read, env.__setitem__,
                          jax.random.fold_in(base_key, i)
                          if _op_needs_key(op) else None)
        for v in program.list_vars():
            if v.persistable and v.name in env:
                scope.set(v.name, env[v.name])

    def close(self):
        self._cache.clear()


def scope_has_initialized(program, scope=None):
    scope = scope or global_scope()
    return all(scope.find(v.name) is not None
               for v in program.list_vars() if v.persistable)
