"""Whole-Program liveness walk → peak-HBM memory plan. Zero tracing.

The executor lowers a Program into one jitted step whose live HBM is
state + feeds + activations-held-for-backward + gradients; before this
module, that peak was discovered by OOM. ``plan_program`` re-derives it
in milliseconds from the VarInfo lattice (infer.py) and the cost model
(cost.py), mirroring the executor's actual lowering:

- **state** (persistables): resident for the whole step. Donated buffers
  (params/slots XLA updates in place — executor.py donation split) count
  1×; kept-but-written buffers (fetch-aliased, or ``donate=False``) run
  copy-in/copy-out and count 2×.
- **feeds**: live from step start to their last reader.
- **activations**: live from producer to last reader. With a backward
  marker, forward intermediates are *residuals*: ``jax.value_and_grad``
  holds them until the backward consumes them — without checkpoints,
  every forward output is stored into the backward; with checkpoints
  (``RecomputeOptimizer`` / the ``auto_remat`` pass), only each segment
  boundary's live-set is stored and the backward re-materializes one
  segment at a time (``executor._remat_segments`` semantics), so the
  activation term becomes Σ boundary-carried bytes + the largest single
  segment's internal bytes (the recompute transient).
- **gradients**: one buffer per diff target, live from the backward
  until the update ops consume them.
- **backward FLOPs**: 2× the forward's (the standard fwd:bwd ratio);
  checkpointing adds one extra forward pass of the checkpointed span.

``select_checkpoints`` is the auto-remat planner: candidate boundaries
are single-output forward ops; the greedy picks the boundary that
minimizes predicted peak (ties → the narrowest live-set waist) until the
budget fits. Recompute cost is one extra forward pass regardless of
boundary count, so selection is bytes-first by construction —
"cheap-to-recompute" falls out of narrow waists having low
FLOPs-per-byte-saved.

Dynamic dims: UNKNOWN dims substitute ``assume_dim`` unless
``feed_shapes`` pins the real feed signature (the executor's plan hook
passes the actual shapes, making the plan exact for static programs).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Set

from ..framework import BACKWARD_OP_TYPE
from . import infer
from .checks import _op_external_reads
from .cost import (OpCost, dtype_nbytes, has_cost_rule, info_nbytes,
                   op_flops)
from .infer import UNKNOWN, VarInfo, declared_info, infer_op, seed_env

__all__ = ['MemoryPlan', 'plan_program', 'select_checkpoints',
           'gradient_bytes']


class Resident:
    """One var's residency contribution at the plan's peak."""

    __slots__ = ('name', 'nbytes', 'kind')

    def __init__(self, name, nbytes, kind):
        self.name = name
        self.nbytes = int(nbytes)
        self.kind = kind

    def __repr__(self):
        return f'Resident({self.name!r}, {self.nbytes}, {self.kind!r})'


def _mib(b):
    return b / float(1 << 20)


class MemoryPlan:
    """The planner's output: peak HBM, residency breakdown, per-op costs,
    and the backward/remat byte model. All byte figures use runtime
    widths (cost.dtype_nbytes); ``accounted_bytes`` is the
    state+feed+fetch subset the executor's measured counterpart
    (``program_measured_hbm_bytes``) reports."""

    def __init__(self):
        self.peak_bytes = 0
        self.peak_index = 0            # op index (bwd marker = the phase)
        self.peak_phase = ''           # 'forward' | 'backward' | 'op'
        self.state_bytes = 0           # Σ persistable bytes (1× each)
        self.donated_bytes = 0
        self.kept_written_bytes = 0    # kept AND written → 2× transient
        self.donation_saved_bytes = 0
        self.feed_bytes = 0
        self.fetch_bytes = 0
        self.grad_bytes = 0
        self.activation_bytes = 0      # stored into the backward
        self.transient_bytes = 0       # largest remat segment's internals
        self.fwd_flops = 0
        self.total_flops = 0
        self.checkpoints: List[str] = []
        self.op_costs: List[tuple] = []     # (idx, op_type, OpCost, site)
        self.timeline: List[tuple] = []     # (idx, op_type, live_bytes)
        self.residents: List[Resident] = []  # live set at the peak
        self.uncosted_ops: List[str] = []   # op types without a cost rule
        self.n_ops = 0
        self.plan_seconds = 0.0

    @property
    def accounted_bytes(self):
        """state + feeds + fetches — the subset with a measured runtime
        counterpart (executor fetch/feed/state byte accounting)."""
        return self.state_bytes + self.feed_bytes + self.fetch_bytes

    def top_residents(self, n=10):
        return sorted(self.residents, key=lambda r: -r.nbytes)[:n]

    def top_op_costs(self, n=10):
        return sorted(self.op_costs, key=lambda t: -t[2].flops)[:n]

    def to_dict(self, top=10):
        return {
            'peak_hbm_bytes': self.peak_bytes,
            'peak_hbm_mib': round(_mib(self.peak_bytes), 3),
            'peak_phase': self.peak_phase,
            'accounted_bytes': self.accounted_bytes,
            'state_bytes': self.state_bytes,
            'donated_bytes': self.donated_bytes,
            'donation_saved_bytes': self.donation_saved_bytes,
            'feed_bytes': self.feed_bytes,
            'fetch_bytes': self.fetch_bytes,
            'grad_bytes': self.grad_bytes,
            'activation_bytes': self.activation_bytes,
            'transient_bytes': self.transient_bytes,
            'fwd_flops': self.fwd_flops,
            'total_flops': self.total_flops,
            'checkpoints': list(self.checkpoints),
            'n_ops': self.n_ops,
            'plan_seconds': round(self.plan_seconds, 6),
            'top_residents': [
                {'name': r.name, 'bytes': r.nbytes, 'kind': r.kind}
                for r in self.top_residents(top)],
            'top_op_costs': [
                {'index': i, 'op': t, 'flops': c.flops, 'bytes': c.bytes,
                 'site': s}
                for i, t, c, s in self.top_op_costs(top)],
            'uncosted_ops': sorted(set(self.uncosted_ops)),
        }

    def format_report(self, top=10, budget_bytes=None):
        """Human-readable report lines (plan_program.py / lint --plan)."""
        lines = ['# Memory plan', '']
        verdict = ''
        if budget_bytes:
            fits = self.peak_bytes <= budget_bytes
            verdict = (f"  [{'FITS' if fits else 'EXCEEDS'} budget "
                       f"{_mib(budget_bytes):.1f} MiB]")
        lines.append(f"predicted peak HBM:  {_mib(self.peak_bytes):.3f} MiB "
                     f"(at {self.peak_phase}){verdict}")
        lines.append(f"state:               {_mib(self.state_bytes):.3f} MiB "
                     f"({_mib(self.donated_bytes):.3f} donated in-place, "
                     f"{_mib(self.donation_saved_bytes):.3f} saved vs "
                     f"copy-in/copy-out)")
        lines.append(f"feeds / fetches:     {_mib(self.feed_bytes):.3f} / "
                     f"{_mib(self.fetch_bytes):.3f} MiB")
        if self.grad_bytes:
            lines.append(f"gradients:           "
                         f"{_mib(self.grad_bytes):.3f} MiB")
            lines.append(f"activations->bwd:    "
                         f"{_mib(self.activation_bytes):.3f} MiB stored"
                         + (f" + {_mib(self.transient_bytes):.3f} MiB "
                            f"recompute transient "
                            f"({len(self.checkpoints)} checkpoint(s))"
                            if self.checkpoints else ' (no remat)'))
        lines.append(f"forward FLOPs:       {self.fwd_flops:,} "
                     f"(total {self.total_flops:,})")
        lines.append('')
        lines.append(f'## Top residents at peak (of {len(self.residents)})')
        for r in self.top_residents(top):
            lines.append(f"  {_mib(r.nbytes):>10.3f} MiB  {r.kind:<10} "
                         f"{r.name}")
        lines.append('')
        lines.append(f'## Top ops by FLOPs (of {self.n_ops})')
        for i, t, c, site in self.top_op_costs(top):
            lines.append(f"  {c.flops:>14,} flops  {_mib(c.bytes):>9.3f} "
                         f"MiB  #{i:<4} {t}"
                         + (f"  ({site})" if site else ''))
        if self.uncosted_ops:
            lines.append('')
            lines.append(f"(bytes-only coverage — no cost rule: "
                         f"{', '.join(sorted(set(self.uncosted_ops)))})")
        return lines


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------

def _last_reads(program, ops, fetch_set):
    """var name → last op index that reads it (external reads incl.
    sub-blocks); fetched names read at the very end."""
    last: Dict[str, int] = {}
    for idx, op in enumerate(ops):
        for n in _op_external_reads(op, program):
            last[n] = idx
        # backward marker reads loss/params/checkpoints by name
        for attr in ('loss', 'params', 'checkpoints'):
            v = op.attrs.get(attr)
            names = [v] if isinstance(v, str) else \
                list(v) if isinstance(v, (list, tuple)) else []
            for n in names:
                if isinstance(n, str):
                    last[n] = idx
    for n in fetch_set:
        last[n] = len(ops)
    return last


def plan_program(program, fetch_names=(), feed_names=(), feed_shapes=None,
                 donate=True, assume_dim=1, checkpoints=None):
    """Build the :class:`MemoryPlan` for `program`'s global block.

    `feed_shapes` (name → concrete shape) pins dynamic dims to the real
    feed signature; remaining UNKNOWN dims substitute `assume_dim`.
    `checkpoints` overrides the backward marker's checkpoint list (the
    auto-remat selector evaluates candidate sets through this)."""
    t0 = time.perf_counter()
    plan = MemoryPlan()
    blk = program.global_block()
    ops = list(blk.ops)
    plan.n_ops = len(ops)
    fetch_set = set(fetch_names)
    persist = {v.name for v in program.list_vars() if v.persistable}
    data_vars = {v.name for v in program.list_vars() if v.is_data}
    feed_set = (set(feed_names) | data_vars) - persist

    # --- infer walk: concrete-as-possible VarInfos + per-op costs ---
    env = seed_env(program)
    if feed_shapes:
        for n, shp in feed_shapes.items():
            base = env.get(n) or (declared_info(blk.var(n))
                                  if blk.has_var(n) else VarInfo())
            env[n] = VarInfo(tuple(shp), base.dtype, base.lod_level)

    bwd_idx = next((i for i, op in enumerate(ops)
                    if op.type == BACKWARD_OP_TYPE), None)
    marker = ops[bwd_idx] if bwd_idx is not None else None

    var_bytes: Dict[str, int] = {}       # resolved at binding time

    def nbytes_of(name):
        if name in var_bytes:
            return var_bytes[name]
        info = env.get(name)
        if info is None and blk.has_var(name):
            info = declared_info(blk.var(name))
        b = info_nbytes(info, assume_dim)
        var_bytes[name] = b
        return b

    for idx, op in enumerate(ops):
        if op.type == BACKWARD_OP_TYPE:
            # grads mirror their params
            for p, g in zip(op.attrs.get('params', []),
                            op.outputs.get('Grads', [])):
                if blk.has_var(p):
                    pi = env.get(p) or declared_info(blk.var(p))
                    env[g] = VarInfo(pi.shape, pi.dtype)
            # sparse tables emit padded-COO pairs (docs/SPARSE.md); K is
            # the runtime bucket rung — UNKNOWN prices at assume_dim
            for p, r, v in zip(op.attrs.get('sparse_params', []),
                               op.outputs.get('SparseRows', []),
                               op.outputs.get('SparseVals', [])):
                pi = (env.get(p) or declared_info(blk.var(p))
                      if blk.has_var(p) else VarInfo())
                dim = (pi.shape[1] if pi.shape is not None
                       and len(pi.shape) == 2 else UNKNOWN)
                env[r] = VarInfo((UNKNOWN,), 'int32')
                env[v] = VarInfo((UNKNOWN, dim), pi.dtype)
            plan.op_costs.append((idx, op.type, OpCost(), None))
            continue
        try:
            result = infer_op(op, env, blk)
        except infer.InferError:
            result = None
        if result is None:
            for n in op.output_names():
                env[n] = (declared_info(blk.var(n)) if blk.has_var(n)
                          else VarInfo())
        else:
            from ..ops.registry import get_op, has_op
            slots = (get_op(op.type).output_slots if has_op(op.type)
                     else list(op.outputs))
            for slot in slots:
                names = op.outputs.get(slot, [])
                if not names:
                    continue
                res = result.get(slot)
                infos = (list(res) if isinstance(res, (list, tuple))
                         else [res] * len(names))
                for n, info in zip(names, infos):
                    env[n] = info if info is not None else VarInfo()
            # any output slot the rule didn't mention keeps its declaration
            for n in op.output_names():
                if n not in env:
                    env[n] = (declared_info(blk.var(n)) if blk.has_var(n)
                              else VarInfo())
        c = OpCost(op_flops(op, env, blk, assume_dim),
                   sum(nbytes_of(n) for n in op.input_names()),
                   sum(nbytes_of(n) for n in op.output_names()))
        if not has_cost_rule(op.type):
            plan.uncosted_ops.append(op.type)
        plan.op_costs.append((idx, op.type, c,
                              getattr(op, '_site', None)))

    # --- byte categories ---
    state_written = set()
    for op in ops:
        state_written |= set(op.output_names()) & persist
    plan.state_bytes = sum(nbytes_of(n) for n in sorted(persist))
    for n in sorted(persist):
        kept = (not donate) or n in fetch_set
        if kept and n in state_written:
            plan.kept_written_bytes += nbytes_of(n)
        elif n in state_written:
            plan.donated_bytes += nbytes_of(n)
    plan.donation_saved_bytes = plan.donated_bytes
    plan.feed_bytes = sum(nbytes_of(n) for n in sorted(feed_set))
    plan.fetch_bytes = sum(nbytes_of(n) for n in sorted(fetch_set))

    last = _last_reads(program, ops, fetch_set)

    # --- forward/backward activation model ---
    fwd_flops = sum(c.flops for i, _, c, _ in plan.op_costs
                    if bwd_idx is None or i < bwd_idx)
    plan.fwd_flops = fwd_flops
    plan.total_flops = sum(c.flops for _, _, c, _ in plan.op_costs)

    eff_checkpoints = list(checkpoints) if checkpoints is not None else \
        list((marker.attrs.get('checkpoints') or []) if marker else [])
    plan.checkpoints = eff_checkpoints

    base = (plan.state_bytes + plan.kept_written_bytes)

    if marker is not None:
        plan.total_flops += 2 * fwd_flops        # bwd ≈ 2× fwd
        if eff_checkpoints:
            plan.total_flops += fwd_flops        # remat = one extra fwd
        fwd_ops = ops[:bwd_idx]
        plan.grad_bytes = sum(nbytes_of(g)
                              for g in marker.outputs.get('Grads', []))
        produced_at = {}
        for i, op in enumerate(fwd_ops):
            for n in op.output_names():
                if n not in persist and n not in produced_at:
                    produced_at[n] = i
        out_bytes = [0] * len(fwd_ops)
        for n, i in produced_at.items():
            out_bytes[i] += nbytes_of(n)
        # carried[b]: bytes of fwd-produced vars live across boundary b
        # (produced < b, still read at >= b — incl. the backward tail)
        carried = [0] * (len(fwd_ops) + 1)
        for n, i in produced_at.items():
            end = min(last.get(n, i), len(fwd_ops))
            lo, hi = i + 1, end            # live across boundaries lo..hi
            if hi >= lo:
                carried[lo] += nbytes_of(n)
                if hi + 1 <= len(fwd_ops):
                    carried[hi + 1] -= nbytes_of(n)
        for b in range(1, len(fwd_ops) + 1):
            carried[b] += carried[b - 1]

        def bwd_terms(bounds):
            """(stored, transient) for sorted segment boundaries."""
            if not bounds:
                return sum(out_bytes), 0
            stored = sum(carried[b] for b in bounds)
            transient, prev = 0, 0
            for b in list(bounds) + [len(fwd_ops)]:
                transient = max(transient, sum(out_bytes[prev:b]))
                prev = b
            # the final segment's outputs feed the loss/backward directly
            return stored + carried[len(fwd_ops)], transient

        bounds = sorted({produced_at[c] + 1 for c in eff_checkpoints
                         if c in produced_at})
        stored, transient = bwd_terms(bounds)
        plan.activation_bytes = stored
        plan.transient_bytes = transient
        plan._bwd_model = (out_bytes, carried, produced_at, last)

    # --- timeline + peak (incremental: O(ops + vars), not O(ops²)) ---
    live: Set[str] = set()
    live_bytes = 0
    expired: Dict[int, List[str]] = {}
    feed_expire: Dict[int, List[str]] = {}
    feed_live_bytes = 0
    for n in feed_set:
        e = last.get(n, -1)
        if e >= 0:
            feed_live_bytes += nbytes_of(n)
            feed_expire.setdefault(e, []).append(n)
    peak, peak_idx, peak_live = base, 0, set()
    for idx, op in enumerate(ops):
        if marker is not None and idx == bwd_idx:
            # the backward phase: residuals + grads + recompute transient
            cur = (base + feed_live_bytes + plan.activation_bytes
                   + plan.transient_bytes + plan.grad_bytes)
            if cur > peak:
                peak, peak_idx, peak_live = cur, idx, None
            plan.timeline.append((idx, op.type, cur))
            # after the backward: grads live until their tail readers
            for g in marker.outputs.get('Grads', []):
                if g not in live:
                    live.add(g)
                    live_bytes += nbytes_of(g)
                    expired.setdefault(last.get(g, idx), []).append(g)
        else:
            for n in op.output_names():
                if n not in persist and n not in live:
                    live.add(n)
                    live_bytes += nbytes_of(n)
                    expired.setdefault(last.get(n, idx), []).append(n)
            cur = base + live_bytes + feed_live_bytes
            if cur > peak:
                peak, peak_idx, peak_live = cur, idx, set(live)
            plan.timeline.append((idx, op.type, cur))
        for n in expired.pop(idx, ()):
            if n in live:
                live.discard(n)
                live_bytes -= nbytes_of(n)
        for n in feed_expire.pop(idx, ()):
            feed_live_bytes -= nbytes_of(n)

    plan.peak_bytes = peak
    plan.peak_index = peak_idx
    if marker is not None and peak_idx == bwd_idx:
        plan.peak_phase = 'backward'
    else:
        plan.peak_phase = (f'op #{peak_idx} '
                           f'{ops[peak_idx].type}' if ops else 'empty')

    # --- residents at peak ---
    res = []
    for n in sorted(persist):
        kind = 'state-kept' if ((not donate) or n in fetch_set) \
            else 'state'
        res.append(Resident(n, nbytes_of(n), kind))
    for n in sorted(feed_set):
        if last.get(n, -1) >= peak_idx:
            res.append(Resident(n, nbytes_of(n), 'feed'))
    if marker is not None and peak_idx == bwd_idx:
        fwd_ops = ops[:bwd_idx]
        stored_names = _stored_names(plan, fwd_ops, persist)
        for n in sorted(stored_names):
            res.append(Resident(n, nbytes_of(n), 'activation'))
        for g in marker.outputs.get('Grads', []):
            res.append(Resident(g, nbytes_of(g), 'gradient'))
    elif peak_live:
        for n in sorted(peak_live):
            res.append(Resident(n, nbytes_of(n), 'activation'))
    plan.residents = [r for r in res if r.nbytes > 0]
    plan.plan_seconds = time.perf_counter() - t0
    return plan


def _stored_names(plan, fwd_ops, persist):
    """Names the backward holds as residuals under the plan's checkpoint
    set (for the residents report)."""
    produced = [n for op in fwd_ops for n in op.output_names()
                if n not in persist]
    if not plan.checkpoints:
        return set(produced)
    # stored = boundary-carried vars; approximate with vars live across
    # any boundary (exact bytes already computed in activation_bytes)
    _, _, produced_at, last = plan._bwd_model
    bounds = sorted({produced_at[c] + 1 for c in plan.checkpoints
                     if c in produced_at})
    stored = set()
    for n, i in produced_at.items():
        end = min(last.get(n, i), len(fwd_ops))
        if any(i + 1 <= b <= end for b in bounds) or end >= len(fwd_ops):
            stored.add(n)
    return stored


def gradient_bytes(program, assume_dim=1):
    """Σ bytes of the backward marker's gradient outputs (runtime widths)
    — what `PADDLE_TPU_ALLREDUCE_BUCKET_MB=auto` sizes buckets from.
    0 for inference programs."""
    blk = program.global_block()
    marker = next((op for op in blk.ops if op.type == BACKWARD_OP_TYPE),
                  None)
    if marker is None:
        return 0
    total = 0
    for p in marker.attrs.get('params', []):
        if blk.has_var(p):
            total += info_nbytes(declared_info(blk.var(p)), assume_dim)
    return total


# ---------------------------------------------------------------------------
# auto-remat checkpoint selection
# ---------------------------------------------------------------------------

def select_checkpoints(program, budget_bytes, fetch_names=(),
                       feed_names=(), feed_shapes=None, donate=True,
                       assume_dim=1, max_checkpoints=16):
    """Greedy checkpoint selection from the plan: returns
    ``(checkpoint_names, predicted_peak_bytes)``. Empty list when the
    program already fits, has no backward, or no boundary helps.

    Candidates are forward ops with exactly one non-persistable output
    that later ops read — the boundaries ``executor._remat_segments``
    can split at. Each greedy round evaluates every remaining boundary
    against the closed-form backward model (Σ carried + max segment
    internal) and adds the one minimizing predicted peak; ties prefer
    the narrowest live-set waist. Recompute cost is one extra forward
    pass total, independent of how many boundaries are chosen, so the
    selection is bytes-first — exactly the low-FLOPs-per-byte-saved
    policy documented in docs/ANALYSIS.md."""
    no_remat = plan_program(program, fetch_names=fetch_names,
                            feed_names=feed_names, feed_shapes=feed_shapes,
                            donate=donate, assume_dim=assume_dim,
                            checkpoints=[])
    if no_remat.grad_bytes == 0 or not hasattr(no_remat, '_bwd_model'):
        return [], no_remat.peak_bytes
    if no_remat.peak_bytes <= budget_bytes:
        return [], no_remat.peak_bytes

    out_bytes, carried, produced_at, last = no_remat._bwd_model
    n_fwd = len(out_bytes)
    blk = program.global_block()
    persist = {v.name for v in program.list_vars() if v.persistable}
    # boundary b → checkpoint var name (single output of op b-1)
    boundary_var = {}
    for i, op in enumerate(blk.ops[:n_fwd]):
        outs = [n for n in op.output_names() if n not in persist]
        if len(outs) != 1:
            continue
        n = outs[0]
        if last.get(n, i) > i:                 # somebody reads it later
            boundary_var[i + 1] = n

    base_non_act = no_remat.peak_bytes - no_remat.activation_bytes \
        - no_remat.transient_bytes

    def peak_for(bounds):
        if not bounds:
            return no_remat.peak_bytes
        stored = sum(carried[b] for b in bounds) + carried[n_fwd]
        transient, prev = 0, 0
        for b in sorted(bounds) + [n_fwd]:
            transient = max(transient, sum(out_bytes[prev:b]))
            prev = b
        return base_non_act + stored + transient

    chosen: List[int] = []
    cur_peak = no_remat.peak_bytes
    while cur_peak > budget_bytes and len(chosen) < max_checkpoints:
        best = None
        for b, name in boundary_var.items():
            if b in chosen:
                continue
            p = peak_for(chosen + [b])
            key = (p, carried[b])
            if best is None or key < best[0]:
                best = (key, b)
        if best is None or best[0][0] >= cur_peak:
            break                              # no boundary helps further
        chosen.append(best[1])
        cur_peak = best[0][0]

    if not chosen:
        return [], no_remat.peak_bytes
    names = [boundary_var[b] for b in sorted(chosen)]
    return names, cur_peak
