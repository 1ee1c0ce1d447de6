"""Staged-program extension of the memory planner: pipeline stage costs,
schedule-aware peak-HBM, auto stage-cut, and microbatch-count solves.

``plan_program`` (plan.py) prices a Program as ONE device's step. A
pipelined Program is p stages × m microbatches with a *schedule* deciding
how many microbatches' residuals are in flight at once — that residency,
not the kernels, separates GPipe from 1F1B. This module re-derives the
staged view from the same zero-trace walk:

- ``plan_staged_program`` splits the forward at the cut vars and reports
  per-stage FLOPs / bytes / parameter state / activation residuals, then
  charges each stage ``in_flight(schedule, stage)`` microbatches of
  residuals: GPipe holds all ``m`` (every forward runs before any
  backward), 1F1B holds ``min(m, p - stage)`` (warm-up depth — the last
  stage holds one), interleaved holds ``min(m, p)`` (p in flight over
  finer virtual chunks). ``host_peak_bytes`` is the single-program view —
  what the executor's scan lowering actually keeps live on a host where
  all stages share one device — and is the number to compare against
  ``jit(...).compile().memory_analysis()``.
- ``solve_stage_cuts`` is the auto-cut: candidates are the same
  single-output forward boundaries ``select_checkpoints`` uses, and a DP
  picks the p−1 cuts minimizing the max per-stage predicted cost
  (FLOPs + bytes) — balance computed, not hand-tuned.
- ``solve_microbatches`` picks the smallest microbatch count whose
  predicted staged peak fits ``PADDLE_TPU_HBM_BUDGET_MB``, the same way
  ``auto_remat`` consumes the plan. GPipe's peak is flat in m (m × act/m
  is constant — the reason 1F1B exists), so under GPipe the solve returns
  the stage count and reports the shortfall honestly.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ..framework import BACKWARD_OP_TYPE
from .checks import _op_external_reads
from .plan import plan_program

__all__ = ['StagedPlan', 'StageReport', 'plan_staged_program',
           'solve_stage_cuts', 'solve_microbatches', 'schedule_in_flight',
           'stage_cut_candidates', 'wave_size']

# the schedule set (mirrored by partition.pipeline.PP_SCHEDULES — kept
# literal here so analysis stays importable without the partition layer)
SCHEDULES = ('gpipe', '1f1b', 'interleaved')


def wave_size(schedule, num_stages, num_microbatches):
    """Microbatches whose residuals one backward wave keeps in flight on
    the single-program (host/scan) lowering: GPipe backpropagates after
    all m forwards, 1F1B after each one, interleaved after each wave of
    ≤ num_stages (the largest divisor of m, so waves tile the batch)."""
    m = int(num_microbatches)
    if schedule == 'gpipe':
        return m
    if schedule == '1f1b':
        return 1
    if schedule == 'interleaved':
        p = max(1, int(num_stages))
        return max(w for w in range(1, min(p, m) + 1) if m % w == 0)
    raise ValueError(
        f"unknown pipeline schedule {schedule!r} "
        f"(supported: {', '.join(SCHEDULES)})")


def schedule_in_flight(schedule, stage_idx, num_stages, num_microbatches):
    """In-flight microbatches at `stage_idx` in the DISTRIBUTED view (one
    stage per device): GPipe m everywhere; 1F1B p−i at stage i (stage 0
    admits the whole warm-up, the last stage drains immediately);
    interleaved ≤ p in flight across its virtual chunks."""
    m, p = int(num_microbatches), int(num_stages)
    if schedule == 'gpipe':
        return m
    if schedule == '1f1b':
        return min(m, p - int(stage_idx))
    if schedule == 'interleaved':
        return min(m, p)
    raise ValueError(
        f"unknown pipeline schedule {schedule!r} "
        f"(supported: {', '.join(SCHEDULES)})")


class StageReport:
    """One pipeline stage's predicted cost/residency."""

    __slots__ = ('index', 'n_ops', 'flops', 'bytes', 'param_bytes',
                 'act_bytes', 'act_bytes_per_mb', 'in_flight',
                 'peak_bytes')

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, int(kw.get(k, 0)))

    def to_dict(self):
        return {k: getattr(self, k) for k in self.__slots__}


class StagedPlan:
    """Per-stage breakdown + schedule-charged peaks for one cut/m pair."""

    def __init__(self, schedule, num_microbatches, cut_vars, stages,
                 base_plan):
        self.schedule = schedule
        self.num_microbatches = int(num_microbatches)
        self.cut_vars = list(cut_vars)
        self.stages: List[StageReport] = stages
        self.base = base_plan
        m = max(1, self.num_microbatches)
        w = wave_size(schedule, len(stages), m)
        act = base_plan.activation_bytes
        # single-program view: state/feeds/grads unchanged, residuals
        # scale to the in-flight wave (GPipe w=m keeps this the unstaged
        # peak — bit-for-bit the plan_program number)
        self.host_in_flight = w
        self.host_peak_bytes = (base_plan.peak_bytes - act
                                + (act // m) * w)

    @property
    def num_stages(self):
        return len(self.stages)

    @property
    def max_stage_peak_bytes(self):
        return max((s.peak_bytes for s in self.stages), default=0)

    @property
    def max_stage_flops(self):
        return max((s.flops for s in self.stages), default=0)

    @property
    def balance(self):
        """max/mean per-stage cost — 1.0 is a perfectly balanced cut."""
        costs = [s.flops + s.bytes for s in self.stages]
        mean = sum(costs) / max(1, len(costs))
        return (max(costs) / mean) if mean else 1.0

    def to_dict(self):
        return {
            'schedule': self.schedule,
            'num_microbatches': self.num_microbatches,
            'num_stages': self.num_stages,
            'cut_vars': list(self.cut_vars),
            'host_in_flight': self.host_in_flight,
            'host_peak_bytes': self.host_peak_bytes,
            'max_stage_peak_bytes': self.max_stage_peak_bytes,
            'balance': round(self.balance, 4),
            'stages': [s.to_dict() for s in self.stages],
        }

    def format_report(self, budget_bytes=None):
        mib = float(1 << 20)
        lines = [f'# Staged plan: {self.num_stages} stage(s), '
                 f'schedule={self.schedule}, m={self.num_microbatches}']
        verdict = ''
        if budget_bytes:
            fits = self.host_peak_bytes <= budget_bytes
            verdict = (f"  [{'FITS' if fits else 'EXCEEDS'} budget "
                       f"{budget_bytes / mib:.1f} MiB]")
        lines.append(f'host peak (scan lowering): '
                     f'{self.host_peak_bytes / mib:.3f} MiB '
                     f'({self.host_in_flight} microbatch(es) of residuals '
                     f'in flight){verdict}')
        lines.append(f'balance (max/mean stage cost): {self.balance:.3f}')
        lines.append('stage   ops        flops      bytes(MiB)  '
                     'params(MiB)  act/mb(MiB)  in-flight  peak(MiB)')
        for s in self.stages:
            lines.append(
                f'  {s.index:<4}  {s.n_ops:<4} {s.flops:>12,}  '
                f'{s.bytes / mib:>10.3f}  {s.param_bytes / mib:>11.3f}  '
                f'{s.act_bytes_per_mb / mib:>11.3f}  {s.in_flight:>9}  '
                f'{s.peak_bytes / mib:>9.3f}')
        return lines


def _forward_split(program):
    """(ops, fwd_ops, marker) of the global block; marker None when the
    program has no backward."""
    ops = list(program.global_block().ops)
    bwd_idx = next((i for i, op in enumerate(ops)
                    if op.type == BACKWARD_OP_TYPE), None)
    if bwd_idx is None:
        return ops, ops, None
    return ops, ops[:bwd_idx], ops[bwd_idx]


def _stage_bounds(fwd_ops, cut_vars):
    """[(lo, hi)] per stage — the loss tail after the last cut joins the
    final stage for accounting (the executor runs it on the reassembled
    batch either way). Raises naming any cut no forward op produces or
    any out-of-order cut."""
    producer: Dict[str, int] = {}
    for i, op in enumerate(fwd_ops):
        for n in op.output_names():
            producer.setdefault(n, i)
    bounds = []
    for c in cut_vars:
        if c not in producer:
            raise ValueError(
                f'pipeline cut var {c!r} is not produced by any forward '
                f'op — cuts must name forward activations')
        bounds.append(producer[c] + 1)
    if bounds != sorted(bounds) or len(set(bounds)) != len(bounds):
        raise ValueError(
            f'pipeline cut vars {list(cut_vars)!r} are not in forward '
            f'order (producer boundaries {bounds})')
    stages, prev = [], 0
    for b in bounds:
        stages.append((prev, b))
        prev = b
    stages.append((prev, len(fwd_ops)))
    return stages


def plan_staged_program(program, cut_vars, num_microbatches,
                        schedule='gpipe', fetch_names=(), feed_names=(),
                        feed_shapes=None, donate=True, assume_dim=1):
    """Build the :class:`StagedPlan` for `program` split at `cut_vars`.

    Per-stage bytes come straight from the plan's per-op cost walk;
    activation residuals are attributed to the stage whose op produced
    them (the ``out_bytes`` term of the backward model), scaled to one
    microbatch and multiplied by the schedule's in-flight count."""
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown pipeline schedule {schedule!r} "
            f"(supported: {', '.join(SCHEDULES)})")
    m = int(num_microbatches)
    if m <= 0:
        raise ValueError(f'num_microbatches must be > 0, got {m}')
    base = plan_program(program, fetch_names=fetch_names,
                        feed_names=feed_names, feed_shapes=feed_shapes,
                        donate=donate, assume_dim=assume_dim,
                        checkpoints=[])
    if not hasattr(base, '_bwd_model'):
        raise ValueError(
            'plan_staged_program: program has no backward marker — '
            'pipeline schedules stage a training step')
    _, fwd_ops, marker = _forward_split(program)
    bounds = _stage_bounds(fwd_ops, cut_vars)
    out_bytes, _, _, _ = base._bwd_model

    persist = {v.name for v in program.list_vars() if v.persistable}
    blk = program.global_block()
    has_grad = set(marker.attrs.get('params', []))
    cost_by_idx = {i: c for i, _t, c, _s in base.op_costs}

    from .cost import info_nbytes
    from .infer import declared_info

    def var_nbytes(name):
        return (info_nbytes(declared_info(blk.var(name)), assume_dim)
                if blk.has_var(name) else 0)

    stages = []
    p = len(bounds)
    for si, (lo, hi) in enumerate(bounds):
        flops = sum(cost_by_idx[i].flops for i in range(lo, hi)
                    if i in cost_by_idx)
        nbytes = sum(cost_by_idx[i].bytes for i in range(lo, hi)
                     if i in cost_by_idx)
        sparams = []
        for op in fwd_ops[lo:hi]:
            for n in op.input_names():
                if n in persist and n not in sparams:
                    sparams.append(n)
        param_bytes = sum(var_nbytes(n) for n in sparams)
        # stage state = params (1×) + their gradient buffers (grads
        # mirror their parameter's shape — plan.py's backward model)
        param_bytes += sum(var_nbytes(n) for n in sparams
                           if n in has_grad)
        act = sum(out_bytes[lo:hi])
        act_mb = act // m
        in_flight = schedule_in_flight(schedule, si, p, m)
        stages.append(StageReport(
            index=si, n_ops=hi - lo, flops=flops, bytes=nbytes,
            param_bytes=param_bytes, act_bytes=act,
            act_bytes_per_mb=act_mb, in_flight=in_flight,
            peak_bytes=param_bytes + in_flight * act_mb))
    return StagedPlan(schedule, m, cut_vars, stages, base)


def stage_cut_candidates(program, fetch_names=(), feed_names=(),
                         feed_shapes=None, assume_dim=1):
    """Every cuttable forward boundary, in program order: the names of
    single-non-persistable-output activations later ops read — the same
    candidate set ``solve_stage_cuts`` optimizes over, exposed so manual
    cuts can be enumerated against the auto-cut
    (tests/framework/test_pp_schedules.py)."""
    base = plan_program(program, fetch_names=fetch_names,
                        feed_names=feed_names, feed_shapes=feed_shapes,
                        assume_dim=assume_dim, checkpoints=[])
    if not hasattr(base, '_bwd_model'):
        raise ValueError(
            'stage_cut_candidates: program has no backward marker')
    _, fwd_ops, _ = _forward_split(program)
    _, _, _, last = base._bwd_model
    persist = {v.name for v in program.list_vars() if v.persistable}
    out = []
    for i, op in enumerate(fwd_ops):
        outs = [v for v in op.output_names() if v not in persist]
        if len(outs) == 1 and last.get(outs[0], i) > i:
            out.append(outs[0])
    return out


def solve_stage_cuts(program, num_stages, fetch_names=(), feed_names=(),
                     feed_shapes=None, assume_dim=1):
    """Auto-cut: pick num_stages−1 cut vars balancing predicted per-stage
    cost (FLOPs + bytes). Returns ``(cut_var_names, report)`` where the
    report carries the per-stage costs of the chosen cut.

    Candidates are forward ops with exactly ONE non-persistable output
    that later ops read — the boundaries the executor can split at (the
    same candidate set as auto-remat, so every solvable cut is also a
    lowerable one). A DP over those boundaries minimizes the maximum
    stage cost; with fewer candidates than stages it raises rather than
    return a degenerate cut."""
    p = int(num_stages)
    if p < 2:
        raise ValueError(f'num_stages must be >= 2, got {num_stages}')
    base = plan_program(program, fetch_names=fetch_names,
                        feed_names=feed_names, feed_shapes=feed_shapes,
                        assume_dim=assume_dim, checkpoints=[])
    if not hasattr(base, '_bwd_model'):
        raise ValueError(
            'solve_stage_cuts: program has no backward marker')
    _, fwd_ops, _ = _forward_split(program)
    _, _, _, last = base._bwd_model
    persist = {v.name for v in program.list_vars() if v.persistable}
    cost_by_idx = {i: c for i, _t, c, _s in base.op_costs}
    n = len(fwd_ops)
    op_cost = [cost_by_idx[i].flops + cost_by_idx[i].bytes
               if i in cost_by_idx else 0 for i in range(n)]
    prefix = [0] * (n + 1)
    for i in range(n):
        prefix[i + 1] = prefix[i] + op_cost[i]

    # boundary b (split before op b) ← single-output op b-1 read later
    boundary_var = {}
    for i, op in enumerate(fwd_ops):
        outs = [v for v in op.output_names() if v not in persist]
        if len(outs) != 1:
            continue
        if last.get(outs[0], i) > i:
            boundary_var[i + 1] = outs[0]
    cands = sorted(boundary_var)
    if len(cands) < p - 1:
        raise ValueError(
            f'solve_stage_cuts: only {len(cands)} cuttable boundaries for '
            f'{p} stages — the forward has too few single-output '
            f'activations to cut')

    def seg(a, b):
        return prefix[b] - prefix[a]

    # dp[k][j]: min over first k segments ending at boundary cands[j] of
    # the max segment cost; reconstruct via choice[]
    INF = float('inf')
    ncand = len(cands)
    dp = [[INF] * ncand for _ in range(p - 1)]
    choice = [[-1] * ncand for _ in range(p - 1)]
    for j, b in enumerate(cands):
        dp[0][j] = seg(0, b)
    for k in range(1, p - 1):
        for j, b in enumerate(cands):
            for jp in range(j):
                prev = dp[k - 1][jp]
                if prev == INF:
                    continue
                cur = max(prev, seg(cands[jp], b))
                if cur < dp[k][j]:
                    dp[k][j] = cur
                    choice[k][j] = jp
    best, best_j = INF, -1
    for j, b in enumerate(cands):
        if dp[p - 2][j] == INF:
            continue
        total = max(dp[p - 2][j], seg(b, n))
        if total < best:
            best, best_j = total, j
    if best_j < 0:
        raise ValueError('solve_stage_cuts: no feasible cut found')
    picks, k, j = [], p - 2, best_j
    while k >= 0:
        picks.append(cands[j])
        j = choice[k][j]
        k -= 1
    picks.reverse()
    cuts = [boundary_var[b] for b in picks]
    seg_costs = []
    prev = 0
    for b in picks + [n]:
        seg_costs.append(seg(prev, b))
        prev = b
    mean = sum(seg_costs) / len(seg_costs)
    return cuts, {
        'cut_vars': cuts,
        'num_stages': p,
        'stage_costs': seg_costs,
        'max_stage_cost': max(seg_costs),
        'balance': (max(seg_costs) / mean) if mean else 1.0,
        'candidates': len(cands),
    }


def solve_microbatches(program, cut_vars, schedule, budget_bytes,
                       fetch_names=(), feed_names=(), feed_shapes=None,
                       assume_dim=1, max_microbatches=64):
    """Smallest microbatch count whose predicted staged host peak fits
    `budget_bytes` (the ``PADDLE_TPU_HBM_BUDGET_MB`` consumption path).
    Returns ``(m, predicted_peak_bytes, fits)``.

    More microbatches shrink 1F1B/interleaved residency (w × act/m) but
    leave GPipe flat (m × act/m) — under GPipe the solve returns the
    stage count (the schedule's natural minimum) with ``fits`` reporting
    whether even that is under budget. Candidates are capped at
    `max_microbatches`; runtime batch divisibility is enforced by the
    executor, not here."""
    nstages = len(cut_vars) + 1
    if schedule == 'gpipe':
        plan = plan_staged_program(program, cut_vars, nstages, schedule,
                                   fetch_names=fetch_names,
                                   feed_names=feed_names,
                                   feed_shapes=feed_shapes,
                                   assume_dim=assume_dim)
        return nstages, plan.host_peak_bytes, \
            plan.host_peak_bytes <= budget_bytes
    best_m, best_peak = None, None
    m = nstages
    while m <= max_microbatches:
        plan = plan_staged_program(program, cut_vars, m, schedule,
                                   fetch_names=fetch_names,
                                   feed_names=feed_names,
                                   feed_shapes=feed_shapes,
                                   assume_dim=assume_dim)
        peak = plan.host_peak_bytes
        if best_peak is None or peak < best_peak:
            best_m, best_peak = m, peak
        if peak <= budget_bytes:
            return m, peak, True
        m *= 2
    return best_m, best_peak, False
