"""Static Program verifier: shape/dtype inference + dataflow diagnostics.

Runs BEFORE lowering and BETWEEN IR passes, with zero tracing — a
malformed program fails here with the op and its Python construction
site, not three layers later inside an XLA trace error.

Layers:

- :mod:`infer` — per-op ``VarInfo(shape, dtype, lod_level)`` inference
  (``infer_rule`` registry, UNKNOWN-dim lattice);
- :mod:`checks` — the diagnostic suite (read-before-write, dead code,
  shape/dtype mismatch, collective consistency, donation hazards, RNG
  salt lint);
- :mod:`cost` / :mod:`plan` — the per-op FLOP/byte cost model
  (``cost_rule`` registry, same coverage contract) and the
  whole-Program peak-HBM planner feeding ``tools/plan_program.py``,
  the ``auto_remat`` IR pass (``PADDLE_TPU_HBM_BUDGET_MB``), and
  ``PADDLE_TPU_ALLREDUCE_BUCKET_MB=auto`` (docs/ANALYSIS.md "Cost
  model & memory planner");
- :func:`verify_program` — one call returning the diagnostics;
- :func:`assert_verified` — raise :class:`ProgramVerificationError` on
  error-severity findings.

``PADDLE_TPU_VERIFY`` ∈ {``off``, ``passes``, ``full``} (default
``off``):

- ``off``    — nothing runs, construction-site capture disabled;
- ``passes`` — every IR pass output is re-verified at the pass boundary
  (ir/pass_base.PassManager); a pass emitting an inconsistent program
  raises naming the pass;
- ``full``   — ``passes`` plus an Executor pre-lowering validation of
  the user program on every compile-cache miss.

All verification is program-BUILD-time work (it runs on compile-cache
misses, never per step), recorded as ``program_verify_seconds`` beside
``executor_compile_seconds``. ``tools/lint_program.py`` runs the same
checks from the command line over saved inference models or recipe
builders.
"""
from __future__ import annotations

import os

from .diagnostics import (Diagnostic, ProgramVerificationError,  # noqa: F401
                          SEVERITIES, format_report, max_severity,
                          severity_at_least)
from .infer import (UNKNOWN, VarInfo, InferError, infer_rule,  # noqa: F401
                    has_rule, all_rules)
from .cost import (OpCost, cost_rule, has_cost_rule,  # noqa: F401
                   all_cost_rules, op_cost)
from .plan import (MemoryPlan, plan_program,  # noqa: F401
                   select_checkpoints, gradient_bytes)
from .checks import run_checks

__all__ = ['Diagnostic', 'ProgramVerificationError', 'SEVERITIES',
           'VarInfo', 'UNKNOWN', 'InferError', 'infer_rule', 'has_rule',
           'all_rules', 'verify_program', 'assert_verified', 'verify_level',
           'format_report', 'max_severity', 'severity_at_least',
           'VERIFY_ENV', 'VERIFY_LEVELS',
           'OpCost', 'cost_rule', 'has_cost_rule', 'all_cost_rules',
           'op_cost', 'MemoryPlan', 'plan_program', 'select_checkpoints',
           'gradient_bytes']

VERIFY_ENV = 'PADDLE_TPU_VERIFY'
VERIFY_LEVELS = ('off', 'passes', 'full')


def verify_level() -> str:
    """Current ``PADDLE_TPU_VERIFY`` level; unknown values raise listing
    the choices (strict parse, same contract as the other env knobs)."""
    raw = os.environ.get(VERIFY_ENV)
    if raw is None or raw == '':
        return 'off'
    lvl = raw.strip().lower()
    if lvl not in VERIFY_LEVELS:
        raise ValueError(
            f'{VERIFY_ENV}={raw!r} invalid; expected one of '
            f'{list(VERIFY_LEVELS)}')
    return lvl


def capture_sites() -> bool:
    """Whether framework.Operator records construction sites (off at
    level 'off' — the per-op stack walk is program-build-time-cheap but
    not free)."""
    return verify_level() != 'off'


def verify_program(program, fetch_names=(), feed_names=(), stage='pre'):
    """Statically verify `program`; returns the list of Diagnostics
    (never raises on findings — see :func:`assert_verified`)."""
    return run_checks(program, fetch_names=fetch_names,
                      feed_names=feed_names, stage=stage)


def assert_verified(program, fetch_names=(), feed_names=(), stage='pre',
                    pass_name=None, baseline=None):
    """Verify and RAISE :class:`ProgramVerificationError` on
    error-severity diagnostics. With `baseline` (a set of Diagnostic
    keys), only NEW errors raise — the pass post-condition contract: a
    pass must not introduce inconsistencies, but is not blamed for ones
    already present in its input. Returns the full diagnostic list."""
    diags = verify_program(program, fetch_names=fetch_names,
                           feed_names=feed_names, stage=stage)
    errors = severity_at_least(diags, 'error')
    if baseline is not None:
        errors = [d for d in errors if d.key() not in baseline]
    if errors:
        raise ProgramVerificationError(errors, stage=stage,
                                       pass_name=pass_name)
    return diags
