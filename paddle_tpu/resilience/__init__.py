"""Production resilience: async checkpoints, deterministic resume,
preemption handling, fault injection, goodput accounting.

The design constraints (ROADMAP item 5, docs/RESILIENCE.md):

1. **Checkpointing must not stall the step loop.** State is captured as
   non-blocking FetchHandles at a step boundary (donation-protected through
   the executor's inflight window, or cloned on-device for the donating
   fused TrainStep); a background writer overlaps the D2H + serialization +
   atomic commit with subsequent compute (the stall per checkpoint is
   ``checkpoint_stall_seconds``; not yet measured on the chip).
2. **A committed checkpoint is never torn.** Payload and manifest are each
   written temp-in-dir + fsync + ``os.replace``; the manifest (with payload
   size + CRC32) is the commit marker and is written last. Discovery
   (:func:`latest_checkpoint`) validates and SKIPS anything else.
3. **Resume is bitwise.** The snapshot covers params/slots/BN stats, the
   global step, the DataLoader cursor, and every RNG counter feeding the
   per-op ``_rng_salt`` streams — a resumed run replays the identical loss
   trajectory (tests/framework/test_crash_resume.py proves it through a
   literal ``kill -9``).
4. **Failures are a test fixture, not a hope.** ``PADDLE_TPU_FAULT_INJECT``
   kills/hangs the process, fails checkpoint IO, or poisons the observed
   loss on schedule; goodput (productive/wall time, lost work on restart)
   flows through the telemetry registry into ``tools/telemetry_report.py``.

PR 8 adds the **self-healing** layer on top (docs/RESILIENCE.md
"Self-healing"): :class:`TrainingSupervisor` detects non-finite and spiking
losses at step boundaries and applies the skip / rollback / escalate policy
ladder (``PADDLE_TPU_SUPERVISOR``), and the :mod:`watchdog` turns hangs —
wedged steps, stalled DataLoader producers, stuck checkpoint writers — into
stack-dumped, resumable aborts (``PADDLE_TPU_WATCHDOG``).
"""
from .fault import FaultInjector, get_injector, reset_injector  # noqa: F401
from .goodput import GoodputTracker  # noqa: F401
from .manager import CheckpointManager  # noqa: F401
from .preemption import PreemptionGuard  # noqa: F401
from .snapshot import (Checkpoint, latest_checkpoint,  # noqa: F401
                       list_checkpoints, read_checkpoint, write_checkpoint)
from .state import (capture_training_state,  # noqa: F401
                    restore_training_state, rng_state, restore_rng_state)
from .supervisor import (TrainingDiverged, TrainingSupervisor,  # noqa: F401
                         Verdict, parse_supervisor_spec)
from .watchdog import (WATCHDOG_EXIT_CODE, Watchdog,  # noqa: F401
                       active_watchdog)
from . import watchdog  # noqa: F401

__all__ = [
    'CheckpointManager', 'Checkpoint', 'FaultInjector', 'GoodputTracker',
    'PreemptionGuard', 'capture_training_state', 'restore_training_state',
    'rng_state', 'restore_rng_state', 'latest_checkpoint',
    'list_checkpoints', 'read_checkpoint', 'write_checkpoint',
    'get_injector', 'reset_injector',
    'TrainingSupervisor', 'TrainingDiverged', 'Verdict',
    'parse_supervisor_spec', 'Watchdog', 'active_watchdog', 'watchdog',
    'WATCHDOG_EXIT_CODE',
]
