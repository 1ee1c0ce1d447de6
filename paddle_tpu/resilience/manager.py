"""CheckpointManager: async non-stalling saves, keep-N, preemption, goodput.

The train-loop contract (docs/RESILIENCE.md)::

    mgr = resilience.CheckpointManager('ckpts', every_n_steps=100)
    ck = mgr.latest()
    if ck is not None:
        arrays, meta = mgr.restore(ck)
        resilience.restore_training_state(arrays, meta, executor=exe,
                                          program=main, loader=loader)
        step = meta['step']
    for batch in loader():
        ...run one step...
        step += 1
        if mgr.end_of_step(step, lambda: resilience.capture_training_state(
                executor=exe, program=main, loader=loader)):
            break            # preempted: final checkpoint committed, exit 0
    mgr.close()

Why the step loop never stalls: ``end_of_step`` captures state as
NON-BLOCKING :class:`~paddle_tpu.core.fetch_handle.FetchHandle` s (the
capture helpers either register donation protection with the executor's
inflight window or clone on-device — both are dispatch-cost-only) and hands
them to a background writer thread, which performs the device→host
materialization, the ``np.savez``, the CRC, and the atomic
temp→``os.replace``→manifest commit while the main thread is already
dispatching the next steps. The only synchronous cost at a checkpoint
boundary is handle creation plus — if a previous checkpoint is somehow
still in flight — waiting for it; both are recorded as
``checkpoint_stall_seconds`` (not yet measured on the chip: ROADMAP R10).
"""
from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time

import numpy as np

from .. import observability as _obs
from ..log_helper import get_logger
from . import snapshot as _snap
from . import watchdog as _wdg
from .fault import get_injector
from .goodput import GoodputTracker
from .preemption import PreemptionGuard

__all__ = ['CheckpointManager']

_logger = get_logger(
    __name__, logging.INFO,
    fmt='%(asctime)s-%(levelname)s: [resilience] %(message)s')

ENV_DIR = 'PADDLE_TPU_CKPT_DIR'
ENV_EVERY = 'PADDLE_TPU_CKPT_EVERY_N_STEPS'
ENV_KEEP = 'PADDLE_TPU_CKPT_KEEP'
ENV_RETRIES = 'PADDLE_TPU_CKPT_RETRIES'

PROGRESS_FILE = 'progress.json'
_TMP_MAX_AGE_S = 600.0


def _env_int(name, default):
    raw = os.environ.get(name, '').strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f'{name} must be an integer, got {raw!r}')


class _SaveJob:
    __slots__ = ('step', 'arrays', 'meta', 'done', 'error')

    def __init__(self, step, arrays, meta):
        self.step = step
        self.arrays = arrays        # {flat_key: FetchHandle | array}
        self.meta = meta
        self.done = threading.Event()
        self.error = None


class CheckpointManager:
    """Rolling async checkpointer with preemption + goodput accounting.

    Parameters (env fallbacks in parentheses): `directory`
    (``PADDLE_TPU_CKPT_DIR``), `every_n_steps` — periodic-save cadence for
    :meth:`end_of_step` (``PADDLE_TPU_CKPT_EVERY_N_STEPS``), `keep` — last-N
    retention (``PADDLE_TPU_CKPT_KEEP``, default 3), `retries` — attempts
    per checkpoint IO failure with exponential backoff
    (``PADDLE_TPU_CKPT_RETRIES``, default 3). ``async_save=False`` commits
    on the calling thread (simplest-possible mode, and the bench baseline
    the stall numbers are measured against)."""

    def __init__(self, directory=None, every_n_steps=None, keep=None,
                 async_save=True, retries=None, backoff_s=0.05,
                 install_signal_handlers=True):
        directory = directory or os.environ.get(ENV_DIR)
        if not directory:
            raise ValueError(
                f'CheckpointManager needs a directory (argument or {ENV_DIR})')
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.every_n_steps = (every_n_steps if every_n_steps is not None
                              else _env_int(ENV_EVERY, 0)) or None
        self.keep = max(1, keep if keep is not None else _env_int(ENV_KEEP, 3))
        self.retries = max(0, retries if retries is not None
                           else _env_int(ENV_RETRIES, 3))
        self.backoff_s = float(backoff_s)
        self.async_save = bool(async_save)
        self.goodput = GoodputTracker()
        self._fault = get_injector()
        self._preemption = PreemptionGuard()
        if install_signal_handlers:
            self._preemption.install()
        self._queue = queue.Queue(maxsize=1)
        self._inflight = None         # last submitted _SaveJob
        self._writer = None
        self._error = None            # first unrecovered write failure
        self._last_boundary = None
        self._last_saved_step = None
        self._closed = False
        # runtime-health integration (supervisor.py): a TrainingSupervisor
        # constructed with manager=self attaches here; end_of_step(...,
        # loss=) then judges the step before any save decision, and the
        # verdict is readable as `last_verdict`
        self._supervisor = None
        self.last_verdict = None
        # fleet integration (fleet_runtime/): when another host poisons
        # the fleet, end_of_step returns True (exit-for-resume) and the
        # observed record lands here so the loop can exit with
        # FLEET_EXIT_CODE instead of 0
        self.fleet_poisoned = None
        self._rank = None              # resolved lazily (post-bootstrap)
        # fleet telemetry (docs/OBSERVABILITY.md "Training fleet"): host 0
        # folds every host's published snapshot and runs the straggler
        # monitor; built lazily the first boundary the KV is configured
        self._straggler = None
        # elastic scheduled resize (elastic/schedule.py): armed from
        # PADDLE_TPU_ELASTIC_RESIZE. At the first due boundary
        # end_of_step commits a SYNCHRONOUS checkpoint, rank 0 writes
        # resize.json, and the call returns True with `resize_requested`
        # set — the loop exits through the exit-for-resume ladder and
        # the restarter relaunches at the new size
        from ..elastic.schedule import parse_resize_env
        self._resize_plan = parse_resize_env()
        self.resize_requested = None
        self._resize_exit = False

    # ------------------------------------------------------------------
    # fleet plumbing (fleet_runtime/)
    # ------------------------------------------------------------------
    def _rank_index(self):
        if self._rank is None:
            import jax
            self._rank = jax.process_index()
        return self._rank

    @staticmethod
    def _fleet_world():
        import jax
        return jax.process_count()

    def _sharded(self):
        from ..fleet_runtime.sharded_ckpt import sharded_save_enabled
        return sharded_save_enabled()

    def _sentinel(self):
        from ..fleet_runtime.coordinator import active_sentinel
        return active_sentinel()

    # ------------------------------------------------------------------
    # discovery / restore
    # ------------------------------------------------------------------
    def latest(self):
        """Newest VALID checkpoint (torn/corrupt ones are skipped with a
        logged warning), or None on a fresh directory."""
        return _snap.latest_checkpoint(self.directory)

    def all_checkpoints(self):
        return _snap.list_checkpoints(self.directory)

    def restore(self, ckpt=None):
        """→ (arrays, meta) from `ckpt` (default: latest). Books restart +
        lost-work accounting from the previous incarnation's heartbeat.
        Returns None when there is nothing to restore.

        Fleet restore contract (docs/RESILIENCE.md "Fleet"): on a
        multi-host fleet every host must restore the SAME checkpoint —
        the hosts first agree on the discovered step (a shared-FS race or
        a half-synced directory raises instead of silently diverging),
        then barrier so no host starts stepping against peers still
        loading; sharded checkpoints additionally reassemble full values
        from every host's validated shard and overlay this host's own
        local meta (RNG/loader cursor) from its shard manifest."""
        fleet = self._fleet_world() > 1
        ckpt = ckpt if ckpt is not None else self.latest()
        if fleet:
            from ..elastic.reshard import current_mesh_axes
            from ..fleet_runtime.bootstrap import (all_hosts_agree,
                                                   fleet_barrier)
            step = -1 if ckpt is None else int(ckpt.step)
            # the resize restore barrier: a (possibly resized) fleet must
            # agree on BOTH the step and the mesh it restores onto before
            # any host starts re-laying tiles — a half-updated launch
            # config (one host still at the old world size) fails here,
            # typed, instead of diverging inside the first collective
            if not all_hosts_agree({'restore_step': step,
                                    'mesh_axes': current_mesh_axes()},
                                   tag='ckpt_restore'):
                raise RuntimeError(
                    f'fleet restore: hosts disagree on the checkpoint '
                    f'step or the restoring mesh (this host found step '
                    f'{step}, mesh {current_mesh_axes()}); checkpoint '
                    f'directory {self.directory} is not consistently '
                    f'visible, or the fleet was relaunched with '
                    f'mismatched sizes')
            fleet_barrier(f'ckpt_restore_{step}')
        if ckpt is None:
            return None
        arrays, meta = _snap.read_checkpoint(ckpt)
        saved_part = meta.get('partition')
        if saved_part:
            # reshard-manifest check (elastic/reshard.py): the saved
            # mesh/specs must be re-layable onto THIS fleet's mesh —
            # divisibility validated up front, ReshardError instead of a
            # device_put shape error after minutes of bring-up
            from ..elastic.reshard import check_reshard
            info = check_reshard(
                saved_part,
                shapes={k: np.shape(v) for k, v in arrays.items()},
                step=ckpt.step)
            if info['resharded']:
                _logger.info(
                    'reshard-on-restore: checkpoint step %d saved on '
                    'mesh %s, re-laying onto %s', ckpt.step,
                    info['saved_axes'], info['current_axes'])
                if _obs._ENABLED:
                    _obs.inc('elastic_reshard_restores',
                             help='restores that re-laid checkpoint '
                                  'tiles onto a different mesh than '
                                  'they were saved under')
        host_meta = meta.get('host_meta')
        if host_meta:
            # this host's own RNG / loader cursor (falls back to host 0's
            # when the fleet SHRANK and this rank is new... which cannot
            # happen — rank < world — but a GROWN fleet's extra hosts do
            # take host 0's meta: same lockstep cursor, fresh host RNG)
            mine = host_meta.get(str(self._rank_index())) \
                or host_meta.get('0') or {}
            for key in ('rng', 'python_rng', 'loader'):
                if key in mine:
                    meta[key] = mine[key]
        self.goodput.record_restart(meta.get('goodput'),
                                    self._read_progress())
        self.goodput.export_metrics()
        self._last_saved_step = ckpt.step
        _logger.info('restored checkpoint step %d from %s (lost work: '
                     '%d step(s))', ckpt.step, self.directory,
                     self.goodput.lost_steps)
        return arrays, meta

    # ------------------------------------------------------------------
    # saving
    # ------------------------------------------------------------------
    def save(self, step, arrays, meta=None, block=False):
        """Queue one checkpoint. `arrays` values may be FetchHandles (the
        non-stalling path — D2H happens on the writer thread), jax arrays,
        or numpy. Raises the previous save's error, if any, rather than
        silently dropping checkpoints after the writer broke."""
        if self._closed:
            raise RuntimeError('CheckpointManager is closed')
        self._raise_pending_error()
        meta = dict(meta or {})
        meta.setdefault('step', int(step))
        job = _SaveJob(int(step), dict(arrays), meta)
        t0 = time.perf_counter()
        if not self.async_save or block:
            # commit on the calling thread (final/preemption checkpoints
            # must be durable before the process exits)
            if self._inflight is not None:
                self._inflight.done.wait()
            self._write(job)
            if job.error is not None:
                self._error = None
                raise job.error
        else:
            self._ensure_writer()
            if self._inflight is not None and not self._inflight.done.is_set():
                # one checkpoint in flight at a time bounds host memory to
                # 1× state; waiting here (rare: save cadence outpacing disk)
                # is counted as stall
                self._inflight.done.wait()
                self._raise_pending_error()
            self._inflight = job
            self._queue.put(job)
        stall = time.perf_counter() - t0
        if _obs._ENABLED:
            _obs.observe('checkpoint_stall_seconds', stall,
                         help='time the step loop was blocked per '
                              'checkpoint request (capture + enqueue; the '
                              'write itself is off-thread)')
        self._last_saved_step = int(step)
        return job

    def wait(self):
        """Block until the in-flight save (if any) committed; re-raise its
        failure."""
        if self._inflight is not None:
            self._inflight.done.wait()
        self._raise_pending_error()

    def _raise_pending_error(self):
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _ensure_writer(self):
        if self._writer is None or not self._writer.is_alive():
            self._writer = threading.Thread(
                target=self._writer_loop, daemon=True,
                name='paddle_tpu_checkpoint_writer')
            self._writer.start()

    def _writer_loop(self):
        while True:
            job = self._queue.get()
            if job is None:
                return
            self._write(job)

    def _write(self, job):
        t0 = time.perf_counter()
        # a wedged write (dead NFS mount, stuck D2H) must not silently stop
        # all future checkpoints: the process watchdog, when armed, holds an
        # IO lease over the materialize+commit (watchdog.py)
        lease = _wdg.arm_io('checkpoint_writer')
        try:
            if self._sharded():
                return self._write_fleet(job, lease, t0)
            # materialize: for FetchHandles this is the device→host wait +
            # copy, overlapped with the main thread's subsequent steps
            arrays = {k: np.asarray(v) for k, v in job.arrays.items()}
            job.arrays = None          # drop handles → donation unblocks
            nbytes = None
            for attempt in range(self.retries + 1):
                try:
                    self._fault.on_io()
                    ck = _snap.write_checkpoint(
                        self.directory, job.step, arrays, job.meta,
                        saved_unix_time=time.time())
                    nbytes = ck.manifest['payload_bytes']
                    break
                except OSError as e:
                    if attempt >= self.retries:
                        raise
                    delay = self.backoff_s * (2 ** attempt)
                    _logger.warning(
                        'checkpoint step %d attempt %d/%d failed (%s); '
                        'retrying in %.3fs', job.step, attempt + 1,
                        self.retries + 1, e, delay)
                    if _obs._ENABLED:
                        _obs.inc('checkpoint_retries',
                                 help='checkpoint IO attempts retried '
                                      'after a failure')
                    time.sleep(delay)
            self._gc()
            if _obs._ENABLED:
                _obs.inc('checkpoint_saves',
                         help='checkpoints committed (manifest written)')
                _obs.inc('checkpoint_bytes', nbytes,
                         help='checkpoint payload bytes written')
                _obs.observe('checkpoint_save_seconds',
                             time.perf_counter() - t0,
                             help='materialize + write + commit time per '
                                  'checkpoint (background thread)')
                _obs.set_gauge('checkpoint_last_step', job.step,
                               help='step of the newest committed '
                                    'checkpoint')
        except BaseException as e:      # surface on the next save()/wait()
            job.error = e
            self._error = e
            _logger.error('checkpoint step %d FAILED after %d attempt(s): '
                          '%s: %s', job.step, self.retries + 1,
                          type(e).__name__, e)
            if _obs._ENABLED:
                _obs.inc('checkpoint_failures',
                         help='checkpoints abandoned after exhausting '
                              'retries')
        finally:
            _wdg.disarm(lease)
            job.done.set()

    def _write_fleet(self, job, lease, t0):
        """Sharded fleet save (fleet_runtime/sharded_ckpt.py): this host
        materializes + commits ONLY the tiles it owns; host 0 then waits
        on the coordinator-KV shard barrier and commits the fleet
        manifest — the single global marker — LAST. Runs on the writer
        thread; any raise is surfaced by _write's error handling."""
        from ..fleet_runtime import sharded_ckpt as _shard
        rank, world = self._rank_index(), self._fleet_world()
        meta = dict(job.meta)
        host_meta = {k: meta[k] for k in ('rng', 'python_rng', 'loader')
                     if k in meta}
        arrays, job.arrays = job.arrays, None
        for attempt in range(self.retries + 1):
            try:
                self._fault.on_io()
                sm = _shard.write_host_shard(
                    self.directory, job.step, arrays,
                    host_meta=host_meta, rank=rank, world=world)
                break
            except OSError as e:
                if attempt >= self.retries:
                    raise
                delay = self.backoff_s * (2 ** attempt)
                _logger.warning(
                    'fleet shard step %d attempt %d/%d failed (%s); '
                    'retrying in %.3fs', job.step, attempt + 1,
                    self.retries + 1, e, delay)
                if _obs._ENABLED:
                    _obs.inc('checkpoint_retries',
                             help='checkpoint IO attempts retried after '
                                  'a failure')
                time.sleep(delay)
        arrays = None                  # drop handles → donation unblocks
        if rank == 0:
            _shard.commit_fleet_manifest(
                self.directory, job.step, world, meta=meta,
                saved_unix_time=time.time())
            self._gc()
        if _obs._ENABLED:
            _obs.inc('checkpoint_saves',
                     help='checkpoints committed (manifest written)')
            _obs.inc('checkpoint_bytes', sm['payload_bytes'],
                     help='checkpoint payload bytes written')
            _obs.inc('checkpoint_shard_bytes', sm['payload_bytes'],
                     help='bytes this host wrote into its own fleet '
                          'checkpoint shards (owned tiles only)')
            _obs.observe('checkpoint_save_seconds',
                         time.perf_counter() - t0,
                         help='materialize + write + commit time per '
                              'checkpoint (background thread)')
            _obs.set_gauge('checkpoint_last_step', job.step,
                           help='step of the newest committed checkpoint')

    def _gc(self):
        """Keep the newest `keep` valid checkpoints; delete manifest FIRST
        (decommit), then payloads — a crash mid-gc can only leave orphan
        payloads, never a manifest pointing at nothing valid. Fleet
        checkpoints are GC'd by host 0 only (the manifest committer);
        stale temp litter from crashed writers is swept too."""
        ckpts = _snap.list_checkpoints(self.directory)
        for ck in ckpts[:-self.keep] if len(ckpts) > self.keep else []:
            if ck.sharded and self._rank_index() != 0:
                continue
            try:
                os.unlink(ck.manifest_path)
                for p in ck.payload_paths:
                    os.unlink(p)
            except OSError:
                pass
        now = time.time()
        for name in os.listdir(self.directory):
            if '.tmp-' in name:
                p = os.path.join(self.directory, name)
                try:
                    if now - os.path.getmtime(p) > _TMP_MAX_AGE_S:
                        os.unlink(p)
                except OSError:
                    pass

    # ------------------------------------------------------------------
    # the step-boundary hook
    # ------------------------------------------------------------------
    @property
    def preemption_requested(self):
        return self._preemption.requested

    def request_preemption(self):
        """Programmatic SIGTERM equivalent (tests, external agents)."""
        self._preemption.request()

    def end_of_step(self, step, state_fn, meta=None, loss=None,
                    batch_desc=None):
        """Call once per completed training step. Runs the fault-injection
        step hook, judges health when a supervisor is attached and `loss`
        is given, books goodput, saves when the cadence is due — and, on a
        pending SIGTERM/SIGINT, saves a FINAL checkpoint synchronously and
        returns True (the loop should exit cleanly).

        `state_fn` is called only when a save actually happens; it returns
        either an arrays dict or an ``(arrays, meta)`` tuple (the shape
        :func:`~paddle_tpu.resilience.state.capture_training_state`
        produces).

        Supervision (docs/RESILIENCE.md "Self-healing"): pass the step's
        `loss` (host value or FetchHandle). The attached
        :class:`~paddle_tpu.resilience.supervisor.TrainingSupervisor` runs
        FIRST — a quarantined boundary never checkpoints the poisoned
        state — and its verdict lands in ``self.last_verdict``; on
        ``action == 'rollback'`` the caller must reset its step counter to
        ``last_verdict.resume_step`` and restart its DataLoader iteration.
        Escalations raise ``TrainingDiverged`` out of this call."""
        self._fault.on_step(step)      # may SIGKILL or hang (that's the point)
        now = time.perf_counter()
        # the first boundary has no prior timestamp: the step still COUNTS
        # (lost-work deltas are in steps), its duration is just unknown
        step_time = (now - self._last_boundary
                     if self._last_boundary is not None else None)
        self.goodput.record_step(step_time if step_time is not None else 0.0)
        if step_time is not None:
            from ..observability import distributed as _dobs
            _dobs.series('step_time').observe(step_time)
        sentinel = self._sentinel()
        if sentinel is not None:
            # fleet poison poll (docs/RESILIENCE.md "Fleet propagation"):
            # another host failed — exit for resume NOW, before
            # dispatching a step into a collective with a dead peer. No
            # save: a partial fleet cannot commit a fleet checkpoint; the
            # restart resumes from the last committed one.
            rec = sentinel.check()
            if rec is not None:
                self.fleet_poisoned = rec
                _logger.error(
                    'fleet poisoned by host %s (%s) — exiting for resume '
                    'at step %d', rec.get('source'), rec.get('reason'),
                    step)
                self._write_progress(step)
                self.goodput.export_metrics()
                return True
        self.last_verdict = None
        if self._supervisor is not None and loss is not None:
            try:
                verdict = self._supervisor.end_of_step(step, loss,
                                                       batch_desc)
            except BaseException as e:
                # supervisor escalation (TrainingDiverged) on THIS host
                # must take the whole fleet down for resume, not leave
                # p-1 peers blocked in the next collective
                if sentinel is not None:
                    sentinel.post(f'supervisor escalation: '
                                  f'{type(e).__name__}: {e}',
                                  step=step, kind='supervisor')
                raise
            self.last_verdict = verdict
            if verdict.action == 'rollback':
                # state/RNG/step are back at the restored checkpoint: no
                # save, no heartbeat at the now-bogus step number
                self.goodput.export_metrics()
                self._last_boundary = time.perf_counter()
                return False
        preempt = self._preemption.requested
        # scheduled elastic resize (elastic/schedule.py): at the first
        # boundary >= the planned step, checkpoint SYNCHRONOUSLY and exit
        # for relaunch at the new size — exactly the preemption shape,
        # plus the resize.json handoff for the restarter
        resize = (self._resize_plan is not None
                  and self.resize_requested is None
                  and self._resize_plan.due(step))
        due = (self.every_n_steps is not None
               and step % self.every_n_steps == 0)
        if self.last_verdict is not None and \
                self.last_verdict.action == 'skip':
            due = False                # never checkpoint a dropped update
        if due or preempt or resize:
            got = state_fn()
            arrays, cap_meta = got if isinstance(got, tuple) else (got, {})
            cap_meta = dict(cap_meta)
            if meta:
                cap_meta.update(meta)
            cap_meta['step'] = int(step)
            cap_meta['goodput'] = self.goodput.meta()
            cap_meta['preempted'] = bool(preempt)
            self.save(step, arrays, cap_meta, block=preempt or resize)
        if resize:
            self._begin_resize(step)
        self._publish_fleet_telemetry(step, step_time)
        self._write_progress(step)
        self.goodput.export_metrics()
        self._last_boundary = time.perf_counter()
        if preempt:
            self.wait()
            _logger.info('preemption checkpoint committed at step %d; '
                         'stopping', step)
            return True
        if resize:
            return True
        return False

    def _begin_resize(self, step):
        """The resize checkpoint is committed (save was synchronous);
        record the handoff. Rank 0 writes ``resize.json`` beside the
        checkpoints so the restarter knows the target size; every rank
        stamps ``resize_exit`` into its heartbeat so the NEXT incarnation
        books the downtime into the resize bucket, not crash loss."""
        plan = self._resize_plan
        self.wait()                    # surface a failed resize save HERE
        from ..elastic import schedule as _sched
        if self._rank_index() == 0:
            _sched.write_resize_request(self.directory, step, plan.nproc,
                                        from_nproc=self._fleet_world())
        self._resize_exit = True
        self.resize_requested = {'step': int(step),
                                 'target_nproc': int(plan.nproc)}
        if _obs._ENABLED:
            _obs.inc('elastic_resize_exits',
                     help='scheduled resize exits taken at a step '
                          'boundary (checkpoint committed, relaunch '
                          'pending)')
        _logger.info('scheduled resize at step %d: checkpoint committed, '
                     'exiting for relaunch at nproc=%d', step, plan.nproc)

    # ------------------------------------------------------------------
    # fleet telemetry (docs/OBSERVABILITY.md "Training fleet")
    # ------------------------------------------------------------------
    def _publish_fleet_telemetry(self, step, step_time_s):
        """Per-host metric snapshot through the coordinator KV at each
        step boundary; host 0 folds the fleet aggregate + straggler
        verdict into ``fleet_metrics.json`` beside the checkpoints.
        Gated on the KV being configured — one env read when it isn't —
        and never allowed to fail a training step."""
        from ..fleet_runtime.coordinator import ENV_FLEET_DIR
        if not os.environ.get(ENV_FLEET_DIR):
            return
        from ..observability import distributed as _dobs
        try:
            rank = self._rank_index()
            _dobs.publish_host_snapshot(rank, step,
                                        step_time_s=step_time_s)
            if rank == 0:
                if self._straggler is None:
                    self._straggler = _dobs.StragglerMonitor(
                        out_dir=self.directory)
                _dobs.aggregate_fleet_snapshots(
                    straggler=self._straggler,
                    out_path=os.path.join(self.directory,
                                          'fleet_metrics.json'),
                    step=step)
        except Exception as e:   # noqa: broad — telemetry must not kill a step
            _logger.warning('fleet telemetry publish failed: %s', e)

    # ------------------------------------------------------------------
    # heartbeat
    # ------------------------------------------------------------------
    def _progress_path(self):
        """Per-host heartbeat file: on a fleet the hosts share the
        checkpoint directory, and p writers clobbering ONE progress.json
        would corrupt the lost-work delta (booked from each host's own
        heartbeat — once per host, and the fleet-level counters are host
        0's, whose steps ARE the fleet's steps in lockstep training)."""
        rank = self._rank_index()
        if rank == 0:
            return os.path.join(self.directory, PROGRESS_FILE)
        return os.path.join(self.directory, f'progress-{rank:04d}.json')

    def _write_progress(self, step):
        """Tiny atomic heartbeat: how far THIS incarnation actually got.
        On restart, (heartbeat − restored checkpoint) is the lost work."""
        doc = {'step': int(step),
               'last_checkpoint_step': self._last_saved_step,
               'unix_time': time.time()}
        doc.update(self.goodput.meta())
        if self._resize_exit:
            # next incarnation's record_restart routes the downtime into
            # the resize bucket instead of crash loss
            doc['resize_exit'] = True
        try:
            _snap.atomic_write_bytes(self._progress_path(),
                                     json.dumps(doc).encode())
        except OSError as e:
            _logger.warning('progress heartbeat failed: %s', e)

    def _read_progress(self):
        try:
            with open(self._progress_path()) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    # ------------------------------------------------------------------
    def close(self):
        """Flush the writer, uninstall signal handlers. Idempotent."""
        if self._closed:
            return
        self._closed = True
        try:
            if self._inflight is not None:
                self._inflight.done.wait()
        finally:
            if self._writer is not None and self._writer.is_alive():
                self._queue.put(None)
                self._writer.join(5)
            self._preemption.uninstall()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
