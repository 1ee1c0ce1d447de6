"""Multi-host bring-up: fleet-env discovery, jax.distributed init, and the
cross-host primitive set.

The Fluid reference's ``distributed/launch.py`` spawned one process per GPU
and wired NCCL env vars; on a TPU pod each host runs ONE process and the
runtime needs exactly three facts: how many trainers, which one am I, and
where the coordinator lives. :func:`discover_fleet_env` reads those from the
reference's env-var contract — **strict-parse**: a malformed or internally
contradictory environment raises immediately, listing every expected var,
instead of silently training single-host while the rest of the pod waits in
a collective (the classic fleet bring-up failure mode).

Recognized variables (docs/DISTRIBUTED.md "Multi-host runtime")::

    PADDLE_TRAINERS_NUM        world size (int >= 1)
    PADDLE_TRAINER_ID          this host's rank in [0, num)
    PADDLE_TRAINER_ENDPOINTS   comma list "host:port,..." (len == num)
    PADDLE_CURRENT_ENDPOINT    this host's entry of the list
    PADDLE_TPU_FLEET_COORDINATOR  coordinator addr override (defaults to
                               endpoint[0], the reference convention)

Bring-up order (each step idempotent): parse env → ``jax.distributed
.initialize`` (gloo CPU collectives for the test/bench fleets) → wire the
Partitioner's mesh from the now-GLOBAL device list → install the
:class:`~paddle_tpu.fleet_runtime.coordinator.FleetSentinel`.

``local_fleet(nproc)`` is the test spawner: it launches ``nproc``
REAL ``jax.distributed`` CPU worker processes (one device each) with the
full fleet env wired, so multi-host behavior is exercised by actual
multi-process rendezvous, not simulation.
"""
from __future__ import annotations

import json
import logging
import os
import socket
import subprocess
import sys
import time

import numpy as np
import jax

from ..log_helper import get_logger

__all__ = ['FleetSpec', 'discover_fleet_env', 'bootstrap', 'process_index',
           'process_count', 'is_host0', 'local_fleet', 'LocalFleet',
           'fleet_barrier', 'broadcast_from_host0', 'all_hosts_agree',
           'fleet_allreduce_scalars', 'ENV_NUM', 'ENV_ID', 'ENV_ENDPOINTS',
           'ENV_CURRENT', 'ENV_COORDINATOR']

_logger = get_logger(
    __name__, logging.INFO,
    fmt='%(asctime)s-%(levelname)s: [fleet] %(message)s')

ENV_NUM = 'PADDLE_TRAINERS_NUM'
ENV_ID = 'PADDLE_TRAINER_ID'
ENV_ENDPOINTS = 'PADDLE_TRAINER_ENDPOINTS'
ENV_CURRENT = 'PADDLE_CURRENT_ENDPOINT'
ENV_COORDINATOR = 'PADDLE_TPU_FLEET_COORDINATOR'

_EXPECTED = (ENV_NUM, ENV_ID, ENV_ENDPOINTS, ENV_CURRENT, ENV_COORDINATOR)

_BOOTSTRAPPED = False


def _distributed_client_up():
    try:
        from jax._src.distributed import global_state
        return global_state.client is not None
    except Exception:
        return False


def _fail(problem):
    raise ValueError(
        f'fleet env: {problem}. Expected variables: '
        f'{ENV_NUM} (int >= 1), {ENV_ID} (int in [0, {ENV_NUM})), '
        f'{ENV_ENDPOINTS} (comma list of host:port, one per trainer), '
        f'{ENV_CURRENT} (this host\'s endpoint, member of the list), '
        f'{ENV_COORDINATOR} (optional coordinator host:port; defaults to '
        f'the first endpoint)')


def _parse_int(environ, name):
    raw = environ.get(name, '').strip()
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        _fail(f'{name} must be an integer, got {raw!r}')


class FleetSpec:
    """Parsed + validated fleet topology. ``num_trainers == 1`` is a valid
    single-host fleet (bring-up becomes a no-op)."""

    __slots__ = ('num_trainers', 'trainer_id', 'endpoints',
                 'coordinator_address')

    def __init__(self, num_trainers, trainer_id, endpoints=None,
                 coordinator_address=None):
        num_trainers = int(num_trainers)
        trainer_id = int(trainer_id)
        if num_trainers < 1:
            _fail(f'{ENV_NUM} must be >= 1, got {num_trainers}')
        if not (0 <= trainer_id < num_trainers):
            _fail(f'{ENV_ID}={trainer_id} outside [0, '
                  f'{ENV_NUM}={num_trainers})')
        if endpoints is not None:
            if len(endpoints) != num_trainers:
                _fail(f'{ENV_ENDPOINTS} lists {len(endpoints)} endpoints '
                      f'but {ENV_NUM}={num_trainers}')
            if len(set(endpoints)) != len(endpoints):
                _fail(f'{ENV_ENDPOINTS} has duplicate entries')
        if coordinator_address is None and endpoints:
            coordinator_address = endpoints[0]
        if num_trainers > 1 and not coordinator_address:
            _fail(f'{ENV_NUM}={num_trainers} > 1 but neither '
                  f'{ENV_COORDINATOR} nor {ENV_ENDPOINTS} is set (no way '
                  f'to rendezvous)')
        self.num_trainers = num_trainers
        self.trainer_id = trainer_id
        self.endpoints = list(endpoints) if endpoints else None
        self.coordinator_address = coordinator_address

    def __repr__(self):
        return (f'FleetSpec(num={self.num_trainers}, id={self.trainer_id}, '
                f'coordinator={self.coordinator_address!r})')


def discover_fleet_env(environ=None):
    """→ :class:`FleetSpec` from the environment, or None when NO fleet
    vars are set (plain single-process run). A partially/contradictorily
    set environment raises (strict parse — see module docstring)."""
    environ = environ if environ is not None else os.environ
    num = _parse_int(environ, ENV_NUM)
    tid = _parse_int(environ, ENV_ID)
    eps_raw = environ.get(ENV_ENDPOINTS, '').strip()
    cur = environ.get(ENV_CURRENT, '').strip()
    coord = environ.get(ENV_COORDINATOR, '').strip() or None
    if num is None and tid is None and not eps_raw and not cur \
            and coord is None:
        return None
    if num is None:
        _fail(f'{ENV_ID}/{ENV_ENDPOINTS} set but {ENV_NUM} is missing')
    if tid is None:
        tid = 0 if num == 1 else _fail(
            f'{ENV_NUM}={num} set but {ENV_ID} is missing')
    endpoints = None
    if eps_raw:
        endpoints = [e.strip() for e in eps_raw.split(',') if e.strip()]
        for e in endpoints:
            if ':' not in e:
                _fail(f'{ENV_ENDPOINTS} entry {e!r} is not host:port')
    spec = FleetSpec(num, tid, endpoints, coord)
    if cur:
        if spec.endpoints is None:
            _fail(f'{ENV_CURRENT} set but {ENV_ENDPOINTS} is missing')
        if cur not in spec.endpoints:
            _fail(f'{ENV_CURRENT}={cur!r} not in {ENV_ENDPOINTS}')
        if spec.endpoints.index(cur) != spec.trainer_id:
            _fail(f'{ENV_CURRENT}={cur!r} is endpoint '
                  f'#{spec.endpoints.index(cur)} but {ENV_ID}='
                  f'{spec.trainer_id} (contradictory rank)')
    return spec


def bootstrap(spec=None, configure_mesh=True, install_sentinel_flag=True):
    """Multi-host bring-up (idempotent). Order matters and is part of the
    documented contract (docs/DISTRIBUTED.md):

    1. parse/validate the fleet env (strict) unless `spec` is given;
    2. ``jax.distributed.initialize`` against the coordinator — after
       this, ``jax.devices()`` is the GLOBAL device list (gloo CPU
       collectives are configured first so test fleets work off-TPU);
    3. wire the Partitioner's owned mesh from the global devices when it
       is still unconfigured (``{'dp': jax.device_count()}`` — the fleet
       default; strategies/env can override before or after);
    4. install the process :class:`FleetSentinel` so one host's failure
       propagates (skippable for tools that only want the mesh).

    Returns the effective :class:`FleetSpec` (or None for a plain
    single-process run with no fleet env)."""
    global _BOOTSTRAPPED
    spec = spec if spec is not None else discover_fleet_env()
    if spec is not None and spec.num_trainers > 1 and not _BOOTSTRAPPED \
            and not _distributed_client_up():
        try:
            # the CPU backend needs the gloo collectives implementation
            # for cross-process computations (no-op when unavailable)
            jax.config.update('jax_cpu_collectives_implementation', 'gloo')
        except Exception:
            pass
        t0 = time.perf_counter()
        jax.distributed.initialize(
            coordinator_address=spec.coordinator_address,
            num_processes=spec.num_trainers,
            process_id=spec.trainer_id)
        _logger.info(
            'jax.distributed up: process %d/%d, coordinator %s, '
            '%d global device(s), %.2fs',
            spec.trainer_id, spec.num_trainers, spec.coordinator_address,
            jax.device_count(), time.perf_counter() - t0)
        _BOOTSTRAPPED = True
    if configure_mesh:
        from ..partition import configure, get_partitioner
        if get_partitioner().mesh is None:
            configure(mesh_shape={'dp': jax.device_count()})
    if install_sentinel_flag:
        from . import coordinator as _coord
        sentinel = _coord.install_sentinel()
        if jax.process_index() == 0:
            # a restarted fleet must not instantly re-observe LAST
            # incarnation's poison flag: host 0 clears stale flags, and
            # the barrier below keeps every other host from polling
            # before the clear landed
            sentinel.clear()
        fleet_barrier('fleet_bootstrap')
    from .. import observability as _obs
    # name this process in distributed span records (trace_merge.py shows
    # 'host<rank>' lanes) — a no-op unless PADDLE_TPU_TRACE_DIR is set
    _obs.distributed.set_process_label('host%d' % process_index())
    if _obs._ENABLED:
        _obs.set_gauge('fleet_world_size', process_count(),
                       help='number of trainer processes in the fleet')
        _obs.set_gauge('fleet_process_index', process_index(),
                       help='this process\'s trainer id')
    return spec


def process_index():
    return jax.process_index()


def process_count():
    return jax.process_count()


def is_host0():
    return jax.process_index() == 0


# ---------------------------------------------------------------------------
# cross-host primitives
# ---------------------------------------------------------------------------

def fleet_barrier(tag='fleet_barrier'):
    """Block until every host reached this `tag` (device-collective
    barrier; no-op single-host). Use only from the MAIN thread — the
    checkpoint writer's cross-host commit uses the coordinator KV store
    instead, precisely so a background barrier can never interleave with
    the step stream's collectives."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(tag)


def broadcast_from_host0(value):
    """Host 0's pytree of arrays, replicated to every host (no-op
    single-host)."""
    if jax.process_count() <= 1:
        return value
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(value)


def all_hosts_agree(value, tag='fleet_agree'):
    """True iff every host passed an identical `value` (JSON-serialized
    comparison — meshes, steps, manifest digests). Single-host: True."""
    if jax.process_count() <= 1:
        return True
    import zlib
    from jax.experimental import multihost_utils
    digest = zlib.crc32(
        json.dumps(value, sort_keys=True, default=str).encode()) \
        & 0xFFFFFFFF
    all_digests = multihost_utils.process_allgather(
        np.asarray(digest, np.uint32))
    return bool((np.asarray(all_digests) == digest).all())


def fleet_allreduce_scalars(values, op='sum'):
    """Reduce a list of host-local python scalars across all hosts — the
    cross-host eval-metric reduction (``run_eval_graph`` sums per-host
    metric accumulators and batch counts through this). Identity
    single-host. `op` ∈ {'sum', 'mean', 'max', 'min'}."""
    ops = {'sum': np.sum, 'mean': np.mean, 'max': np.max, 'min': np.min}
    if op not in ops:
        raise ValueError(f'fleet_allreduce_scalars: unknown op {op!r} '
                         f'(supported: {", ".join(sorted(ops))})')
    vals = [float(v) for v in values]
    if jax.process_count() <= 1:
        return vals
    from jax.experimental import multihost_utils
    gathered = np.asarray(multihost_utils.process_allgather(
        np.asarray(vals, np.float64)))       # (num_hosts, len(values))
    return [float(v) for v in ops[op](gathered, axis=0)]


# ---------------------------------------------------------------------------
# local_fleet: the test/bench spawner (real jax.distributed CPU workers)
# ---------------------------------------------------------------------------

class LocalFleet:
    """Handle on a spawned local fleet: one subprocess per trainer, each a
    REAL ``jax.distributed`` CPU worker (one device per process, gloo
    collectives, full fleet env wired)."""

    def __init__(self, procs, spec_envs):
        self.procs = procs
        self.spec_envs = spec_envs

    def wait(self, timeout=600):
        """→ list of return codes (one per rank); kills stragglers on
        timeout rather than hanging the caller."""
        deadline = time.monotonic() + timeout
        rcs = []
        for p in self.procs:
            left = max(0.1, deadline - time.monotonic())
            try:
                rcs.append(p.wait(timeout=left))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
                rcs.append(None)
        return rcs

    def poll(self):
        return [p.poll() for p in self.procs]

    def terminate(self):
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def free_port():
    with socket.socket() as s:
        s.bind(('localhost', 0))
        return s.getsockname()[1]


def local_fleet(nproc, script, args=(), env=None, rank_env=None,
                stdout=None, cwd=None):
    """Spawn `nproc` real ``jax.distributed`` CPU workers running
    ``python script args...`` with the complete fleet env wired
    (endpoints on free localhost ports, coordinator = endpoint 0,
    ``JAX_PLATFORMS=cpu``, ``XLA_FLAGS`` stripped so each process owns
    exactly one device). Shared by the fleet tests
    (tests/framework/test_fleet_runtime.py, test_fleet_crash_resume.py,
    test_elastic_resize.py).

    `env` merges extra vars into every rank; `rank_env` is
    ``{rank: {var: value}}`` per-rank overrides (fault injection on ONE
    worker). `stdout` may be a callable ``rank -> file object``.
    Returns a :class:`LocalFleet`."""
    ports = [free_port() for _ in range(nproc)]
    endpoints = [f'localhost:{p}' for p in ports]
    procs, envs = [], []
    for r in range(nproc):
        e = dict(os.environ, JAX_PLATFORMS='cpu')
        e.pop('XLA_FLAGS', None)            # one device per process
        e.pop('PADDLE_TPU_FAULT_INJECT', None)
        e[ENV_NUM] = str(nproc)
        e[ENV_ID] = str(r)
        e[ENV_ENDPOINTS] = ','.join(endpoints)
        e[ENV_CURRENT] = endpoints[r]
        if env:
            e.update(env)
        if rank_env and r in rank_env:
            e.update(rank_env[r])
        out = stdout(r) if callable(stdout) else stdout
        procs.append(subprocess.Popen(
            [sys.executable, str(script)] + [str(a) for a in args],
            env=e, cwd=cwd, stdout=out,
            stderr=subprocess.STDOUT if out is not None else None))
        envs.append(e)
    return LocalFleet(procs, envs)
