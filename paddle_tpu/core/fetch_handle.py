"""Non-blocking fetch handles + the bounded in-flight dispatch window.

The async train-loop pipeline: every `Executor.run` fetch ends
in `np.asarray`, a blocking device→host sync that serializes host feed prep,
device compute, and D2H — the per-step input/host-wait loss arXiv:1909.09756
identifies as the dominant non-compute cost at high step rates. Instead of
materializing eagerly, the executor (and `TrainStep(async_fetch=True)`) hands
back a :class:`FetchHandle` wrapping the still-on-device array; jax's async
dispatch keeps computing in the background while the host prepares and
dispatches the next step. `np.asarray(handle)` / `handle.numpy()` is the one
synchronization point, and :class:`InflightWindow` bounds how many dispatched
steps may be outstanding (default K=2, classic double buffering) so the
dispatch queue and fetch-buffer memory stay bounded.

Snapshot semantics: jax arrays are immutable, so holding the fetched array IS
a point-in-time snapshot — with one exception: buffer donation. A pending
handle whose fetch aliases a persistable would be overwritten in place when a
later step donates that state buffer, so the executor consults
:meth:`InflightWindow.protected_names` and keeps those names out of the
donated set until the handle materializes (or is dropped — handles are held
weakly, a dropped handle neither blocks admission nor pins its buffers).
"""
from __future__ import annotations

import collections
import os
import time
import weakref

import numpy as np

from .. import observability as _obs

__all__ = ['FetchHandle', 'InflightWindow', 'resolve_inflight_steps']


def resolve_inflight_steps(exec_strategy=None, default=0):
    """→ K, the max dispatched-but-unconsumed steps (0 = synchronous loop).

    Resolution order: ``PADDLE_TPU_ASYNC`` overrides everything — ``0``
    forces the synchronous loop (exact pre-pipeline behavior), ``1`` enables
    the default double-buffered window (K=2), any larger integer is K
    itself. With the env unset, ``ExecutionStrategy.num_inflight_steps > 1``
    enables the window at that depth; otherwise `default` applies."""
    env = os.environ.get('PADDLE_TPU_ASYNC', '').strip()
    if env:
        if env == '0':
            return 0
        try:
            k = int(env)
        except ValueError:
            return 2
        return 2 if k <= 1 else k
    if exec_strategy is not None:
        try:
            k = int(getattr(exec_strategy, 'num_inflight_steps', 1) or 1)
        except (TypeError, ValueError):
            k = 1
        if k > 1:
            return k
    return default


class FetchHandle:
    """A pending fetch: the on-device result of a dispatched step whose
    device→host materialization is deferred until the value is actually
    read. `numpy()` / `np.asarray(handle)` / `float(handle)` materialize
    (and cache) the host array; `block_until_ready()` waits for the device
    computation without a host copy. After materialization the device
    reference is dropped so a kept handle pins host memory only."""

    __slots__ = ('_value', '_host', '_name', '_check_nan', '__weakref__')

    def __init__(self, value, name=None, check_nan=False):
        self._value = value          # jax.Array, possibly still computing
        self._host = None            # cached np.ndarray once materialized
        self._name = name
        # FLAGS_check_nan_inf captured at dispatch: the scan runs at
        # materialization time instead of forcing a per-step sync
        # (docs/OBSERVABILITY.md "NaN/Inf wiring")
        self._check_nan = check_nan

    # -- metadata (never synchronizes) ---------------------------------
    @property
    def name(self):
        return self._name

    @property
    def shape(self):
        v = self._host if self._value is None else self._value
        return tuple(v.shape)

    @property
    def dtype(self):
        return (self._host if self._value is None else self._value).dtype

    @property
    def nbytes(self):
        v = self._host if self._value is None else self._value
        return getattr(v, 'nbytes', 0)

    @property
    def materialized(self):
        return self._host is not None

    @property
    def done(self):
        """True once the device computation finished (or the handle was
        materialized); never blocks."""
        if self._host is not None:
            return True
        try:
            return bool(self._value.is_ready())
        except (AttributeError, RuntimeError):
            return True          # non-jax value: nothing pending

    def device_array(self):
        """The wrapped value WITHOUT forcing a device→host copy: the
        still-on-device jax array while unmaterialized, the cached host
        array after. The supervisor's skip policy uses this to write a
        pre-step snapshot back into the scope as a device-to-device
        assignment instead of a D2H+H2D round trip."""
        return self._value if self._value is not None else self._host

    # -- synchronization -----------------------------------------------
    def block_until_ready(self):
        """Wait for the device computation; the value stays on device."""
        if self._host is None:
            try:
                self._value.block_until_ready()
            except AttributeError:
                pass
        return self

    def numpy(self):
        """Materialize (D2H copy), cache, and return the host array. The
        wait+copy is recorded as `fetch_materialize_seconds`; with
        FLAGS_check_nan_inf on at dispatch time, the non-finite scan runs
        here — once, on the host copy — instead of re-serializing the
        pipelined loop."""
        if self._host is None:
            t0 = time.perf_counter()
            arr = np.asarray(self._value)
            if _obs._ENABLED:
                _obs.observe(
                    'fetch_materialize_seconds', time.perf_counter() - t0,
                    help='device→host wait+copy per FetchHandle '
                         'materialization (the async loop\'s only sync '
                         'point)')
            self._host = arr
            self._value = None   # release the device buffer reference
            if self._check_nan:
                self._scan_finite(arr)
        return self._host

    def _scan_finite(self, arr):
        if arr.dtype.kind == 'f' and not np.isfinite(arr).all():
            _obs.inc('nonfinite_detections', 1,
                     help='fetched variables containing NaN/Inf '
                          '(FLAGS_check_nan_inf)')
            _obs.instant('nonfinite_detected',
                         variables=self._name or 'fetch')
            from ..debugging import check_numerics
            check_numerics(arr, self._name or 'fetch')

    # -- array protocol ------------------------------------------------
    def __array__(self, dtype=None, copy=None):
        a = self.numpy()
        if dtype is not None and a.dtype != np.dtype(dtype):
            return a.astype(dtype)
        return np.array(a) if copy else a

    def __float__(self):
        return float(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __len__(self):
        return self.shape[0] if self.shape else 0

    def __repr__(self):
        state = ('materialized' if self.materialized
                 else 'ready' if self.done else 'pending')
        return (f"FetchHandle({self._name or '?'}, shape={self.shape}, "
                f"dtype={self.dtype}, {state})")


class _InflightStep:
    """One dispatched step: weak refs to its fetch handles."""

    __slots__ = ('handles',)

    def __init__(self, handles):
        self.handles = [weakref.ref(h) for h in handles]

    def done(self):
        for r in self.handles:
            h = r()
            if h is not None and not h.done:
                return False
        return True

    def block(self):
        for r in self.handles:
            h = r()
            if h is not None:
                h.block_until_ready()


class InflightWindow:
    """FIFO of dispatched-but-unconsumed steps. `admit(k)` enforces the
    K-in-flight bound by blocking on the OLDEST pending step only when the
    window is full — so host-side work for step N+1 overlaps device
    execution of steps N..N-K+1. Entries whose handles are all ready,
    materialized, or garbage-collected retire for free.

    Window occupancy and snapshot protection have different lifetimes: a
    step leaves the WINDOW once its device computation finished (ready),
    but a persistable-aliasing handle stays donation-PROTECTED until the
    user actually materializes (or drops) it — whether XLA gives a fetch
    output its own buffer or aliases it with the state output is a backend
    detail the snapshot guarantee must not depend on."""

    def __init__(self):
        self._entries = collections.deque()
        self._snapshots = []      # weak refs to persistable-fetch handles

    def retire(self):
        while self._entries and self._entries[0].done():
            self._entries.popleft()
        return self

    def admit(self, k):
        """Call BEFORE dispatching a new step: waits until < k outstanding."""
        self.retire()
        while len(self._entries) >= max(1, int(k)):
            self._entries.popleft().block()

    def push(self, handles, protected=()):
        self._entries.append(_InflightStep(handles))
        for h in handles:
            if h.name in protected:
                self._snapshots.append(weakref.ref(h))
        if _obs._ENABLED:
            _obs.set_gauge(
                'executor_inflight_steps', len(self._entries),
                help='dispatched steps whose fetch handles are still '
                     'pending (async pipeline window occupancy)')

    def protect(self, handles):
        """Register snapshot protection WITHOUT occupying the dispatch
        window: each handle's named buffer stays out of the donated set
        until the handle materializes or is dropped. This is the zero-copy
        checkpoint capture path (resilience/state.py) — the handles are
        point-in-time state snapshots a background writer will materialize,
        not step outputs, so they must not gate `admit`."""
        for h in handles:
            self._snapshots.append(weakref.ref(h))

    def protected_names(self):
        """Persistable names snapshotted by a live, not-yet-materialized
        handle: the executor must not donate their buffers this step."""
        live, names = [], set()
        for r in self._snapshots:
            h = r()
            if h is not None and not h.materialized:
                live.append(r)
                names.add(h.name)
        self._snapshots = live
        return names

    def drain(self):
        while self._entries:
            self._entries.popleft().block()

    def __len__(self):
        self.retire()
        return len(self._entries)
