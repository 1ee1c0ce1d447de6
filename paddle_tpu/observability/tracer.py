"""Step tracer: host-side span trees written as chrome-trace JSON.

Every `Executor.run`, `TrainStep.__call__`, and (optionally) tape dispatch
opens a span; nesting is tracked per thread, so the emitted events form a
tree under each step exactly the way Perfetto / chrome://tracing render
"complete" (`ph: "X"`) events — containment of [ts, ts+dur] on one tid IS
the tree. Unlike profiler.start_profiler this does not touch jax.profiler:
it works on any backend, costs two perf_counter() calls per span, and the
output is a single self-contained JSON file.

The event buffer is bounded (PADDLE_TPU_TRACE_MAX_EVENTS, a count of
events, default 100000); past the bound new events are dropped and counted,
never silently lost (`dropped`, `otherData.dropped_events` of a snapshot: a
trace with a count above 0 covers the time before the buffer filled, not the
run). An event is kept as a compact record, the tuple
``(name, start, end, tid, args)`` of the caller's own perf_counter stamps
and its args dict as handed over, and becomes the chrome-trace dict in
`snapshot()` only: recording builds no dict and converts no arg. A record
takes about 170 bytes without args, 380 with one and 520 with six (the
tuple, two floats, the thread id, the args dict; names are interned;
tracemalloc over 100,000 events), so a full buffer at the default bound
holds 17-52 MB. A traced decode server records a few thousand events a
second (one `replica/token` a traced request and token): the default bound
holds about half a minute of that, and a longer traced window needs the
bound raised (the benchmark's harness sets 2,000,000).
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

__all__ = ['Span', 'StepTracer', 'tracer']


class Span:
    """One timed region. Context manager; after exit `duration` is valid."""

    __slots__ = ('name', 'args', 'start', 'duration', '_tracer', '_depth')

    def __init__(self, tracer, name, args):
        self.name = name
        self.args = args
        self.start = 0.0
        self.duration = 0.0
        self._tracer = tracer
        self._depth = 0

    def __enter__(self):
        self._depth = self._tracer._enter()
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        end = time.perf_counter()
        self.duration = end - self.start
        self._tracer._exit(self, end, exc_type)
        return False


class _NullSpan:
    """Shared no-op span for the disabled path (one instance, no allocs)."""

    __slots__ = ()
    name = None
    duration = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


NULL_SPAN = _NullSpan()


class StepTracer:
    def __init__(self, max_events=None):
        if max_events is None:
            max_events = int(os.environ.get('PADDLE_TPU_TRACE_MAX_EVENTS',
                                            '100000'))
        self.max_events = max_events
        self._lock = threading.Lock()
        self._events = []
        self.dropped = 0
        self._epoch = time.perf_counter()
        self._local = threading.local()

    # -- recording ---------------------------------------------------------
    def span(self, name, **args):
        return Span(self, name, args or None)

    def _enter(self):
        depth = getattr(self._local, 'depth', 0)
        self._local.depth = depth + 1
        return depth

    def _exit(self, span, end, exc_type):
        self._local.depth = span._depth
        args = span.args
        if exc_type is not None:
            args = dict(args or {}, error=exc_type.__name__)
        self._keep(span.name, span.start, end, args)

    def _keep(self, name, start, end, args):
        """One compact record (``end`` None: an instant) into the bounded
        buffer, on the calling thread, which is the thread it describes."""
        record = (sys.intern(name), start, end, threading.get_ident(),
                  args or None)
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
            else:
                self._events.append(record)

    def complete(self, name, start_perf, end_perf, **args):
        """Append one already-measured complete event (ph 'X') from
        explicit perf_counter stamps — distributed trace spans are often
        measured retroactively (queue wait is known only at admission),
        so they can't ride the context-manager path."""
        self._keep(name, start_perf, max(start_perf, end_perf), args)

    def instant(self, name, **args):
        """Zero-duration marker (ph 'i') — e.g. a nonfinite detection."""
        self._keep(name, time.perf_counter(), None, args)

    # -- export ------------------------------------------------------------
    def snapshot(self):
        with self._lock:
            records = list(self._events)
            dropped = self.dropped
            epoch = self._epoch
        pid = os.getpid()
        return {'traceEvents': [_event(r, epoch, pid) for r in records],
                'displayTimeUnit': 'ms',
                'otherData': {'producer': 'paddle_tpu.observability',
                              'dropped_events': dropped}}

    def chrome_trace_json(self):
        return json.dumps(self.snapshot())

    def dump(self, path):
        """Write the Perfetto-loadable chrome-trace file; returns `path`."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, 'w') as f:
            json.dump(self.snapshot(), f)
        return path

    def reset(self):
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._epoch = time.perf_counter()

    def __len__(self):
        with self._lock:
            return len(self._events)


def _event(record, epoch, pid):
    """The chrome-trace dict of one compact record."""
    name, start, end, tid, args = record
    ts = (start - epoch) * 1e6                  # µs, trace-relative
    if end is None:
        ev = {'name': name, 'ph': 'i', 's': 't', 'ts': ts,
              'pid': pid, 'tid': tid}
    else:
        ev = {'name': name, 'ph': 'X', 'ts': ts, 'dur': (end - start) * 1e6,
              'pid': pid, 'tid': tid}
    if args:
        ev['args'] = {k: _jsonable(v) for k, v in args.items()}
    return ev


def _jsonable(v):
    if isinstance(v, (str, int, float, bool, type(None))):
        return v
    return str(v)


tracer = StepTracer()
