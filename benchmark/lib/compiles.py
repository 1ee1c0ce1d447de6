"""Compile accounting through jax's public monitoring hooks (copied from
chip_smoke.py's CompileCounter; the original stays there as the bring-up
proof's own). Counts every executable XLA builds or loads, the seconds spent
inside XLA, and the persistent cache's requests, hits and writes."""
from __future__ import annotations

import threading

BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'
EVENTS = {
    '/jax/compilation_cache/compile_requests_use_cache': 'requests',
    '/jax/compilation_cache/cache_hits': 'hits',
    '/jax/compilation_cache/cache_misses': 'writes',
}


class CompileCounter:
    def __init__(self):
        from jax import monitoring
        self.counts = {'compiles': 0, 'compile_secs': 0.0, 'requests': 0,
                       'hits': 0, 'writes': 0}
        self._lock = threading.Lock()
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **kw):
        key = EVENTS.get(event)
        if key is not None:
            with self._lock:
                self.counts[key] += 1

    def _on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.counts['compiles'] += 1
                self.counts['compile_secs'] += duration

    def snapshot(self):
        with self._lock:
            return dict(self.counts)

    def since(self, before):
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
