"""Typed serving errors.

Every failure mode of the serving path maps to exactly one exception type so
callers (and the HTTP front end) can distinguish *your request is bad*
(InvalidRequest), *the system is protecting itself* (Overloaded), *you asked
for a latency we could not meet* (DeadlineExceeded), and *we are going away*
(EngineClosed). All derive from ServingError; the multiple-inheritance bases
(ValueError / TimeoutError) keep generic ``except`` clauses working.
"""
from __future__ import annotations

__all__ = ['ServingError', 'InvalidRequest', 'Overloaded', 'DeadlineExceeded',
           'EngineClosed', 'EngineUnhealthy', 'OutOfBlocks',
           'OutOfStateRows', 'NoReplicaAvailable',
           'UnsupportedCacheFeature']


class ServingError(RuntimeError):
    """Base class for every serving-layer failure."""


class InvalidRequest(ServingError, ValueError):
    """Request rejected at validation time, BEFORE enqueue — a malformed
    request never reaches a batch, so it can never poison co-batched
    requests. Maps to HTTP 400."""


class Overloaded(ServingError):
    """Bounded-queue backpressure: the request queue is full. The request was
    NOT enqueued; the client should back off and retry. Maps to HTTP 429."""

    def __init__(self, queue_depth):
        super().__init__(
            f'serving queue full ({queue_depth} requests waiting); '
            f'back off and retry')
        self.queue_depth = queue_depth


class DeadlineExceeded(ServingError, TimeoutError):
    """The request's deadline expired while it waited in the queue — it was
    dropped before wasting device time. Maps to HTTP 504."""


class EngineClosed(ServingError):
    """Submitted after shutdown began. In-flight requests at shutdown are
    drained, not dropped; new ones get this. Maps to HTTP 503."""


class EngineUnhealthy(ServingError):
    """The circuit breaker is OPEN: the engine failed enough consecutive
    batches that feeding it more requests would only burn their deadlines
    (serving/breaker.py). Rejected in O(µs), BEFORE the queue; the client
    should fail over to another replica — a half-open probe re-admits
    traffic automatically once the engine answers again. Maps to HTTP 503
    (and flips ``/healthz`` to ``degraded``)."""

    def __init__(self, name='engine', failures=None):
        detail = (f' after {failures} consecutive failed batches'
                  if failures else '')
        super().__init__(
            f'{name} circuit breaker is open{detail}; '
            f'failing fast instead of queueing onto a broken engine')
        self.failures = failures


class NoReplicaAvailable(ServingError):
    """The serving-tier router found no routable replica — every replica is
    cold, draining, degraded, or dead — and the wait window expired. Maps
    to HTTP 503; clients back off and retry (tier/router.py)."""

    def __init__(self, replica_states=None):
        states = ''
        if replica_states:
            states = '; replicas: ' + ', '.join(
                f"{s['url']} (healthy={s['healthy']} warmed={s['warmed']} "
                f"draining={s['draining']})" for s in replica_states)
        super().__init__(
            f'no routable replica (all cold, draining, degraded, or '
            f'dead){states}')
        self.replica_states = replica_states


class OutOfBlocks(ServingError):
    """The paged KV-cache pool cannot cover a block reservation right now.
    Inside the decode scheduler this is a WAIT signal (the request stays
    queued until finishing slots free their blocks), never a client error;
    it only escapes to callers driving a DecodeEngine directly.
    ``layer_class`` ('full' | 'sliding') says which class of a pool with
    layer classes ran out (docs/SERVING.md "Layer classes"): the full
    class is sized by ``max_blocks``, the sliding class derived from the
    slots and the span (a ring a slot), so it runs out only with more live
    tables than slots."""

    def __init__(self, requested, available, layer_class=None):
        if layer_class == 'sliding':
            where, cure = ' (the sliding class)', (
                'a ring a slot is all it holds: lower concurrency')
        else:
            where = ' (the full class)' if layer_class else ''
            cure = ('raise PADDLE_TPU_DECODE_MAX_BLOCKS or lower '
                    'concurrency')
        super().__init__(
            f'KV cache pool exhausted{where}: need {requested} blocks, '
            f'{available} free ({cure})')
        self.requested = requested
        self.available = available
        self.layer_class = layer_class


class OutOfStateRows(OutOfBlocks):
    """Every row of the recurrent-state layers is held by a live request
    (docs/SERVING.md "Recurrent state"). The same WAIT signal to the decode
    scheduler as :class:`OutOfBlocks`, which it is: the request stays queued
    until a finishing slot returns its row."""

    def __init__(self, capacity):
        ServingError.__init__(
            self, f'state cache exhausted: all {capacity} state rows are '
            f'held by live requests (one row a slot; lower concurrency)')
        self.requested = 1
        self.available = 0


class UnsupportedCacheFeature(ServingError, ValueError):
    """A cache feature (``features``) was asked of a model whose cached
    state cannot hold it, refused as a cache of ``kind`` ('latent', 'state',
    'sliding', 'grouped' or 'window'). Raised when the engine (or the
    prefill role beside it) is built, never under traffic: which kind
    refuses what, and why, is one table (serving/decode/layout.py,
    docs/SERVING.md "Cache layout")."""

    def __init__(self, features, kind):
        from .decode.layout import refusal_message
        self.features = list(features)
        self.kind = kind
        super().__init__(refusal_message(self.features, kind))
