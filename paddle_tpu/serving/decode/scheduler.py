"""Slot-based continuous-batching scheduler + per-request token streams.

The scheduling model is S fixed decode slots stepped in lockstep:

    submit() ─ validate ─▶ bounded waiting queue ─▶ worker loop, per step:
                  │               │                   1. expire deadlines
           InvalidRequest     Overloaded              2. admit waiting →
           (never queued)    (queue full)                free slots (prefill)
                                                      3. ONE decode step,
                                                         all S slots
                                                      4. emit tokens, free
                                                         finished slots,
                                                         hand the touched
                                                         streams over once
                                                      ▼
                                          per-request GenerationStream

**Continuous vs drain** (``admission=``): 'continuous' admits into freed
slots every step — the batch never drains, so slot occupancy stays near 1
under backlog. 'drain' (the strawman tests/framework/test_decode_engine.py
counts steps against; ROADMAP D12) only admits when ALL slots are free:
short requests finish early and their slots idle until the longest in the
wave completes: 1.3× the lockstep steps on a heavy-tailed workload.

Admission takes the request's full block reservation (prompt + token
budget) up front, so a generation can never die of OutOfBlocks mid-flight;
when the pool can't cover the next waiting request the scheduler simply
keeps stepping until a finishing slot frees blocks (FIFO admission — no
starvation of big requests behind small ones). Over a model with layer
classes (docs/SERVING.md "Layer classes") the reservation is of BOTH free
lists, the full class's table and the sliding class's ring
(`engine.reserve_table` → `KVCachePool.new_table`: all or nothing), the
`OutOfBlocks` that makes a request wait names the class that ran out, and
retirement (`_retire`, `_fail_request` → `engine.release_table`) returns
both. Over a model with state layers, alone or beside row layers
(docs/SERVING.md "Recurrent state", "Hybrid models"), the same call takes a
state ROW as well, from a free list of its own: `OutOfStateRows` is an
`OutOfBlocks`, the same wait, and retirement returns the row with the blocks.

**Window models** (block diffusion, ``engine.window`` B > 1: docs/SERVING.md
"Window models"): a slot holds a BLOCK, not a next token: B ids, which of
them are still masked, the positions a forward unmasks (``_blocks``,
``_masked``, ``_quota``, one row a slot). A step (``_window_step``, the
engine's ``window_step``) denoises the slots with masked positions and
commits the others; a commit emits the block's tokens in position order
(0 or up to B tokens a slot a step), then opens the next block or retires
the request at the exact length asked for, cutting its last block. A model
of window 1 takes none of this.

Deadlines bound WAITING only: once a request holds a slot it runs to
completion (aborting mid-generation would waste the prefill — the
ROADMAP's preemption item is about checkpointed resume, not dropping
work). Backpressure and drain/fail-fast close mirror MicroBatcher.
"""
from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
import uuid

import numpy as np

from .. import metrics as _m
from ... import observability as _obs
from ...observability import distributed as _dobs
from ..breaker import CircuitBreaker
from ..errors import (DeadlineExceeded, EngineClosed, EngineUnhealthy,
                      InvalidRequest, Overloaded, OutOfBlocks, ServingError)
from ..batcher import DEFAULT_QUEUE_DEPTH
from .diffusion import denoise_quota, validate_denoising_steps
from .sampling import SamplingParams, TokenSampler

__all__ = ['DecodeScheduler', 'GenerationStream']


class GenerationStream:
    """Per-request handle: iterate tokens as they decode, or block for the
    full result.

        for tok in stream:            # per-token streaming
            ...
        toks = stream.result(30)      # or: block until done

    ``finish_reason``: 'stop' (eos) | 'length' (budget) | None while
    running. Failures (engine error, deadline, shutdown) raise from both
    the iterator and ``result()``.

    Identity (``meta`` / the final HTTP NDJSON line): ``replica_id`` names
    the serving process, ``request_id`` is restart-safe — a fresh random
    component per submission (or the client's pinned id), so retries after
    a replica restart or a router failover never collide and clients can
    correlate the attempts of one logical request across replicas. For
    SAMPLED requests the request_id is also the stream seed (sampling.py):
    replaying the same id + params reproduces the token stream bitwise.

    The tokens are one append-only list, and a consumer is a cursor into it:
    an in-process iterator (``iter_tokens``) sleeps on the stream's condition
    and is woken a token; a consumer that serves many streams (the HTTP
    server's stream writer) takes ``tokens_since(cursor)`` when the
    scheduler's hand-off names the stream (``DecodeScheduler.stream_sink``)
    and no thread is woken for it a token."""

    def __init__(self, prompt_len, max_new_tokens, replica_id=None,
                 request_id=None, trace_id=None):
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.replica_id = replica_id
        self.request_id = request_id or uuid.uuid4().hex[:16]
        self.trace_id = trace_id
        self._tokens = []
        self._grew = threading.Condition()     # iter_tokens' sleepers
        self._done = threading.Event()
        self._exc = None
        self.finish_reason = None

    @property
    def meta(self):
        """Result metadata: {'request_id', 'replica_id'} (+ 'trace_id' for
        sampled-trace requests) — stable from submission, valid
        before/after completion."""
        meta = {'request_id': self.request_id, 'replica_id': self.replica_id}
        if self.trace_id is not None:
            meta['trace_id'] = self.trace_id
        return meta

    # -- consumer side -----------------------------------------------------
    def __iter__(self):
        return self.iter_tokens()

    def iter_tokens(self, timeout=None):
        """Yield token ids as they decode. ``timeout`` bounds the wait for
        EACH token (TimeoutError), so a stuck stream cannot pin its
        consumer forever."""
        cursor = 0
        while True:
            with self._grew:
                while True:
                    # done is read before the length: a stream is done
                    # after its last token
                    done = self._done.is_set()
                    if done or len(self._tokens) > cursor:
                        break
                    if not self._grew.wait(timeout):
                        raise TimeoutError(self.stalled_message(timeout))
            new = self._tokens[cursor:]
            cursor += len(new)
            yield from new
            if done:
                if self._exc is not None:
                    raise self._exc
                return

    def stalled_message(self, timeout):
        """What the TimeoutError of a stream says that yielded no token for
        ``timeout`` seconds."""
        return (f'no token within {timeout}s (generated '
                f'{len(self._tokens)} so far)')

    def tokens_since(self, cursor):
        """The tokens emitted after the first ``cursor``, without blocking:
        a consumer that keeps its own cursor reads each token once. Read
        ``done()`` BEFORE it: a stream is done after its last token."""
        return self._tokens[cursor:]

    def exception(self):
        """The request's failure, None while it runs or if it finished."""
        return self._exc

    def result(self, timeout=None):
        """All generated token ids; raises the request's failure."""
        if not self._done.wait(timeout):
            raise TimeoutError('generation not completed in time')
        if self._exc is not None:
            raise self._exc
        return list(self._tokens)

    def done(self):
        return self._done.is_set()

    @property
    def tokens(self):
        """Snapshot of tokens emitted so far."""
        return list(self._tokens)

    # -- scheduler side ----------------------------------------------------
    def _emit(self, token):
        self._tokens.append(int(token))
        self._wake()

    def _finish(self, reason):
        self.finish_reason = reason
        self._done.set()
        self._wake()

    def _fail(self, exc):
        self._exc = exc
        self._done.set()
        self._wake()

    def _wake(self):
        with self._grew:
            self._grew.notify_all()


class _Request:
    __slots__ = ('prompt', 'max_new_tokens', 'eos_id', 'stream', 'deadline',
                 'enqueued_at', 'table', 'next_token', 'generated',
                 'pending_prompt', 'prefilling', 'handoff_pending',
                 'sampling', 'sampler', 'history', 'trace', 'enqueued_perf',
                 'handoff_t0', 'denoising_steps', 'block_from', 'block_t0')

    def __init__(self, prompt, max_new_tokens, eos_id, deadline,
                 replica_id=None, sampling=None, request_id=None,
                 trace=None, denoising_steps=None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        # distributed trace carrier (observability.TraceContext | None):
        # spans recorded here parent under the router's dispatch span
        self.trace = trace if (trace is not None and trace.sampled) else None
        self.stream = GenerationStream(
            len(prompt), max_new_tokens, replica_id=replica_id,
            request_id=request_id,
            trace_id=self.trace.trace_id if self.trace else None)
        self.deadline = deadline
        self.enqueued_at = time.monotonic()
        self.enqueued_perf = time.perf_counter()
        self.handoff_t0 = None
        # a window model's request: denoising forwards a block; the first
        # position of the open block that is the answer's (the prompt's
        # tail comes before it in the first block); when the open block's
        # first forward began (None: not yet)
        self.denoising_steps = denoising_steps
        self.block_from = 0
        self.block_t0 = None
        self.table = None
        self.next_token = None        # sampled but not yet cached/emitted?
        self.generated = 0
        # per-request sampling: sampler is None on the greedy path (exact
        # argmax, bitwise-unchanged); sampled draws are keyed off the
        # stream's restart-safe request_id → replayable (sampling.py)
        self.sampling = sampling or SamplingParams()
        self.sampler = (None if self.sampling.greedy else
                        TokenSampler(self.sampling, self.stream.request_id))
        # prompt + emitted tokens — what the speculative drafter continues
        # from (its last element is the pending uncached token)
        self.history = list(prompt)
        # chunked suffix fill (prefix-cache hit): prompt tokens still to be
        # fed through the lockstep step; while prefilling, step outputs are
        # discarded (the next fed token is forced to the prompt)
        self.pending_prompt = None
        self.prefilling = False
        # disaggregation: admitted, slot reserved, waiting for the prefill
        # replica's KV payload — inactive in the lockstep step until then
        self.handoff_pending = False

    def expired(self, now):
        return self.deadline is not None and now > self.deadline


def _now():
    """(perf_counter, thread_time): the wall clock and, beside it, the
    calling thread's CPU clock, which runs only while the thread does."""
    return time.perf_counter(), time.thread_time()


class DecodeScheduler:
    """Continuous-batching front end over a :class:`DecodeEngine`.

    - ``queue_depth``: waiting-queue bound → typed ``Overloaded``.
    - ``admission``: 'continuous' (default) | 'drain' (bench strawman).
    - ``default_timeout_ms``: waiting deadline applied when submit() gets
      none (None = wait forever).
    """

    def __init__(self, engine, queue_depth=DEFAULT_QUEUE_DEPTH,
                 admission='continuous', default_timeout_ms=None,
                 breaker_failures=None, breaker_reset_s=None, start=True,
                 replica_id=None, disagg=None, drafter=None,
                 denoising_steps=None):
        if admission not in ('continuous', 'drain'):
            raise ValueError(f"admission must be 'continuous' or 'drain', "
                             f"got {admission!r}")
        self.engine = engine
        # a window model (block diffusion): every slot's open block on the
        # host, and the replica's default denoising steps a block (a
        # request may ask for its own, 1..B; default B: one position a
        # forward)
        self._window = int(getattr(engine, 'window', 1))
        self.denoising_steps = None
        if self._window > 1:
            self.denoising_steps = validate_denoising_steps(
                self._window if denoising_steps is None else denoising_steps,
                self._window)
            self._blocks = np.zeros((engine.slots, self._window), np.int64)
            self._masked = np.zeros((engine.slots, self._window), bool)
            self._quota = np.zeros(engine.slots, np.int64)
        elif denoising_steps is not None:
            raise ValueError(
                'denoising_steps is a window model\'s (block diffusion); '
                'this engine\'s model generates one token a step')
        # speculative decoding (engine.spec_enabled): the engine owns the
        # batched (S, k) verify step; the scheduler owns the DRAFTER —
        # proposals are host-side policy. ``drafter`` may be a name
        # ('ngram' / 'draft_model' / 'off'), a duck-typed .propose object,
        # or None → the PADDLE_TPU_SPEC_DRAFTER knob (default 'ngram').
        self.drafter = None
        self._spec_drafted = 0
        self._spec_accepted = 0
        if getattr(engine, 'spec_enabled', False):
            from ..tier.knobs import ENV_SPEC_DRAFTER, parse_choice_env
            from .drafter import DRAFTER_CHOICES, build_drafter
            if drafter is None:
                drafter = parse_choice_env(ENV_SPEC_DRAFTER,
                                           DRAFTER_CHOICES, 'ngram')
            self.drafter = build_drafter(
                drafter, getattr(engine, 'padded_context', 0))
        # identity stamped into every GenerationStream's result metadata
        # (serving-tier failover correlation); free-form, not a strict knob
        self.replica_id = (replica_id
                           or os.environ.get('PADDLE_TPU_REPLICA_ID')
                           or f'replica-{os.getpid()}')
        # disaggregated prefill (serving/tier/disagg.py): cache-miss
        # prompts hand off to prefill-role replicas instead of stalling
        # the lockstep decode loop on an inline bucket forward
        self.disagg = disagg
        # circuit breaker (serving/breaker.py): consecutive engine failures
        # (prefill or lockstep step) trip it — waiting requests fail fast
        # with EngineUnhealthy, /healthz reports degraded, a half-open probe
        # re-admits traffic once the engine answers
        self.breaker = CircuitBreaker(
            failure_threshold=breaker_failures, reset_after_s=breaker_reset_s,
            metrics=_m.DECODE_BREAKER_METRICS, name='decode engine')
        self.queue_depth = int(queue_depth)
        self.admission = admission
        self.default_timeout_ms = default_timeout_ms
        self._waiting = collections.deque()
        self._slots = [None] * engine.slots      # _Request | None
        # worker-owned: spans of traced requests since the last batch
        # (_trace_span / _record_spans); wall and thread-CPU seconds inside
        # engine calls this loop iteration (_run_cycles); the stamps (wall,
        # CPU) at which the last leaf of the thread's time ended, and the
        # iteration's stretches between leaves, (start, end, CPU seconds):
        # its bookkeeping (_leaf_begins)
        self._spans = []
        self._engine_s = self._engine_cpu = 0.0
        # who else serves the streams' consumers: the HTTP server's stream
        # writer sets it (serving/stream_writer.py). Called on the worker
        # thread with the streams touched since the last call (a token, a
        # finish, a failure), ONE call an emit whatever the slot count
        # (`_hand_off`); None: a stream's own iterators are its consumers.
        # Worker-owned beside it: the streams touched and the tokens emitted
        # and not yet booked
        self.stream_sink = None
        self._touched = []
        self._unbooked = 0
        self._leaf = (0.0, 0.0)          # the worker stamps it as it starts
        self._book = []
        self._cv = threading.Condition()
        self._closing = False
        self._abort = False
        self._closed = False
        self._worker = threading.Thread(target=self._worker_loop,
                                        name='paddle-tpu-decode-scheduler',
                                        daemon=True)
        if start:
            self._worker.start()

    # -- client side -------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=16, eos_id=None,
               timeout_ms=None, sampling=None, request_id=None, trace=None,
               denoising_steps=None):
        """Validate and enqueue one generation; returns its
        :class:`GenerationStream`. Raises InvalidRequest / Overloaded /
        EngineUnhealthy (breaker open) / EngineClosed (all pre-enqueue).

        ``sampling``: None (greedy) | dict | SamplingParams — typed
        validation happens HERE, pre-enqueue, naming the bad field.
        ``request_id``: optional client-pinned id; for sampled requests it
        seeds the stream, so resubmitting the same id + params replays the
        exact token sequence (after a restart, on another replica, ...).
        ``trace``: optional :class:`observability.TraceContext` carried in
        from the HTTP front end — queue-wait/prefill/per-token spans of
        this generation are recorded under it (docs/OBSERVABILITY.md).
        ``denoising_steps``: a window model's alone (block diffusion): the
        denoising forwards a block of this request takes, 1..B, the
        replica's default where None; typed validation pre-enqueue."""
        if not self.breaker.allow():
            raise EngineUnhealthy('decode engine',
                                  self.breaker.consecutive_failures)
        try:
            prompt, max_new = self.engine.validate(prompt_ids,
                                                   max_new_tokens)
            params = SamplingParams.validate(sampling)
            denoising_steps = self._validate_denoising(denoising_steps,
                                                       params)
            if request_id is not None:
                request_id = str(request_id)
                if not 0 < len(request_id) <= 128 or any(
                        c in request_id for c in '\r\n'):
                    raise InvalidRequest(
                        'request_id must be 1-128 characters with no '
                        'newlines')
        except Exception:
            _m.decode_requests_rejected_invalid.inc()
            raise
        if timeout_ms is None:
            timeout_ms = self.default_timeout_ms
        deadline = None if timeout_ms is None \
            else time.monotonic() + float(timeout_ms) / 1e3
        req = _Request(prompt, max_new,
                       self.engine.eos_id if eos_id is None else eos_id,
                       deadline, replica_id=self.replica_id,
                       sampling=params, request_id=request_id, trace=trace,
                       denoising_steps=denoising_steps)
        if req.trace is not None:
            _m.trace_requests_sampled.inc()
        with self._cv:
            if self._closing:
                raise EngineClosed('decode scheduler is shutting down')
            if len(self._waiting) >= self.queue_depth:
                _m.decode_requests_rejected_overload.inc()
                raise Overloaded(len(self._waiting))
            self._waiting.append(req)
            _m.decode_requests_accepted.inc()
            _m.decode_queue_depth.set(len(self._waiting))
            _dobs.series('queue_depth').observe(len(self._waiting))
            self._cv.notify()
        return req.stream

    def _validate_denoising(self, denoising_steps, params):
        """A request's denoising steps (None on a model of window 1)."""
        if self._window == 1:
            if denoising_steps is not None:
                raise InvalidRequest(
                    'denoising_steps is a window model\'s (block '
                    'diffusion); this replica\'s model generates one token '
                    'a step')
            return None
        if not params.greedy:
            raise InvalidRequest(
                'a window model unmasks by confidence: temperature, top_k '
                'and top_p have no meaning for it')
        if denoising_steps is None:
            return self.denoising_steps
        return validate_denoising_steps(denoising_steps, self._window)

    def generate(self, prompt_ids, max_new_tokens=16, eos_id=None,
                 timeout_ms=None, result_timeout=120.0):
        """Synchronous convenience: submit + wait for the full token list."""
        return self.submit(prompt_ids, max_new_tokens, eos_id,
                           timeout_ms).result(result_timeout)

    def pending(self):
        with self._cv:
            return len(self._waiting)

    def active(self):
        with self._cv:
            return sum(r is not None for r in self._slots)

    # -- worker side -------------------------------------------------------
    def _expire_waiting(self, now):
        kept = collections.deque()
        for req in self._waiting:
            if req.expired(now):
                _m.decode_requests_deadline_missed.inc()
                self._fail_stream(req.stream, DeadlineExceeded(
                    f'deadline expired after {now - req.enqueued_at:.3f}s '
                    f'waiting for a decode slot'))
            else:
                kept.append(req)
        self._waiting = kept
        _m.decode_queue_depth.set(len(self._waiting))

    def _admit_locked(self):
        """Move waiting requests into free slots (FIFO; stops at the first
        one the pool cannot cover). Returns the admitted requests — their
        prefill runs OUTSIDE the lock."""
        if self.admission == 'drain' and any(
                r is not None for r in self._slots):
            return []
        admitted = []
        for i, slot in enumerate(self._slots):
            if slot is not None or not self._waiting:
                continue
            req = self._waiting[0]
            try:
                req.table = self.engine.reserve_table(len(req.prompt),
                                                      req.max_new_tokens,
                                                      prompt=req.prompt)
            except OutOfBlocks:
                break                 # FIFO: wait for blocks, don't skip
            self._waiting.popleft()
            self._slots[i] = req
            admitted.append(req)
        _m.decode_queue_depth.set(len(self._waiting))
        return admitted

    def _publish(self, req):
        """Publish the fully-cached prompt into the engine's prefix cache
        (no-op for cache-off and duck-typed engines)."""
        if getattr(self.engine, 'prefix_cache', None) is not None:
            self.engine.publish_prefix(req.prompt, req.table)

    def _trace_span(self, req, name, start_perf, end_perf, **args):
        """Note one replica-side span of a traced request (no-op when the
        request carries no sampled trace — one None check). This runs per
        slot per step between a decode step and the next admission, so it
        is one append; ``_record_spans`` makes the spans of it."""
        if req.trace is not None:
            self._spans.append((req, name, start_perf, end_perf, args))

    def _record_spans(self):
        """Record the spans noted since the last call, in one batch: child
        contexts, dicts, the JSONL lines and the mirror into the chrome
        buffer cost the worker thread one pass, not one per slot. It runs
        when an engine call has just returned and before the call's tokens
        are emitted: the HTTP side is then idle, so a batch that yields
        the interpreter (an id draw, the file write) hands it to nobody.
        The emit's hand-off wakes the stream writer (a thread a connection
        until PR 35), and whatever yields between there and the next engine
        call may wait for it (PERF.md, PR 24): per-slot spans there are what
        made a traced run admit earlier than an untraced one."""
        noted, self._spans = self._spans, []
        if not noted:
            return
        _m.trace_spans_recorded.inc(len(noted))
        _dobs.record_spans(
            [(req.trace.child(), name, start, end,
              dict(args, request_id=req.stream.request_id,
                   replica_id=self.replica_id))
             for req, name, start, end, args in noted])

    def _phase(self, phase, wall, cpu, start=None, **args):
        """One observation of a worker-thread phase, wall and thread-CPU
        seconds, and, where ``start`` (the stamp it began at) is given and
        telemetry is on, its ``scheduler/<phase>`` span from the wall
        stamps."""
        _m.decode_scheduler_phase_seconds.labels(phase=phase).observe(wall)
        _m.decode_scheduler_phase_cpu_seconds.labels(phase=phase).inc(cpu)
        if start is not None and _obs._ENABLED:
            _obs.tracer.complete('scheduler/' + phase, start, start + wall,
                                 **args)

    def _leaf_begins(self, wall, cpu):
        """A leaf of the worker thread's time (admit, an engine call's
        phases, emit, a wait) begins at these stamps: what lies between the
        last leaf's end and it is the thread's bookkeeping, one stretch of
        the iteration's ``book``."""
        last, last_cpu = self._leaf
        if wall > last:
            self._book.append((last, wall, cpu - last_cpu))

    def _engine_returned(self, t0):
        """An engine call that began at ``t0`` has returned: its seconds go
        to the iteration's ``engine`` time, and the thread's last leaf now
        ends here, where an ``emit`` begins (`_emitted`). The call's own
        phases began after ``t0`` and ended before now
        (``engine.last_call``): the entry before them and the call's record
        and gauges after them are bookkeeping. The thread-CPU clock is not
        read before the call (the call's own clock reads it a few
        microseconds later, and a read is a system call): the ``engine`` CPU
        seconds run from the call's first stamp. A duck-typed engine, or a
        call that kept no clock, is one leaf whole from ``t0``, its CPU
        counted from the last leaf's end. Returns now."""
        t1, cpu1 = _now()
        clock = getattr(self.engine, 'last_call', None)
        if clock is None or clock.start < t0:
            cpu0 = self._leaf[1]
            self._leaf_begins(t0, cpu0)
        else:
            cpu0 = clock.start_cpu
            self._leaf_begins(clock.start, cpu0)
            self._leaf = (clock.last, clock.last_cpu)
            self._leaf_begins(t1, cpu1)
        self._engine_s += t1 - t0
        self._engine_cpu += cpu1 - cpu0
        self._leaf = (t1, cpu1)
        return t1

    def _touch(self, stream):
        """Notes a stream for the next hand-off, once however many tokens a
        step gave it (they come one after the other)."""
        if not self._touched or self._touched[-1] is not stream:
            self._touched.append(stream)

    def _fail_stream(self, stream, exc):
        self._book_tokens()
        stream._fail(exc)
        self._touch(stream)

    def _book_tokens(self):
        """Books the tokens emitted since the last call: once a step and
        before any stream ends, so whoever sees a stream done reads a
        counter that holds its tokens."""
        n, self._unbooked = self._unbooked, 0
        if n:
            _m.decode_tokens_generated.inc(n)
            _dobs.series('tokens').observe(1.0, times=n)

    def _hand_off(self):
        """Gives the streams touched since the last call to ``stream_sink``
        in ONE call: one put and one wake for a step's tokens, where every
        token used to wake its connection's thread (PERF.md section 6, PR
        35). Every path that touches a stream ends here: an ``emit``
        (`_emitted`), a loop iteration's failures and expiries
        (`_run_cycles`), the fail-fast close (`_fail_all_locked`)."""
        self._book_tokens()
        touched, self._touched = self._touched, []
        if touched and self.stream_sink is not None:
            self.stream_sink(touched)

    def _emitted(self):
        """Closes the ``emit`` that began where the last engine call
        returned, with its hand-off."""
        self._hand_off()
        t1, cpu1 = self._leaf
        self._leaf = _now()
        self._phase('emit', self._leaf[0] - t1, self._leaf[1] - cpu1,
                    start=t1)

    def _blocked(self, wait, *args):
        """``wait(*args)``, a blocking call of the idle worker, as its
        ``wait`` leaf."""
        t0, cpu0 = _now()
        self._leaf_begins(t0, cpu0)
        out = wait(*args)
        self._leaf = _now()
        self._phase('wait', self._leaf[0] - t0, self._leaf[1] - cpu0)
        return out

    def _prefill(self, req):
        now = time.perf_counter()
        _m.decode_queue_wait_seconds.observe(now - req.enqueued_perf)
        self._trace_span(req, 'replica/queue_wait', req.enqueued_perf, now)
        cached = getattr(req.table, 'cached_len', 0)
        if cached:
            # prefix-cache hit: the front of the table is already-filled
            # shared blocks; the uncached suffix rides the SAME lockstep
            # decode step as everyone else's generation (chunked prefill —
            # the same program, so the same rows as any other step), so a
            # long shared prompt costs only its suffix
            req.table.context_len = cached
            req.next_token = req.prompt[cached]
            req.pending_prompt = collections.deque(req.prompt[cached + 1:])
            req.prefilling = True
            return
        if self.disagg is not None and req.sampler is None:
            # cache miss under disaggregation: ship the prompt to a
            # prefill-role replica; this slot stays inactive (and the
            # decode loop keeps stepping) until the KV payload lands.
            # Sampled requests prefill INLINE — the handoff payload carries
            # a greedy first token, not logits, so the draw must happen
            # here where the row is
            req.handoff_pending = True
            req.handoff_t0 = time.perf_counter()
            self.disagg.submit(req, req.prompt, req.max_new_tokens)
            return
        if self._window > 1:
            return self._prefill_window(req)
        t0 = time.perf_counter()
        try:
            if req.sampler is None:     # kwarg-free call: duck-typed
                first = self.engine.prefill(req.prompt, req.table)
            else:
                first = self.engine.prefill(
                    req.prompt, req.table,
                    sampler=lambda row: self._pick_token(req, row))
        except Exception as e:
            self._fail_request(req, e)
            self._record_engine_failure()
            return
        t1 = self._engine_returned(t0)
        self._trace_span(req, 'replica/prefill', t0, t1,
                         prompt_len=len(req.prompt))
        self._record_spans()
        self.breaker.record_success()
        self._publish(req)
        self._emit_token(req, first)
        self._emitted()

    def _prefill_window(self, req):
        """A window model's admission: the prompt's whole blocks are
        prefilled (nothing is picked) and the first block is opened with
        the prompt's tail fixed in its first positions."""
        t0 = time.perf_counter()
        try:
            self.engine.prefill(req.prompt, req.table)
        except Exception as e:
            self._fail_request(req, e)
            self._record_engine_failure()
            return
        t1 = self._engine_returned(t0)
        self._trace_span(req, 'replica/prefill', t0, t1,
                         prompt_len=len(req.prompt))
        self._record_spans()
        self.breaker.record_success()
        tail = req.prompt[req.table.context_len:]
        self._open_block(self._slots.index(req), req, tail)

    def _open_block(self, slot, req, fixed=()):
        """Slot ``slot``'s next block: ``fixed`` tokens (a prompt's tail, in
        the first block alone) then masks, and the positions a denoising
        forward of it unmasks."""
        n = len(fixed)
        self._blocks[slot, :n] = fixed
        self._blocks[slot, n:] = self.engine.model.mask_token_id
        self._masked[slot, :n] = False
        self._masked[slot, n:] = True
        self._quota[slot] = denoise_quota(self._window - n,
                                          req.denoising_steps)
        req.block_from = n
        req.block_t0 = None

    def _drain_handoffs(self, timeout=0.0):
        """Apply finished prefill handoffs: inject the KV payload into the
        decode pool (worker thread — the engine has ONE owner) and emit the
        first token. Payloads for requests already failed/closed are
        dropped (their table is gone)."""
        if self.disagg is None:
            return
        if timeout:
            completed = self._blocked(self.disagg.drain_completed, timeout)
        else:
            completed = self.disagg.drain_completed(timeout)
        for req, payload, exc in completed:
            if req not in self._slots or req.table is None:
                continue              # failed or closed while in flight
            req.handoff_pending = False
            if exc is not None:
                self._fail_request(req, exc)
                self._record_engine_failure()
                continue
            t0 = time.perf_counter()
            try:
                first = self.engine.inject_prefill(req.table, payload)
            except Exception as e:
                self._fail_request(req, e)
                self._record_engine_failure()
                continue
            t1 = self._engine_returned(t0)
            if req.handoff_t0 is not None:
                self._trace_span(req, 'replica/handoff_wait',
                                 req.handoff_t0, t1,
                                 prompt_len=len(req.prompt))
            self.breaker.record_success()
            self._publish(req)
            self._emit_token(req, first)
            self._emitted()

    def _record_engine_failure(self):
        """Book one engine-failure batch with the breaker; on a trip, fail
        everything still waiting — it would only burn its deadline against
        a broken engine (in-flight slots were already failed by
        isolation)."""
        if not self.breaker.record_failure():
            return
        exc = EngineUnhealthy('decode engine',
                              self.breaker.consecutive_failures)
        with self._cv:
            failed = len(self._waiting)
            while self._waiting:
                self._fail_stream(self._waiting.popleft().stream, exc)
            _m.decode_queue_depth.set(0)
        if failed:
            _m.decode_requests_failed.inc(failed)

    def _pick_token(self, req, row):
        """Next token from a logits row the host was handed: the request's
        deterministic sampler (indexed by tokens generated so far — the
        replay contract) or exact greedy argmax (the speculative accept
        loop's rows alone: a prefill's and a lockstep step's greedy picks
        are the engine program's own)."""
        if req.sampler is not None:
            tok = req.sampler.sample(row, req.generated)
            _m.decode_tokens_sampled.inc()
            return int(tok)
        return int(row.argmax())

    def _emit_token(self, req, token):
        """Account one sampled token; marks the request finished when it
        hits eos or its budget. The token still needs to be FED to the next
        decode step (its K/V are uncached) unless the request finished."""
        req.generated += 1
        req.history.append(int(token))
        req.stream._emit(token)
        self._touch(req.stream)
        self._unbooked += 1
        if req.generated == 1:
            ttft = time.perf_counter() - req.enqueued_perf
            _m.decode_ttft_seconds.observe(ttft)
            _dobs.series('ttft').observe(ttft)
        if req.eos_id is not None and int(token) == int(req.eos_id):
            self._retire(req, 'stop')
        elif req.generated >= req.max_new_tokens:
            self._retire(req, 'length')
        else:
            req.next_token = int(token)

    def _retire(self, req, reason):
        self.engine.release_table(req.table)
        req.table = None
        self._slots[self._slots.index(req)] = None
        self._book_tokens()
        req.stream._finish(reason)
        self._touch(req.stream)
        _m.decode_requests_completed.inc()

    def _fail_request(self, req, exc):
        if req.table is not None:
            self.engine.release_table(req.table)
            req.table = None
        if req in self._slots:
            self._slots[self._slots.index(req)] = None
        _m.decode_requests_failed.inc()
        self._fail_stream(req.stream, exc if isinstance(exc, ServingError)
                          else ServingError(
                              f'generation failed: '
                              f'{type(exc).__name__}: {exc}'))

    def _step(self):
        """One lockstep decode step over the current slots. Handoff-pending
        slots are inactive lanes (scratch reads); suffix-filling slots feed
        their next PROMPT token and their sampled output is discarded until
        the prompt is exhausted — the step after the last prompt token
        yields the first generated token."""
        live = [r for r in self._slots if r is not None]
        active = [r for r in live if not r.handoff_pending]
        if not active:
            return bool(live)         # only pending handoffs: work remains
        tokens = [r.next_token if r is not None and not r.handoff_pending
                  else None for r in self._slots]
        tables = [r.table if r is not None and not r.handoff_pending
                  else None for r in self._slots]
        # greedy-only batches take the original call (byte-identical path);
        # a sampled slot that will EMIT this step needs its logits row
        rows = None
        need_rows = any(r.sampler is not None and not r.prefilling
                        for r in active)
        t0 = time.perf_counter()
        try:
            if need_rows:
                out, rows = self.engine.decode_step(tokens, tables,
                                                    return_rows=True)
            else:
                out = self.engine.decode_step(tokens, tables)
        except Exception as e:
            for req in active:      # isolate: fail the batch, keep serving
                self._fail_request(req, e)
            self._record_engine_failure()
            return True
        t1 = self._engine_returned(t0)
        self.breaker.record_success()
        self._record_spans()
        for i, req in enumerate(self._slots):
            if req is None or req.handoff_pending:
                continue
            if req.prefilling:
                if req.pending_prompt:
                    req.next_token = req.pending_prompt.popleft()
                    continue          # still feeding the prompt suffix
                # the step above consumed the LAST prompt token: its whole
                # K/V is now cached — publish, then emit the first token
                req.prefilling = False
                self._publish(req)
            if req.sampler is not None and rows is not None:
                self._emit_token(req, self._pick_token(req, rows[i]))
            else:
                self._emit_token(req, int(out[i]))
            if req.trace is not None:
                self._trace_span(req, 'replica/token', t0, t1,
                                 index=req.generated - 1)
        self._emitted()
        return True

    def _window_step(self):
        """One lockstep step of a window model over the current slots: a
        slot whose block still has masked positions denoises (the engine
        unmasks its most confident ones in place), a slot whose block is
        finished commits. A commit emits the block's answer tokens in
        position order, up to the length asked for, and opens the next
        block or retires the request; a denoising forward emits nothing."""
        active = [r for r in self._slots if r is not None]
        if not active:
            return False
        tables = [None if r is None else r.table for r in self._slots]
        commits = [r is not None and not self._masked[i].any()
                   for i, r in enumerate(self._slots)]
        t0 = time.perf_counter()
        for req in active:
            if req.block_t0 is None:
                req.block_t0 = t0
        try:
            self.engine.window_step(self._blocks, self._masked, self._quota,
                                    tables, commits)
        except Exception as e:
            for req in active:      # isolate: fail the batch, keep serving
                self._fail_request(req, e)
            self._record_engine_failure()
            return True
        t1 = self._engine_returned(t0)
        self.breaker.record_success()
        self._record_spans()
        for i, req in enumerate(self._slots):
            if req is None or not commits[i]:
                continue
            _m.decode_block_seconds.observe(t1 - req.block_t0)
            first = req.generated
            for token in self._blocks[i, req.block_from:].tolist():
                self._emit_token(req, token)
                if req.table is None:
                    break             # retired (eos / budget) mid-block
            _m.decode_diffusion_tokens_committed.inc(req.generated - first)
            if req.trace is not None:
                self._trace_span(req, 'replica/token', t0, t1,
                                 index=req.generated - 1,
                                 emitted=req.generated - first)
            if req.table is not None:
                self._open_block(i, req)
        self._emitted()
        return True

    def _spec_step(self):
        """One speculative (S, k) verify round (engine.spec_enabled).

        Greedy slots feed their pending token plus up to k-1 drafter
        guesses; the target model's (S, k, V) rows verify them all in ONE
        step and the longest prefix the target agrees with is emitted
        (row j is the lockstep step's row at the same context, computed by
        the (S, k) program: equal up to its rounding, and the emitted
        stream equals non-speculative greedy in every test, engine.py's
        contract). Rejected tails roll
        the block table back — one integer store; the stale K/V positions
        are masked until overwritten (kv_cache scratch contract). Sampled
        slots ride the same batched step with a single fed token (their
        draw stays exact + replayable); suffix-filling slots feed up to k
        prompt tokens per round (chunked prefill, k× fewer steps)."""
        live = [r for r in self._slots if r is not None]
        active = [r for r in live if not r.handoff_pending]
        if not active:
            return bool(live)
        K = self.engine.spec_k
        fed = [None] * len(self._slots)
        tables = [None] * len(self._slots)
        bases = [0] * len(self._slots)
        for i, req in enumerate(self._slots):
            if req is None or req.handoff_pending:
                continue
            tables[i] = req.table
            bases[i] = req.table.context_len
            if req.prefilling:
                toks = [req.next_token]
                while len(toks) < K and req.pending_prompt:
                    toks.append(req.pending_prompt.popleft())
            elif req.sampler is not None:
                toks = [req.next_token]
            else:
                # never draft past the budget: the last verify round feeds
                # exactly the remaining token allowance
                budget = req.max_new_tokens - req.generated
                n = min(K, max(budget, 1)) - 1
                drafts = []
                if n > 0 and self.drafter is not None:
                    # the draft model shares the process-global no_grad
                    # flag with the engine models — serialize under the
                    # same lock disaggregation uses (None → no-op)
                    with (getattr(self.engine, '_model_lock', None)
                          or contextlib.nullcontext()):
                        drafts = [int(t) for t in self.drafter.propose(
                            req.history, n)][:n]
                toks = [req.next_token] + drafts
            fed[i] = toks
        t0 = time.perf_counter()
        try:
            rows = self.engine.spec_step(fed, tables)
        except Exception as e:
            for req in active:      # isolate: fail the batch, keep serving
                self._fail_request(req, e)
            self._record_engine_failure()
            return True
        t1 = self._engine_returned(t0)
        self.breaker.record_success()
        self._record_spans()
        for i, req in enumerate(self._slots):
            if req is None or req.handoff_pending:
                continue
            toks = fed[i]
            f = len(toks)
            if req.prefilling:
                if req.pending_prompt:
                    req.next_token = req.pending_prompt.popleft()
                    continue          # all fed prompt tokens stay cached
                req.prefilling = False
                self._publish(req)
                self._emit_token(req, self._pick_token(req, rows[i, f - 1]))
                continue
            drafted = f - 1
            emitted = 0
            j = 0
            while j < f:
                tok = self._pick_token(req, rows[i, j])
                self._emit_token(req, tok)
                emitted += 1
                if req.table is None:
                    break             # retired (eos / budget) mid-round
                if j + 1 < f and int(toks[j + 1]) == tok:
                    j += 1            # draft confirmed; keep verifying
                    continue
                break                 # first rejection (or window end)
            if req.table is not None:
                # commit the accepted prefix, roll back the rejected tail
                req.table.context_len = bases[i] + emitted
            self._trace_span(req, 'replica/verify_round', t0, t1,
                             fed=f, emitted=emitted)
            _m.decode_spec_accept_len.observe(emitted)
            if drafted:
                self._spec_drafted += drafted
                self._spec_accepted += emitted - 1
                _m.decode_spec_draft_tokens.inc(drafted)
                if emitted > 1:
                    _m.decode_spec_accepted_tokens.inc(emitted - 1)
                _m.decode_spec_acceptance.set(
                    self._spec_accepted / max(self._spec_drafted, 1))
        self._emitted()
        return True

    def _fail_all_locked(self):
        """Fail-fast shutdown: error every waiting and in-flight request.
        Runs on the WORKER thread (slot state is worker-owned; the close()
        caller only raises the abort flag), so no step can race a release."""
        while self._waiting:
            self._fail_stream(self._waiting.popleft().stream, EngineClosed(
                'decode scheduler shut down before this request ran'))
        _m.decode_queue_depth.set(0)
        for i, req in enumerate(self._slots):
            if req is not None:
                self.engine.release_table(req.table)
                req.table = None
                self._slots[i] = None
                self._fail_stream(req.stream, EngineClosed(
                    'decode scheduler shut down mid-generation'))
        _m.decode_slots_active.set(0)
        self._hand_off()

    def _worker_loop(self):
        try:
            self._run_cycles()
        finally:
            self._record_spans()        # what the last iteration noted

    def _run_cycles(self):
        """The worker thread's life, one iteration (``cycle``) after another:
        each is observed whole and by phase (``_phase``), wall and
        thread-CPU seconds, so the thread's self time is cycle - wait -
        engine; iterations that admitted or ran an engine call also leave
        ``scheduler/cycle|admit|emit|book`` spans, which contain the
        engine's own (one thread: containment is the tree). ``emit`` is what
        follows an engine call that returned tokens, once per call; ``book``
        is every stretch between two leaves (`_leaf_begins`), observed as
        one sum an iteration and as one span a stretch. The leaves tile the
        cycle: cycle = admit + the engine calls' phases + emit + book +
        wait. A cycle runs from the loop's top to the stamp before its own
        ``book`` and ``cycle`` observations, as it did before there was a
        ``book``: what lies between two cycles (those observations and the
        iteration's span writes) is one more ``scheduler/book`` span, under
        no cycle and in no phase's sum, so that the leaves' spans tile the
        thread's life."""
        cycle = 0
        self._leaf = _now()
        while True:
            between = self._leaf[0]
            t0, cpu0 = self._leaf = _now()
            with self._cv:
                if self._closing and self._abort:
                    self._fail_all_locked()
                    break
                self._expire_waiting(time.monotonic())
                admitted = self._admit_locked()
            t_admit, cpu_admit = self._leaf = _now()
            for req in admitted:
                self._prefill(req)
            # finished prefill handoffs join before the step; when ONLY
            # handoffs are in flight, block briefly on the completion
            # queue instead of spinning the loop hot
            only_pending = (self.disagg is not None
                            and any(r is not None and r.handoff_pending
                                    for r in self._slots)
                            and all(r is None or r.handoff_pending
                                    for r in self._slots))
            self._drain_handoffs(0.01 if only_pending else 0.0)
            if self._window > 1:
                stepped = self._window_step()
            elif getattr(self.engine, 'spec_enabled', False):
                stepped = self._spec_step()
            else:
                stepped = self._step()
            self._hand_off()            # what failed or expired outside an emit
            if not stepped and not admitted:
                self._record_spans()    # idle: nothing else will
                with self._cv:
                    if self._closing:
                        if self._abort:
                            self._fail_all_locked()
                        if not self._waiting:
                            break
                    else:
                        self._blocked(self._cv.wait, 0.05)
            engine_s, self._engine_s = self._engine_s, 0.0
            engine_cpu, self._engine_cpu = self._engine_cpu, 0.0
            worked = bool(admitted) or engine_s > 0
            cycle += worked
            self._phase('admit', t_admit - t0, cpu_admit - cpu0,
                        start=t0 if worked else None)
            if engine_s:
                self._phase('engine', engine_s, engine_cpu)
            t_end, cpu_end = _now()
            self._leaf_begins(t_end, cpu_end)   # the iteration's tail
            self._leaf = (t_end, cpu_end)
            book, self._book = self._book, []
            self._phase('book', sum(b - a for a, b, _ in book),
                        sum(cpu for _, _, cpu in book))
            if worked and _obs._ENABLED:
                for a, b in [(between, t0)] + [s[:2] for s in book]:
                    _obs.tracer.complete('scheduler/book', a, b)
            self._phase('cycle', t_end - t0, cpu_end - cpu0,
                        start=t0 if worked else None,
                        cycle=cycle, admitted=len(admitted),
                        slots_active=sum(r is not None for r in self._slots))

    # -- lifecycle ---------------------------------------------------------
    def close(self, drain=True, timeout=None):
        """Stop admission; ``drain=True`` runs every admitted AND waiting
        generation to completion, ``drain=False`` fails waiting requests
        and in-flight generations fast with EngineClosed (the failing
        itself happens on the worker thread — slot state has one owner)."""
        with self._cv:
            first = not self._closing
            self._closing = True
            if first:
                self._abort = not drain
            elif not drain:
                # escalation: a drain already in progress is converted to
                # fail-fast (server.py's SIGTERM drain-timeout cap)
                self._abort = True
            self._cv.notify_all()
        if self._worker.is_alive():
            self._worker.join(timeout)
        self._closed = True

    @property
    def closed(self):
        return self._closed

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
