"""Stdlib HTTP front end for the serving subsystem.

A ``ThreadingHTTPServer`` (one handler thread per connection — a handler
thread parses a request, submits it, and sleeps until its reply is ready: on a
future for ``/predict``, on the ONE stream writer thread for a streamed
``/generate``, which writes every connection's token lines, serving/
stream_writer.py; all device work stays on the batcher's and the decode
scheduler's single workers) exposing:

- ``POST /predict`` — body ``{"inputs": {name: nested-list}, "timeout_ms":
  optional}`` (or inputs as a list in feed order). Reply ``{"outputs":
  {fetch_name: nested-list}, "rows": n, "latency_ms": ...}``. Typed errors
  map to status codes: InvalidRequest→400, Overloaded→429 (backpressure —
  clients retry with backoff), DeadlineExceeded→504, EngineClosed→503,
  anything else→500. Every error body is ``{"error": type, "message": ...}``.
- ``GET /healthz`` — 200 ``{"status": "ok"}`` while serving, 503
  ``{"status": "draining"}`` once shutdown begins (load-balancer eviction).
- ``GET /metrics`` — Prometheus text exposition from the shared
  observability registry (serving_* series plus anything telemetry
  collected).

Run one from the CLI::

    python -m paddle_tpu.serving.server --model-dir /path/to/model \
        --port 8080 --max-batch-size 16 --batch-timeout-ms 2

Shutdown (SIGINT / :meth:`ServingServer.shutdown`) is graceful: healthz
flips to draining, the batcher drains every admitted request, then the
listener stops.
"""
from __future__ import annotations

import json
import logging
import os
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from . import metrics as _m
from .batcher import (DEFAULT_BATCH_TIMEOUT_MS, DEFAULT_QUEUE_DEPTH,
                      MicroBatcher)
from .engine import InferenceEngine
from .errors import (DeadlineExceeded, EngineClosed, EngineUnhealthy,
                     InvalidRequest, Overloaded)
from .stream_writer import StreamWriter
from .. import observability as _obs
from ..log_helper import get_logger
from ..observability import TraceContext
from ..observability import distributed as _dobs

__all__ = ['ServingServer', 'create_server']

_logger = get_logger(
    __name__, logging.INFO,
    fmt='%(asctime)s-%(levelname)s: [serving] %(message)s')

MAX_BODY_BYTES = 64 * 1024 * 1024

_STATUS_BY_ERROR = ((InvalidRequest, 400), (Overloaded, 429),
                    (DeadlineExceeded, 504), (EngineUnhealthy, 503),
                    (EngineClosed, 503))

# /generate request schema: unknown keys are a 400 naming the field (a
# typo'd sampling knob silently dropped would serve greedy while the
# client believes it set temperature)
_SAMPLING_KEYS = frozenset(('temperature', 'top_k', 'top_p', 'seed'))
_GENERATE_KEYS = frozenset(('prompt', 'max_new_tokens', 'eos_id', 'stream',
                            'timeout_ms', 'request_id',
                            'denoising_steps')) | _SAMPLING_KEYS


class _Handler(BaseHTTPRequestHandler):
    protocol_version = 'HTTP/1.1'
    server_version = 'paddle-tpu-serving'

    # BaseHTTPRequestHandler writes access logs to stderr with print-style
    # formatting; route through log_helper instead (never print)
    def log_message(self, fmt, *args):
        _logger.debug('%s %s', self.address_string(), fmt % args)

    def _reply(self, code, body, content_type='application/json'):
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
        self.send_response(code)
        self.send_header('Content-Type', content_type)
        self.send_header('Content-Length', str(len(data)))
        self.end_headers()
        try:
            self.wfile.write(data)
        except (BrokenPipeError, ConnectionResetError):
            pass                      # client went away; nothing to salvage
        _m.http_responses.labels(code=code).inc()

    def _error(self, code, exc):
        self._reply(code, {'error': type(exc).__name__, 'message': str(exc)})

    def do_GET(self):
        srv = self.server.serving
        if self.path == '/healthz':
            # unix_time rides every healthz reply: the router's poll uses
            # it for the clock-offset handshake that aligns this process's
            # trace spans onto the router's timeline (trace_merge.py)
            if srv.draining:
                self._reply(503, {'status': 'draining',
                                  'unix_time': time.time()})
            elif srv.breaker_states():
                # a tripped (or probing) circuit breaker: this replica is
                # alive but should not receive traffic — 503 'degraded'
                # evicts it from the balancer until the probe closes the
                # breaker again (docs/SERVING.md "Circuit breaker")
                self._reply(503, {'status': 'degraded',
                                  'breakers': srv.breaker_states(),
                                  'unix_time': time.time()})
            else:
                body = {'status': 'ok', 'replica': srv.replica_id,
                        'warmup': srv.warmup_status(),
                        'unix_time': time.time()}
                if srv.engine is not None:
                    body['buckets'] = srv.engine.buckets
                    body['compiled'] = srv.engine.compiled_buckets
                if srv.generator is not None:
                    # the always-on windowed load series ride every
                    # healthz reply: the router caches them per replica
                    # and the elastic autoscaler reads queue_depth /
                    # occupancy / ttft p99 off that cache — no second
                    # scrape channel (docs/SERVING.md "Autoscaler")
                    body['series'] = {
                        name: _dobs.series(name).snapshot()
                        for name in ('queue_depth', 'occupancy', 'ttft')}
                    eng = srv.generator.engine
                    body['decode'] = {
                        'slots': eng.slots,
                        'active': srv.generator.active(),
                        'waiting': srv.generator.pending(),
                        'cache_blocks_used': eng.pool.allocator.used,
                        'cache_blocks_total': eng.pool.allocator.capacity,
                        'prompt_buckets': eng.prompt_buckets,
                    }
                slo = srv.slo_status()
                if slo is not None:
                    body['slo'] = slo
                self._reply(200, body)
        elif self.path == '/metrics':
            from ..observability import registry
            self._reply(200, registry.prometheus_text().encode(),
                        content_type='text/plain; version=0.0.4')
        else:
            self._reply(404, {'error': 'NotFound', 'message': self.path})

    def _read_json_body(self):
        """Parse the request body; returns the payload dict or None after
        replying with the 4xx itself."""
        try:
            length = int(self.headers.get('Content-Length') or 0)
        except ValueError:
            length = -1
        if length <= 0:
            self._error(400, InvalidRequest('missing request body'))
            return None
        if length > MAX_BODY_BYTES:
            self._error(413, InvalidRequest(
                f'body of {length} bytes exceeds {MAX_BODY_BYTES}'))
            return None
        try:
            payload = json.loads(self.rfile.read(length))
        except (ValueError, UnicodeDecodeError) as e:
            self._error(400, InvalidRequest(f'bad JSON body: {e}'))
            return None
        if not isinstance(payload, dict):
            self._error(400, InvalidRequest('body must be a JSON object'))
            return None
        return payload

    def do_POST(self):
        if self.path == '/generate':
            return self._do_generate()
        if self.path != '/predict':
            return self._reply(404, {'error': 'NotFound',
                                     'message': self.path})
        srv = self.server.serving
        if srv.batcher is None:
            return self._reply(404, {
                'error': 'NotFound',
                'message': 'no predict engine configured (decode-only '
                           'server; use POST /generate)'})
        try:
            length = int(self.headers.get('Content-Length') or 0)
        except ValueError:
            length = -1
        if length <= 0:
            return self._error(400, InvalidRequest('missing request body'))
        if length > MAX_BODY_BYTES:
            return self._error(413, InvalidRequest(
                f'body of {length} bytes exceeds {MAX_BODY_BYTES}'))
        try:
            payload = json.loads(self.rfile.read(length))
        except (ValueError, UnicodeDecodeError) as e:
            return self._error(400, InvalidRequest(f'bad JSON body: {e}'))
        if not isinstance(payload, dict) or 'inputs' not in payload:
            return self._error(400, InvalidRequest(
                'body must be {"inputs": {...}, "timeout_ms": optional}'))
        timeout_ms = payload.get('timeout_ms')
        if timeout_ms is not None and not isinstance(timeout_ms, (int, float)):
            return self._error(400, InvalidRequest(
                f'timeout_ms must be a number, got {timeout_ms!r}'))
        t0 = time.perf_counter()
        try:
            fut = srv.batcher.submit(payload['inputs'], timeout_ms)
            outs = fut.result(srv.request_timeout)
        except tuple(e for e, _ in _STATUS_BY_ERROR) as e:
            for etype, code in _STATUS_BY_ERROR:
                if isinstance(e, etype):
                    return self._error(code, e)
        except TimeoutError as e:
            return self._error(504, e)
        except Exception as e:     # engine/internal failure: a 500, not a hang
            _logger.error('predict failed: %s: %s', type(e).__name__, e)
            return self._error(500, e)
        names = srv.engine.get_output_names()
        self._reply(200, {
            'outputs': {n: np.asarray(o).tolist() for n, o in
                        zip(names, outs)},
            'rows': int(np.asarray(outs[0]).shape[0]) if outs else 0,
            'latency_ms': round((time.perf_counter() - t0) * 1e3, 3)})

    def _do_generate(self):
        """POST /generate — stateful streaming generation (docs/SERVING.md
        "Stateful decode"). Body::

            {"prompt": [token ids], "max_new_tokens": 16,
             "eos_id": optional, "stream": true, "timeout_ms": optional,
             "temperature": 0.0, "top_k": 0, "top_p": 1.0,
             "seed": optional, "request_id": optional,
             "denoising_steps": optional}

        ``denoising_steps`` is a window model's alone (block diffusion,
        docs/SERVING.md "Window models"): the denoising forwards a block of
        this request takes, 1..B, the replica's default where absent; on
        any other model it is a 400.

        Sampling keys are validated typed (serving/decode/sampling.py):
        a bad value OR an unknown body key is a 400 naming the field —
        a typo'd knob must never be silently dropped. Sampled streams
        replay bitwise from ``request_id`` (or ``seed``); greedy
        (temperature 0, the default) is exact argmax.

        ``stream=true`` (default) replies 200 with chunked NDJSON: one
        ``{"token": id, "index": i}`` line per decoded token, then a final
        ``{"done": true, "finish_reason": ..., "tokens": [...],
        "latency_ms": ...}`` line. A failure after streaming began arrives
        as an ``{"error": ..., "message": ...}`` line (the 200 status is
        already on the wire — chunked streaming's standard caveat).
        ``stream=false`` blocks and returns the whole generation as one
        JSON reply. Pre-admission failures map like /predict:
        InvalidRequest→400, Overloaded→429, DeadlineExceeded→504,
        EngineClosed→503.

        The handler thread's own CPU seconds for the request, entry to last
        byte written, go to ``http_handler_cpu_seconds``: once a request,
        nothing per token (a streamed reply's lines are the stream writer's,
        which books its own CPU seconds there a turn of its loop). With
        telemetry on an answered generation leaves one ``http/generate``
        span on this thread's ``tid``, from its submit to here."""
        cpu0 = time.thread_time()
        answered = self._generate()
        cpu = time.thread_time() - cpu0
        _m.http_handler_cpu_seconds.inc(cpu)
        if answered is not None and _obs._ENABLED:
            t0, stream = answered
            _obs.tracer.complete(
                'http/generate', t0, time.perf_counter(),
                request_id=stream.request_id, tokens=len(stream.tokens),
                cpu_us=cpu * 1e6)

    def _generate(self):
        """`_do_generate`'s request, parsed, submitted and answered;
        ``(perf_counter at its submit, its stream)`` once the generation
        was answered with a 200, else None."""
        srv = self.server.serving
        if srv.generator is None:
            return self._reply(404, {
                'error': 'NotFound',
                'message': 'no decode engine configured (predict-only '
                           'server; use POST /predict)'})
        payload = self._read_json_body()
        if payload is None:
            return
        prompt = payload.get('prompt')
        if not isinstance(prompt, list):
            return self._error(400, InvalidRequest(
                'body must include "prompt": [token ids]'))
        unknown = sorted(set(payload) - _GENERATE_KEYS)
        if unknown:
            return self._error(400, InvalidRequest(
                f'unknown request field(s): {", ".join(unknown)}; '
                f'supported: {", ".join(sorted(_GENERATE_KEYS))}'))
        sampling = {k: payload[k] for k in _SAMPLING_KEYS if k in payload}
        try:
            # distributed trace carrier (docs/OBSERVABILITY.md): absent
            # header = untraced (one dict get); malformed = client bug, 400
            trace = TraceContext.from_headers(self.headers)
        except ValueError as e:
            return self._error(400, InvalidRequest(str(e)))
        t0 = time.perf_counter()
        # handed on only where the body has it: a generator that is not a
        # DecodeScheduler need not know the key
        extra = {'denoising_steps': payload['denoising_steps']} \
            if 'denoising_steps' in payload else {}
        try:
            stream = srv.generator.submit(
                prompt,
                max_new_tokens=payload.get('max_new_tokens', 16),
                eos_id=payload.get('eos_id'),
                timeout_ms=payload.get('timeout_ms'),
                sampling=sampling or None,
                request_id=payload.get('request_id'),
                trace=trace, **extra)
        except tuple(e for e, _ in _STATUS_BY_ERROR) as e:
            for etype, code in _STATUS_BY_ERROR:
                if isinstance(e, etype):
                    return self._error(code, e)
        except Exception as e:
            _logger.error('generate failed: %s: %s', type(e).__name__, e)
            return self._error(500, e)

        if payload.get('stream', True) is False:
            try:
                toks = stream.result(srv.request_timeout)
            except tuple(e for e, _ in _STATUS_BY_ERROR) as e:
                for etype, code in _STATUS_BY_ERROR:
                    if isinstance(e, etype):
                        return self._error(code, e)
            except TimeoutError as e:
                return self._error(504, e)
            except Exception as e:
                _logger.error('generate failed: %s: %s',
                              type(e).__name__, e)
                return self._error(500, e)
            self._reply(200, {
                'tokens': toks, 'finish_reason': stream.finish_reason,
                'latency_ms': round((time.perf_counter() - t0) * 1e3, 3),
                **stream.meta})
            return t0, stream

        # chunked per-token streaming: the token lines, the done (or error)
        # line and the last chunk are the stream writer's; this thread
        # sleeps until they are out (serving/stream_writer.py)
        self.send_response(200)
        self.send_header('Content-Type', 'application/x-ndjson')
        self.send_header('Transfer-Encoding', 'chunked')
        try:
            self.end_headers()
        except (BrokenPipeError, ConnectionResetError):
            self.close_connection = True
        else:
            if not srv.stream_writer.serve(self.connection, stream, t0):
                # the client went away (generation continues server-side)
                # or the server is stopping: not a connection to keep
                self.close_connection = True
        _m.http_responses.labels(code=200).inc()
        return t0, stream


class Listener(ThreadingHTTPServer):
    """The serving tier's HTTP listener: one daemon handler thread per
    connection (it wants the interpreter twice a request, to parse and to
    book: a streamed reply's tokens are written by the server's one stream
    writer while it sleeps), and a listen backlog that holds a burst of
    connections.
    socketserver's default backlog of 5 overflows when a replica's clients
    connect together (128 at once in the benchmark's closed loop) while the
    accept loop waits for the interpreter lock behind a busy scheduler
    thread; the kernel then drops the SYNs, and a client retries after 1, 3,
    7, 15, 31, 63 s: the first token of the last client came 4 to 62 s after
    the burst, by chance (PERF.md section 6, PR 29). The kernel caps the
    backlog at net.core.somaxconn."""
    daemon_threads = True
    request_queue_size = 1024


class ServingServer:
    """Engine + batcher + ThreadingHTTPServer, wired and lifecycle-managed.

    Pass an :class:`InferenceEngine` (or a model dir, from which one is
    built). ``port=0`` binds an ephemeral port (tests); read ``.port`` after
    construction.
    """

    def __init__(self, engine, host='127.0.0.1', port=8080,
                 max_batch_size=None, batch_timeout_ms=None, queue_depth=None,
                 default_timeout_ms=None, request_timeout=60.0, warmup=False,
                 generator=None):
        """``generator``: an optional :class:`decode.DecodeScheduler` —
        enables ``POST /generate`` streaming generation beside (or, with
        ``engine=None``, instead of) the stateless ``/predict`` path."""
        if engine is None:
            if generator is None:
                raise ValueError('need an engine, a generator, or both')
            self.engine = None
            self.batcher = None
        else:
            if not isinstance(engine, InferenceEngine):
                engine = InferenceEngine(engine,
                                         max_batch_size=max_batch_size)
            self.engine = engine
            if warmup:
                timings = self.engine.warmup()
                _logger.info('warmed %d buckets: %s', len(timings),
                             {b: round(s, 3) for b, s in timings.items()})
            self.batcher = MicroBatcher(
                engine,
                max_batch_size=max_batch_size,
                batch_timeout_ms=(DEFAULT_BATCH_TIMEOUT_MS
                                  if batch_timeout_ms is None
                                  else batch_timeout_ms),
                queue_depth=(DEFAULT_QUEUE_DEPTH if queue_depth is None
                             else queue_depth),
                default_timeout_ms=default_timeout_ms)
        self.generator = generator
        # the one thread that writes every streamed /generate reply; the
        # scheduler hands it the streams a step touched in one call where
        # it offers the hook (a generator that does not is looked at on a
        # timer)
        self.stream_writer = None
        if generator is not None:
            hooked = hasattr(generator, 'stream_sink')
            self.stream_writer = StreamWriter(request_timeout,
                                              polled=not hooked)
            if hooked:
                generator.stream_sink = self.stream_writer.touched
        if generator is not None and warmup:
            timings = generator.engine.warmup()
            _logger.info('warmed decode engine: %s',
                         {k: round(s, 3) for k, s in timings.items()})
        self.request_timeout = request_timeout
        # PADDLE_TPU_SLO monitor (strict parse fails construction, not the
        # first /healthz) + span-record process label for trace merging
        self._slo = _dobs.SLOMonitor.from_env()
        _dobs.set_process_label(self.replica_id)
        self.draining = False
        self._shutdown_started = False
        self._shutdown_lock = threading.Lock()
        self._old_handlers = {}
        self._httpd = Listener((host, port), _Handler)
        self._httpd.serving = self
        self._thread = None

    @property
    def port(self):
        return self._httpd.server_address[1]

    @property
    def replica_id(self):
        """This serving process's identity (stamped into /healthz and every
        GenerationStream's metadata)."""
        if self.generator is not None:
            return self.generator.replica_id
        return (os.environ.get('PADDLE_TPU_REPLICA_ID')
                or f'replica-{os.getpid()}')

    def warmup_status(self):
        """Per-component compile-warmth for /healthz: the serving-tier
        router refuses to route to a replica whose ``done`` is false, so a
        restart never serves its first requests into a compile cliff.
        ``done`` = every configured component (predict bucket ladder,
        decode prefill ladder + lockstep step shape) is precompiled."""
        status = {}
        if self.engine is not None:
            status['predict'] = self.engine.warmed
        if self.generator is not None:
            status['decode'] = self.generator.engine.warmed
        status['done'] = all(status.values()) if status else False
        return status

    def slo_status(self):
        """Evaluate the PADDLE_TPU_SLO clauses against the live windowed
        series (None when no SLO is configured). Each evaluation also
        drives the slo_ok gauges + slo_breaches burn counters."""
        if self._slo is None:
            return None
        return self._slo.evaluate()

    def breaker_states(self):
        """{component: breaker state} for every NON-closed circuit breaker
        (empty dict = fully healthy)."""
        states = {}
        if self.batcher is not None and \
                self.batcher.breaker.state != 'closed':
            states['predict'] = self.batcher.breaker.state
        if self.generator is not None:
            breaker = getattr(self.generator, 'breaker', None)
            if breaker is not None and breaker.state != 'closed':
                states['decode'] = breaker.state
        return states

    def start(self):
        """Serve in a background thread; returns self."""
        if self.stream_writer is not None:
            self.stream_writer.start()
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name='paddle-tpu-serving-http',
                                        daemon=True)
        self._thread.start()
        _logger.info('serving on %s:%d (buckets %s)',
                     self._httpd.server_address[0], self.port,
                     self.engine.buckets if self.engine else '[decode-only]')
        return self

    def serve_forever(self):
        """Foreground serve (the CLI path). SIGTERM (pod preemption) and
        SIGINT (Ctrl-C) both trigger the graceful, timeout-capped drain —
        see :meth:`install_signal_handlers`."""
        _logger.info('serving on %s:%d (buckets %s)',
                     self._httpd.server_address[0], self.port,
                     self.engine.buckets if self.engine else '[decode-only]')
        try:
            self.install_signal_handlers()
        except ValueError:
            pass                       # not the main thread: Ctrl-C only
        if self.stream_writer is not None:
            self.stream_writer.start()
        try:
            self._httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            self.uninstall_signal_handlers()
            self.shutdown()

    def install_signal_handlers(self, signals=(signal.SIGTERM, signal.SIGINT)):
        """SIGTERM-safe shutdown (docs/RESILIENCE.md): on signal, /healthz
        flips to draining immediately (load-balancer eviction) and a
        background thread runs the graceful ``shutdown(drain=True)`` — the
        handler itself returns right away (signal context must stay cheap).
        The drain is capped by ``PADDLE_TPU_DRAIN_TIMEOUT_S`` (default 30);
        past the cap, remaining queued work fails fast with EngineClosed
        rather than holding the pod through its kill grace period.

        Must be called from the main thread; returns self. The CLI path
        (`serve_forever`) installs these automatically."""
        self._old_handlers = {}
        for s in signals:
            self._old_handlers[s] = signal.signal(s, self._on_signal)
        return self

    def uninstall_signal_handlers(self):
        for s, old in getattr(self, '_old_handlers', {}).items():
            try:
                signal.signal(s, old)
            except (ValueError, TypeError):
                pass
        self._old_handlers = {}

    def _on_signal(self, signum, frame):
        _logger.warning('signal %d: draining (healthz now 503)', signum)
        self.draining = True           # visible to /healthz immediately
        threading.Thread(target=self.shutdown, kwargs={'drain': True},
                         name='paddle-tpu-serving-drain',
                         daemon=True).start()

    def shutdown(self, drain=True, timeout=None):
        """Graceful stop: healthz flips to draining, admission closes, queued
        requests run to completion (drain=True), then the listener stops.
        `timeout` (default ``PADDLE_TPU_DRAIN_TIMEOUT_S``, 30s) caps the
        drain: components still busy at the deadline are re-closed with
        drain=False, failing their remaining queue fast."""
        with self._shutdown_lock:
            if self._shutdown_started:
                return
            self._shutdown_started = True
        self.draining = True
        if timeout is None:
            timeout = float(
                os.environ.get('PADDLE_TPU_DRAIN_TIMEOUT_S', '') or 30.0)
        deadline = time.monotonic() + timeout
        for comp in (self.batcher, self.generator):
            if comp is None:
                continue
            remaining = max(0.0, deadline - time.monotonic())
            comp.close(drain=drain, timeout=remaining if drain else None)
            if comp._worker.is_alive():
                # drain exceeded its budget: escalate to fail-fast so the
                # process exits inside the kill grace period
                _logger.warning(
                    'drain timeout (%.1fs) exceeded; failing remaining '
                    'queued work fast', timeout)
                comp.close(drain=False, timeout=5)
        if self.stream_writer is not None:
            # every stream has ended by now (drained or failed): the writer
            # takes the hand-offs it still holds, then lets the handlers go
            self.stream_writer.stop()
            if hasattr(self.generator, 'stream_sink'):
                self.generator.stream_sink = None
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None and \
                self._thread is not threading.current_thread():
            self._thread.join(5)
        _logger.info('serving stopped (drained=%s)', drain)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()


def create_server(model_dir_or_config, **kwargs):
    """One-call constructor: ``create_server('/path', port=8080).start()``."""
    return ServingServer(model_dir_or_config, **kwargs)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description='paddle_tpu serving: micro-batched inference over HTTP')
    ap.add_argument('--model-dir', required=True)
    ap.add_argument('--model-filename', default=None)
    ap.add_argument('--params-filename', default=None)
    ap.add_argument('--host', default='0.0.0.0')
    ap.add_argument('--port', type=int, default=8080)
    ap.add_argument('--max-batch-size', type=int, default=None)
    ap.add_argument('--batch-timeout-ms', type=float, default=None)
    ap.add_argument('--queue-depth', type=int, default=None)
    ap.add_argument('--default-timeout-ms', type=float, default=None)
    ap.add_argument('--buckets', default=None,
                    help='comma-separated ladder, e.g. 1,2,4,8,16')
    ap.add_argument('--bf16', action='store_true')
    ap.add_argument('--no-warmup', action='store_true',
                    help='skip precompiling the bucket ladder at startup')
    args = ap.parse_args(argv)

    from ..inference import Config
    cfg = Config(args.model_dir, args.model_filename, args.params_filename)
    if args.bf16:
        cfg.enable_bf16()
    buckets = [int(b) for b in args.buckets.split(',')] if args.buckets \
        else None
    engine = InferenceEngine(cfg, max_batch_size=args.max_batch_size,
                             buckets=buckets)
    ServingServer(engine, host=args.host, port=args.port,
                  max_batch_size=args.max_batch_size,
                  batch_timeout_ms=args.batch_timeout_ms,
                  queue_depth=args.queue_depth,
                  default_timeout_ms=args.default_timeout_ms,
                  warmup=not args.no_warmup).serve_forever()


if __name__ == '__main__':
    main()
