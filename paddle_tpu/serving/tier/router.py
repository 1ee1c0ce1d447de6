"""Multi-replica router: least-loaded dispatch over N engine replicas with
circuit-breaker awareness, cold-replica gating, mid-stream failover, and
rolling restarts behind drain (docs/SERVING.md "Serving tier").

One router process fronts N independent replica processes (each a
``ServingServer`` with a decode scheduler — ``python -m
paddle_tpu.serving.tier.replica`` is the canonical one). The router holds
NO model state: it reads each replica's always-on ``/healthz`` (status,
breaker states, decode load, and the PR-13 ``warmup`` field) on a poll
loop, and dispatches each ``/generate`` to the lowest-loaded routable
replica.

Routability ladder (per replica):

- ``draining`` (router-side, rolling restart) → never routed;
- ``/healthz`` 503 ``degraded`` (circuit breaker open) → drained, EXCEPT a
  breaker reporting ``half_open``: the router routes exactly ONE in-flight
  request there as the probe — success closes the replica's breaker and
  re-admits it (the breaker can only heal if someone feeds it a probe);
- ``warmup.done`` false → not routed (a restarted replica never serves its
  first requests into the compile cliff);
- otherwise routable; ties broken by load = router-side in-flight + the
  replica's reported ``active + waiting``.

Failover contract (the zero-drop rule): a dispatch that fails BEFORE the
first generation event — connection refused, replica died pre-stream, 500,
503 — is transparently retried on the next-best replica (generation is
deterministic greedy, so a retry is idempotent). Once a token has been
forwarded, a replica death surfaces as an error event on that stream: a
dying replica kills only its in-flight streams; everything queued or new
reroutes with zero drops (subprocess kill -9 tested,
tests/framework/test_router_failover.py).

Observability (docs/OBSERVABILITY.md): the router is the trace EDGE —
``maybe_sample()`` decides once per request, the context rides the
``X-PaddleTPU-Trace`` header to the replica, and the router records the
request root / per-attempt dispatch / retry spans around the replica's
spans. ``GET /metrics/fleet`` serves the replicas' merged Prometheus
export (counter-sum / gauge-label / bucket-merge), and the health poll
doubles as the clock handshake trace_merge.py aligns timelines with.

Strict-parse knobs (tier/knobs.py): ``PADDLE_TPU_ROUTER_REPLICAS``,
``PADDLE_TPU_ROUTER_PORT``, ``PADDLE_TPU_ROUTER_HEALTH_POLL_S``; plus
``PADDLE_TPU_TRACE_SAMPLE`` / ``PADDLE_TPU_TRACE_DIR``
(observability/trace_context.py).
"""
from __future__ import annotations

import json
import logging
import threading
import time
import urllib.error
import urllib.request
import uuid
from http.server import BaseHTTPRequestHandler

from .. import metrics as _m
from ..errors import InvalidRequest, NoReplicaAvailable
from ..server import Listener
from ...log_helper import get_logger
from ...observability import distributed as _dobs
from ...observability.trace_context import maybe_sample
from .knobs import (ENV_ROUTER_HEALTH_POLL_S, ENV_ROUTER_PORT,
                    ENV_ROUTER_REPLICAS, parse_float_env, parse_int_env,
                    parse_replicas_env)

__all__ = ['Router', 'RouterServer', 'RoutedGeneration', 'Replica']

# /generate schema mirrored from serving/server.py: the router rejects
# unknown keys with the same 400 so a typo fails at the FRONT door instead
# of after a replica round-trip
_SAMPLING_KEYS = ('temperature', 'top_k', 'top_p', 'seed')
_GENERATE_KEYS = frozenset(('prompt', 'max_new_tokens', 'eos_id', 'stream',
                            'timeout_ms', 'request_id', *_SAMPLING_KEYS))


def _attach_sampling(payload, temperature, top_k, top_p, seed, request_id):
    """Add per-request sampling keys to a /generate payload. A SAMPLED
    request with no pinned identity gets a router-stamped ``request_id``:
    the id seeds the stream (serving/decode/sampling.py), so a pre-stream
    failover retry on another replica REPLAYS the same tokens — the
    determinism that makes zero-drop rerouting idempotent extends from
    greedy to sampled traffic."""
    if temperature is not None:
        payload['temperature'] = float(temperature)
    if top_k is not None:
        payload['top_k'] = int(top_k)
    if top_p is not None:
        payload['top_p'] = float(top_p)
    if seed is not None:
        payload['seed'] = int(seed)
    if request_id is not None:
        payload['request_id'] = str(request_id)
    elif payload.get('temperature') and seed is None:
        payload['request_id'] = uuid.uuid4().hex[:16]
    return payload

_logger = get_logger(
    __name__, logging.INFO,
    fmt='%(asctime)s-%(levelname)s: [router] %(message)s')


def _span(ctx, name, start_perf, end_perf, **args):
    """Router-side span record; free (one None check) when untraced."""
    if ctx is None:
        return
    _m.trace_spans_recorded.inc()
    _dobs.record_span(ctx, name, start_perf, end_perf, **args)

#: dispatch failures that are the REPLICA's fault → retry elsewhere.
#: 4xx (bad request, overload backpressure, deadline) are the CLIENT's
#: contract with the tier and propagate unchanged.
_REROUTE_HTTP_CODES = (500, 503)


class Replica:
    """Router-side view of one replica process."""

    def __init__(self, url):
        self.url = url.rstrip('/')
        self.healthy = False
        self.warmed = False
        self.half_open = False
        self.draining = False         # router-side (rolling restart)
        self.reported_load = 0        # decode active + waiting at last poll
        self.inflight = 0             # router-side, updated at dispatch
        self.last_poll_ok = 0.0
        # clock handshake (docs/OBSERVABILITY.md): estimated replica-unix
        # minus router-unix, from the health poll's RTT midpoint — what
        # trace_merge.py uses to align this replica's spans
        self.clock_offset = None
        self.replica_id = None        # reported by /healthz when available
        # cached /healthz windowed-series snapshots (queue_depth /
        # occupancy / ttft) — the autoscaler's decision inputs
        self.series = {}
        self._lock = threading.Lock()

    def load(self):
        return self.inflight + self.reported_load

    def routable(self):
        if self.draining:
            return False
        if self.healthy and self.warmed:
            return True
        # half-open probe: one request at a time re-admits a tripped replica
        return self.half_open and self.inflight == 0

    def begin(self):
        with self._lock:
            self.inflight += 1
            _m.router_replica_inflight.labels(replica=self.url).set(
                self.inflight)

    def end(self):
        with self._lock:
            self.inflight = max(self.inflight - 1, 0)
            _m.router_replica_inflight.labels(replica=self.url).set(
                self.inflight)

    def mark_dead(self):
        self.healthy = False
        self.half_open = False

    def state(self):
        return {'url': self.url, 'healthy': self.healthy,
                'warmed': self.warmed, 'half_open': self.half_open,
                'draining': self.draining, 'inflight': self.inflight,
                'reported_load': self.reported_load}


class RoutedGeneration:
    """One routed streaming generation: ``events()`` yields the replica's
    NDJSON events (``{'token','index'}`` lines, then the ``done`` line with
    routing metadata added). ``replica``/``retries`` describe the dispatch
    that is actually streaming."""

    def __init__(self, router, payload, timeout):
        self._router = router
        self._payload = payload
        self._timeout = timeout
        self.replica = None           # url actually streaming
        self.retries = 0              # reroutes before streaming began
        self.first_event_at = None
        # sampling is decided ONCE here at the edge; the context travels
        # with every dispatch so a trace is complete or absent
        self.trace = maybe_sample()
        if self.trace is not None:
            _m.trace_requests_sampled.inc()

    def events(self):
        router, payload = self._router, self._payload
        deadline = time.monotonic() + self._timeout
        tried = set()
        req_t0 = time.perf_counter()
        while True:
            rep = router._pick(tried, deadline)
            self.replica = rep.url
            rep.begin()
            t0 = time.perf_counter()
            emitted = False
            # each dispatch attempt is its own span under the request
            # root; its id is what the replica parents its spans under
            attempt = self.trace.child() if self.trace is not None else None
            try:
                try:
                    resp = router._post(rep, payload, self._timeout,
                                        trace=attempt)
                except urllib.error.HTTPError as e:
                    if e.code in _REROUTE_HTTP_CODES:
                        raise ConnectionError(f'replica replied {e.code}')
                    raise                     # client-contract error: 4xx
                _m.router_dispatch_seconds.observe(time.perf_counter() - t0)
                for raw in resp:
                    event = json.loads(raw)
                    if not emitted:
                        emitted = True
                        self.first_event_at = time.monotonic()
                    if event.get('done'):
                        event['replica'] = rep.url
                        event['retries'] = self.retries
                        if self.trace is not None:
                            event.setdefault('trace_id',
                                             self.trace.trace_id)
                            # spans must land BEFORE the done yield: the
                            # consumer may drop the generator right after
                            now = time.perf_counter()
                            _span(attempt, 'router/dispatch', t0, now,
                                  replica=rep.url)
                            _span(self.trace, 'router/request', req_t0,
                                  now, retries=self.retries)
                        _m.router_requests_completed.inc()
                        yield event
                        return
                    if 'error' in event:      # replica-side typed failure
                        _m.router_requests_failed.inc()
                        now = time.perf_counter()
                        _span(attempt, 'router/dispatch', t0, now,
                              replica=rep.url, error=event.get('error'))
                        _span(self.trace, 'router/request', req_t0, now,
                              retries=self.retries,
                              error=event.get('error'))
                        yield event
                        return
                    yield event
                # stream ended with no done line: replica died mid-write
                raise ConnectionError('replica stream ended early')
            except urllib.error.HTTPError:
                # only client-contract 4xx reach here (reroutable codes were
                # converted to ConnectionError above); HTTPError must be
                # caught BEFORE URLError, its base class
                raise
            except (ConnectionError, urllib.error.URLError, OSError) as e:
                rep.mark_dead()
                if emitted:
                    # tokens already forwarded: this stream dies with its
                    # replica (the only thing a replica death may kill)
                    _m.router_requests_failed.inc()
                    now = time.perf_counter()
                    _span(attempt, 'router/dispatch', t0, now,
                          replica=rep.url, error='ReplicaDied')
                    _span(self.trace, 'router/request', req_t0, now,
                          retries=self.retries, error='ReplicaDied')
                    yield {'error': 'ReplicaDied',
                           'message': f'replica {rep.url} failed '
                                      f'mid-stream: {e}',
                           'replica': rep.url, 'retries': self.retries}
                    return
                # nothing streamed yet: reroute, zero client-visible drops
                tried.add(rep)
                self.retries += 1
                _m.router_requests_rerouted.inc()
                # the failed attempt becomes a retry span — the failover
                # drill asserts this sits between the two replicas' spans
                _span(attempt, 'router/retry', t0, time.perf_counter(),
                      replica=rep.url, error=str(e))
                _logger.warning('rerouting (attempt %d) off %s: %s',
                                self.retries + 1, rep.url, e)
            finally:
                rep.end()


class Router:
    """See module docstring. ``replica_urls``: base URLs of the replicas
    (``http://host:port``). ``health_poll_s`` defaults from the strict-parse
    ``PADDLE_TPU_ROUTER_HEALTH_POLL_S`` knob (1.0s)."""

    def __init__(self, replica_urls, health_poll_s=None,
                 request_timeout=120.0, connect_timeout=5.0, start=True):
        if not replica_urls:
            raise ValueError('need at least one replica URL')
        self.replicas = [Replica(u) for u in replica_urls]
        self._replicas_lock = threading.Lock()   # guards membership only
        self.health_poll_s = (parse_float_env(ENV_ROUTER_HEALTH_POLL_S, 1.0)
                              if health_poll_s is None
                              else float(health_poll_s))
        self.request_timeout = float(request_timeout)
        self.connect_timeout = float(connect_timeout)
        _dobs.set_process_label('router')
        self._closed = threading.Event()
        self.poll_once()              # constructor returns with fresh state
        self._poll_thread = threading.Thread(
            target=self._poll_loop, name='paddle-tpu-router-health',
            daemon=True)
        if start:
            self._poll_thread.start()

    # -- health ------------------------------------------------------------
    def _poll_replica(self, rep):
        _m.router_health_polls.inc()
        try:
            u0 = time.time()
            with urllib.request.urlopen(rep.url + '/healthz',
                                        timeout=self.connect_timeout) as r:
                body = json.load(r)
            u1 = time.time()
            rep.healthy = body.get('status') == 'ok'
            rep.half_open = False
            warm = body.get('warmup')
            # replicas predating the warmup field are assumed warm
            rep.warmed = bool(warm.get('done')) if warm else rep.healthy
            decode = body.get('decode') or {}
            rep.reported_load = (int(decode.get('active', 0))
                                 + int(decode.get('waiting', 0)))
            rep.last_poll_ok = time.monotonic()
            rep.replica_id = body.get('replica') or rep.replica_id
            rep.series = body.get('series') or rep.series
            if 'unix_time' in body:
                # handshake offset estimate: the replica stamped its clock
                # somewhere inside [u0, u1]; the RTT midpoint is the
                # minimum-bias guess (error bounded by RTT/2)
                rep.clock_offset = float(body['unix_time']) - (u0 + u1) / 2.0
                _m.trace_clock_offset_seconds.labels(
                    replica=rep.replica_id or rep.url).set(rep.clock_offset)
                _dobs.record_clock_offset(rep.replica_id or rep.url,
                                          rep.clock_offset, rtt_s=u1 - u0)
        except urllib.error.HTTPError as e:
            try:
                body = json.load(e)
            except Exception:
                body = {}
            rep.healthy = False
            # a half-open breaker needs ONE probe request to re-admit the
            # replica; the router is the only traffic source, so it routes
            # exactly one there
            rep.half_open = any(
                s == 'half_open'
                for s in (body.get('breakers') or {}).values())
            rep.last_poll_ok = time.monotonic()
        except OSError:
            rep.mark_dead()
        _m.router_replicas_routable.set(
            sum(r.healthy and r.warmed and not r.draining
                for r in self.replicas))

    def poll_once(self):
        for rep in list(self.replicas):
            self._poll_replica(rep)

    def _poll_loop(self):
        while not self._closed.wait(self.health_poll_s):
            self.poll_once()

    def _fast_poll(self, rep):
        """Admission poll for a freshly added replica: short initial
        backoff (50 ms, doubling up to the regular ``health_poll_s``)
        until the first moment it is routable — so scale-up
        time-to-routable tracks the replica's actual warmup, instead of
        being quantized to a full health-poll period."""
        delay = 0.05
        while not self._closed.wait(delay):
            if rep not in self.replicas:
                return                 # removed before it came up
            self._poll_replica(rep)
            if rep.routable():
                _logger.info('replica %s admitted: routable after fast '
                             'poll', rep.url)
                return
            delay = min(delay * 2, self.health_poll_s)

    # -- elastic membership (elastic/autoscaler.py) ------------------------
    def add_replica(self, url, fast_poll=True):
        """Register a replica at runtime (scale-up). It starts unpolled —
        NOT routable — and is admitted by the fast initial poll the
        moment ``/healthz`` reports healthy + warm (the cold-replica gate
        applies to elastic replicas exactly as to static ones). Returns
        the :class:`Replica` (the existing one if already registered)."""
        url = url.rstrip('/')
        with self._replicas_lock:
            for r in self.replicas:
                if r.url == url:
                    return r
            rep = Replica(url)
            # copy-on-write: readers iterate a stable list snapshot
            self.replicas = self.replicas + [rep]
        if fast_poll:
            threading.Thread(target=self._fast_poll, args=(rep,),
                             name='paddle-tpu-router-admit',
                             daemon=True).start()
        return rep

    def remove_replica(self, url):
        """Deregister a replica (scale-down, after drain). In-flight
        streams keep their handle to it; it just stops being a dispatch
        candidate. Raises KeyError when unknown."""
        url = url.rstrip('/')
        with self._replicas_lock:
            rep = next((r for r in self.replicas if r.url == url), None)
            if rep is None:
                raise KeyError(f'unknown replica {url}')
            self.replicas = [r for r in self.replicas if r is not rep]
        _m.router_replicas_routable.set(
            sum(r.healthy and r.warmed and not r.draining
                for r in self.replicas))
        return rep

    # -- dispatch ----------------------------------------------------------
    def _pick(self, exclude, deadline):
        """Lowest-loaded routable replica, waiting (bounded by ``deadline``)
        through transient all-down windows so momentary blips don't drop
        requests. Raises :class:`NoReplicaAvailable` at the deadline."""
        while True:
            candidates = [r for r in self.replicas
                          if r not in exclude and r.routable()]
            if candidates:
                rep = min(candidates, key=lambda r: r.load())
                if rep.half_open and not rep.healthy:
                    _m.router_probes.inc()
                    _logger.info('routing a probe to half-open replica %s',
                                 rep.url)
                return rep
            _m.router_no_replica.inc()
            if time.monotonic() >= deadline:
                raise NoReplicaAvailable(
                    [r.state() for r in self.replicas])
            # blip window: excluded replicas may recover; re-admit them
            exclude.clear()
            time.sleep(min(0.2, self.health_poll_s))
            self.poll_once()

    def _post(self, rep, payload, timeout, trace=None):
        headers = {'Content-Type': 'application/json'}
        if trace is not None:
            headers.update(trace.to_headers())
        req = urllib.request.Request(
            rep.url + '/generate', data=json.dumps(payload).encode(),
            headers=headers)
        return urllib.request.urlopen(req, timeout=timeout)

    # -- fleet metrics -----------------------------------------------------
    def scrape_replica_metrics(self, timeout_s=2.0):
        """Scrape every replica's ``/metrics``; → ``[(label, text), ...]``
        for the scrapes that succeeded. A dead or wedged replica costs one
        bounded timeout and a ``router_scrape_failures`` tick — never a
        fleet-scrape failure (the kill -9 hardening contract)."""
        scrapes = []
        for rep in self.replicas:
            label = rep.replica_id or rep.url
            try:
                with urllib.request.urlopen(rep.url + '/metrics',
                                            timeout=timeout_s) as r:
                    scrapes.append((label,
                                    r.read().decode('utf-8', 'replace')))
            except (OSError, ValueError) as e:
                _m.router_scrape_failures.labels(replica=label).inc()
                _logger.warning('fleet scrape of %s failed: %s',
                                rep.url, e)
        return scrapes

    def fleet_metrics_text(self, timeout_s=2.0):
        """Merged replica-labeled Prometheus text for ``/metrics/fleet``
        (docs/OBSERVABILITY.md "Aggregation semantics"). Router-local
        metrics stay on ``/metrics`` — this is the REPLICAS' merged
        view, so the two exports never double-count."""
        _m.router_fleet_scrapes.inc()
        return _dobs.merge_fleet_metrics(
            self.scrape_replica_metrics(timeout_s))

    # -- client API --------------------------------------------------------
    def stream_generate(self, prompt, max_new_tokens=16, eos_id=None,
                        timeout_ms=None, timeout=None, temperature=None,
                        top_k=None, top_p=None, seed=None, request_id=None):
        """Route one streaming generation; returns a
        :class:`RoutedGeneration` (consume ``.events()``). Sampling knobs
        forward to the replica's /generate schema; see
        :func:`_attach_sampling` for the sampled-failover identity rule."""
        _m.router_requests.inc()
        payload = {'prompt': list(prompt),
                   'max_new_tokens': int(max_new_tokens), 'stream': True}
        if eos_id is not None:
            payload['eos_id'] = int(eos_id)
        if timeout_ms is not None:
            payload['timeout_ms'] = timeout_ms
        _attach_sampling(payload, temperature, top_k, top_p, seed,
                         request_id)
        return RoutedGeneration(self, payload,
                                timeout or self.request_timeout)

    def generate(self, prompt, max_new_tokens=16, eos_id=None,
                 timeout_ms=None, timeout=None, **sampling):
        """Blocking convenience: route, stream to completion, return the
        final done dict (raises on an error event). ``**sampling`` passes
        temperature/top_k/top_p/seed/request_id through."""
        gen = self.stream_generate(prompt, max_new_tokens, eos_id,
                                   timeout_ms, timeout, **sampling)
        from ..errors import ServingError
        final = None
        for event in gen.events():
            if 'error' in event and not event.get('done'):
                raise ServingError(
                    f"routed generation failed: {event['error']}: "
                    f"{event.get('message')}")
            final = event
        if final is None or not final.get('done'):
            raise NoReplicaAvailable([r.state() for r in self.replicas])
        return final

    def generate_nonstream(self, prompt, max_new_tokens=16, eos_id=None,
                           timeout_ms=None, timeout=None, temperature=None,
                           top_k=None, top_p=None, seed=None,
                           request_id=None):
        """Non-streamed routed generation: the replica replies with ONE
        JSON body, so a failure at ANY point before the reply — connection
        refused, replica killed mid-generation, 5xx — is safely retried on
        another replica (generation is deterministic: greedy exactly, and
        sampled streams replay from the request_id the router stamps —
        so retries are idempotent). Non-streamed requests therefore
        survive a replica death with zero drops even while in flight."""
        _m.router_requests.inc()
        timeout = timeout or self.request_timeout
        payload = {'prompt': list(prompt),
                   'max_new_tokens': int(max_new_tokens), 'stream': False}
        if eos_id is not None:
            payload['eos_id'] = int(eos_id)
        if timeout_ms is not None:
            payload['timeout_ms'] = timeout_ms
        _attach_sampling(payload, temperature, top_k, top_p, seed,
                         request_id)
        deadline = time.monotonic() + timeout
        tried = set()
        retries = 0
        trace = maybe_sample()        # edge decision, as in events()
        if trace is not None:
            _m.trace_requests_sampled.inc()
        req_t0 = time.perf_counter()
        while True:
            rep = self._pick(tried, deadline)
            rep.begin()
            t0 = time.perf_counter()
            attempt = trace.child() if trace is not None else None
            try:
                try:
                    with self._post(rep, payload, timeout,
                                    trace=attempt) as resp:
                        body = json.load(resp)
                except urllib.error.HTTPError as e:
                    if e.code in _REROUTE_HTTP_CODES:
                        raise ConnectionError(f'replica replied {e.code}')
                    raise                     # client-contract error: 4xx
                _m.router_dispatch_seconds.observe(time.perf_counter() - t0)
                body['replica'] = rep.url
                body['retries'] = retries
                if trace is not None:
                    body.setdefault('trace_id', trace.trace_id)
                    now = time.perf_counter()
                    _span(attempt, 'router/dispatch', t0, now,
                          replica=rep.url)
                    _span(trace, 'router/request', req_t0, now,
                          retries=retries)
                _m.router_requests_completed.inc()
                return body
            except urllib.error.HTTPError:
                raise                         # 4xx (see events(): order!)
            except (ConnectionError, urllib.error.URLError, OSError,
                    ValueError) as e:
                rep.mark_dead()
                tried.add(rep)
                retries += 1
                _m.router_requests_rerouted.inc()
                _span(attempt, 'router/retry', t0, time.perf_counter(),
                      replica=rep.url, error=str(e))
                _logger.warning('retrying non-streamed request off %s: %s',
                                rep.url, e)
            finally:
                rep.end()

    # -- operations --------------------------------------------------------
    def drain(self, url):
        self._replica_by_url(url).draining = True

    def undrain(self, url):
        self._replica_by_url(url).draining = False

    def _replica_by_url(self, url):
        url = url.rstrip('/')
        for r in self.replicas:
            if r.url == url:
                return r
        raise KeyError(f'unknown replica {url}')

    def rolling_restart(self, restart_fn, drain_timeout=60.0,
                        warm_timeout=300.0, poll_interval=0.1):
        """Restart every replica one at a time behind a drain: stop routing
        to it, wait for its router-side in-flight work to finish, call
        ``restart_fn(url)`` (which may return the restarted replica's NEW
        url), then wait until it reports healthy AND warm before re-admitting
        it and moving on — traffic keeps flowing through the other replicas
        the whole time."""
        for rep in self.replicas:
            rep.draining = True
            deadline = time.monotonic() + drain_timeout
            while rep.inflight > 0 and time.monotonic() < deadline:
                time.sleep(poll_interval)
            new_url = restart_fn(rep.url)
            if new_url:
                rep.url = str(new_url).rstrip('/')
            rep.healthy = rep.warmed = False
            deadline = time.monotonic() + warm_timeout
            while time.monotonic() < deadline:
                self._poll_replica(rep)
                if rep.healthy and rep.warmed:
                    break
                time.sleep(poll_interval)
            else:
                rep.draining = False
                raise RuntimeError(
                    f'replica {rep.url} did not come back healthy+warm '
                    f'within {warm_timeout}s')
            rep.draining = False
            _m.router_rolling_restarts.inc()
            _logger.info('rolling restart: %s back and warm', rep.url)

    def close(self):
        self._closed.set()
        if self._poll_thread.is_alive():
            self._poll_thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _RouterHandler(BaseHTTPRequestHandler):
    protocol_version = 'HTTP/1.1'
    server_version = 'paddle-tpu-router'

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
            pass

    def _write_chunk(self, obj):
        data = json.dumps(obj).encode() + b'\n'
        self.wfile.write(b'%x\r\n' % len(data) + data + b'\r\n')
        self.wfile.flush()

    def do_GET(self):
        router = self.server.router
        if self.path == '/healthz':
            states = [r.state() for r in router.replicas]
            routable = sum(r.routable() for r in router.replicas)
            self._reply(200 if routable else 503,
                        {'status': 'ok' if routable else 'no_replicas',
                         'routable': routable, 'replicas': states})
        elif self.path == '/metrics':
            from ...observability import registry
            self._reply(200, registry.prometheus_text().encode(),
                        content_type='text/plain; version=0.0.4')
        elif self.path == '/metrics/fleet':
            self._reply(200, router.fleet_metrics_text().encode(),
                        content_type='text/plain; version=0.0.4')
        else:
            self._reply(404, {'error': 'NotFound', 'message': self.path})

    def do_POST(self):
        if self.path != '/generate':
            return self._reply(404, {'error': 'NotFound',
                                     'message': self.path})
        router = self.server.router
        try:
            length = int(self.headers.get('Content-Length') or 0)
            payload = json.loads(self.rfile.read(length)) if length > 0 \
                else None
        except (ValueError, UnicodeDecodeError):
            payload = None
        if not isinstance(payload, dict) or \
                not isinstance(payload.get('prompt'), list):
            return self._reply(400, {
                'error': 'InvalidRequest',
                'message': 'body must include "prompt": [token ids]'})
        unknown = sorted(set(payload) - _GENERATE_KEYS)
        if unknown:
            return self._reply(400, {
                'error': 'InvalidRequest',
                'message': f'unknown request field(s): {", ".join(unknown)}'
                           f'; supported: '
                           f'{", ".join(sorted(_GENERATE_KEYS))}'})
        stream = payload.get('stream', True) is not False
        try:
            gen = router.stream_generate(
                payload['prompt'],
                max_new_tokens=payload.get('max_new_tokens', 16),
                eos_id=payload.get('eos_id'),
                timeout_ms=payload.get('timeout_ms'),
                **{k: payload[k] for k in (*_SAMPLING_KEYS, 'request_id')
                   if k in payload})
            if not stream:
                events = list(gen.events())
                final = events[-1] if events else {}
                if 'error' in final and not final.get('done'):
                    return self._reply(502, final)
                reply = {
                    'tokens': final.get('tokens', []),
                    'finish_reason': final.get('finish_reason'),
                    'replica': final.get('replica'),
                    'retries': final.get('retries', 0),
                    'request_id': final.get('request_id'),
                    'replica_id': final.get('replica_id')}
                if 'trace_id' in final:   # sampled: hand the id back
                    reply['trace_id'] = final['trace_id']
                return self._reply(200, reply)
            # prime the FIRST event before committing the 200: replica 4xx /
            # no-replica failures raise here, while an error reply is still
            # possible on the wire
            events = gen.events()
            try:
                first = next(events)
            except StopIteration:
                first = None
            self.send_response(200)
            self.send_header('Content-Type', 'application/x-ndjson')
            self.send_header('Transfer-Encoding', 'chunked')
            self.end_headers()
            try:
                if first is not None:
                    self._write_chunk(first)
                for event in events:
                    self._write_chunk(event)
                self.wfile.write(b'0\r\n\r\n')
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):
                pass                  # client went away
        except NoReplicaAvailable as e:
            self._reply(503, {'error': 'NoReplicaAvailable',
                              'message': str(e)})
        except urllib.error.HTTPError as e:
            # a replica's 4xx client-contract reply, relayed verbatim
            try:
                body = e.read()
            except Exception:
                body = json.dumps({'error': 'HTTPError',
                                   'message': str(e)}).encode()
            self._reply(e.code, body)
        except InvalidRequest as e:
            self._reply(400, {'error': 'InvalidRequest', 'message': str(e)})


class RouterServer:
    """Stdlib HTTP front for a :class:`Router` (same shape as
    serving/server.py): ``POST /generate`` (streamed NDJSON or one JSON
    reply), ``GET /healthz``, ``GET /metrics``. ``port=0`` binds an
    ephemeral port."""

    def __init__(self, router, host='127.0.0.1', port=None):
        if port is None:
            port = parse_int_env(ENV_ROUTER_PORT, 8180, minimum=0,
                                 maximum=65535)
        self.router = router
        self._httpd = Listener((host, int(port)), _RouterHandler)
        self._httpd.router = router
        self._thread = None

    @property
    def port(self):
        return self._httpd.server_address[1]

    def start(self):
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name='paddle-tpu-router-http',
                                        daemon=True)
        self._thread.start()
        _logger.info('routing on %s:%d over %d replicas',
                     self._httpd.server_address[0], self.port,
                     len(self.router.replicas))
        return self

    def serve_forever(self):
        _logger.info('routing on %s:%d over %d replicas',
                     self._httpd.server_address[0], self.port,
                     len(self.router.replicas))
        try:
            self._httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            self.shutdown()

    def shutdown(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self.router.close()
        if self._thread is not None and \
                self._thread is not threading.current_thread():
            self._thread.join(5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description='paddle_tpu serving tier: multi-replica router')
    ap.add_argument('--replica', action='append', default=None,
                    help='replica base URL (repeatable); defaults from '
                         'PADDLE_TPU_ROUTER_REPLICAS')
    ap.add_argument('--host', default='0.0.0.0')
    ap.add_argument('--port', type=int, default=None,
                    help='defaults from PADDLE_TPU_ROUTER_PORT (8180)')
    ap.add_argument('--health-poll-s', type=float, default=None,
                    help='defaults from PADDLE_TPU_ROUTER_HEALTH_POLL_S (1)')
    args = ap.parse_args(argv)
    urls = args.replica or parse_replicas_env(ENV_ROUTER_REPLICAS)
    if not urls:
        ap.error(f'no replicas: pass --replica or set {ENV_ROUTER_REPLICAS}')
    router = Router(urls, health_poll_s=args.health_poll_s)
    scaler = None
    from ...elastic.autoscaler import AutoscaleConfig, Autoscaler
    if AutoscaleConfig.enabled_from_env():      # PADDLE_TPU_AUTOSCALE=1
        from ...elastic.launcher import ProcessReplicaLauncher
        scaler = Autoscaler(router, ProcessReplicaLauncher(),
                            AutoscaleConfig.from_env())
    try:
        RouterServer(router, host=args.host, port=args.port).serve_forever()
    finally:
        if scaler is not None:
            scaler.close()
            scaler.launcher.close()


if __name__ == '__main__':
    main()
