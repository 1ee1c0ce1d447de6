"""One writer thread for every streamed ``POST /generate`` of a server.

A decode step of S slots emits S tokens for S connections. With a thread a
connection in ``for tok in stream`` that was S wakes, S threads wanting the
interpreter for a ``json.dumps`` and two socket calls each, and the scheduler's
worker, which wants it for the next dispatch, waiting behind them: on the
chip's host a GPT-1 prefill's dispatch took 6.9 ms where the same call with no
HTTP side takes 1.3 (PERF.md section 6, PR 34 and PR 35). Here the worker hands
over the streams a step touched in ONE call (``DecodeScheduler.stream_sink`` =
:meth:`StreamWriter.touched`: an append and one byte down a socket pair) and
one thread does the rest::

    handler thread               worker thread            writer thread
    parse, submit, 200 + headers
    serve(sock, stream) ─ open ─────────────────────────▶ keeps (sock, cursor)
      blocks ONCE                emit: S tokens
                                 touched([streams]) ────▶ per touched stream:
                                                          tokens_since(cursor)
                                                          → NDJSON lines, one
                                                          chunk, one send
      returns ◀──────────────────────────────────────────  done line, 0-chunk

- The wire is the handler's of old, byte for byte a line: ``{"token": t,
  "index": i}`` as ``json.dumps`` writes it, the ``done`` line, the error line
  after a failure mid-stream, the terminating ``0\\r\\n\\r\\n``. The lines one
  hand-off yields for a connection (a window model's block) share a chunk.
- It never blocks on a socket: ``send(MSG_DONTWAIT)``; what a socket does not
  take is kept for that connection alone and sent when the selector says it is
  writable. A connection that resets is dropped alone (generation goes on
  server-side); nothing a client does ends the thread.
- ``request_timeout`` keeps the handler's meaning: a stream with no token for
  that long ends with the ``TimeoutError`` line. A connection that takes no
  byte for that long is dropped.
- A generator that offers no ``stream_sink`` is served too: the writer then
  looks at its connections every ``POLL_S``.

Counted, always on: ``http_stream_writer_wakes`` (hand-offs taken),
``http_stream_writer_tokens`` (token lines written), ``http_stream_writer_sends``
(send calls); the thread's CPU seconds go to ``http_handler_cpu_seconds``, read
once a turn of its loop. With telemetry on, a span ``http/write`` a turn that
took a hand-off (args ``streams``, ``tokens``).
"""
from __future__ import annotations

import collections
import json
import logging
import selectors
import socket
import threading
import time

from . import metrics as _m
from .. import observability as _obs
from ..log_helper import get_logger

__all__ = ['StreamWriter']

_logger = get_logger(
    __name__, logging.INFO,
    fmt='%(asctime)s-%(levelname)s: [serving] %(message)s')

POLL_S = 0.002          # between looks at streams nobody hands over
IDLE_S = 0.25           # between looks at the deadlines, at most
_LAST_CHUNK = b'0\r\n\r\n'


def _chunk(data):
    return b'%x\r\n%b\r\n' % (len(data), data)


def _line(obj):
    return json.dumps(obj).encode() + b'\n'


class _Connection:
    """One streamed reply: the handler's socket, the request's stream, the
    index of the next token to write, the bytes the socket did not take
    yet, and when it last moved (a line formatted, a byte taken)."""
    __slots__ = ('sock', 'stream', 't0', 'cursor', 'backlog', 'ending',
                 'moved', 'released', 'dropped')

    def __init__(self, sock, stream, t0):
        self.sock, self.stream, self.t0 = sock, stream, t0
        self.cursor = 0
        self.backlog = b''
        self.ending = False          # the reply's last byte is formatted
        self.moved = time.monotonic()
        self.released = threading.Event()
        self.dropped = False


class StreamWriter:
    """The thread and its inbox. ``serve`` is the handler threads' side,
    ``touched`` the scheduler worker's, the rest runs on the writer thread."""

    def __init__(self, request_timeout=None, polled=False):
        """``request_timeout``: seconds a stream may yield no token (and a
        connection take no byte), None for ever. ``polled``: nobody calls
        ``touched``, so look at every connection each ``POLL_S``."""
        self.request_timeout = request_timeout
        self._tick = IDLE_S if request_timeout is None \
            else min(IDLE_S, request_timeout / 4)
        self._polled = polled
        self._inbox = collections.deque()    # _Connection | [streams]
        self._conns = {}                     # stream -> _Connection
        self._selector = selectors.DefaultSelector()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._selector.register(self._wake_r, selectors.EVENT_READ)
        self._lock = threading.Lock()        # _stopping and the inbox's end
        self._stopping = False
        self._cpu = 0.0
        self._now = 0.0                      # monotonic, read once a turn
        self._sends = self._tokens = 0       # of the turn, booked at its end
        self._thread = None

    # -- the other threads' side -------------------------------------------
    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name='paddle-tpu-serving-writer',
                daemon=True)
            self._thread.start()
        return self

    def serve(self, sock, stream, t0):
        """Write ``stream`` to ``sock`` (the 200 and its headers are out):
        blocks the calling handler thread ONCE, until the reply's last byte
        is out or the connection is gone. ``t0``: the request's submit, for
        the done line's ``latency_ms``. True if the reply went out whole."""
        conn = _Connection(sock, stream, t0)
        with self._lock:
            if self._stopping:
                return False
            self._inbox.append(conn)
        self._wake()
        conn.released.wait()
        return not conn.dropped

    def touched(self, streams):
        """The scheduler's hand-off (``DecodeScheduler.stream_sink``): these
        streams have new tokens or ended. One append and one wake, whatever
        their number; never raises, never blocks."""
        self._inbox.append(streams)
        self._wake()

    def _wake(self):
        try:
            self._wake_w.send(b'\0')
        except OSError:
            pass      # full: a wake is pending already; closed: stopped

    def stop(self, timeout=5.0):
        """Take what the inbox still holds, flush what the sockets take, let
        every handler go (a reply not out whole counts as dropped), end the
        thread."""
        with self._lock:
            self._stopping = True
        self._wake()
        if self._thread is not None:
            self._thread.join(timeout)
        else:
            self._close()             # never started: nobody else will

    # -- the writer thread -------------------------------------------------
    def _run(self):
        check_at = time.monotonic() + self._tick
        self._cpu = time.thread_time()
        while True:
            try:
                if self._polled and self._conns:
                    check_at = min(check_at, time.monotonic() + POLL_S)
                events = self._selector.select(
                    max(0.0, check_at - time.monotonic()))
                t0 = time.perf_counter()
                self._now = now = time.monotonic()
                for key, _ in events:
                    if key.data is None:
                        self._drain_wakes()
                    else:
                        self._guarded(self._flush, key.data)
                with self._lock:
                    stopping = self._stopping
                wakes = streams = tokens = 0
                while self._inbox:
                    item = self._inbox.popleft()
                    if isinstance(item, _Connection):
                        self._conns[item.stream] = item
                        self._guarded(self._pump, item)
                        continue
                    wakes += 1
                    for stream in item:
                        conn = self._conns.get(stream)
                        if conn is not None:
                            streams += 1
                            tokens += self._guarded(self._pump, conn) or 0
                if now >= check_at:
                    check_at = now + self._tick
                    self._look(now)
                self._book(t0, wakes, streams, tokens)
                if stopping:
                    break
            except Exception:     # the boundary: the thread outlives a bug
                _logger.exception('stream writer: a turn failed')
        self._close()

    def _close(self):
        self._release_all()
        self._selector.close()
        self._wake_r.close()
        self._wake_w.close()

    def _drain_wakes(self):
        try:
            while self._wake_r.recv(4096):
                pass
        except BlockingIOError:
            pass

    def _guarded(self, step, conn):
        """``step(conn)``; whatever it raises costs this connection alone."""
        try:
            return step(conn)
        except Exception as e:
            if not isinstance(e, OSError):    # a reset is a client's doing
                _logger.exception('stream writer: dropping a connection')
            self._release(conn, dropped=True)

    def _pump(self, conn):
        """Format and send what ``conn``'s stream holds beyond the cursor,
        and the reply's end if the stream has ended; the tokens taken."""
        if conn.ending:
            return 0
        stream = conn.stream
        done = stream.done()      # before the tokens: done comes after them
        new = stream.tokens_since(conn.cursor)
        lines = [b'{"token": %d, "index": %d}\n' % (tok, i)
                 for i, tok in enumerate(new, conn.cursor)]
        conn.cursor += len(new)
        if done:
            exc = stream.exception()
            if exc is None:
                lines.append(_line({
                    'done': True, 'finish_reason': stream.finish_reason,
                    'tokens': stream.tokens,
                    'latency_ms': round(
                        (time.perf_counter() - conn.t0) * 1e3, 3),
                    **stream.meta}))
            else:
                lines.append(_line({'error': type(exc).__name__,
                                    'message': str(exc)}))
        if not lines:
            return 0
        conn.moved = self._now
        self._end_or_send(conn, _chunk(b''.join(lines)), done)
        self._tokens += len(new)
        return len(new)

    def _end_or_send(self, conn, data, ending):
        if ending:
            conn.ending = True
            data += _LAST_CHUNK
        if conn.backlog:
            conn.backlog += data      # its turn comes when it is writable
        else:
            self._send(conn, data)

    def _send(self, conn, data):
        """As much of ``data`` as the socket takes now; the rest is the
        connection's backlog, sent when the selector says so (`_flush`)."""
        self._sends += 1
        try:
            sent = conn.sock.send(data, socket.MSG_DONTWAIT)
        except BlockingIOError:
            sent = 0
        if sent:
            conn.moved = self._now
        if sent < len(data):
            if not conn.backlog:
                self._selector.register(conn.sock, selectors.EVENT_WRITE,
                                        conn)
            conn.backlog = data[sent:]
            return
        if conn.backlog:
            conn.backlog = b''
            self._selector.unregister(conn.sock)
        if conn.ending:
            self._release(conn, dropped=False)

    def _flush(self, conn):
        if conn.backlog:
            self._send(conn, conn.backlog)

    def _look(self, now):
        """The periodic look: streams nobody hands over, streams that
        stalled, connections that take no byte."""
        limit = self.request_timeout
        for conn in list(self._conns.values()):
            if self._polled:
                self._guarded(self._pump, conn)
            if limit is None or conn.released.is_set():
                continue
            if now - conn.moved <= limit:
                continue
            if conn.backlog:
                self._release(conn, dropped=True)
            elif not conn.ending:
                self._guarded(self._stalled, conn)

    def _stalled(self, conn):
        self._end_or_send(conn, _chunk(_line({
            'error': 'TimeoutError',
            'message': conn.stream.stalled_message(self.request_timeout)})),
            True)

    def _release(self, conn, dropped):
        """The connection is the handler's again (which closes it if the
        reply did not go out whole)."""
        if self._conns.pop(conn.stream, None) is None:
            return
        if conn.backlog:
            conn.backlog = b''
            try:
                self._selector.unregister(conn.sock)
            except (KeyError, ValueError, OSError):
                pass                  # the socket is closed already
        conn.dropped = dropped
        conn.released.set()

    def _release_all(self):
        for conn in list(self._conns.values()):
            self._release(conn, dropped=True)
        with self._lock:              # `serve` appends under it or not at all
            late = [c for c in self._inbox if isinstance(c, _Connection)]
            self._inbox.clear()
        for conn in late:
            conn.dropped = True
            conn.released.set()

    def _book(self, t0, wakes, streams, tokens):
        """A turn's counters, the thread's CPU seconds since the last turn
        (one read of the thread clock) and, traced, its span."""
        cpu = time.thread_time()
        _m.http_handler_cpu_seconds.inc(cpu - self._cpu)
        self._cpu = cpu
        if self._sends:
            _m.http_stream_writer_sends.inc(self._sends)
            self._sends = 0
        if self._tokens:
            _m.http_stream_writer_tokens.inc(self._tokens)
            self._tokens = 0
        if wakes:
            _m.http_stream_writer_wakes.inc(wakes)
            if _obs._ENABLED:
                _obs.tracer.complete('http/write', t0, time.perf_counter(),
                                     streams=streams, tokens=tokens)
