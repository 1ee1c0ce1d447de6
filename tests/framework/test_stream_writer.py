"""The server's one stream writer (paddle_tpu/serving/stream_writer.py): the
bytes a streamed ``POST /generate`` puts on the wire are the per-connection
handler's of old, keep-alive holds, many streams at once are each exact, a
client that reads nothing or goes away costs nobody else, failures and the
per-token timeout arrive as their lines, ``shutdown()`` returns with streams
open, and a step's tokens cross from the scheduler's worker in ONE hand-off
(counters ``http_stream_writer_*``)."""
import http.client
import json
import socket
import sys
import threading
import time

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import (CausalLMConfig, TransformerLM,
                                         greedy_generate)
from paddle_tpu.serving import DecodeEngine, DecodeScheduler, ServingServer
from paddle_tpu.serving.decode.scheduler import GenerationStream
from paddle_tpu.serving.stream_writer import StreamWriter


@pytest.fixture(scope='module')
def lm():
    with guard():
        model = TransformerLM(CausalLMConfig.tiny())
        model.eval()
        yield model


def make_engine(model, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 16)
    kw.setdefault('max_new_tokens_cap', 16)
    return DecodeEngine(model, **kw)


class _Served:
    """A scheduler behind a started server, shut down on the way out."""

    def __init__(self, engine, generator=None, drain=False, **server_kw):
        self.engine = engine
        self.sched = DecodeScheduler(engine)
        self.server = ServingServer(
            None, host='127.0.0.1', port=0,
            generator=self.sched if generator is None
            else generator(self.sched), **server_kw)
        self.port = self.server.port
        self._drain = drain

    def __enter__(self):
        self.server.start()
        return self

    def __exit__(self, *exc):
        self.server.shutdown(drain=self._drain, timeout=20)


def _request(body):
    data = json.dumps(body).encode()
    return (b'POST /generate HTTP/1.1\r\nHost: x\r\n'
            b'Content-Type: application/json\r\n'
            b'Content-Length: %d\r\n\r\n' % len(data)) + data


def _read_reply(sock):
    """One chunked reply off a raw socket: (head, [chunk payloads], bytes
    after the head)."""
    buf = b''
    while b'\r\n\r\n' not in buf:
        got = sock.recv(65536)
        assert got, f'closed inside the head: {buf!r}'
        buf += got
    head, _, rest = buf.partition(b'\r\n\r\n')
    chunks, raw = [], rest
    while True:
        while b'\r\n' not in rest:
            got = sock.recv(65536)
            assert got, f'closed inside the body: {raw!r}'
            rest += got
            raw += got
        size, _, rest = rest.partition(b'\r\n')
        n = int(size, 16)
        while len(rest) < n + 2:
            got = sock.recv(65536)
            assert got, f'closed inside a chunk: {raw!r}'
            rest += got
            raw += got
        assert rest[n:n + 2] == b'\r\n'
        if n == 0:
            assert rest == b'\r\n', 'bytes after the terminating chunk'
            return head, chunks, raw
        chunks.append(rest[:n])
        rest = rest[n + 2:]


def _stream(port, body, timeout=60):
    """A streamed request over http.client: (token ids, the last line)."""
    conn = http.client.HTTPConnection('127.0.0.1', port, timeout=timeout)
    try:
        conn.request('POST', '/generate', json.dumps(body),
                     {'Content-Type': 'application/json'})
        resp = conn.getresponse()
        assert resp.status == 200
        lines = [json.loads(ln) for ln in resp.read().splitlines()]
    finally:
        conn.close()
    return [ln['token'] for ln in lines if 'token' in ln], lines[-1]


def _counter(name):
    d = obs.registry.to_dict().get(name)
    return sum(s['value'] for s in d['samples']) if d else 0.0


# -- the wire ----------------------------------------------------------------

def test_the_bytes_on_the_wire_are_the_old_handlers_line_for_line(lm):
    engine = make_engine(lm)
    prompt = [5, 9, 2, 44]
    ref = greedy_generate(lm, prompt, 8, pad_len=engine.padded_context)
    with _Served(engine) as s, \
            socket.create_connection(('127.0.0.1', s.port), 30) as sock:
        sock.sendall(_request({'prompt': prompt, 'max_new_tokens': 8,
                               'request_id': 'wire-1'}))
        head, chunks, raw = _read_reply(sock)
        assert head.startswith(b'HTTP/1.1 200 ')
        assert b'Content-Type: application/x-ndjson' in head
        assert b'Transfer-Encoding: chunked' in head
        assert raw.endswith(b'\r\n0\r\n\r\n')
        lines = b''.join(chunks).splitlines(keepends=True)
        # what `json.dumps(obj).encode() + b'\n'` wrote, a line a token
        assert lines[:-1] == [
            json.dumps({'token': t, 'index': i}).encode() + b'\n'
            for i, t in enumerate(ref)]
        done = json.loads(lines[-1])
        assert list(done) == ['done', 'finish_reason', 'tokens',
                              'latency_ms', 'request_id', 'replica_id']
        assert done['done'] is True and done['finish_reason'] == 'length'
        assert done['tokens'] == ref and done['request_id'] == 'wire-1'
        assert done['replica_id'] == s.sched.replica_id
        assert done['latency_ms'] > 0
        assert lines[-1] == json.dumps(done).encode() + b'\n'
        # every chunk holds whole lines
        assert all(c.endswith(b'\n') for c in chunks)

        # the connection is kept: a second request on the same socket
        sock.sendall(_request({'prompt': prompt, 'max_new_tokens': 3}))
        head, chunks, _ = _read_reply(sock)
        assert head.startswith(b'HTTP/1.1 200 ')
        again = [json.loads(ln) for ln in b''.join(chunks).splitlines()]
        assert [ln['token'] for ln in again[:-1]] == ref[:3]
        assert again[-1]['tokens'] == ref[:3]


def test_keep_alive_over_http_client_and_a_refusal_between(lm):
    engine = make_engine(lm)
    ref = greedy_generate(lm, [7, 8, 9], 6, pad_len=engine.padded_context)
    with _Served(engine) as s:
        conn = http.client.HTTPConnection('127.0.0.1', s.port, timeout=60)
        for body, status in [({'prompt': [7, 8, 9], 'max_new_tokens': 6}, 200),
                             ({'prompt': 'no list'}, 400),
                             ({'prompt': [7, 8, 9], 'max_new_tokens': 6}, 200),
                             ({'prompt': [7, 8, 9], 'max_new_tokens': 6,
                               'stream': False}, 200)]:
            conn.request('POST', '/generate', json.dumps(body),
                         {'Content-Type': 'application/json'})
            resp = conn.getresponse()
            data = resp.read()
            assert resp.status == status
            if status == 200 and body.get('stream', True):
                lines = [json.loads(ln) for ln in data.splitlines()]
                assert [ln['token'] for ln in lines[:-1]] == ref
                assert lines[-1]['tokens'] == ref
            elif status == 200:
                assert json.loads(data)['tokens'] == ref
        conn.close()


def test_many_streams_at_once_are_each_exact_and_cross_in_hand_offs(lm):
    """32 connections on 4 slots, more client threads than cores and a short
    switch interval: every answer exact; the writer's tokens are the
    scheduler's, and a wake brought more than one."""
    engine = make_engine(lm)
    prompts = [[3 + (i % 7), 5 + i, 2 + (i % 11)][:1 + i % 3]
               for i in range(32)]
    budgets = [4 + (i * 5) % 13 for i in range(32)]
    with DecodeScheduler(engine) as alone:
        refs = [alone.submit(p, max_new_tokens=n).result(120)
                for p, n in zip(prompts[:8], budgets[:8])]
    refs += [greedy_generate(lm, p, n, pad_len=engine.padded_context)
             for p, n in zip(prompts[8:], budgets[8:])]
    got = [None] * 32
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        obs.reset()
        with _Served(make_engine(lm), drain=True) as s:
            def client(i):
                got[i] = _stream(s.port, {'prompt': prompts[i],
                                          'max_new_tokens': budgets[i]})
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(32)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not any(t.is_alive() for t in threads)
        tokens = _counter('http_stream_writer_tokens')
        wakes = _counter('http_stream_writer_wakes')
        sends = _counter('http_stream_writer_sends')
        generated = _counter('decode_tokens_generated')
    finally:
        sys.setswitchinterval(interval)
    for i, (toks, last) in enumerate(got):
        assert toks == refs[i] and last['tokens'] == refs[i], i
        assert last['done'] is True
    assert tokens == generated == sum(budgets)
    assert 0 < wakes < tokens            # several slots live: > 1 a wake
    assert tokens / wakes > 1.5
    assert 32 <= sends <= tokens + 32    # at most one a connection a wake


# -- clients that misbehave --------------------------------------------------

def test_a_client_that_goes_away_mid_stream_costs_nobody_else(lm):
    engine = make_engine(lm, slots=2)
    real_step = engine.decode_step

    def slow_step(*a, **kw):
        time.sleep(0.02)
        return real_step(*a, **kw)

    engine.decode_step = slow_step
    ref = greedy_generate(lm, [4, 5, 6], 10, pad_len=engine.padded_context)
    with _Served(engine) as s:
        sock = socket.create_connection(('127.0.0.1', s.port), 30)
        sock.sendall(_request({'prompt': [9, 8, 7], 'max_new_tokens': 16}))
        seen = b''
        while b'"index": 0}' not in seen:
            seen += sock.recv(4096)
        # gone with the answer unfinished: a reset, not an orderly close
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        b'\x01\x00\x00\x00\x00\x00\x00\x00')
        sock.close()
        toks, last = _stream(s.port, {'prompt': [4, 5, 6],
                                      'max_new_tokens': 10})
        assert toks == ref and last['tokens'] == ref
        # the abandoned generation ran to its end server-side
        until = time.monotonic() + 30
        while (s.sched.active() or engine.pool.allocator.used) \
                and time.monotonic() < until:
            time.sleep(0.01)
        assert s.sched.active() == 0 and engine.pool.allocator.used == 0
        assert s.server.stream_writer._thread.is_alive()
        toks, _ = _stream(s.port, {'prompt': [4, 5, 6], 'max_new_tokens': 10})
        assert toks == ref


def _hand_made(n_tokens=0):
    stream = GenerationStream(3, 4096, replica_id='r', request_id='q')
    for t in range(n_tokens):
        stream._emit(t)
    return stream


def test_a_client_that_reads_nothing_delays_no_other_stream():
    """Driven by hand: a reply larger than its socket's buffers stays that
    connection's backlog and goes out, whole and in order, when its client
    reads at last; meanwhile another connection's reply is out at once."""
    writer = StreamWriter(request_timeout=30).start()
    mine, theirs = socket.socketpair()
    other_mine, other_theirs = socket.socketpair()
    for sock in (mine, theirs):
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    try:
        stalled, quick = _hand_made(), _hand_made()
        results = {}
        handlers = [threading.Thread(
            target=lambda k=k, sock=sock, st=st: results.__setitem__(
                k, writer.serve(sock, st, time.perf_counter())))
            for k, sock, st in (('stalled', mine, stalled),
                                ('quick', other_mine, quick))]
        for h in handlers:
            h.start()
        n = 40000                  # ~1.2 MB of lines: no buffer holds them
        for t in range(n):
            stalled._emit(t)
        stalled._finish('length')
        writer.touched([stalled])
        for t in range(5):
            quick._emit(t)
        quick._finish('length')
        writer.touched([quick])
        handlers[1].join(10)
        assert results.get('quick') is True
        other_theirs.settimeout(10)
        data = b''
        while not data.endswith(b'0\r\n\r\n'):
            data += other_theirs.recv(65536)
        assert data.count(b'"token"') == 5 and b'"done": true' in data
        assert handlers[0].is_alive()      # the stalled reply is not out
        theirs.settimeout(10)
        data = bytearray()
        while not data.endswith(b'\r\n0\r\n\r\n'):
            data += theirs.recv(1 << 20)
        handlers[0].join(10)
        assert results.get('stalled') is True
        first = data.index(b'{"token": 0, "index": 0}\n')
        assert data.count(b'"index"') == n and first < 16
        assert b'{"token": %d, "index": %d}\n' % (n - 1, n - 1) in data
    finally:
        writer.stop()
        for sock in (mine, theirs, other_mine, other_theirs):
            sock.close()


def test_a_connection_that_takes_no_byte_is_dropped_after_the_timeout():
    writer = StreamWriter(request_timeout=0.2).start()
    mine, theirs = socket.socketpair()
    for sock in (mine, theirs):
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    try:
        stream = _hand_made(40000)
        stream._finish('length')
        t0 = time.monotonic()
        assert writer.serve(mine, stream, time.perf_counter()) is False
        assert 0.15 < time.monotonic() - t0 < 5
        assert writer._thread.is_alive() and not writer._conns
    finally:
        writer.stop()
        mine.close()
        theirs.close()


# -- failures, the timeout, shutdown ----------------------------------------

def test_a_failure_mid_stream_arrives_as_the_error_line(lm):
    engine = make_engine(lm, slots=2)
    real_step = engine.decode_step
    calls = {'n': 0}

    def flaky_step(*a, **kw):
        calls['n'] += 1
        if calls['n'] == 3:
            raise RuntimeError('injected device failure')
        return real_step(*a, **kw)

    engine.decode_step = flaky_step
    with _Served(engine) as s, \
            socket.create_connection(('127.0.0.1', s.port), 30) as sock:
        sock.sendall(_request({'prompt': [1, 2], 'max_new_tokens': 12}))
        head, chunks, raw = _read_reply(sock)
        assert head.startswith(b'HTTP/1.1 200 ')
        lines = [json.loads(ln) for ln in b''.join(chunks).splitlines()]
        assert [ln['index'] for ln in lines[:-1]] == [0, 1, 2]
        assert lines[-1] == {
            'error': 'ServingError',
            'message': 'generation failed: RuntimeError: injected device '
                       'failure'}
        assert raw.endswith(b'\r\n0\r\n\r\n')
        # the connection and the server go on
        sock.sendall(_request({'prompt': [1, 2], 'max_new_tokens': 2}))
        _, chunks, _ = _read_reply(sock)
        assert json.loads(b''.join(chunks).splitlines()[-1])['done'] is True


def test_a_stream_with_no_token_for_request_timeout_ends_with_its_line(lm):
    engine = make_engine(lm, slots=1)
    engine.warmup()                    # no compile inside the timeout
    real_step = engine.decode_step
    gate = threading.Event()
    calls = {'n': 0}

    def stuck_step(*a, **kw):
        calls['n'] += 1
        if calls['n'] == 3:
            gate.wait(20)
        return real_step(*a, **kw)

    engine.decode_step = stuck_step
    try:
        with _Served(engine, request_timeout=0.4) as s, \
                socket.create_connection(('127.0.0.1', s.port), 30) as sock:
            t0 = time.monotonic()
            sock.sendall(_request({'prompt': [1, 2], 'max_new_tokens': 12}))
            _, chunks, raw = _read_reply(sock)
            assert 0.3 < time.monotonic() - t0 < 10
            lines = [json.loads(ln) for ln in b''.join(chunks).splitlines()]
            assert [ln['index'] for ln in lines[:-1]] == [0, 1, 2]
            assert lines[-1] == {
                'error': 'TimeoutError',
                'message': 'no token within 0.4s (generated 3 so far)'}
            assert raw.endswith(b'\r\n0\r\n\r\n')
            gate.set()
    finally:
        gate.set()


def test_shutdown_with_streams_open_returns_and_ends_them(lm):
    engine = make_engine(lm, slots=2)
    real_step = engine.decode_step

    def slow_step(*a, **kw):
        time.sleep(0.05)
        return real_step(*a, **kw)

    engine.decode_step = slow_step
    sched = DecodeScheduler(engine)
    server = ServingServer(None, host='127.0.0.1', port=0,
                           generator=sched).start()
    socks = [socket.create_connection(('127.0.0.1', server.port), 30)
             for _ in range(3)]            # two in slots, one waiting
    try:
        for sock in socks:
            sock.sendall(_request({'prompt': [1, 2, 3],
                                   'max_new_tokens': 16}))
        seen = b''
        while b'"index": 0}' not in seen:
            seen += socks[0].recv(4096)
        until = time.monotonic() + 20
        while (sched.active(), sched.pending()) != (2, 1) \
                and time.monotonic() < until:
            time.sleep(0.005)              # all three are admitted or queued
        assert (sched.active(), sched.pending()) == (2, 1)
        t0 = time.monotonic()
        server.shutdown(drain=False, timeout=10)
        assert time.monotonic() - t0 < 8
        assert not server.stream_writer._thread.is_alive()
        assert sched.stream_sink is None
        for sock in socks[1:]:
            # fail-fast close: the typed error as the stream's last line
            sock.settimeout(10)
            _, chunks, raw = _read_reply(sock)
            last = json.loads(b''.join(chunks).splitlines()[-1])
            assert last['error'] == 'EngineClosed', raw
        socks[0].settimeout(10)
        while not seen.endswith(b'\r\n0\r\n\r\n'):
            seen += socks[0].recv(65536)
        assert b'"error": "EngineClosed"' in seen
    finally:
        for sock in socks:
            sock.close()
        server.shutdown(drain=False)
    assert engine.pool.allocator.used == 0


# -- who hands over, and how often -------------------------------------------

def test_a_served_stream_wakes_nobody_a_token_and_iterators_still_work(lm):
    """The scheduler's hand-off is one call an emit; a stream's in-process
    consumers (`iter_tokens`, `result`, `tokens`, `done`) keep their
    contracts beside it."""
    engine = make_engine(lm, slots=4)
    calls = []
    sched = DecodeScheduler(engine, start=False)
    sched.stream_sink = lambda streams: calls.append(list(streams))
    sched._worker.start()
    try:
        streams = [sched.submit([2 + i, 3, 4], max_new_tokens=6)
                   for i in range(4)]
        walked = list(streams[0].iter_tokens(timeout=60))
        outs = [st.result(60) for st in streams]
        assert walked == outs[0] == streams[0].tokens
        assert all(st.done() and len(o) == 6 for st, o in zip(streams, outs))
        assert streams[0].tokens_since(4) == outs[0][4:]
        assert streams[0].exception() is None
    finally:
        sched.close()
    assert not hasattr(streams[0], '_q')         # no queue a stream
    # every stream was named, none twice in a hand-off, and the steps after
    # the prefills named all four at once
    assert {id(st) for c in calls for st in c} == {id(st) for st in streams}
    assert all(len({id(st) for st in c}) == len(c) for c in calls)
    assert max(len(c) for c in calls) == 4
    assert len(calls) < sum(len(o) for o in outs)


def test_a_generator_that_offers_no_hand_off_is_served_on_a_timer(lm):
    class Plain:
        """A generator that is not a DecodeScheduler: submit and what
        /healthz reads, no `stream_sink`."""

        def __init__(self, sched):
            self._sched = sched
            self.engine, self.replica_id = sched.engine, sched.replica_id
            self.breaker, self._worker = sched.breaker, sched._worker

        def submit(self, *a, **kw):
            return self._sched.submit(*a, **kw)

        def close(self, **kw):
            return self._sched.close(**kw)

    engine = make_engine(lm)
    ref = greedy_generate(lm, [6, 7], 5, pad_len=engine.padded_context)
    obs.reset()
    with _Served(engine, generator=Plain) as s:
        assert s.sched.stream_sink is None
        toks, last = _stream(s.port, {'prompt': [6, 7], 'max_new_tokens': 5})
        assert toks == ref and last['tokens'] == ref
    assert _counter('http_stream_writer_tokens') == 5
    assert _counter('http_stream_writer_wakes') == 0


def test_a_window_models_block_arrives_whole():
    """Block diffusion commits a block's tokens together: they share one
    chunk (one send), where a thread a connection wrote a chunk a token."""
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.block_diffusion_lm import (
        BlockDiffusionMoEConfig, BlockDiffusionMoELM)
    with guard():
        default_generator.seed(11)
        model = BlockDiffusionMoELM(BlockDiffusionMoEConfig.tiny())
        model.eval()
        engine = DecodeEngine(model, slots=3, block_size=4, max_blocks=64,
                              max_prompt_len=16, max_new_tokens_cap=16,
                              prompt_buckets=[8, 16])
        block = engine.window
        assert block == 4
        sched = DecodeScheduler(engine, denoising_steps=2)
        server = ServingServer(None, host='127.0.0.1', port=0,
                               generator=sched).start()
        try:
            with socket.create_connection(('127.0.0.1', server.port),
                                          60) as sock:
                # a prompt of two whole blocks: the answer's blocks are whole
                sock.sendall(_request({'prompt': list(range(1, 9)),
                                       'max_new_tokens': 10}))
                _, chunks, _ = _read_reply(sock)
            lines = [c.splitlines() for c in chunks]
            counts = [sum(b'"token"' in ln for ln in c) for c in lines]
            assert counts == [4, 4, 2]       # the last block cut to length
            assert b'"done": true' in lines[-1][-1]
            done = json.loads(lines[-1][-1])
            assert len(done['tokens']) == 10
            assert [json.loads(ln)['token'] for c in lines for ln in c
                    if b'"token"' in ln] == done['tokens']
        finally:
            server.shutdown(drain=False)
