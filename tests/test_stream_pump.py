"""The stream pump: one writer thread for every streamed /generate reply
(serving/stream_pump.py), against a deterministic fake decode program."""
import json
import random
import socket
import struct
import sys
import threading
import time

import pytest

from mxnet_tpu.serving.decode.engine import DecodeEngine
from mxnet_tpu.serving.server import ServingHTTPServer
from mxnet_tpu.serving.stream_pump import StreamPump, _Adoption
from test_decode import _EngineSession, _expected, _FakeProgram


class _PacedProgram(_FakeProgram):
    """The fake program with a step of ``step_s``: requests that arrive
    together share ticks, and a long stream stays live for a while."""

    def __init__(self, step_s=0.001, **kw):
        super().__init__(**kw)
        self.step_s = step_s

    def run_step(self, cache, tokens, positions):
        time.sleep(self.step_s)
        return super().run_step(cache, tokens, positions)


def _request(prompt, max_new, start_index=0):
    body = json.dumps({'tokens': prompt, 'max_new_tokens': max_new,
                       'start_index': start_index,
                       'stream': True}).encode()
    return (b'POST /generate HTTP/1.1\r\nHost: x\r\n'
            b'Content-Type: application/json\r\nConnection: close\r\n'
            b'Content-Length: %d\r\n\r\n' % len(body)) + body


def _dechunk(raw):
    """The NDJSON lines of a whole chunked reply, as bytes."""
    head, body = raw.split(b'\r\n\r\n', 1)
    assert head.startswith(b'HTTP/1.1 200')
    return _lines(body)


def _lines(body):
    """The lines of a chunked body up to its last chunk."""
    data = b''
    while True:
        size, body = body.split(b'\r\n', 1)
        n = int(size, 16)
        if n == 0:
            return data.splitlines(keepends=True)
        assert body[n:n + 2] == b'\r\n'
        data, body = data + body[:n], body[n + 2:]


def _stream(port, prompt, max_new, start_index=0, out=None):
    """One streamed /generate over a raw socket: its reply's lines."""
    with socket.create_connection(('127.0.0.1', port), timeout=30) as s:
        s.sendall(_request(prompt, max_new, start_index))
        raw = b''
        while True:
            part = s.recv(65536)
            if not part:
                break
            raw += part
    lines = _dechunk(raw)
    if out is not None:
        out[tuple(prompt)] = lines
    return lines


def _check(lines, prompt, max_new, start_index=0):
    recs = [json.loads(ln) for ln in lines]
    want = _expected(prompt, max_new)
    assert [r['token'] for r in recs[:-1]] == want
    assert [r['index'] for r in recs[:-1]] == list(
        range(start_index, start_index + max_new))
    assert recs[-1]['done'] and recs[-1]['tokens'] == want


def _serve(program, **engine):
    eng = DecodeEngine(program, timeout_s=30.0, **engine)
    return eng, ServingHTTPServer(_EngineSession(eng), 0).start()


def _concurrent(port, prompts, max_new):
    out = {}
    threads = [threading.Thread(target=_stream,
                                args=(port, p, max_new, 0, out))
               for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    return out


@pytest.mark.parametrize('streams, max_new', [(32, 24), (32, 1), (5, 40)])
def test_pump_writes_the_handler_paths_lines_in_fewer_wake_ups(
        monkeypatch, streams, max_new):
    """Concurrent streams through the pump read, line for line and byte
    for byte, what each handler wrote on its own; a wake-up of the pump
    carries more than one token."""
    prompts = [[1 + i, 2, 3 + i % 5] for i in range(streams)]

    def run():
        eng, srv = _serve(_PacedProgram(slots=32, max_len=128))
        try:
            out = _concurrent(srv.port, prompts, max_new)
            counts = eng.stats()['counts']
        finally:
            srv.stop()
            eng.close()
        return out, counts

    pumped, counts = run()
    assert counts['pump_tokens'] == streams * max_new
    assert counts['pump_fallbacks'] == 0
    assert counts['pump_tokens'] / counts['pump_batches'] > 1
    assert counts['pump_sends'] >= counts['pump_batches']
    for p in prompts:
        _check(pumped[tuple(p)], p, max_new)
    monkeypatch.setattr(StreamPump, 'adopt', lambda *a: None)
    handled, counts = run()
    assert counts['pump_tokens'] == counts['pump_batches'] == 0
    assert handled == pumped


def test_a_client_that_stops_reading_falls_back_alone():
    """A client whose window is full gets its stream handed back to its
    handler: the other streams finish without waiting for it, and once it
    reads, every token arrives once and in order."""
    max_new = 3000
    eng, srv = _serve(_PacedProgram(step_s=0.0, slots=4, max_len=4096))
    # accepted sockets inherit the listener's buffer sizes
    srv._httpd.socket.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    slow = socket.socket()
    try:
        slow.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        slow.settimeout(30)
        slow.connect(('127.0.0.1', srv.port))
        slow.sendall(_request([9, 9, 9], max_new))
        deadline = time.monotonic() + 30
        while eng.stats()['counts']['pump_fallbacks'] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        others = [[1, 2], [3, 4, 5], [6]]
        out = _concurrent(srv.port, others, 20)
        for p in others:
            _check(out[tuple(p)], p, 20)
        raw = b''
        while True:
            part = slow.recv(65536)
            if not part:
                break
            raw += part
        _check(_dechunk(raw), [9, 9, 9], max_new)
        assert eng.stats()['counts']['pump_fallbacks'] == 1
    finally:
        slow.close()
        srv.stop()
        eng.close()


def test_a_client_that_leaves_cancels_its_stream_and_no_other():
    """A send that finds the client gone cancels its stream and frees the
    slot; the pump goes on writing for the others."""
    eng, srv = _serve(_PacedProgram(step_s=0.002, slots=4, max_len=4096))
    gone = socket.socket()
    try:
        gone.settimeout(30)
        gone.connect(('127.0.0.1', srv.port))
        gone.sendall(_request([4, 4], 2000))
        got = b''
        while got.count(b'"token"') < 3:
            got += gone.recv(4096)
        # close with a reset: the next send to it fails
        gone.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack('ii', 1, 0))
        gone.close()
        deadline = time.monotonic() + 30
        while eng.stats()['counts']['retired'].get('cancelled', 0) < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        others = [[1, 2], [3, 4, 5]]
        out = _concurrent(srv.port, others, 30)
        for p in others:
            _check(out[tuple(p)], p, 30)
        st = eng.stats()
        assert st['free_slots'] == 4
        assert st['counts']['retired'] == {'cancelled': 1, 'length': 2}
    finally:
        gone.close()
        srv.stop()
        eng.close()


@pytest.mark.parametrize('kind, error_class',
                         [('worker_crash', 'WorkerCrashError'),
                          ('preempt', 'PreemptionSignal')])
def test_an_engine_fault_mid_stream_ends_the_pumped_stream_typed(
        kind, error_class):
    """A fault after the pump has written some tokens: the stream's last
    line is today's typed error line, after the tokens in order."""
    import mxnet_tpu as mx
    from mxnet_tpu.resilience.policy import CircuitBreaker
    eng, srv = _serve(_FakeProgram(slots=2),
                      breaker=CircuitBreaker(failure_threshold=1,
                                             reset_timeout=0.2))
    mx.config.set('MXNET_TPU_FAULT', '%s@serving.decode.3:1' % kind)
    try:
        recs = [json.loads(ln) for ln in _stream(srv.port, [1, 2], 8)]
        toks = [r['token'] for r in recs[:-1]]
        assert 0 < len(toks) < 8 and toks == _expected([1, 2], len(toks))
        assert [r['index'] for r in recs[:-1]] == list(range(len(toks)))
        last = recs[-1]
        assert last['done'] and last['error_class'] == error_class
        assert error_class in last['error']
        assert eng.stats()['counts']['pump_tokens'] >= len(toks)
    finally:
        mx.config.unset('MXNET_TPU_FAULT')
        srv.stop()
        eng.close()


class _Wrapped(socket.socket):
    """A connection that is a socket of another kind, as TLS's is."""


@pytest.mark.parametrize('why', ['socket_subclass', 'socket_timeout'])
def test_a_connection_the_pump_cannot_adopt_is_relayed_by_its_handler(
        monkeypatch, why):
    """Where a send could block (a socket of another kind, or with a
    timeout, which a send would wait out), the handler writes its stream
    itself, as it always has, and the pump never starts."""
    import socketserver
    if why == 'socket_subclass':
        def get_request(server):
            conn, addr = server.socket.accept()
            return _Wrapped(fileno=conn.detach()), addr
        monkeypatch.setattr(socketserver.TCPServer, 'get_request',
                            get_request)
    else:
        monkeypatch.setattr(socketserver.StreamRequestHandler, 'timeout',
                            20.0)
    eng, srv = _serve(_FakeProgram(slots=2))
    try:
        _check(_stream(srv.port, [5, 6], 6, start_index=11), [5, 6], 6,
               start_index=11)
        counts = eng.stats()['counts']
        assert counts['pump_tokens'] == counts['pump_batches'] == 0
        assert srv._pump._thread is None
        assert counts['stream_cpu_s'] > 0
    finally:
        srv.stop()
        eng.close()


class _Counts:
    """What a pump books, for a pump with no engine behind it."""

    def __init__(self):
        self.tokens = 0

    def note_pump(self, tokens, sends, fallbacks, cpu_s):
        self.tokens += tokens


def test_adoption_racing_the_scheduler_loses_and_reorders_nothing():
    """Handlers adopt their streams at any moment while the scheduler
    thread emits and flushes: what a stream's own queue got before the
    adoption and what the pump got after reach the socket once each, in
    order, for every stream."""
    from mxnet_tpu.serving.decode.engine import GenerateStream, _Outbox
    n_streams, n_tokens = 32, 200
    box, counts = _Outbox(), _Counts()
    pump = StreamPump(counts, name='test')
    streams = [GenerateStream(1) for _ in range(n_streams)]
    for s in streams:
        s._outbox = box
    pairs = [socket.socketpair() for _ in streams]
    got = [None] * n_streams

    def schedule():
        box.owner = threading.get_ident()
        for i in range(n_tokens):
            for s in streams:
                s._emit(i)
            if i % 3 == 0:
                box.flush()
        for s in streams:
            s._finish('length')
        box.flush()

    def handle(k):
        time.sleep(random.random() * 0.01)
        back = pump.adopt(streams[k], pairs[k][0], 0,
                          lambda: b'0\r\n\r\n')
        assert back is not None and back.finished and not back.unsent

    def read(k):
        raw = b''
        while not raw.endswith(b'0\r\n\r\n'):
            raw += pairs[k][1].recv(65536)
        got[k] = _lines(raw)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=schedule)] + [
            threading.Thread(target=f, args=(k,))
            for k in range(n_streams) for f in (handle, read)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
        pump.stop()
        for a, b in pairs:
            a.close()
            b.close()
    want = [b'{"index": %d, "token": %d}\n' % (i, i)
            for i in range(n_tokens)]
    assert got == [want] * n_streams
    assert counts.tokens == n_streams * n_tokens
    assert all(s._q.empty() for s in streams)


class _Books(_Counts):
    def __init__(self):
        super().__init__()
        self.wakeups = []

    def note_pump(self, tokens, sends, fallbacks, cpu_s):
        super().note_pump(tokens, sends, fallbacks, cpu_s)
        self.wakeups.append((tokens, sends))


def test_a_pump_that_fell_behind_sends_the_waiting_ticks_at_once():
    """Batches that queued while the pump was busy go out in one wake-up,
    one send a stream for all their tokens, in order, and the stream's end
    in the same send."""
    from mxnet_tpu.serving.decode.engine import GenerateStream
    books = _Books()
    pump = StreamPump(books, name='test')
    streams = [GenerateStream(1) for _ in range(2)]
    pairs = [socket.socketpair() for _ in streams]
    adoptions = [_Adoption(s, a, 5, lambda: b'0\r\n\r\n')
                 for s, (a, _) in zip(streams, pairs)]
    try:
        for s, adoption in zip(streams, adoptions):
            s._adopt(pump._put, adoption)
        for tick in range(3):           # three ticks, the pump busy
            pump._put([(s, 10 * k + tick) for k, s in enumerate(streams)])
        for s in streams:
            s._finish('length')
        pump.stop()
        pump._run()                     # here: every batch is waiting
        assert books.wakeups[0] == (6, 2)
        assert all(a.back.is_set() and a.finished for a in adoptions)
        for k, (a, b) in enumerate(pairs):
            a.close()
            raw = b''
            while True:
                part = b.recv(4096)
                if not part:
                    break
                raw += part
            assert raw.count(b'\r\n') == 4    # one chunk, then the end
            assert _lines(raw) == [
                b'{"index": %d, "token": %d}\n' % (5 + t, 10 * k + t)
                for t in range(3)]
    finally:
        for a, b in pairs:
            a.close()
            b.close()


def test_a_batch_that_fails_hands_every_stream_back_typed(monkeypatch):
    """A fault in the pump's own work does not strand a handler: every
    stream the pump holds ends on a typed error line after its tokens in
    order, its slot frees, and the pump goes on writing new streams."""
    write, calls = StreamPump._write, []

    def planted(pump, batches):
        calls.append(1)
        if len(calls) == 12:
            raise RuntimeError('planted')
        return write(pump, batches)

    monkeypatch.setattr(StreamPump, '_write', planted)
    eng, srv = _serve(_PacedProgram(step_s=0.002, slots=4, max_len=4096))
    try:
        prompts = [[1, 2], [3, 4, 5], [6], [7, 8]]
        out = _concurrent(srv.port, prompts, 400)
        for p in prompts:
            recs = [json.loads(ln) for ln in out[tuple(p)]]
            idx = [r['index'] for r in recs[:-1]]
            assert idx == sorted(set(idx)) and len(idx) < 400
            assert recs[-1]['done'] and recs[-1]['error_class'] \
                == 'RuntimeError' and 'planted' in recs[-1]['error']
        deadline = time.monotonic() + 30
        while eng.stats()['free_slots'] < 4:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert eng.stats()['counts']['retired'] == {'cancelled': 4}
        _check(_stream(srv.port, [9, 1], 20), [9, 1], 20)
    finally:
        srv.stop()
        eng.close()
