"""One writer thread for every streamed ``/generate`` reply of a server.

A stream's HTTP handler thread sends the response headers and hands the
stream and its socket to the server's :class:`StreamPump`. The decode
engine's scheduler thread then puts a tick's items for every adopted
stream on the pump's queue in ONE ``put`` (``engine._Outbox.flush``);
the pump wakes once, builds one chunk a stream (several NDJSON lines
where a stream brought several tokens; the lines are byte for byte
what the handler writes) and sends it with one ``send`` that never
blocks. At the stream's end the same send carries the done line and the
last chunk, which the handler hands the pump when it adopts the stream,
so a client's reply ends as soon as its last token is written; the
handler then only returns.

Without the pump every token woke its own handler thread: a hundred
wake-ups and twice as many hand-offs of the interpreter lock a tick,
competing with the scheduler. With it, a tick costs one wake-up, and a
pump that fell behind takes every batch waiting at once.

A client that stops reading must not hold the others up: when a send
is short or would block, the pump hands that stream back to its handler
with the bytes it could not send and the index it reached, and keeps
forwarding the stream's later items to the stream's own queue, where
the handler reads them and writes, blocking, as before. A send that
finds the client gone cancels the stream; the handler then returns
without touching the socket. A stream that ends with an error, and
every stream the pump holds when a batch fails, goes back to its
handler, which writes the typed error line.
"""
from __future__ import annotations

import logging
import queue as _queue
import socket
import threading
import time

from ..observability.spans import span as _span
from .decode.engine import GenerateStream

__all__ = ['StreamPump']

# a send that never blocks, and that raises rather than signals on a
# peer that closed
_FLAGS = socket.MSG_DONTWAIT | socket.MSG_NOSIGNAL


class _Adoption:
    """One stream the pump writes, and how it comes back to its handler."""

    __slots__ = ('stream', 'sock', 'index', 'end', 'lines', 'ended',
                 'finished', 'forward', 'gone', 'failed', 'unsent', 'back')

    def __init__(self, stream, sock, index, end):
        self.stream = stream
        self.sock = sock
        self.index = index          # the next token's index on the wire
        self.end = end              # () -> the bytes a sound stream ends on
        self.lines = []             # this wake-up's lines, not yet sent
        self.ended = False          # the stream's end mark has come
        self.finished = False       # the end's bytes are sent or unsent
        self.forward = False        # handed back before its end
        self.gone = False           # the client went away
        self.failed = None          # the pump's own fault, if it failed
        self.unsent = b''           # what the last send left behind
        self.back = threading.Event()

    def token(self, tok):
        # byte for byte the handler's line for the token
        self.lines.append(b'{"index": %d, "token": %d}\n' % (self.index, tok))
        self.index += 1


class StreamPump:
    """Writes the token chunks of every stream a server's handlers hand it
    (:meth:`adopt`), on one thread, and books its work in ``engine``'s
    counts: ``pump_batches`` (wake-ups that sent), ``pump_tokens``,
    ``pump_sends``, ``pump_fallbacks`` (streams handed back with bytes
    unsent), and its thread's CPU in ``stream_cpu_s``."""

    def __init__(self, engine, name='serving'):
        self._engine = engine
        self._name = name
        self._q = _queue.SimpleQueue()
        self._put = self._q.put
        self._live = {}             # stream -> _Adoption; pump thread only
        self._lock = threading.Lock()
        self._stopping = False
        self._thread = None

    def adopt(self, stream, sock, start_index, end):
        """Write ``stream``'s tokens to ``sock`` from the pump, numbered
        from ``start_index``, and at a sound end the bytes ``end()``
        returns (called on the pump's thread); block until the stream
        comes back. Returns the :class:`_Adoption`: ``gone``, the client
        went away; ``unsent``, what the handler writes first; then
        ``finished``, nothing is left to write; ``failed``, the pump's
        fault to report; ``ended``, the stream's error to report; else
        the handler relays the rest of the stream from ``index``. Returns
        None at once where a send to ``sock`` could block: a socket of
        another kind (TLS) or with a timeout (the send would wait out the
        timeout), or a pump stopping."""
        if (type(sock) is not socket.socket
                or sock.gettimeout() is not None
                or not isinstance(stream, GenerateStream)):
            return None
        adoption = _Adoption(stream, sock, start_index, end)
        with self._lock:
            if self._stopping:
                return None
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True,
                    name='mxnet-tpu-%s-stream-pump' % self._name)
                self._thread.start()
            stream._adopt(self._put, adoption)
        adoption.back.wait()
        return adoption

    def stop(self):
        """Adopt nothing more; the thread ends once every stream it holds
        has come back."""
        with self._lock:
            if self._stopping:
                return
            self._stopping = True
            self._put(None)

    # -- the pump thread ----------------------------------------------------

    def _run(self):
        # one span a wake-up, around its work alone: the wait on the queue
        # is outside it (docs/OBSERVABILITY.md "No span covers a wait")
        write = _span.profiler_only('srv.stream.write')
        get, get_nowait = self._q.get, self._q.get_nowait
        stopping = False
        cpu = time.thread_time()
        while not (stopping and not self._live):
            batches = [get()]
            # a pump that fell behind takes every batch waiting at once:
            # a send then carries a stream's tokens of several ticks
            try:
                while True:
                    batches.append(get_nowait())
            except _queue.Empty:
                pass
            if None in batches:
                stopping = True
                batches = [b for b in batches if b is not None]
            with write:
                try:
                    tokens, sends, fallbacks = self._write(batches)
                except Exception as exc:
                    logging.exception('stream pump %s: a batch failed',
                                      self._name)
                    self._fail(batches, exc)
                    tokens = sends = fallbacks = 0
            now = time.thread_time()
            self._engine.note_pump(tokens, sends, fallbacks, now - cpu)
            cpu = now

    def _write(self, batches):
        """Sort one wake-up's items onto their streams, send one chunk a
        stream, hand back the streams that ended or could not be written.
        Returns (tokens, sends, fallbacks)."""
        live = self._live
        touched = {}
        for batch in batches:
            for stream, item in batch:
                st = live.get(stream)
                if st is None:
                    if type(item) is _Adoption:
                        # what the stream's own queue got before the
                        # adoption comes first
                        live[stream] = st = item
                        touched[st] = True
                        queued, st.ended = stream._take_queued()
                        for tok in queued:
                            st.token(tok)
                    # else: the stream came back and ended already
                    continue
                if st.forward:
                    if stream._forward(item):
                        del live[stream]
                elif st.ended:
                    continue
                elif type(item) is int:
                    st.token(item)
                    touched[st] = True
                else:                   # the stream's end mark
                    st.ended = True
                    touched[st] = True
        tokens = sends = fallbacks = 0
        for st in touched:
            data = b''
            if st.lines:
                body = b''.join(st.lines)
                tokens += len(st.lines)
                st.lines = []
                data = b'%x\r\n%s\r\n' % (len(body), body)
            if st.ended and st.stream.exception() is None:
                # the done line and the last chunk ride the same send
                data += st.end()
                st.finished = True
            if data:
                try:
                    sent = st.sock.send(data, _FLAGS)
                except BlockingIOError:
                    sent = 0
                except OSError:
                    # the client went away: retire the sequence, and
                    # nobody touches the socket again
                    st.stream.cancel()
                    st.gone = True
                    del live[st.stream]
                    st.back.set()
                    continue
                sends += 1
                if sent < len(data):
                    # the client's window is full: its handler writes the
                    # rest, blocking, while the pump serves the others
                    st.unsent = data[sent:]
                    fallbacks += 1
                    if st.ended:
                        del live[st.stream]
                    else:
                        st.forward = True
                    st.back.set()
                    continue
            if st.ended:
                del live[st.stream]
                st.back.set()
        return tokens, sends, fallbacks

    def _fail(self, batches, exc):
        """A batch raised: hand every stream the pump holds back to its
        handler with the fault, which the handler reports as the stream's
        typed error line; the stream is cancelled, and its later items go
        to its own queue. The pump carries on with new streams."""
        held = list(self._live.values())
        held += [item for batch in batches for _, item in batch
                 if type(item) is _Adoption and item not in held]
        self._live.clear()
        for st in held:
            if st.back.is_set() and not st.forward:
                continue                # came back already
            st.failed = exc
            st.stream._disown()
            st.stream.cancel()
            st.back.set()
