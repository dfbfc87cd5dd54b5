"""InferenceSession: the serving engine's composition layer + HTTP.

One object wires the frozen program, the bucket ladder, the
micro-batcher, and the resilience/observability layers into the
request path a production frontend talks to:

    session = serving.InferenceSession(frozen)
    fut = session.submit(x)          # futures API
    y = session.infer(x)             # blocking convenience

Request path: submit -> admission control (bounded queue, typed
:class:`~.batcher.BackpressureError`) -> micro-batch flush (max_batch
or deadline) -> pad to bucket -> AOT executable -> unpad -> future.

Failure path (docs/RESILIENCE.md, threaded through rather than bolted
on): every device-side batch runs under the circuit breaker; a
transient failure — injected ``hang@serving.infer`` (stall watchdog
artifact + ``DeviceStallError``), injected ``device_loss@serving``, or
a real backend error — counts a breaker failure and the batch is
re-served on the CPU fallback path, so requests complete degraded
instead of erroring. When the breaker opens, batches skip the dead
accelerator entirely until the reset probe closes it again. Breaker
trips land in the metrics registry and the flight recorder
(``breaker_open`` event + ring dump), and :meth:`InferenceSession.status`
reports ``degraded`` while the fallback is serving.

The JSON-over-HTTP endpoint is stdlib-only and OFF by default
(``MXNET_TPU_SERVE_HTTP_PORT=0``), the same opt-in pattern as the
Prometheus exporter: production fronts this engine with a real
gateway; the endpoint exists for interactive runs and the selftest.
"""
from __future__ import annotations

import json
import logging
import threading
import time

import numpy as onp

from ..observability import trace as _trace
from ..observability.spans import span as _span
from .batcher import BackpressureError, BatcherClosed, MicroBatcher, \
    RequestTimeout
from .freeze import FrozenProgram

__all__ = ['InferenceSession', 'ServingHTTPServer',
           'maybe_start_http_server']


def _chunked(data):
    """``data`` as one HTTP/1.1 chunk."""
    return b'%x\r\n%s\r\n' % (len(data), data)


def _done_line(stream, request_id):
    """The last NDJSON line of a sound stream, as a chunk."""
    done = {'done': True, 'tokens': stream.tokens,
            'finish_reason': stream.finish_reason,
            'degraded': stream.degraded}
    # prefill_only admission: the exported seqstate rides the done line
    # so the gateway can POST it straight to a decode-class replica (no
    # /drain round-trip — this replica stays healthy)
    seqst = getattr(stream, 'seqstate', None)
    if seqst is not None:
        done['seqstate'] = seqst
    if request_id is not None:
        done['request_id'] = request_id
    return _chunked((json.dumps(done, sort_keys=True) + '\n').encode())


# ceiling on an HTTP handler's wait when MXNET_TPU_SERVE_TIMEOUT_S=0
# disables the per-request budget: handler threads must never block
# forever (ThreadingHTTPServer wedges one thread per connection)
_HTTP_MAX_WAIT_S = 300.0


def _knob(name, default):
    try:
        from .. import config as _config
        v = _config.get(name)
        return default if v is None else v
    except Exception:
        return default


class InferenceSession:
    """Serve a :class:`~.freeze.FrozenProgram` behind dynamic
    micro-batching, a circuit breaker, and a CPU fallback — or a
    :class:`~.decode.DecodeProgram` behind the continuous-batching
    decode engine (:meth:`generate` streams tokens; docs/SERVING.md
    "Autoregressive decoding").

    Knob defaults come from ``MXNET_TPU_SERVE_*`` (docs/ENV_VARS.md);
    constructor arguments win. ``watchdog=True`` (default) arms a
    stall watchdog whose fault-injection site is ``serving.infer``
    (one-shot) or ``serving.decode`` (generation);
    ``stall_artifact`` overrides its dump path.
    """

    def __init__(self, frozen, max_batch=None, deadline_ms=None,
                 max_queue=None, timeout_s=None, breaker=None,
                 watchdog=True, stall_artifact=None, name=None,
                 warmup=False, max_new_tokens=None,
                 prefill_interleave=None, draft=None, adapters=None):
        from .decode import DecodeProgram
        from ..resilience.policy import CircuitBreaker
        if isinstance(frozen, DecodeProgram):
            self._init_decode(frozen, max_queue, timeout_s, breaker,
                              watchdog, stall_artifact, name, warmup,
                              max_new_tokens, prefill_interleave,
                              draft, adapters)
            return
        if draft is not None:
            raise TypeError('draft= (speculative decoding) applies to '
                            'decode-mode sessions only')
        if adapters is not None:
            raise TypeError('adapters= (multi-adapter serving) '
                            'applies to decode-mode sessions only')
        self._engine = None
        if not isinstance(frozen, FrozenProgram):
            raise TypeError('InferenceSession serves a FrozenProgram '
                            'or a DecodeProgram; got %s (use '
                            'serving.freeze / freeze_decode first)'
                            % type(frozen).__name__)
        self.frozen = frozen
        self.name = name or frozen.name
        max_batch = int(max_batch
                        if max_batch is not None
                        else min(frozen.policy.max_batch,
                                 int(_knob('MXNET_TPU_SERVE_MAX_BATCH',
                                           64))))
        if max_batch > frozen.policy.max_batch:
            raise ValueError(
                'max_batch %d exceeds the largest bucket %d'
                % (max_batch, frozen.policy.max_batch))
        threshold = int(_knob('MXNET_TPU_SERVE_BREAKER', 3))
        self._breaker = breaker if breaker is not None else \
            CircuitBreaker(failure_threshold=max(1, threshold),
                           reset_timeout=30.0)
        self._watchdog = None
        if watchdog:
            from ..resilience.watchdog import Watchdog
            self._watchdog = Watchdog(
                budgets={'infer': float(
                    _knob('MXNET_TPU_WATCHDOG_STEP_S', 300.0))},
                artifact_path=stall_artifact, name=self.name,
                site='serving.infer', on_stall=self._on_real_stall)
            # background monitor: a REAL hang blocks the batcher
            # worker inside the device call, so only a separate
            # thread can observe the stale heartbeat — it writes the
            # stall artifact, trips the breaker, and flips status to
            # degraded (the wedged worker itself cannot; pending
            # requests fail via the batcher's per-request timeouts)
            self._watchdog.start()
        self._lock = threading.Lock()
        self._batch_seq = 0
        self._fallback_batches = 0
        self._accel_batches = 0
        self._degraded = False
        self._last_error = None
        if warmup:
            frozen.warmup()
        self._batcher = MicroBatcher(
            self._run_batch,
            max_batch=max_batch,
            deadline_ms=float(deadline_ms if deadline_ms is not None
                              else _knob('MXNET_TPU_SERVE_DEADLINE_MS',
                                         5.0)),
            max_queue=int(max_queue if max_queue is not None
                          else _knob('MXNET_TPU_SERVE_QUEUE_DEPTH',
                                     256)),
            timeout_s=float(timeout_s if timeout_s is not None
                            else _knob('MXNET_TPU_SERVE_TIMEOUT_S',
                                       30.0)),
            name=self.name,
            # rank-exact request validation at admission (a genuine
            # (1, h, w) example is never mistaken for a batched one)
            example_shapes=[s for _n, s, _dt in frozen.data_descs])

    def _init_decode(self, program, max_queue, timeout_s, breaker,
                     watchdog, stall_artifact, name, warmup,
                     max_new_tokens, prefill_interleave, draft=None,
                     adapters=None):
        """Generation mode: continuous-batching decode engine instead
        of the flush micro-batcher (same admission/resilience
        contract, new injection site ``serving.decode``).

        ``draft`` (or the ``MXNET_TPU_SERVE_SPEC_DRAFT`` artifact
        path) enables speculative decoding on paged targets with
        ``spec_k > 0``: the draft proposes, the target verifies.
        ``adapters`` (an AdapterRegistry or an artifact-directory
        root, default ``MXNET_TPU_SERVE_ADAPTER_DIR``) backs
        per-request LoRA selection on adapter-carrying programs."""
        from .decode.engine import DecodeEngine
        from ..resilience.policy import CircuitBreaker
        if draft is None and getattr(program, 'paged', False) \
                and int(getattr(program, 'spec_k', 0)) > 0:
            draft_path = _knob('MXNET_TPU_SERVE_SPEC_DRAFT', None)
            if draft_path:
                from .decode import load_decode
                draft = load_decode(str(draft_path))
        self.frozen = program
        self.name = name or program.name
        self._batcher = None
        threshold = int(_knob('MXNET_TPU_SERVE_BREAKER', 3))
        self._breaker = breaker if breaker is not None else \
            CircuitBreaker(failure_threshold=max(1, threshold),
                           reset_timeout=30.0)
        self._watchdog = None
        if watchdog:
            from ..resilience.watchdog import Watchdog
            self._watchdog = Watchdog(
                budgets={'decode': float(
                    _knob('MXNET_TPU_WATCHDOG_STEP_S', 300.0))},
                artifact_path=stall_artifact, name=self.name,
                site='serving.decode',
                on_stall=lambda rec: self._engine.on_stall(rec))
            self._watchdog.start()
        if warmup:
            program.warmup()
        self._engine = DecodeEngine(
            program,
            max_queue=int(max_queue if max_queue is not None
                          else _knob('MXNET_TPU_SERVE_QUEUE_DEPTH',
                                     256)),
            timeout_s=float(timeout_s if timeout_s is not None
                            else _knob('MXNET_TPU_SERVE_TIMEOUT_S',
                                       30.0)),
            max_new_tokens=int(
                max_new_tokens if max_new_tokens is not None
                else _knob('MXNET_TPU_SERVE_MAX_NEW_TOKENS', 64)),
            prefill_interleave=int(
                prefill_interleave if prefill_interleave is not None
                else _knob('MXNET_TPU_SERVE_PREFILL_INTERLEAVE', 1)),
            breaker=self._breaker, watchdog=self._watchdog,
            name=self.name, draft=draft, adapters=adapters)

    # -- request API -------------------------------------------------------

    def _require_oneshot(self, what):
        if self._engine is not None:
            raise TypeError('%s serves one-shot programs; this session '
                            'wraps a DecodeProgram — use generate()'
                            % what)

    def submit(self, *arrays):
        """Enqueue one single-example request; returns a Future whose
        result is the list of per-example output arrays."""
        self._require_oneshot('submit')
        return self._batcher.submit(*arrays)

    def infer(self, *arrays, timeout=None):
        """Blocking single-request inference through the batched
        engine."""
        self._require_oneshot('infer')
        return self._batcher.infer(*arrays, timeout=timeout)

    def infer_batch(self, arrays, timeout=None):
        """Run an already-stacked batch (one array per input, n rows)
        through the bucketed program directly — the bulk path bench /
        offline scoring uses; the micro-batch queue is for concurrent
        single requests."""
        self._require_oneshot('infer_batch')
        n = onp.asarray(arrays[0]).shape[0]
        seq = self._next_seq()
        return self._serve(list(arrays), n, seq)

    def generate(self, tokens, max_new_tokens=None, eos_id=None,
                 request_id=None, prefill_only=False, trace=None,
                 adapter=None, temperature=None, top_p=None,
                 seed=None):
        """Stream a generation: returns a
        :class:`~.decode.GenerateStream` (iterate per-token, or
        ``.result(timeout)`` for the full sequence). Decode-mode
        sessions only. ``request_id`` makes re-admission idempotent
        (the gateway's mid-stream failover contract);
        ``prefill_only=True`` is the disaggregated-serving admission
        — the stream finishes ``'migrated'`` with its exported
        seqstate payload on ``stream.seqstate``. ``adapter`` selects
        the LoRA variant and ``temperature``/``top_p``/``seed`` the
        sampling law (engine defaults: base weights, greedy)."""
        if self._engine is None:
            raise TypeError('generate() needs a DecodeProgram session '
                            '(use serving.freeze_decode)')
        kwargs = {'max_new_tokens': max_new_tokens, 'eos_id': eos_id,
                  'request_id': request_id}
        # ride as a kwarg only when asked for: duck-typed engines
        # predating disaggregation / multi-adapter keep working
        if prefill_only:
            kwargs['prefill_only'] = True
        if trace is not None:
            kwargs['trace'] = trace
        if adapter is not None:
            kwargs['adapter'] = adapter
        if temperature is not None:
            kwargs['temperature'] = temperature
        if top_p is not None:
            kwargs['top_p'] = top_p
        if seed is not None:
            kwargs['seed'] = seed
        return self._engine.generate(tokens, **kwargs)

    # -- batched execution (batcher worker thread) -------------------------

    def _next_seq(self):
        with self._lock:
            seq = self._batch_seq
            self._batch_seq += 1
        return seq

    def _run_batch(self, stacked, n):
        return self._serve(stacked, n, self._next_seq())

    def _on_real_stall(self, record):
        """Watchdog monitor-thread escalation: a device call overran
        the stall budget with the worker still blocked inside it."""
        with self._lock:
            self._degraded = True
            self._last_error = ('stall: %s phase stalled %.1fs '
                                '(budget %.1fs)'
                                % (record.get('phase'),
                                   record.get('waited_s', 0.0),
                                   record.get('budget_s', 0.0)))
        self._breaker.record_failure()
        try:
            from .. import observability as _obs
            if _obs.enabled():
                _obs.serving_instruments().degraded.set(1.0)
        except Exception:
            pass

    def _execute_accel(self, stacked, n, seq):
        from ..resilience.policy import inject
        inject('serving',
               ('device_loss', 'device_unavailable', 'device_stall',
                'worker_crash', 'preempt'), step=seq)
        if self._watchdog is not None:
            # an injected hang@serving.infer aged the heartbeat at
            # beat(); check() now writes the stall artifact + flight
            # dump and raises DeviceStallError into the breaker
            self._watchdog.check()
        return self.frozen.run(stacked, n)

    def _serve(self, stacked, n, seq):
        from ..resilience.policy import (CircuitOpenError,
                                         PreemptionSignal,
                                         WorkerCrashError, is_transient)
        if self._watchdog is not None:
            self._watchdog.beat(step=seq, phase='infer')
        was_open = self._breaker.state == 'open'
        try:
            outs = self._breaker.call(self._execute_accel, stacked, n,
                                      seq)
        except (WorkerCrashError, PreemptionSignal) as exc:
            # the work itself died (worker crash / preemption notice):
            # fail the batch typed — clients retry against a recovered
            # engine — rather than completing it degraded. The breaker
            # counted the failure, so repeated crashes still open it.
            self._note_failure(exc, seq, was_open)
            raise
        except Exception as exc:
            if not (is_transient(exc)
                    or isinstance(exc, CircuitOpenError)):
                raise               # bug-shaped: fail the requests loudly
            self._note_failure(exc, seq, was_open)
            outs = self.frozen.run_fallback(stacked, n)
            with self._lock:
                self._fallback_batches += 1
            self._instrument_fallback()
            return outs
        with self._lock:
            self._accel_batches += 1
            self._degraded = False
            self._last_error = None
        self._instrument_ok()
        return outs

    def _note_failure(self, exc, seq, was_open):
        with self._lock:
            self._degraded = True
            self._last_error = '%s: %s' % (type(exc).__name__, exc)
        state = self._breaker.state
        newly_open = state != 'closed' and not was_open
        logging.warning('serving %s: batch %d failed (%s); state=%s, '
                        'serving on CPU fallback', self.name, seq,
                        self._last_error, state)
        try:
            from .. import observability as _obs
            if _obs.enabled():
                inst = _obs.serving_instruments()
                inst.degraded.set(1.0)
                if newly_open:
                    inst.breaker_trips.inc()
                    # flight escalation: the trip event lands in the
                    # ring, then the whole ring dumps — post-mortems
                    # see the requests leading up to the trip
                    _obs.record_event('breaker_open', step=seq,
                                      error=self._last_error)
                    _obs.flight_dump(reason='breaker')
                else:
                    _obs.record_event('serve_fallback', step=seq,
                                      error=self._last_error)
        except Exception:
            pass

    def _instrument_fallback(self):
        try:
            from .. import observability as _obs
            if _obs.enabled():
                _obs.serving_instruments().fallbacks.inc()
        except Exception:
            pass

    def _instrument_ok(self):
        try:
            from .. import observability as _obs
            if _obs.enabled():
                _obs.serving_instruments().degraded.set(0.0)
        except Exception:
            pass

    # -- introspection / lifecycle -----------------------------------------

    def retry_after_hint(self):
        """Estimated seconds until a newly admitted request could be
        served (queue depth x recent batch/step latency); the HTTP 429
        path advertises it as ``Retry-After``."""
        if self._engine is not None:
            return self._engine.retry_after_hint()
        return self._batcher.retry_after_hint()

    def status(self):
        """Machine-readable session state (the /status JSON)."""
        if self._engine is not None:
            stats = self._engine.stats()
            record = {
                'status': 'degraded' if stats['degraded'] else 'ok',
                'name': self.name,
                'mode': 'decode',
                'breaker': stats['breaker'],
                'error': stats['error'],
                'decode': stats,
                'prefill_buckets':
                    list(self.frozen.policy.buckets),
                'slots': self.frozen.slots,
                'max_len': self.frozen.max_len,
                'compiled': self.frozen.compile_count,
            }
            if getattr(self.frozen, 'paged', False):
                record['paged'] = {
                    'page_size': self.frozen.page_size,
                    'pages': self.frozen.pages,
                    'max_pages': self.frozen.max_pages,
                    'spec_k': int(getattr(self.frozen, 'spec_k', 0)),
                }
            return record
        with self._lock:
            degraded = self._degraded
            record = {
                'status': 'degraded' if degraded else 'ok',
                'name': self.name,
                'breaker': self._breaker.state,
                'error': self._last_error,
                'batches': {'accel': self._accel_batches,
                            'fallback': self._fallback_batches},
            }
        record['buckets'] = list(self.frozen.policy.buckets)
        record['compiled'] = self.frozen.compile_count
        record['queue'] = self._batcher.stats()
        return record

    def close(self, drain=True):
        if self._engine is not None:
            self._engine.close(drain=drain)
        else:
            self._batcher.close(drain=drain)
        if self._watchdog is not None:
            self._watchdog.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class ServingHTTPServer:
    """Stdlib JSON endpoint over an :class:`InferenceSession`.

    Routes::

        GET  /status    session status JSON
        GET  /healthz   {"ok": true|false, "status": ...}
        GET  /trace     mxnet_tpu.trace.v1 span records as NDJSON
                        (?since=N drain cursor); empty unless
                        MXNET_TPU_TRACE is on (docs/OBSERVABILITY.md
                        "Distributed request tracing")
        POST /predict   {"data": [...]}            one example
                        {"instances": [[...], ...]} many examples
        POST /generate  {"tokens": [...], "max_new_tokens": N,
                         "eos_id": E, "stream": true|false}
                        decode-mode sessions; ``stream: true``
                        answers chunked NDJSON — one
                        {"token": t, "index": i} line per decoded
                        token, then a {"done": true, ...} summary

    Binds 127.0.0.1 only; OFF by default — enable per-process with
    ``MXNET_TPU_SERVE_HTTP_PORT=<port>`` + :func:`maybe_start_http_server`
    or construct directly (port 0 picks a free port).

    ``decode_session`` (optional) mounts a SECOND, decode-mode session
    behind ``/generate`` so one endpoint fronts both workloads — the
    shape the open-loop load harness (``mxnet_tpu.loadgen``) drives.
    ``/status`` then nests both sessions and ``/healthz`` is healthy
    only when both are.

    Status codes are the error taxonomy the load harness keys on:
    200 served (``degraded`` flag in the payload when the CPU fallback
    did the work), 429 shed by admission control (with a
    ``Retry-After`` header estimated from queue depth x recent batch
    latency), 504 per-request budget lapsed, 503 engine closed or
    unhealthy, 500 request aborted (worker crash / preemption) or
    engine bug, 400 caller error.

    ``max_concurrent`` (default ``MXNET_TPU_SERVE_MAX_CONCURRENT``,
    0 = unbounded) caps in-flight POST handlers: each connection gets
    a thread, so without a cap an overload saturates the host with
    thread-scheduling contention BEFORE any bounded queue fills — the
    latency-degradation mode the load harness measures. Past the cap,
    requests shed instantly with 429 + Retry-After, the same typed
    contract as queue-depth backpressure.
    """

    def __init__(self, session, port, host='127.0.0.1',
                 decode_session=None, max_concurrent=None):
        self.session = session
        self.decode_session = decode_session
        self.host = host
        self.port = int(port)
        self.max_concurrent = int(
            max_concurrent if max_concurrent is not None
            else _knob('MXNET_TPU_SERVE_MAX_CONCURRENT', 0))
        self._httpd = None
        self._thread = None
        # graceful drain (docs/SERVING.md "Drain & live migration"):
        # begin_drain() flips /healthz to 'draining', sheds new
        # admissions 503-typed, exports every in-flight sequence as a
        # seqstate payload served over GET /drain, and records the
        # resumable exit code once the handoff completes
        self._draining = False
        self._drain_lock = threading.Lock()
        self._drain_payloads = []
        self._drain_unserved = set()
        self._drain_result = None
        self._drain_done = threading.Event()
        self._drain_thread = None
        self._preempt = None
        self._preempt_stop = threading.Event()
        self._preempt_thread = None
        # request tracing: a per-server span buffer (NOT the process
        # global) so one test process hosting a whole fleet still gets
        # distinct sites; the site label resolves with the port
        self._trace_buf = _trace.SpanBuffer(site='replica:%d'
                                            % self.port)
        # one writer thread for every stream's tokens, made at the first
        # stream (serving/stream_pump.py)
        self._pump = None
        self._pump_lock = threading.Lock()

    def start(self):
        if self._httpd is not None:
            return self
        from http.server import BaseHTTPRequestHandler, \
            ThreadingHTTPServer
        session = self.session
        decode_session = self.decode_session
        limit = self.max_concurrent
        gate = threading.BoundedSemaphore(limit) if limit > 0 else None
        srv = self

        def _statuses():
            st = session.status()
            if decode_session is None:
                return st, st['status']
            dst = decode_session.status()
            worst = st['status'] if st['status'] != 'ok' \
                else dst['status']
            return {'status': worst, 'predict': st,
                    'generate': dst}, worst

        class Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 so /generate can stream chunked NDJSON; every
            # non-chunked response carries Content-Length already
            protocol_version = 'HTTP/1.1'

            def _json(handler, code, payload, headers=None):
                body = (json.dumps(payload, sort_keys=True)
                        + '\n').encode()
                handler.send_response(code)
                handler.send_header('Content-Type', 'application/json')
                handler.send_header('Content-Length', str(len(body)))
                for k, v in (headers or {}).items():
                    handler.send_header(k, v)
                handler.end_headers()
                handler.wfile.write(body)

            def do_GET(handler):
                from urllib.parse import parse_qs, urlparse
                parsed = urlparse(handler.path)
                path = parsed.path.rstrip('/')
                if path == '/status':
                    payload, _worst = _statuses()
                    handler._json(200, payload)
                elif path == '/healthz':
                    # a load balancer keys on the status code: an
                    # unhealthy replica (breaker open / degraded) must
                    # answer 503 so it is routed around, while the
                    # JSON body keeps the human-readable detail.
                    # 'draining' rides the same 503 body: the gateway
                    # routes away but still fetches /drain payloads
                    if srv._draining:
                        handler._json(503, {'ok': False,
                                            'status': 'draining'})
                        return
                    _payload, worst = _statuses()
                    ok = worst == 'ok'
                    handler._json(200 if ok else 503,
                                  {'ok': ok, 'status': worst})
                elif path == '/drain':
                    q = parse_qs(parsed.query)
                    rid = (q.get('request_id') or [None])[0]
                    tctx = _trace.inbound(handler.headers)
                    with srv._trace_buf.span('srv.drain', tctx,
                                             request_id=rid):
                        handler._json(200, srv._drain_snapshot(rid))
                elif path == '/trace':
                    # span-buffer drain (NDJSON): one header line then
                    # one line per record with seq > since; the caller
                    # advances its own cursor to the returned one
                    q = parse_qs(parsed.query)
                    try:
                        since = int((q.get('since') or ['0'])[0] or 0)
                    except (TypeError, ValueError):
                        since = 0
                    body = srv._trace_buf.ndjson(since)
                    handler.send_response(200)
                    handler.send_header('Content-Type',
                                        'application/x-ndjson')
                    handler.send_header('Content-Length',
                                        str(len(body)))
                    handler.end_headers()
                    handler.wfile.write(body)
                else:
                    handler.send_error(404)

            def _chunk(handler, obj):
                handler._chunk_line((json.dumps(obj, sort_keys=True)
                                     + '\n').encode())

            def _chunk_line(handler, data):
                # one write a chunk: one system call, one segment, one
                # wake-up of whoever reads the stream
                handler.wfile.write(_chunked(data))
                handler.wfile.flush()

            def _generate(handler, req):
                """POST /generate — per-token chunked streaming (or a
                single JSON when stream=false)."""
                gen = decode_session if decode_session is not None \
                    else session
                tokens = req.get('tokens')
                if not tokens:
                    handler._json(400, {'error': "need 'tokens'"})
                    return
                # resume plumbing (gateway mid-stream failover):
                # start_index offsets the streamed token indices so a
                # spliced continuation keeps the client's numbering,
                # request_id dedups re-admissions engine-side and is
                # echoed on the done line
                try:
                    start_index = int(req.get('start_index', 0) or 0)
                except (TypeError, ValueError):
                    handler._json(400,
                                  {'error': "bad 'start_index'"})
                    return
                request_id = req.get('request_id')
                # request_id rides as a kwarg only when the caller
                # sent one: duck-typed sessions predating it keep
                # working
                kwargs = {'max_new_tokens': req.get('max_new_tokens'),
                          'eos_id': req.get('eos_id')}
                if request_id is not None:
                    kwargs['request_id'] = request_id
                # disaggregated serving: a prefill-class admission
                # exports at the prefill boundary; the done line
                # carries the seqstate payload inline
                if req.get('prefill_only'):
                    kwargs['prefill_only'] = True
                # multi-adapter + sampling: the body wins over the
                # X-Mxnet-Adapter header (the header is the gateway's
                # routing relay; both ride the same request)
                adapter = req.get('adapter')
                if adapter is None:
                    adapter = handler.headers.get('X-Mxnet-Adapter')
                if adapter is not None:
                    kwargs['adapter'] = adapter
                try:
                    for key, cast in (('temperature', float),
                                      ('top_p', float),
                                      ('seed', int)):
                        val = req.get(key)
                        if val is not None:
                            kwargs[key] = cast(val)
                except (TypeError, ValueError):
                    handler._json(400, {'error': "bad sampling "
                                                 "parameters"})
                    return
                # the engine's eng.* spans nest under this handler's
                # srv.generate span (the ctx rides the sequence — the
                # worker thread owns the admission, not this thread)
                tctx = _trace.current()
                if tctx is not None:
                    kwargs['trace'] = tctx
                stream = gen.generate(tokens, **kwargs)
                wait_s = (gen._engine.timeout_s
                          or _HTTP_MAX_WAIT_S)
                if not req.get('stream', True):
                    toks = stream.result(wait_s)
                    done = {'tokens': toks,
                            'finish_reason': stream.finish_reason,
                            'degraded': stream.degraded}
                    seqst = getattr(stream, 'seqstate', None)
                    if seqst is not None:
                        done['seqstate'] = seqst
                    if request_id is not None:
                        done['request_id'] = request_id
                    handler._json(200, done)
                    return
                handler._stream_ndjson(stream, start_index,
                                       request_id, gen._engine)

            def _stream_ndjson(handler, stream, start_index,
                               request_id, engine):
                """Chunked NDJSON relay of one GenerateStream: a
                {"token","index"} line per token, then the done line.
                A 'migrated' finish is NOT an error — the gateway
                fetches the exported seqstate from /drain and splices
                the continuation into the same client stream.

                The server's stream pump writes the tokens, and at a
                sound end the done line and the last chunk, where it can
                adopt the connection (serving/stream_pump.py); this
                thread waits for the stream to come back, then writes
                what the pump could not: bytes a full window left, the
                tokens after them, a typed error line. Each token's
                chunk this thread writes is a ``srv.stream.write`` span,
                from the token leaving the stream's queue to its chunk
                flushed, and the end it writes ``srv.stream.done``: on
                the profiler's clock alone, so a device-idle gap over
                which a writer was mid-token names the writers, not
                where the scheduler waited for the interpreter
                (docs/OBSERVABILITY.md). No span covers the wait for a
                token. The thread's CPU seconds over the stream go to
                the engine's ``stream_cpu_s``."""
                cpu0 = time.thread_time()
                try:
                    handler._relay(stream, start_index, request_id,
                                   srv._stream_pump(engine))
                finally:
                    engine.note_stream_cpu(time.thread_time() - cpu0)

            def _relay(handler, stream, start_index, request_id, pump):
                handler.send_response(200)
                handler.send_header('Content-Type',
                                    'application/x-ndjson')
                handler.send_header('Transfer-Encoding', 'chunked')
                handler.end_headers()
                write = _span.profiler_only('srv.stream.write')
                end = _span.profiler_only('srv.stream.done')
                try:
                    tokens, index = stream, start_index
                    back = pump.adopt(
                        stream, handler.connection, start_index,
                        lambda: (_done_line(stream, request_id)
                                 + b'0\r\n\r\n'))
                    if back is not None:
                        if back.gone:
                            return      # the pump cancelled the stream
                        if back.unsent:
                            with write:
                                handler.wfile.write(back.unsent)
                                handler.wfile.flush()
                        if back.finished:
                            return      # the pump wrote the end
                        if back.failed is not None:
                            raise back.failed
                        if back.ended:
                            raise stream.exception()
                        index = back.index
                    for tok in tokens:
                        with write:
                            # byte for byte what _chunk writes for
                            # {'token': tok, 'index': ...}, without an
                            # encoder built for every token of every
                            # stream
                            handler._chunk_line(
                                b'{"index": %d, "token": %d}\n'
                                % (index, tok))
                        index += 1
                    if back is not None and back.failed is not None:
                        raise back.failed
                    with end:
                        handler.wfile.write(_done_line(stream,
                                                       request_id))
                        handler.wfile.flush()
                except OSError:
                    # client went away mid-stream: retire the
                    # sequence so it stops occupying a decode slot,
                    # and never touch the dead socket again
                    stream.cancel()
                    return
                except Exception as exc:
                    # mid-stream engine failure: the error rides the
                    # last NDJSON line (headers are long gone)
                    stream.cancel()
                    try:
                        handler._chunk({'done': True,
                                        'error': '%s: %s'
                                        % (type(exc).__name__, exc),
                                        'error_class':
                                            type(exc).__name__,
                                        'tokens': stream.tokens})
                    except OSError:
                        return
                try:
                    with end:
                        handler.wfile.write(b'0\r\n\r\n')
                        handler.wfile.flush()
                except OSError:
                    pass

            def _import(handler, req):
                """POST /import — land an exported seqstate payload
                (GET /drain on the draining replica) in this
                replica's engine and stream the continuation. No
                prefill runs; token indices continue at the number of
                tokens the source already emitted."""
                gen = decode_session if decode_session is not None \
                    else session
                if gen._engine is None:
                    handler._json(400, {'error': '/import needs a '
                                                 'decode-mode session'})
                    return
                payload = req.get('seqstate')
                if not isinstance(payload, dict):
                    handler._json(400,
                                  {'error': "need 'seqstate' (a "
                                            "mxnet_tpu.seqstate.v1 "
                                            "object)"})
                    return
                tctx = _trace.current()
                if tctx is not None:
                    stream = gen._engine.import_sequence(payload,
                                                         trace=tctx)
                else:
                    stream = gen._engine.import_sequence(payload)
                # default: continue numbering after the handed-off
                # prefix. The gateway overrides with its RELAYED
                # watermark so indices stay aligned when the source
                # admission was itself a re-admission (its payload
                # counts only the segment's tokens)
                start_index = len(payload.get('emitted') or [])
                if req.get('start_index') is not None:
                    try:
                        start_index = int(req['start_index'])
                    except (TypeError, ValueError):
                        pass
                request_id = payload.get('request_id')
                if not req.get('stream', True):
                    wait_s = (gen._engine.timeout_s
                              or _HTTP_MAX_WAIT_S)
                    toks = stream.result(wait_s)
                    done = {'tokens': toks,
                            'finish_reason': stream.finish_reason,
                            'degraded': stream.degraded}
                    if request_id is not None:
                        done['request_id'] = request_id
                    handler._json(200, done)
                    return
                handler._stream_ndjson(stream, start_index,
                                       request_id, gen._engine)

            def _retry_after(handler, path):
                src = decode_session \
                    if (path in ('/generate', '/import')
                        and decode_session is not None) else session
                try:
                    return float(src.retry_after_hint())
                except Exception:
                    return 1.0

            def do_POST(handler):
                path = handler.path.rstrip('/')
                if path not in ('/predict', '/generate', '/import'):
                    handler.send_error(404)
                    return
                if srv._draining:
                    # drain admission stop: every new request — and
                    # every seqstate import, this replica is leaving —
                    # sheds typed 503 before any byte streams, so the
                    # gateway fails over cleanly
                    try:
                        length = int(handler.headers.get(
                            'Content-Length', 0) or 0)
                        if length:
                            handler.rfile.read(length)
                    except (ValueError, OSError):
                        pass
                    handler._json(
                        503,
                        {'error': 'replica draining (sequences are '
                                  'being handed off)',
                         'error_class': 'Draining'},
                        headers={'Retry-After': '1'})
                    return
                if gate is not None \
                        and not gate.acquire(blocking=False):
                    # concurrency shed: past the in-flight cap every
                    # extra handler thread only adds scheduling
                    # contention — reject instantly, typed, with the
                    # same Retry-After contract as queue backpressure.
                    # Drain the unread body first: on a keep-alive
                    # connection it would otherwise be parsed as the
                    # NEXT request line, garbling the client's retry.
                    try:
                        length = int(handler.headers.get(
                            'Content-Length', 0) or 0)
                        if length:
                            handler.rfile.read(length)
                    except (ValueError, OSError):
                        pass
                    hint = handler._retry_after(path)
                    handler._json(
                        429,
                        {'error': 'serving concurrency limit '
                                  'reached; shed load or retry with '
                                  'backoff',
                         'limit': limit, 'retry_after_s': hint},
                        headers={'Retry-After':
                                 str(max(1, int(hint + 0.999)))})
                    return
                # server-side request span: parent is the sender's
                # relay span (X-Mxnet-Trace), or none (a root span)
                # for a request that arrives without one; the span
                # covers parse, admission, execution, and the full
                # streamed relay. With tracing off every request gets
                # the shared null span (no header read, no allocation)
                tctx = _trace.inbound(handler.headers)
                name = {'/generate': 'srv.generate',
                        '/import': 'srv.import'}.get(path,
                                                     'srv.predict')
                try:
                    with srv._trace_buf.span(name, tctx) as sp, \
                            _trace.activate(sp.ctx):
                        handler._do_post_admitted(path)
                finally:
                    if gate is not None:
                        gate.release()

            def _do_post_admitted(handler, path):
                try:
                    length = int(handler.headers.get('Content-Length',
                                                     0))
                    req = json.loads(handler.rfile.read(length)
                                     or b'{}')
                except ValueError:
                    handler._json(400, {'error': 'bad JSON'})
                    return
                from concurrent.futures import TimeoutError as \
                    _FutWaitTimeout
                wait_s = (session._batcher.timeout_s
                          if session._batcher is not None
                          else session._engine.timeout_s) \
                    or _HTTP_MAX_WAIT_S
                try:
                    if path == '/generate':
                        handler._generate(req)
                    elif path == '/import':
                        handler._import(req)
                    elif 'instances' in req:
                        futs = [session.submit(onp.asarray(x))
                                for x in req['instances']]
                        outs = [[o.tolist() for o in f.result(wait_s)]
                                for f in futs]
                        handler._json(200, {'outputs': outs})
                    elif 'data' in req:
                        outs = session.infer(onp.asarray(req['data']),
                                             timeout=wait_s)
                        handler._json(200, {'outputs':
                                            [o.tolist() for o in outs]})
                    else:
                        handler._json(400,
                                      {'error': "need 'data' or "
                                                "'instances'"})
                except BackpressureError as exc:
                    # Retry-After from queue depth x recent batch
                    # latency: a well-behaved client backs off for
                    # roughly one queue-drain instead of hammering
                    hint = handler._retry_after(path)
                    handler._json(429, {'error': str(exc),
                                        'depth': exc.depth,
                                        'limit': exc.limit,
                                        'retry_after_s': hint},
                                  headers={'Retry-After':
                                           str(max(1, int(hint
                                                          + 0.999)))})
                except (RequestTimeout, _FutWaitTimeout) as exc:
                    handler._json(504, {'error': str(exc)
                                        or 'request timed out'})
                except BatcherClosed as exc:
                    handler._json(503, {'error': str(exc)})
                except (ValueError, TypeError) as exc:
                    # admission-time validation: bad shapes/arity,
                    # over-long prompt, or the wrong endpoint for the
                    # session's mode
                    handler._json(400, {'error': str(exc)})
                except Exception as exc:  # noqa: BLE001 - typed 500
                    # aborted work (worker crash / preemption) or an
                    # engine bug: a typed 500 beats a dropped
                    # connection — the load harness taxonomizes on
                    # error_class
                    handler._json(500, {'error': '%s: %s'
                                        % (type(exc).__name__, exc),
                                        'error_class':
                                            type(exc).__name__})

            def log_message(handler, *args):
                pass        # no per-request stderr noise

        class _QuietServer(ThreadingHTTPServer):
            # socketserver's listen backlog defaults to 5: at a few
            # hundred connections/s the SYN queue overflows and
            # clients stall in 1s/3s TCP retransmit — a latency cliff
            # admission control never sees. A deep backlog keeps the
            # kernel accepting; the concurrency gate and bounded
            # queues stay the real admission control.
            request_queue_size = 128

            # a client hanging up (load-gen teardown, impatient
            # caller) is normal serving weather, not a stack trace:
            # keep real handler bugs loud, silence benign disconnects
            def handle_error(server_self, request, client_address):
                import sys as _sys
                exc = _sys.exc_info()[1]
                if isinstance(exc, (ConnectionError, TimeoutError)):
                    return
                ThreadingHTTPServer.handle_error(
                    server_self, request, client_address)

        self._httpd = _QuietServer((self.host, self.port),
                                   Handler)
        self.port = self._httpd.server_address[1]    # resolve port 0
        # the trace site carries the BOUND port; engine eng.* spans
        # land in this server's buffer so /trace serves them
        self._trace_buf.site = 'replica:%d' % self.port
        for s in (session, decode_session):
            eng = getattr(s, '_engine', None) if s is not None \
                else None
            if eng is not None:
                try:
                    eng.trace_sink = self._trace_buf
                except Exception:
                    pass
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name='mxnet-tpu-serving-http')
        self._thread.start()
        return self

    # -- graceful drain (docs/SERVING.md "Drain & live migration") ---------

    @property
    def draining(self):
        return self._draining

    @property
    def drain_result(self):
        """``{'rc', 'reason', 'sequences', 'handed_off',
        'duration_s'}`` once the drain completes (``rc`` is the
        resumable exit code, 75 by default), else None."""
        with self._drain_lock:
            return dict(self._drain_result) \
                if self._drain_result else None

    def install_preempt_hook(self, handler=None, poll_s=0.05):
        """Arm the SIGTERM/SIGINT → graceful-drain path (the serving
        analog of training's PreemptionHandler protocol): the signal
        only sets a flag; a watcher thread notices it and calls
        :meth:`begin_drain`. Pass an existing
        :class:`~..resilience.preempt.PreemptionHandler` to share one
        (e.g. scripted ``preempt`` faults); otherwise one is created
        and installed. Returns the handler — the process's main
        thread pairs this with :meth:`serve_until_drained` to exit
        with the resumable code."""
        from ..resilience.preempt import PreemptionHandler
        if self._preempt is not None:
            return self._preempt
        if handler is None:
            handler = PreemptionHandler().install()
        self._preempt = handler

        def _watch():
            while not self._preempt_stop.wait(poll_s):
                if handler.stop_requested:
                    self.begin_drain(reason=handler.reason
                                     or 'preempted')
                    return

        self._preempt_thread = threading.Thread(
            target=_watch, daemon=True,
            name='mxnet-tpu-serving-preempt')
        self._preempt_thread.start()
        return handler

    def begin_drain(self, reason='requested', handoff_timeout_s=None):
        """Start a graceful drain (idempotent): /healthz answers 503
        ``draining``, new POSTs shed typed, every in-flight sequence
        exports to a seqstate payload served over GET /drain, and the
        drain result (resumable rc) records once payloads are handed
        off (or ``handoff_timeout_s``, default
        ``MXNET_TPU_SERVE_DRAIN_TIMEOUT_S``, expires)."""
        with self._drain_lock:
            if self._draining:
                return self
            self._draining = True
        if handoff_timeout_s is None:
            handoff_timeout_s = float(
                _knob('MXNET_TPU_SERVE_DRAIN_TIMEOUT_S', 30.0))
        t0 = time.monotonic()
        try:
            from .. import observability as _obs
            if _obs.enabled():
                _obs.serving_instruments().drains.inc()
                _obs.record_event('drain_begin', reason=reason)
        except Exception:
            pass
        self._drain_thread = threading.Thread(
            target=self._drain_worker,
            args=(reason, t0, float(handoff_timeout_s)),
            daemon=True, name='mxnet-tpu-serving-drain')
        self._drain_thread.start()
        return self

    def wait_drained(self, timeout=None):
        """Block until the drain completes; returns True when it has
        (then :attr:`drain_result` is populated)."""
        return self._drain_done.wait(timeout)

    def serve_until_drained(self, timeout=None):
        """Real-process shape: block the main thread until a drain
        completes, then raise
        :class:`~..resilience.preempt.Preempted` so the process exits
        with the resumable code (rc 75) a scheduler restarts."""
        from ..resilience.preempt import Preempted, \
            resumable_exit_code
        self._drain_done.wait(timeout)
        res = self.drain_result or {}
        raise Preempted(res.get('rc', resumable_exit_code()),
                        reason=res.get('reason', 'drained'))

    def _drain_snapshot(self, request_id=None):
        """GET /drain response; serving a payload marks it handed
        off (the drain completes once every payload is fetched)."""
        with self._drain_lock:
            if request_id is not None:
                picked = [i for i, p in
                          enumerate(self._drain_payloads)
                          if p.get('request_id') == request_id]
            else:
                picked = list(range(len(self._drain_payloads)))
            seqs = [self._drain_payloads[i] for i in picked]
            self._drain_unserved.difference_update(picked)
            doc = {'schema': 'mxnet_tpu.drain.v1',
                   'draining': self._draining,
                   'complete': self._drain_done.is_set(),
                   'pending': len(self._drain_unserved),
                   'sequences': seqs}
        return doc

    def _drain_worker(self, reason, t0, handoff_timeout_s):
        sessions = [s for s in (self.session, self.decode_session)
                    if s is not None
                    and getattr(s, '_engine', None) is not None]
        payloads = []
        for s in sessions:
            try:
                payloads.extend(s._engine.export_all())
            except Exception:
                logging.exception('drain: export_all failed on '
                                  'session %r', getattr(s, 'name', s))
        with self._drain_lock:
            self._drain_payloads = payloads
            self._drain_unserved = set(range(len(payloads)))
        # the handoff window: the gateway (or an operator) fetches
        # the payloads over GET /drain; a replica with no consumer
        # moves on once the window closes
        deadline = t0 + handoff_timeout_s
        while payloads and time.monotonic() < deadline:
            with self._drain_lock:
                if not self._drain_unserved:
                    break
            time.sleep(0.02)
        for s in sessions:
            try:
                s.close(drain=True)
            except Exception:
                logging.exception('drain: close failed on session %r',
                                  getattr(s, 'name', s))
        dt = time.monotonic() - t0
        from ..resilience.preempt import resumable_exit_code
        with self._drain_lock:
            handed = len(payloads) - len(self._drain_unserved)
            self._drain_result = {
                'rc': resumable_exit_code(),
                'reason': reason,
                'sequences': len(payloads),
                'handed_off': handed,
                'duration_s': round(dt, 3),
            }
        self._drain_done.set()
        try:
            from .. import observability as _obs
            if _obs.enabled():
                _obs.serving_instruments().drain_seconds.observe(dt)
                _obs.record_event('drain_complete', reason=reason,
                                  sequences=len(payloads),
                                  handed_off=handed,
                                  duration_s=round(dt, 3))
        except Exception:
            pass

    def _stream_pump(self, engine):
        """The server's stream pump, writing for ``engine``: one a server,
        which streams from one decode engine."""
        from .stream_pump import StreamPump
        name = 'serving-%d' % self.port
        with self._pump_lock:
            if self._pump is None:
                self._pump = StreamPump(engine, name=name)
            return self._pump

    def stop(self):
        with self._pump_lock:
            if self._pump is not None:
                self._pump.stop()
                self._pump = None
        self._preempt_stop.set()
        if self._preempt_thread is not None:
            self._preempt_thread.join(timeout=2.0)
            self._preempt_thread = None
        if self._httpd is None:
            return
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)
        self._httpd = None
        self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def maybe_start_http_server(session):
    """Start the serving endpoint iff ``MXNET_TPU_SERVE_HTTP_PORT`` is
    a nonzero port (same opt-in contract as the Prometheus exporter).
    Returns the server or None."""
    port = int(_knob('MXNET_TPU_SERVE_HTTP_PORT', 0) or 0)
    if not port:
        return None
    return ServingHTTPServer(session, port).start()
