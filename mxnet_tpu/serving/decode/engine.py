"""Continuous batching: sequences join and leave the in-flight decode
batch at token granularity.

Flush batching (batcher.py) is the wrong shape for generation: one
short request stuck in a batch of long ones holds its slot until the
LONGEST member finishes, and a new arrival waits for the whole batch
to drain — time-to-first-token inflates with someone else's
generation length. The decode engine instead schedules a fixed
register file of ``slots`` sequences (the decode program's one
compiled shape):

  * a finished sequence (EOS / max-new / max_len / timeout / cancel)
    retires its slot at the very next token boundary;
  * a pending request is admitted into any free slot by running ONE
    bucketed prefill, interleaved between decode steps
    (``prefill_interleave`` per step keeps decode latency bounded
    while arrivals land);
  * every decode step advances ALL live slots one token — batch
    occupancy tracks load continuously instead of sawtoothing.

Admission control, typed errors, and resilience carry over from the
one-shot path: bounded pending queue -> :class:`BackpressureError`,
per-request budget enforced by a reaper independent of a wedged
worker -> :class:`RequestTimeout`, every device call under the
circuit breaker + stall watchdog (fault-injection site
``serving.decode``), and a breaker trip completes every in-flight
sequence DEGRADED on the CPU fallback (same math, same tokens) rather
than erroring mid-stream.

**Paged scheduling** (program.paged — docs/SERVING.md "Paged KV
cache, prefix sharing, speculative decoding"): the page pool's host
state has one owner, a :class:`~.paged.PageOwner` — free lists with
refcounts, lazy per-token page allocation when a sequence's position
crosses a page boundary, prefix registries that land matching
prompts on shared read-only pages (no prefill program runs; the
suffix streams through the regular decode step), copy-on-write
before any write into a shared page, LRU eviction of unreferenced
cached prefixes under pool pressure, and whatever else differs
between the kinds of layer a model has. The engine asks it (open,
share, place, make writable, advance, drop) and runs the device
calls; it knows no kind of layer.
Pool exhaustion is TYPED — admission and mid-stream allocation
failures finish the stream with :class:`BackpressureError`, never a
stall — and the compiled programs never see any of it (page churn
costs zero retraces). **Speculative decoding**: with a ``draft``
program and ``spec_k > 0``, each tick runs the draft ``k`` single
steps to propose tokens and ONE target ``verify`` call to score all
``k + 1`` positions; the longest greedy-matching prefix is accepted
(plus the target's own correction token), and rejected KV rows are
simply masked until overwritten — paged rollback is free.

The scheduler is pure queue/slot math over a duck-typed program
(``slots``, ``new_cache``, ``run_prefill``, ``run_step``,
``fallback_generate``; paged programs add ``page_size`` / ``pages``
/ ``max_pages`` / ``run_copy_page`` / ``run_verify``) — numpy +
stdlib only, testable with a fake program and a fake clock, the same
discipline as batcher.py.
"""
from __future__ import annotations

import functools
import logging
import queue as _queue
import threading
import time

import numpy as onp

from ...observability import spans as _spans
from ...observability.spans import span as _span
from ..batcher import BackpressureError, BatcherClosed, RequestTimeout
from .paged import PageOwner, pages_for
from .sampling import key_for
from .seqstate import SeqStateError, build_payload, decode_payload

__all__ = ['GenerateStream', 'DecodeEngine', 'DrainTimeout']

_DONE = object()          # stream sentinel

# a tick longer than this books ``slow_ticks`` and a ``decode_slow_tick``
# flight event: what stalls an untraced run leaves its cause behind
_SLOW_TICK_S = 1.0


class _RunLength:
    """How many decode steps the next call of the step program runs:
    ``max_run`` while the server is saturated and the host's own time a
    tick is at least the device's time a step, else 1.

    A call of n steps pays the tick's host time (the read, the loops over
    the slots, the emit, one flush to the streams) once for n tokens a
    slot. Where the step outlasts the host, the host already hides behind
    it and a longer call would only hold tokens back; below saturation,
    a call's last steps would keep a slot that finished early from the
    next arrival. Both times are moving averages of what the scheduler
    observes: the host's is a tick's wall less the time it blocked in
    reads, the device's the step call's read over its steps. The rule
    engages at a ratio of 1 and lets go below ``RELEASE``, so that n does
    not flap."""

    ALPHA = 0.2
    RELEASE = 0.75

    def __init__(self, max_run):
        self.max_run = int(max_run)
        self.host_s = self.step_s = None
        self.engaged = False

    def observe(self, host_s, step_s):
        """One tick that ran the step: its host seconds, and the device
        seconds of one of its steps."""
        if self.host_s is None:
            self.host_s, self.step_s = host_s, step_s
        else:
            a = self.ALPHA
            self.host_s += a * (host_s - self.host_s)
            self.step_s += a * (step_s - self.step_s)
        floor = self.RELEASE if self.engaged else 1.0
        self.engaged = self.host_s >= floor * self.step_s

    def steps(self, live, pending, free, interleave):
        """The steps of the tick that opens with ``live`` sequences,
        ``pending`` requests and ``free`` slots: saturated when its
        admissions (``interleave`` a step of the run, every free slot
        where none is live) leave no slot free or a request waiting."""
        if not self.engaged:
            return 1
        admits = min(interleave * self.max_run if live else free,
                     pending, free)
        return self.max_run if admits == free or pending > admits else 1


def _knob(name, default):
    try:
        from ... import config as _config
        v = _config.get(name)
        return default if v is None else v
    except Exception:
        return default


def _serving_instruments():
    try:
        from ... import observability as _obs
        if _obs.enabled():
            return _obs.serving_instruments()
    except Exception:
        pass
    return None


def _record_event(kind, **fields):
    try:
        from ... import observability as _obs
        if _obs.enabled():
            _obs.record_event(kind, **fields)
    except Exception:
        pass


def _samples(active):
    """1 when a live slot of ``active`` asks for a temperature above
    0, so that the program it ran took the sampler's nucleus branch
    (sampling.sample_tokens), else 0 — what ``sampled_steps`` books."""
    return int(any(seq.temperature > 0 for seq in active.values()))


def _flight_dump(reason):
    try:
        from ... import observability as _obs
        if _obs.enabled():
            _obs.flight_dump(reason=reason)
    except Exception:
        pass


class GenerateStream:
    """Per-request handle: iterate tokens as they decode, or block for
    the full sequence.

        for tok in session.generate(prompt, max_new_tokens=32):
            ...                       # per-token streaming
        toks = stream.result(timeout) # or: the whole generation

    Iteration ends at EOS/max-new; a failed request raises its typed
    error (RequestTimeout, BatcherClosed, ...) from the iterator and
    from :meth:`result` alike. ``degraded`` flips when any part of the
    generation ran on the CPU fallback."""

    def __init__(self, prompt_len):
        self.prompt_len = int(prompt_len)
        self.tokens = []
        self.finish_reason = None       # eos | length | error | closed
        # prefill_only admission: the exported seqstate payload is
        # stashed HERE (set before _finish so any consumer woken by
        # the done event observes it) and the server's done line
        # carries it to the gateway for the decode-class handoff
        self.seqstate = None
        self.degraded = False
        # the C queue: a token costs its reader one wake-up, with no
        # lock and condition in Python on either side (128 readers and
        # the scheduler share one interpreter)
        self._q = _queue.SimpleQueue()
        self._done = threading.Event()
        self._exc = None
        self._cancelled = False
        self._outbox = None             # the admitting engine's
        # once a server's stream pump adopts the stream, its put: every
        # later item goes there, in order (serving/stream_pump.py)
        self._sink = None
        self._route = threading.Lock()

    # -- consumer side -----------------------------------------------------

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _DONE:
                if self._exc is not None:
                    raise self._exc
                return
            yield item

    def result(self, timeout=None):
        """Block until the generation finishes; returns the full token
        list or raises the request's typed error."""
        if not self._done.wait(timeout):
            raise RequestTimeout(
                'generation not finished within %r s' % (timeout,))
        if self._exc is not None:
            raise self._exc
        return list(self.tokens)

    def cancel(self):
        """Ask the engine to retire this sequence at the next token
        boundary (its slot frees; already-streamed tokens remain)."""
        self._cancelled = True

    def done(self):
        return self._done.is_set()

    def exception(self):
        return self._exc

    # -- engine side -------------------------------------------------------

    def _emit(self, token):
        self.tokens.append(int(token))
        self._put(int(token))

    def _finish(self, reason, exc=None):
        if self._done.is_set():
            return
        self.finish_reason = reason
        self._exc = exc
        self._done.set()
        self._put(_DONE)

    def _put(self, item):
        """Hand ``item`` to whoever reads this stream: at once, or, from
        the scheduler thread of the engine that admitted it, through that
        engine's outbox (:class:`_Outbox`). The reader is the stream's own
        queue until a stream pump adopts the stream, the pump after."""
        box = self._outbox
        if box is not None and box.take(self, item):
            return
        if self._sink is None and self._put_own(item):
            return
        self._sink([(self, item)])

    def _put_own(self, item):
        """Put ``item`` on the stream's own queue and return True, unless a
        pump has adopted the stream (False: the item is the pump's)."""
        with self._route:
            if self._sink is not None:
                return False
            self._q.put(item)
            return True

    # -- a stream pump's side (serving/stream_pump.py): a reader gets
    # the tokens as ints, then one end mark ------------------------------

    def _adopt(self, sink, adoption):
        """Route every later item to ``sink``, after ``adoption``, which
        goes first: the pump then takes what the stream's own queue got
        before (:meth:`_take_queued`), and nothing lands there after
        (:meth:`_put_own`)."""
        with self._route:
            sink([(self, adoption)])
            self._sink = sink

    def _take_queued(self):
        """What the stream's own queue holds: (tokens, whether the end
        mark came)."""
        tokens = []
        while True:
            try:
                item = self._q.get_nowait()
            except _queue.Empty:
                return tokens, False
            if item is _DONE:
                return tokens, True
            tokens.append(item)

    def _forward(self, item):
        """Put a pumped ``item`` on the stream's own queue, for a handler
        that took the stream back; True for the end mark."""
        self._q.put(item)
        return item is _DONE

    def _disown(self):
        """Take the stream back from a pump that failed: later items land
        on its own queue, and a reader of that queue finds an end mark
        (the one a cancelled stream gets, or one here if it ended)."""
        with self._route:
            self._sink = None
            if self._done.is_set():
                self._q.put(_DONE)


class _Outbox:
    """What the scheduler thread has emitted and its streams' readers
    have not been woken for yet.

    Waking a reader costs the scheduler the interpreter lock: a step
    that ends by waking 128 HTTP handler threads prepares the next
    device call while they, and their client, work through their
    tokens. So the scheduler thread's tokens and end marks wait here,
    in order, and go to their queues right after the next program is
    enqueued (``program.while_device_runs``), when the device has work
    and the scheduler is about to block on it; or at the end of a tick
    that enqueued nothing, or when the engine falls idle or hands its
    sequences to other threads. ``stream.tokens``, ``finish_reason``
    and ``done()`` are up to date at once: only the wake-up waits.
    Other threads (the reaper, ``close``, the CPU fallback) put
    directly.

    The items of streams a server's stream pump has adopted go to the
    pump as one batch a flush: one wake-up for all of them, not one a
    token (serving/stream_pump.py)."""

    def __init__(self):
        self.owner = None               # the scheduler thread's ident
        self.items = []
        self.flushed = False            # since the tick began

    def take(self, stream, item):
        if threading.get_ident() != self.owner:
            return False
        self.items.append((stream, item))
        return True

    def flush(self):
        """Scheduler thread only."""
        self.flushed = True
        items, self.items = self.items, []
        batches = {}
        for stream, item in items:
            if stream._sink is None and stream._put_own(item):
                continue
            batch = batches.get(stream._sink)
            if batch is None:
                batch = batches[stream._sink] = []
            batch.append((stream, item))
        for sink, batch in batches.items():
            sink(batch)


class _Seq:
    """One admitted request's scheduling state."""

    __slots__ = ('stream', 'prompt', 'max_new', 'eos_id', 'slot',
                 'pos', 'last_token', 'enqueued_at', 'deadline_at',
                 'first_token_at', 'pages', 'prefill_only',
                 'trace', 'adapter_id', 'adapter_idx', 'temperature',
                 'top_p', 'seed')

    def __init__(self, stream, prompt, max_new, eos_id, enqueued_at,
                 deadline_at, prefill_only=False, adapter_id=None,
                 temperature=0.0, top_p=1.0, seed=0):
        self.stream = stream
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.slot = None
        self.pos = None            # next cache write position
        self.last_token = None
        self.enqueued_at = enqueued_at
        self.deadline_at = deadline_at
        self.first_token_at = None
        # paged scheduling: the sequence's record with the page owner
        # (paged.SeqPages: its tables and the pages it holds)
        self.pages = None
        # disaggregated serving: export the seqstate at the prefill
        # boundary instead of entering the step loop
        self.prefill_only = prefill_only
        # multi-adapter + sampling: the LoRA variant this sequence
        # decodes under (id -> refcounted pool index at admission) and
        # its sampling law (temperature 0 = greedy; keys derive from
        # (seed, absolute position), so continuations stay
        # bit-identical)
        self.adapter_id = adapter_id
        self.adapter_idx = 0
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.seed = int(seed)
        # request tracing: {'ctx': TraceContext, 'enq': wall seconds,
        # 'last': wall phase boundary, 'first_w': wall first-token,
        # 'tok0': tokens already present at attach} — None unless the
        # admission carried a trace context (the untraced hot path
        # pays one None check per site)
        self.trace = None

    @property
    def prompt_len(self):
        return len(self.prompt)

    @property
    def extending(self):
        """True while a prefix-hit sequence is still streaming its
        un-shared prompt suffix through the decode step (its step
        outputs are not emitted until the last prompt token feeds)."""
        return self.pos is not None and self.pos < len(self.prompt)


class DrainTimeout(RequestTimeout):
    """A draining close's budget expired with this stream still in
    flight: the stream fails TYPED (its NDJSON stream gets this as an
    error line, a blocking ``result()`` raises it) and its slot frees
    — a drain never returns with work silently wedged in flight."""


class _DegradedPath(Exception):
    """Internal: the device call failed transiently / breaker open —
    finish the work on the CPU fallback."""


class _AbortPath(Exception):
    """Internal: the device call died in a way that kills the work
    itself (worker crash, preemption notice) — the in-flight
    sequences fail with the typed error instead of completing
    degraded; the client retries against a recovered engine."""

    def __init__(self, exc):
        super().__init__(str(exc))
        self.exc = exc


class DecodeEngine:
    """Continuous-batching scheduler over a decode program.

    ``program`` duck-type: ``slots``, ``max_len``,
    ``max_prompt_len()``, ``new_cache()``,
    ``run_prefill(cache, tokens, slot) -> (cache, tok, logits)``,
    ``run_step(cache, tokens, positions) -> (cache, toks, logits)``,
    ``fallback_generate(tokens, max_new, eos_id) -> [tok]``.
    """

    def __init__(self, program, max_queue=256, timeout_s=30.0,
                 max_new_tokens=64, breaker=None, watchdog=None,
                 prefill_interleave=1, name='decode',
                 clock=time.monotonic, draft=None, prefix_cache=None,
                 adapters=None):
        from ...resilience.policy import CircuitBreaker
        self.program = program
        self.slots = int(program.slots)
        self.max_queue = int(max_queue)
        self.timeout_s = float(timeout_s) if timeout_s else None
        self.default_max_new = int(max_new_tokens)
        self.prefill_interleave = max(1, int(prefill_interleave))
        self.name = name
        self._clock = clock
        # request-trace span sink: the HTTP server points this at its
        # per-server SpanBuffer (distinct sites when one process hosts
        # a whole fleet); None falls back to the process buffer
        self.trace_sink = None
        self._breaker = breaker if breaker is not None else \
            CircuitBreaker(failure_threshold=3, reset_timeout=30.0)
        self._watchdog = watchdog
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._pending = []                 # FIFO of _Seq
        self._active = {}                  # slot -> _Seq
        self._free = list(range(self.slots))
        self._cache = None                 # built lazily on the worker
        self._closed = False
        self._degraded = False
        self._last_error = None
        self._op_seq = 0
        self._ema_step_s = None    # EWMA decode-step latency (hints)
        self._fallback_threads = []   # degraded completions in flight
        # request_id -> newest GenerateStream: a re-admission under
        # the same id (gateway mid-stream failover) cancels the prior
        # stream so a resumed request never decodes twice
        self._requests = {}
        self._counts = {'requests': 0, 'rejected': 0, 'tokens': 0,
                        'prefills': 0, 'steps': 0, 'timeouts': 0,
                        'fallback_tokens': 0, 'retired': {},
                        'prefix_hits': 0, 'prefix_tokens_saved': 0,
                        'spec_proposed': 0, 'spec_accepted': 0,
                        'spec_rounds': 0, 'cow_copies': 0,
                        'pool_exhausted': 0, 'page_evictions': 0,
                        'migrated_out': 0, 'migrated_in': 0,
                        'prefill_exports': 0,
                        'handoff_pages': 0, 'drain_timeouts': 0,
                        'sampled_tokens': 0, 'sampled_steps': 0,
                        'adapter_rejects': 0,
                        # the host's CPU at the tick's and the stream's
                        # boundaries: worker_cpu_s over worker_wall_s is
                        # the share of its ticks in which the scheduler
                        # held the interpreter
                        'worker_cpu_s': 0.0, 'worker_wall_s': 0.0,
                        'stream_cpu_s': 0.0, 'slow_ticks': 0,
                        # a server's stream pump (serving/stream_pump.py):
                        # wake-ups that sent, tokens, sends, streams
                        # handed back to their handlers with bytes unsent
                        'pump_batches': 0, 'pump_tokens': 0,
                        'pump_sends': 0, 'pump_fallbacks': 0,
                        # calls of the step program, those that ran more
                        # than one step, and the slot-steps a run spent
                        # on slots that had finished or were past their
                        # budget (``steps`` counts every step a call ran)
                        'step_calls': 0, 'multi_step_calls': 0,
                        'slot_steps_idle': 0}
        # live-migration requests serviced by the worker at tick
        # boundaries (the only thread that owns the device cache):
        # (op, arg, result_box, done_event)
        self._migrations = []
        # paged scheduling: the one owner of the pools' host state
        self.paged = bool(getattr(program, 'paged', False))
        self._pages = None
        if self.paged:
            if prefix_cache is None:
                prefix_cache = bool(
                    _knob('MXNET_TPU_SERVE_PREFIX_CACHE', True))
            self._pages = PageOwner(
                program.page_spec, program.pool_pages, self._lock,
                prefix_cache, self._counts, _record_event)
        # device-side counts the step program brings back behind its
        # tokens (program.last_step_stats), booked beside the host's
        self._step_stats = tuple(getattr(
            getattr(program, 'model', None), 'step_stats', ()))
        for name in self._step_stats:
            self._counts[name] = 0
        # and what a prefill counts on the host (program.
        # last_prefill_stats)
        for name in getattr(getattr(program, 'model', None),
                            'prefill_stats', ()):
            self._counts[name] = 0
        # speculative decoding: draft proposes spec_k tokens per tick,
        # the target verifies them in one batched call
        self._draft = None
        self._draft_cache = None
        self.spec_k = 0
        if draft is not None:
            spec_k = int(getattr(program, 'spec_k', 0))
            if not self.paged or not spec_k:
                raise ValueError(
                    'speculative decoding needs a paged target '
                    'program with spec_k > 0 (got paged=%r spec_k=%r)'
                    % (self.paged, spec_k))
            if int(draft.slots) != self.slots:
                raise ValueError('draft slots %d != target slots %d'
                                 % (int(draft.slots), self.slots))
            if getattr(draft, 'paged', False):
                raise ValueError(
                    'the draft must be a slot-addressed program (its '
                    'whole cache fits — there is no memory wall to '
                    'page at draft size); freeze it with paged=False')
            dm = getattr(draft, 'model', None)
            if dm is not None and not getattr(dm, 'supports_paging',
                                              True):
                raise ValueError(
                    'draft family %r cannot roll back rejected '
                    'proposals (needs a position-addressed cache: '
                    'use a transformer draft)' % (dm.family,))
            self._draft = draft
            # its XLA modules read jit_draft_* in a trace (programs it
            # compiled or loaded before now keep the names they have)
            draft.module_prefix = 'draft_'
            self.spec_k = spec_k
        # decode steps a call: the paged step runs up to its program's
        # ``max_run`` where no draft proposes (_RunLength); ``_run_n`` is
        # the current tick's, ``_step_read`` its step call's (read
        # seconds, steps)
        self._runs = _RunLength(
            getattr(program, 'max_run', 1)
            if self.paged and draft is None else 1)
        self._run_n = 1
        self._step_read = None
        # multi-adapter serving: the id -> pool-index registry. The
        # program must have been frozen with an adapter_spec (the pool
        # argument is part of its compiled signature); ``adapters``
        # may be a prebuilt AdapterRegistry or an artifact-directory
        # root (default: MXNET_TPU_SERVE_ADAPTER_DIR)
        self._adapters = None
        aspec = getattr(program, 'adapter_spec', None)
        if aspec is not None:
            from ..adapters import AdapterPool, AdapterRegistry
            if adapters is None:
                adapters = _knob('MXNET_TPU_SERVE_ADAPTER_DIR', None)
            if isinstance(adapters, AdapterRegistry):
                ps = adapters.pool.spec
                if (ps.capacity != aspec.capacity
                        or ps.rank != aspec.rank
                        or ps.targets != aspec.targets):
                    raise ValueError(
                        'adapter registry pool (rank=%d capacity=%d) '
                        'does not match the program\'s compiled '
                        'adapter_spec (rank=%d capacity=%d) — the '
                        'pool shape is part of the one compiled '
                        'step\'s signature'
                        % (ps.rank, ps.capacity, aspec.rank,
                           aspec.capacity))
                self._adapters = adapters
            else:
                self._adapters = AdapterRegistry(AdapterPool(aspec),
                                                 root=adapters or None)
        elif adapters is not None:
            raise ValueError(
                'adapters given but the program was frozen without an '
                'adapter_spec (freeze with adapter_rank > 0)')
        self._outbox = _Outbox()
        # the collector's pauses: py.gc on the profiler's clock, gc_* in
        # the counts, until close
        _spans.gc_watch(True)
        self._gc_watched = True
        self._worker = threading.Thread(
            target=self._run, daemon=True,
            name='mxnet-tpu-%s-decode' % name)
        self._worker.start()
        self._reaper = None
        if self.timeout_s:
            self._reaper = threading.Thread(
                target=self._reap_loop, daemon=True,
                name='mxnet-tpu-%s-decode-reaper' % name)
            self._reaper.start()

    # -- request tracing ---------------------------------------------------

    def _trace_span(self, seq, name, t0, t1, **attrs):
        """Emit one ``eng.*`` span under the request's trace context
        (worker-thread sites use explicit wall timestamps — the trace
        ctx rides ``seq.trace``, not thread-local state). No-op when
        the admission carried no context; never raises into the
        scheduler."""
        tr = seq.trace
        if tr is None:
            return
        sink = self.trace_sink
        if sink is None:
            try:
                from ...observability import trace as _tr
                sink = _tr.get_buffer()
            except Exception:
                return
        try:
            sink.emit(name, tr['ctx'].child(), t0, t1, **attrs)
        except Exception:
            pass

    # -- submission --------------------------------------------------------

    def generate(self, tokens, max_new_tokens=None, eos_id=None,
                 request_id=None, prefill_only=False, trace=None,
                 adapter=None, temperature=0.0, top_p=1.0, seed=0):
        """Admit one prompt; returns its :class:`GenerateStream`.

        ``adapter`` selects the LoRA variant (an id the engine's
        adapter registry resolves; ``None``/``''``/``'base'`` is the
        frozen base). ``temperature``/``top_p``/``seed`` select the
        sampling law — 0.0 temperature is greedy, byte-identical to
        pre-sampling engines. Both are per-request ARRAY arguments of
        the one compiled step: mixing greedy/sampled/multi-adapter
        traffic in one batch costs zero retraces.

        ``request_id`` makes admission idempotent: a second admission
        under the same id (the gateway re-admitting a stream after a
        mid-stream failover) cancels the previous stream at the next
        token boundary, so at most one decode works the request.

        ``prefill_only=True`` is the disaggregated-serving admission:
        the sequence runs its prefill (emitting the first token as
        usual), then exports its ``mxnet_tpu.seqstate.v1`` payload at
        the prefill boundary instead of entering the step loop. The
        stream finishes with reason ``'migrated'`` and the payload on
        ``stream.seqstate``; a first-token EOS / ``max_new_tokens=1``
        sequence finishes normally (nothing left to hand off).

        ``trace`` attaches a request-trace context
        (``observability.trace.TraceContext``): the engine emits
        ``eng.queue_wait`` / ``eng.prefill`` / ``eng.first_token`` /
        ``eng.steps`` spans for this request into its ``trace_sink``.

        Raises :class:`BackpressureError` when the pending queue is at
        depth, ``ValueError`` for an empty/over-long prompt (typed at
        admission, not mid-decode), :class:`BatcherClosed` after
        :meth:`close`."""
        prompt = [int(t) for t in onp.asarray(tokens).reshape(-1)]
        if not prompt:
            raise ValueError('empty prompt')
        if prefill_only and self.paged:
            self.program._one_page_list('prefill_only admission (its '
                                    'seqstate export)')
        if len(prompt) > self.program.max_prompt_len():
            raise ValueError(
                'prompt of %d tokens exceeds the top prefill bucket %d'
                % (len(prompt), self.program.max_prompt_len()))
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.default_max_new)
        if max_new < 1:
            raise ValueError('max_new_tokens must be >= 1')
        temperature = float(temperature)
        top_p = float(top_p)
        if temperature < 0:
            raise ValueError('temperature must be >= 0')
        if not 0 < top_p <= 1:
            raise ValueError('top_p must be in (0, 1]')
        from ..adapters import AdapterRegistry as _AR
        if adapter not in _AR.BASE_IDS and self._adapters is None:
            raise ValueError(
                'adapter %r requested but this engine serves no '
                'adapters (freeze with adapter_rank > 0 and point '
                'MXNET_TPU_SERVE_ADAPTER_DIR at the artifacts)'
                % (adapter,))
        now = self._clock()
        stream = GenerateStream(len(prompt))
        stream._outbox = self._outbox
        seq = _Seq(stream, prompt, max_new, eos_id, now,
                   now + self.timeout_s if self.timeout_s else None,
                   prefill_only=bool(prefill_only),
                   adapter_id=(None if adapter in _AR.BASE_IDS
                               else str(adapter)),
                   temperature=temperature, top_p=top_p, seed=seed)
        if trace is not None:
            w = time.time()
            seq.trace = {'ctx': trace, 'enq': w, 'last': w,
                         'first_w': None, 'tok0': 0}
        rejected_depth = None
        superseded = None
        with self._lock:
            if self._closed:
                raise BatcherClosed('decode engine %r is closed'
                                    % self.name)
            depth = len(self._pending)
            if depth >= self.max_queue:
                self._counts['rejected'] += 1
                rejected_depth = depth
            else:
                self._pending.append(seq)
                self._counts['requests'] += 1
                if request_id is not None:
                    superseded = self._requests.get(request_id)
                    self._requests[request_id] = stream
                    # bound the map: finished streams age out once it
                    # outgrows everything that can be in flight
                    if len(self._requests) > 4 * (self.max_queue
                                                  + self.slots):
                        self._requests = {
                            k: s for k, s in self._requests.items()
                            if not s.done()}
                self._wake.notify()
        # admission telemetry outside the lock (locklint LOCK-EMIT:
        # flight-recorder/metrics emits never extend a critical
        # section — same hierarchy as serving/batcher.py)
        if rejected_depth is not None:
            inst = _serving_instruments()
            if inst is not None:
                inst.rejected.labels(reason='queue_full').inc()
            _record_event('serve_reject', reason='queue_full',
                          depth=rejected_depth, limit=self.max_queue)
            raise BackpressureError(rejected_depth, self.max_queue)
        if superseded is not None and not superseded.done():
            # at-most-once per request_id: retire the older stream at
            # its next token boundary (cancel outside the lock — it
            # only flips a flag, but keep the critical section lean)
            superseded.cancel()
        inst = _serving_instruments()
        if inst is not None:
            inst.requests.inc()
            inst.queue_depth.set(depth + 1)
        return stream

    # -- reaper (budget enforcement independent of the worker) -------------

    def _reap_loop(self):
        while True:
            time.sleep(min(0.05, max(self.timeout_s / 4.0, 0.005)))
            with self._lock:
                if self._closed and not self._pending \
                        and not self._active:
                    return
                now = self._clock()
                kept = []
                for seq in self._pending:
                    if seq.deadline_at is not None \
                            and now >= seq.deadline_at:
                        self._counts['timeouts'] += 1
                        seq.stream._finish('error', RequestTimeout(
                            'request waited %.3fs in queue (budget '
                            '%.3fs)' % (now - seq.enqueued_at,
                                        self.timeout_s)))
                    elif seq.stream._cancelled:
                        seq.stream._finish('cancelled')
                    else:
                        kept.append(seq)
                self._pending = kept
                # active sequences past budget: mark the stream NOW
                # (the client unblocks even if the worker is wedged
                # inside a device call); the worker retires the slot
                # at the next token boundary
                for seq in self._active.values():
                    if seq.deadline_at is not None \
                            and now >= seq.deadline_at \
                            and not seq.stream.done():
                        self._counts['timeouts'] += 1
                        seq.stream._finish('error', RequestTimeout(
                            'generation exceeded its %.3fs budget '
                            'mid-stream (%d tokens emitted)'
                            % (self.timeout_s,
                               len(seq.stream.tokens))))

    # -- worker ------------------------------------------------------------

    def _idle(self):
        """Nothing to schedule (caller holds the lock)."""
        return not self._pending and not self._active \
            and not self._migrations

    def _run(self):
        from .program import while_device_runs
        self._outbox.owner = threading.get_ident()
        while_device_runs.hook = self._outbox.flush
        while True:
            with _span('eng.loop'):
                head = self._loop_head()
            if head is None:
                return
            try:
                self._tick(*head)
            except Exception:           # pragma: no cover - last resort
                logging.exception('decode engine %s: scheduler tick '
                                  'failed', self.name)
                time.sleep(0.01)

    def _loop_head(self):
        """Wait for work; the next tick's ``eng.tick`` arguments, or None
        once the engine is closed and drained."""
        with self._lock:
            idle = self._idle()
        if idle:
            # no device call is coming for them to wait behind
            self._outbox.flush()
            # the span opens outside the lock: its own emits never
            # extend the critical section
            with _span('eng.wait_work'):
                with self._lock:
                    while self._idle() and not self._closed:
                        self._wake.wait(0.05)
        with self._lock:
            if self._closed and self._idle():
                self._outbox.flush()
                return None
            return (self._counts['steps'], len(self._active),
                    len(self._pending), len(self._free))

    def _tick(self, step, active, pending, free):
        """One scheduler iteration: retire finished/abandoned slots,
        service migration requests, admit prefills, advance the live
        batch ``n`` tokens in one call (:class:`_RunLength`; admissions
        ``prefill_interleave`` a step of it).

        The tick and each of its phases is a host span on the
        profiler's clock (``eng.tick`` / ``eng.tick.*``, worker thread
        only), so a trace's idle gaps name the phase the scheduler was
        in. ``step`` is the id ``eng.queue_wait`` / ``eng.prefill``
        carry as ``tick``; ``wall`` ties the profiler's clock to the
        span buffer's."""
        n = self._runs.steps(active, pending, free,
                             self.prefill_interleave)
        with _span('eng.tick', step=step, active=active,
                   pending=pending, n=n, wall=time.time()):
            mark = (time.thread_time(), time.perf_counter(),
                    getattr(self.program, 'read_s', 0.0),
                    _spans.gc_pause_total())
            self._run_n = n
            self._step_read = None
            self._outbox.flushed = False
            with _span('eng.tick.retire'):
                self._retire_abandoned()
            with _span('eng.tick.migrate'):
                self._service_migrations()
            budget = self.prefill_interleave * n if self._active \
                else self.slots
            while budget > 0:
                with self._lock:
                    if not self._pending or not self._free:
                        break
                    seq = self._pending.pop(0)
                    slot = self._free.pop(0)
                # one span an admission, not one around the loop: the
                # reduction that reads them (benchmark/xplane.py) looks
                # back 64 spans for the one open over a gap, and one
                # prefill's device call leaves some forty behind it
                with _span('eng.tick.admit'):
                    self._admit(seq, slot)
                budget -= 1
            if self._active:
                self._step()
            if not self._outbox.flushed:
                # a tick that enqueued no program (every admission
                # refused, every slot done): nothing to wait behind
                with _span('eng.tick.wake_readers'):
                    self._outbox.flush()
            with _span('eng.tick.telemetry'):
                inst = _serving_instruments()
                if inst is not None:
                    with self._lock:
                        inst.active_slots.set(len(self._active))
                        inst.queue_depth.set(len(self._pending))
                        if self._pages is not None:
                            pool = self._pages.pool_stats()
                            inst.pages_total.set(pool['pages_total'])
                            inst.pages_free.set(pool['pages_free'])
                            inst.page_occupancy.set(pool['occupancy_pct'])
                self._book_tick(step, *mark)

    def _book_tick(self, step, cpu0, wall0, read0, gc0):
        """The tick's CPU and wall seconds into the counts; a slow tick
        also leaves its parts in the flight recorder."""
        cpu = time.thread_time() - cpu0
        wall = time.perf_counter() - wall0
        read = getattr(self.program, 'read_s', 0.0) - read0
        if self._step_read is not None and self._runs.max_run > 1:
            step_read, n = self._step_read
            self._runs.observe(wall - read, step_read / n)
        slow = wall > _SLOW_TICK_S
        with self._lock:
            self._counts['worker_cpu_s'] += cpu
            self._counts['worker_wall_s'] += wall
            if slow:
                self._counts['slow_ticks'] += 1
        if slow:
            _record_event(
                'decode_slow_tick', step=step, wall_s=round(wall, 6),
                cpu_s=round(cpu, 6),
                read_s=round(read, 6),
                gc_pause_s=round(_spans.gc_pause_total() - gc0, 6))

    def note_stream_cpu(self, seconds):
        """A stream's reader books the CPU seconds its thread spent on the
        stream (``ServingHTTPServer``'s NDJSON relay)."""
        with self._lock:
            self._counts['stream_cpu_s'] += seconds

    def note_pump(self, tokens, sends, fallbacks, cpu_s):
        """A stream pump books one wake-up: the tokens it sent, its sends,
        the streams it handed back with bytes unsent, and its thread's CPU
        seconds since the last wake-up (``stream_cpu_s``)."""
        with self._lock:
            counts = self._counts
            counts['pump_batches'] += 1 if sends else 0
            counts['pump_tokens'] += tokens
            counts['pump_sends'] += sends
            counts['pump_fallbacks'] += fallbacks
            counts['stream_cpu_s'] += cpu_s

    def _retire_abandoned(self):
        """Free slots whose stream is already done (timeout reaper or
        client cancel) so they stop consuming decode batch slots —
        the same contract the micro-batcher applies at flush time."""
        with self._lock:
            doomed = [(slot, seq) for slot, seq in self._active.items()
                      if seq.stream.done() or seq.stream._cancelled]
        for slot, seq in doomed:
            if seq.stream._cancelled and not seq.stream.done():
                seq.stream._finish('cancelled')
            self._retire(slot, seq, seq.stream.finish_reason
                         or 'cancelled')

    def _retire(self, slot, seq, reason):
        with self._lock:
            if self._active.get(slot) is seq:
                del self._active[slot]
                self._free.append(slot)
                self._counts['retired'][reason] = \
                    self._counts['retired'].get(reason, 0) + 1
                # drop the sequence's page holds; pages whose prefix
                # registration still holds a ref stay resident for
                # future hits (evicted LRU under pool pressure)
                if seq.pages is not None:
                    self._pages.drop(seq.pages)
        # adapter pool unpin outside the lock (the pool has its own)
        self._release_adapter(seq)
        _record_event('decode_retire', slot=slot, reason=reason,
                      tokens=len(seq.stream.tokens))
        tr = seq.trace
        if tr is not None and tr.get('first_w') is not None:
            # step-loop summary for THIS engine's segment of the
            # request (a migrated-out sequence closes its segment
            # here; the importer opens its own)
            w = time.time()
            ntok = len(seq.stream.tokens) - tr.get('tok0', 0)
            steps = max(0, ntok - 1)
            if steps and w > tr['first_w']:
                self._trace_span(seq, 'eng.steps', tr['first_w'], w,
                                 tokens=ntok, steps=steps,
                                 reason=reason)
            tr['first_w'] = None     # at-most-once per segment

    # -- paged pool bookkeeping (worker thread only) -----------------------

    def _rebuild_cache(self):
        """Fresh device cache after a failed call (donated buffers are
        unusable): the pools' host state — free lists, refcounts,
        prefix registrations — describes garbage now, so it resets
        with it (under the lock: stats()/cache_accounting() readers
        must never observe a half-reset pool). Callers retire (and
        release) in-flight slots FIRST."""
        self._cache = self.program.new_cache()
        if self._pages is not None:
            self._pages.reset()
        if self._draft is not None:
            self._draft_cache = self._draft.new_cache()

    def _fail_pool_exhausted(self, seq, slot, where):
        """Pool exhaustion is typed backpressure, never a stall: the
        stream fails with BackpressureError (the flight recorder
        explains the admission rejection), the client backs off."""
        with self._lock:
            self._counts['pool_exhausted'] += 1
            depth = len(self._pending)
            free = self._pages.pool_stats()['pages_free']
        inst = _serving_instruments()
        if inst is not None:
            inst.rejected.labels(reason='pool_exhausted').inc()
        _record_event('serve_reject', reason='pool_exhausted',
                      slot=slot, where=where, pages_free=free,
                      depth=depth)
        seq.stream._finish('error', BackpressureError(
            depth, self.max_queue))

    def _copy_page(self, src, dst):
        """The page owner's copy-on-write, as a device call under the
        breaker and the watchdog like every other."""
        self._cache = self._device(self.program.run_copy_page,
                                   self._cache, src, dst)

    def _take_cache(self, out):
        """A program's result ``(cache', tokens, logits)``: the cache it
        hands back replaces the one it was given, and the rest is
        returned. Dropping the given cache deletes its donated buffers,
        and the worker waits for the interpreter on its way back from
        that: ``eng.tick.release_cache``, a leaf of its own."""
        with _span('eng.tick.release_cache'):
            self._cache, toks, logits = out
        return toks, logits

    # -- device calls under breaker + watchdog -----------------------------

    def _next_op(self):
        with self._lock:
            seq = self._op_seq
            self._op_seq += 1
        return seq

    def _execute(self, fn, step, *args, **kwargs):
        from ...resilience.policy import inject
        inject('serving.decode',
               ('device_loss', 'device_unavailable', 'device_stall',
                'worker_crash', 'preempt'), step=step)
        if self._watchdog is not None:
            self._watchdog.check()
        return fn(*args, **kwargs)

    def _device(self, fn, *args, **kwargs):
        """Run one device call under the breaker; a transient failure
        or an open breaker raises :class:`_DegradedPath` after
        recording the trip (server.py's _serve contract). A worker
        crash / preemption notice raises :class:`_AbortPath` instead:
        infrastructure trouble degrades, a dying worker aborts its
        in-flight requests typed.

        The call is the span ``eng.tick.call``: the guard and the
        program's preparation of its arguments are its own time;
        ``eng.tick.dispatch``, ``eng.tick.read_tokens`` and
        ``eng.tick.after_call`` nest inside it."""
        with _span('eng.tick.call'):
            from ...resilience.policy import (CircuitOpenError,
                                              PreemptionSignal,
                                              WorkerCrashError,
                                              is_transient)
            step = self._next_op()
            if self._watchdog is not None:
                self._watchdog.beat(step=step, phase='decode')
            was_open = self._breaker.state == 'open'
            try:
                out = self._breaker.call(self._execute, fn, step, *args,
                                         **kwargs)
            except (WorkerCrashError, PreemptionSignal) as exc:
                # the breaker already counted the failure (breaker.call)
                self._note_failure(exc, step, was_open)
                raise _AbortPath(exc) from exc
            except Exception as exc:
                if not (is_transient(exc)
                        or isinstance(exc, CircuitOpenError)):
                    raise               # bug-shaped: surface loudly
                self._note_failure(exc, step, was_open)
                raise _DegradedPath() from exc
            # a span of its own: the call has left some hundred runtime
            # spans behind it, more than the reduction looks back over
            with _span('eng.tick.after_call'):
                with self._lock:
                    self._degraded = False
                    self._last_error = None
                inst = _serving_instruments()
                if inst is not None:
                    inst.degraded.set(0.0)
            return out

    def on_stall(self, record):
        """Watchdog monitor-thread escalation (wired by the server):
        a decode device call overran its budget with the worker still
        blocked inside it."""
        with self._lock:
            self._degraded = True
            self._last_error = ('stall: %s phase stalled %.1fs '
                                '(budget %.1fs)'
                                % (record.get('phase'),
                                   record.get('waited_s', 0.0),
                                   record.get('budget_s', 0.0)))
        self._breaker.record_failure()
        inst = _serving_instruments()
        if inst is not None:
            inst.degraded.set(1.0)

    def _note_failure(self, exc, step, was_open):
        with self._lock:
            self._degraded = True
            self._last_error = '%s: %s' % (type(exc).__name__, exc)
        state = self._breaker.state
        newly_open = state != 'closed' and not was_open
        logging.warning('decode %s: device call %d failed (%s); '
                        'state=%s, completing in-flight sequences on '
                        'CPU fallback', self.name, step,
                        self._last_error, state)
        inst = _serving_instruments()
        if inst is not None:
            inst.degraded.set(1.0)
            if newly_open:
                inst.breaker_trips.inc()
        if newly_open:
            _record_event('breaker_open', step=step,
                          error=self._last_error)
            _flight_dump(reason='breaker')
        else:
            _record_event('serve_fallback', step=step,
                          error=self._last_error)

    # -- scheduling primitives ---------------------------------------------

    def _export_at_boundary(self, seq, slot):
        """``prefill_only`` admission: the prefill just landed —
        export the seqstate payload (stashed on the stream) and finish
        'migrated' instead of entering the step loop. Runs on the
        worker thread, the cache owner — same ownership rule as
        migration servicing."""
        try:
            self._do_export(seq.stream, stash=True)
            with self._lock:
                self._counts['prefill_exports'] = \
                    self._counts.get('prefill_exports', 0) + 1
        except BaseException as exc:
            # never leave the client hanging: a failed boundary export
            # fails THIS request typed, and its slot/pages free
            if not seq.stream.done():
                seq.stream._finish('error', exc)
                self._retire(slot, seq, 'error')
            logging.exception('decode %s: prefill-boundary export '
                              'failed', self.name)

    # -- sampling / adapter array args of the compiled step ----------------

    def _acquire_adapter(self, seq):
        """Resolve + pin the sequence's adapter pool row (worker
        thread — a cold load uploads the padded A/B stacks once; a
        warm one is a refcount bump). No-op for base traffic."""
        if seq.adapter_id is None or self._adapters is None:
            seq.adapter_idx = 0
            return
        seq.adapter_idx = self._adapters.acquire(seq.adapter_id)

    def _release_adapter(self, seq):
        if self._adapters is not None and seq.adapter_idx:
            self._adapters.release(seq.adapter_idx)
            seq.adapter_idx = 0

    def _admit_adapter(self, seq, slot):
        """Pin the adapter row at admission. On failure — unknown id,
        or :class:`~..adapters.AdapterExhaustedError` with every row
        pinned — THIS request fails typed (shed/retry contract) and
        the slot frees. Returns False when admission must stop."""
        try:
            self._acquire_adapter(seq)
            return True
        except Exception as exc:
            with self._lock:
                self._free.append(slot)
                self._counts['adapter_rejects'] += 1
            seq.stream._finish('error', exc)
            inst = _serving_instruments()
            if inst is not None:
                inst.rejected.labels(reason='adapter_pool').inc()
            _record_event('adapter_reject', adapter=seq.adapter_id,
                          error=str(exc))
            return False

    def _prefill_extras(self, seq):
        """Sampling/adapter kwargs for one ``run_prefill``; what is
        left out takes the program's neutral value (greedy, the base
        adapter)."""
        kw = {}
        if seq.temperature > 0:
            # the prefill's emitted token is the logits row at
            # absolute position len(prompt) - 1
            kw['temps'] = onp.asarray([seq.temperature], 'float32')
            kw['top_ps'] = onp.asarray([seq.top_p], 'float32')
            kw['keys'] = key_for(seq.seed, seq.prompt_len - 1)[None]
        if self._adapters is not None:
            kw['apool'] = self._adapters.pool.device_tree()
            kw['aidx'] = seq.adapter_idx
        return kw

    def _sampling_extras(self, active, spec_c=0, off=0):
        """Per-slot sampling arrays for one step call at absolute
        positions ``pos + off`` (or one verify call, or one call of a
        run of steps: ``spec_c`` keys per slot at ``pos .. pos + spec_c
        - 1``, exactly the keys the plain path would burn at those
        positions). {} with no live slot that samples: the program
        fills in the same neutral values (greedy rows)."""
        if not _samples(active):
            return {}
        temps = onp.zeros(self.slots, 'float32')
        top_ps = onp.ones(self.slots, 'float32')
        shape = (self.slots, spec_c, 2) if spec_c else (self.slots, 2)
        keys = onp.zeros(shape, 'uint32')
        for slot, seq in active.items():
            if seq.temperature <= 0:
                continue
            temps[slot] = seq.temperature
            top_ps[slot] = seq.top_p
            if spec_c:
                for c in range(spec_c):
                    keys[slot, c] = key_for(seq.seed, seq.pos + c)
            else:
                keys[slot] = key_for(seq.seed, seq.pos + off)
        return {'temps': temps, 'top_ps': top_ps, 'keys': keys}

    def _step_extras(self, active, spec_c=0):
        """Per-slot sampling/adapter arrays for one step, run or
        verify call of the target program."""
        kw = self._sampling_extras(active, spec_c)
        if self._adapters is not None:
            aidx = onp.zeros(self.slots, 'int32')
            for slot, seq in active.items():
                aidx[slot] = seq.adapter_idx
            kw['apool'] = self._adapters.pool.device_tree()
            kw['aidx'] = aidx
        return kw

    def _admit(self, seq, slot):
        """Join: prefill one pending request into ``slot``. The two
        kinds of program (slot cache, paged) differ in the body only:
        what a failed device call does to the request is written
        here, once."""
        if seq.stream.done() or seq.stream._cancelled:
            if not seq.stream.done():
                seq.stream._finish('cancelled')
            with self._lock:
                self._free.append(slot)
            return
        tr = seq.trace
        if tr is not None:
            w0 = time.time()
            # the eng.tick span (profiler's clock) that admitted it
            tr['tick'] = self._counts['steps']
            self._trace_span(seq, 'eng.queue_wait', tr['enq'], w0,
                             tick=tr['tick'])
            tr['last'] = w0
        if not self._admit_adapter(seq, slot):
            return
        try:
            if self._cache is None:
                self._rebuild_cache()
            emit = self._prefill_paged(seq, slot) if self.paged \
                else self._prefill_slot(seq, slot)
        except _DegradedPath:
            self._give_back(seq, slot)
            self._spawn_fallback([seq])
        except _AbortPath as ab:
            # worker crash / preemption at prefill: fail THIS request
            # with the typed error (client retries), free the slot
            self._give_back(seq, slot)
            seq.stream._finish('error', ab.exc)
        except Exception as exc:
            # bug-shaped (non-transient) failure: fail THIS request
            # loudly with the typed error, but never leak its slot or
            # leave its stream blocking forever
            self._give_back(seq, slot)
            seq.stream._finish('error', exc)
            logging.exception('decode %s: %s failed with a '
                              'non-transient error', self.name,
                              'paged prefill' if self.paged
                              else 'prefill')
        else:
            if emit is not None:
                with _span('eng.tick.emit'):
                    emit()

    def _give_back(self, seq, slot):
        """An admission that came to nothing: unpin the adapter row,
        release the pages, free the slot."""
        self._release_adapter(seq)
        with self._lock:
            if seq.pages is not None:
                self._pages.drop(seq.pages)
            self._free.append(slot)

    def _prefill_slot(self, seq, slot):
        """One bucketed prefill into ``slot`` of the slot cache.
        Returns what emits its first token."""
        tok, _logits = self._take_cache(self._device(
            self.program.run_prefill, self._cache,
            onp.asarray(seq.prompt, 'int32'), slot,
            **self._prefill_extras(seq)))
        return functools.partial(self._emit_prefilled, seq, slot, tok)

    def _prefill_paged(self, seq, slot):
        """Paged join: a prefix-cache hit references the shared pages
        and streams the remaining prompt through the decode step (no
        prefill program runs — the prefix was prefilled ONCE); a miss
        allocates pages and runs one bucketed prefill into them.
        Returns what emits the first token, None where nothing is to
        emit yet (a hit) or ever (pool exhausted: failed typed)."""
        prompt = seq.prompt
        n = len(prompt)
        seq.pages = self._pages.open(slot)
        # namespaced by adapter id: an adapter's KV rows for the same
        # tokens differ from the base's — a warm hit must never splice
        # across variants
        covered, shared = self._pages.share_prefix(
            seq.pages, prompt, namespace=seq.adapter_id)
        if covered > 0:
            seq.slot = slot
            seq.pos = covered
            seq.last_token = int(prompt[covered])
            if self._draft is not None:
                # the draft has no prefix cache: prefill it whole
                # (cheap — that is what makes it a draft)
                self._draft_cache, _dt, _dl = self._device(
                    self._draft.run_prefill, self._draft_cache,
                    onp.asarray(prompt, 'int32'), slot)
            inst = _serving_instruments()
            if inst is not None:
                inst.prefix_hits.inc()
                inst.prefix_tokens_saved.inc(covered)
            _record_event('prefix_hit', slot=slot, prompt_len=n,
                          tokens_shared=covered, pages_shared=shared)
            _record_event('decode_admit', slot=slot, prompt_len=n,
                          prefix_tokens=covered)
            with self._lock:
                self._active[slot] = seq
            if seq.prefill_only:
                # hand off the extending state (pos=covered, no
                # token emitted yet): the importer streams the
                # un-shared suffix through ITS decode step
                self._export_at_boundary(seq, slot)
            return None
        ids = self._pages.place(seq.pages, n)
        if ids is None:
            self._fail_pool_exhausted(seq, slot, where='admit')
            self._give_back(seq, slot)
            return None
        tok, _logits = self._take_cache(self._device(
            self.program.run_prefill, self._cache,
            onp.asarray(prompt, 'int32'), ids,
            **self._prefill_extras(seq)))
        booked = getattr(self.program, 'last_prefill_stats', None)
        if booked:
            with self._lock:
                for name, v in booked.items():
                    self._counts[name] += v
        if self._draft is not None:
            self._draft_cache, _dt, _dl = self._device(
                self._draft.run_prefill, self._draft_cache,
                onp.asarray(prompt, 'int32'), slot)
        self._pages.register(prompt, ids, namespace=seq.adapter_id)
        return functools.partial(self._emit_prefilled, seq, slot, tok,
                                 prefix_tokens=0)

    def _emit_prefilled(self, seq, slot, tok, **event):
        """A prefill has landed in ``slot``: book it, make the sequence
        live and stream its first token (``event`` adds fields to the
        ``decode_admit`` flight event)."""
        n = len(seq.prompt)
        with self._lock:
            self._counts['prefills'] += 1
            self._counts['tokens'] += 1
            if seq.temperature > 0:
                self._counts['sampled_tokens'] += 1
        seq.slot = slot
        seq.pos = n
        seq.last_token = int(tok)
        now = self._clock()
        seq.first_token_at = now
        inst = _serving_instruments()
        if inst is not None:
            inst.prefills.inc()
            inst.tokens.inc()
            if seq.temperature > 0:
                inst.sampled_tokens.inc()
            inst.ttft.observe(max(0.0, now - seq.enqueued_at))
        tr = seq.trace
        if tr is not None:
            w1 = time.time()
            self._trace_span(seq, 'eng.prefill', tr['last'], w1,
                             tokens=n, tick=tr['tick'])
            self._trace_span(seq, 'eng.first_token', tr['last'], w1,
                             ttft_s=round(w1 - tr['enq'], 6))
            tr['last'] = tr['first_w'] = w1
        _record_event('decode_admit', slot=slot, prompt_len=n, **event)
        # register BEFORE the finish check so a first-token EOS /
        # max_new=1 retirement flows through _retire and frees the
        # slot instead of leaking it
        with self._lock:
            self._active[slot] = seq
        seq.stream._emit(tok)
        reason = self._finished_reason(seq, int(tok))
        if reason is not None:
            seq.stream._finish(reason)
            self._retire(slot, seq, reason)
        elif seq.prefill_only:
            self._export_at_boundary(seq, slot)

    def _finished_reason(self, seq, tok):
        if seq.eos_id is not None and tok == seq.eos_id:
            return 'eos'
        if len(seq.stream.tokens) >= seq.max_new:
            return 'length'
        if seq.pos + 1 >= self.program.max_len:
            return 'length'
        return None

    def _step(self):
        """Advance every live slot one token (the single fixed-shape
        decode program); paged engines dispatch the page-table step,
        or the speculative draft+verify tick when eligible. The three
        differ in the body only: what a failed device call does to
        the sequences in flight is written here, once."""
        with self._lock:
            active = dict(self._active)
        if not active:
            return
        if not self.paged:
            what, run = 'step', self._slot_step
        elif (self._draft is not None and self.spec_k
              and all(not s.extending
                      and s.pos + self.spec_k < self.program.max_len
                      for s in active.values())):
            what, run = 'speculative step', self._spec_step
        else:
            what, run = 'paged step', self._paged_step
        try:
            emit = run(active)
        except _DegradedPath:
            self._degrade_inflight(active)
        except _AbortPath as ab:
            # worker crash / preemption mid-stream: every in-flight
            # sequence terminates with the typed error (an NDJSON
            # stream gets it as its final line), slots retire, and
            # the cache rebuilds for the engine's recovery
            self._fail_inflight(active, ab.exc, 'aborted')
        except Exception as exc:
            # bug-shaped failure: a deterministic error would recur
            # every tick — fail the in-flight streams with the typed
            # error, retire their slots, rebuild the (possibly
            # donated-away) cache, and keep the engine serviceable
            logging.exception('decode %s: %s failed with a '
                              'non-transient error', self.name, what)
            self._fail_inflight(active, exc, 'error')
        else:
            if emit is not None:
                with _span('eng.tick.emit'):
                    emit()

    def _fail_inflight(self, active, exc, reason):
        for slot, seq in active.items():
            seq.stream._finish('error', exc)
            self._retire(slot, seq, reason)
        self._rebuild_cache()

    def _slot_step(self, active):
        """One decode step over the slot cache. Returns what emits
        its tokens."""
        with _span('eng.tick.build_inputs'):
            tokens = onp.zeros(self.slots, 'int32')
            positions = onp.zeros(self.slots, 'int32')
            for slot, seq in active.items():
                tokens[slot] = seq.last_token
                positions[slot] = seq.pos
            extras = self._step_extras(active)
        t0 = self._clock()
        toks, _logits = self._take_cache(self._device(
            self.program.run_step, self._cache, tokens, positions,
            **extras))
        return lambda: self._emit_step(active, toks, self._clock() - t0)

    def _emit_step(self, active, toks, dt):
        """Book the step and hand each live slot its token."""
        sampled_step = _samples(active)
        with self._lock:
            self._counts['steps'] += 1
            self._counts['step_calls'] += 1
            self._counts['sampled_steps'] += sampled_step
            self._counts['tokens'] += len(active)
            self._ema_step_s = dt if self._ema_step_s is None \
                else 0.7 * self._ema_step_s + 0.3 * dt
        inst = _serving_instruments()
        if inst is not None:
            inst.decode_steps.inc()
            if sampled_step:
                inst.sampled_steps.inc()
            inst.tokens.inc(len(active))
            inst.tpot.observe(dt)
        sampled = 0
        for slot, seq in active.items():
            if seq.stream.done() or seq.stream._cancelled:
                continue            # retired at the next tick
            tok = int(toks[slot])
            seq.pos += 1
            seq.last_token = tok
            seq.stream._emit(tok)
            if seq.temperature > 0:
                sampled += 1
            reason = self._finished_reason(seq, tok)
            if reason is not None:
                seq.stream._finish(reason)
                self._retire(slot, seq, reason)
        if sampled:
            with self._lock:
                self._counts['sampled_tokens'] += sampled
            if inst is not None:
                inst.sampled_tokens.inc(sampled)

    def _emit_token(self, seq, tok):
        """Stream one generated token (TTFT observed on the first —
        prefix-hit sequences earn their first token from a decode
        step, not a prefill)."""
        if seq.first_token_at is None:
            now = self._clock()
            seq.first_token_at = now
            inst = _serving_instruments()
            if inst is not None:
                inst.ttft.observe(max(0.0, now - seq.enqueued_at))
            tr = seq.trace
            if tr is not None:
                w = time.time()
                self._trace_span(seq, 'eng.first_token', tr['last'], w,
                                 ttft_s=round(w - tr['enq'], 6))
                tr['first_w'] = w
        seq.stream._emit(tok)

    def _page_faults(self, active, lookahead=0):
        """Pre-step page maintenance for every live slot: lazy
        allocation at boundary crossings + copy-on-write of shared
        pages, for the positions from ``pos`` to ``pos + lookahead``
        that the call writes (``lookahead`` one number, or one a slot).
        Pool exhaustion fails THAT stream typed and drops it from this
        tick; device errors propagate to the caller."""
        self._pages.advance(
            (seq.pages, seq.pos) for seq in active.values()
            if not (seq.stream.done() or seq.stream._cancelled))
        for slot, seq in list(active.items()):
            if seq.stream.done() or seq.stream._cancelled:
                continue
            ahead = lookahead[slot] if isinstance(lookahead, dict) \
                else lookahead
            if not self._pages.make_writable(
                    seq.pages, seq.pos, seq.pos + ahead,
                    self._copy_page):
                self._fail_pool_exhausted(seq, slot, where='step')
                self._retire(slot, seq, 'error')
                del active[slot]
        return active

    def _paged_step(self, active):
        """One call of the step program through the page tables: ``n``
        steps, this tick's (:class:`_RunLength`). Extension slots
        (prefix hits still consuming their prompt suffix) feed prompt
        tokens and emit nothing until the last prompt token's logits
        produce their first generated token. Returns what emits the
        call's tokens, None where no sequence is left to step."""
        t0 = self._clock()
        n = self._run_n
        with _span('eng.tick.page_faults'):
            budgets = self._budgets(active, n) if n > 1 else None
            active = self._page_faults(
                active, {slot: b - 1 for slot, b in budgets.items()}
                if budgets else 0)
        if not active:
            return None
        with _span('eng.tick.build_inputs'):
            tokens = onp.zeros(self.slots, 'int32')
            positions = onp.zeros(self.slots, 'int32')
            for slot, seq in active.items():
                tokens[slot] = seq.last_token
                positions[slot] = seq.pos
            tables = self._pages.tables(
                self.slots, ((slot, seq.pages)
                             for slot, seq in active.items()))
            run = {}
            live = [seq.pos for seq in active.values()]
            if budgets:
                run = self._run_inputs(active, budgets)
                live = [seq.pos + k for slot, seq in active.items()
                        for k in range(budgets[slot])]
            walked, view = self._pages.step_pages(self.slots, live)
            # a run's copies are reckoned from its first step's operands
            kv_pages = (walked, n * view,
                        n * self._pages.step_copies(tables, positions))
            extras = self._step_extras(active, spec_c=n if n > 1 else 0)
        read0 = getattr(self.program, 'read_s', 0.0)
        toks, _logits = self._take_cache(self._device(
            self.program.run_steps, self._cache, tokens, positions,
            tables, n, **run, **extras))
        self._step_read = (getattr(self.program, 'read_s', 0.0) - read0,
                           n)
        if self._draft is not None:
            # keep the draft's KV history in lockstep on
            # non-speculative ticks (extension / near-max_len):
            # a hole at these positions would starve every later
            # speculative round's proposals
            self._draft_cache, _dt, _dl = self._device(
                self._draft.run_step, self._draft_cache, tokens,
                positions)
        return lambda: self._emit_paged_step(active, toks,
                                             self._clock() - t0, kv_pages)

    def _budgets(self, active, n):
        """The steps each live slot takes in a call of ``n``: no further
        than the step whose token ends it by length (``max_new`` or
        ``max_len``), so that a slot writes no position past those its
        pages were made writable for; 0 for a stream already done."""
        last = self.program.max_len - 1
        out = {}
        for slot, seq in active.items():
            if seq.stream.done() or seq.stream._cancelled:
                out[slot] = 0
                continue
            extending = max(0, len(seq.prompt) - 1 - seq.pos)
            out[slot] = min(n, extending + seq.max_new
                            - len(seq.stream.tokens), last - seq.pos)
        return out

    def _run_inputs(self, active, budgets):
        """``run_steps``' budgets and forced tokens for a call of several
        steps: a slot still extending feeds the prompt's next tokens."""
        budget = onp.zeros(self.slots, 'int32')
        forced = onp.full((self.program.max_run, self.slots), -1, 'int32')
        for slot, seq in active.items():
            budget[slot] = b = budgets[slot]
            prompt, pos = seq.prompt, seq.pos
            for k in range(1, min(b, len(prompt) - pos)):
                forced[k, slot] = prompt[pos + k]
        return {'budgets': budget, 'forced': forced}

    def _emit_paged_step(self, active, toks, dt, kv_pages):
        """Advance positions, stream each slot's tokens, book the call
        (``toks`` (steps, slots); ``kv_pages``: the pages its attention
        had to read, those of a gathered view and the copies of a walk,
        ``PageOwner.step_pages`` and ``step_copies``). Each token's
        finish checks run at its own position; a slot's tokens after its
        finish are dropped and counted idle, as are the steps of a slot
        whose stream was already done."""
        n = len(toks)
        rows = toks.tolist()
        emitted = 0
        sampled = 0
        idle = 0
        sampled_step = _samples(active)
        for slot, seq in active.items():
            if seq.stream.done() or seq.stream._cancelled:
                idle += n
                continue            # retired at the next tick
            prompt_last = len(seq.prompt) - 1
            for k in range(n):
                fed_pos = seq.pos
                seq.pos += 1
                if fed_pos < prompt_last:
                    # extension: the fed token was a prompt token and the
                    # prediction is ignored; the next prompt token feeds
                    seq.last_token = int(seq.prompt[seq.pos])
                    continue
                tok = rows[k][slot]
                seq.last_token = tok
                self._emit_token(seq, tok)
                emitted += 1
                if seq.temperature > 0:
                    sampled += 1
                reason = self._finished_reason(seq, tok)
                if reason is not None:
                    seq.stream._finish(reason)
                    self._retire(slot, seq, reason)
                    idle += n - 1 - k
                    break
        with self._lock:
            self._counts['steps'] += n
            self._counts['step_calls'] += 1
            self._counts['multi_step_calls'] += n > 1
            self._counts['slot_steps_idle'] += idle
            self._counts['sampled_steps'] += sampled_step * n
            self._counts['tokens'] += emitted
            self._counts['sampled_tokens'] += sampled
            self._counts['kv_pages_walked'] += kv_pages[0]
            self._counts['kv_pages_view'] += kv_pages[1]
            self._counts['kv_page_copies'] += kv_pages[2]
            if self._step_stats:
                # what the device counted came back behind the tokens
                for name, v in self.program.last_step_stats.items():
                    self._counts[name] += v
            self._ema_step_s = dt / n if self._ema_step_s is None \
                else 0.7 * self._ema_step_s + 0.3 * dt / n
        inst = _serving_instruments()
        if inst is not None:
            inst.decode_steps.inc(n)
            if sampled_step:
                inst.sampled_steps.inc(n)
            inst.tokens.inc(emitted)
            inst.tpot.observe(dt / n)
            if sampled:
                inst.sampled_tokens.inc(sampled)

    def _spec_step(self, active):
        """Speculative tick: the draft proposes ``spec_k`` tokens
        (that many single draft steps), the target scores all
        ``spec_k + 1`` positions in ONE verify call, and the longest
        greedy-matching prefix is accepted plus the target's own
        correction token — 1..k+1 tokens per sequence per tick for
        one target pass. Rejected K/V rows need no rollback: they sit
        masked behind each slot's position until overwritten. Returns
        what emits the round's tokens, None where no sequence is left
        to step."""
        k = self.spec_k
        C = k + 1
        t0 = self._clock()
        with _span('eng.tick.page_faults'):
            active = self._page_faults(active, lookahead=k)
        if not active:
            return None
        with _span('eng.tick.build_inputs'):
            inputs = onp.zeros((self.slots, C), 'int32')
            positions = onp.zeros(self.slots, 'int32')
            for slot, seq in active.items():
                inputs[slot, 0] = seq.last_token
                positions[slot] = seq.pos
            tables = self._pages.tables(
                self.slots, ((slot, seq.pages)
                             for slot, seq in active.items()))
        cur = inputs[:, 0].copy()
        for c in range(1, C):
            # coupled (shared-noise) proposals: the draft samples its
            # proposal for absolute position pos + c - 1 with the SAME
            # key the verify pass burns there, so under agreement it
            # proposes exactly the token the target would sample — the
            # greedy longest-prefix acceptance walk then preserves the
            # 1 + k*r win for sampled traffic without biasing the
            # output (every emitted token is the target's own draw)
            self._draft_cache, dtoks, _dl = self._device(
                self._draft.run_step, self._draft_cache, cur,
                positions + (c - 1),
                **self._sampling_extras(active, off=c - 1))
            cur = onp.asarray(dtoks, 'int32').copy()
            inputs[:, c] = cur
        # feed the LAST proposal too (its output is discarded):
        # a fully-accepted round advances pos past pos+k, so this
        # is the only chance to write that draft KV row — skipping
        # it leaves a permanent zero-row hole every later proposal
        # attends (for shorter acceptances the row is masked and
        # overwritten later, harmless)
        self._draft_cache, _dt, _dl = self._device(
            self._draft.run_step, self._draft_cache, cur,
            positions + k)
        vtoks, _logits = self._take_cache(self._device(
            self.program.run_verify, self._cache, inputs,
            positions, tables,
            **self._step_extras(active, spec_c=C)))
        return lambda: self._emit_spec_step(active, inputs, vtoks,
                                            self._clock() - t0)

    def _emit_spec_step(self, active, inputs, vtoks, dt):
        """Walk each slot's verified chunk, stream what was accepted,
        book the round."""
        k = self.spec_k
        C = k + 1
        emitted_total = 0
        sampled_total = 0
        accepted_total = 0
        proposed_total = 0
        sampled_step = _samples(active)
        for slot, seq in active.items():
            if seq.stream.done() or seq.stream._cancelled:
                continue            # its proposals were never judged
            proposed_total += k
            # walk the chunk: target token at index c predicts
            # position pos+c+1; the draft's next input is accepted
            # while it matches, and the first mismatch still yields
            # the target's correction token
            emitted = []
            adv = 1
            for c in range(C):
                emitted.append(int(vtoks[slot, c]))
                if c < k and int(inputs[slot, c + 1]) == emitted[-1]:
                    adv += 1
                    continue
                break
            p0 = seq.pos
            seq.pos = p0 + adv
            seq.last_token = emitted[-1]
            accepted_total += adv - 1
            reason = None
            for i, tok in enumerate(emitted):
                self._emit_token(seq, tok)
                emitted_total += 1
                if seq.temperature > 0:
                    sampled_total += 1
                # per-token finish checks at the token's OWN position
                # (p0 + i + 1) — the already-advanced seq.pos would
                # truncate verified tokens near the max_len wall
                if seq.eos_id is not None and tok == seq.eos_id:
                    reason = 'eos'
                elif len(seq.stream.tokens) >= seq.max_new:
                    reason = 'length'
                elif p0 + i + 2 >= self.program.max_len:
                    reason = 'length'
                if reason is not None:
                    break
            if reason is not None:
                seq.stream._finish(reason)
                self._retire(slot, seq, reason)
        with self._lock:
            self._counts['steps'] += 1
            self._counts['step_calls'] += 1
            self._counts['sampled_steps'] += sampled_step
            self._counts['spec_rounds'] += 1
            self._counts['spec_proposed'] += proposed_total
            self._counts['spec_accepted'] += accepted_total
            self._counts['tokens'] += emitted_total
            self._counts['sampled_tokens'] += sampled_total
            self._ema_step_s = dt if self._ema_step_s is None \
                else 0.7 * self._ema_step_s + 0.3 * dt
        inst = _serving_instruments()
        if inst is not None:
            inst.decode_steps.inc()
            if sampled_step:
                inst.sampled_steps.inc()
            inst.tokens.inc(emitted_total)
            inst.tpot.observe(dt)
            inst.spec_proposed.inc(proposed_total)
            inst.spec_accepted.inc(accepted_total)
            if sampled_total:
                inst.sampled_tokens.inc(sampled_total)

    # -- live migration (seqstate export/import) ---------------------------
    #
    # In-flight decode state is a PORTABLE artifact (seqstate.py):
    # export gathers a live sequence's device state to host and seals
    # it into a versioned payload; import lands it in another engine
    # so the destination SKIPS prefill entirely and continues
    # token-bit-identically under greedy. Both run on the worker
    # thread at tick boundaries — the only thread that owns the
    # device cache — via a request queue the public methods block on.

    def _request_migration(self, op, arg, timeout):
        box, ev = {}, threading.Event()
        with self._wake:
            if self._closed:
                raise BatcherClosed('decode engine %r is closed'
                                    % self.name)
            self._migrations.append((op, arg, box, ev))
            self._wake.notify()
        if not ev.wait(timeout):
            raise RequestTimeout(
                'sequence %s not serviced within %r s (worker wedged?)'
                % (op, timeout))
        if 'error' in box:
            raise box['error']
        return box['result']

    def _service_migrations(self):
        """Worker thread: service queued export/import requests at the
        tick boundary (sequences sit exactly on a token boundary, the
        cache reference is stable)."""
        while True:
            with self._lock:
                if not self._migrations:
                    return
                op, arg, box, ev = self._migrations.pop(0)
            try:
                if op == 'export':
                    box['result'] = self._do_export(arg)
                else:
                    box['result'] = self._do_import(arg)
            except Exception as exc:
                box['error'] = exc
            ev.set()

    @staticmethod
    def _sampling_of(seq):
        """The seqstate sampling block — None for greedy sequences,
        keeping pre-sampling payloads byte-identical."""
        if seq.temperature <= 0:
            return None
        return {'temperature': seq.temperature, 'top_p': seq.top_p,
                'seed': seq.seed}

    def _request_id_for(self, stream):
        for rid, s in self._requests.items():
            if s is stream:
                return rid
        return None

    def export_sequence(self, stream, timeout=30.0):
        """Snapshot a live sequence into a ``mxnet_tpu.seqstate.v1``
        payload and retire it here (its stream finishes with
        ``finish_reason='migrated'`` — no error line; the importer
        continues it).

        Paged engines gather the sequence's valid KV rows from the
        pool through its page table; slot engines (RNNLM) read the
        O(1) recurrent slot state; a still-queued sequence exports
        ``cold`` (prompt + budget only) and re-admits through the
        destination's ordinary path. Raises :class:`SeqStateError`
        for a finished/unknown stream, :class:`BatcherClosed` after
        :meth:`close`."""
        cold = None
        with self._lock:
            if self._closed:
                raise BatcherClosed('decode engine %r is closed'
                                    % self.name)
            for i, seq in enumerate(self._pending):
                if seq.stream is stream:
                    cold = self._pending.pop(i)
                    break
            rid = self._request_id_for(stream)
        if cold is not None:
            payload = build_payload(
                'cold', cold.prompt, [], 0, None, cold.max_new,
                eos_id=cold.eos_id, request_id=rid,
                adapter_id=cold.adapter_id,
                sampling=self._sampling_of(cold))
            stream._finish('migrated')
            with self._lock:
                self._counts['migrated_out'] += 1
            _record_event('seq_export', seq_kind='cold',
                          prompt_len=len(cold.prompt), request_id=rid)
            w = time.time()
            self._trace_span(cold, 'eng.export', w, w, kind='cold')
            inst = _serving_instruments()
            if inst is not None:
                inst.sequences_migrated.inc()
            return payload
        return self._request_migration('export', stream, timeout)

    def export_all(self, timeout=30.0):
        """Drain helper: export every in-flight sequence (queued and
        active). Sequences that finish naturally while the drain walks
        the list are skipped — their streams already completed clean.
        Returns the list of payloads."""
        with self._lock:
            streams = [seq.stream for seq in self._pending] \
                + [seq.stream for seq in self._active.values()]
        payloads = []
        for stream in streams:
            try:
                payloads.append(self.export_sequence(stream,
                                                     timeout=timeout))
            except SeqStateError:
                continue            # finished before its export ran
            except BatcherClosed:
                break
        return payloads

    def _do_export(self, stream, stash=False):
        with self._lock:
            found = None
            for slot, seq in self._active.items():
                if seq.stream is stream:
                    found = (slot, seq)
                    break
            rid = self._request_id_for(stream)
        if found is None or stream.done():
            raise SeqStateError(
                'sequence is not live in this engine (finished with '
                '%r or never admitted)' % (stream.finish_reason,))
        slot, seq = found
        t0 = self._clock()
        w0 = time.time()
        npages = 0
        if self.paged:
            ps = self.program.page_size
            npages = pages_for(seq.pos, ps)
            entries = self.program.export_pages(
                self._cache, self._pages.first_pages(seq.pages, seq.pos))
            entries = {k: v[:seq.pos] for k, v in entries.items()}
            payload = build_payload(
                'paged', seq.prompt, list(stream.tokens), seq.pos,
                seq.last_token, seq.max_new, eos_id=seq.eos_id,
                request_id=rid, page_size=ps, entries=entries,
                adapter_id=seq.adapter_id,
                sampling=self._sampling_of(seq))
        else:
            entries = self.program.export_slot_state(self._cache, slot)
            payload = build_payload(
                'slot', seq.prompt, list(stream.tokens), seq.pos,
                seq.last_token, seq.max_new, eos_id=seq.eos_id,
                request_id=rid, entries=entries,
                adapter_id=seq.adapter_id,
                sampling=self._sampling_of(seq))
        # the stream ends HERE, cleanly: 'migrated' is not an error
        # (the server's done line carries it; the gateway splices the
        # destination's continuation into the same client stream).
        # stash the payload BEFORE _finish: the done event wakes the
        # consumer, which must observe stream.seqstate
        if stash:
            stream.seqstate = payload
        stream._finish('migrated')
        self._retire(slot, seq, 'migrated')
        with self._lock:
            self._counts['migrated_out'] += 1
            self._counts['handoff_pages'] += npages
        dt = self._clock() - t0
        inst = _serving_instruments()
        if inst is not None:
            inst.sequences_migrated.inc()
            inst.migration_seconds.observe(dt)
            if npages:
                inst.handoff_pages.inc(npages)
        _record_event('seq_export', seq_kind=payload['kind'], slot=slot,
                      pos=int(seq.pos), tokens=len(stream.tokens),
                      pages=npages, request_id=rid)
        self._trace_span(seq, 'eng.export', w0, time.time(),
                         pages=npages, kind=payload['kind'])
        return payload

    def import_sequence(self, payload, timeout=30.0, trace=None):
        """Land an exported sequence in THIS engine and continue it —
        no prefill runs (the ``prefills`` counter is untouched): KV
        rows are re-chunked to this engine's page size and written via
        ``write_prefill_pages``; slot state lands via ``write_slot``.
        Returns the continuation :class:`GenerateStream` whose
        iterator yields only the NEW tokens (``stream.tokens`` holds
        the full sequence including the handed-off prefix).

        Raises :class:`SeqStateError` for torn/version-mismatched/
        incompatible payloads, :class:`BackpressureError` when no
        slot/pages are available, :class:`BatcherClosed` after
        :meth:`close`."""
        if self.paged:
            self.program._one_page_list('import_sequence')
        state = decode_payload(payload)
        state['trace'] = trace
        # a pinned adapter must land in an engine that can CONTINUE
        # it exactly — never silently under the base weights
        if state['adapter_id'] is not None and self._adapters is None:
            raise SeqStateError(
                'payload pins adapter %r but this engine serves no '
                'adapter pool' % (state['adapter_id'],))
        if state['kind'] == 'cold':
            # never prefilled at the source: ordinary admission
            samp = state['sampling'] or {}
            return self.generate(state['prompt'],
                                 max_new_tokens=state['max_new'],
                                 eos_id=state['eos_id'],
                                 request_id=state['request_id'],
                                 adapter=state['adapter_id'],
                                 temperature=samp.get('temperature',
                                                      0.0),
                                 top_p=samp.get('top_p', 1.0),
                                 seed=samp.get('seed', 0),
                                 trace=trace)
        if state['kind'] == 'paged' and not self.paged:
            raise SeqStateError('paged seqstate cannot land in a '
                                'slot-cache engine')
        if state['kind'] == 'slot' and self.paged:
            raise SeqStateError('slot seqstate cannot land in a '
                                'paged engine')
        if state['pos'] + 1 >= self.program.max_len:
            raise SeqStateError(
                'sequence at pos=%d does not fit this engine '
                '(max_len=%d)' % (state['pos'], self.program.max_len))
        if self.paged and pages_for(state['pos'] + 1,
                                    self.program.page_size) \
                > self.program.max_pages:
            raise SeqStateError(
                'sequence needs more pages than this engine maps per '
                'sequence (max_pages=%d)' % self.program.max_pages)
        return self._request_migration('import', state, timeout)

    def _do_import(self, state):
        t0 = self._clock()
        w0 = time.time()
        prompt, emitted = state['prompt'], state['emitted']
        pos = state['pos']
        with self._lock:
            if not self._free:
                raise BackpressureError(len(self._pending),
                                        self.max_queue)
            slot = self._free.pop(0)
        rec = None
        npages = 0
        aidx = 0
        try:
            if state['adapter_id'] is not None:
                # re-pin the SAME adapter before any device writes; a
                # warm pool row is a refcount bump, a cold one uploads
                try:
                    aidx = self._adapters.acquire(state['adapter_id'])
                except BackpressureError:
                    raise
                except Exception as exc:
                    raise SeqStateError(
                        'cannot re-pin adapter %r at import: %s'
                        % (state['adapter_id'], exc))
            if self._cache is None:
                self._rebuild_cache()
            if self.paged:
                ps = self.program.page_size
                npages = pages_for(pos, ps)
                rec = self._pages.open(slot)
                ids = self._pages.place(rec, pos)
                if ids is None:
                    with self._lock:
                        self._counts['pool_exhausted'] += 1
                        depth = len(self._pending)
                    raise BackpressureError(depth, self.max_queue)
                # re-chunk to THIS engine's page geometry: the rows
                # are page-size-free, only the zero tail padding to
                # whole pages differs (zeros = the pool's init state;
                # masked until overwritten)
                rows = {}
                for name, arr in state['arrays'].items():
                    pad = onp.zeros((npages * ps - pos,)
                                    + arr.shape[1:], arr.dtype)
                    rows[name] = onp.concatenate([arr, pad], axis=0)
                try:
                    self._cache = self.program.import_pages(
                        self._cache, rows, ids)
                except ValueError as exc:
                    raise SeqStateError(
                        'seqstate incompatible with this engine: %s'
                        % (exc,))
            else:
                try:
                    self._cache = self.program.import_slot_state(
                        self._cache, state['arrays'], slot)
                except ValueError as exc:
                    raise SeqStateError(
                        'seqstate incompatible with this engine: %s'
                        % (exc,))
        except BaseException:
            with self._lock:
                if rec is not None:
                    self._pages.drop(rec)
                self._free.append(slot)
            if aidx:
                self._adapters.release(aidx)
            raise
        now = self._clock()
        stream = GenerateStream(len(prompt))
        stream._outbox = self._outbox
        # already streamed by the SOURCE engine: the full token list
        # stays intact (finish budgets, done-line tokens) while the
        # iterator yields only the continuation
        stream.tokens = list(emitted)
        samp = state['sampling'] or {}
        seq = _Seq(stream, prompt, state['max_new'], state['eos_id'],
                   now, now + self.timeout_s if self.timeout_s
                   else None, adapter_id=state['adapter_id'],
                   temperature=samp.get('temperature', 0.0),
                   top_p=samp.get('top_p', 1.0),
                   seed=samp.get('seed', 0))
        seq.adapter_idx = aidx
        seq.slot = slot
        seq.pos = pos
        seq.last_token = state['last_token']
        if emitted:
            seq.first_token_at = now
        if self.paged:
            seq.pages = rec
            if pos >= len(prompt):
                # re-register the prompt so future shared-prefix
                # admissions hit (one ref per newly registered page,
                # exactly the admit-path contract)
                self._pages.register(prompt, ids,
                                     namespace=seq.adapter_id)
            if self._draft is not None:
                # re-sync the draft from the fed context; a failure
                # only lowers speculative acceptance (greedy verify
                # keeps emitted tokens exactly target-greedy)
                context = (prompt + emitted)[:pos]
                try:
                    self._draft_cache, _dt, _dl = \
                        self._draft.run_prefill(
                            self._draft_cache,
                            onp.asarray(context, 'int32'), slot)
                except Exception:
                    logging.warning(
                        'decode %s: draft re-sync failed on import; '
                        'speculation degrades to low acceptance',
                        self.name)
        tctx = state.get('trace')
        if tctx is not None:
            w1 = time.time()
            seq.trace = {'ctx': tctx, 'enq': w0, 'last': w1,
                         'first_w': w1 if emitted else None,
                         'tok0': len(emitted)}
            self._trace_span(seq, 'eng.import', w0, w1,
                             pages=npages, kind=state['kind'],
                             tokens=len(emitted))
        rid = state['request_id']
        superseded = None
        with self._lock:
            self._counts['requests'] += 1
            self._counts['migrated_in'] += 1
            self._counts['handoff_pages'] += npages
            if rid is not None:
                superseded = self._requests.get(rid)
                self._requests[rid] = stream
            self._active[slot] = seq
        if superseded is not None and not superseded.done():
            superseded.cancel()    # at-most-once per request_id
        dt = self._clock() - t0
        inst = _serving_instruments()
        if inst is not None:
            inst.migration_seconds.observe(dt)
            if npages:
                inst.handoff_pages.inc(npages)
        _record_event('seq_import', seq_kind=state['kind'], slot=slot,
                      pos=int(pos), tokens=len(emitted), pages=npages,
                      request_id=rid)
        return stream

    # -- degraded completion -----------------------------------------------

    def _fallback_complete(self, seq):
        """Finish one sequence start-to-finish (or from wherever it
        got to) on the CPU fallback. Same greedy math (or the same
        (seed, position)-keyed sampling law, adapter delta applied
        host-side) -> same tokens."""
        if seq.stream.done():
            return
        remaining = seq.max_new - len(seq.stream.tokens)
        room = self.program.max_len - (len(seq.prompt)
                                       + len(seq.stream.tokens)) - 1
        remaining = min(remaining, max(0, room) + 1)
        try:
            ad = None
            if self._adapters is not None and seq.adapter_id is not None:
                ad = self._adapters.host_tree(seq.adapter_id)
            toks = self.program.fallback_generate(
                seq.prompt + seq.stream.tokens, remaining, seq.eos_id,
                temperature=seq.temperature, top_p=seq.top_p,
                seed=seq.seed, ad=ad)
        except Exception as exc:     # fallback itself failed: typed
            seq.stream._finish('error', exc)
            return
        seq.stream.degraded = True
        with self._lock:
            self._counts['fallback_tokens'] += len(toks)
            self._counts['tokens'] += len(toks)
        inst = _serving_instruments()
        if inst is not None:
            inst.fallbacks.inc()
            inst.tokens.inc(len(toks))
        for i, tok in enumerate(toks):
            if seq.first_token_at is None:
                seq.first_token_at = self._clock()
                if inst is not None:
                    inst.ttft.observe(max(
                        0.0, seq.first_token_at - seq.enqueued_at))
            seq.stream._emit(tok)
            if seq.eos_id is not None and tok == seq.eos_id:
                seq.stream._finish('eos')
                return
        seq.stream._finish('length')

    def _spawn_fallback(self, seqs):
        """Degraded completions run OFF the scheduler thread: the CPU
        fallback decodes un-jitted at a couple hundred ms per token,
        and serializing that into the worker loop would stall
        admissions and every healthy slot behind one trip — the
        availability hole the chaos soak measures. The scheduler
        retires the slots, rebuilds the cache, and keeps serving at
        device speed while this thread finishes the degraded work."""
        # the fallback thread puts directly: what this thread emitted
        # for these streams goes first
        self._outbox.flush()

        def _complete():
            for seq in seqs:
                self._fallback_complete(seq)

        th = threading.Thread(target=_complete, daemon=True,
                              name='mxnet-tpu-%s-fallback' % self.name)
        with self._lock:
            self._fallback_threads = [
                t for t in self._fallback_threads if t.is_alive()]
            self._fallback_threads.append(th)
        th.start()

    def _degrade_inflight(self, active):
        """Breaker tripped mid-decode: every in-flight sequence
        completes degraded on the CPU fallback; the accelerator cache
        is rebuilt when the breaker lets traffic through again."""
        for slot, seq in active.items():
            self._retire(slot, seq, 'degraded')
        # donated cache buffers are unusable after a failed call;
        # start clean when the accelerator comes back (paged: the
        # allocator + prefix registry describe garbage — reset too)
        self._rebuild_cache()
        self._spawn_fallback(list(active.values()))

    # -- introspection / lifecycle -----------------------------------------

    def retry_after_hint(self):
        """Estimated seconds until a newly admitted generation could
        get a slot: pending requests ahead x the per-sequence service
        time (default generation budget x recent step latency) spread
        over the slot pool. Basis for Retry-After on 429s."""
        with self._lock:
            pending = len(self._pending)
            est = self._ema_step_s
        if est is None:
            est = 0.02
        per_seq = est * max(1, self.default_max_new)
        return max(0.05, (pending + 1) * per_seq
                   / float(max(1, self.slots)))

    def cache_accounting(self):
        """Pool-bytes accounting (docs/SERVING.md): the REAL device
        residency plus per-sequence amortized bytes — the slot
        cache's ``slots × max_len`` figure overstated residency for
        every sequence shorter than max_len."""
        prog = self.program
        out = {'paged': self.paged}
        cache_bytes = getattr(prog, 'cache_bytes', None)
        if callable(cache_bytes):
            out['cache_bytes'] = int(cache_bytes())
        per_seq = getattr(prog, 'per_sequence_bytes', None)
        if callable(per_seq):
            out['per_sequence_bytes_max'] = int(per_seq())
        if self.paged:
            with self._lock:
                pool = self._pages.pool_stats()
                live = len(self._active)
                held = self._pages.held_bytes(
                    s.pages for s in self._active.values())
            out['pool'] = pool
            page_bytes = getattr(prog, 'page_bytes', None)
            if callable(page_bytes):
                pb = int(page_bytes())
                out['page_bytes'] = pb
                # amortized: what the CURRENT live population actually
                # holds, per sequence (falls back to one page when
                # idle — the floor a new sequence costs)
                amort = held // live if live else pb
                out['per_sequence_bytes_amortized'] = int(amort)
                if amort:
                    out['max_concurrent_sequences_per_gb'] = \
                        int((1 << 30) // amort)
        elif 'per_sequence_bytes_max' in out \
                and out['per_sequence_bytes_max']:
            out['per_sequence_bytes_amortized'] = \
                out['per_sequence_bytes_max']
            out['max_concurrent_sequences_per_gb'] = \
                int((1 << 30) // out['per_sequence_bytes_max'])
        return out

    def stats(self):
        with self._lock:
            out = {
                'pending': len(self._pending),
                'active': len(self._active),
                'free_slots': len(self._free),
                'slots': self.slots,
                'degraded': self._degraded,
                'breaker': self._breaker.state,
                'error': self._last_error,
                'counts': {k: (dict(v) if isinstance(v, dict) else v)
                           for k, v in self._counts.items()},
                'closed': self._closed,
                'paged': self.paged,
            }
            if self._pages is not None:
                out.update(self._pages.stats())
                out['counts'].update(self._pages.live_gauges())
            out['counts'].update(_spans.gc_counts())
            if self._draft is not None:
                proposed = self._counts['spec_proposed']
                out['spec'] = {
                    'k': self.spec_k,
                    'proposed': proposed,
                    'accepted': self._counts['spec_accepted'],
                    'acceptance_rate': round(
                        self._counts['spec_accepted'] / proposed, 4)
                    if proposed else None,
                }
        if self._adapters is not None:
            out['adapters'] = self._adapters.pool.stats()
        out['cache'] = self.cache_accounting()
        return out

    def close(self, drain=True, timeout=30.0):
        """Stop admissions; ``drain=True`` lets in-flight AND queued
        generations finish, ``drain=False`` fails them with
        :class:`BatcherClosed`.

        A drain is BOUNDED: when ``timeout`` expires with work still
        in flight (a wedged device call, a stream that cannot make
        progress), the leftover streams fail typed with
        :class:`DrainTimeout` and their slots/pages free — close never
        returns with streams silently blocking forever."""
        with self._lock:
            self._closed = True
            if not drain:
                for seq in self._pending:
                    seq.stream._finish('closed', BatcherClosed(
                        'decode engine closed'))
                self._pending = []
                for seq in self._active.values():
                    seq.stream._finish('closed', BatcherClosed(
                        'decode engine closed'))
            self._wake.notify_all()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not self._pending and not self._active:
                    break
            time.sleep(0.01)
        leftovers = []
        with self._lock:
            if drain and (self._pending or self._active):
                leftovers = list(self._pending)
                self._pending = []
                for slot, seq in list(self._active.items()):
                    leftovers.append(seq)
                    del self._active[slot]
                    self._free.append(slot)
                    if seq.pages is not None:
                        self._pages.drop(seq.pages)
                self._counts['drain_timeouts'] += len(leftovers)
            # migration requests the worker will never service now
            orphans = list(self._migrations)
            self._migrations = []
        for seq in leftovers:
            self._release_adapter(seq)
            seq.stream._finish('error', DrainTimeout(
                'stream unfinished after the %.1fs drain budget '
                '(%d tokens emitted)'
                % (timeout, len(seq.stream.tokens))))
            _record_event('drain_timeout',
                          tokens=len(seq.stream.tokens))
        for _op, _arg, box, ev in orphans:
            box['error'] = BatcherClosed(
                'decode engine %r closed before the migration was '
                'serviced' % self.name)
            ev.set()
        self._worker.join(max(0.1, deadline - time.monotonic()))
        # degraded completions run off-worker; drain waits for them
        # too (zero-hang: no stream left mid-fallback at close)
        with self._lock:
            fallbacks = list(self._fallback_threads)
        if drain:
            for th in fallbacks:
                th.join(max(0.1, deadline - time.monotonic()))
        with self._lock:
            watched, self._gc_watched = self._gc_watched, False
        if watched:
            _spans.gc_watch(False)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
