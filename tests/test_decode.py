"""Autoregressive decode engine tests (docs/SERVING.md
"Autoregressive decoding"): slot-cache math, cached-decode
bit-identity against the whole-sequence forward, the
(prefill ladder + 1) compile bound with zero retraces after warmup,
frozen decode artifacts, continuous-batching invariants (FIFO
admission, join/leave isolation, EOS/max-len/timeout retirement,
typed admission control), the gluon RNN-LM adapter, and the degraded
CPU-fallback completion path."""
import json
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.serving.batcher import (BackpressureError, BatcherClosed,
                                       RequestTimeout)
from mxnet_tpu.serving.decode import (CacheSpec, DecodeEngine,
                                      DecodeProgram, cache_bytes,
                                      freeze_decode, init_cache,
                                      init_rnn_lm, init_transformer_lm,
                                      load_decode, write_position,
                                      write_slot)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _greedy_reference(model, params, prompt, n):
    """Greedy tokens by re-running the UNCACHED whole-sequence forward
    after every token and slicing its last position."""
    import jax.numpy as jnp
    dev = {k: jnp.asarray(v) for k, v in params.items()}
    toks = list(prompt)
    out, logits = [], []
    for _ in range(n):
        full = np.asarray(model.full_forward(
            dev, jnp.asarray([toks], 'int32')))
        lg = full[0, -1]
        t = int(lg.argmax())
        out.append(t)
        logits.append(lg)
        toks.append(t)
    return out, logits


def _cached_decode(prog, prompt, n, slot=0):
    """Greedy tokens through the prefill + decode-step programs."""
    cache = prog.new_cache()
    cache, tok, lg = prog.run_prefill(cache, prompt, slot)
    toks, logits = [tok], [lg]
    pos = len(prompt)
    last = tok
    for _ in range(n - 1):
        tk = np.zeros(prog.slots, 'int32')
        ps = np.zeros(prog.slots, 'int32')
        tk[slot] = last
        ps[slot] = pos
        cache, out, lgs = prog.run_step(cache, tk, ps)
        last = int(out[slot])
        pos += 1
        toks.append(last)
        logits.append(lgs[slot])
    return toks, logits


# ---------------------------------------------------------------------------
# cache math
# ---------------------------------------------------------------------------

def test_cache_spec_round_trip_and_footprint():
    spec = CacheSpec({'k': ((16, 8), 'float32'),
                      'h': ((2, 4), 'float32')})
    again = CacheSpec.from_json(json.loads(json.dumps(spec.to_json())))
    assert again.entries == spec.entries
    assert spec.full_shape('k', 4) == (4, 16, 8)
    assert cache_bytes(spec, 4) == 4 * (16 * 8 + 2 * 4) * 4


def test_cache_write_slot_touches_only_that_slot():
    spec = CacheSpec({'h': ((2, 3), 'float32')})
    cache = init_cache(spec, 4)
    state = np.arange(6, dtype='float32').reshape(2, 3)
    out = np.asarray(write_slot(cache['h'], state, 2))
    assert np.array_equal(out[2], state)
    for s in (0, 1, 3):
        assert not out[s].any()


def test_cache_write_position_per_slot_positions():
    spec = CacheSpec({'k': ((5, 2), 'float32')})
    cache = init_cache(spec, 3)
    rows = np.arange(6, dtype='float32').reshape(3, 2)
    out = np.asarray(write_position(cache['k'], rows,
                                    np.array([0, 3, 4], 'int32')))
    assert np.array_equal(out[0, 0], rows[0])
    assert np.array_equal(out[1, 3], rows[1])
    assert np.array_equal(out[2, 4], rows[2])
    assert np.count_nonzero(out) == np.count_nonzero(rows)


# ---------------------------------------------------------------------------
# cached decode == whole-sequence forward (per family)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('mode', ['lstm', 'gru'])
def test_rnn_cached_decode_matches_full_forward(mode):
    model, params = init_rnn_lm(vocab=19, embed=8, hidden=12, layers=2,
                                mode=mode, max_len=32)
    prog = DecodeProgram(model, params, slots=3,
                         prefill_buckets=(4, 8))
    prompt = [3, 1, 4, 1, 5]
    ref_toks, ref_logits = _greedy_reference(model, params, prompt, 6)
    got_toks, got_logits = _cached_decode(prog, prompt, 6, slot=1)
    # the decode OUTPUT — the token stream — is bit-identical
    assert got_toks == ref_toks
    # logits agree to float32 precision (XLA tiles gemms differently
    # per program shape, so exact logit bits across different-shaped
    # programs are not promised — tokens are)
    for a, b in zip(got_logits, ref_logits):
        assert np.allclose(a, b, atol=1e-5)


def test_transformer_cached_decode_matches_full_forward():
    model, params = init_transformer_lm(vocab=19, units=16, hidden=24,
                                        layers=2, heads=4, max_len=32)
    prog = DecodeProgram(model, params, slots=3,
                         prefill_buckets=(4, 8))
    prompt = [7, 2, 9]
    ref_toks, ref_logits = _greedy_reference(model, params, prompt, 6)
    got_toks, got_logits = _cached_decode(prog, prompt, 6, slot=2)
    assert got_toks == ref_toks
    for a, b in zip(got_logits, ref_logits):
        assert np.allclose(a, b, atol=1e-5)


def test_fallback_generate_bit_identical_to_accel_path():
    model, params = init_rnn_lm(vocab=19, embed=8, hidden=12, layers=1,
                                mode='lstm', max_len=32)
    prog = DecodeProgram(model, params, slots=2, prefill_buckets=(8,))
    prompt = [2, 4, 6]
    accel, _ = _cached_decode(prog, prompt, 7)
    assert prog.fallback_generate(prompt, 7) == accel


# ---------------------------------------------------------------------------
# compile bound + zero retrace
# ---------------------------------------------------------------------------

def test_compile_bound_prefill_ladder_plus_one():
    model, params = init_rnn_lm(vocab=19, embed=8, hidden=12, layers=1,
                                mode='gru', max_len=64)
    prog = DecodeProgram(model, params, slots=4,
                         prefill_buckets=(2, 4, 8, 16))
    # mixed prompt lengths, many generations
    for i, plen in enumerate([1, 3, 8, 2, 15, 4, 1, 16, 7]):
        _cached_decode(prog, list(range(1, plen + 1)), 4,
                       slot=i % prog.slots)
    assert prog.compile_count <= len(prog.prefill_buckets) + 1
    # every program traced exactly once: zero retraces after warmup
    assert all(v == 1 for v in prog.trace_counts.values()), \
        prog.trace_counts
    assert 'step' in prog.trace_counts


def test_frozen_decode_round_trip_same_tokens_no_trace(tmp_path):
    model, params = init_rnn_lm(vocab=19, embed=8, hidden=12, layers=1,
                                mode='lstm', max_len=32)
    prog = DecodeProgram(model, params, slots=2,
                         prefill_buckets=(4, 8)).warmup()
    prompt = [5, 3, 1]
    want, _ = _cached_decode(prog, prompt, 5)
    art = str(tmp_path / 'decoder.frozen')
    prog.save(art)
    again = load_decode(art)
    assert again.slots == 2
    assert tuple(again.prefill_buckets) == (4, 8)
    got, _ = _cached_decode(again, prompt, 5)
    assert got == want
    # executables deserialized: serving never traced python
    assert again.trace_counts == {}
    assert again.retraced_buckets == []
    # load_frozen dispatches on the manifest kind
    assert isinstance(serving.load_frozen(art), DecodeProgram)


def test_frozen_decode_rejects_wrong_kind(tmp_path):
    art = str(tmp_path / 'bogus')
    os.makedirs(art)
    with open(os.path.join(art, 'MANIFEST.json'), 'w') as f:
        json.dump({'schema': serving.FROZEN_SCHEMA, 'kind': 'nope'}, f)
    with pytest.raises(ValueError):
        load_decode(art)


def test_prompt_longer_than_ladder_rejects_typed():
    model, params = init_rnn_lm(vocab=19, embed=8, hidden=12, layers=1,
                                mode='lstm', max_len=32)
    prog = DecodeProgram(model, params, slots=2, prefill_buckets=(4,))
    with serving.InferenceSession(prog, watchdog=False) as sess:
        with pytest.raises(ValueError):
            sess.generate(list(range(9)), max_new_tokens=2)


# ---------------------------------------------------------------------------
# continuous-batching invariants (fake program: pure scheduler math)
# ---------------------------------------------------------------------------

class _FakeProgram:
    """Deterministic per-sequence token source: slot-local state only,
    so any cross-sequence interference is detectable. Token stream for
    a prompt p: (sum(p)*31 + i) % 97 for i = 1, 2, 3, ..."""

    def __init__(self, slots=4, max_len=64, max_prompt=16,
                 fail_ops=()):
        self.slots = slots
        self.max_len = max_len
        self._max_prompt = max_prompt
        self.prefills = 0
        self.steps = 0
        self.fallbacks = 0
        self._fail_ops = set(fail_ops)   # op indices that raise
        self._op = 0

    def max_prompt_len(self):
        return self._max_prompt

    def new_cache(self):
        return {'seed': np.zeros(self.slots, 'int64'),
                'i': np.zeros(self.slots, 'int64')}

    def _maybe_fail(self):
        op = self._op
        self._op += 1
        if op in self._fail_ops:
            from mxnet_tpu.resilience.policy import DeviceLossError
            raise DeviceLossError('device_loss', 'serving.decode')

    @staticmethod
    def _tok(seed, i):
        return int((seed * 31 + i) % 97)

    def run_prefill(self, cache, tokens, slot):
        self._maybe_fail()
        self.prefills += 1
        cache = {k: v.copy() for k, v in cache.items()}
        cache['seed'][slot] = int(np.sum(tokens))
        cache['i'][slot] = 1
        return cache, self._tok(cache['seed'][slot], 1), None

    def run_step(self, cache, tokens, positions):
        self._maybe_fail()
        self.steps += 1
        cache = {k: v.copy() for k, v in cache.items()}
        cache['i'] += 1
        toks = np.array([self._tok(cache['seed'][s], cache['i'][s])
                         for s in range(self.slots)], 'int32')
        return cache, toks, None

    def fallback_generate(self, tokens, max_new, eos_id=None,
                          temperature=0.0, top_p=1.0, seed=0,
                          ad=None):
        self.fallbacks += 1
        # `tokens` is prompt + already-generated; re-find the prompt
        # boundary by replaying the deterministic stream (shortest
        # prompt wins — unambiguous for the prompts these tests use)
        for cut in range(1, len(tokens) + 1):
            seed = int(np.sum(tokens[:cut]))
            stream = [self._tok(seed, i + 1)
                      for i in range(len(tokens) - cut)]
            if list(tokens[cut:]) == stream:
                done = len(stream)
                out = []
                for j in range(max_new):
                    tok = self._tok(seed, done + j + 1)
                    out.append(tok)
                    if eos_id is not None and tok == eos_id:
                        break
                return out
        raise AssertionError('unreachable: token tail not a stream')


def _expected(prompt, n):
    seed = int(np.sum(prompt))
    return [int((seed * 31 + i) % 97) for i in range(1, n + 1)]


def test_engine_streams_and_retires_on_length():
    eng = DecodeEngine(_FakeProgram(), timeout_s=10.0)
    try:
        s = eng.generate([1, 2, 3], max_new_tokens=5)
        assert list(s) == _expected([1, 2, 3], 5)
        assert s.finish_reason == 'length'
        assert s.result(5) == _expected([1, 2, 3], 5)
        st = eng.stats()
        assert st['active'] == 0 and st['free_slots'] == 4
    finally:
        eng.close()


def test_outbox_holds_the_scheduler_threads_tokens_and_no_other_threads():
    """A stream's list of tokens and its end are up to date at once; its
    reader is woken when the outbox is flushed, in order. Another thread
    (the reaper, close, the CPU fallback) puts directly."""
    from mxnet_tpu.serving.decode.engine import GenerateStream, _Outbox
    box, s = _Outbox(), GenerateStream(3)
    s._outbox = box
    box.owner = threading.get_ident()
    s._emit(5)
    s._emit(6)
    s._finish('length')
    assert s.tokens == [5, 6] and s.done() and s.result(0) == [5, 6]
    assert s._q.empty() and len(box.items) == 3
    box.flush()
    assert list(s) == [5, 6] and not box.items and box.flushed
    other = GenerateStream(3)
    other._outbox = box
    th = threading.Thread(target=other._emit, args=(7,))
    th.start()
    th.join()
    assert other._q.get_nowait() == 7 and not box.items


def test_engine_hands_tokens_over_once_the_next_program_is_enqueued():
    """A program that, like ``DecodeProgram._call``, runs its thread's
    ``while_device_runs.hook`` between enqueueing and reading: the token a
    call produced is in ``stream.tokens`` when the call returns and reaches
    the stream's reader inside the NEXT call, while the device would be
    busy; the last ones when the engine falls idle. The order holds."""
    from mxnet_tpu.serving.decode.program import while_device_runs
    gate = threading.Event()

    class Program(_FakeProgram):
        watch, seen = None, []

        def _enqueued(self):
            gate.wait(10)
            s = self.watch
            before = s._q.qsize()
            while_device_runs.hook()
            self.seen.append((len(s.tokens), before, s._q.qsize()))

        def run_prefill(self, cache, tokens, slot):
            self._enqueued()
            return super().run_prefill(cache, tokens, slot)

        def run_step(self, cache, tokens, positions):
            self._enqueued()
            return super().run_step(cache, tokens, positions)

    prog = Program()
    eng = DecodeEngine(prog, timeout_s=10.0)
    try:
        s = eng.generate([1, 2, 3], max_new_tokens=5)
        prog.watch = s
        gate.set()
        assert s.result(10) == _expected([1, 2, 3], 5)
        # one prefill and four steps: each call finds the tokens of the
        # calls before it emitted, the last of them not yet handed over
        assert prog.seen == [(0, 0, 0)] + [(n, n - 1, n)
                                           for n in range(1, 5)]
        assert list(s) == _expected([1, 2, 3], 5)     # idle: all there
    finally:
        eng.close()


def test_engine_eos_retires_early():
    prompt = [4, 1]
    eos = _expected(prompt, 3)[2]
    eng = DecodeEngine(_FakeProgram(), timeout_s=10.0)
    try:
        s = eng.generate(prompt, max_new_tokens=50, eos_id=eos)
        assert s.result(5) == _expected(prompt, 3)
        assert s.finish_reason == 'eos'
    finally:
        eng.close()


def test_engine_request_id_readmission_supersedes():
    """Idempotent re-admission (gateway mid-stream failover): a second
    generate under the same request_id becomes the id's live stream
    and the superseded one retires at its next token boundary —
    at-most-once engine-side."""
    eng = DecodeEngine(_FakeProgram(), timeout_s=10.0)
    try:
        first = eng.generate([1, 2, 3], max_new_tokens=40,
                             request_id='gw1-1')
        second = eng.generate([1, 2, 3, 4], max_new_tokens=5,
                              request_id='gw1-1')
        assert eng._requests['gw1-1'] is second
        assert second.result(10) == _expected([1, 2, 3, 4], 5)
        first.result(10)
        # cancelled at a token boundary, or already finished — never
        # left running as a zombie under the same id
        assert first.finish_reason in ('cancelled', 'length')
        # distinct ids stay independent
        third = eng.generate([2, 2], max_new_tokens=3,
                             request_id='gw1-2')
        assert third.result(10) == _expected([2, 2], 3)
        assert second.finish_reason == 'length'
    finally:
        eng.close()


def test_engine_join_leave_isolation_and_slot_reuse():
    """Sequences joining/leaving mid-stream never perturb the others,
    and more sequences than slots complete by reusing retired slots."""
    prog = _FakeProgram(slots=2)
    eng = DecodeEngine(prog, timeout_s=30.0)
    try:
        prompts = [[i, i + 1] for i in range(1, 7)]   # 6 seqs, 2 slots
        lens = [3, 7, 2, 5, 1, 4]
        streams = [eng.generate(p, max_new_tokens=n)
                   for p, n in zip(prompts, lens)]
        for st, p, n in zip(streams, prompts, lens):
            assert st.result(20) == _expected(p, n), \
                'sequence %r perturbed' % (p,)
    finally:
        eng.close()


def test_engine_max_len_bounds_generation():
    prog = _FakeProgram(slots=2, max_len=6, max_prompt=4)
    eng = DecodeEngine(prog, timeout_s=10.0)
    try:
        s = eng.generate([1, 1, 1], max_new_tokens=50)   # room for 3
        toks = s.result(10)
        assert toks == _expected([1, 1, 1], 3)
        assert s.finish_reason == 'length'
    finally:
        eng.close()


def test_engine_backpressure_typed_and_immediate():
    class _Stuck(_FakeProgram):
        def __init__(self):
            super().__init__(slots=1)
            self.gate = threading.Event()

        def run_prefill(self, cache, tokens, slot):
            self.gate.wait(30)
            return super().run_prefill(cache, tokens, slot)

    prog = _Stuck()
    eng = DecodeEngine(prog, max_queue=2, timeout_s=30.0)
    try:
        streams = [eng.generate([1], max_new_tokens=1)]
        deadline = time.monotonic() + 5.0
        while eng.stats()['pending'] and time.monotonic() < deadline:
            time.sleep(0.002)     # worker now blocked inside prefill
        streams += [eng.generate([1], max_new_tokens=1)
                    for _ in range(2)]    # fill the bounded queue
        t0 = time.monotonic()
        with pytest.raises(BackpressureError) as exc:
            eng.generate([1], max_new_tokens=1)
        assert time.monotonic() - t0 < 1.0
        assert exc.value.limit == 2
    finally:
        prog.gate.set()
        eng.close(drain=False)


def test_engine_timeout_frees_slot_and_types_error():
    class _Slow(_FakeProgram):
        def run_step(self, cache, tokens, positions):
            time.sleep(0.05)
            return super().run_step(cache, tokens, positions)

    eng = DecodeEngine(_Slow(slots=1), timeout_s=0.3)
    try:
        s = eng.generate([1, 2], max_new_tokens=10 ** 6)
        with pytest.raises(RequestTimeout):
            s.result(10)
        assert s.finish_reason == 'error'
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if eng.stats()['free_slots'] == 1:
                break
            time.sleep(0.01)
        assert eng.stats()['free_slots'] == 1   # slot retired
        assert eng.stats()['counts']['timeouts'] >= 1
    finally:
        eng.close(drain=False)


def test_engine_cancel_retires_mid_stream():
    class _Slow(_FakeProgram):
        def run_step(self, cache, tokens, positions):
            time.sleep(0.02)
            return super().run_step(cache, tokens, positions)

    eng = DecodeEngine(_Slow(slots=1), timeout_s=30.0)
    try:
        s = eng.generate([1, 2], max_new_tokens=10 ** 6)
        it = iter(s)
        next(it)                      # at least one token streamed
        s.cancel()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if eng.stats()['free_slots'] == 1:
                break
            time.sleep(0.01)
        assert eng.stats()['free_slots'] == 1
        assert s.finish_reason in ('cancelled', 'error')
    finally:
        eng.close(drain=False)


def test_engine_first_token_retirement_frees_slot():
    """Regression: a sequence finishing on its very first token
    (max_new=1, or first-token EOS) must free its slot — more
    one-token requests than slots all complete."""
    eng = DecodeEngine(_FakeProgram(slots=2), timeout_s=10.0)
    try:
        streams = [eng.generate([i + 1], max_new_tokens=1)
                   for i in range(6)]
        for i, s in enumerate(streams):
            assert s.result(10) == _expected([i + 1], 1)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if eng.stats()['free_slots'] == 2:
                break
            time.sleep(0.01)
        assert eng.stats()['free_slots'] == 2
    finally:
        eng.close()


def test_engine_closed_rejects_and_drain_completes():
    eng = DecodeEngine(_FakeProgram(), timeout_s=10.0)
    s = eng.generate([2, 2], max_new_tokens=3)
    eng.close(drain=True)
    assert s.result(5) == _expected([2, 2], 3)
    with pytest.raises(BatcherClosed):
        eng.generate([1], max_new_tokens=1)


def test_engine_bug_shaped_failure_fails_typed_without_leaking_slots():
    """A NON-transient (bug-shaped) device error must fail the
    request's stream with that error and free the slot — not orphan
    the client or shrink the slot pool."""
    class _Buggy(_FakeProgram):
        def __init__(self):
            super().__init__(slots=2)
            self.boom = 3        # prefills 1..3 raise

        def run_prefill(self, cache, tokens, slot):
            if self.boom:
                self.boom -= 1
                raise ValueError('bad dtype in custom model')
            return super().run_prefill(cache, tokens, slot)

    eng = DecodeEngine(_Buggy(), timeout_s=10.0)
    try:
        broken = [eng.generate([i + 1], max_new_tokens=2)
                  for i in range(3)]
        for s in broken:
            with pytest.raises(ValueError):
                s.result(10)
            assert s.finish_reason == 'error'
        # pool intact: a later request still gets a slot and completes
        ok = eng.generate([9], max_new_tokens=2)
        assert ok.result(10) == _expected([9], 2)
        assert eng.stats()['free_slots'] == 2
    finally:
        eng.close()


def test_engine_device_failure_completes_degraded():
    """A transient device failure mid-decode completes every in-flight
    sequence on the fallback path with the SAME tokens."""
    prog = _FakeProgram(slots=2, fail_ops=(2,))  # 3rd device op dies
    eng = DecodeEngine(prog, timeout_s=30.0)
    try:
        a = eng.generate([1, 2], max_new_tokens=6)
        b = eng.generate([3, 4], max_new_tokens=6)
        assert a.result(20) == _expected([1, 2], 6)
        assert b.result(20) == _expected([3, 4], 6)
        assert a.degraded or b.degraded
        assert prog.fallbacks >= 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# real-model engine + session integration
# ---------------------------------------------------------------------------

def _small_prog(**kw):
    model, params = init_rnn_lm(vocab=23, embed=8, hidden=16, layers=1,
                                mode='lstm', max_len=32)
    kw.setdefault('slots', 3)
    kw.setdefault('prefill_buckets', (4, 8))
    return DecodeProgram(model, params, **kw)


def test_session_generate_isolation_real_model():
    prog = _small_prog()
    with serving.InferenceSession(prog, watchdog=False) as sess:
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 2]]
        solo = [sess.generate(p, max_new_tokens=5).result(30)
                for p in prompts]
        streams = [sess.generate(p, max_new_tokens=5) for p in prompts]
        concurrent = [s.result(30) for s in streams]
        assert concurrent == solo
        st = sess.status()
        assert st['mode'] == 'decode'
        assert st['decode']['counts']['prefills'] == 8
    # recompiles stay bounded through all of it
    assert prog.compile_count <= len(prog.prefill_buckets) + 1


def test_session_decode_mode_guards_oneshot_api():
    prog = _small_prog()
    with serving.InferenceSession(prog, watchdog=False) as sess:
        with pytest.raises(TypeError):
            sess.infer(np.zeros(3))
        with pytest.raises(TypeError):
            sess.submit(np.zeros(3))


def test_session_device_loss_decode_degrades_with_same_tokens():
    prog = _small_prog()
    ref = prog.fallback_generate([1, 2, 3], 5)
    mx.config.set('MXNET_TPU_FAULT', 'device_loss@serving.decode:3')
    try:
        with serving.InferenceSession(prog, watchdog=False,
                                      timeout_s=60.0) as sess:
            streams = [sess.generate([1, 2, 3], max_new_tokens=5)
                       for _ in range(4)]
            outs = [s.result(60) for s in streams]
            st = sess.status()
    finally:
        mx.config.unset('MXNET_TPU_FAULT')
    assert all(o == ref for o in outs)
    assert all(s.degraded for s in streams)
    assert st['status'] == 'degraded'
    assert st['breaker'] == 'open'


def test_gluon_rnn_lm_adapter_matches_gluon_forward():
    """freeze_decode of trained gluon blocks: the decode engine's
    greedy next token equals argmax of the gluon model's own forward
    at the last position."""
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn, rnn
    mx.random.seed(11)
    np.random.seed(11)
    vocab, embed, hidden = 17, 8, 12
    embedding = nn.Embedding(vocab, embed)
    lstm = rnn.LSTM(hidden, num_layers=1, layout='TNC')
    decoder = nn.Dense(vocab, flatten=False)
    for blk in (embedding, lstm, decoder):
        blk.initialize(mx.init.Xavier())
    prompt = [3, 1, 4, 1, 5]
    x = nd.array(np.asarray(prompt, 'float32')[:, None])   # (T, B=1)
    emb = embedding(x)
    out, _states = lstm(emb, lstm.begin_state(batch_size=1))
    gl_logits = decoder(out).asnumpy()[:, 0]               # (T, V)

    prog = freeze_decode((embedding, lstm, decoder), max_len=32,
                         slots=2, prefill_buckets=(8,))
    cache = prog.new_cache()
    cache, tok, logits = prog.run_prefill(cache, prompt, 0)
    assert np.allclose(logits, gl_logits[-1], atol=1e-5)
    assert tok == int(gl_logits[-1].argmax())
    # and the whole cached stream equals the gluon-weights reference
    ref, _ = _greedy_reference(prog.model, prog._params_np, prompt, 4)
    got, _ = _cached_decode(prog, prompt, 4)
    assert got == ref


def test_freeze_decode_rejects_unfreezable():
    with pytest.raises(TypeError):
        freeze_decode(object())


# ---------------------------------------------------------------------------
# mid-stream faults: typed aborts + breaker recovery
# (docs/SERVING.md "SLOs and overload behavior")
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('kind,exc_name', [
    ('worker_crash', 'WorkerCrashError'),
    ('preempt', 'PreemptionSignal'),
])
def test_engine_mid_stream_fault_aborts_typed_and_recovers(kind,
                                                           exc_name):
    """worker_crash / preempt mid-decode abort the in-flight stream
    with the TYPED error (infra trouble degrades, dying workers
    abort), free the slot, and after the breaker's half-open probe
    the same engine serves clean again."""
    from mxnet_tpu.resilience import policy as rp
    exc_type = getattr(rp, exc_name)
    prog = _FakeProgram(slots=2)
    eng = DecodeEngine(
        prog, timeout_s=10.0,
        breaker=rp.CircuitBreaker(failure_threshold=1,
                                  reset_timeout=0.2))
    # device ops for a solo stream: op0 prefill, op1.. steps — fire
    # at op 2 so the abort lands MID-stream (>= 2 tokens out)
    mx.config.set('MXNET_TPU_FAULT',
                  '%s@serving.decode.2:1' % kind)
    try:
        s = eng.generate([1, 2], max_new_tokens=6)
        with pytest.raises(exc_type):
            s.result(10)
        assert s.finish_reason == 'error'
        assert len(s.tokens) >= 1          # aborted mid-stream
        assert not s.degraded              # aborted, NOT degraded
        # the slot retired
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if eng.stats()['free_slots'] == 2:
                break
            time.sleep(0.01)
        assert eng.stats()['free_slots'] == 2
        assert eng.stats()['counts']['retired'].get('aborted') == 1
        # breaker opened (threshold 1); past the reset window the
        # half-open probe admits the next generation, which succeeds
        assert eng.stats()['breaker'] in ('open', 'half-open')
        time.sleep(0.25)
        ok = eng.generate([3, 4], max_new_tokens=3)
        assert ok.result(10) == _expected([3, 4], 3)
        assert not ok.degraded
        assert eng.stats()['breaker'] == 'closed'
    finally:
        mx.config.unset('MXNET_TPU_FAULT')
        eng.close()


class _EngineSession:
    """Duck-typed decode-mode session over a DecodeEngine: the HTTP
    layer only needs ._engine/.generate/.status/.retry_after_hint."""

    _batcher = None

    def __init__(self, engine):
        self._engine = engine

    def generate(self, tokens, max_new_tokens=None, eos_id=None):
        return self._engine.generate(tokens,
                                     max_new_tokens=max_new_tokens,
                                     eos_id=eos_id)

    def status(self):
        st = self._engine.stats()
        return {'status': 'degraded' if st['degraded'] else 'ok',
                'breaker': st['breaker']}

    def retry_after_hint(self):
        return self._engine.retry_after_hint()


@pytest.mark.parametrize('kind', ['worker_crash', 'preempt'])
def test_http_generate_stream_fault_typed_error_line_and_recovery(
        kind):
    """Satellite contract: a fault injected mid-/generate stream must
    terminate the NDJSON stream with a typed error line, free the
    decode slot, and a subsequent request on the SAME session must
    succeed after the breaker's half-open probe."""
    import http.client
    from mxnet_tpu.resilience.policy import CircuitBreaker
    from mxnet_tpu.serving.server import ServingHTTPServer
    exc_names = {'worker_crash': 'WorkerCrashError',
                 'preempt': 'PreemptionSignal'}
    prog = _FakeProgram(slots=2)
    eng = DecodeEngine(prog, timeout_s=10.0,
                       breaker=CircuitBreaker(failure_threshold=1,
                                              reset_timeout=0.2))
    sess = _EngineSession(eng)
    mx.config.set('MXNET_TPU_FAULT',
                  '%s@serving.decode.2:1' % kind)
    try:
        with ServingHTTPServer(sess, 0) as srv:
            def post(payload, timeout=20):
                conn = http.client.HTTPConnection(
                    '127.0.0.1', srv.port, timeout=timeout)
                body = json.dumps(payload).encode()
                conn.request('POST', '/generate', body=body,
                             headers={'Content-Type':
                                      'application/json',
                                      'Connection': 'close'})
                resp = conn.getresponse()
                raw = resp.read().decode()
                conn.close()
                return resp.status, raw

            status, raw = post({'tokens': [1, 2],
                                'max_new_tokens': 6, 'stream': True})
            assert status == 200
            lines = [json.loads(ln) for ln in raw.strip().split('\n')]
            # tokens streamed before the fault...
            assert any('token' in ln for ln in lines)
            # ...then the stream TERMINATES with a typed error line
            last = lines[-1]
            assert last.get('done') is True
            assert last.get('error_class') == exc_names[kind]
            assert exc_names[kind] in last.get('error', '')
            # the decode slot is freed
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if eng.stats()['free_slots'] == 2:
                    break
                time.sleep(0.01)
            assert eng.stats()['free_slots'] == 2
            # after the half-open window the SAME session serves the
            # next request clean
            time.sleep(0.25)
            status, raw = post({'tokens': [3, 4],
                                'max_new_tokens': 3, 'stream': False})
            assert status == 200
            body = json.loads(raw)
            assert body['tokens'] == _expected([3, 4], 3)
            assert body['finish_reason'] == 'length'
            assert body['degraded'] is False
    finally:
        mx.config.unset('MXNET_TPU_FAULT')
        eng.close()


@pytest.mark.parametrize('start_index, max_new',
                         [(0, 5), (7, 1), (123456, 3)])
def test_http_generate_stream_writes_each_chunk_once(monkeypatch,
                                                     start_index, max_new):
    """A streamed token costs its handler one write (one system call,
    one segment, one wake-up of the reader): the chunk's size line, its
    NDJSON line and the closing CRLF go out together, and the bytes on
    the wire are the chunked encoding they were, whatever the width of
    the index (a resumed stream's starts past 0)."""
    import http.client
    import socketserver
    from mxnet_tpu.serving.server import ServingHTTPServer
    writes = []
    write = socketserver._SocketWriter.write

    def recording(self, b):
        writes.append(bytes(b))
        return write(self, b)

    monkeypatch.setattr(socketserver._SocketWriter, 'write', recording)
    eng = DecodeEngine(_FakeProgram(slots=2), timeout_s=10.0)
    try:
        with ServingHTTPServer(_EngineSession(eng), 0) as srv:
            conn = http.client.HTTPConnection('127.0.0.1', srv.port,
                                              timeout=20)
            conn.request('POST', '/generate', body=json.dumps(
                {'tokens': [1, 2, 3], 'max_new_tokens': max_new,
                 'start_index': start_index, 'stream': True}).encode(),
                headers={'Content-Type': 'application/json',
                         'Connection': 'close'})
            resp = conn.getresponse()
            lines = [json.loads(ln) for ln in
                     resp.read().decode().strip().split('\n')]
            conn.close()
    finally:
        eng.close()
    want = _expected([1, 2, 3], max_new)
    assert [ln['token'] for ln in lines[:-1]] == want
    assert [ln['index'] for ln in lines[:-1]] \
        == list(range(start_index, start_index + max_new))
    assert lines[-1]['done'] and lines[-1]['tokens'] == want
    chunks = [w for w in writes if b'"token"' in w or b'"done"' in w]
    assert len(chunks) == max_new + 1
    for chunk in chunks:
        size, rest = chunk.split(b'\r\n', 1)
        assert rest.endswith(b'\n\r\n') and int(size, 16) == len(rest) - 2
        # a token's line is what json.dumps writes for it, keys sorted
        assert rest[:-2].decode() == json.dumps(json.loads(rest),
                                                sort_keys=True) + '\n'


def test_engine_degraded_fallback_runs_off_worker_thread():
    """A breaker trip must not serialize the (slow) CPU fallback into
    the scheduler loop: while a degraded completion is still running,
    the engine keeps admitting and decoding fresh sequences."""
    import threading as _threading
    release = _threading.Event()
    entered = _threading.Event()

    class _SlowFallback(_FakeProgram):
        def fallback_generate(self, tokens, max_new, eos_id=None,
                              **kw):
            entered.set()
            release.wait(10)       # a deliberately wedged fallback
            return super().fallback_generate(tokens, max_new, eos_id,
                                             **kw)

    prog = _SlowFallback(slots=2, fail_ops=(1,))   # 2nd op dies
    eng = DecodeEngine(prog, timeout_s=15.0)
    try:
        victim = eng.generate([1, 2], max_new_tokens=4)
        # wait until the fault fired and the victim is IN the wedged
        # fallback (otherwise the scripted failure could hit the
        # fresh sequence instead)
        assert entered.wait(5.0)
        # with the fallback thread still blocked, a fresh generation
        # must complete at device speed
        fresh = eng.generate([5, 6], max_new_tokens=3)
        assert fresh.result(10) == _expected([5, 6], 3)
        assert not victim.done()      # fallback still wedged
        release.set()
        assert victim.result(10) == _expected([1, 2], 4)
        assert victim.degraded
    finally:
        release.set()
        eng.close()
