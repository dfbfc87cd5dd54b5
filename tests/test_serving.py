"""Serving-engine tests (docs/SERVING.md): bucket math, the
micro-batcher contract (deadline vs max-batch flush, FIFO ordering
under concurrent submitters, queue-full rejection type, per-request
timeout), pad/unpad bit-exactness, frozen save/load, the circuit
breaker -> CPU-fallback degraded path, the partial-batch predict fix,
and the persistent compile-cache warm-start."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serving
from mxnet_tpu.io import DataBatch
from mxnet_tpu.serving.batcher import (BackpressureError, BatcherClosed,
                                       MicroBatcher, RequestTimeout)
from mxnet_tpu.serving.bucket import (BucketPolicy, bucket_for,
                                      default_buckets, pad_axis0,
                                      parse_buckets, unpad_axis0)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp_symbol(features=8, classes=4):
    data = mx.sym.Variable('data')
    h = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
    h = mx.sym.Activation(h, act_type='relu')
    h = mx.sym.FullyConnected(h, num_hidden=classes, name='fc2')
    return mx.sym.SoftmaxOutput(h, name='softmax')


def _fitted_module(features=8, classes=4, n=32, batch=8):
    sym = _mlp_symbol(features, classes)
    mod = mx.mod.Module(sym, context=mx.cpu())
    rs = np.random.RandomState(0)
    x = rs.randn(n, features).astype('float32')
    y = rs.randint(0, classes, (n,)).astype('float32')
    it = mx.io.NDArrayIter(x, y, batch_size=batch)
    mod.fit(it, num_epoch=1, optimizer_params=(('learning_rate', 0.1),))
    return mod, x, y


# ---------------------------------------------------------------------------
# bucket math
# ---------------------------------------------------------------------------

def test_default_buckets_powers_of_two():
    assert default_buckets(8) == (1, 2, 4, 8)
    assert default_buckets(1) == (1,)
    # a non-power-of-two cap is always included as the top bucket
    assert default_buckets(12) == (1, 2, 4, 8, 12)


def test_bucket_for_smallest_fit_and_overflow():
    buckets = (1, 2, 4, 8)
    assert [bucket_for(n, buckets) for n in (1, 2, 3, 5, 8)] == \
        [1, 2, 4, 8, 8]
    with pytest.raises(ValueError):
        bucket_for(9, buckets)


def test_parse_buckets_knob_format():
    assert parse_buckets('8, 1,4,4') == (1, 4, 8)
    with pytest.raises(ValueError):
        parse_buckets('0,4')


def test_pad_unpad_round_trip_bit_exact():
    rs = np.random.RandomState(3)
    x = rs.randn(5, 7).astype('float32')
    padded = pad_axis0(x, 8)
    assert padded.shape == (8, 7)
    assert np.array_equal(padded[5:], np.zeros((3, 7), 'float32'))
    assert np.array_equal(unpad_axis0(padded, 5), x)
    assert pad_axis0(x, 5) is x      # no copy when already at bucket
    with pytest.raises(ValueError):
        pad_axis0(x, 4)


def test_bucket_ladder_validation_matches_knob_path():
    # a sequence ladder gets the same validation as the knob string
    with pytest.raises(ValueError):
        BucketPolicy(buckets=[0, 8])
    with pytest.raises(ValueError):
        BucketPolicy(buckets=(-4, 8))
    assert BucketPolicy(buckets=[8, 1, 4, 4]).buckets == (1, 4, 8)


def test_bucket_policy_seq_buckets():
    p = BucketPolicy(buckets=(2, 4), seq_buckets=(8, 16))
    assert p.key_for(3, 10) == (4, 16)
    padded, n = p.pad([np.ones((3, 10), 'float32')], seq_len=10)
    assert padded[0].shape == (4, 16) and n == 3


# ---------------------------------------------------------------------------
# micro-batcher contract
# ---------------------------------------------------------------------------

def _echo_runner(calls=None):
    def runner(stacked, n):
        if calls is not None:
            calls.append(n)
        return [stacked[0] * 2.0]
    return runner


def test_batcher_max_batch_flush():
    calls = []
    with MicroBatcher(_echo_runner(calls), max_batch=4,
                      deadline_ms=60000.0, timeout_s=30.0) as b:
        futs = [b.submit(np.full(2, i, 'float32')) for i in range(4)]
        outs = [f.result(10)[0] for f in futs]
    assert 4 in calls, calls    # one aggregated batch, not 4 singles
    assert b.stats()['flushes']['full'] >= 1
    for i, out in enumerate(outs):
        assert np.array_equal(out, np.full(2, 2.0 * i))


def test_batcher_deadline_flush():
    with MicroBatcher(_echo_runner(), max_batch=1024, deadline_ms=5.0,
                      timeout_s=30.0) as b:
        out = b.infer(np.ones(3, 'float32'))[0]
        assert np.array_equal(out, 2.0 * np.ones(3))
    assert b.stats()['flushes']['deadline'] >= 1
    assert b.stats()['flushes']['full'] == 0


def test_batcher_fifo_under_concurrent_submitters():
    results = {}
    with MicroBatcher(_echo_runner(), max_batch=8, deadline_ms=5.0,
                      timeout_s=30.0) as b:
        def client(i):
            results[i] = b.infer(np.full(3, i, 'float32'))[0]
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    for i in range(24):
        assert np.array_equal(results[i], np.full(3, 2.0 * i)), \
            'request %d got another request\'s row' % i


def test_batcher_queue_full_rejection_typed_and_immediate():
    gate = threading.Event()

    def blocked(stacked, n):
        gate.wait(30)
        return [stacked[0]]

    b = MicroBatcher(blocked, max_batch=1, deadline_ms=0.0,
                     max_queue=2, timeout_s=30.0)
    try:
        b.submit(np.zeros(2))
        deadline = time.monotonic() + 5.0
        while b.stats()['depth'] and time.monotonic() < deadline:
            time.sleep(0.002)   # worker holds request 0 in the runner
        b.submit(np.zeros(2))
        b.submit(np.zeros(2))
        t0 = time.monotonic()
        with pytest.raises(BackpressureError) as exc:
            b.submit(np.zeros(2))
        assert time.monotonic() - t0 < 1.0, 'rejection must not block'
        assert exc.value.limit == 2 and exc.value.depth == 2
        assert b.stats()['rejected'] == 1
    finally:
        gate.set()
        b.close(drain=False)


def test_batcher_per_request_timeout_while_worker_stuck():
    gate = threading.Event()

    def blocked(stacked, n):
        gate.wait(30)
        return [stacked[0]]

    b = MicroBatcher(blocked, max_batch=1, deadline_ms=0.0,
                     max_queue=8, timeout_s=0.2)
    try:
        inflight = b.submit(np.zeros(2))    # occupies the worker
        fut = b.submit(np.zeros(2))         # ages out in the queue
        with pytest.raises(RequestTimeout):
            fut.result(10)
        # the IN-FLIGHT request (popped into the stuck batch) must
        # honor the budget too, not hang until the runner returns
        with pytest.raises(RequestTimeout):
            inflight.result(10)
        assert b.stats()['timeouts'] >= 2
    finally:
        gate.set()
        b.close(drain=False)


def test_batcher_flush_drops_expired_and_cancelled_requests():
    """Regression: requests expired by the timeout reaper (or
    cancelled) between the batch pop and the flush must NOT consume
    device batch rows — the flush recomputes expiry and stacks only
    live requests, preserving their FIFO row mapping."""
    from concurrent.futures import Future
    from mxnet_tpu.serving.batcher import _Request
    now = [100.0]
    calls = []

    def runner(stacked, n):
        calls.append(n)
        return [stacked[0] * 2.0]

    b = MicroBatcher(runner, max_batch=8, deadline_ms=1e9, max_queue=8,
                     timeout_s=1.0, name='flush-expire',
                     clock=lambda: now[0])
    try:
        live = _Request([np.ones(3, 'float32')], Future(), 99.5, 101.0)
        # deadline already past at flush time: exactly the state the
        # reaper produces between _take_batch and _run_batch
        expired = _Request([np.full(3, 7.0, 'float32')], Future(),
                           98.0, 99.0)
        cancelled = _Request([np.full(3, 9.0, 'float32')], Future(),
                             99.5, 101.0)
        cancelled.future.cancel()
        batch = [expired, live, cancelled]
        with b._lock:
            b._inflight = batch
        b._run_batch(batch, 'full')
        # only the live request's row reached the runner
        assert calls == [1]
        assert np.array_equal(live.future.result(0)[0],
                              np.full(3, 2.0, 'float32'))
        with pytest.raises(RequestTimeout):
            expired.future.result(0)
        # an all-dead batch skips the device entirely
        gone = _Request([np.ones(3, 'float32')], Future(), 90.0, 91.0)
        with b._lock:
            b._inflight = [gone]
        b._run_batch([gone], 'full')
        assert calls == [1]
        with pytest.raises(RequestTimeout):
            gone.future.result(0)
    finally:
        b.close(drain=False)


def test_batcher_example_shape_validation():
    got = []

    def runner(stacked, n):
        got.append(stacked[0].shape)
        return [stacked[0]]

    with MicroBatcher(runner, max_batch=1, deadline_ms=0.0,
                      timeout_s=10.0,
                      example_shapes=[(1, 4, 4)]) as b:
        # a genuine rank-3 example whose first dim is 1 must NOT be
        # mistaken for a batched rank-2 one
        b.infer(np.zeros((1, 4, 4), 'float32'))
        # an explicit batch axis of 1 is stripped by rank
        b.infer(np.zeros((1, 1, 4, 4), 'float32'))
        with pytest.raises(ValueError):
            b.submit(np.zeros((4, 4), 'float32'))
        with pytest.raises(ValueError):
            b.submit(np.zeros(3), np.zeros(3))   # wrong input arity
    assert got == [(1, 1, 4, 4), (1, 1, 4, 4)]


def test_session_rank3_single_example_round_trip():
    """Regression: a conv-style (c, h, w) example with a leading dim
    of 1 served through the session (the HTTP /predict path)."""
    data = mx.sym.Variable('data')
    h = mx.sym.Flatten(data)
    h = mx.sym.FullyConnected(h, num_hidden=4, name='fc')
    sym = mx.sym.SoftmaxOutput(h, name='softmax')
    mod = mx.mod.Module(sym, context=mx.cpu())
    rs = np.random.RandomState(0)
    x = rs.randn(8, 1, 4, 4).astype('float32')
    y = rs.randint(0, 4, (8,)).astype('float32')
    it = mx.io.NDArrayIter(x, y, batch_size=4)
    mod.fit(it, num_epoch=1, optimizer_params=(('learning_rate', 0.1),))
    frozen = serving.freeze(mod, max_batch=4)
    ref = frozen.run([x[:1]])[0][0]
    with serving.InferenceSession(frozen, deadline_ms=1.0,
                                  watchdog=False) as sess:
        out = sess.infer(x[0], timeout=30)[0]       # (1, 4, 4) example
    assert np.array_equal(out, ref)


def test_batcher_runner_error_propagates_and_closed_rejects():
    def boom(stacked, n):
        raise ValueError('deterministic bug')

    b = MicroBatcher(boom, max_batch=1, deadline_ms=0.0, timeout_s=5.0)
    with pytest.raises(ValueError):
        b.infer(np.zeros(2))
    b.close()
    with pytest.raises(BatcherClosed):
        b.submit(np.zeros(2))


# ---------------------------------------------------------------------------
# freeze: AOT programs, bit-identity, persistence
# ---------------------------------------------------------------------------

def test_freeze_batched_bit_identical_to_single():
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=8)
    got = frozen.run([x[:5]])[0]
    for i in range(5):
        ref = frozen.run([x[i:i + 1]])[0][0]
        assert np.array_equal(got[i], ref)


def test_freeze_recompile_bounded_by_buckets():
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=8)
    for n in (1, 3, 8, 2, 5, 8, 1, 7):
        frozen.run([x[:n]])
    assert frozen.compile_count <= 4      # ladder 1,2,4,8
    # tracing matches compiling: one python trace per bucket, ever
    assert all(v == 1 for v in frozen.trace_counts.values())


def test_freeze_oversized_bulk_batch_chunks():
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=4)
    got = frozen.run([x[:11]])[0]
    assert got.shape[0] == 11
    ref = np.concatenate([frozen.run([x[i:i + 1]])[0]
                          for i in range(11)])
    assert np.array_equal(got, ref)


def test_frozen_save_load_round_trip(tmp_path):
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=4, name='rt')
    expected = frozen.warmup().run([x[:3]])[0]
    art = str(tmp_path / 'model.frozen')
    frozen.save(art)
    manifest = json.load(open(os.path.join(art, 'MANIFEST.json')))
    assert manifest['schema'] == serving.FROZEN_SCHEMA
    assert manifest['buckets'] == [1, 2, 4]
    loaded = serving.load_frozen(art)
    got = loaded.run([x[:3]])[0]
    assert np.array_equal(got, expected)
    # same process, same platform: every program deserialized — the
    # reload served WITHOUT tracing python
    assert loaded.trace_counts == {}
    assert loaded.retraced_buckets == []


def test_frozen_load_rejects_wrong_schema(tmp_path):
    art = tmp_path / 'bogus'
    art.mkdir()
    (art / 'MANIFEST.json').write_text('{"schema": "nope"}')
    with pytest.raises(ValueError):
        serving.load_frozen(str(art))


def test_freeze_module_bound_with_plain_tuples():
    """Regression: Module.bind with plain (name, shape) tuples leaves
    DataDesc.dtype as the np.float32 CLASS; freeze must normalize it
    to a parseable dtype string."""
    sym = _mlp_symbol()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind([('data', (4, 8))], for_training=False)
    mod.init_params(initializer=mx.init.Xavier())
    frozen = serving.freeze(mod, max_batch=4)
    assert frozen.data_descs[0][2] == 'float32'
    out = frozen.run([np.zeros((2, 8), 'float32')])[0]
    assert out.shape == (2, 4)


def test_freeze_gluon_block():
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation='relu'), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = np.random.RandomState(2).randn(6, 8).astype('float32')
    ref = net(nd.array(x)).asnumpy()
    frozen = serving.freeze(net, data_shapes=[('data', (8,))],
                            max_batch=8)
    got = frozen.run([x])[0]
    assert np.allclose(got, ref, atol=1e-6)


# ---------------------------------------------------------------------------
# InferenceSession: batching engine + resilience threading
# ---------------------------------------------------------------------------

def test_session_concurrent_requests_bit_identical():
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=8)
    refs = [frozen.run([x[i:i + 1]])[0][0] for i in range(10)]
    with serving.InferenceSession(frozen, deadline_ms=10.0,
                                  watchdog=False) as sess:
        futs = [sess.submit(x[i]) for i in range(10)]
        for i, f in enumerate(futs):
            assert np.array_equal(f.result(30)[0], refs[i])
        st = sess.status()
    assert st['status'] == 'ok' and st['batches']['accel'] >= 1


def test_session_device_loss_falls_back_and_degrades():
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=4)
    ref = frozen.run_fallback([x[:1]])[0][0]
    mx.config.set('MXNET_TPU_FAULT', 'device_loss@serving:3')
    try:
        with serving.InferenceSession(frozen, deadline_ms=1.0,
                                      max_batch=1,
                                      watchdog=False) as sess:
            outs = [sess.infer(x[0], timeout=30)[0] for _ in range(4)]
            st = sess.status()
    finally:
        mx.config.unset('MXNET_TPU_FAULT')
    for out in outs:   # degraded but correct
        assert np.allclose(out, ref, atol=1e-5)
    assert st['status'] == 'degraded'
    assert st['breaker'] == 'open'        # 3 consecutive failures
    assert st['batches']['fallback'] == 4
    assert st['batches']['accel'] == 0


_COMPILE_ERROR = ('INTERNAL: Mosaic failed to compile TPU kernel: '
                  'failed to legalize operation')


def test_session_compile_failure_raises_instead_of_cpu_completion():
    """A compile-shaped failure on the serving path is not transient:
    the breaker re-raises it to the caller — no CPU completion, no
    fallback batch, no serve_fallback / breaker_open event."""
    from mxnet_tpu import observability as obs
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=4)

    def refused(arrays, n=None):
        raise RuntimeError(_COMPILE_ERROR)

    frozen.run = refused
    frozen.run_fallback = lambda *a, **k: pytest.fail(
        'a refused program was completed on the CPU')
    obs.get_recorder().clear()
    with serving.InferenceSession(frozen, deadline_ms=1.0, max_batch=1,
                                  watchdog=False) as sess:
        for _ in range(4):      # past the breaker threshold of 3
            with pytest.raises(RuntimeError, match='Mosaic failed'):
                sess.infer(x[0], timeout=30)
        st = sess.status()
    assert st['batches'] == {'accel': 0, 'fallback': 0}
    kinds = [e.get('kind') for e in obs.get_recorder().events()]
    assert 'serve_fallback' not in kinds and 'breaker_open' not in kinds


def test_decode_compile_failure_fails_stream_instead_of_cpu_tokens():
    """Same rule through DecodeEngine._device: the stream fails with
    the compiler's error and no token is produced on the CPU."""
    from mxnet_tpu.serving.decode import init_transformer_lm
    model, params = init_transformer_lm(vocab=19, units=16, hidden=24,
                                        layers=1, heads=4, max_len=32)
    prog = serving.freeze_decode(model, params, slots=2,
                                 prefill_buckets=(4, 8), max_len=32)

    def refused(*a, **k):
        raise RuntimeError(_COMPILE_ERROR)

    prog.run_prefill = refused
    prog.fallback_generate = lambda *a, **k: pytest.fail(
        'a refused program was completed on the CPU')
    with serving.InferenceSession(prog, watchdog=False) as sess:
        stream = sess.generate([3, 1, 4], max_new_tokens=4)
        with pytest.raises(RuntimeError, match='Mosaic failed'):
            stream.result(30)
        st = sess.status()
    assert st['decode']['counts']['fallback_tokens'] == 0
    assert stream.degraded is False


def test_session_recovers_after_transient_faults():
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=4)
    mx.config.set('MXNET_TPU_FAULT', 'device_loss@serving:1')
    try:
        with serving.InferenceSession(frozen, deadline_ms=1.0,
                                      max_batch=1,
                                      watchdog=False) as sess:
            sess.infer(x[0], timeout=30)      # fault consumed: fallback
            sess.infer(x[0], timeout=30)      # accelerator again
            st = sess.status()
    finally:
        mx.config.unset('MXNET_TPU_FAULT')
    assert st['status'] == 'ok'
    assert st['batches'] == {'accel': 1, 'fallback': 1}
    assert st['breaker'] == 'closed'


def test_session_real_hang_detected_by_watchdog_monitor():
    """A REAL hang (device call blocks, no injected fault) must be
    observed by the watchdog's monitor thread: stall artifact written,
    breaker failure counted, status degraded — even though the worker
    is still wedged inside the call."""
    import tempfile
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=4)
    gate = threading.Event()
    real_run = frozen.run

    def hung_run(arrays, n=None):
        gate.wait(30)
        return real_run(arrays, n)

    frozen.run = hung_run
    mx.config.set('MXNET_TPU_WATCHDOG_STEP_S', 0.15)
    mx.config.set('MXNET_TPU_WATCHDOG_POLL_S', 0.05)
    stall = os.path.join(tempfile.gettempdir(),
                         'mxnet_tpu_test_serve_stall.json')
    if os.path.exists(stall):
        os.unlink(stall)
    try:
        sess = serving.InferenceSession(frozen, deadline_ms=1.0,
                                        max_batch=1, timeout_s=0.5,
                                        stall_artifact=stall)
        fut = sess.submit(x[0])
        with pytest.raises(RequestTimeout):   # budget still honored
            fut.result(10)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                not os.path.exists(stall):
            time.sleep(0.02)
        st = sess.status()
        assert os.path.exists(stall), 'monitor wrote no stall artifact'
        assert json.load(open(stall))['phase'] == 'infer'
        assert st['status'] == 'degraded'
    finally:
        gate.set()
        mx.config.unset('MXNET_TPU_WATCHDOG_STEP_S')
        mx.config.unset('MXNET_TPU_WATCHDOG_POLL_S')
        sess.close(drain=False)
        if os.path.exists(stall):
            os.unlink(stall)


def test_session_rejects_non_frozen():
    with pytest.raises(TypeError):
        serving.InferenceSession(object())


def test_serving_knob_defaults_flow_from_config():
    mod, _, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=8)
    mx.config.set('MXNET_TPU_SERVE_QUEUE_DEPTH', 7)
    try:
        sess = serving.InferenceSession(frozen, watchdog=False)
        assert sess._batcher.max_queue == 7
        sess.close()
    finally:
        mx.config.unset('MXNET_TPU_SERVE_QUEUE_DEPTH')


# ---------------------------------------------------------------------------
# partial final batch: predict must pad, not recompile (module fix)
# ---------------------------------------------------------------------------

def test_module_partial_batch_pads_instead_of_reshaping():
    mod, x, _ = _fitted_module(n=32, batch=8)
    x = x[:19]
    exec_before = mod._exec
    outs = []
    for i in range(0, 19, 8):        # 8, 8, 3 — partial tail
        mod.forward(DataBatch([nd.array(x[i:i + 8])]), is_train=False)
        outs.append(mod.get_outputs()[0].asnumpy())
    assert mod._exec is exec_before, \
        'partial batch reshaped the executor (recompile)'
    got = np.concatenate(outs)
    assert got.shape[0] == 19
    # unpadded reference: a fresh module bound at exactly 3
    sym = mod.symbol
    ref_mod = mx.mod.Module(sym, context=mx.cpu())
    ref_mod.bind([('data', (3, 8))], for_training=False)
    arg, aux = mod.get_params()
    ref_mod.init_params(arg_params=arg, aux_params=aux)
    ref_mod.forward(DataBatch([nd.array(x[16:19])]), is_train=False)
    ref = ref_mod.get_outputs()[0].asnumpy()
    assert np.array_equal(got[16:], ref), \
        'padded partial batch is not bit-identical to unpadded'


def test_module_predict_iterator_partial_tail():
    mod, x, _ = _fitted_module(n=32, batch=8)
    # 'discard' would drop the tail; roll our own batches so predict
    # sees a genuine partial final DataBatch
    class _It:
        def __init__(self, x, bs):
            self.x, self.bs = x, bs
        def reset(self):
            pass
        def __iter__(self):
            for i in range(0, len(self.x), self.bs):
                yield DataBatch([nd.array(self.x[i:i + self.bs])])
    out = mod.predict(_It(x[:19], 8))
    assert out.shape[0] == 19
    # row 16 (first of the padded tail) equals its bucket-1 reference
    single = serving.freeze(mod, max_batch=1).run([x[16:17]])[0][0]
    assert np.allclose(out.asnumpy()[16], single, atol=1e-6)


def test_module_train_batch_still_reshapes():
    mod, x, y = _fitted_module(n=32, batch=8)
    exec_before = mod._exec
    b = DataBatch([nd.array(x[:4])], [nd.array(y[:4])])
    mod.forward(b, is_train=True)
    assert mod._exec is not exec_before, \
        'training forward must reshape (padding would corrupt grads)'


# ---------------------------------------------------------------------------
# persistent compilation cache: JAX_COMPILATION_CACHE_DIR where set,
# else the fixed <repo>/.jax_cache (config.configure_compile_cache)
# ---------------------------------------------------------------------------

_CACHE_CHILD = r"""
import sys
import mxnet_tpu as mx
from mxnet_tpu import nd
import numpy as np
data = mx.sym.Variable('data')
h = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
out = mx.sym.SoftmaxOutput(h, name='softmax')
ex = out.simple_bind(ctx=mx.cpu(), data=(4, 8))
ex.forward(is_train=False, data=nd.array(np.ones((4, 8), 'float32')))
ex.outputs[0].wait_to_read()
print('CHILD_OK')
"""


@pytest.mark.slow
def test_compile_cache_second_process_warm_starts(tmp_path):
    """Warm-start through JAX_COMPILATION_CACHE_DIR: the first process
    populates the persistent cache; a second identical process compiles
    nothing new — zero new cache entries, every XLA compile (the
    expensive part of a jit-cache miss) served from disk. jax's
    write thresholds are zeroed so this toy program is cached at all."""
    cache = str(tmp_path / 'jitcache')
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS='0',
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES='-1')

    def run_child():
        r = subprocess.run([sys.executable, '-c', _CACHE_CHILD],
                           cwd=REPO, env=env, capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 0 and 'CHILD_OK' in r.stdout, r.stderr

    def cache_entries():
        return sorted(f for f in os.listdir(cache)
                      if f.endswith('-cache'))

    run_child()
    first = cache_entries()
    assert first, 'first process wrote no persistent cache entries'
    run_child()
    assert cache_entries() == first, \
        'second process recompiled (new cache entries) instead of ' \
        'warm-starting'


_CACHE_DIR_CHILD = (
    'import jax, mxnet_tpu as mx\n'
    'print("DIR", jax.config.jax_compilation_cache_dir)\n'
    'print("RESOLVED", mx.config.configure_compile_cache())\n')


def _cache_dir_child(env):
    r = subprocess.run([sys.executable, '-c', _CACHE_DIR_CHILD],
                       cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    lines = dict(ln.split(' ', 1) for ln in r.stdout.splitlines()
                 if ln.startswith(('DIR ', 'RESOLVED ')))
    return lines['DIR'], lines['RESOLVED']


def test_compile_cache_env_var_places_the_cache(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set => the code sets no directory of
    its own: jax's config holds exactly the variable's value."""
    cache = str(tmp_path / 'x')
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               JAX_COMPILATION_CACHE_DIR=cache)
    assert _cache_dir_child(env) == (cache, cache)


def test_compile_cache_default_is_fixed_path_in_checkout():
    """JAX_COMPILATION_CACHE_DIR unset => one fixed path inside the
    checkout (the path is part of the cache key: never a tempdir, a
    pid or a time), and the old knob no longer exists."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    env['MXNET_TPU_COMPILE_CACHE'] = '/nonexistent/ignored'
    want = os.path.join(REPO, '.jax_cache')
    assert _cache_dir_child(env) == (want, want)
    assert 'MXNET_TPU_COMPILE_CACHE' not in mx.config.KNOBS


# ---------------------------------------------------------------------------
# overload behavior: doomed-request shedding, Retry-After, health codes
# (docs/SERVING.md "SLOs and overload behavior")
# ---------------------------------------------------------------------------

class _FakeClock:
    """Thread-safe manual clock for deterministic deadline math."""

    def __init__(self):
        self._t = 0.0
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            return self._t

    def advance(self, dt):
        with self._lock:
            self._t += dt


def test_batcher_sheds_doomed_requests_at_dequeue():
    """A request whose deadline will lapse before a batch of recent
    latency could return must fail fast at dequeue (shed_doomed), not
    burn a batch slot on a future the reaper is about to expire."""
    clock = _FakeClock()

    def runner(stacked, n):
        clock.advance(0.6)          # every batch "takes" 0.6s
        return [stacked[0]]

    b = MicroBatcher(runner, max_batch=1, deadline_ms=0.0,
                     timeout_s=1.0, name='doomed', clock=clock)
    try:
        futs = [b.submit(np.zeros(2)) for _ in range(3)]
        # f0 served (no latency estimate yet); after it the EWMA is
        # 0.6s, so f1/f2 (deadline t=1.0, dequeued at t>=0.6) are
        # doomed: 0.6 + 0.6 > 1.0
        assert futs[0].result(10)[0].shape == (2,)
        for f in futs[1:]:
            with pytest.raises(RequestTimeout) as ei:
                f.result(10)
            assert 'shed at dequeue' in str(ei.value)
        stats = b.stats()
        assert stats['shed_doomed'] == 2
        # doomed sheds are their own bucket, not queue-age timeouts
        assert stats['timeouts'] == 0
    finally:
        b.close(drain=False)


def test_batcher_retry_after_hint_tracks_queue_depth():
    gate = threading.Event()

    def runner(stacked, n):
        gate.wait(20)
        return [stacked[0]]

    b = MicroBatcher(runner, max_batch=2, deadline_ms=0.0,
                     timeout_s=30.0, max_queue=64, name='hint')
    try:
        empty_hint = b.retry_after_hint()
        assert empty_hint > 0.0
        b._ema_batch_s = 0.2        # pretend batches take 200ms
        base = b.retry_after_hint()
        futs = [b.submit(np.zeros(2)) for _ in range(9)]
        deep = b.retry_after_hint()
        assert deep > base          # more queue -> larger backoff
        assert deep >= 0.2 * (len(futs) - 2) / 2.0 * 0.5
    finally:
        gate.set()
        b.close(drain=False)
        assert futs is not None


class _FakeOneShotSession:
    """Duck-typed stand-in for InferenceSession: exercises the HTTP
    layer's status codes without building a model."""

    def __init__(self, status='ok', fail=None, block=None):
        import types as _types
        self._batcher = _types.SimpleNamespace(timeout_s=5.0)
        self._engine = None
        self._status = status
        self._fail = fail
        self._block = block
        self.entered = threading.Event()

    def status(self):
        return {'status': self._status, 'breaker': 'closed'}

    def retry_after_hint(self):
        return 2.5

    def infer(self, x, timeout=None):
        if self._block is not None:
            self.entered.set()
            self._block.wait(10)
        if self._fail is not None:
            raise self._fail
        return [np.asarray([1.0, 2.0])]

    def submit(self, x):
        raise AssertionError('unused')


def _post_json(port, path, payload, timeout=10):
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        'http://127.0.0.1:%d%s' % (port, path),
        data=json.dumps(payload).encode(),
        headers={'Content-Type': 'application/json'})
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, dict(resp.headers), \
            json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, dict(err.headers), json.loads(err.read())


def test_healthz_503_when_unhealthy_200_when_ok():
    """A load balancer keys on the STATUS CODE: a degraded replica
    must answer 503 (with the JSON detail intact) so it is routed
    around, and 200 again once healthy."""
    import urllib.error
    import urllib.request
    sess = _FakeOneShotSession(status='degraded')
    with serving.ServingHTTPServer(sess, 0) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                'http://127.0.0.1:%d/healthz' % srv.port, timeout=10)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body == {'ok': False, 'status': 'degraded'}
        sess._status = 'ok'
        body = json.loads(urllib.request.urlopen(
            'http://127.0.0.1:%d/healthz' % srv.port,
            timeout=10).read())
        assert body == {'ok': True, 'status': 'ok'}


def test_http_429_carries_retry_after_header():
    sess = _FakeOneShotSession(fail=BackpressureError(5, 4))
    with serving.ServingHTTPServer(sess, 0) as srv:
        code, headers, body = _post_json(srv.port, '/predict',
                                         {'data': [0.0]})
    assert code == 429
    assert body['retry_after_s'] == 2.5
    assert int(headers['Retry-After']) == 3      # ceil(2.5)
    assert body['depth'] == 5 and body['limit'] == 4


def test_http_500_typed_on_aborted_request():
    """worker_crash / preempt abort the request typed: the HTTP layer
    answers a taxonomized 500, never a dropped connection."""
    from mxnet_tpu.resilience.policy import WorkerCrashError
    sess = _FakeOneShotSession(
        fail=WorkerCrashError('worker_crash', 'serving'))
    with serving.ServingHTTPServer(sess, 0) as srv:
        code, _headers, body = _post_json(srv.port, '/predict',
                                          {'data': [0.0]})
    assert code == 500
    assert body['error_class'] == 'WorkerCrashError'
    assert 'WorkerCrashError' in body['error']


def test_http_concurrency_gate_sheds_429():
    """Past max_concurrent in-flight POSTs the endpoint sheds
    instantly with 429 + Retry-After instead of stacking handler
    threads."""
    block = threading.Event()
    sess = _FakeOneShotSession(block=block)
    with serving.ServingHTTPServer(sess, 0, max_concurrent=1) as srv:
        results = {}

        def first():
            results['first'] = _post_json(srv.port, '/predict',
                                          {'data': [0.0]}, timeout=15)

        t = threading.Thread(target=first)
        t.start()
        # the first request holds the one gate slot (proven by it
        # reaching infer); a concurrent POST must shed 429
        assert sess.entered.wait(5.0)
        code, headers, body = _post_json(srv.port, '/predict',
                                         {'data': [0.0]})
        assert code == 429
        assert 'concurrency limit' in body['error']
        assert 'Retry-After' in headers
        block.set()
        t.join(10)
        assert results['first'][0] == 200


def test_http_concurrency_shed_keeps_keepalive_in_sync():
    """The gate 429 must drain the unread request body: on a
    keep-alive connection the leftover bytes would be parsed as the
    NEXT request line, garbling a well-behaved client's retry."""
    import http.client
    block = threading.Event()
    sess = _FakeOneShotSession(block=block)
    with serving.ServingHTTPServer(sess, 0, max_concurrent=1) as srv:
        t = threading.Thread(target=lambda: _post_json(
            srv.port, '/predict', {'data': [0.0]}, timeout=15))
        t.start()
        try:
            assert sess.entered.wait(5.0)   # the slot is held
            conn = http.client.HTTPConnection('127.0.0.1', srv.port,
                                              timeout=10)
            body = json.dumps({'data': [0.0] * 64}).encode()
            hdrs = {'Content-Type': 'application/json',
                    'Content-Length': str(len(body))}
            conn.request('POST', '/predict', body=body, headers=hdrs)
            resp = conn.getresponse()
            assert resp.status == 429
            resp.read()
            # SAME connection: the retry must be parsed as a fresh
            # request (429 again), not a 400 from stale body bytes
            conn.request('POST', '/predict', body=body, headers=hdrs)
            resp = conn.getresponse()
            assert resp.status == 429
            resp.read()
            conn.close()
        finally:
            block.set()
            t.join(10)


def test_session_serve_aborts_typed_on_worker_crash():
    """One-shot path: an injected worker_crash fails the batch with
    the typed error (clients retry), it does NOT complete degraded."""
    from mxnet_tpu.resilience.policy import WorkerCrashError
    mod, x, _ = _fitted_module()
    frozen = serving.freeze(mod, max_batch=4)
    mx.config.set('MXNET_TPU_FAULT', 'worker_crash@serving:1')
    try:
        with serving.InferenceSession(frozen, deadline_ms=1.0,
                                      watchdog=False) as sess:
            with pytest.raises(WorkerCrashError):
                sess.infer(x[0], timeout=30)
            # the engine recovers: the next batch serves clean
            out = sess.infer(x[1], timeout=30)[0]
            st = sess.status()
    finally:
        mx.config.unset('MXNET_TPU_FAULT')
    ref = frozen.run([x[1:2]])[0][0]
    assert np.array_equal(out, ref)
    assert st['batches']['accel'] >= 1
