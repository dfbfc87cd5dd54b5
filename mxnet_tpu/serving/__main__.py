"""Serving selftest (CI stage 'serving', tools/ci.py).

CPU-runnable proof of the inference-engine contract
(docs/SERVING.md), in six legs:

  1. bit_identical  — a mixed stream of concurrent single requests
                      batched through the engine returns outputs
                      BIT-IDENTICAL to direct single-request
                      inference (pad/unpad is exact, batching is
                      invisible to numerics).
  2. recompile      — a mixed-shape request stream compiles at most
                      one program per distinct bucket (the
                      BucketingModule bound applied to the jit cache).
  3. frozen_reload  — a saved ``mxnet_tpu.frozen.v1`` artifact
                      reloads in a FRESH python process and serves
                      with ZERO retraces (trace counter stays empty)
                      and identical outputs.
  4. backpressure   — a full queue rejects with the typed
                      BackpressureError immediately instead of
                      hanging; a queued request past its budget fails
                      with RequestTimeout.
  5. batcher        — deadline flush vs max-batch flush causes, FIFO
                      result integrity under concurrent submitters.
  6. http           — the JSON endpoint is OFF by default and serves
                      /predict, /status, /healthz when constructed.

Autoregressive-decode legs (docs/SERVING.md "Autoregressive
decoding"):

  7. decode_bit_identity — N tokens generated through the in-jit
                      cache (prefill + decode-step programs) equal
                      the tokens from slicing an uncached
                      whole-sequence forward after every token, and
                      the CPU-fallback path emits the same stream.
  8. decode_reload  — a saved decode artifact (prefill ladder + the
                      single step program) reloads in a FRESH process
                      and generates with ZERO retraces and identical
                      tokens.
  9. decode_continuous — continuous-batching contract: concurrent
                      mixed-length generations each match their solo
                      baseline (join/leave never perturbs a
                      neighbor), EOS retires early, FIFO admission
                      holds, and total compiled programs stay <=
                      prefill ladder + 1.
  10. decode_migrate — disaggregated prefill/decode over the live-
                      migration path: a prefill engine exports a
                      just-prefilled sequence
                      (``export_sequence`` seals KV pages + position
                      into a ``mxnet_tpu.seqstate.v1`` payload), a
                      decode engine with a DIFFERENT page size
                      imports it (pages re-chunked in flight) and
                      streams the rest with ZERO prefills — the
                      combined token stream bit-identical to one
                      engine end to end.

``--serve-smoke`` is the fault-injection mode tools/fault_smoke.py
drives (legs 7-8 of the CI fault tier): with
``MXNET_TPU_FAULT=hang@serving.infer:3`` the stall watchdog writes
its artifact, the circuit breaker opens, and requests keep completing
on the CPU fallback (status=degraded); with
``device_loss@serving:3`` the breaker trip dumps the flight ring
(tail event ``breaker_open``). ``--decode-smoke`` is the decode
analog (fault_smoke check 9): ``hang@serving.decode:3`` must write
the stall artifact, trip the breaker, and every in-flight sequence
must complete degraded on the CPU fallback with the same tokens.

Usage:
  JAX_PLATFORMS=cpu python -m mxnet_tpu.serving --out SERVE_SELFTEST.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

from .. import config as _config  # noqa: E402
_config.cpu_rig('serving')

import numpy as onp  # noqa: E402

FEATURES = 8
CLASSES = 4


def _toy_frozen(max_batch=8, buckets=None):
    """Deterministic tiny MLP, trained one epoch, frozen."""
    import mxnet_tpu as mx
    from .freeze import freeze
    onp.random.seed(3)
    mx.random.seed(3)
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu')
    fc2 = mx.sym.FullyConnected(act, num_hidden=CLASSES, name='fc2')
    out = mx.sym.SoftmaxOutput(fc2, name='softmax')
    mod = mx.mod.Module(out, context=mx.cpu())
    rs = onp.random.RandomState(0)
    x = rs.randn(32, FEATURES).astype('float32')
    y = rs.randint(0, CLASSES, (32,)).astype('float32')
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    mod.fit(it, num_epoch=1,
            optimizer_params=(('learning_rate', 0.1),))
    return freeze(mod, max_batch=max_batch, buckets=buckets,
                  name='selftest-mlp')


def _requests(n, seed=7):
    rs = onp.random.RandomState(seed)
    return rs.randn(n, FEATURES).astype('float32')


def check_bit_identical():
    from .server import InferenceSession
    frozen = _toy_frozen()
    x = _requests(13)
    # reference: every example alone through the bucket-1 program
    ref = [frozen.run([x[i:i + 1]])[0][0] for i in range(len(x))]
    with InferenceSession(frozen, deadline_ms=20.0, max_batch=8,
                          watchdog=False) as sess:
        futs = [sess.submit(x[i]) for i in range(len(x))]
        got = [f.result(30)[0] for f in futs]
    bad = [i for i in range(len(x))
           if not onp.array_equal(got[i], ref[i])]
    if bad:
        return ('batched outputs differ from single-request inference '
                'at indices %r (max abs delta %.3g)'
                % (bad, max(float(onp.abs(got[i] - ref[i]).max())
                            for i in bad)))
    return None


def check_recompile_bound():
    frozen = _toy_frozen(max_batch=8)      # ladder 1,2,4,8
    sizes = [1, 3, 8, 2, 5, 8, 1, 7, 4, 6]
    x = _requests(8)
    for n in sizes:
        frozen.run([x[:n]])
    used = {frozen.policy.bucket_for(n) for n in sizes}
    if frozen.compile_count > len(used):
        return ('%d programs compiled for %d distinct buckets %r'
                % (frozen.compile_count, len(used), sorted(used)))
    if frozen.compile_count > len(frozen.policy.buckets):
        return 'compile count exceeds the bucket ladder'
    return None


def check_frozen_reload(tmp):
    frozen = _toy_frozen()
    x = _requests(11)
    expected = frozen.warmup().run([x])[0]
    art = os.path.join(tmp, 'model.frozen')
    frozen.save(art)
    onp.savez(os.path.join(tmp, 'io.npz'), x=x, expected=expected)
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    r = subprocess.run(
        [sys.executable, '-m', 'mxnet_tpu.serving', '--reload-check',
         tmp], env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    if r.returncode != 0:
        return ('reload subprocess exited %d\nstdout:%s\nstderr:%s'
                % (r.returncode, r.stdout[-1500:], r.stderr[-1500:]))
    verdict = json.load(open(os.path.join(tmp, 'reload.json')))
    if not verdict.get('identical'):
        return 'reloaded artifact served different outputs'
    if verdict.get('traces'):
        return ('reloaded artifact retraced: %r (programs did not '
                'deserialize)' % verdict['traces'])
    if verdict.get('retraced_buckets'):
        return ('buckets fell back to re-jit: %r'
                % verdict['retraced_buckets'])
    return None


def run_reload_check(tmp):
    """Fresh-process half of leg 3: load + serve + prove no tracing."""
    from .freeze import FrozenProgram
    frozen = FrozenProgram.load(os.path.join(tmp, 'model.frozen'))
    with onp.load(os.path.join(tmp, 'io.npz')) as z:
        x, expected = z['x'], z['expected']
    got = frozen.run([x])[0]
    verdict = {
        'identical': bool(onp.array_equal(got, expected)),
        'traces': {str(k): v for k, v in frozen.trace_counts.items()},
        'retraced_buckets': list(frozen.retraced_buckets),
        'compiled': frozen.compile_count,
    }
    with open(os.path.join(tmp, 'reload.json'), 'w') as f:
        json.dump(verdict, f, indent=1, sort_keys=True)
    print('reload-check: identical=%s traces=%r'
          % (verdict['identical'], verdict['traces']), flush=True)
    return 0 if verdict['identical'] and not verdict['traces'] else 1


def check_backpressure():
    from .batcher import (BackpressureError, MicroBatcher,
                          RequestTimeout)
    gate = threading.Event()

    def runner(stacked, n):
        gate.wait(30)
        return [stacked[0]]

    b = MicroBatcher(runner, max_batch=1, deadline_ms=0.0, max_queue=2,
                     timeout_s=0.3, name='bp-selftest')
    try:
        # first request occupies the worker (blocked in the runner)...
        futs = [b.submit(onp.zeros(2))]
        deadline = time.monotonic() + 5.0
        while b.stats()['depth'] and time.monotonic() < deadline:
            time.sleep(0.002)
        # ...then 2 more fill the bounded queue
        futs += [b.submit(onp.zeros(2)) for _ in range(2)]
        t0 = time.monotonic()
        try:
            b.submit(onp.zeros(2))
            return 'overflow submit did not raise BackpressureError'
        except BackpressureError as exc:
            if time.monotonic() - t0 > 1.0:
                return 'rejection took %.2fs (must be immediate)' \
                    % (time.monotonic() - t0)
            if exc.limit != 2:
                return 'BackpressureError.limit=%r, want 2' % exc.limit
        # queued (not yet running) requests age out past timeout_s
        try:
            futs[2].result(5)
            return 'queued request did not time out'
        except RequestTimeout:
            pass
        except Exception as exc:
            return ('queued request failed with %s, want '
                    'RequestTimeout' % type(exc).__name__)
    finally:
        gate.set()
        b.close(drain=False)
    return None


def check_batcher_contract():
    from .batcher import MicroBatcher
    calls = []

    def runner(stacked, n):
        calls.append(n)
        return [stacked[0] * 2.0]

    # max-batch flush: 4 instant submits with a huge deadline
    b = MicroBatcher(runner, max_batch=4, deadline_ms=5000.0,
                     max_queue=64, timeout_s=10.0, name='contract')
    futs = [b.submit(onp.full(3, i, dtype='float32'))
            for i in range(4)]
    for i, f in enumerate(futs):
        out = f.result(10)[0]
        if not onp.array_equal(out, onp.full(3, 2.0 * i)):
            return 'FIFO row mapping broken at %d' % i
    if b.stats()['flushes']['full'] < 1:
        return 'no max-batch flush recorded'
    # deadline flush: a single request must not wait for max_batch
    b2 = MicroBatcher(runner, max_batch=64, deadline_ms=10.0,
                      max_queue=64, timeout_s=10.0, name='contract2')
    t0 = time.monotonic()
    out = b2.infer(onp.ones(3))
    if time.monotonic() - t0 > 5.0:
        return 'deadline flush did not fire'
    if b2.stats()['flushes']['deadline'] < 1:
        return 'no deadline flush recorded'
    # FIFO integrity under concurrent submitters
    results = {}

    def client(i):
        results[i] = b2.infer(onp.full(3, i, dtype='float32'))[0]

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(15)
    bad = [i for i in range(16)
           if not onp.array_equal(results.get(i),
                                  onp.full(3, 2.0 * i))]
    b.close()
    b2.close()
    if bad:
        return 'concurrent submitters got wrong rows: %r' % bad
    return None


def check_http():
    import urllib.request
    from .server import InferenceSession, ServingHTTPServer, \
        maybe_start_http_server
    frozen = _toy_frozen()
    with InferenceSession(frozen, deadline_ms=5.0,
                          watchdog=False) as sess:
        if maybe_start_http_server(sess) is not None:
            return ('HTTP server started without '
                    'MXNET_TPU_SERVE_HTTP_PORT')
        with ServingHTTPServer(sess, 0) as srv:
            base = 'http://127.0.0.1:%d' % srv.port
            x = _requests(1)[0]
            req = urllib.request.Request(
                base + '/predict',
                data=json.dumps({'data': x.tolist()}).encode(),
                headers={'Content-Type': 'application/json'})
            body = json.loads(urllib.request.urlopen(
                req, timeout=10).read())
            got = onp.asarray(body['outputs'][0], dtype='float32')
            ref = frozen.run([x[None]])[0][0]
            if not onp.allclose(got, ref, rtol=0, atol=0):
                return 'HTTP /predict outputs differ from engine'
            status = json.loads(urllib.request.urlopen(
                base + '/status', timeout=10).read())
            if status.get('status') not in ('ok', 'degraded'):
                return 'bad /status payload: %r' % status
            health = json.loads(urllib.request.urlopen(
                base + '/healthz', timeout=10).read())
            if 'ok' not in health:
                return 'bad /healthz payload: %r' % health
    return None


def _toy_decoder(slots=3, prefill_buckets=(4, 8)):
    """Deterministic tiny LSTM LM decode program."""
    from .decode import DecodeProgram, init_rnn_lm
    model, params = init_rnn_lm(vocab=23, embed=8, hidden=16, layers=1,
                                mode='lstm', max_len=32, seed=5)
    return DecodeProgram(model, params, slots=slots,
                         prefill_buckets=prefill_buckets,
                         name='selftest-lm')


def _reference_tokens(prog, prompt, n):
    """Greedy tokens via the UNCACHED whole-sequence forward, resliced
    after every token."""
    import jax.numpy as jnp
    params = {k: jnp.asarray(v) for k, v in prog._params_np.items()}
    toks = list(prompt)
    out = []
    for _ in range(n):
        full = prog.model.full_forward(params,
                                       jnp.asarray([toks], 'int32'))
        t = int(onp.asarray(full)[0, -1].argmax())
        out.append(t)
        toks.append(t)
    return out


def check_decode_bit_identity():
    from .server import InferenceSession
    prog = _toy_decoder()
    prompt = [3, 1, 4, 1, 5]
    ref = _reference_tokens(prog, prompt, 6)
    with InferenceSession(prog, watchdog=False) as sess:
        got = sess.generate(prompt, max_new_tokens=6).result(60)
    if got != ref:
        return ('cached decode %r != whole-sequence forward slice %r'
                % (got, ref))
    fb = prog.fallback_generate(prompt, 6)
    if fb != ref:
        return 'CPU fallback stream %r != reference %r' % (fb, ref)
    return None


def check_decode_reload(tmp):
    prog = _toy_decoder().warmup()
    prompt = [5, 3, 1]
    with open(os.path.join(tmp, 'decode_io.json'), 'w') as f:
        json.dump({'prompt': prompt,
                   'expected': _reference_tokens(prog, prompt, 5)}, f)
    art = os.path.join(tmp, 'decoder.frozen')
    prog.save(art)
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    r = subprocess.run(
        [sys.executable, '-m', 'mxnet_tpu.serving',
         '--decode-reload-check', tmp], env=env, capture_output=True,
        text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    if r.returncode != 0:
        return ('decode reload subprocess exited %d\nstdout:%s\n'
                'stderr:%s' % (r.returncode, r.stdout[-1500:],
                               r.stderr[-1500:]))
    verdict = json.load(open(os.path.join(tmp, 'decode_reload.json')))
    if not verdict.get('identical'):
        return 'reloaded decoder generated different tokens'
    if verdict.get('traces'):
        return ('reloaded decoder retraced: %r (programs did not '
                'deserialize)' % verdict['traces'])
    if verdict.get('retraced_buckets'):
        return ('decode programs fell back to re-jit: %r'
                % verdict['retraced_buckets'])
    return None


def run_decode_reload_check(tmp):
    """Fresh-process half of the decode_reload leg."""
    from .server import InferenceSession
    from .freeze import load_frozen
    prog = load_frozen(os.path.join(tmp, 'decoder.frozen'))
    io = json.load(open(os.path.join(tmp, 'decode_io.json')))
    with InferenceSession(prog, watchdog=False) as sess:
        got = sess.generate(io['prompt'],
                            max_new_tokens=len(io['expected'])) \
            .result(60)
    verdict = {
        'identical': got == io['expected'],
        'traces': dict(prog.trace_counts),
        'retraced_buckets': list(prog.retraced_buckets),
        'compiled': prog.compile_count,
    }
    with open(os.path.join(tmp, 'decode_reload.json'), 'w') as f:
        json.dump(verdict, f, indent=1, sort_keys=True)
    print('decode-reload-check: identical=%s traces=%r'
          % (verdict['identical'], verdict['traces']), flush=True)
    return 0 if verdict['identical'] and not verdict['traces'] else 1


def check_decode_continuous():
    """Continuous-batching contract on the real model: solo == joined
    streams, EOS retirement, bounded compiles."""
    from .server import InferenceSession
    prog = _toy_decoder(slots=2)        # fewer slots than requests
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8], [2, 2], [7, 1]]
    lens = [5, 3, 6, 2, 4]
    with InferenceSession(prog, watchdog=False) as sess:
        solo = [sess.generate(p, max_new_tokens=n).result(60)
                for p, n in zip(prompts, lens)]
        streams = [sess.generate(p, max_new_tokens=n)
                   for p, n in zip(prompts, lens)]
        joined = [s.result(60) for s in streams]
        if joined != solo:
            bad = [i for i in range(len(solo))
                   if joined[i] != solo[i]]
            return ('join/leave perturbed sequences %r '
                    '(continuous != solo)' % bad)
        # EOS retirement: replay the first stream with its 2nd token
        # as the stop symbol — generation must truncate at that
        # token's FIRST occurrence
        eos = solo[0][1]
        want = solo[0][:solo[0].index(eos) + 1]
        s = sess.generate(prompts[0], max_new_tokens=50, eos_id=eos)
        got = s.result(60)
        if got != want or s.finish_reason != 'eos':
            return ('EOS retirement broken: %r (reason %r), want %r'
                    % (got, s.finish_reason, want))
        counts = sess.status()['decode']['counts']
    if counts['retired'].get('eos', 0) < 1:
        return 'no eos retirement recorded: %r' % (counts['retired'],)
    bound = len(prog.prefill_buckets) + 1
    if prog.compile_count > bound:
        return ('%d programs compiled; decode bound is prefill ladder'
                ' + 1 = %d' % (prog.compile_count, bound))
    retraced = {k: v for k, v in prog.trace_counts.items() if v > 1}
    if retraced:
        return 'programs retraced after warmup: %r' % retraced
    return None


def check_decode_migrate():
    """Leg 10: the prefill/decode disaggregation probe
    (docs/SERVING.md "Drain & live migration")."""
    from .server import InferenceSession
    from .decode import PagedDecodeProgram, init_transformer_lm
    model, params = init_transformer_lm(vocab=23, units=16, hidden=32,
                                        layers=1, heads=2, max_len=64,
                                        seed=11)
    prompt = [3, 5, 7, 11, 2, 9, 4]
    n = 12

    def paged(page_size, pages):
        return PagedDecodeProgram(model, params, slots=2,
                                  prefill_buckets=(8,),
                                  page_size=page_size, pages=pages,
                                  name='selftest-mig%d' % page_size)

    with InferenceSession(paged(8, 32), watchdog=False) as ref:
        want = ref.generate(prompt, max_new_tokens=n).result(60)
    with InferenceSession(paged(8, 32), watchdog=False) as pre, \
            InferenceSession(paged(16, 16), watchdog=False) as dec:
        s = pre.generate(prompt, max_new_tokens=n)
        next(iter(s))            # prefill landed (first token out)
        payload = pre._engine.export_sequence(s, timeout=30)
        if s.finish_reason != 'migrated':
            return ('exported stream finished %r, want migrated'
                    % s.finish_reason)
        if payload.get('schema') != 'mxnet_tpu.seqstate.v1':
            return 'bad payload schema: %r' % payload.get('schema')
        stream = dec._engine.import_sequence(payload)
        got = list(payload['emitted']) + list(stream)
        pre_counts = pre._engine._counts
        dec_counts = dec._engine._counts
    if got != want:
        return ('disaggregated stream %r != single-engine %r'
                % (got, want))
    if dec_counts['prefills'] != 0:
        return ('decode engine ran %d prefills; the handoff must '
                'skip prefill entirely' % dec_counts['prefills'])
    if pre_counts['prefills'] != 1 \
            or pre_counts['migrated_out'] != 1 \
            or dec_counts['migrated_in'] != 1:
        return ('migration counters off: prefill side %r, decode '
                'side %r' % (pre_counts, dec_counts))
    return None


def run_decode_smoke(args):
    """Decode fault-injection mode (tools/fault_smoke.py check 9)."""
    from mxnet_tpu import observability
    from .server import InferenceSession
    observability.configure_flight(path=args.flight_artifact,
                                   name='decode-smoke')
    prog = _toy_decoder(slots=2, prefill_buckets=(8,))
    prompt = [3, 1, 4, 1, 5]
    ref = prog.fallback_generate(prompt, 6)
    served = 0
    mismatches = 0
    degraded_streams = 0
    with InferenceSession(prog, timeout_s=120.0,
                          stall_artifact=args.stall_artifact) as sess:
        streams = [sess.generate(prompt, max_new_tokens=6)
                   for _ in range(args.requests)]
        for s in streams:
            try:
                toks = s.result(240)
                served += 1
            except Exception:
                continue
            if toks != ref:
                mismatches += 1
            if s.degraded:
                degraded_streams += 1
        status = sess.status()
    verdict = {
        'requests': args.requests,
        'served': served,
        'mismatches': mismatches,
        'degraded_streams': degraded_streams,
        'status': status['status'],
        'breaker': status['breaker'],
        'fallback_tokens':
            status['decode']['counts']['fallback_tokens'],
        'stall_artifact': args.stall_artifact
        if os.path.exists(args.stall_artifact) else None,
    }
    from ..resilience.checkpoint import atomic_write_bytes
    atomic_write_bytes(args.out, (json.dumps(
        verdict, indent=1, sort_keys=True) + '\n').encode())
    print('decode-smoke: served %d/%d status=%s breaker=%s '
          'degraded_streams=%d -> %s'
          % (served, args.requests, verdict['status'],
             verdict['breaker'], degraded_streams, args.out),
          flush=True)
    return 0 if served == args.requests and mismatches == 0 else 1


def run_serve_smoke(args):
    """Fault-injection mode (tools/fault_smoke.py legs 7-8)."""
    from mxnet_tpu import observability
    from .server import InferenceSession
    observability.configure_flight(path=args.flight_artifact,
                                   name='serving-smoke')
    frozen = _toy_frozen()
    x = _requests(args.requests)
    ref = [frozen.run_fallback([x[i:i + 1]])[0][0]
           for i in range(len(x))]
    served = 0
    mismatches = 0
    with InferenceSession(frozen, deadline_ms=1.0, max_batch=1,
                          stall_artifact=args.stall_artifact) as sess:
        for i in range(len(x)):
            out = sess.infer(x[i], timeout=60)[0]
            served += 1
            # fallback-served rows must still be numerically right
            if not onp.allclose(out, ref[i], atol=1e-5):
                mismatches += 1
        status = sess.status()
    verdict = {
        'requests': len(x),
        'served': served,
        'mismatches': mismatches,
        'status': status['status'],
        'breaker': status['breaker'],
        'fallback_batches': status['batches']['fallback'],
        'accel_batches': status['batches']['accel'],
        'stall_artifact': args.stall_artifact
        if os.path.exists(args.stall_artifact) else None,
    }
    from ..resilience.checkpoint import atomic_write_bytes
    atomic_write_bytes(args.out, (json.dumps(
        verdict, indent=1, sort_keys=True) + '\n').encode())
    print('serve-smoke: served %d/%d status=%s breaker=%s '
          'fallback=%d -> %s'
          % (served, len(x), verdict['status'], verdict['breaker'],
             verdict['fallback_batches'], args.out), flush=True)
    return 0 if served == len(x) and mismatches == 0 else 1


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m mxnet_tpu.serving',
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument('--out', default='SERVE_SELFTEST.json')
    p.add_argument('--reload-check', default=None, metavar='DIR',
                   help='internal: fresh-process half of the '
                        'frozen_reload leg')
    p.add_argument('--decode-reload-check', default=None, metavar='DIR',
                   help='internal: fresh-process half of the '
                        'decode_reload leg')
    p.add_argument('--serve-smoke', action='store_true',
                   help='fault-injection mode (fault_smoke legs 7-8)')
    p.add_argument('--decode-smoke', action='store_true',
                   help='decode fault-injection mode (fault_smoke '
                        'check 9)')
    p.add_argument('--requests', type=int, default=8)
    p.add_argument('--stall-artifact', default='STALL.json')
    p.add_argument('--flight-artifact', default='FLIGHT.jsonl')
    args = p.parse_args(argv)

    if args.reload_check:
        return run_reload_check(args.reload_check)
    if args.decode_reload_check:
        return run_decode_reload_check(args.decode_reload_check)
    if args.serve_smoke:
        return run_serve_smoke(args)
    if args.decode_smoke:
        return run_decode_smoke(args)

    checks = {}
    with tempfile.TemporaryDirectory() as tmp:
        legs = [('bit_identical', check_bit_identical),
                ('recompile', check_recompile_bound),
                ('frozen_reload', lambda: check_frozen_reload(tmp)),
                ('backpressure', check_backpressure),
                ('batcher', check_batcher_contract),
                ('http', check_http),
                ('decode_bit_identity', check_decode_bit_identity),
                ('decode_reload', lambda: check_decode_reload(tmp)),
                ('decode_continuous', check_decode_continuous),
                ('decode_migrate', check_decode_migrate)]
        for name, fn in legs:
            try:
                problem = fn()
            except Exception as exc:
                import traceback
                traceback.print_exc()
                problem = '%s: %s' % (type(exc).__name__, exc)
            checks[name] = problem or 'ok'
            print('selftest %-13s %s' % (name, checks[name]),
                  flush=True)
    ok = all(v == 'ok' for v in checks.values())
    verdict = {'ok': ok, 'checks': checks}
    try:
        from ..resilience.checkpoint import atomic_write_bytes
        atomic_write_bytes(args.out, (json.dumps(
            verdict, indent=1, sort_keys=True) + '\n').encode())
    except Exception:
        with open(args.out, 'w') as f:
            json.dump(verdict, f, indent=1, sort_keys=True)
    print('selftest: %s -> %s' % ('OK' if ok else 'FAIL', args.out),
          flush=True)
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
