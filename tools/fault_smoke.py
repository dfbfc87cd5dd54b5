#!/usr/bin/env python
"""Fault-injection CI tier (tools/ci.py stage 'fault-inject').

Eight checks:
  1. tests/test_resilience.py passes (policy math, checkpoint resume,
     worker restart — the deterministic fault suite).
  2. bench.py in forced-degraded mode: with
     MXNET_TPU_FAULT=device_unavailable the bench must EXIT 0 and write
     an artifact whose status != "ok" with the full degraded-mode
     schema (docs/RESILIENCE.md) — a backend-init traceback is the
     regression this tier gates against.
  3. NaN-injection guardrail contract: with MXNET_TPU_FAULT=nan@grads:2
     the guardrail selftest (python -m mxnet_tpu.guardrail) must skip
     both poisoned updates with params bit-identical, halve the loss
     scale each time, trip the persistent-non-finite policy, roll back
     to the last-good snapshot, and replay to within 1e-5 of an
     uninterrupted run (docs/GUARDRAILS.md).
  4. Preemption contract (python -m mxnet_tpu.resilience): an injected
     SIGTERM-analog mid-run must drain an emergency checkpoint and
     exit with the resumable rc; re-running the same command must
     resume at the preempted step and finish with params
     BIT-IDENTICAL to an uninterrupted run.
  5. Elastic mesh shrink: the same checkpoint resumed on a HALVED
     virtual mesh (8 -> 4 devices) must engage 2-step gradient
     accumulation and match the uninterrupted loss trajectory to fp32
     tolerance.
  6. Stall watchdog: an injected hang@train.step must be detected
     within the stall budget and emit the structured
     mxnet_tpu.stall.v1 artifact.

Checks 4 and 6 additionally assert the flight-recorder contract
(docs/OBSERVABILITY.md): the injected preempt and hang escalations
must each dump a parseable mxnet_tpu.flight.v1 JSONL artifact whose
tail event matches the fault site (preempt_exit@9 / stall@3).

  7. Serving hang (python -m mxnet_tpu.serving --serve-smoke,
     docs/SERVING.md): with MXNET_TPU_FAULT=hang@serving.infer:3 the
     inference engine's stall watchdog must write the
     mxnet_tpu.stall.v1 artifact, the circuit breaker must open
     after the threshold, and every request must still complete on
     the CPU fallback with the verdict JSON reporting
     status=degraded.
  8. Serving device loss: with MXNET_TPU_FAULT=device_loss@serving:3
     the breaker trip must dump the flight ring with tail event
     breaker_open at the tripping batch, and the session keeps
     serving degraded (all requests complete, zero mismatches).
  9. Decode hang (python -m mxnet_tpu.serving --decode-smoke,
     docs/SERVING.md "Autoregressive decoding"): with
     MXNET_TPU_FAULT=hang@serving.decode:3 the decode engine's
     watchdog must write the stall artifact (phase=decode), the
     breaker must trip, and every in-flight SEQUENCE must complete
     degraded on the CPU fallback with bit-identical tokens
     (status=degraded, breaker=open, zero mismatches).

  10. Prefetch hang (docs/PERFORMANCE.md): with
     MXNET_TPU_FAULT=hang@io.prefetch:1 the input-staging thread of
     Module.fit wedges mid-stage; fit must degrade to synchronous
     transfers (recovering the pending batch) and finish with params
     bit-identical to a staging-off run — never deadlock.

Usage: python tools/fault_smoke.py [--skip-tests]
(--skip-tests runs only the subprocess contract checks; ci.py's fast
tier already ran the test files, so the gate uses it to avoid double
work.)
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REQUIRED_KEYS = {'schema', 'name', 'status', 'backend', 'resumable',
                  'error', 'payload'}
_REQUIRED_BACKEND_KEYS = {'state', 'platform', 'device_kind',
                          'device_count', 'attempts', 'error'}
_REQUIRED_RESUMABLE_KEYS = {'preempted', 'reason', 'exit_code'}
_RESUMABLE_RC = 75          # MXNET_TPU_PREEMPT_EXIT_CODE default
_STALL_KEYS = {'schema', 'name', 'phase', 'step', 'waited_s',
               'budget_s', 'pid', 'thread_stacks'}
_FLIGHT_SCHEMA = 'mxnet_tpu.flight.v1'
_FLIGHT_HEADER_KEYS = {'schema', 'name', 'reason', 'pid', 'dumped_at',
                       'capacity', 'recorded', 'dropped', 'events'}


def _check_flight(path, reason, tail_kind, tail_step):
    """Validate a flight-recorder dump (docs/OBSERVABILITY.md): JSONL,
    v1 header, and a tail event matching the injected fault site.
    Returns a list of problems (empty = ok)."""
    problems = []
    if not os.path.exists(path):
        return ['no flight artifact at %s' % path]
    lines = [ln for ln in open(path).read().splitlines() if ln.strip()]
    if not lines:
        return ['flight artifact %s is empty' % path]
    try:
        header = json.loads(lines[0])
        events = [json.loads(ln) for ln in lines[1:]]
    except ValueError as exc:
        return ['flight artifact not parseable JSONL: %s' % exc]
    if header.get('schema') != _FLIGHT_SCHEMA:
        problems.append('flight schema %r != %r'
                        % (header.get('schema'), _FLIGHT_SCHEMA))
    if not _FLIGHT_HEADER_KEYS <= set(header):
        problems.append('flight header keys %s missing %s'
                        % (sorted(header),
                           sorted(_FLIGHT_HEADER_KEYS - set(header))))
    if header.get('reason') != reason:
        problems.append('flight reason %r, want %r'
                        % (header.get('reason'), reason))
    if header.get('events') != len(events):
        problems.append('flight header says %r events, file has %d'
                        % (header.get('events'), len(events)))
    if not events:
        problems.append('flight dump has no events')
        return problems
    tail = events[-1]
    if tail.get('kind') != tail_kind:
        problems.append('flight tail event kind %r, want %r (tail: %r)'
                        % (tail.get('kind'), tail_kind, tail))
    elif tail.get('step') != tail_step:
        problems.append('flight tail event at step %r, want %r'
                        % (tail.get('step'), tail_step))
    return problems


def _selftest(argv, devices, fault=None, timeout=420):
    """Run `python -m mxnet_tpu.resilience` on a virtual CPU mesh."""
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=%d'
                         % devices)
    env.pop('MXNET_TPU_FAULT', None)
    if fault:
        env['MXNET_TPU_FAULT'] = fault
    return subprocess.run(
        [sys.executable, '-m', 'mxnet_tpu.resilience'] + argv
        + ['--devices', str(devices)],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


def run_faulted_bench():
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, 'BENCH.json')
        env = dict(os.environ,
                   MXNET_TPU_FAULT='device_unavailable',
                   JAX_PLATFORMS='cpu')
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, 'bench.py'),
             '--out', out],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        if r.returncode != 0:
            print('FAIL: faulted bench exited %d (must degrade, not '
                  'crash)\nstdout:\n%s\nstderr:\n%s'
                  % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
            return False
        if not os.path.exists(out):
            print('FAIL: faulted bench wrote no artifact')
            return False
        art = json.load(open(out))
        problems = []
        if set(art) != _REQUIRED_KEYS:
            problems.append('artifact keys %s != required %s'
                            % (sorted(art), sorted(_REQUIRED_KEYS)))
        elif set(art['backend']) != _REQUIRED_BACKEND_KEYS:
            problems.append('backend keys %s != required %s'
                            % (sorted(art['backend']),
                               sorted(_REQUIRED_BACKEND_KEYS)))
        elif set(art['resumable']) != _REQUIRED_RESUMABLE_KEYS:
            problems.append('resumable keys %s != required %s'
                            % (sorted(art['resumable']),
                               sorted(_REQUIRED_RESUMABLE_KEYS)))
        if art.get('status') == 'ok':
            problems.append("status is 'ok' under forced device fault")
        if art.get('status') not in ('degraded', 'unavailable'):
            problems.append('status %r not a degraded status'
                            % art.get('status'))
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        print('faulted bench: rc=0, status=%r, schema ok'
              % art['status'])
        return True


def run_nan_guardrail():
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, 'GUARD_SELFTEST.json')
        env = dict(os.environ, MXNET_TPU_FAULT='nan@grads:2',
                   JAX_PLATFORMS='cpu')
        r = subprocess.run(
            [sys.executable, '-m', 'mxnet_tpu.guardrail', '--out', out],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        if r.returncode != 0:
            print('FAIL: guardrail selftest exited %d\nstdout:\n%s\n'
                  'stderr:\n%s' % (r.returncode, r.stdout[-2000:],
                                   r.stderr[-2000:]))
            return False
        if not os.path.exists(out):
            print('FAIL: guardrail selftest wrote no verdict artifact')
            return False
        v = json.load(open(out))
        problems = []
        if v.get('skips', 0) < 2:
            problems.append('expected >= 2 skipped updates, got %r'
                            % v.get('skips'))
        if v.get('rollbacks', 0) < 1:
            problems.append('no rollback happened')
        if not v.get('converged'):
            problems.append('replay did not converge (loss_delta=%r, '
                            'param_delta=%r)' % (v.get('loss_delta'),
                                                 v.get('param_delta')))
        if v.get('report_schema') != 'mxnet_tpu.guardrail.v1':
            problems.append('quarantine report schema %r'
                            % v.get('report_schema'))
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        print('nan guardrail: rc=0, skips=%d, rollbacks=%d, '
              'loss_delta=%.2g' % (v['skips'], v['rollbacks'],
                                   v['loss_delta']))
        return True


def run_preempt_resume():
    """Checks 4+5: preempt -> resumable rc -> bit-identical resume,
    then the same checkpoint resumed on a halved mesh to fp32
    tolerance."""
    with tempfile.TemporaryDirectory() as tmp:
        ref_out = os.path.join(tmp, 'ref.json')
        a_out = os.path.join(tmp, 'a.json')
        b_out = os.path.join(tmp, 'b.json')
        c_out = os.path.join(tmp, 'c.json')
        d_ref = os.path.join(tmp, 'ck_ref')
        d_run = os.path.join(tmp, 'ck_run')
        train = ['--train', '--steps', '18', '--ckpt-dir']

        # uninterrupted reference on the 8-device virtual mesh
        r = _selftest(train + [d_ref, '--out', ref_out], devices=8)
        if r.returncode != 0:
            print('FAIL: uninterrupted selftest exited %d\n%s\n%s'
                  % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
            return False
        ref = json.load(open(ref_out))

        # preempted run: must exit with the RESUMABLE rc, not 0/1
        flight = os.path.join(tmp, 'FLIGHT_preempt.jsonl')
        r = _selftest(train + [d_run, '--out', a_out,
                               '--flight-artifact', flight], devices=8,
                      fault='preempt@train.step.9:1')
        if r.returncode != _RESUMABLE_RC:
            print('FAIL: preempted run exited %d, want resumable rc %d'
                  '\n%s\n%s' % (r.returncode, _RESUMABLE_RC,
                                r.stdout[-2000:], r.stderr[-2000:]))
            return False
        if not any(f.endswith('.ckpt') for f in os.listdir(d_run)):
            print('FAIL: preempted run drained no emergency checkpoint')
            return False
        # the preemption must also have dumped a flight-recorder
        # artifact whose tail is the preempt_exit at the fault site
        problems = _check_flight(flight, reason='preempt',
                                 tail_kind='preempt_exit', tail_step=9)
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        print('flight(preempt): %s schema ok, tail=preempt_exit@9'
              % _FLIGHT_SCHEMA)
        # snapshot the drained state NOW: the same-mesh resume below
        # writes newer checkpoints into d_run, and the elastic leg
        # must resume from the preemption point, not from those
        d_elastic = os.path.join(tmp, 'ck_elastic')
        shutil.copytree(d_run, d_elastic)

        # restart, same command: bit-identical params to the reference
        r = _selftest(train + [d_run, '--out', b_out], devices=8)
        if r.returncode != 0:
            print('FAIL: resumed run exited %d\n%s\n%s'
                  % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
            return False
        b = json.load(open(b_out))
        problems = []
        if b['start_step'] != 9:
            problems.append('resumed at step %r, want 9'
                            % b['start_step'])
        if b['param_hash'] != ref['param_hash']:
            problems.append(
                'resumed params NOT bit-identical to uninterrupted '
                '(%s != %s)' % (b['param_hash'][:12],
                                ref['param_hash'][:12]))
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        print('preempt/resume: rc=%d on preempt, resumed@9, params '
              'bit-identical' % _RESUMABLE_RC)

        # elastic shrink: resume the preemption-time checkpoint on 4
        # devices. The emergency checkpoint at step 9 is the newest;
        # the shrunk run must engage accum=2 and track the reference
        # losses over the whole remaining window.
        r = _selftest(train + [d_elastic, '--out', c_out], devices=4)
        if r.returncode != 0:
            print('FAIL: elastic resume exited %d\n%s\n%s'
                  % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
            return False
        c = json.load(open(c_out))
        problems = []
        if c['accum'] != 2 or c['mesh'].get('dp') != 4:
            problems.append('elastic plan accum=%r mesh=%r, want '
                            'accum=2 dp=4' % (c['accum'], c['mesh']))
        # the resumed run starts from the step-9 checkpoint the run on
        # 8 devices drained; compare its per-step losses to the same
        # window of the uninterrupted run (fp32 tolerance: reduction
        # order changes across meshes, bit-exactness does not hold)
        start = c['start_step']
        ref_window = ref['losses'][start:]
        if len(c['losses']) != len(ref_window) or not ref_window:
            problems.append('elastic run produced %d losses, want %d'
                            % (len(c['losses']), len(ref_window)))
        else:
            worst = max(abs(x - y) / max(abs(y), 1e-6)
                        for x, y in zip(c['losses'], ref_window))
            if worst > 5e-3:
                problems.append('elastic loss trajectory diverged: '
                                'worst rel err %.2e > 5e-3' % worst)
            else:
                print('elastic shrink: dp 8->4, accum=2, worst rel '
                      'loss err %.2e' % worst)
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        return True


def run_watchdog_smoke():
    """Check 6: injected hang detected within the stall budget, with
    the structured mxnet_tpu.stall.v1 artifact."""
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, 'w.json')
        stall = os.path.join(tmp, 'STALL.json')
        flight = os.path.join(tmp, 'FLIGHT_stall.jsonl')
        r = _selftest(['--watchdog-smoke', '--steps', '6', '--out', out,
                       '--stall-artifact', stall,
                       '--flight-artifact', flight], devices=1,
                      fault='hang@train.step.3:1')
        if r.returncode != 0:
            print('FAIL: watchdog smoke exited %d\n%s\n%s'
                  % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
            return False
        verdict = json.load(open(out))
        problems = []
        if not verdict.get('detected'):
            problems.append('hang not detected')
        if not os.path.exists(stall):
            problems.append('no stall artifact written')
        else:
            art = json.load(open(stall))
            if set(art) != _STALL_KEYS:
                problems.append('stall artifact keys %s != %s'
                                % (sorted(art), sorted(_STALL_KEYS)))
            elif art['schema'] != 'mxnet_tpu.stall.v1':
                problems.append('stall schema %r' % art['schema'])
        # the stall escalation must also dump the flight ring; its
        # tail event is the stall record at the injected step
        problems += _check_flight(flight, reason='stall',
                                  tail_kind='stall', tail_step=3)
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        print('watchdog: injected hang@step.3 detected, stall artifact '
              'schema ok, flight tail=stall@3')
        return True


def _serve_smoke(fault, requests, out, stall, flight, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('MXNET_TPU_FAULT', None)
    env['MXNET_TPU_FAULT'] = fault
    return subprocess.run(
        [sys.executable, '-m', 'mxnet_tpu.serving', '--serve-smoke',
         '--requests', str(requests), '--out', out,
         '--stall-artifact', stall, '--flight-artifact', flight],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


def run_serving_hang():
    """Check 7: injected hang@serving.infer -> stall artifact +
    breaker open + every request served degraded."""
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, 'v.json')
        stall = os.path.join(tmp, 'STALL.json')
        flight = os.path.join(tmp, 'FLIGHT.jsonl')
        r = _serve_smoke('hang@serving.infer:3', 8, out, stall, flight)
        if r.returncode != 0:
            print('FAIL: serving hang smoke exited %d\n%s\n%s'
                  % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
            return False
        v = json.load(open(out))
        problems = []
        if v.get('served') != v.get('requests'):
            problems.append('only %r/%r requests served'
                            % (v.get('served'), v.get('requests')))
        if v.get('status') != 'degraded':
            problems.append('status %r, want degraded'
                            % v.get('status'))
        if v.get('breaker') != 'open':
            problems.append('breaker %r, want open' % v.get('breaker'))
        if v.get('mismatches'):
            problems.append('%d fallback outputs numerically wrong'
                            % v['mismatches'])
        if not os.path.exists(stall):
            problems.append('no stall artifact written')
        else:
            art = json.load(open(stall))
            if set(art) != _STALL_KEYS:
                problems.append('stall artifact keys %s != %s'
                                % (sorted(art), sorted(_STALL_KEYS)))
            elif art['schema'] != 'mxnet_tpu.stall.v1':
                problems.append('stall schema %r' % art['schema'])
            elif art['phase'] != 'infer':
                problems.append('stall phase %r, want infer'
                                % art['phase'])
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        print('serving hang: stall artifact ok, breaker=open, '
              '%d/%d requests served degraded'
              % (v['served'], v['requests']))
        return True


def run_serving_device_loss():
    """Check 8: injected device_loss@serving -> cpu-fallback serving
    continues; the flight dump tail records the breaker trip."""
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, 'v.json')
        stall = os.path.join(tmp, 'STALL.json')
        flight = os.path.join(tmp, 'FLIGHT.jsonl')
        r = _serve_smoke('device_loss@serving:3', 8, out, stall,
                         flight)
        if r.returncode != 0:
            print('FAIL: serving device-loss smoke exited %d\n%s\n%s'
                  % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
            return False
        v = json.load(open(out))
        problems = []
        if v.get('served') != v.get('requests') or v.get('mismatches'):
            problems.append('fallback serving broken: %r' % v)
        if v.get('status') != 'degraded':
            problems.append('status %r, want degraded'
                            % v.get('status'))
        if not v.get('fallback_batches'):
            problems.append('no batches served on the CPU fallback')
        # breaker opens at the 3rd consecutive failure = batch 2; the
        # trip dumps the flight ring with the trip event as its tail
        problems += _check_flight(flight, reason='breaker',
                                  tail_kind='breaker_open',
                                  tail_step=2)
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        print('serving device-loss: cpu-fallback served %d/%d, '
              'flight tail=breaker_open@2' % (v['served'],
                                              v['requests']))
        return True


def run_decode_hang():
    """Check 9: injected hang@serving.decode -> stall artifact +
    breaker trip + every in-flight sequence completes degraded on the
    CPU fallback with the same tokens."""
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, 'v.json')
        stall = os.path.join(tmp, 'STALL.json')
        flight = os.path.join(tmp, 'FLIGHT.jsonl')
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        env.pop('MXNET_TPU_FAULT', None)
        env['MXNET_TPU_FAULT'] = 'hang@serving.decode:3'
        r = subprocess.run(
            [sys.executable, '-m', 'mxnet_tpu.serving',
             '--decode-smoke', '--requests', '6', '--out', out,
             '--stall-artifact', stall, '--flight-artifact', flight],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        if r.returncode != 0:
            print('FAIL: decode hang smoke exited %d\n%s\n%s'
                  % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
            return False
        v = json.load(open(out))
        problems = []
        if v.get('served') != v.get('requests'):
            problems.append('only %r/%r sequences completed'
                            % (v.get('served'), v.get('requests')))
        if v.get('mismatches'):
            problems.append('%d degraded sequences decoded wrong '
                            'tokens' % v['mismatches'])
        if v.get('status') != 'degraded':
            problems.append('status %r, want degraded'
                            % v.get('status'))
        if v.get('breaker') != 'open':
            problems.append('breaker %r, want open' % v.get('breaker'))
        if not v.get('degraded_streams'):
            problems.append('no sequence flagged degraded')
        if not v.get('fallback_tokens'):
            problems.append('no tokens decoded on the CPU fallback')
        if not os.path.exists(stall):
            problems.append('no stall artifact written')
        else:
            art = json.load(open(stall))
            if set(art) != _STALL_KEYS:
                problems.append('stall artifact keys %s != %s'
                                % (sorted(art), sorted(_STALL_KEYS)))
            elif art['schema'] != 'mxnet_tpu.stall.v1':
                problems.append('stall schema %r' % art['schema'])
            elif art['phase'] != 'decode':
                problems.append('stall phase %r, want decode'
                                % art['phase'])
        if problems:
            print('FAIL: ' + '; '.join(problems))
            return False
        print('decode hang: stall artifact ok (phase=decode), '
              'breaker=open, %d/%d sequences completed degraded '
              '(%d fallback tokens)'
              % (v['served'], v['requests'], v['fallback_tokens']))
        return True


_PREFETCH_SCRIPT = r'''
import hashlib, json
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import io as mio

def run(prefetch):
    mx.random.seed(0); np.random.seed(0)
    X = np.random.RandomState(1).randn(48, 8).astype("float32")
    Y = np.random.RandomState(2).randint(0, 4, (48,)).astype("float32")
    it = mio.NDArrayIter(X, Y, batch_size=8, label_name="sm_label")
    d = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="sm")
    mod = mx.mod.Module(net, label_names=("sm_label",))
    mod.fit(it, num_epoch=2,
            optimizer_params=(("learning_rate", 0.1),),
            prefetch=prefetch)
    h = hashlib.sha256()
    params = mod.get_params()[0]
    for k in sorted(params):
        h.update(params[k].asnumpy().tobytes())
    return h.hexdigest()

ref = run(0)       # staging off: the site never fires, fault unspent
faulted = run(2)   # staging on: hang@io.prefetch:1 wedges the thread
from mxnet_tpu import observability as obs
fam = obs.snapshot().get("mxnet_tpu_prefetch_degraded_total")
deg = fam["series"][0]["value"] if fam and fam["series"] else 0
print(json.dumps({"match": ref == faulted, "degraded": deg}))
'''


def run_prefetch_hang():
    """Check 10: injected hang in the input-staging thread
    (hang@io.prefetch) must degrade Module.fit to synchronous
    transfers — completing with params BIT-IDENTICAL to the
    staging-off run (no batch dropped or duplicated) — instead of
    deadlocking fit (docs/PERFORMANCE.md)."""
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               MXNET_TPU_FAULT='hang@io.prefetch:1',
               MXNET_TPU_PREFETCH_TIMEOUT_S='1')
    r = subprocess.run([sys.executable, '-c', _PREFETCH_SCRIPT],
                       cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=300)
    if r.returncode != 0:
        print('FAIL: prefetch hang smoke exited %d (deadlock or '
              'crash)\nstdout:\n%s\nstderr:\n%s'
              % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
        return False
    try:
        v = json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print('FAIL: prefetch hang smoke wrote no verdict JSON:\n%s'
              % r.stdout[-2000:])
        return False
    problems = []
    if not v.get('match'):
        problems.append('degraded-prefetch params differ from the '
                        'synchronous run (batch dropped/duplicated?)')
    if not v.get('degraded'):
        problems.append('staging never degraded — the injected hang '
                        'did not reach the staging thread')
    if problems:
        print('FAIL: ' + '; '.join(problems))
        return False
    print('prefetch hang: staging degraded to synchronous transfer, '
          'params bit-identical to the unstaged run')
    return True


def run_resilience_tests():
    r = subprocess.run(
        [sys.executable, '-m', 'pytest', 'tests/test_resilience.py',
         'tests/test_guardrail.py', 'tests/test_elastic.py', '-q',
         '-p', 'no:cacheprovider'],
        cwd=REPO)
    return r.returncode == 0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    ok = True
    if '--skip-tests' not in argv:
        ok = run_resilience_tests()
    ok = run_faulted_bench() and ok
    ok = run_nan_guardrail() and ok
    ok = run_preempt_resume() and ok
    ok = run_watchdog_smoke() and ok
    ok = run_serving_hang() and ok
    ok = run_serving_device_loss() and ok
    ok = run_decode_hang() and ok
    ok = run_prefetch_hang() and ok
    print('fault_smoke: %s' % ('OK' if ok else 'FAIL'))
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
