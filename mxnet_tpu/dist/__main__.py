"""Dist selftest (CI stage 'dist', tools/ci.py; docs/DISTRIBUTED.md).

CPU-runnable proof of the pod-scale multi-host contract over the local
launcher (two real processes, one virtual device each, Gloo
collectives), in seven legs:

  1. join            two processes join via the DMLC_* env, agree on a
                     broadcast seed, pass a named barrier, see each
                     other's heartbeats, and report complementary
                     per-host data shards of the global dp=2 mesh;
                     plus: a DMLC_ROLE=server process with the same
                     env must NOT join (scheduler/server roles are
                     launch-compat no-ops).
  2. init_timeout    a worker pointed at a dead coordinator fails with
                     the typed DistInitError within the
                     MXNET_TPU_DIST_INIT_TIMEOUT_S budget — import
                     never blocks forever.
  3. barrier_timeout a peer that never arrives surfaces as a typed
                     HostLostError within the barrier budget — the
                     collective-hang failure mode is gone.
  4. bit_identity    THE tentpole gate: dp=2 across two processes
                     (ZeRO sharded update on, per-host data shards)
                     trains 10 steps with losses AND final params
                     bit-identical to the single-process dp=2 run at
                     the same global batch.
  5. guarded         same shape through the in-jit guardrail with one
                     injected NaN step: skip is lockstep across hosts,
                     trajectory still bit-identical to single-process.
  6. ckpt_resume     the checkpoint written at process_count=2 (rank 0
                     behind a barrier, cross-host ZeRO shards gathered
                     in-program) resumes bit-identically at
                     process_count=1 and finishes on the baseline
                     trajectory.
  7. host_loss       rank 1 dies mid-run: rank 0 gets the typed
                     HostLostError within budget, exits with the
                     resumable rc (75) which the launcher propagates,
                     and the surviving host re-forms the mesh from the
                     last checkpoint via elastic.host_loss_plan
                     (dp 2→1, grad-accum 2) tracking the unshrunk
                     losses to fp tolerance.
  8. gateway         two live serving replicas behind the gateway:
                     requests succeed, one replica dies, the gateway
                     keeps serving (degraded, SLO-recorded latencies /
                     availability), 429 Retry-After passes through,
                     all-replicas-down sheds typed 503.

Usage:
  JAX_PLATFORMS=cpu python -m mxnet_tpu.dist --out DIST_SELFTEST.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

# the driver's own baselines run on a 2-device virtual CPU mesh
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=2').strip()
from .. import config as _config  # noqa: E402
_config.cpu_rig('dist')

_WORKER = [sys.executable, '-m', 'mxnet_tpu.dist._selftest_worker']


def _repo_env():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    py = os.environ.get('PYTHONPATH', '')
    return {'PYTHONPATH': root + (os.pathsep + py if py else '')}


def _spawn(phase, outdir, timeout=240):
    from .launcher import launch_local
    return launch_local(
        2, _WORKER + [phase, outdir], env=_repo_env(),
        log_dir=os.path.join(outdir, 'logs-' + phase),
        platform='cpu', local_devices=1, timeout=timeout)


def _tail(res):
    return ' | '.join('rank%d rc=%s: %s'
                      % (w.rank, w.returncode,
                         w.log_tail(500).replace('\n', ' ')[-300:])
                      for w in res)


# -- driver-side baselines (single process, 2 virtual devices) -------------

def _seeded_net(seed=0):
    from ._selftest_worker import _seeded_net as f
    return f(seed)


def _baseline(steps=10, guard_spec=None, zero=False):
    """Single-process dp=2 run at the same global batch: the reference
    trajectory every multi-process leg diffs against."""
    import numpy as np
    import jax
    from mxnet_tpu import gluon, nd, parallel
    from ._selftest_worker import _data, _params_sorted
    net = _seeded_net()
    xs, ys = _data(steps=steps)
    mesh = parallel.create_mesh({'dp': 2}, devices=jax.devices()[:2])
    guard = None
    if guard_spec:
        from mxnet_tpu.guardrail import Guardrail, GuardrailConfig
        from mxnet_tpu.resilience import FaultInjector
        guard = Guardrail(GuardrailConfig(init_scale=8.0, patience=10),
                          injector=FaultInjector(guard_spec))
    pt = parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1, 'momentum': 0.9}, mesh,
        guardrail=guard, zero=zero)
    losses = [float(pt.step(nd.array(x), nd.array(y)).asscalar())
              for x, y in zip(xs, ys)]
    actions = [e['action'] for e in guard.events] if guard else None
    return net, pt, losses, actions, _params_sorted(net)


def _params_equal(a_dict, b_dict):
    import numpy as np
    if sorted(a_dict) != sorted(b_dict):
        return False
    return all(np.array_equal(np.asarray(a_dict[k]),
                              np.asarray(b_dict[k])) for k in a_dict)


# -- legs ------------------------------------------------------------------

def check_join(tmp):
    res = _spawn('join', tmp, timeout=180)
    if not res.ok:
        return 'join workers failed: %s' % _tail(res)
    recs = []
    for r in range(2):
        with open(os.path.join(tmp, 'join-%d.json' % r)) as f:
            recs.append(json.load(f))
    if [r['process_id'] for r in recs] != [0, 1]:
        return 'ranks wrong: %r' % recs
    if any(r['seed'] != {'seed': 20260804} for r in recs):
        return 'broadcast seed mismatch: %r' % [r['seed'] for r in recs]
    shards = sorted(tuple(r['shard']) for r in recs)
    if shards != [(0, 4), (4, 8)]:
        return 'per-host shards wrong: %r' % shards
    for r in recs:
        if r['maps']['global_devices'] != 2 or \
                r['maps']['local_devices'] != 1:
            return 'device maps wrong: %r' % r['maps']
        if r['peers_seen'] != [0, 1]:
            return 'heartbeats not visible: %r' % r['peers_seen']
    # a scheduler/server role with the same env must NOT join (and
    # must not block): it imports single-process and exits fast
    env = dict(os.environ, **_repo_env())
    env.update({'DMLC_ROLE': 'server', 'DMLC_PS_ROOT_URI': '127.0.0.1',
                'DMLC_PS_ROOT_PORT': '9', 'DMLC_NUM_WORKER': '2',
                'DMLC_WORKER_ID': '0', 'JAX_PLATFORMS': 'cpu'})
    probe = subprocess.run(
        [sys.executable, '-c',
         'import mxnet_tpu as mx, sys;'
         'from mxnet_tpu import dist;'
         'sys.exit(0 if not dist.is_initialized() else 3)'],
        env=env, timeout=120)
    if probe.returncode != 0:
        return ('DMLC_ROLE=server process joined as a worker '
                '(rc=%d)' % probe.returncode)
    return None


def check_init_timeout(tmp):
    env = dict(os.environ, **_repo_env())
    env.update({'DMLC_ROLE': 'worker', 'DMLC_PS_ROOT_URI': '127.0.0.1',
                'DMLC_PS_ROOT_PORT': '9',        # nothing listens here
                'DMLC_NUM_WORKER': '2', 'DMLC_WORKER_ID': '1',
                'JAX_PLATFORMS': 'cpu',
                'MXNET_TPU_DIST_INIT_TIMEOUT_S': '3'})
    t0 = time.time()
    probe = subprocess.run([sys.executable, '-c', 'import mxnet_tpu'],
                           env=env, capture_output=True, timeout=120)
    waited = time.time() - t0
    err = probe.stderr.decode('utf-8', 'replace')
    if probe.returncode == 0:
        return 'join against a dead coordinator succeeded?'
    if 'DistInitError' not in err:
        return 'failure is not typed DistInitError: %s' % err[-300:]
    if waited > 60:
        return 'timed out only after %.0fs (budget was 3s)' % waited
    return None


def check_barrier_timeout(tmp):
    res = _spawn('barrier', tmp, timeout=120)
    if not res.ok:
        return 'barrier workers failed: %s' % _tail(res)
    with open(os.path.join(tmp, 'barrier-0.json')) as f:
        rec = json.load(f)
    if rec.get('typed') not in ('BarrierTimeout', 'HostLostError'):
        return 'no typed HostLostError: %r' % rec
    if not rec.get('within_budget'):
        return 'timeout exceeded budget: %r' % rec
    return None


def check_bit_identity(tmp, shared):
    res = _spawn('train', tmp, timeout=300)
    if not res.ok:
        return 'train workers failed: %s' % _tail(res)
    with open(os.path.join(tmp, 'train-0.json')) as f:
        multi = json.load(f)
    if not multi.get('zero'):
        return 'ZeRO did not activate across hosts'
    net, pt, losses, _a, params = _baseline(steps=10, zero=False)
    shared['baseline'] = (losses, params)
    shared['ckpt_dir'] = os.path.join(tmp, 'ckpt')
    if multi['losses'] != losses:
        return ('losses diverge: multi %r vs single %r'
                % (multi['losses'][:3], losses[:3]))
    if not _params_equal(multi['params'], params):
        return 'final params not bit-identical'
    return None


def check_guarded(tmp):
    res = _spawn('guarded', tmp, timeout=300)
    if not res.ok:
        return 'guarded workers failed: %s' % _tail(res)
    with open(os.path.join(tmp, 'guarded-0.json')) as f:
        multi = json.load(f)
    _n, _pt, losses, actions, params = _baseline(
        steps=6, guard_spec='nan@grads:1', zero=False)
    if 'skip' not in multi['actions']:
        return ('injected NaN step did not skip across hosts: %r'
                % (multi['actions'],))
    if multi['actions'] != actions:
        return ('guardrail actions diverge: %r vs %r'
                % (multi['actions'], actions))
    if multi['losses'] != losses:
        return ('guarded losses diverge: %r vs %r'
                % (multi['losses'][:3], losses[:3]))
    if not _params_equal(multi['params'], params):
        return 'guarded params not bit-identical'
    return None


def check_ckpt_resume(tmp, shared):
    """Resume the process_count=2 checkpoint at process_count=1."""
    import jax
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.resilience import CheckpointManager
    from ._selftest_worker import _data, _params_sorted
    if 'baseline' not in shared:
        return 'bit_identity leg must run first'
    ckpt_dir = shared['ckpt_dir']
    if not os.path.isdir(ckpt_dir):
        return 'no checkpoint directory from the 2-process run'
    base_losses, base_params = shared['baseline']
    net = _seeded_net()
    xs, ys = _data()
    mesh = parallel.create_mesh({'dp': 2}, devices=jax.devices()[:2])
    pt = parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1, 'momentum': 0.9}, mesh, zero=False)
    pt.build(nd.array(xs[0]), nd.array(ys[0]))
    got = pt.resume(CheckpointManager(ckpt_dir, prefix='pt'))
    if got is None:
        return 'resume found no checkpoint'
    step, plan = got
    if step != 5 or plan is not None:
        return 'resume step %r plan %r (wanted 5, None)' % (step, plan)
    cont = [float(pt.step(nd.array(x), nd.array(y)).asscalar())
            for x, y in zip(xs[5:], ys[5:])]
    if cont != base_losses[5:]:
        return ('post-resume losses diverge: %r vs %r'
                % (cont, base_losses[5:]))
    if not _params_equal(_params_sorted(net), base_params):
        return 'post-resume params not bit-identical to baseline'
    return None


def check_host_loss(tmp):
    import numpy as np
    import jax
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.resilience import CheckpointManager, host_loss_plan
    from ._selftest_worker import _data, _params_sorted
    res = _spawn('hostloss', tmp, timeout=300)
    # rank 0 exits 75 (resumable), rank 1 exits 0: pod rc must be 75
    if res.exit_code() != 75:
        return ('launcher did not propagate the resumable rc: %r (%s)'
                % (res.returncodes, _tail(res)))
    with open(os.path.join(tmp, 'hostloss-0.json')) as f:
        rec = json.load(f)
    if rec.get('typed') not in ('BarrierTimeout', 'HostLostError'):
        return 'worker death was not typed: %r' % rec
    if not rec.get('within_budget'):
        return 'HostLostError exceeded the timeout budget: %r' % rec
    flight = rec.get('flight')
    if flight:
        from mxnet_tpu.observability import read_flight
        # rank-suffixed dump path: 2 processes, rank 0 dumped
        root, ext = os.path.splitext(flight)
        suffixed = '%s.r0%s' % (root, ext)
        if not os.path.exists(suffixed):
            return 'no rank-suffixed flight dump at %s' % suffixed
        _h, events = read_flight(suffixed)
        if not any(e.get('kind') == 'host_lost' for e in events):
            return 'flight dump has no host_lost event'

    # elastic re-form: surviving 1 host x 1 device, dp 2→1, accum 2
    mgr = CheckpointManager(os.path.join(tmp, 'ckpt'), prefix='pt')
    latest = mgr.latest()
    if latest is None:
        return 'no checkpoint from the killed 2-process run'
    meta = latest[1]['mesh']
    plan = host_loss_plan(meta, surviving_processes=1,
                          devices_per_host=1)
    if plan.accum_steps != 2 or plan.new_axes.get('dp') != 1:
        return 'host-loss plan wrong: %r' % plan

    # uninterrupted single-process baseline for the loss trajectory
    _n0, _p0, base_losses, _a0, _pp0 = _baseline(steps=10, zero=False)

    net = _seeded_net()
    xs, ys = _data()
    mesh1 = parallel.create_mesh(plan.new_axes,
                                 devices=jax.devices()[:1])
    pt = parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1, 'momentum': 0.9}, mesh1, zero=False)
    pt.build(nd.array(xs[0][:8]), nd.array(ys[0][:8]))
    step, rplan = pt.resume(mgr, elastic=True)
    if step != 3:
        return 'elastic resume step %r (wanted 3)' % (step,)
    if rplan is None or rplan.accum_steps != 2:
        return 'elastic resume plan wrong: %r' % (rplan,)
    got = [float(pt.step_accum(nd.array(x), nd.array(y), 2).asscalar())
           for x, y in zip(xs[3:6], ys[3:6])]
    if not np.allclose(got, base_losses[3:6], rtol=1e-4, atol=1e-5):
        return ('re-formed-mesh losses off the baseline: %r vs %r'
                % (got, base_losses[3:6]))
    return None


def check_gateway(tmp):
    import urllib.error
    import urllib.request
    from mxnet_tpu.loadgen.harness import GatewayRig

    def post(base, payload, path='/predict'):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={'Content-Type': 'application/json'},
            method='POST')
        t0 = time.monotonic()
        try:
            with urllib.request.urlopen(req, timeout=15) as r:
                r.read()
                return r.status, dict(r.headers), \
                    time.monotonic() - t0
        except urllib.error.HTTPError as e:
            e.read()
            return e.code, dict(e.headers), time.monotonic() - t0

    def get(base, path):
        try:
            with urllib.request.urlopen(base + path, timeout=15) as r:
                return r.status, json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    rig = GatewayRig(replicas=2, generate=False, max_queue=2,
                     max_batch=4, deadline_ms=2.0, timeout_s=5.0,
                     max_concurrent=8, health_period_s=0.25)
    try:
        base = 'http://127.0.0.1:%d' % rig.port
        st, payload = get(base, '/healthz')
        if st != 200 or payload['status'] != 'ok':
            return 'initial healthz not ok: %r' % payload
        lat_ok = []
        for _ in range(12):
            code, _h, dt = post(base, {'data': [0.1] * 8})
            if code != 200:
                return 'healthy-phase request failed: %d' % code
            lat_ok.append(dt)
        # one replica down: still serving, /healthz says degraded
        rig.kill_replica(1)
        time.sleep(1.0)           # > 2 probe periods
        st, payload = get(base, '/healthz')
        if st != 200 or payload['status'] != 'degraded':
            return 'post-kill healthz not degraded: %r %r' \
                % (st, payload)
        served = shed = 0
        lat_deg = []
        for _ in range(12):
            code, _h, dt = post(base, {'data': [0.1] * 8})
            if code == 200:
                served += 1
                lat_deg.append(dt)
            else:
                shed += 1
        if served < 10:
            return ('gateway stopped serving with one replica down: '
                    '%d/12 ok' % served)
        # Retry-After passthrough: saturate the tiny surviving queue
        saw_429 = saw_hint = False
        import threading
        codes = []
        lock = threading.Lock()

        def flood():
            code, headers, _dt = post(base, {'data': [0.1] * 8})
            with lock:
                codes.append((code, headers.get('Retry-After')))

        threads = [threading.Thread(target=flood) for _ in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for code, ra in codes:
            if code == 429:
                saw_429 = True
                if ra is not None:
                    saw_hint = True
        if saw_429 and not saw_hint:
            return '429 passed through without its Retry-After header'
        # all replicas down: typed 503 + Retry-After, never a hang
        rig.kill_replica(0)
        time.sleep(1.0)
        st, payload = get(base, '/healthz')
        if st != 503:
            return 'all-down healthz was %d, wanted 503' % st
        code, headers, dt = post(base, {'data': [0.1] * 8})
        if code != 503 or headers.get('Retry-After') is None:
            return ('all-down POST: code %d Retry-After %r'
                    % (code, headers.get('Retry-After')))
        stats = rig.gateway.stats()
        slo = {
            'healthy_p99_ms': round(
                sorted(lat_ok)[-1] * 1000, 2),
            'degraded_p99_ms': round(
                sorted(lat_deg)[-1] * 1000, 2) if lat_deg else None,
            'degraded_availability': served / 12.0,
            'shed': shed,
            'saw_429_retry_after': saw_hint,
            'gateway_stats': stats,
        }
        _record = os.path.join(tmp, 'gateway_slo.json')
        with open(_record, 'w') as f:
            json.dump(slo, f, sort_keys=True, indent=1)
        check_gateway.slo = slo
        if served / 12.0 < 0.85:
            return 'degraded availability %.2f < 0.85' % (served / 12.0)
        return None
    finally:
        rig.close()


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m mxnet_tpu.dist',
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument('--out', default='DIST_SELFTEST.json')
    p.add_argument('--skip-gateway', action='store_true',
                   help='skip the serving-gateway leg (debug)')
    args = p.parse_args(argv)

    import jax
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_default_matmul_precision', 'float32')
    if len(jax.devices()) < 2:
        print('selftest: needs 2 virtual devices for the baselines')
        return 1

    shared = {}
    checks = {}
    t0 = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        legs = [
            ('join', lambda: check_join(_leg_dir(tmp, 'join'))),
            ('init_timeout',
             lambda: check_init_timeout(_leg_dir(tmp, 'it'))),
            ('barrier_timeout',
             lambda: check_barrier_timeout(_leg_dir(tmp, 'bt'))),
            ('bit_identity',
             lambda: check_bit_identity(_leg_dir(tmp, 'bit'), shared)),
            ('guarded', lambda: check_guarded(_leg_dir(tmp, 'gd'))),
            ('ckpt_resume', lambda: check_ckpt_resume(tmp, shared)),
            ('host_loss',
             lambda: check_host_loss(_leg_dir(tmp, 'hl'))),
        ]
        if not args.skip_gateway:
            legs.append(('gateway',
                         lambda: check_gateway(_leg_dir(tmp, 'gw'))))
        for name, fn in legs:
            t1 = time.time()
            try:
                problem = fn()
            except Exception as exc:
                import traceback
                traceback.print_exc()
                problem = '%s: %s' % (type(exc).__name__, exc)
            checks[name] = problem or 'ok'
            print('selftest %-16s %s (%.1fs)'
                  % (name, checks[name], time.time() - t1),
                  flush=True)
    ok = all(v == 'ok' for v in checks.values())
    verdict = {'ok': ok, 'checks': checks,
               'seconds': round(time.time() - t0, 1)}
    slo = getattr(check_gateway, 'slo', None)
    if slo is not None:
        verdict['gateway_slo'] = slo
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


def _leg_dir(tmp, name):
    d = os.path.join(tmp, name)
    os.makedirs(d, exist_ok=True)
    return d


if __name__ == '__main__':
    sys.exit(main())
