"""Sharding selftest (CI tier 'sharding', tools/ci.py).

CPU-runnable proof of the 2-D mesh + ZeRO sharded-weight-update
contract (docs/PARALLEL.md), in six legs:

  1. bit_identity — dp-only mesh: 10 steps with MXNET_TPU_ZERO on vs
                    off produce bit-identical losses AND params (the
                    reduce-scatter sums the same values the all-reduce
                    does; the per-shard update math is elementwise).
  2. guarded      — same A/B through the in-jit guardrail with one
                    injected NaN step: the lax.cond skip branch leaves
                    the dp-sharded optimizer state bit-identical and
                    both runs skip/update in lockstep.
  3. memory       — per-device optimizer-state bytes with the knob on
                    are <= 1/4 of the replicated footprint on the
                    8-device mesh (ideal 1/8; the gate tolerates
                    replicated odd-sized leaves), measured from the
                    live shard shapes, and the sharded step's HLO
                    carries the closing all-gather (XLA:CPU lowers the
                    logical reduce-scatter as all-reduce + slice; TPU
                    emits reduce-scatter).
  4. mesh_2d      — a dp×model mesh with an annotated P(None, 'model')
                    weight trains to the dp-only trajectory (fp
                    tolerance: model sharding re-orders reductions)
                    with params genuinely sharded on the model axis.
  5. resume_2d    — a checkpoint written under the 2-D ZeRO mesh
                    resumes bit-identically on a 1-D replicated dp
                    mesh and vice versa (checkpoints hold logical
                    arrays; placement is free), and an elastic 8→4
                    shrink keeps the model axis intact (dp 4→2,
                    accum=2) tracking the unshrunk loss trajectory.
  6. spec_errors  — ShardingRules rejects a spec naming an axis the
                    mesh lacks / reusing an axis / not dividing the
                    dim with a typed ShardingSpecError naming the
                    parameter, eagerly at build.

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python -m mxnet_tpu.parallel --out SHARDING_SELFTEST.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# honor --devices (default 8) before the jax backend initializes;
# argparse accepts both '--devices N' and '--devices=N', so match both
_n = '8'
if '--devices' in sys.argv[:-1]:
    _n = sys.argv[sys.argv.index('--devices') + 1]
else:
    for _a in sys.argv[1:]:
        if _a.startswith('--devices='):
            _n = _a.split('=', 1)[1]
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=%s'
        % _n).strip()
from .. import config as _config  # noqa: E402
_config.cpu_rig('parallel')


def _net_and_data(seed=0, classes=8, hidden=32, feats=16, batch=16):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    np.random.seed(seed)
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(hidden, activation='relu'), nn.Dense(classes))
    net.initialize(mx.init.Xavier())
    rs = np.random.RandomState(seed + 1)
    xs = [rs.randn(batch, feats).astype('float32') for _ in range(10)]
    ys = [rs.randint(0, classes, (batch,)).astype('float32')
          for _ in range(10)]
    return net, xs, ys


def _params_sorted(net):
    import numpy as np
    return [np.asarray(p.data().asnumpy())
            for k, p in sorted(net.collect_params().items(),
                               key=lambda kv: kv[0].split('_', 1)[-1])]


def _run(zero, axes, guard=None, steps=10, rules=None, annotate=None,
         seed=0):
    import numpy as np
    import jax
    from mxnet_tpu import gluon, nd, parallel
    net, xs, ys = _net_and_data(seed=seed)
    if annotate:
        net.annotate_sharding(annotate)
    n = 1
    for v in axes.values():
        n *= v
    mesh = parallel.create_mesh(axes, devices=jax.devices()[:n])
    pt = parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1, 'momentum': 0.9}, mesh, rules=rules,
        guardrail=guard, zero=zero)
    losses = [float(pt.step(nd.array(x), nd.array(y)).asscalar())
              for x, y in zip(xs[:steps], ys[:steps])]
    return net, pt, losses


def check_bit_identity(devices):
    net0, pt0, l0 = _run(False, {'dp': devices})
    net1, pt1, l1 = _run(True, {'dp': devices})
    if not pt1.zero:
        return 'zero=True did not activate on the dp=%d mesh' % devices
    if l0 != l1:
        return 'losses diverge: %r vs %r' % (l0[:3], l1[:3])
    import numpy as np
    for a, b in zip(_params_sorted(net0), _params_sorted(net1)):
        if not np.array_equal(a, b):
            return 'params not bit-identical after 10 steps'
    return None


def check_guarded(devices):
    import numpy as np
    from mxnet_tpu.guardrail import Guardrail, GuardrailConfig
    from mxnet_tpu.resilience import FaultInjector

    def guarded(zero):
        guard = Guardrail(GuardrailConfig(init_scale=8.0, patience=10),
                          injector=FaultInjector('nan@grads:1'))
        net, pt, losses = _run(zero, {'dp': devices}, guard=guard,
                               steps=6)
        actions = [e['action'] for e in guard.events]
        return net, losses, actions

    net0, l0, a0 = guarded(False)
    net1, l1, a1 = guarded(True)
    if 'skip' not in a1:
        return 'injected NaN step did not skip (actions %r)' % (a1,)
    if a0 != a1:
        return 'guardrail actions diverge: %r vs %r' % (a0, a1)
    if l0 != l1:
        return 'guarded losses diverge: %r vs %r' % (l0[:3], l1[:3])
    for a, b in zip(_params_sorted(net0), _params_sorted(net1)):
        if not np.array_equal(a, b):
            return 'guarded params not bit-identical'
    return None


def check_memory(devices):
    from mxnet_tpu.observability.hlo import collective_bytes
    net0, pt0, _ = _run(False, {'dp': devices}, steps=1)
    net1, pt1, _ = _run(True, {'dp': devices}, steps=1)
    rep_dev, rep_log = pt0.optimizer_state_bytes()
    z_dev, z_log = pt1.optimizer_state_bytes()
    if rep_log != z_log:
        return 'logical state bytes differ: %d vs %d' % (rep_log, z_log)
    if rep_dev != rep_log:
        return 'replicated per-device bytes %d != logical %d' \
            % (rep_dev, rep_log)
    ratio = z_dev / float(z_log)
    if ratio > 0.25:
        return ('per-device optimizer state %d/%d = %.3f of replicated '
                '(> 1/4 budget on the %d-device mesh)'
                % (z_dev, z_log, ratio, devices))
    _, kinds = collective_bytes(pt1.compiled_text())
    if 'all-gather' not in kinds:
        return ('sharded step HLO has no all-gather (collectives: %r) '
                '— the update is not running on shards' % (kinds,))
    print('  memory: %d -> %d bytes/device (%.3fx), collectives %s'
          % (rep_dev, z_dev, ratio, sorted(kinds)), flush=True)
    return None


def check_mesh_2d(devices):
    import numpy as np
    from jax.sharding import PartitionSpec as P
    net0, pt0, l0 = _run(False, {'dp': devices})
    net2, pt2, l2 = _run(
        True, {'dp': devices // 2, 'model': 2},
        annotate={'dense0_weight': P(None, 'model')})
    if not np.allclose(l2, l0, rtol=1e-4, atol=1e-6):
        return '2-D losses off the dp-only trajectory: %r vs %r' \
            % (l2[:3], l0[:3])
    for a, b in zip(_params_sorted(net0), _params_sorted(net2)):
        if not np.allclose(a, b, rtol=1e-4, atol=1e-5):
            return '2-D params off the dp-only values'
    sharded = [w for w in pt2._param_arrays
               if any(s.data.shape != w.shape
                      for s in w.addressable_shards)]
    if not sharded:
        return 'no parameter was actually model-sharded on the 2-D mesh'
    return None


def check_resume_2d(devices, tmpdir):
    import numpy as np
    import jax
    from mxnet_tpu import nd
    from mxnet_tpu.resilience import CheckpointManager

    def snap_state(pt):
        return ([np.asarray(w) for w in pt._param_arrays],
                [np.asarray(a) for a in pt._state_leaves])

    # 2-D ZeRO checkpoint → 1-D replicated trainer (same device count)
    net_a, pt_a, _ = _run(True, {'dp': devices // 2, 'model': 2},
                          steps=3)
    mgr = CheckpointManager(os.path.join(tmpdir, 'x2d'), prefix='pt')
    pt_a.save_checkpoint(mgr)
    ref_p, ref_l = snap_state(pt_a)
    net_b, pt_b, _ = _run(False, {'dp': devices}, steps=1)
    step, plan = pt_b.resume(mgr)
    if step != 3 or plan is not None:
        return '2-D→1-D resume: step %r plan %r' % (step, plan)
    got_p, got_l = snap_state(pt_b)
    for a, b in zip(ref_p + ref_l, got_p + got_l):
        if not np.array_equal(a, b):
            return '2-D→1-D resumed state not bit-identical'

    # 1-D checkpoint → 2-D ZeRO trainer
    net_c, pt_c, _ = _run(False, {'dp': devices}, steps=3, seed=2)
    mgr2 = CheckpointManager(os.path.join(tmpdir, 'x1d'), prefix='pt')
    pt_c.save_checkpoint(mgr2)
    ref_p, ref_l = snap_state(pt_c)
    net_d, pt_d, _ = _run(True, {'dp': devices // 2, 'model': 2},
                          steps=1, seed=2)
    step, plan = pt_d.resume(mgr2)
    if step != 3 or plan is not None:
        return '1-D→2-D resume: step %r plan %r' % (step, plan)
    got_p, got_l = snap_state(pt_d)
    for a, b in zip(ref_p + ref_l, got_p + got_l):
        if not np.array_equal(a, b):
            return '1-D→2-D resumed state not bit-identical'

    # elastic 8→4: dp shrinks 4→2, model axis preserved, accum=2
    net_e, pt_e, _ = _run(True, {'dp': devices // 2, 'model': 2},
                          steps=3, seed=3)
    mgr3 = CheckpointManager(os.path.join(tmpdir, 'el'), prefix='pt')
    pt_e.save_checkpoint(mgr3)
    _, xs, ys = _net_and_data(seed=3)
    ref = []
    for x, y in zip(xs[3:6], ys[3:6]):
        ref.append(float(pt_e.step(nd.array(x), nd.array(y))
                         .asscalar()))

    from mxnet_tpu import gluon, parallel
    net_f, xs_f, ys_f = _net_and_data(seed=3)
    mesh4 = parallel.create_mesh({'dp': devices // 4, 'model': 2},
                                 devices=jax.devices()[:devices // 2])
    pt_f = parallel.ParallelTrainer(
        net_f, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1, 'momentum': 0.9}, mesh4, zero=True)
    pt_f.build(nd.array(xs_f[0][:8]), nd.array(ys_f[0][:8]))
    step, plan = pt_f.resume(mgr3)
    if step != 3:
        return 'elastic resume step %r' % (step,)
    if plan is None or plan.accum_steps != 2 or \
            plan.new_axes.get('model') != 2:
        return 'elastic plan wrong: %r' % (plan,)
    got = [float(pt_f.step_accum(nd.array(x), nd.array(y), 2)
                 .asscalar()) for x, y in zip(xs_f[3:6], ys_f[3:6])]
    if not np.allclose(got, ref, rtol=1e-4, atol=1e-5):
        return 'elastic-shrunk losses diverge: %r vs %r' % (got, ref)
    return None


def check_spec_errors(devices):
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.parallel import ShardingRules, ShardingSpecError

    mesh = parallel.create_mesh({'dp': devices},
                                devices=jax.devices()[:devices])
    cases = [
        (P('ghost'), 'ghost'),           # axis the mesh lacks
        (P('dp', 'dp'), 'more than once'),
    ]
    rules = ShardingRules()
    for spec, needle in cases:
        try:
            rules.spec_for('w', (32, 16), mesh, annotation=spec)
            return 'spec %r was not rejected' % (spec,)
        except ShardingSpecError as e:
            if needle not in str(e) or 'w' not in str(e):
                return 'error for %r lacks detail: %s' % (spec, e)
    # not-dividing dim: 10 rows over 8 devices
    try:
        rules.spec_for('w', (10, 16), mesh, annotation=P('dp'))
        return 'non-dividing spec was not rejected'
    except ShardingSpecError as e:
        if 'does not divide' not in str(e):
            return 'non-dividing error lacks detail: %s' % e
    # the whole-trainer path surfaces the same typed error at build
    net, xs, ys = _net_and_data()
    net.annotate_sharding({'dense1_weight': P('ghost')})
    from mxnet_tpu import nd
    pt = parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1}, mesh)
    try:
        pt.build(nd.array(xs[0]), nd.array(ys[0]))
        return 'trainer build accepted a ghost-axis annotation'
    except ShardingSpecError as e:
        if 'dense1_weight' not in str(e):
            return 'build error does not name the parameter: %s' % e
    return None


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m mxnet_tpu.parallel',
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument('--devices', type=int, default=8,
                   help='virtual device count (sets XLA_FLAGS before '
                        'jax initializes; default 8)')
    p.add_argument('--out', default='SHARDING_SELFTEST.json')
    args = p.parse_args(argv)

    import tempfile
    import jax
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_default_matmul_precision', 'float32')
    n = min(args.devices, len(jax.devices()))
    if n < 4:
        print('selftest: needs >= 4 devices, have %d' % n)
        return 1
    if n & (n - 1):
        # the memory leg's state tensors and the mesh_2d leg's
        # dp×model factorization assume a power-of-two dp — on e.g.
        # n=6 nothing divides, the library correctly keeps state
        # replicated, and the selftest would report a false failure
        p2 = 1 << (n.bit_length() - 1)
        print('selftest: rounding %d devices down to %d '
              '(legs assume a power-of-two mesh)' % (n, p2))
        n = p2

    checks = {}
    with tempfile.TemporaryDirectory() as tmp:
        legs = [('bit_identity', lambda: check_bit_identity(n)),
                ('guarded', lambda: check_guarded(n)),
                ('memory', lambda: check_memory(n)),
                ('mesh_2d', lambda: check_mesh_2d(n)),
                ('resume_2d', lambda: check_resume_2d(n, tmp)),
                ('spec_errors', lambda: check_spec_errors(n))]
        for name, fn in legs:
            try:
                problem = fn()
            except Exception as exc:
                import traceback
                traceback.print_exc()
                problem = '%s: %s' % (type(exc).__name__, exc)
            checks[name] = problem or 'ok'
            print('selftest %-12s %s' % (name, checks[name]),
                  flush=True)
    ok = all(v == 'ok' for v in checks.values())
    verdict = {'ok': ok, 'devices': n, 'checks': checks}
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
