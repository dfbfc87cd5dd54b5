"""AMP selftest (CI tier 'amp', tools/ci.py).

CPU-runnable proof of the mixed-precision contract
(docs/PRECISION.md), in five legs:

  1. policy          — resolution matrix (names / booleans / Policy
                       passthrough / env knob / typed error), scope
                       re-entrancy, and the per-op cast classification
                       (matmul family down, softmax/loss/reduction up,
                       everything else untouched).
  2. off_bit_identity— a trainer built with amp='off' walks the SAME
                       trajectory bit-for-bit as one built with no amp
                       argument at all, and its compiled step contains
                       no bf16 buffers: the knob off is a true no-op.
  3. master_roundtrip— amp='bf16': the compiled step carries bf16
                       compute but every parameter and optimizer-state
                       leaf stays float32; a checkpoint written
                       mid-run restores bit-identically into a fresh
                       bf16 trainer AND into an amp-off trainer
                       (masters are precision-independent), and the
                       resumed bf16 run replays the exact losses.
  4. guardrail       — amp='fp16' auto-enables dynamic loss scaling:
                       an injected-NaN step is skipped with params and
                       optimizer state bit-identical, the scale
                       halves, and training continues finite.
  5. gluon_master    — the eager path: net.cast('bfloat16') +
                       Trainer(amp='bf16') forces the optimizer's
                       multi-precision protocol, so every bf16 weight
                       updates against a float32 master (bfloat16
                       support is this PR's optimizer fix).

Usage:
  JAX_PLATFORMS=cpu python -m mxnet_tpu.amp --out AMP_SELFTEST.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

from .. import config as _config  # noqa: E402
_config.cpu_rig('amp')

SCHEMA = 'mxnet_tpu.amp_selftest.v1'


def _net_and_data(seed=0, classes=4, hidden=16, feats=6, batch=8,
                  nsteps=10):
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
    xs = [rs.randn(batch, feats).astype('float32')
          for _ in range(nsteps)]
    ys = [rs.randint(0, classes, (batch,)).astype('float32')
          for _ in range(nsteps)]
    return net, xs, ys


def _trainer(net, amp=None, guardrail=None, **amp_kwargs):
    import jax
    from mxnet_tpu import gluon, parallel
    mesh = parallel.create_mesh({'dp': 1}, devices=jax.devices()[:1])
    kwargs = dict(amp_kwargs)
    if amp is not None:
        kwargs['amp'] = amp
    return parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1, 'momentum': 0.9}, mesh,
        guardrail=guardrail, **kwargs)


def _run_steps(pt, xs, ys, n):
    from mxnet_tpu import nd
    return [float(pt.step(nd.array(x), nd.array(y)).asscalar())
            for x, y in zip(xs[:n], ys[:n])]


def check_policy():
    import numpy as np
    import jax.numpy as jnp
    from . import Policy, bf16, fp16, resolve, scope, current_policy
    p = resolve('bf16')
    if p is None or p.name != 'bf16' or p.loss_scaling:
        return 'bf16 resolution wrong: %r' % p
    if not resolve('fp16').loss_scaling:
        return 'fp16 policy must mark loss_scaling'
    if resolve('off') is not None or resolve(False) is not None:
        return "resolve('off')/False must be None"
    if resolve(True).name != 'bf16':
        return 'resolve(True) must be the bf16 default'
    if resolve(p) is not p:
        return 'Policy instances must pass through'
    try:
        resolve('int7')
    except ValueError:
        pass
    else:
        return "resolve('int7') must raise ValueError"
    try:
        Policy('bad', 'bfloat16', cast_ops=('dot',), fp32_ops=('dot',))
    except ValueError:
        pass
    else:
        return 'overlapping op classes must raise'
    # env knob path (config.set/unset mirror the env registry)
    from .. import config as _config
    _config.set('MXNET_TPU_AMP', 'fp16')
    try:
        if resolve(None).name != 'fp16':
            return 'resolve(None) must read MXNET_TPU_AMP'
    finally:
        _config.unset('MXNET_TPU_AMP')
    if resolve(None) is not None and \
            not os.environ.get('MXNET_TPU_AMP'):
        return 'resolve(None) with the knob unset must be off'
    # cast classification (raw arrays stand in for tracers)
    f32 = jnp.ones((2, 2), jnp.float32)
    i32 = jnp.ones((2,), jnp.int32)
    lo = f32.astype(jnp.bfloat16)
    w, idx = p.cast_op_inputs('FullyConnected', [f32, i32])
    if str(w.dtype) != 'bfloat16' or str(idx.dtype) != 'int32':
        return 'matmul-family cast wrong: %s/%s' % (w.dtype, idx.dtype)
    up, = p.cast_op_inputs('log_softmax', [lo])
    if str(up.dtype) != 'float32':
        return 'keep-fp32 upcast wrong: %s' % up.dtype
    same, = p.cast_op_inputs('Activation', [lo])
    if same is not lo:
        return 'unlisted ops must pass operands through untouched'
    # scope: re-entrant, thread-local, None is a no-op
    if current_policy() is not None:
        return 'policy leaked into the selftest thread'
    with scope(p):
        if current_policy() is not p:
            return 'scope did not activate'
        with scope(None):
            if current_policy() is not p:
                return 'scope(None) must not clear the active policy'
        with scope(fp16()):
            if current_policy().name != 'fp16':
                return 'nested scope did not override'
        if current_policy() is not p:
            return 'nested scope did not restore'
    if current_policy() is not None:
        return 'scope did not deactivate'
    _ = (np, bf16)
    return None


def check_off_bit_identity():
    import numpy as onp
    net0, xs, ys = _net_and_data()
    pt0 = _trainer(net0)                    # no amp argument at all
    l0 = _run_steps(pt0, xs, ys, 5)
    net1, xs, ys = _net_and_data()
    pt1 = _trainer(net1, amp='off')
    l1 = _run_steps(pt1, xs, ys, 5)
    if l0 != l1:
        return "amp='off' losses diverge from no-amp: %r vs %r" \
            % (l0[:3], l1[:3])
    for a, b in zip(pt0._param_arrays, pt1._param_arrays):
        if not onp.array_equal(onp.asarray(a), onp.asarray(b)):
            return "amp='off' params not bit-identical to no-amp"
    text = pt1.compiled_text()
    if 'bf16[' in text or 'f16[' in text:
        return "amp='off' compiled step contains low-precision buffers"
    return None


def check_master_roundtrip(tmpdir):
    import numpy as onp
    from mxnet_tpu.resilience import CheckpointManager

    net, xs, ys = _net_and_data()
    pt = _trainer(net, amp='bf16')
    l_first = _run_steps(pt, xs, ys, 4)
    text = pt.compiled_text()
    if 'bf16[' not in text:
        return 'bf16 compute missing from the compiled step'
    for w in pt._param_arrays:
        if str(w.dtype) != 'float32':
            return 'param master is %s, not float32' % w.dtype
    for s in pt._state_leaves:
        if str(s.dtype) != 'float32':
            return 'optimizer state leaf is %s, not float32' % s.dtype
    mgr = CheckpointManager(tmpdir, prefix='amp')
    pt.save_checkpoint(mgr)
    snap = [onp.asarray(w) for w in pt._param_arrays]
    l_tail = _run_steps(pt, xs[4:], ys[4:], 3)

    # resume into a fresh bf16 trainer: bit-identical restore + replay
    net2, xs, ys = _net_and_data()
    pt2 = _trainer(net2, amp='bf16')
    from mxnet_tpu import nd
    pt2.build(nd.array(xs[0]), nd.array(ys[0]))
    if pt2.resume(mgr) is None:
        return 'resume found no checkpoint'
    for a, b in zip(snap, pt2._param_arrays):
        if not onp.array_equal(a, onp.asarray(b)):
            return 'bf16 resume not bit-identical'
    l_tail2 = _run_steps(pt2, xs[4:], ys[4:], 3)
    if l_tail != l_tail2:
        return 'resumed bf16 run diverges: %r vs %r' % (l_tail, l_tail2)

    # resume into an amp-OFF trainer: masters are fp32 either way
    net3, xs, ys = _net_and_data()
    pt3 = _trainer(net3, amp='off')
    pt3.build(nd.array(xs[0]), nd.array(ys[0]))
    pt3.resume(mgr)
    for a, b in zip(snap, pt3._param_arrays):
        if not onp.array_equal(a, onp.asarray(b)):
            return 'cross-precision resume not bit-identical'
    if pt.amp != 'bf16' or pt3.amp != 'off':
        return 'amp property wrong: %s / %s' % (pt.amp, pt3.amp)
    return None


def check_guardrail():
    import numpy as onp
    from mxnet_tpu import nd
    from mxnet_tpu.guardrail import Guardrail, GuardrailConfig
    from mxnet_tpu.resilience import FaultInjector

    guard = Guardrail(GuardrailConfig(init_scale=1024.0, check_every=0),
                      injector=FaultInjector('nan@grads:1'))
    net, xs, ys = _net_and_data()
    pt = _trainer(net, amp='fp16', guardrail=guard)
    if pt.amp != 'fp16' or pt.guardrail is not guard:
        return 'fp16 trainer lost its guardrail'
    pt.build(nd.array(xs[0]), nd.array(ys[0]))
    before = [onp.asarray(w) for w in pt._param_arrays]
    leaves = [onp.asarray(a) for a in pt._state_leaves]
    pt.step(nd.array(xs[0]), nd.array(ys[0]))   # poisoned -> skipped
    for a, b in zip(before, pt._param_arrays):
        if not onp.array_equal(a, onp.asarray(b)):
            return 'skipped fp16 step touched params'
    for a, b in zip(leaves, pt._state_leaves):
        if not onp.array_equal(a, onp.asarray(b)):
            return 'skipped fp16 step touched optimizer state'
    scale = float(pt._gstate[0])
    if scale != 512.0:
        return 'overflow did not halve the scale: %r' % scale
    losses = _run_steps(pt, xs[1:], ys[1:], 3)
    if not all(onp.isfinite(losses)):
        return 'fp16 training went non-finite after the skip: %r' \
            % losses
    if not any(not onp.array_equal(a, onp.asarray(b))
               for a, b in zip(before, pt._param_arrays)):
        return 'healthy fp16 steps never updated params'
    guard.flush()
    return None


def check_gluon_master():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon import nn
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation='relu'), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net.cast('bfloat16')
    net.hybridize()
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), 'sgd',
                       {'learning_rate': 0.1, 'momentum': 0.9},
                       amp='bf16')
    if tr.amp != 'bf16' or not tr.optimizer.multi_precision:
        return 'Trainer(amp=) did not force multi_precision'
    x = nd.array(np.random.randn(8, 6), dtype='bfloat16')
    y = nd.array(np.random.randint(0, 4, (8,)))
    first = None
    for _ in range(8):
        with autograd.record():
            loss = L(net(x), y)
        loss.backward()
        tr.step(8)
        cur = float(loss.mean().asscalar())
        first = cur if first is None else first
    if not cur < first:
        return 'bf16 eager loss did not decrease: %r -> %r' \
            % (first, cur)
    masters = 0
    for st in tr._updaters[0].states.values():
        if isinstance(st, tuple) and hasattr(st[0], 'dtype') and \
                str(st[0].dtype) == 'float32':
            masters += 1
    if masters == 0:
        return 'no float32 masters created for bf16 weights'
    return None


def main(argv=None):
    p = argparse.ArgumentParser(
        description='AMP selftest (docs/PRECISION.md)')
    p.add_argument('--out', default=None,
                   help='write the JSON verdict here too')
    args = p.parse_args(argv)

    tmpdir = tempfile.mkdtemp(prefix='amp_selftest_')
    legs = [
        ('policy', check_policy),
        ('off_bit_identity', check_off_bit_identity),
        ('master_roundtrip', lambda: check_master_roundtrip(tmpdir)),
        ('guardrail', check_guardrail),
        ('gluon_master', check_gluon_master),
    ]
    results = {}
    ok = True
    for name, fn in legs:
        try:
            err = fn()
        except Exception as e:      # a crash is a failed leg, not a crash
            import traceback
            traceback.print_exc()
            err = '%s: %s' % (type(e).__name__, e)
        results[name] = {'ok': err is None, 'error': err}
        print('amp selftest %-18s %s%s'
              % (name, 'OK' if err is None else 'FAIL',
                 '' if err is None else ' — ' + err), flush=True)
        ok = ok and err is None
    verdict = {'schema': SCHEMA, 'ok': ok, 'legs': results}
    print(json.dumps({'schema': SCHEMA, 'ok': ok,
                      'failed': [k for k, v in results.items()
                                 if not v['ok']]}))
    if args.out:
        with open(args.out, 'w') as f:
            json.dump(verdict, f, indent=1, sort_keys=True)
            f.write('\n')
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
