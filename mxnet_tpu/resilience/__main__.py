"""Preemption / elasticity / watchdog selftest.

The CPU-runnable proof of the preemption-tolerance contract
(docs/RESILIENCE.md), driven by tools/fault_smoke.py in the CI fault
tier:

  # uninterrupted reference
  python -m mxnet_tpu.resilience --train --steps 18 --devices 8 \
      --ckpt-dir /tmp/d0 --out ref.json

  # preempted run: exits with the resumable rc (75) after draining an
  # emergency checkpoint at step 9
  MXNET_TPU_FAULT=preempt@train.step.9:1 \
  python -m mxnet_tpu.resilience --train --steps 18 --devices 8 \
      --ckpt-dir /tmp/d1 --out a.json

  # restart with the same command: resumes at step 9, finishes, and
  # its param_hash is BIT-IDENTICAL to ref.json's
  python -m mxnet_tpu.resilience --train --steps 18 --devices 8 \
      --ckpt-dir /tmp/d1 --out b.json

  # elastic restart on a halved mesh: dp 8 -> 4 with 2-step gradient
  # accumulation; the loss trajectory matches ref to fp32 tolerance
  python -m mxnet_tpu.resilience --train --steps 18 --devices 4 \
      --ckpt-dir /tmp/d1 --out c.json

  # watchdog: an injected hang at step 3 is detected within the stall
  # budget and the structured stall artifact is written
  MXNET_TPU_FAULT=hang@train.step.3:1 \
  python -m mxnet_tpu.resilience --watchdog-smoke \
      --stall-artifact /tmp/STALL.json --out w.json

Everything is deterministic: model init under fixed seeds, per-step
synthetic batches derived from the step index (the sampler-rewind
contract), scripted faults instead of real signals. The caller must
export ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` matching
``--devices`` (fault_smoke does; a best-effort fallback below covers
direct invocation).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

# best-effort: honor --devices before the jax backend initializes
# (import of the parent package has happened, backend init has not)
if '--devices' in sys.argv[:-1]:
    _n = sys.argv[sys.argv.index('--devices') + 1]
    _flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in _flags:
        os.environ['XLA_FLAGS'] = (
            _flags + ' --xla_force_host_platform_device_count=%s'
            % _n).strip()
from .. import config as _config  # noqa: E402
_config.cpu_rig('resilience')

FEATURES = 16
CLASSES = 4


def _net_and_loss():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    np.random.seed(11)      # initializer draws use numpy's RNG
    mx.random.seed(11)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation='relu'), nn.Dense(CLASSES))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, FEATURES)))    # materialize deferred init
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def _batch(step, batch):
    """Deterministic synthetic batch for global step ``step`` — data
    order is a pure function of the step index, which is what makes
    the sampler fast-forward on resume exact."""
    import numpy as np
    rs = np.random.RandomState(1000 + step)
    x = rs.randn(batch, FEATURES).astype('float32')
    y = rs.randint(0, CLASSES, (batch,)).astype('float32')
    return x, y


def _param_hash(net):
    """sha256 over the float32 bytes of every parameter in
    architecture order — equal hash == bit-identical params."""
    import numpy as np
    h = hashlib.sha256()
    prefix = getattr(net, 'prefix', '')
    for name, p in sorted(net.collect_params().items()):
        key = name[len(prefix):] if prefix and name.startswith(prefix) \
            else name
        h.update(key.encode())
        h.update(np.ascontiguousarray(p.data().asnumpy(),
                                      dtype='<f4').tobytes())
    return h.hexdigest()


def _write(path, payload):
    from .checkpoint import atomic_write_bytes
    atomic_write_bytes(path, (json.dumps(payload, indent=1,
                                         sort_keys=True) + '\n')
                       .encode())


def _configure_flight(args):
    """Point the global flight recorder at the requested artifact path
    so an injected stall/preempt escalation dumps somewhere the caller
    (tools/fault_smoke.py) can validate."""
    from mxnet_tpu import observability
    observability.configure_flight(path=args.flight_artifact,
                                   name='resilience-selftest')


def run_train(args):
    import numpy as onp
    from mxnet_tpu import nd, parallel
    from . import (CheckpointManager, PreemptionHandler, Watchdog,
                   available_devices, shrink_plan)

    _configure_flight(args)
    devs = available_devices()     # honors device_loss@elastic.restart
    mgr = CheckpointManager(args.ckpt_dir, prefix='pt', keep=3) \
        if args.ckpt_dir else None
    latest = mgr.latest() if mgr is not None else None

    accum = 1
    if latest is not None and latest[1].get('mesh'):
        meta = latest[1]['mesh']
        plan = shrink_plan(meta, len(devs))
        axes, accum = plan.new_axes, plan.accum_steps
    else:
        axes = {'dp': len(devs)}
    n_mesh = 1
    for v in axes.values():
        n_mesh *= int(v)
    mesh = parallel.create_mesh(axes, devices=devs[:n_mesh])

    net, loss = _net_and_loss()
    pt = parallel.ParallelTrainer(net, loss, 'sgd',
                                  {'learning_rate': 0.1,
                                   'momentum': 0.9}, mesh)
    if args.batch % (accum or 1):
        raise SystemExit('batch %d not divisible by accum %d'
                         % (args.batch, accum))
    x0, y0 = _batch(0, args.batch)
    micro = args.batch // accum
    pt.build(nd.array(x0[:micro]), nd.array(y0[:micro]))

    start = 0
    if mgr is not None:
        resumed = pt.resume(mgr)
        if resumed is not None:
            start = resumed[0]
            print('selftest: resumed at step %d (accum=%d, mesh=%s)'
                  % (start, accum, dict(axes)), flush=True)

    handler = PreemptionHandler().install()
    watchdog = Watchdog(artifact_path=args.stall_artifact)
    pt.attach_preemption(handler).attach_watchdog(watchdog)
    if mgr is not None:
        pt.attach_checkpointing(mgr, every_n=args.ckpt_every)

    losses = []
    for step in range(start, args.steps):
        x, y = _batch(step, args.batch)
        if accum > 1:
            out = pt.step_accum(nd.array(x), nd.array(y), accum)
        else:
            out = pt.step(nd.array(x), nd.array(y))
        losses.append(float(onp.asarray(out.asnumpy())))

    _write(args.out, {
        'steps': args.steps,
        'start_step': start,
        'accum': accum,
        'mesh': {k: int(v) for k, v in dict(axes).items()},
        'losses': losses,
        'final_loss': losses[-1] if losses else None,
        'param_hash': _param_hash(net),
    })
    print('selftest: trained steps [%d, %d) accum=%d -> %s'
          % (start, args.steps, accum, args.out), flush=True)
    return 0


def run_watchdog_smoke(args):
    from mxnet_tpu import nd, parallel
    from . import DeviceStallError, Watchdog

    _configure_flight(args)
    mesh = parallel.create_mesh()      # whatever devices exist
    net, loss = _net_and_loss()
    pt = parallel.ParallelTrainer(net, loss, 'sgd',
                                  {'learning_rate': 0.1}, mesh)
    watchdog = Watchdog(artifact_path=args.stall_artifact,
                        name='watchdog-smoke')
    pt.attach_watchdog(watchdog)
    detected = None
    try:
        for step in range(args.steps):
            x, y = _batch(step, args.batch)
            pt.step(nd.array(x), nd.array(y))
    except DeviceStallError as exc:
        detected = {'step': pt.num_update - 1, 'error': str(exc)}
    record = watchdog.last_record or {}
    artifact_ok = os.path.exists(args.stall_artifact)
    _write(args.out, {
        'detected': detected is not None,
        'detail': detected,
        'artifact': args.stall_artifact if artifact_ok else None,
        'schema': record.get('schema'),
        'phase': record.get('phase'),
        'waited_s': record.get('waited_s'),
        'budget_s': record.get('budget_s'),
    })
    ok = detected is not None and artifact_ok
    print('selftest: watchdog %s (artifact=%s)'
          % ('detected the hang' if ok else 'MISSED the hang',
             args.stall_artifact), flush=True)
    return 0 if ok else 1


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m mxnet_tpu.resilience',
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    mode = p.add_mutually_exclusive_group(required=True)
    mode.add_argument('--train', action='store_true',
                      help='deterministic training leg (preempt / '
                           'resume / elastic-shrink contract)')
    mode.add_argument('--watchdog-smoke', action='store_true',
                      help='injected-hang detection leg')
    p.add_argument('--steps', type=int, default=18)
    p.add_argument('--batch', type=int, default=32)
    p.add_argument('--devices', type=int, default=None,
                   help='virtual device count (also set XLA_FLAGS '
                        'before jax initializes; fault_smoke does)')
    p.add_argument('--ckpt-dir', default=None)
    p.add_argument('--ckpt-every', type=int, default=5)
    p.add_argument('--out', default='SELFTEST.json')
    p.add_argument('--stall-artifact', default='STALL.json')
    p.add_argument('--flight-artifact', default='FLIGHT.jsonl',
                   help='flight-recorder dump path (written on an '
                        'injected stall/preempt escalation; schema '
                        'mxnet_tpu.flight.v1, docs/OBSERVABILITY.md)')
    args = p.parse_args(argv)
    if args.train:
        return run_train(args)
    return run_watchdog_smoke(args)


if __name__ == '__main__':
    sys.exit(main())
