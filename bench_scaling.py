"""Data-parallel scaling-efficiency harness (BASELINE.json metric 3).

Measures the fused train step at dp=1/2/4/... over whatever devices
exist, reports throughput, efficiency vs dp=1, and per-step collective
traffic (all-reduce / all-gather / reduce-scatter bytes parsed from the
optimized HLO), and writes a JSON artifact. This is the measuring
instrument for the reference's multi-GPU scaling table
(example/image-classification/README.md:307-319, ~90% efficiency at
8-256 GPUs): on real multi-chip hardware it is one command; on this rig
it validates its plumbing on the virtual 8-device CPU mesh (numbers
there are meaningless, the artifact structure and comm accounting are
not).

Usage:
  python bench_scaling.py                       # resnet50, dp=1..8
  python bench_scaling.py --model mlp --dp 1,2  # tiny smoke (tests)
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python bench_scaling.py --image 64        # virtual-mesh check

The per-chip batch is held constant (weak scaling, like the reference
table), so efficiency = rate(dp) / (dp * rate(1)).

The sharded-update leg (skip with --no-zero-leg) A/Bs the replicated
weight update against the ZeRO dp-sharded one (MXNET_TPU_ZERO,
docs/PARALLEL.md) at the largest measured dp and records per-device
optimizer-state bytes (ideal 1/dp of replicated), per-step collective
traffic, and step time under artifact key ``zero_update``.

The MULTICHIP leg (``--dist``, docs/DISTRIBUTED.md) spawns a REAL
two-process dp=2 pod over the local Gloo launcher and records the
cross-host trainer's step time and per-step collective bytes under
artifact key ``dist`` — the multi-host analog of the rows table (the
same key the ``dist`` CI stage checks; on this rig the numbers price
the Gloo loopback, on a pod they price DCN).
"""
import argparse
import json
import time

import numpy as np

def collective_bytes(hlo_text):
    """Sum output bytes of collective ops in optimized HLO text.

    The accounting now lives in the library
    (mxnet_tpu/observability/hlo.py) so normal training runs can
    record their own comm volume; this compatibility shim delegates
    lazily — the bench drivers keep all mxnet_tpu imports inside
    functions so ``--help`` stays instant."""
    from mxnet_tpu.observability.hlo import collective_bytes as impl
    return impl(hlo_text)


def _build(model, dp, batch_per_chip, image, devices, zero=False):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.gluon import model_zoo, nn

    mesh = parallel.create_mesh({'dp': dp}, devices=devices[:dp])
    if model == 'resnet50':
        net = model_zoo.vision.resnet50_v1()
        classes = 1000
    elif model == 'mlp':
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(64, activation='relu'), nn.Dense(10))
        classes = 10
    else:
        raise ValueError(model)
    net.initialize(mx.init.Xavier())
    on_accel = devices[0].platform != 'cpu'
    if on_accel:
        net.cast('bfloat16')
    net.hybridize(static_alloc=True, static_shape=True)
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    B = dp * batch_per_chip
    shape = (B, 3, image, image) if model == 'resnet50' else (B, 32)
    dtype = 'bfloat16' if on_accel else 'float32'
    x = nd.array(np.random.uniform(-1, 1, shape), dtype=dtype)
    y = nd.array(np.random.randint(0, classes, (B,)))
    pt = parallel.ParallelTrainer(
        net, L, 'sgd', {'learning_rate': 0.05, 'momentum': 0.9}, mesh,
        zero=zero)
    pt.step(x, y)          # compile
    return pt, x, y


def _time_step(pt, x, y, iters, slope):
    def window(n):
        out = pt.step(x, y)
        out.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(n):
            out = pt.step(x, y)
        out.wait_to_read()
        return time.perf_counter() - t0

    if slope:
        # accelerators: difference out the fixed per-sync cost
        t_lo = window(iters)
        t_hi = window(3 * iters)
        return (t_hi - t_lo) / (2 * iters)
    return window(iters) / iters


def step_hlo(pt, x, y):
    """Optimized HLO of the compiled fused step (lower() only reads
    shapes — nothing executes, nothing is donated)."""
    import jax.numpy as jnp
    indices = list(range(len(pt._params)))
    hyper = pt._hyper(indices, pt._opt, advance=False)
    key = np.zeros(2, np.uint32)
    xs = tuple(jnp.asarray(a._data) for a in [x])
    ys = tuple(jnp.asarray(a._data) for a in [y])
    lowered = pt._jitted.lower(key, hyper, pt._param_arrays,
                               pt._state_leaves, xs, ys)
    return lowered.compile().as_text()


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--model', default='resnet50',
                   choices=['resnet50', 'mlp'])
    p.add_argument('--dp', default=None,
                   help='comma list of dp sizes (default: 1,2,4,.. up '
                        'to the device count)')
    p.add_argument('--batch-per-chip', type=int, default=None)
    p.add_argument('--image', type=int, default=None)
    p.add_argument('--iters', type=int, default=None)
    p.add_argument('--no-zero-leg', action='store_true',
                   help='skip the sharded-update (ZeRO) A/B leg')
    p.add_argument('--dist', action='store_true',
                   help='add the MULTICHIP leg: a 2-process dp=2 pod '
                        'over the local Gloo launcher (step time + '
                        'collective bytes under artifact key "dist")')
    p.add_argument('--dist-worker', default=None,
                   help=argparse.SUPPRESS)   # internal: pod worker out
    p.add_argument('--out', default='SCALING.json')
    args = p.parse_args(argv)

    if args.dist_worker:
        return _dist_worker(args)

    import jax
    from mxnet_tpu.resilience import acquire_backend, write_artifact
    status = acquire_backend()
    if not status.usable:
        # degraded-mode contract (docs/RESILIENCE.md): record the
        # outage in the artifact and exit 0 instead of tracebacking
        print('bench_scaling: backend unavailable after %d attempt(s): '
              '%s' % (status.attempts, status.error), flush=True)
        artifact = {'model': args.model, 'batch_per_chip': None,
                    'image': None, 'weak_scaling': True, 'rows': [],
                    'status': 'unavailable',
                    'backend': status.as_dict(), 'error': status.error}
        write_artifact(args.out, artifact)
        return artifact
    # enumerate the platform acquire_backend settled on: a bare
    # jax.devices() would re-trigger the failed TPU init that the
    # cpu-fallback just absorbed
    devices = jax.devices(status.platform)
    on_accel = devices[0].platform != 'cpu'
    n = len(devices)
    if args.dp:
        dp_list = [int(s) for s in args.dp.split(',')]
    else:
        dp_list = [d for d in (1, 2, 4, 8, 16, 32) if d <= n]
    batch = args.batch_per_chip or (128 if on_accel else 4)
    image = args.image or (224 if on_accel else 32)
    iters = args.iters or (30 if on_accel else 3)

    rows = []
    base_rate = None
    last = None           # (dp, pt, dt, comm, per_kind) of the last row
    for dp in dp_list:
        if dp > n:
            row = {'dp': dp, 'skipped': 'only %d devices' % n}
            rows.append(row)          # artifact stays self-describing
            print(json.dumps(row), flush=True)
            continue
        pt, x, y = _build(args.model, dp, batch, image, devices)
        dt = _time_step(pt, x, y, iters, slope=on_accel)
        rate = dp * batch / dt
        if base_rate is None:
            base_rate = rate / dp   # first measured row is the reference
        comm, per_kind = collective_bytes(step_hlo(pt, x, y))
        row = {
            'dp': dp,
            'global_batch': dp * batch,
            'ms_per_step': round(dt * 1e3, 2),
            'samples_per_sec': round(rate, 1),
            'efficiency_pct': round(100 * rate / (dp * base_rate), 1)
            if base_rate else None,
            'comm_bytes_per_step': comm,
            'comm_by_kind': per_kind,
            'device_kind': devices[0].device_kind,
            'platform': devices[0].platform,
        }
        rows.append(row)
        last = (dp, pt, dt, comm, per_kind)
        print(json.dumps(row), flush=True)

    # sharded-update leg (docs/PARALLEL.md): A/B the replicated weight
    # update against MXNET_TPU_ZERO=1 at the largest measured dp —
    # per-device optimizer-state bytes (the ZeRO memory win, ideal
    # 1/dp), per-step collective traffic (the reduce-scatter +
    # all-gather the sharded update trades the plain all-reduce for),
    # and step time
    zero_leg = None
    measured = [dp for dp in dp_list if dp <= n and dp > 1]
    if not args.no_zero_leg and measured:
        dp = max(measured)

        def leg(zero):
            pt, x, y = _build(args.model, dp, batch, image, devices,
                              zero=zero)
            dt = _time_step(pt, x, y, iters, slope=on_accel)
            per_dev, logical = pt.optimizer_state_bytes()
            comm, per_kind = collective_bytes(step_hlo(pt, x, y))
            return {'ms_per_step': round(dt * 1e3, 2),
                    'opt_state_bytes_per_device': per_dev,
                    'opt_state_bytes_logical': logical,
                    'comm_bytes_per_step': comm,
                    'comm_by_kind': per_kind}

        # free the rows-loop trainer (params + state + executable in
        # device memory) before building anything new — holding two
        # trainers doubles peak HBM at the largest dp; the loop locals
        # alias it too
        reuse = last if last is not None and last[0] == dp else None
        last = pt = x = y = None
        if reuse is not None:
            # the rows loop just compiled+timed this exact replicated
            # config — only the state-bytes accounting is new
            _, pt, dt, comm, per_kind = reuse
            per_dev, logical = pt.optimizer_state_bytes()
            replicated = {'ms_per_step': round(dt * 1e3, 2),
                          'opt_state_bytes_per_device': per_dev,
                          'opt_state_bytes_logical': logical,
                          'comm_bytes_per_step': comm,
                          'comm_by_kind': per_kind}
            del pt, reuse
        else:
            replicated = leg(False)
        sharded = leg(True)
        zero_leg = {
            'dp': dp,
            'replicated': replicated,
            'sharded': sharded,
            'state_bytes_ratio': round(
                sharded['opt_state_bytes_per_device']
                / max(1, replicated['opt_state_bytes_per_device']), 4),
        }
        print(json.dumps({'zero_update': zero_leg}), flush=True)

    dist_leg = None
    if args.dist:
        dist_leg = _dist_leg(batch, iters)
        print(json.dumps({'dist': dist_leg}), flush=True)

    artifact = {'model': args.model, 'batch_per_chip': batch,
                'image': image, 'weak_scaling': True, 'rows': rows,
                'zero_update': zero_leg, 'dist': dist_leg,
                'status': 'ok' if on_accel else 'degraded',
                'backend': status.as_dict(), 'error': status.error}
    write_artifact(args.out, artifact)
    return artifact


def _dist_worker(args):
    """Pod-worker half of the MULTICHIP leg: joined via the launcher
    env, train dp=2 across both processes, rank 0 writes the record."""
    import jax
    jax.config.update('jax_default_matmul_precision', 'float32')
    import mxnet_tpu as mx
    from mxnet_tpu import dist, gluon, nd, parallel
    from mxnet_tpu.gluon import nn

    c = dist.get_coordinator()
    c.start_heartbeat()
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(64, activation='relu'), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    mesh = dist.global_mesh({'dp': 2})
    batch = args.batch_per_chip or 4
    B = 2 * batch
    x = np.random.uniform(-1, 1, (B, 32)).astype('float32')
    y = np.random.randint(0, 10, (B,)).astype('float32')
    lo, hi = dist.host_shard(mesh, B)
    xl, yl = nd.array(x[lo:hi]), nd.array(y[lo:hi])
    pt = parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.05, 'momentum': 0.9}, mesh)
    pt.step(xl, yl)                       # compile
    iters = args.iters or 10
    c.barrier('bench_start', timeout_s=60)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = pt.step(xl, yl)
    out.wait_to_read()
    dt = (time.perf_counter() - t0) / iters
    comm, per_kind = collective_bytes(pt.compiled_text())
    c.barrier('bench_done', timeout_s=60)
    if c.process_id == 0:
        from mxnet_tpu.resilience.checkpoint import atomic_write_bytes
        record = {
            'model': 'mlp',
            'processes': c.process_count,
            'devices_per_host': 1,
            'dp': 2,
            'global_batch': B,
            'ms_per_step': round(dt * 1e3, 2),
            'samples_per_sec': round(B / dt, 1),
            'comm_bytes_per_step': comm,
            'comm_by_kind': per_kind,
            'transport': 'gloo-loopback',
        }
        atomic_write_bytes(args.dist_worker,
                           (json.dumps(record, sort_keys=True)
                            + '\n').encode())
    return 0


def _dist_leg(batch, iters):
    """Spawn the 2-process pod and collect rank 0's record (the
    MULTICHIP bench leg; always the MLP model — the record says so).
    A launch failure degrades to a typed record instead of failing the
    whole bench — same posture as the backend acquire."""
    import os
    import sys
    import tempfile
    from mxnet_tpu.dist import launcher
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, 'dist_row.json')
        res = launcher.launch_local(
            2,
            [sys.executable, os.path.abspath(__file__),
             '--model', 'mlp', '--batch-per-chip', str(batch),
             '--iters', str(iters), '--dist-worker', out],
            env={'PYTHONPATH': os.pathsep.join(
                [os.path.dirname(os.path.abspath(__file__)),
                 os.environ.get('PYTHONPATH', '')])},
            log_dir=os.path.join(tmp, 'logs'), platform='cpu',
            local_devices=1, timeout=300)
        if not res.ok or not os.path.exists(out):
            # tail the CAUSAL rank's log: a launcher-terminated peer
            # (-15) is collateral, its log hides the real error
            causes = [w for w in res.failures()
                      if w.returncode != -15] or res.failures()
            return {'status': 'failed',
                    'returncodes': res.returncodes,
                    'rank': causes[0].rank if causes else None,
                    'tail': causes[0].log_tail(600) if causes else ''}
        with open(out) as f:
            record = json.load(f)
    record['status'] = 'ok'
    return record


if __name__ == '__main__':
    main()
