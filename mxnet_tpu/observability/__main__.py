"""Observability selftest (CI tier 'observability', tools/ci.py).

CPU-runnable proof of the unified-telemetry contract
(docs/OBSERVABILITY.md), in eight legs:

  1. registry     — counter/gauge/histogram math, label children,
                    power-of-two bucket placement, snapshot shape,
                    redeclaration-mismatch rejection.
  2. disabled     — with telemetry off, mutators change nothing AND
                    allocate nothing per call (tracemalloc-verified:
                    the acceptance bar for the hot-path no-op).
  2b. trace       — request-tracing header round trip, span-buffer
                    bound + NDJSON drain, and the disabled path
                    allocating nothing per span (same tracemalloc
                    bar).
  3. flight       — ring overflow drops oldest, dump round-trips
                    through read_flight with the v1 schema, torn tail
                    lines are tolerated.
  4. exporters    — Prometheus text parses under the schema check
                    (counter monotonicity across samples, cumulative
                    histogram buckets ending at count); the HTTP
                    server is OFF by default and serves when asked.
  5. spans        — phase spans land in the phase histogram.
  6. train        — a tiny fused ParallelTrainer run on the virtual
                    mesh populates step/compile/example instruments,
                    flight step events, and the HLO collective-bytes
                    gauges (all-reduce visible when dp > 1).
  7. bit_identical — telemetry on vs off trains to bit-identical
                    params (instruments never touch the compiled
                    program; the wall-clock A/B lives in bench.py as
                    telemetry_overhead_pct).

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
      python -m mxnet_tpu.observability --out OBS_SELFTEST.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tracemalloc

# best-effort: honor --devices before the jax backend initializes
if '--devices' in sys.argv[:-1]:
    _n = sys.argv[sys.argv.index('--devices') + 1]
    _flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in _flags:
        os.environ['XLA_FLAGS'] = (
            _flags + ' --xla_force_host_platform_device_count=%s'
            % _n).strip()
from .. import config as _config  # noqa: E402
_config.cpu_rig('observability')


def check_registry():
    from . import metrics
    reg = metrics.MetricsRegistry()
    c = reg.counter('c_total', help='h')
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5, c.value
    try:
        c.inc(-1)
        return 'negative counter inc not rejected'
    except ValueError:
        pass
    g = reg.gauge('g', labels=('k',))
    g.labels(k='a').set(4)
    g.labels(k='a').inc()
    g.labels(k='b').dec(2)
    assert g.labels(k='a').value == 5.0
    assert g.labels(k='b').value == -2.0
    h = reg.histogram('h_seconds')
    h.observe(1.0)      # exact power of two: must land in le=1.0
    h.observe(0.75)     # in (0.5, 1.0]
    h.observe(1e9)      # +Inf overflow bucket
    idx_1 = metrics.P2_BOUNDS.index(1.0)
    buckets = h.buckets()
    assert buckets[idx_1] - (buckets[idx_1 - 1] if idx_1 else 0) == 2, \
        'power-of-two placement wrong: %r' % (buckets,)
    assert buckets[-1] == h.count == 3
    assert abs(h.sum - (1.75 + 1e9)) < 1e-3
    try:
        reg.counter('g')        # type mismatch with the gauge
        return 'metric type mismatch not rejected'
    except ValueError:
        pass
    snap = reg.snapshot()
    # every snapshot carries the synthetic process-identity stamp
    # (docs/DISTRIBUTED.md) alongside the declared families
    assert set(snap) == {'c_total', 'g', 'h_seconds',
                         'mxnet_tpu_process'}
    stamp = snap['mxnet_tpu_process']['series'][0]['labels']
    assert set(stamp) == {'process_id', 'process_count'}
    assert snap['h_seconds']['series'][0]['buckets'][-1] == 3
    return None


def check_disabled():
    from . import metrics
    reg = metrics.MetricsRegistry()
    c = reg.counter('d_total')
    g = reg.gauge('d_gauge')
    h = reg.histogram('d_seconds')
    c.inc()
    prev_counter = c.value
    metrics.set_enabled(False)
    try:
        # warm up any lazy state, then measure allocations
        for _ in range(4):
            c.inc()
            g.set(1.0)
            h.observe(0.5)
        tracemalloc.start()
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            c.inc()
            g.set(1.0)
            h.observe(0.5)
        after = tracemalloc.take_snapshot()
        tracemalloc.stop()
        # attribute allocations to the metric implementation only: the
        # measuring loop itself (this file) legitimately allocates its
        # range iterator etc. CPython occasionally heap-materializes a
        # couple of call frames (O(1), not O(calls)) — the bar is "no
        # PER-CALL allocation", i.e. counts must not scale with the
        # 3000 mutator calls above.
        from . import metrics as _m
        impl = os.path.abspath(_m.__file__)
        grew = nalloc = 0
        for stat in after.compare_to(before, 'filename'):
            fname = stat.traceback[0].filename
            if os.path.abspath(fname) == impl and stat.size_diff > 0:
                grew += stat.size_diff
                nalloc += stat.count_diff
        if nalloc > 100 or grew > 4096:
            return ('disabled-path mutators allocated %d bytes / %d '
                    'blocks over 3000 calls (per-call allocation)'
                    % (grew, nalloc))
        if c.value != prev_counter or g.value != 0.0 or h.count != 0:
            return 'disabled-path mutators changed metric state'
    finally:
        metrics.set_enabled(None)
    return None


def check_trace():
    """Request tracing (docs/OBSERVABILITY.md "Distributed request
    tracing"): header round trip, buffer bound + NDJSON drain,
    stitch/verdict, and the disabled path allocating nothing per
    span."""
    from . import trace
    ctx = trace.TraceContext.new()
    hop = trace.parse_header(ctx.to_header())
    if hop is None or hop.trace_id != ctx.trace_id:
        return 'trace header did not round-trip'
    trace.set_enabled(True)
    try:
        buf = trace.SpanBuffer(capacity=4, site='selftest')
        root = ctx.child()
        buf.emit('gw.request', root, 0.0, 1.0)
        for i in range(6):
            with buf.span('gw.relay', root):
                pass
        st = buf.stats()
        if st['buffered'] != 4 or st['dropped'] != 3:
            return ('buffer bound broken: %r' % (st,))
        recs = trace.read_ndjson(buf.ndjson())
        if len(recs) != 4:
            return 'ndjson drain lost records'
    finally:
        trace.set_enabled(None)
    trace.set_enabled(False)
    try:
        buf = trace.SpanBuffer(capacity=4, site='selftest')
        for _ in range(4):                    # warm lazy state
            with buf.span('x', ctx):
                pass
            buf.emit('y', ctx.child(), 0.0, 1.0)
        tracemalloc.start()
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            with buf.span('x', ctx):
                pass
        after = tracemalloc.take_snapshot()
        tracemalloc.stop()
        impl = os.path.abspath(trace.__file__)
        grew = nalloc = 0
        for stat in after.compare_to(before, 'filename'):
            fname = stat.traceback[0].filename
            if os.path.abspath(fname) == impl and stat.size_diff > 0:
                grew += stat.size_diff
                nalloc += stat.count_diff
        if nalloc > 100 or grew > 4096:
            return ('disabled-path spans allocated %d bytes / %d '
                    'blocks over 1000 calls (per-call allocation)'
                    % (grew, nalloc))
        if buf.read() or buf.stats()['emitted'] != 0:
            return 'disabled-path spans reached the buffer'
    finally:
        trace.set_enabled(None)
    return None


def check_flight(tmpdir):
    from .recorder import FLIGHT_SCHEMA, FlightRecorder, read_flight
    rec = FlightRecorder(capacity=8, name='selftest')
    rec.set_enabled(True)
    for i in range(20):
        rec.record('step', step=i)
    rec.record('stall', step=19, phase='step')
    events = rec.events()
    assert len(events) == 8, len(events)
    assert events[-1]['kind'] == 'stall'
    assert events[0]['step'] == 13       # oldest 13 of 21 dropped
    path = os.path.join(tmpdir, 'FLIGHT.jsonl')
    assert rec.dump(path=path, reason='selftest') == path
    header, parsed = read_flight(path)
    assert header['schema'] == FLIGHT_SCHEMA
    assert header['dropped'] == 13 and header['events'] == 8
    assert [e['kind'] for e in parsed] == \
        [e['kind'] for e in events]
    # torn tail line must not break the parse
    with open(path, 'a') as f:
        f.write('{"kind": "trunc')
    header2, parsed2 = read_flight(path)
    assert len(parsed2) == 8
    return None


def check_exporters(tmpdir):
    from . import export, metrics
    reg_mod_snapshot = metrics.snapshot      # uses default registry
    c = metrics.counter('selftest_requests_total', help='n')
    h = metrics.histogram('selftest_latency_seconds',
                          labels=('path',))
    c.inc(3)
    h.labels(path='/a').observe(0.1)
    h.labels(path='/a').observe(0.2)
    text1 = export.prometheus_text()
    types, samples1 = export.parse_prometheus(text1)
    assert types['selftest_requests_total'] == 'counter'
    assert types['selftest_latency_seconds'] == 'histogram'
    c.inc(2)
    _, samples2 = export.parse_prometheus(export.prometheus_text())

    def sample(samples, name, **labels):
        for n, lab, v in samples:
            if n == name and all(lab.get(k) == v2
                                 for k, v2 in labels.items()):
                return v
        raise AssertionError('sample %s%r missing' % (name, labels))

    v1 = sample(samples1, 'selftest_requests_total')
    v2 = sample(samples2, 'selftest_requests_total')
    assert v2 > v1, 'counter not monotonic (%r -> %r)' % (v1, v2)
    # cumulative buckets: non-decreasing, +Inf bucket == count
    buckets = [(lab['le'], v) for n, lab, v in samples1
               if n == 'selftest_latency_seconds_bucket'
               and lab.get('path') == '/a']
    vals = [v for _, v in buckets]
    assert vals == sorted(vals), 'buckets not cumulative'
    count = sample(samples1, 'selftest_latency_seconds_count',
                   path='/a')
    assert buckets[-1][0] == '+Inf' and buckets[-1][1] == count == 2
    ssum = sample(samples1, 'selftest_latency_seconds_sum', path='/a')
    assert abs(ssum - 0.3) < 1e-9
    # file + jsonl exporters
    p = export.write_prometheus(os.path.join(tmpdir, 'metrics.prom'))
    export.parse_prometheus(open(p).read())
    export.write_jsonl(os.path.join(tmpdir, 'metrics.jsonl'),
                       snapshot=reg_mod_snapshot())
    for ln in open(os.path.join(tmpdir, 'metrics.jsonl')):
        json.loads(ln)
    # HTTP: off by default...
    assert export.maybe_start_http_server() is None, \
        'HTTP server started without MXNET_TPU_TELEMETRY_HTTP_PORT'
    # ...serves when constructed explicitly
    import urllib.request
    with export.PrometheusServer(0) as srv:
        body = urllib.request.urlopen(
            'http://127.0.0.1:%d/metrics' % srv.port, timeout=5).read()
    export.parse_prometheus(body.decode())
    return None


def check_spans():
    from . import spans
    child = spans.phase_histogram('sync')
    before = child.count
    with spans.span('sync'):
        pass
    assert child.count == before + 1, 'span did not record'
    return None


def check_train(devices):
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.gluon import nn
    from . import (get_recorder, trainer_collective_stats,
                   trainer_instruments)

    devs = jax.devices()
    dp = min(devices or len(devs), len(devs))
    np.random.seed(7)
    mx.random.seed(7)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation='relu'), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    mesh = parallel.create_mesh({'dp': dp}, devices=devs[:dp])
    pt = parallel.ParallelTrainer(net, gluon.loss
                                  .SoftmaxCrossEntropyLoss(),
                                  'sgd', {'learning_rate': 0.1}, mesh)
    batch = 8 * dp
    x = nd.array(np.random.randn(batch, 16).astype('float32'))
    y = nd.array(np.random.randint(0, 4, (batch,)).astype('float32'))
    inst = trainer_instruments()
    steps0 = inst.steps.value
    examples0 = inst.examples.value
    compile0 = inst.compile_seconds.count
    nsteps = 4
    for _ in range(nsteps):
        pt.step(x, y)
    assert inst.steps.value == steps0 + nsteps
    assert inst.examples.value == examples0 + nsteps * batch
    assert inst.compile_seconds.count > compile0, \
        'first-step compile not recorded'
    assert inst.step_seconds.count >= nsteps - 1
    kinds = [e['kind'] for e in get_recorder().events()]
    assert kinds.count('step') >= nsteps, kinds[-10:]
    total, per_kind = trainer_collective_stats(pt)
    if dp > 1:
        assert total > 0 and 'all-reduce' in per_kind, \
            'no collective bytes accounted on a dp=%d mesh: %r' \
            % (dp, per_kind)
    return None


def check_bit_identical(devices):
    """Telemetry on vs off must not alter training numerics: the
    instruments live on the host dispatch path, the compiled program
    is identical, so params after N identical steps are bit-identical.
    (The wall-clock side of the A/B is recorded by bench.py as
    ``telemetry_overhead_pct`` — deterministic structure is asserted
    here, noisy timing is reported there.)"""
    import hashlib
    import numpy as np
    import jax
    from . import metrics as _metrics

    def run(enabled):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon, nd, parallel
        from mxnet_tpu.gluon import nn
        _metrics.set_enabled(enabled)
        try:
            devs = jax.devices()
            dp = min(devices or len(devs), len(devs))
            np.random.seed(5)
            mx.random.seed(5)
            net = nn.HybridSequential()
            with net.name_scope():
                net.add(nn.Dense(16, activation='relu'), nn.Dense(4))
            net.initialize(mx.init.Xavier())
            net.hybridize()
            mesh = parallel.create_mesh({'dp': dp},
                                        devices=devs[:dp])
            pt = parallel.ParallelTrainer(
                net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
                {'learning_rate': 0.1}, mesh)
            rs = np.random.RandomState(0)
            x = nd.array(rs.randn(8 * dp, 16).astype('float32'))
            y = nd.array(rs.randint(0, 4, (8 * dp,))
                         .astype('float32'))
            for _ in range(5):
                pt.step(x, y)
            h = hashlib.sha256()
            for name, p in sorted(net.collect_params().items()):
                h.update(np.ascontiguousarray(
                    p.data().asnumpy(), dtype='<f4').tobytes())
            return h.hexdigest()
        finally:
            _metrics.set_enabled(None)

    on, off = run(True), run(False)
    if on != off:
        return ('telemetry changed training numerics: on=%s off=%s'
                % (on[:12], off[:12]))
    return None


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m mxnet_tpu.observability',
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument('--devices', type=int, default=None,
                   help='virtual device count for the train leg (also '
                        'set XLA_FLAGS before jax initializes)')
    p.add_argument('--out', default='OBS_SELFTEST.json')
    p.add_argument('--skip-train', action='store_true',
                   help='registry/flight/exporter legs only (no jax)')
    args = p.parse_args(argv)

    import tempfile
    checks = {}
    with tempfile.TemporaryDirectory() as tmp:
        legs = [('registry', check_registry),
                ('disabled', check_disabled),
                ('trace', check_trace),
                ('flight', lambda: check_flight(tmp)),
                ('exporters', lambda: check_exporters(tmp)),
                ('spans', check_spans)]
        if not args.skip_train:
            legs.append(('train', lambda: check_train(args.devices)))
            legs.append(('bit_identical',
                         lambda: check_bit_identical(args.devices)))
        for name, fn in legs:
            try:
                problem = fn()
            except Exception as exc:
                import traceback
                traceback.print_exc()
                problem = '%s: %s' % (type(exc).__name__, exc)
            checks[name] = problem or 'ok'
            print('selftest %-10s %s' % (name, checks[name]),
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
