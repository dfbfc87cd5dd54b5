"""Benchmark driver: training throughput on one chip.

Prints ONE JSON line per metric:
  resnet50_train_img_per_sec_per_chip   (primary; vs V100 fp32 baseline)
  bert_base_pretrain_samples_per_sec_per_chip

Each line also reports tflops_per_sec and mfu_pct (model FLOPs
utilisation against the chip's bf16 peak) and which step path produced
the number (fused vs eager fallback), so a fused-path regression is
visible in the artifact instead of masquerading as a slow-but-green
run.

Baselines: reference MXNet ResNet-50 fp32 train = 363.69 img/s on 1x
V100 bs=128 (BASELINE.md / docs/faq/perf.md:225-237) — the strongest
single-device number published in-tree. BERT-base: ~107 samples/s, a
1x V100 fp16 seq128 pretraining figure from public GluonNLP-era
scripts (the reference ships no in-tree BERT number; BASELINE.md).

Methodology mirrors example/image-classification/benchmark_score.py +
train_imagenet.py --benchmark 1 (synthetic data, steady-state rate),
with slope timing (two windows) so the fixed per-sync cost cancels
instead of biasing the rate.

A third metric line records the numerical-guardrail A/B
(guardrail_overhead_pct, docs/GUARDRAILS.md): the same compiled step
with and without the in-jit health sentinel + cond-guarded update,
plus the HLO op-count delta showing the sentinel is a fused reduction
(outfeed/infeed stay 0 — no host sync added per step).

A fourth line records the telemetry A/B (telemetry_overhead_pct,
docs/OBSERVABILITY.md): the SAME compiled step timed with the unified
telemetry layer on vs off (< 1% bar — the instruments live on the host
dispatch path only). The artifact payload also carries a 'telemetry'
summary block (registry snapshot + flight-recorder stats) so every
bench run ships its own machine-captured evidence.

A fifth line records the input-pipeline overlap A/B
(input_pipeline_overlap_pct, docs/PERFORMANCE.md): the same compiled
step driven from a decode-cost producer synchronously vs through the
double-buffered staging prefetcher; its record carries data_wait_pct
(residual wait share with staging on). The primary ResNet record also
carries hbm_bytes_per_step + fusion_count from the roofline audit of
its compiled step, so fusion-budget health rides every bench artifact.

Degraded-mode contract (docs/RESILIENCE.md): besides the stdout metric
lines, every run writes an atomic JSON artifact (--out, default
BENCH.json) with "status": "ok" | "degraded" | "unavailable" and exits
0 even when the backend cannot be initialised — the outage becomes a
recorded data point instead of a traceback. Backend init goes through
resilience.acquire_backend (bounded exponential-backoff retries,
cpu-fallback, typed status) instead of letting RuntimeError escape.
"""
import argparse
import json
import time

import numpy as np

# model FLOPs per sample (fwd+bwd ~= 3x fwd)
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.1e9       # 4.1 GFLOP fwd @224
BERT_BASE_PARAMS = 110e6

# bf16 peak by device kind; MFU is only reported when the chip is known
_PEAK_BY_KIND = (
    ('v5 lite', 197e12), ('v5e', 197e12),
    ('v5p', 459e12), ('v4', 275e12), ('v6', 918e12),
)


def _peak_flops():
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for tag, peak in _PEAK_BY_KIND:
        if tag in kind:
            return peak, tag
    return None, kind


def _peak_flops_precision(precision):
    """Chip peak at a given compute precision: the bf16 MXU rate from
    the device-kind table, scaled for fp32 by the same rule the
    roofline reference uses (MXNET_TPU_ROOFLINE_PEAK_TFLOPS_FP32 as a
    fraction of the bf16 reference peak; default half — the MXU fp32
    passthrough rate). MFU of an fp32 program against the bf16 peak
    would understate utilisation 2x (docs/PRECISION.md)."""
    peak, kind = _peak_flops()
    if peak and precision == 'fp32':
        from mxnet_tpu.config import get as _cfg
        fp32_ref = float(_cfg('MXNET_TPU_ROOFLINE_PEAK_TFLOPS_FP32'))
        bf16_ref = float(_cfg('MXNET_TPU_ROOFLINE_PEAK_TFLOPS'))
        ratio = (fp32_ref / bf16_ref) if fp32_ref > 0 and bf16_ref > 0 \
            else 0.5
        peak = peak * ratio
    return peak, kind


def _retry_transient(build):
    """Run a fused-step builder, retrying transient backend errors
    with backoff (resilience.Retry); deterministic failures — a
    refused compile among them — propagate immediately."""
    from mxnet_tpu.resilience import Retry, RetryExhausted
    try:
        return Retry(max_attempts=3, base_delay=10.0,
                     max_delay=60.0).call(build)
    except RetryExhausted as e:
        raise (e.last_error or e)


def _measure(step, warmup, iters, nd):
    """Slope timing: time one window of ``iters`` dispatches and one
    of ``3*iters`` (single sync each) and take the slope — the fixed
    cost per sync cancels exactly instead of smearing into the rate."""
    for _ in range(warmup):
        step()
    nd.waitall()

    def window(n):
        out = step()
        out.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(n):
            out = step()
        out.wait_to_read()
        return time.perf_counter() - t0

    t_lo = window(iters)
    t_hi = window(3 * iters)
    return (t_hi - t_lo) / (2 * iters)


def _guardrail_on():
    from mxnet_tpu import config
    return bool(config.get('MXNET_TPU_GUARDRAIL'))


def _telemetry_summary():
    """Compact registry + flight-recorder summary folded into the bench
    artifact so every bench run carries its own machine-captured
    evidence (steps dispatched, compile counts, phase split, jit-cache
    behavior — docs/OBSERVABILITY.md)."""
    try:
        from mxnet_tpu import observability
        return observability.summary()
    except Exception as e:     # telemetry must never sink the artifact
        return {'enabled': False,
                'error': '%s: %s' % (type(e).__name__, e)}


def _emit(metric, rate, unit, baseline, flops_per_sample, step_path,
          extra=None):
    tflops = rate * flops_per_sample / 1e12
    peak, kind = _peak_flops()
    rec = {
        'metric': metric,
        'value': round(rate, 2),
        'unit': unit,
        'vs_baseline': round(rate / baseline, 3),
        'tflops_per_sec': round(tflops, 2),
        'step_path': step_path,
        # fused steps honor MXNET_TPU_GUARDRAIL; a guarded number must
        # be labeled as one (the sentinel costs <2%, but it IS there).
        # The eager fallback applies no guardrail, so the knob alone
        # must not mark it 'on'
        'guardrail': 'on' if (_guardrail_on() and step_path == 'fused')
        else 'off',
        'device_kind': kind,
    }
    if extra:
        rec.update(extra)
    if peak:
        rec['mfu_pct'] = round(100 * tflops * 1e12 / peak, 2)
    print(json.dumps(rec), flush=True)
    return rec


def _fusion_health(pt):
    """Roofline totals of the compiled step (docs/PERFORMANCE.md): the
    same text analysis tools/fusion_audit.py gates on, folded into the
    throughput record so every capture tracks fusion health alongside
    img/s. Never sinks the bench leg."""
    try:
        from mxnet_tpu.observability import roofline
        totals = roofline.analyze(pt.compiled_text())[1]
        return {'hbm_bytes_per_step': totals['hbm_bytes_per_step'],
                'fusion_count': totals['fusion_count']}
    except Exception as e:
        return {'hbm_bytes_per_step': None,
                'fusion_note': '%s: %s' % (type(e).__name__,
                                           str(e)[:120])}


def bench_resnet(on_accel):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, parallel
    from mxnet_tpu.gluon import model_zoo

    batch = 128 if on_accel else 8
    image = 224 if on_accel else 64
    warmup, iters = (5, 120) if on_accel else (3, 3)

    net = model_zoo.vision.resnet50_v1()
    net.initialize(mx.init.Xavier())
    if on_accel:
        net.cast('bfloat16')   # TPU-native precision; BN stats stay safe
    net.hybridize(static_alloc=True, static_shape=True)

    L = gluon.loss.SoftmaxCrossEntropyLoss()
    dtype = 'bfloat16' if on_accel else 'float32'
    x = nd.array(np.random.uniform(-1, 1, (batch, 3, image, image)),
                 dtype=dtype)
    y = nd.array(np.random.randint(0, 1000, (batch,)))

    # one pjit-compiled, buffer-donating program per step (forward +
    # backward + allreduce + optimizer). Falls back to the eager
    # Trainer if the fused build fails — and says so in the artifact.
    step_path = 'fused'

    def _build_fused():
        mesh = parallel.create_mesh({'dp': 1}, devices=jax.devices()[:1])
        pt = parallel.ParallelTrainer(
            net, L, 'sgd', {'learning_rate': 0.1, 'momentum': 0.9,
                            'wd': 1e-4}, mesh)
        pt.step(x, y)   # compile here so a build failure falls back
        return pt

    fusion = {}
    try:
        pt = _retry_transient(_build_fused)
        fusion = _fusion_health(pt)

        def step():
            return pt.step(x, y)
    except Exception:
        step_path = 'eager-fallback'
        trainer = gluon.Trainer(net.collect_params(), 'sgd',
                                {'learning_rate': 0.1, 'momentum': 0.9,
                                 'wd': 1e-4})

        def step():
            with autograd.record():
                loss = L(net(x), y)
            # backward on the per-sample vector seeds ones (gradient of
            # the SUM); step(batch) rescales by 1/batch — together the
            # mean-gradient, identical to the fused path's mean loss
            loss.backward()
            trainer.step(batch)
            return loss

    dt = _measure(step, warmup, iters, nd)
    return _emit('resnet50_train_img_per_sec_per_chip', batch / dt,
                 'img/s', 363.69, RESNET50_TRAIN_FLOPS_PER_IMG,
                 step_path, extra=fusion)


def bench_bert(on_accel):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import bert as bert_zoo

    # bs sweep on-chip: 32 -> 607, 48 -> 630, 64 -> 647, 96 -> 682
    # samples/s; 96 keeps the MLM head matmuls MXU-sized without
    # pushing the step past HBM (older capture, other environment)
    batch = 96 if on_accel else 2
    seqlen = 128 if on_accel else 16
    npred = 20 if on_accel else 2
    vocab = 30522 if on_accel else 100
    warmup, iters = (5, 60) if on_accel else (3, 2)

    if on_accel:
        net = bert_zoo.bert_12_768_12(vocab_size=vocab, max_length=512,
                                      dropout=0.1)
    else:
        net = bert_zoo.get_bert('bert_12_768_12', vocab_size=vocab,
                                max_length=32, units=32, hidden_size=64,
                                num_layers=2, num_heads=4, dropout=0.1)
    net.initialize(mx.init.TruncNorm(stdev=0.02)
                   if hasattr(mx.init, 'TruncNorm') else mx.init.Xavier())
    if on_accel:
        net.cast('bfloat16')
    net.hybridize(static_alloc=True, static_shape=True)

    L = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    ids = nd.array(rs.randint(0, vocab, (batch, seqlen)))
    tt = nd.array((rs.rand(batch, seqlen) > 0.5).astype('float32'))
    vl = nd.array(np.full((batch,), seqlen, np.float32))
    mp = nd.array(rs.randint(0, seqlen, (batch, npred)))
    mlm_y = nd.array(rs.randint(0, vocab, (batch, npred)))
    nsp_y = nd.array(rs.randint(0, 2, (batch,)))

    step_path = 'fused'
    try:
        from mxnet_tpu import parallel

        def pretrain_loss(outs, labels):
            _, _, mlm_s, nsp_s = outs
            my, ny = labels
            return L(mlm_s.reshape((-1, vocab)),
                     my.reshape((-1,))).mean() + L(nsp_s, ny).mean()

        def _build_fused():
            mesh = parallel.create_mesh({'dp': 1},
                                        devices=jax.devices()[:1])
            pt = parallel.ParallelTrainer(
                net, pretrain_loss, 'adamw',
                {'learning_rate': 1e-4, 'wd': 0.01}, mesh)
            pt.step([ids, tt, vl, mp], [mlm_y, nsp_y])  # compile here
            return pt
        pt = _retry_transient(_build_fused)

        def step():
            return pt.step([ids, tt, vl, mp], [mlm_y, nsp_y])
    except Exception:
        step_path = 'eager-fallback'
        trainer = gluon.Trainer(net.collect_params(), 'adamw',
                                {'learning_rate': 1e-4, 'wd': 0.01})

        def step():
            with autograd.record():
                _, _, mlm_s, nsp_s = net(ids, tt, vl, mp)
                loss = L(mlm_s.reshape((-1, vocab)),
                         mlm_y.reshape((-1,))).mean() + \
                    L(nsp_s, nsp_y).mean()
            loss.backward()
            # the loss is already a mean: step(1) keeps the effective
            # lr identical to the fused path
            trainer.step(1)
            return loss

    dt = _measure(step, warmup, iters, nd)
    # transformer train FLOPs ~= 6 * params * tokens per sample
    flops_per_sample = 6 * BERT_BASE_PARAMS * seqlen
    return _emit('bert_base_pretrain_samples_per_sec_per_chip',
                 batch / dt, 'samples/s', 107.0, flops_per_sample,
                 step_path)


def _tiny_cnn_trainer(batch, image, guardrail=False):
    """Shared cnn-tiny A/B rig (guardrail + telemetry overhead legs):
    fixed seeds, same model/mesh, fused step compiled on return — so
    the two overhead records measure the same program family."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.gluon import nn

    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(16, 3, padding=1, activation='relu'),
                nn.Conv2D(32, 3, padding=1, activation='relu'),
                nn.GlobalAvgPool2D(), nn.Flatten(), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.hybridize(static_alloc=True, static_shape=True)
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    x = nd.array(np.random.uniform(-1, 1, (batch, 3, image, image)),
                 dtype='float32')
    y = nd.array(np.random.randint(0, 10, (batch,)))
    mesh = parallel.create_mesh({'dp': 1}, devices=jax.devices()[:1])
    pt = parallel.ParallelTrainer(
        net, L, 'sgd', {'learning_rate': 0.1, 'momentum': 0.9},
        mesh, guardrail=guardrail)
    pt.step(x, y)    # compile
    return pt, x, y


def bench_guardrail(on_accel):
    """Guardrail-on vs guardrail-off compiled-step A/B.

    Same net, same data, two compiled programs; slope timing so the
    measured delta is pure per-step work. The acceptance bar is < 2%
    overhead (docs/GUARDRAILS.md): the sentinel is one fused reduction
    and the skip-guard one conditional, so the HLO op-count delta is
    recorded alongside the timing to show the overhead is structural,
    not a host round-trip (outfeed/infeed must stay zero).
    """
    from mxnet_tpu import nd
    from mxnet_tpu.guardrail import Guardrail, GuardrailConfig
    from mxnet_tpu.resilience import FaultInjector

    batch = 128 if on_accel else 32
    image = 64 if on_accel else 32
    warmup, iters, reps = (5, 40, 2) if on_accel else (2, 8, 3)

    def build(guard):
        return _tiny_cnn_trainer(batch, image, guardrail=guard)

    def hlo_counts(text):
        return {'reduce': text.count(' reduce('),
                'conditional': text.count('conditional'),
                'outfeed': text.count('outfeed'),
                'infeed': text.count('infeed')}

    # check_every=0: no host-side poll in the timed loop — the pipeline
    # depth (and so the fixed-cost cancellation of slope timing) is
    # identical to the unguarded run
    guard = Guardrail(GuardrailConfig(check_every=0),
                      injector=FaultInjector(''))
    # guardrail=False, not None: None would resolve from the
    # MXNET_TPU_GUARDRAIL env knob and silently turn the A/B into
    # guarded-vs-guarded when the knob is set
    trainers = {'off': build(False), 'on': build(guard)}
    # interleaved min-of-reps: host noise (GC, another core's work)
    # hits both modes alike and the min discards it — a lone slope
    # window on a busy CPU host can swing tens of percent either way
    times = {'off': [], 'on': []}
    for _ in range(reps):
        for mode, (pt, x, y) in trainers.items():
            times[mode].append(
                _measure(lambda: pt.step(x, y), warmup, iters, nd))
    guard.flush()   # deferred events; also proves none tripped
    results = {}
    for mode in ('off', 'on'):
        compiled = trainers[mode][0].compiled_step()
        cost = compiled.cost_analysis()
        results[mode] = {
            'ms_per_step': round(min(times[mode]) * 1e3, 4),
            'hlo': hlo_counts(compiled.as_text()),
            'flops': float((cost or {}).get('flops', 0.0)),
            'bytes': float((cost or {}).get('bytes accessed', 0.0)),
        }
    off, on = results['off'], results['on']
    overhead = 100.0 * (on['ms_per_step'] / off['ms_per_step'] - 1.0)
    # deterministic companions to the wall clock: XLA's own static cost
    # model of the two programs — immune to host noise, and the honest
    # measure on a CPU rig whose timing floor exceeds the sentinel cost
    flops_overhead = (100.0 * (on['flops'] / off['flops'] - 1.0)
                      if off['flops'] else None)
    bytes_overhead = (100.0 * (on['bytes'] / off['bytes'] - 1.0)
                      if off['bytes'] else None)
    # measurement noise floor: rep-to-rep spread of the SAME program —
    # an overhead estimate inside this band means "below what this
    # host can resolve" (CPU rigs routinely show ±3%; the acceptance
    # bar is |overhead| < max(2%, noise))
    noise = 100.0 * max(
        (max(ts) - min(ts)) / min(ts) for ts in times.values())
    rec = {
        'metric': 'guardrail_overhead_pct',
        'value': round(overhead, 2),
        'unit': '%',
        'noise_pct': round(noise, 2),
        'flops_overhead_pct': None if flops_overhead is None
        else round(flops_overhead, 3),
        'bytes_overhead_pct': None if bytes_overhead is None
        else round(bytes_overhead, 3),
        'per_step_ms_off': off['ms_per_step'],
        'per_step_ms_on': on['ms_per_step'],
        'hlo_off': off['hlo'],
        'hlo_on': on['hlo'],
        'model': 'cnn-tiny bs%d %dpx' % (batch, image),
        # the timed config defers host policy polling entirely; the
        # default (MXNET_TPU_GUARD_CHECK_EVERY=1) adds one host sync
        # per step on top of this compiled-step overhead
        'check_every': 0,
    }
    print(json.dumps(rec), flush=True)
    return rec


def bench_telemetry(on_accel):
    """Telemetry-on vs telemetry-off compiled-step A/B
    (docs/OBSERVABILITY.md).

    One trainer, one compiled program — the telemetry layer never
    touches the XLA program, only the host dispatch path (a handful of
    counter incs, one histogram observe, one flight-ring append per
    step) — so the A/B toggles the master switch around interleaved
    timed windows of the SAME step. The acceptance bar is < 1%
    overhead (within the host's noise floor); the disabled path is
    additionally proven allocation-free by the observability selftest.
    """
    from mxnet_tpu import nd, observability

    batch = 128 if on_accel else 32
    image = 64 if on_accel else 32
    warmup, iters, reps = (5, 40, 2) if on_accel else (2, 8, 3)

    # compile once; both modes time the SAME program
    pt, x, y = _tiny_cnn_trainer(batch, image)

    # interleaved min-of-reps (the guardrail-A/B protocol): host noise
    # hits both modes alike and the min discards it
    times = {'off': [], 'on': []}
    prev = observability.enabled()
    try:
        for _ in range(reps):
            for mode in ('off', 'on'):
                observability.set_enabled(mode == 'on')
                times[mode].append(
                    _measure(lambda: pt.step(x, y), warmup, iters, nd))
    finally:
        observability.set_enabled(prev)
    off = round(min(times['off']) * 1e3, 4)
    on = round(min(times['on']) * 1e3, 4)
    overhead = 100.0 * (on / off - 1.0)
    noise = 100.0 * max(
        (max(ts) - min(ts)) / min(ts) for ts in times.values())
    rec = {
        'metric': 'telemetry_overhead_pct',
        'value': round(overhead, 2),
        'unit': '%',
        'noise_pct': round(noise, 2),
        'per_step_ms_off': off,
        'per_step_ms_on': on,
        'model': 'cnn-tiny bs%d %dpx' % (batch, image),
        # same compiled program in both modes by construction: the
        # instruments live on the host dispatch path only
        'same_compiled_program': True,
    }
    print(json.dumps(rec), flush=True)
    return rec


def bench_input_overlap(on_accel):
    """Input-pipeline overlap A/B (docs/PERFORMANCE.md).

    The same compiled step driven from a host-side producer whose
    per-batch cost is ~80% of a step (a decode-bound input pipeline),
    measured twice: synchronous (every batch's wait serializes with
    the step) and through the double-buffered staging prefetcher
    (``ParallelTrainer.prefetch_iter``). The metric is how much of the
    synchronous wait the prefetcher hides (target >= 80%); the record
    also carries ``data_wait_pct`` — the residual share of wall time
    the loop spends waiting on input with staging ON — which is the
    number every capture tracks alongside img/s.
    """
    from mxnet_tpu import nd

    batch = 128 if on_accel else 32
    image = 64 if on_accel else 32
    nsteps = 40 if on_accel else 12

    pt, x, y = _tiny_cnn_trainer(batch, image)
    # steady-state step time sets the synthetic producer's cost
    for _ in range(3):
        loss = pt.step(x, y)
    loss.wait_to_read()
    t0 = time.perf_counter()
    for _ in range(5):
        loss = pt.step(x, y)
    loss.wait_to_read()
    step_s = (time.perf_counter() - t0) / 5
    produce_s = max(0.8 * step_s, 0.002)

    def producer():
        for _ in range(nsteps):
            time.sleep(produce_s)     # decode/augment/IO stand-in
            yield (x, y)

    def run(staged):
        it = pt.prefetch_iter(producer()) if staged \
            else iter(producer())
        wait = 0.0
        loss = None
        t_start = time.perf_counter()
        while True:
            t1 = time.perf_counter()
            nxt = next(it, None)
            wait += time.perf_counter() - t1
            if nxt is None:
                break
            loss = pt.step(nxt[0], nxt[1])
        if loss is not None:
            loss.wait_to_read()
        return wait, time.perf_counter() - t_start

    wait_sync, total_sync = run(False)
    wait_pre, total_pre = run(True)
    overlap = 100.0 * (1.0 - wait_pre / wait_sync) if wait_sync else 0.0
    from mxnet_tpu.config import get as _cfg
    rec = {
        'metric': 'input_pipeline_overlap_pct',
        'value': round(overlap, 2),
        'unit': '%',
        # residual input wait with staging ON — the health number
        'data_wait_pct': round(100.0 * wait_pre / total_pre, 2)
        if total_pre else None,
        'data_wait_pct_sync': round(100.0 * wait_sync / total_sync, 2)
        if total_sync else None,
        'steps_per_sec_sync': round(nsteps / total_sync, 2),
        'steps_per_sec_prefetch': round(nsteps / total_pre, 2),
        'produce_ms': round(produce_s * 1e3, 3),
        'step_ms': round(step_s * 1e3, 3),
        'prefetch_depth': int(_cfg('MXNET_TPU_PREFETCH') or 0),
        'model': 'cnn-tiny bs%d %dpx' % (batch, image),
    }
    print(json.dumps(rec), flush=True)
    return rec


def _pallas_ab_trainer(model, on_accel, pallas):
    """Build one side of the Pallas-kernel A/B: same model, optimizer,
    seeds, and data — only MXNET_TPU_PALLAS differs, set around the
    build so the traceknobs snapshot bakes it into the step program.
    Returns (trainer, step, batch, tag)."""
    from mxnet_tpu import config as _mx_config
    prev = _mx_config.get('MXNET_TPU_PALLAS')
    _mx_config.set('MXNET_TPU_PALLAS', pallas)
    try:
        return _amp_ab_trainer(model, on_accel, None)
    finally:
        _mx_config.set('MXNET_TPU_PALLAS', prev)


def _bench_pallas_ab(on_accel, model, families, metric):
    """Knob-off vs knob-on compiled-step A/B over the same model
    (docs/PERFORMANCE.md "Hand-written kernels"): interleaved
    min-of-reps slope timing, per-side roofline byte totals, platform
    tag. On the CPU rig the kernels run through the Pallas
    interpreter — the numbers are recorded honestly but the
    acceptance signal is chip-side: audit-ranked bytes/step down and
    a speedup > 1 on a real TPU."""
    import jax
    from mxnet_tpu import nd
    from mxnet_tpu.observability import roofline

    warmup, iters, reps = (5, 40, 2) if on_accel else (2, 2, 2)
    sides = {}
    for mode, spec in (('off', '0'), ('on', families)):
        pt, step, batch, tag = _pallas_ab_trainer(model, on_accel,
                                                  spec)
        sides[mode] = {'pt': pt, 'step': step, 'batch': batch,
                       'tag': tag}
    times = {'off': [], 'on': []}
    for _ in range(reps):
        for mode, side in sides.items():
            times[mode].append(
                _measure(side['step'], warmup, iters, nd))
    rec = {
        'metric': metric,
        'unit': 'x',
        'pallas': families,
        'model': sides['off']['tag'],
        'platform': jax.default_backend(),
        # interpreter-mode numbers are honest but not the acceptance
        # signal — the chip run is (docs/PERFORMANCE.md)
        'kernel_path': 'mosaic' if jax.default_backend() == 'tpu'
        else 'interpreter',
    }
    rates = {}
    for mode, side in sides.items():
        rate = side['batch'] / min(times[mode])
        rates[mode] = rate
        rec['steps_per_sec_%s' % mode] = round(rate / side['batch'],
                                               3)
        try:
            totals = roofline.analyze(side['pt'].compiled_text())[1]
            rec['hbm_bytes_per_step_%s' % mode] = \
                totals['hbm_bytes_per_step']
        except Exception:
            rec['hbm_bytes_per_step_%s' % mode] = None
    rec['value'] = round(rates['on'] / rates['off'], 3) \
        if rates['off'] else None
    if rec.get('hbm_bytes_per_step_off') and \
            rec.get('hbm_bytes_per_step_on'):
        rec['hbm_bytes_delta'] = rec['hbm_bytes_per_step_on'] \
            - rec['hbm_bytes_per_step_off']
    noise = 100.0 * max(
        (max(ts) - min(ts)) / min(ts) for ts in times.values())
    rec['noise_pct'] = round(noise, 2)
    print(json.dumps(rec), flush=True)
    return rec


def bench_flash_attention(on_accel):
    """BERT step with flash attention (+ the fused loss head it
    composes with) off vs on — the attention clusters are the BERT
    audit's top byte movers."""
    return _bench_pallas_ab(on_accel, 'bert', 'attention,xent',
                            'flash_attention_speedup')


def bench_fused_epilogue(on_accel):
    """ResNet step with the fused BN/activation/residual epilogues
    off vs on — the post-conv elementwise chains the ResNet audit
    ranks."""
    return _bench_pallas_ab(on_accel, 'resnet', 'epilogue,xent',
                            'fused_epilogue_speedup')


def _amp_ab_trainer(model, on_accel, amp):
    """Build one side of the AMP A/B (docs/PRECISION.md): the SAME
    fp32 net, optimizer, seeds, and data for both modes — only the
    ``amp=`` knob differs, so the measured delta is purely the
    in-program low-precision compute casts. Returns (trainer, step)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel
    np.random.seed(0)
    mx.random.seed(0)
    mesh = parallel.create_mesh({'dp': 1}, devices=jax.devices()[:1])
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    if model == 'resnet':
        from mxnet_tpu.gluon import model_zoo
        batch, image = (128, 224) if on_accel else (8, 64)
        net = model_zoo.vision.resnet50_v1()
        net.initialize(mx.init.Xavier())
        net.hybridize(static_alloc=True, static_shape=True)
        x = nd.array(np.random.uniform(-1, 1, (batch, 3, image, image)),
                     dtype='float32')
        y = nd.array(np.random.randint(0, 1000, (batch,)))
        pt = parallel.ParallelTrainer(
            net, L, 'sgd', {'learning_rate': 0.1, 'momentum': 0.9,
                            'wd': 1e-4}, mesh, amp=amp)
        pt.step(x, y)   # compile
        return pt, (lambda: pt.step(x, y)), batch, \
            'resnet50_v1 bs%d %dpx' % (batch, image)
    from mxnet_tpu.gluon.model_zoo import bert as bert_zoo
    if on_accel:
        batch, seqlen, npred, vocab = 96, 128, 20, 30522
        net = bert_zoo.bert_12_768_12(vocab_size=vocab, max_length=512,
                                      dropout=0.1)
    else:
        batch, seqlen, npred, vocab = 2, 16, 2, 100
        net = bert_zoo.get_bert('bert_12_768_12', vocab_size=vocab,
                                max_length=32, units=32, hidden_size=64,
                                num_layers=2, num_heads=4, dropout=0.1)
    net.initialize(mx.init.Xavier())
    net.hybridize(static_alloc=True, static_shape=True)
    rs = np.random.RandomState(0)
    ids = nd.array(rs.randint(0, vocab, (batch, seqlen)))
    tt = nd.array((rs.rand(batch, seqlen) > 0.5).astype('float32'))
    vl = nd.array(np.full((batch,), seqlen, np.float32))
    mp = nd.array(rs.randint(0, seqlen, (batch, npred)))
    mlm_y = nd.array(rs.randint(0, vocab, (batch, npred)))
    nsp_y = nd.array(rs.randint(0, 2, (batch,)))

    def pretrain_loss(outs, labels):
        _, _, mlm_s, nsp_s = outs
        my, ny = labels
        return L(mlm_s.reshape((-1, vocab)),
                 my.reshape((-1,))).mean() + L(nsp_s, ny).mean()

    pt = parallel.ParallelTrainer(
        net, pretrain_loss, 'adamw', {'learning_rate': 1e-4,
                                      'wd': 0.01}, mesh, amp=amp)
    pt.step([ids, tt, vl, mp], [mlm_y, nsp_y])   # compile
    return pt, (lambda: pt.step([ids, tt, vl, mp], [mlm_y, nsp_y])), \
        batch, ('bert_12_768_12' if on_accel else 'bert-tiny') + \
        ' bs%d seq%d' % (batch, seqlen)


def bench_amp(on_accel, model='resnet'):
    """AMP A/B (docs/PRECISION.md): the same fp32 model trained through
    two compiled step programs — amp off vs the bf16 policy — with
    interleaved min-of-reps slope timing. The record carries both
    rates, the speedup ratio (the ROADMAP MFU-attack acceptance signal:
    >= 1.3x resnet50 img/s/chip on a real TPU), and each side's
    mfu_pct measured against its OWN peak — the fp32 passthrough rate
    for the off leg, the bf16 MXU rate for the AMP leg — plus the
    roofline byte totals and detected program precision, and proof the
    parameter masters stayed float32 in both modes.

    On the CPU CI rig the numbers are still recorded but the speedup
    is not the acceptance signal: XLA:CPU rewrites bf16 matmuls to f32
    compute wrapped in converts, so the AMP program can even run
    slower there (the roofline precision field says which machine the
    record came from via 'platform').
    """
    import jax
    from mxnet_tpu import nd
    from mxnet_tpu.observability import roofline

    warmup, iters, reps = (5, 40, 2) if on_accel else (2, 2, 2)
    flops_per_sample = RESNET50_TRAIN_FLOPS_PER_IMG if model == 'resnet' \
        else 6 * BERT_BASE_PARAMS * (128 if on_accel else 16)

    sides = {}
    for mode, amp in (('off', 'off'), ('bf16', 'bf16')):
        pt, step, batch, tag = _amp_ab_trainer(model, on_accel, amp)
        sides[mode] = {'pt': pt, 'step': step, 'batch': batch,
                       'tag': tag}
    times = {'off': [], 'bf16': []}
    for _ in range(reps):
        for mode, side in sides.items():
            times[mode].append(
                _measure(side['step'], warmup, iters, nd))
    rec = {
        'metric': 'amp_speedup_%s' % ('resnet50' if model == 'resnet'
                                      else 'bert'),
        'unit': 'x',
        'policy': 'bf16',
        'model': sides['off']['tag'],
        'platform': jax.default_backend(),
    }
    rates = {}
    for mode, side in sides.items():
        rate = side['batch'] / min(times[mode])
        rates[mode] = rate
        text = side['pt'].compiled_text()
        precision = roofline.program_precision(text)
        tflops = rate * flops_per_sample / 1e12
        peak, _kind = _peak_flops_precision(precision)
        unit = 'img_per_sec' if model == 'resnet' else 'samples_per_sec'
        rec['%s_%s' % (unit, mode)] = round(rate, 2)
        rec['precision_%s' % mode] = precision
        rec['tflops_per_sec_%s' % mode] = round(tflops, 2)
        if peak:
            rec['mfu_pct_%s' % mode] = round(100 * tflops * 1e12 / peak,
                                             2)
        try:
            totals = roofline.analyze(text)[1]
            rec['hbm_bytes_per_step_%s' % mode] = \
                totals['hbm_bytes_per_step']
        except Exception:
            rec['hbm_bytes_per_step_%s' % mode] = None
        # the contract the whole subsystem hangs on: fp32 masters
        # either way (optimizer state checked by tests/test_amp.py)
        rec['fp32_masters_%s' % mode] = all(
            str(w.dtype) == 'float32' for w in side['pt']._param_arrays)
    rec['value'] = round(rates['bf16'] / rates['off'], 3) \
        if rates['off'] else None
    noise = 100.0 * max(
        (max(ts) - min(ts)) / min(ts) for ts in times.values())
    rec['noise_pct'] = round(noise, 2)
    print(json.dumps(rec), flush=True)
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--out', default='BENCH.json',
                   help='artifact path (atomic write; same schema for '
                        'ok/degraded/unavailable runs)')
    args = p.parse_args(argv)

    from mxnet_tpu.resilience import (acquire_backend, artifact_record,
                                      write_artifact, is_transient,
                                      InjectedFault, PreemptionHandler)
    # graceful preemption: SIGTERM between legs stops at the next leg
    # boundary and the artifact's 'resumable' record + the resumable
    # exit code tell the snapshot driver to just re-run the command
    handler = PreemptionHandler().install()
    status = acquire_backend()
    if not status.usable:
        print('bench: backend unavailable after %d attempt(s): %s — '
              'recording it in %s instead of crashing'
              % (status.attempts, status.error, args.out), flush=True)
        write_artifact(args.out, artifact_record(
            'bench', 'unavailable', backend=status, error=status.error,
            payload={'metrics': [], 'telemetry': _telemetry_summary()},
            preempt=handler))
        return 0

    on_accel = status.state == 'tpu'
    verdict = 'ok' if on_accel else 'degraded'
    error = status.error
    metrics = []
    try:
        metrics.append(bench_resnet(on_accel))
    except Exception as e:
        # transient/injected mid-run failure degrades the artifact;
        # anything else is a product bug and must stay a loud crash
        if not (isinstance(e, InjectedFault) or is_transient(e)):
            raise
        verdict = 'degraded'
        error = '%s: %s' % (type(e).__name__, str(e)[:300])
        print('bench: resnet leg lost to a transient fault (%s)'
              % error, flush=True)
    if not handler.stop_requested:
        try:
            metrics.append(bench_bert(on_accel))
        except Exception as e:
            if not (isinstance(e, InjectedFault) or is_transient(e)):
                raise
            # BERT line is best-effort (the primary metric already
            # printed) but a lost leg still degrades the artifact status
            verdict = 'degraded'
            error = '%s: %s' % (type(e).__name__, str(e)[:300])
            print(json.dumps({
                'metric': 'bert_base_pretrain_samples_per_sec_per_chip',
                'value': 0, 'unit': 'samples/s', 'vs_baseline': 0,
                'error': str(e)[:200]}), flush=True)
    if not handler.stop_requested:
        try:
            metrics.append(bench_guardrail(on_accel))
        except Exception as e:
            if not (isinstance(e, InjectedFault) or is_transient(e)):
                raise
            verdict = 'degraded'
            error = '%s: %s' % (type(e).__name__, str(e)[:300])
            print('bench: guardrail A/B leg lost to a transient fault '
                  '(%s)' % error, flush=True)
    if not handler.stop_requested:
        try:
            metrics.append(bench_telemetry(on_accel))
        except Exception as e:
            if not (isinstance(e, InjectedFault) or is_transient(e)):
                raise
            verdict = 'degraded'
            error = '%s: %s' % (type(e).__name__, str(e)[:300])
            print('bench: telemetry A/B leg lost to a transient fault '
                  '(%s)' % error, flush=True)
    if not handler.stop_requested:
        try:
            metrics.append(bench_input_overlap(on_accel))
        except Exception as e:
            if not (isinstance(e, InjectedFault) or is_transient(e)):
                raise
            verdict = 'degraded'
            error = '%s: %s' % (type(e).__name__, str(e)[:300])
            print('bench: input-overlap A/B leg lost to a transient '
                  'fault (%s)' % error, flush=True)
    if not handler.stop_requested:
        try:
            metrics.append(bench_amp(on_accel))
        except Exception as e:
            if not (isinstance(e, InjectedFault) or is_transient(e)):
                raise
            verdict = 'degraded'
            error = '%s: %s' % (type(e).__name__, str(e)[:300])
            print('bench: amp A/B leg lost to a transient fault (%s)'
                  % error, flush=True)
    if not handler.stop_requested:
        try:
            metrics.append(bench_fused_epilogue(on_accel))
        except Exception as e:
            if not (isinstance(e, InjectedFault) or is_transient(e)):
                raise
            verdict = 'degraded'
            error = '%s: %s' % (type(e).__name__, str(e)[:300])
            print('bench: fused-epilogue A/B leg lost to a transient '
                  'fault (%s)' % error, flush=True)

    if handler.stop_requested:
        # preempted mid-bench: the legs already measured stay in the
        # artifact, status degrades, and the resumable rc tells the
        # driver to re-run the command after restart
        verdict = 'degraded'
        error = 'preempted (%s) after %d metric leg(s)' \
            % (handler.reason, len(metrics))
        print('bench: %s' % error, flush=True)
    write_artifact(args.out, artifact_record(
        'bench', verdict, backend=status, error=error,
        payload={'metrics': metrics,
                 'telemetry': _telemetry_summary()}, preempt=handler))
    print('bench: status=%s artifact=%s' % (verdict, args.out),
          flush=True)
    return handler.exit_code if handler.stop_requested else 0


if __name__ == '__main__':
    import sys
    sys.exit(main())
