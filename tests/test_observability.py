"""Unified telemetry layer (docs/OBSERVABILITY.md): metrics registry,
flight recorder, step-phase spans, exporters — plus the profiler /
Monitor satellites (thread-safe Counter, dump(finished=True), dumps
sort options, aggregate_stats(reset=True), gluon-HybridBlock Monitor
tap) that ride along with the observability subsystem."""
import json
import logging
import os
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, observability as obs
from mxnet_tpu.observability import export, metrics, recorder, spans


@pytest.fixture
def registry():
    return metrics.MetricsRegistry()


@pytest.fixture(autouse=True)
def _telemetry_on():
    """Pin the master switch on (and restore env resolution after) so
    tests are hermetic under any MXNET_TPU_TELEMETRY env."""
    metrics.set_enabled(True)
    yield
    metrics.set_enabled(None)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_math(registry):
    c = registry.counter('c_total')
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = registry.gauge('g')
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_labeled_children_are_cached_and_schema_checked(registry):
    fam = registry.counter('req_total', labels=('code',))
    fam.labels(code=200).inc()
    fam.labels(code='200').inc()
    assert fam.labels(code=200).value == 2.0     # same child (str key)
    with pytest.raises(ValueError):
        fam.labels(other='x')
    with pytest.raises(ValueError):
        fam.inc()          # labeled family has no default child


def test_redeclare_same_ok_mismatch_rejected(registry):
    registry.counter('x_total')
    registry.counter('x_total')                 # idempotent
    with pytest.raises(ValueError):
        registry.gauge('x_total')               # type mismatch
    registry.gauge('y', labels=('a',))
    with pytest.raises(ValueError):
        registry.gauge('y', labels=('b',))      # label-schema mismatch


def test_histogram_power_of_two_buckets(registry):
    h = registry.histogram('lat_seconds')
    h.observe(1.0)        # exact power of two -> le=1.0 bucket
    h.observe(0.75)       # (0.5, 1.0]
    h.observe(0.5)        # (0.25, 0.5]
    h.observe(1e12)       # +Inf overflow
    idx_1 = metrics.P2_BOUNDS.index(1.0)
    buckets = h.buckets()
    # cumulative: le=0.5 has 1, le=1.0 has 3, +Inf has all 4
    assert buckets[idx_1 - 1] == 1
    assert buckets[idx_1] == 3
    assert buckets[-1] == h.count == 4
    assert h.sum == pytest.approx(2.25 + 1e12)


def test_reset_zeroes_in_place_keeping_handles_wired(registry):
    c = registry.counter('r_total')
    h = registry.histogram('r_seconds')
    c.inc(5)
    h.observe(0.5)
    registry.reset()
    assert c.value == 0.0 and h.count == 0 and h.buckets()[-1] == 0
    # the SAME cached handles must still feed snapshots after reset —
    # dropping families would orphan every pre-bound instrument
    c.inc(2)
    h.observe(0.25)
    snap = registry.snapshot()
    assert snap['r_total']['series'][0]['value'] == 2.0
    assert snap['r_seconds']['series'][0]['count'] == 1


def test_histogram_tiny_values_land_in_first_bucket(registry):
    h = registry.histogram('tiny_seconds')
    h.observe(0.0)
    h.observe(1e-12)
    assert h.buckets()[0] == 2


def test_disabled_mutators_are_noops(registry):
    c = registry.counter('d_total')
    h = registry.histogram('d_seconds')
    c.inc(5)
    metrics.set_enabled(False)
    c.inc(100)
    h.observe(1.0)
    assert c.value == 5.0 and h.count == 0
    metrics.set_enabled(True)
    c.inc()
    assert c.value == 6.0


def test_registry_thread_safety(registry):
    c = registry.counter('t_total')
    h = registry.histogram('t_seconds')

    def worker():
        for _ in range(2000):
            c.inc()
            h.observe(0.01)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 16000.0
    assert h.count == 16000 and h.buckets()[-1] == 16000


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_bounds_and_dump(tmp_path):
    rec = recorder.FlightRecorder(capacity=4, name='t')
    rec.set_enabled(True)
    for i in range(10):
        rec.record('step', step=i)
    evs = rec.events()
    assert [e['step'] for e in evs] == [6, 7, 8, 9]
    path = str(tmp_path / 'F.jsonl')
    assert rec.dump(path=path, reason='unit') == path
    header, events = recorder.read_flight(path)
    assert header['schema'] == obs.FLIGHT_SCHEMA == 'mxnet_tpu.flight.v1'
    assert header['dropped'] == 6 and header['events'] == 4
    assert events[-1] == {k: v for k, v in evs[-1].items()}
    # every line independently parseable JSONL
    for ln in open(path).read().splitlines():
        json.loads(ln)


def test_flight_read_rejects_wrong_schema(tmp_path):
    p = tmp_path / 'bad.jsonl'
    p.write_text('{"schema": "nope"}\n')
    with pytest.raises(ValueError):
        recorder.read_flight(str(p))


def test_flight_disabled_records_and_dumps_nothing(tmp_path):
    rec = recorder.FlightRecorder(capacity=4)
    rec.set_enabled(False)
    rec.record('step', step=1)
    assert rec.events() == []
    assert rec.dump(path=str(tmp_path / 'x.jsonl')) is None
    assert not (tmp_path / 'x.jsonl').exists()


def test_flight_excepthook_dumps_on_crash(tmp_path):
    import subprocess
    import sys
    path = tmp_path / 'C.jsonl'
    code = (
        'import sys; sys.path.insert(0, %r)\n'
        'from mxnet_tpu.observability import recorder\n'
        'recorder.configure_flight(path=%r)\n'
        'recorder.install_excepthook()\n'
        'recorder.record_event("step", step=3)\n'
        'raise RuntimeError("boom")\n'
        % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
           str(path)))
    r = subprocess.run([sys.executable, '-c', code],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    header, events = recorder.read_flight(str(path))
    assert header['reason'] == 'crash'
    assert events[-1]['kind'] == 'crash'
    assert 'boom' in events[-1]['error']


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_records_phase_histogram():
    child = spans.phase_histogram('checkpoint')
    before = child.count
    with spans.span('checkpoint'):
        pass
    assert child.count == before + 1


def _host_events(trace_dir):
    """[(line index, name, start_ns, end_ns, stats)] of the host plane
    of the one xplane file under ``trace_dir``."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), 'plugins', 'profile',
                                   '*', '*.xplane.pb'))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != '/host:CPU':
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.append((i, ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns,
                            dict(ev.stats)))
    return out


def _jax_trace(trace_dir):
    """A plain ``jax.profiler`` session, as the benchmark opens it."""
    import contextlib
    import jax

    @contextlib.contextmanager
    def session():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    return session()


def test_span_with_mx_profiler_running_writes_one_annotation(tmp_path):
    from mxnet_tpu import profiler
    profiler.set_config(filename=str(tmp_path / 'p.json'),
                        aggregate_stats=True)
    profiler.set_state('run')
    try:
        with spans.span('sync'):
            pass
        table = profiler.aggregate_stats(reset=True)
    finally:
        profiler.set_state('stop')
    # the chrome trace keeps its phase:* row ...
    assert table['phase:sync']['count'] == 1
    assert table['phase:sync']['category'] == 'user'
    # ... and the profiler's own trace holds the span once, under the
    # span's own name (not a second time through profiler.scope)
    names = [n for _, n, *_ in _host_events(tmp_path / 'p_xplane')]
    assert names.count('sync') == 1
    assert names.count('phase:sync') == 0


def test_span_reaches_a_plain_jax_profiler_trace_with_its_arguments(
        tmp_path):
    from mxnet_tpu import profiler
    assert not profiler.is_running()
    child = spans.phase_histogram('checkpoint')
    before = child.count
    with _jax_trace(tmp_path):
        with spans.span('checkpoint', step=7, wall=12.5):
            pass
    hits = [e for e in _host_events(tmp_path) if e[1] == 'checkpoint']
    assert len(hits) == 1
    assert hits[0][4] == {'step': 7, 'wall': 12.5}
    # the arguments went to the annotation only: one histogram child
    assert child.count == before + 1
    assert spans.phase_histogram('checkpoint') is child


def test_phases_lists_the_spans_the_package_opens():
    assert len(set(spans.PHASES)) == len(spans.PHASES)
    assert {'data_wait', 'compile', 'train.dispatch', 'eng.wait_work',
            'eng.tick', 'eng.tick.emit'} <= set(spans.PHASES)


# ---------------------------------------------------------------------------
# the decode scheduler's tick on the profiler's clock; program names
# ---------------------------------------------------------------------------

def _toy_lm(layers=2, max_len=48):
    from mxnet_tpu.serving.decode import init_transformer_lm
    return init_transformer_lm(vocab=23, units=16, hidden=24,
                               layers=layers, heads=4, max_len=max_len,
                               seed=0)


def test_engine_tick_and_its_phases_in_a_profiler_trace(tmp_path):
    """A jax.profiler trace of a few ticks behind the HTTP server:
    eng.tick with its phases nested inside it on one thread, no phase
    twice under one tick (eng.tick.admit apart: one an admission), and
    nothing from a handler thread."""
    import urllib.request
    from mxnet_tpu import serving
    from mxnet_tpu.serving.decode import PagedDecodeProgram
    from mxnet_tpu.serving.server import ServingHTTPServer
    model, params = _toy_lm()
    prog = PagedDecodeProgram(model, params, slots=4,
                              prefill_buckets=(4, 8), page_size=8)
    sess = serving.InferenceSession(prog, watchdog=False)
    with ServingHTTPServer(sess, 0) as srv:
        def post(prompt):
            req = urllib.request.Request(
                'http://127.0.0.1:%d/generate' % srv.port,
                data=json.dumps({'tokens': prompt, 'max_new_tokens': 8,
                                 'stream': True}).encode(),
                headers={'Content-Type': 'application/json'})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.read()
        post([1, 2, 3])                     # compile outside the trace
        with _jax_trace(tmp_path):
            threads = [threading.Thread(target=post, args=(p,))
                       for p in ([5, 11, 7], [3, 1, 4, 1, 5], [9, 9])]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
    sess.close()
    events = _host_events(tmp_path)
    ours = [e for e in events if e[1].startswith(('eng.', 'srv.'))]
    assert {e[1] for e in ours} >= {
        'eng.tick', 'eng.tick.retire', 'eng.tick.migrate',
        'eng.tick.admit', 'eng.tick.page_faults',
        'eng.tick.build_inputs', 'eng.tick.dispatch',
        'eng.tick.read_tokens', 'eng.tick.emit', 'eng.tick.telemetry'}
    assert not any(e[1].startswith('srv.') for e in ours)
    assert len({e[0] for e in ours}) == 1      # the worker thread alone
    ticks = [e for e in ours if e[1] == 'eng.tick']
    assert len(ticks) >= 5
    steps = [t[4]['step'] for t in ticks]
    assert steps == sorted(steps) and all(t[4]['wall'] > 1e9
                                          for t in ticks)
    phases = [e for e in ours if e[1].startswith('eng.tick.')]
    for _, _, a, b, _ in ticks:
        inside = [p for p in phases if a <= p[2] and p[3] <= b]
        # direct children: those no other phase of the tick contains
        # (a prefill's dispatch, read and emit lie inside its
        # eng.tick.admit, of which a tick has one an admission)
        direct = [p[1] for p in inside
                  if not any(q is not p and q[2] <= p[2] and p[3] <= q[3]
                             for q in inside)]
        once = [n for n in direct if n != 'eng.tick.admit']
        assert len(once) == len(set(once)), direct
        assert direct.count('eng.tick.admit') <= 4      # the slots
        assert direct[0] == 'eng.tick.retire'
        assert direct[-1] == 'eng.tick.telemetry'
    # every phase lies inside some tick (the trace may have opened or
    # closed in the middle of one, whose own span it then lacks)
    lo, hi = ticks[0][2], ticks[-1][3]
    assert all(any(a <= p[2] and p[3] <= b for _, _, a, b, _ in ticks)
               for p in phases if lo <= p[2] and p[3] <= hi)


def _module_names(prog):
    import re
    return sorted(re.match(r'HloModule (jit_\w+)', c.as_text()).group(1)
                  for c in prog._compiled.values())


def test_decode_programs_have_names_and_only_the_step_is_jit_fn():
    import re
    from mxnet_tpu.serving.decode import (DecodeEngine, DecodeProgram,
                                          PagedDecodeProgram)
    model, params = _toy_lm()
    paged = PagedDecodeProgram(model, params, slots=2,
                               prefill_buckets=(4, 8), page_size=8,
                               spec_k=2).warmup()
    names = _module_names(paged)
    assert names == ['jit_fn_step', 'jit_page_copy', 'jit_prefill_b4',
                     'jit_prefill_b8', 'jit_verify_k2']
    assert [n for n in names if re.search('^jit_fn', n)] \
        == ['jit_fn_step']
    # the artifact's keys are the program keys, not the module names
    assert sorted(paged._compiled) == ['copy', 'prefill:4', 'prefill:8',
                                       'step', 'verify:3']
    slot = DecodeProgram(model, params, slots=2, prefill_buckets=(4,))
    assert _module_names(slot.warmup()) == ['jit_fn_step',
                                            'jit_prefill_b4']
    # a draft's programs say so, and leave ^jit_fn to the target's step
    draft = DecodeProgram(model, params, slots=2, prefill_buckets=(4, 8))
    eng = DecodeEngine(paged, draft=draft)
    try:
        assert _module_names(draft.warmup()) == [
            'jit_draft_prefill_b4', 'jit_draft_prefill_b8',
            'jit_draft_step']
    finally:
        eng.close()


@pytest.fixture
def fresh_compiles():
    """The persistent compile cache keys a program without its
    metadata, so a hit hands back whatever ``op_name``s the program had
    when it was first compiled: compile afresh to read this tree's."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', True)
    compilation_cache.reset_cache()


def test_decode_step_operations_carry_their_scope(fresh_compiles):
    from mxnet_tpu.serving.decode import PagedDecodeProgram
    model, params = _toy_lm()
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(4,), page_size=8)
    text = prog.compile_step().as_text()
    for scope in ('embed', 'layer0/attn', 'layer0/kv_gather',
                  'layer0/ffn', 'layer1/attn', 'layer1/kv_gather',
                  'layer1/ffn', 'lm_head', 'sampling'):
        assert 'op_name="jit(fn_step)/%s/' % scope in text, scope
    prefill = prog.compile_prefill(4).as_text()
    for scope in ('embed', 'layer1/attn', 'layer1/ffn', 'lm_head',
                  'sampling'):
        assert 'op_name="jit(prefill_b4)/%s/' % scope in prefill, scope


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_prometheus_schema_counter_monotonic_and_buckets():
    c = obs.counter('unit_req_total', help='n')
    h = obs.histogram('unit_lat_seconds', labels=('path',))
    c.inc(2)
    h.labels(path='/x').observe(0.125)
    h.labels(path='/x').observe(0.25)
    types, s1 = export.parse_prometheus(export.prometheus_text())
    assert types['unit_req_total'] == 'counter'
    assert types['unit_lat_seconds'] == 'histogram'
    c.inc()
    _, s2 = export.parse_prometheus(export.prometheus_text())

    def get(samples, name, **labels):
        return [v for n, lab, v in samples if n == name
                and all(lab.get(k) == str(vv) or lab.get(k) == vv
                        for k, vv in labels.items())]

    assert get(s2, 'unit_req_total')[0] > get(s1, 'unit_req_total')[0]
    buckets = [(lab['le'], v) for n, lab, v in s1
               if n == 'unit_lat_seconds_bucket'
               and lab.get('path') == '/x']
    vals = [v for _, v in buckets]
    assert vals == sorted(vals), 'buckets must be cumulative'
    assert buckets[-1][0] == '+Inf'
    assert buckets[-1][1] == get(s1, 'unit_lat_seconds_count',
                                 path='/x')[0] == 2
    assert get(s1, 'unit_lat_seconds_sum', path='/x')[0] == \
        pytest.approx(0.375)


def test_http_server_off_by_default_and_serves_when_asked():
    assert export.maybe_start_http_server() is None
    obs.counter('http_unit_total').inc()
    import urllib.request
    with export.PrometheusServer(0) as srv:
        body = urllib.request.urlopen(
            'http://127.0.0.1:%d/metrics' % srv.port, timeout=10
        ).read().decode()
    export.parse_prometheus(body)
    assert 'http_unit_total' in body


def test_write_prometheus_and_jsonl(tmp_path):
    obs.counter('file_unit_total').inc()
    p = export.write_prometheus(str(tmp_path / 'm.prom'))
    export.parse_prometheus(open(p).read())
    j = export.write_jsonl(str(tmp_path / 'm.jsonl'))
    for ln in open(j):
        json.loads(ln)


# ---------------------------------------------------------------------------
# threaded instrumentation
# ---------------------------------------------------------------------------

def test_parallel_trainer_telemetry_and_collective_bytes():
    import jax
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    np.random.seed(3)
    mx.random.seed(3)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation='relu'), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    mesh = parallel.create_mesh({'dp': 2}, devices=jax.devices()[:2])
    pt = parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1}, mesh)
    x = nd.array(np.random.randn(8, 8).astype('float32'))
    y = nd.array(np.random.randint(0, 4, (8,)).astype('float32'))
    inst = obs.trainer_instruments()
    steps0, ex0 = inst.steps.value, inst.examples.value
    compile0, stepsec0 = (inst.compile_seconds.count,
                          inst.step_seconds.count)
    for _ in range(3):
        pt.step(x, y)
    assert inst.steps.value == steps0 + 3
    assert inst.examples.value == ex0 + 24
    assert inst.compile_seconds.count > compile0
    assert inst.step_seconds.count >= stepsec0 + 2
    kinds = [e['kind'] for e in obs.get_recorder().events()]
    assert kinds.count('step') >= 3
    total, per_kind = obs.trainer_collective_stats(pt)
    assert total > 0 and 'all-reduce' in per_kind
    assert obs.gauge('mxnet_tpu_collective_bytes_per_step').value == \
        total


def test_jit_cache_instruments_count_hits_and_misses():
    inst = obs.dispatch_instruments()
    h0, m0 = inst.jit_hits.value, inst.jit_misses.value
    a = nd.array(np.random.randn(4, 4).astype('float32'))
    b = nd.array(np.random.randn(4, 4).astype('float32'))
    (a * b + a).asnumpy()       # builds cache entries (or hits)
    (a * b + a).asnumpy()       # second round must be pure hits
    assert inst.jit_hits.value + inst.jit_misses.value > h0 + m0
    h1 = inst.jit_hits.value
    (a * b + a).asnumpy()
    assert inst.jit_hits.value > h1


def test_kvstore_byte_counters():
    kv = mx.kv.create('local')
    inst = obs.kv_instruments()
    push0, pull0 = inst.push_bytes.value, inst.pull_bytes.value
    v = nd.ones((16,))
    kv.init('w', v)
    kv.push('w', v)
    out = nd.zeros((16,))
    kv.pull('w', out=out)
    assert inst.push_bytes.value == push0 + 64      # 16 * f32
    assert inst.pull_bytes.value == pull0 + 64


def test_guardrail_skip_feeds_registry_and_flight():
    from mxnet_tpu.guardrail import Guardrail, GuardrailConfig
    guard = Guardrail(GuardrailConfig(check_every=1, patience=10,
                                      warmup=100))
    inst = obs.trainer_instruments()
    skip0 = inst.skipped.value
    nf0 = inst.nonfinite.value
    guard.record(0, 1.5, loss=1.0, scale=1024.0)      # healthy
    guard.record(1, -2.5, loss=1.0, scale=512.0)      # skip
    assert inst.skipped.value == skip0 + 1
    assert inst.nonfinite.value == nf0 + 1
    assert inst.loss_scale.value == 512.0
    kinds = [e['kind'] for e in obs.get_recorder().events()]
    assert 'skip_update' in kinds
    assert 'loss_scale' in kinds      # 1024 -> 512 change event


def test_watchdog_heartbeat_age_gauge():
    from mxnet_tpu.resilience import Watchdog
    fake = [100.0]
    wd = Watchdog(budgets={'step': 50.0}, clock=lambda: fake[0])
    wd.beat(step=1, phase='step')
    age = obs.trainer_instruments().heartbeat_age
    assert age.value == 0.0
    fake[0] = 130.0
    assert wd.stalled() is None
    assert age.value == pytest.approx(30.0)


def test_speedometer_routes_through_registry_logging_unchanged(caplog):
    from mxnet_tpu.callback import Speedometer
    from collections import namedtuple
    Param = namedtuple('Param', ['epoch', 'nbatch', 'eval_metric',
                                 'locals'])
    speedo = Speedometer(batch_size=4, frequent=2, auto_reset=False)
    with caplog.at_level(logging.INFO):
        for nbatch in range(5):
            speedo(Param(epoch=0, nbatch=nbatch, eval_metric=None,
                         locals=None))
    lines = [r.getMessage() for r in caplog.records
             if 'Speed' in r.getMessage()]
    # logging format byte-identical to the reference implementation
    assert lines and all(
        l.startswith('Iter[0] Batch [') and 'samples/sec' in l
        for l in lines)
    gauge = obs.trainer_instruments().speedometer
    assert gauge.value > 0
    # the gauge holds exactly the number the last log line printed
    assert '%.2f' % gauge.value == lines[-1].split('Speed: ')[1] \
        .split(' ')[0]


# ---------------------------------------------------------------------------
# profiler satellites
# ---------------------------------------------------------------------------

def test_profiler_counter_thread_safe():
    from mxnet_tpu import profiler
    c = profiler.Counter(None, 'hot_path', 0)

    def worker():
        for _ in range(2000):
            c.increment(1)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # the unlocked read-modify-write lost updates here before the fix
    assert c._value == 16000
    c2 = profiler.Counter(None, 'iadd', 1)
    c2 += 5
    assert isinstance(c2, profiler.Counter) and c2._value == 6


def test_profiler_dump_finished_ends_collection(tmp_path):
    from mxnet_tpu import profiler
    f = str(tmp_path / 'prof.json')
    profiler.set_config(filename=f)
    profiler.set_state('run')
    with profiler.scope('finished_scope'):
        pass
    profiler.dump(finished=True)
    data = json.load(open(f))
    names = [e['name'] for e in data['traceEvents']]
    assert 'finished_scope' in names
    # finished=True ended collection: profiling stopped AND the buffer
    # cleared — a later dump must not re-emit this run's events
    assert not profiler.is_running()
    profiler.dump(finished=False)
    data2 = json.load(open(f))
    assert all(e['name'] != 'finished_scope'
               for e in data2['traceEvents'])


def test_profiler_dump_unfinished_keeps_collecting(tmp_path):
    from mxnet_tpu import profiler
    f = str(tmp_path / 'prof2.json')
    profiler.set_config(filename=f)
    profiler.set_state('run')
    try:
        with profiler.scope('s1'):
            pass
        profiler.dump(finished=False)
        assert profiler.is_running()
        with profiler.scope('s2'):
            pass
        profiler.dump(finished=False)
        names = [e['name'] for e in json.load(open(f))['traceEvents']]
        assert 's1' in names and 's2' in names
    finally:
        profiler.set_state('stop')
        profiler.aggregate_stats(reset=True)


def test_profiler_dumps_sort_options():
    from mxnet_tpu import profiler
    profiler.aggregate_stats(reset=True)
    profiler.set_state('run')
    try:
        import time
        for name, dur, reps in (('slow_op', 0.05, 1),
                                ('fast_op', 0.001, 3)):
            for _ in range(reps):
                with profiler.scope(name):
                    time.sleep(dur)
    finally:
        profiler.set_state('stop')

    def order(sort_by, ascending=False):
        rows = profiler.dumps(sort_by=sort_by,
                              ascending=ascending).splitlines()[1:]
        return [r.split()[0] for r in rows]

    assert order('count') == ['fast_op', 'slow_op']
    assert order('count', ascending=True) == ['slow_op', 'fast_op']
    assert order('max') == ['slow_op', 'fast_op']
    assert order('avg') == ['slow_op', 'fast_op']
    assert order('min', ascending=True) == ['fast_op', 'slow_op']
    assert order('total')      # valid key; relative order is timing
    with pytest.raises(ValueError):
        profiler.dumps(sort_by='bogus')
    table = json.loads(profiler.dumps(format='json'))
    assert table['fast_op']['count'] == 3
    # aggregate_stats(reset=True) drains the buffer
    profiler.aggregate_stats(reset=True)
    assert profiler.aggregate_stats() == {}


def test_monitor_tap_under_gluon_hybrid_block_forward():
    """Monitor taps the executor of a symbolically-composed gluon
    HybridBlock: the same net object drives both the gluon forward and
    the monitored symbol executor, and the tap sees the outputs."""
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation='relu'), nn.Dense(3))
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.randn(2, 5).astype('float32'))
    eager_out = net(x)                      # gluon forward

    data = mx.sym.var('data')
    sym = net(data)                         # HybridBlock symbol compose
    exe = sym.simple_bind(mx.cpu(), data=(2, 5))
    for name, arr in net.collect_params().items():
        key = name if name in exe.arg_dict else None
        if key is None:
            for cand in exe.arg_dict:
                if cand.endswith(name) or name.endswith(cand):
                    key = cand
                    break
        if key is not None:
            arr.data().copyto(exe.arg_dict[key])
    mon = mx.Monitor(1, pattern='.*')
    mon.install(exe)
    mon.tic()
    out = exe.forward(data=x)[0]
    records = mon.toc()
    assert records, 'monitor tap saw no tensors under the forward'
    names = [name for _, name, _ in records]
    assert any('output' in n or 'fwd' in n or 'dense' in n
               for n in names), names
    np.testing.assert_allclose(out.asnumpy(), eager_out.asnumpy(),
                               rtol=1e-5, atol=1e-5)
