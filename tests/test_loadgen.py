"""Open-loop load & chaos harness tests (docs/SERVING.md "SLOs and
overload behavior"): deterministic Poisson schedules, percentile /
artifact math, the live rig end-to-end over real HTTP (dual-session
routing, overload shedding with Retry-After, mini chaos burst with
recovery + zero-hang), and the full scripted soak (slow tier)."""
import json
import threading
import urllib.request

import pytest

from mxnet_tpu.loadgen import (build_schedule, latency_summary,
                               percentile, summarize)
from mxnet_tpu.loadgen.client import RequestRecord
from mxnet_tpu.loadgen.report import SLO_SCHEMA, build_artifact


# ---------------------------------------------------------------------------
# schedule: pure, deterministic math
# ---------------------------------------------------------------------------

def test_schedule_deterministic_given_seed():
    kw = dict(qps=80.0, duration_s=2.0,
              mix={'predict': 0.6, 'generate': 0.4}, seed=11)
    a = build_schedule(**kw)
    b = build_schedule(**kw)
    assert [(x.t, x.kind, x.rid) for x in a] \
        == [(x.t, x.kind, x.rid) for x in b]
    c = build_schedule(qps=80.0, duration_s=2.0,
                       mix={'predict': 0.6, 'generate': 0.4}, seed=12)
    assert [(x.t, x.kind) for x in c] != [(x.t, x.kind) for x in a]


def test_schedule_rate_mix_and_ordering():
    arr = build_schedule(200.0, 5.0,
                         mix={'predict': 0.75, 'generate': 0.25},
                         seed=3)
    # ~1000 arrivals, Poisson noise well under 20%
    assert 800 < len(arr) < 1200
    assert all(0.0 <= x.t < 5.0 for x in arr)
    assert all(a.t <= b.t for a, b in zip(arr, arr[1:]))
    gen = sum(1 for x in arr if x.kind == 'generate')
    assert 0.15 < gen / len(arr) < 0.35
    assert [x.rid for x in arr] == list(range(len(arr)))


def test_schedule_fixed_rate_and_validation():
    arr = build_schedule(10.0, 1.0, seed=0, poisson=False)
    gaps = [b.t - a.t for a, b in zip(arr, arr[1:])]
    assert all(abs(g - 0.1) < 1e-9 for g in gaps)
    with pytest.raises(ValueError):
        build_schedule(0.0, 1.0)
    with pytest.raises(ValueError):
        build_schedule(10.0, -1.0)
    with pytest.raises(ValueError):
        build_schedule(10.0, 1.0, mix={'predict': -1.0})


# ---------------------------------------------------------------------------
# report: percentiles, taxonomy, artifact schema
# ---------------------------------------------------------------------------

def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert percentile(vals, 50) == 50
    assert percentile(vals, 99) == 99
    assert percentile(vals, 100) == 100
    assert percentile(vals, 0) == 1
    assert percentile([], 50) is None
    with pytest.raises(ValueError):
        percentile(vals, 101)


def test_latency_summary_ms():
    s = latency_summary([0.010, 0.020, 0.500])
    assert s['n'] == 3 and s['p50_ms'] == 20.0 \
        and s['max_ms'] == 500.0


def _rec(rid, kind='predict', status=200, error=None, lat=0.01,
         retry_after=None, resolved=True, degraded=False):
    r = RequestRecord(rid, kind, 0.0)
    r.fired_at = 100.0
    r.done_at = 100.0 + lat
    r.status = status
    r.error_class = error
    r.retry_after_s = retry_after
    r.resolved = resolved
    r.degraded = degraded
    return r


def test_summarize_taxonomy_goodput_and_unresolved():
    recs = [_rec(0), _rec(1, lat=0.05, degraded=True),
            _rec(2, status=429, error='shed_backpressure', lat=0.002,
                 retry_after=1.0),
            _rec(3, status=504, error='timeout_budget', lat=2.0),
            _rec(4, status=None, error='client_timeout',
                 resolved=False)]
    m = summarize(recs)
    assert m['offered'] == 5 and m['admitted'] == 2 \
        and m['served_ok'] == 2
    assert m['shed'] == 1 and m['degraded'] == 1
    assert m['unresolved'] == 1
    assert m['errors'] == {'ok': 2, 'shed_backpressure': 1,
                           'timeout_budget': 1, 'client_timeout': 1}
    assert m['goodput'] == pytest.approx(0.4)
    assert m['availability'] == pytest.approx(0.4)
    assert m['retry_after'] == {'n': 1, 'max_s': 1.0}
    assert m['admitted_latency']['n'] == 2
    assert m['shed_latency']['p99_ms'] == 2.0


def test_generate_metrics_ttft_tpot():
    r = RequestRecord(0, 'generate', 0.0)
    r.fired_at = 10.0
    r.first_at = 10.2
    r.done_at = 10.8
    r.tokens = 4
    r.status = 200
    r.resolved = True
    m = summarize([r])
    assert m['generate']['ttft']['p50_ms'] == pytest.approx(200.0)
    assert m['generate']['tpot']['p50_ms'] == pytest.approx(200.0)


def test_build_artifact_schema_and_verdicts():
    doc = build_artifact('overload', {'qps': 10}, {'offered': 1},
                         verdicts={'a': True, 'b': False})
    assert doc['schema'] == SLO_SCHEMA
    assert doc['ok'] is False
    assert doc['verdicts'] == {'a': True, 'b': False}
    json.dumps(doc)     # artifact must be JSON-serializable


# ---------------------------------------------------------------------------
# the live rig over real HTTP (one build amortized across tests)
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def rig():
    from mxnet_tpu.loadgen.harness import ServingRig
    r = ServingRig()
    yield r
    r.close()


def test_rig_dual_session_routes(rig):
    base = 'http://127.0.0.1:%d' % rig.port
    req = urllib.request.Request(
        base + '/predict',
        data=json.dumps({'data': [0.1] * 8}).encode(),
        headers={'Content-Type': 'application/json'})
    body = json.loads(urllib.request.urlopen(req, timeout=20).read())
    assert len(body['outputs'][0]) == 4
    req = urllib.request.Request(
        base + '/generate',
        data=json.dumps({'tokens': [1, 2, 3], 'max_new_tokens': 3,
                         'stream': False}).encode(),
        headers={'Content-Type': 'application/json'})
    body = json.loads(urllib.request.urlopen(req, timeout=30).read())
    assert len(body['tokens']) == 3
    status = json.loads(urllib.request.urlopen(
        base + '/status', timeout=10).read())
    assert 'predict' in status and 'generate' in status
    assert status['generate']['mode'] == 'decode'
    health = json.loads(urllib.request.urlopen(
        base + '/healthz', timeout=10).read())
    assert health['ok'] is True


def test_rig_streamed_generate_records_ttft(rig):
    from mxnet_tpu.loadgen.client import LoadClient
    client = LoadClient('127.0.0.1', rig.port, timeout_s=20.0)
    rec = RequestRecord(0, 'generate', 0.0)
    client.generate(rec, [2, 3, 4], max_new_tokens=4)
    assert rec.resolved and rec.status == 200
    assert rec.error_class is None
    assert rec.tokens == 4
    assert rec.ttft_s() is not None and rec.ttft_s() >= 0.0
    assert rec.tpot_s() is not None


def test_overload_sheds_fast_429_with_retry_after(rig):
    """Overload at a rate far past the decode queue's capacity: the
    excess must resolve as 429s carrying Retry-After, every record
    must resolve, and nothing may leak server-side."""
    from mxnet_tpu.loadgen.harness import run_overload
    doc = run_overload(rig, capacity_qps=24.0, duration_s=2.0,
                       seed=5)
    m = doc['metrics']
    assert m['unresolved'] == 0
    assert doc['verdicts']['zero_unresolved']
    # open-loop accounting: every arrival is a record
    assert m['offered'] == sum(m['errors'].values())
    if m['shed']:
        # every 429 advertised a Retry-After backoff
        assert m['retry_after']['n'] == m['shed']
    # drain proof
    assert doc['server']['generate']['leaked_slots'] == 0
    assert doc['server']['generate']['pending'] == 0
    # the latency-budget verdicts (p99 under SLO, sheds fast) are
    # asserted by the slo CI stage in a clean process — a contended
    # pytest worker is not a calibrated rig


def test_chaos_single_burst_recovers_and_zero_hang(rig):
    """Mini chaos soak: one device_unavailable burst mid-traffic —
    the burst must be consumed, the endpoint must report healthy
    again within the ceiling, every request must resolve, and no
    decode slot may leak."""
    from mxnet_tpu.loadgen.harness import run_chaos
    script = ((0.25, 'device_unavailable',
               'device_unavailable@serving:3,'
               'device_unavailable@serving.decode:1'),)
    doc = run_chaos(rig, qps=15.0, duration_s=4.0, seed=7,
                    script=script)
    assert len(doc['faults']) == 1
    fault = doc['faults'][0]
    assert fault['consumed'], fault
    assert fault['recovery_s'] is not None, fault
    assert doc['verdicts']['all_faults_recovered']
    assert doc['verdicts']['zero_unresolved']
    assert doc['verdicts']['no_leaked_slots']
    assert doc['metrics']['offered'] > 0


@pytest.mark.slow
def test_chaos_full_script_soak(rig):
    """The full scripted soak (device_unavailable burst, device
    stall, worker crash, preemption mid-stream) at sustained rate:
    every verdict the slo CI stage gates must hold."""
    from mxnet_tpu.loadgen.harness import run_chaos
    doc = run_chaos(rig, qps=20.0, duration_s=12.0, seed=1)
    kinds = [f['kind'] for f in doc['faults']]
    assert kinds == ['device_unavailable', 'device_stall',
                     'worker_crash', 'preempt']
    assert all(f['consumed'] for f in doc['faults'])
    assert doc['verdicts']['all_faults_recovered'], doc['faults']
    assert doc['verdicts']['zero_unresolved']
    assert doc['verdicts']['no_leaked_slots']
    # the calibrated availability floor is gated by the slo CI stage
    # in a clean process; under a contended pytest worker just prove
    # the soak stayed substantially available
    assert doc['metrics']['availability'] >= 0.5, doc['metrics']


# ---------------------------------------------------------------------------
# dispatcher: open-loop accounting without a server
# ---------------------------------------------------------------------------

def test_dispatcher_saturation_is_counted_not_dropped():
    """Arrivals above the in-flight bound resolve as
    client_saturated — the open-loop contract forbids silently
    thinning the offered load."""
    from mxnet_tpu.loadgen.harness import Dispatcher

    class _StuckClient:
        timeout_s = 1.0

        def predict(self, rec, data):
            gate.wait(5.0)
            rec.resolved = True

        def generate(self, rec, tokens, max_new_tokens=8):
            gate.wait(5.0)
            rec.resolved = True

    gate = threading.Event()
    disp = Dispatcher(_StuckClient(), max_inflight=2)
    arrivals = build_schedule(200.0, 0.05, seed=0)
    assert len(arrivals) >= 4
    records, threads = disp.run(arrivals)
    try:
        saturated = [r for r in records
                     if r.error_class == 'client_saturated']
        assert len(records) == len(arrivals)
        assert saturated, 'expected arrivals past the bound'
        assert all(r.resolved for r in saturated)
    finally:
        gate.set()
        assert disp.drain(threads, 5.0) == 0


def test_request_record_derived_metrics_none_safe():
    r = RequestRecord(0, 'predict', 0.0)
    assert r.latency_s() is None and r.ttft_s() is None \
        and r.tpot_s() is None
    assert r.to_json()['resolved'] is False


# ---------------------------------------------------------------------------
# resume taxonomy + client Retry-After backoff
# ---------------------------------------------------------------------------

def test_summarize_counts_resumed_streams_as_success():
    """A stream the gateway failed over mid-generation and completed
    clean is SUCCESS-with-resume: it counts toward goodput, never as
    a failure, and is surfaced in its own stat."""
    clean = _rec(0, kind='generate')
    resumed = _rec(1, kind='generate')
    resumed.resumed = 1
    retried = _rec(2, status=429, error='shed_backpressure')
    retried.retries = 2
    m = summarize([clean, resumed, retried])
    assert m['resumed_streams'] == 1
    assert m['retried'] == 1
    assert m['served_ok'] == 2          # the resumed stream is OK
    assert m['goodput'] == pytest.approx(2 / 3)
    j = resumed.to_json()
    assert j['resumed'] == 1 and j['retries'] == 0


def test_client_retries_honor_retry_after_with_cap():
    """On 429/503 with retry budget, the client sleeps the replica's
    Retry-After (capped) and re-fires; the record keeps its original
    fired_at — backoff is latency the open-loop accounting sees —
    and counts every retry."""
    from mxnet_tpu.loadgen.client import LoadClient
    sleeps = []
    client = LoadClient('127.0.0.1', 1, retries=2, retry_cap_s=0.5,
                        sleep=sleeps.append)
    outcomes = [(429, 3.0), (503, 0.2), (200, None)]

    def attempt(rec):
        if rec.fired_at is None:
            rec.fired_at = 100.0
        status, ra = outcomes[rec.retries]
        rec.status = status
        rec.retry_after_s = ra
        rec.error_class = None if status == 200 else 'shed'
        rec.resolved = True

    rec = RequestRecord(0, 'predict', 0.0)
    client._with_retries(rec, attempt)
    assert rec.status == 200 and rec.retries == 2
    assert sleeps == [0.5, 0.2]         # 3.0 capped to 0.5
    assert rec.fired_at == 100.0        # original firing instant kept


def test_client_retries_default_off():
    """The knob default (0 retries) keeps the one-shot open-loop
    behavior the overload verdicts are calibrated on."""
    from mxnet_tpu.loadgen.client import LoadClient
    client = LoadClient('127.0.0.1', 1)
    assert client.retries == 0
    calls = []

    def attempt(rec):
        calls.append(1)
        rec.status = 429
        rec.resolved = True

    rec = RequestRecord(0, 'predict', 0.0)
    client._with_retries(rec, attempt)
    assert len(calls) == 1 and rec.status == 429
