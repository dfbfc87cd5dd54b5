"""Resilience layer: retry/backoff math (deterministic clock, no real
sleeps), circuit breaking, scripted fault injection, degraded backend
acquisition, atomic checkpoint save/resume (kill-between-write
simulation), the checkpoint-resume == uninterrupted-training
equivalence, DataLoader worker-crash restart, and the degraded-mode
bench artifact contract (docs/RESILIENCE.md).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import (
    Retry, RetryExhausted, Timeout, TimeoutExpired, Deadline,
    CircuitBreaker, CircuitOpenError, FaultInjector,
    DeviceUnavailableError, WorkerCrashError, acquire_backend,
    CheckpointManager, save_state, load_state, snapshot_gluon,
    restore_gluon, artifact_record, write_artifact, is_transient)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


# ---------------------------------------------------------------------------
# Retry math
# ---------------------------------------------------------------------------

def test_retry_backoff_sequence_deterministic():
    clock = FakeClock()
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock.sleep(s)

    calls = []

    def fail():
        calls.append(1)
        raise ConnectionError('down')

    r = Retry(max_attempts=4, base_delay=1.0, multiplier=2.0,
              max_delay=60.0, jitter=0.0, sleep=sleep, clock=clock)
    with pytest.raises(RetryExhausted) as ei:
        r.call(fail)
    assert len(calls) == 4
    assert sleeps == [1.0, 2.0, 4.0]      # no sleep after final attempt
    assert ei.value.attempts == 4
    assert isinstance(ei.value.last_error, ConnectionError)


def test_retry_delay_cap_and_jitter_bounds():
    import random
    r = Retry(base_delay=1.0, multiplier=2.0, max_delay=8.0, jitter=0.25,
              rng=random.Random(0))
    for attempt in range(1, 12):
        raw = min(8.0, 2.0 ** (attempt - 1))
        d = r.delay(attempt)
        assert raw * 0.75 <= d <= raw * 1.25


def test_retry_deadline_caps_total_budget():
    clock = FakeClock()
    r = Retry(max_attempts=10, base_delay=10.0, multiplier=2.0,
              jitter=0.0, deadline=25.0, sleep=clock.sleep, clock=clock)
    with pytest.raises(RetryExhausted) as ei:
        r.call(lambda: (_ for _ in ()).throw(ConnectionError('x')))
    # sleeps would be 10, 20, ...: after the 10s sleep the next 20s
    # pause would pass the 25s deadline, so it stops at attempt 2
    assert ei.value.attempts == 2
    assert clock.t <= 25.0


def test_retry_succeeds_after_transient_failures():
    state = {'n': 0}

    def flaky():
        state['n'] += 1
        if state['n'] < 3:
            raise ConnectionError('transient')
        return 'ok'

    r = Retry(max_attempts=5, jitter=0.0, sleep=lambda s: None)
    assert r.call(flaky) == 'ok'
    assert state['n'] == 3


def test_retry_nontransient_propagates_immediately():
    calls = []

    def bug():
        calls.append(1)
        raise ValueError('deterministic bug')

    r = Retry(max_attempts=5, sleep=lambda s: None)
    with pytest.raises(ValueError):
        r.call(bug)
    assert len(calls) == 1


def test_retry_skips_backoff_for_injected_faults():
    sleeps = []
    inj = FaultInjector('device_unavailable:2')

    def probe():
        inj.fire('device', ('device_unavailable',))
        return 'up'

    r = Retry(max_attempts=3, base_delay=99.0, jitter=0.0,
              sleep=sleeps.append)
    assert r.call(probe) == 'up'
    assert sleeps == []        # InjectedFault.no_backoff


# ---------------------------------------------------------------------------
# Timeout / Deadline / CircuitBreaker
# ---------------------------------------------------------------------------

def test_deadline_math_with_fake_clock():
    clock = FakeClock()
    d = Deadline(5.0, clock=clock)
    assert d.remaining() == 5.0 and not d.expired()
    clock.sleep(4.0)
    d.check('still fine')
    clock.sleep(2.0)
    assert d.expired()
    with pytest.raises(TimeoutExpired):
        d.check('epoch 3')


def test_timeout_run_enforces_budget_and_relays_results():
    t = Timeout(5.0)
    assert t.run(lambda: 42) == 42
    with pytest.raises(ZeroDivisionError):
        t.run(lambda: 1 // 0)
    with pytest.raises(TimeoutExpired):
        Timeout(0.05).run(time.sleep, 2.0)


def test_circuit_breaker_state_machine():
    clock = FakeClock()
    cb = CircuitBreaker(failure_threshold=3, reset_timeout=30.0,
                        clock=clock)

    def boom():
        raise ConnectionError('down')

    for _ in range(3):
        with pytest.raises(ConnectionError):
            cb.call(boom)
    assert cb.state == 'open'
    calls = []
    with pytest.raises(CircuitOpenError):
        cb.call(lambda: calls.append(1))
    assert not calls                       # open = not even attempted
    clock.sleep(31.0)
    assert cb.state == 'half-open'
    assert cb.call(lambda: 'recovered') == 'recovered'
    assert cb.state == 'closed'
    # half-open probe failure re-opens immediately (threshold applies
    # to consecutive failures since the last success)
    for _ in range(3):
        with pytest.raises(ConnectionError):
            cb.call(boom)
    clock.sleep(31.0)
    with pytest.raises(ConnectionError):
        cb.call(boom)
    assert cb.state == 'open'


# ---------------------------------------------------------------------------
# FaultInjector
# ---------------------------------------------------------------------------

def test_injector_counts_and_site_scoping():
    inj = FaultInjector('device_unavailable:2,'
                        'worker_crash@dataloader.worker:1')
    for _ in range(2):
        with pytest.raises(DeviceUnavailableError):
            inj.fire('device', ('device_unavailable',))
    inj.fire('device', ('device_unavailable',))     # count exhausted
    inj.fire('kvstore.init', ('worker_crash',))     # wrong site: silent
    with pytest.raises(WorkerCrashError):
        inj.fire('dataloader.worker', ('worker_crash',))
    inj.fire('dataloader.worker', ('worker_crash',))  # exhausted
    with pytest.raises(ValueError):
        FaultInjector('no_such_kind')


def test_injector_value_faults_poison_instead_of_raise():
    """nan/inf kinds (the guardrail's NaN injection) are consumed via
    poison(): scripted counts, site scoping, never an exception."""
    inj = FaultInjector('nan@grads:2,inf@loss:1')
    assert np.isnan(inj.poison('grads'))
    assert inj.poison('other.site') == 0.0      # site-scoped
    assert np.isnan(inj.poison('grads'))
    assert inj.poison('grads') == 0.0           # count exhausted
    assert np.isinf(inj.poison('loss'))
    assert inj.poison('loss') == 0.0
    # exception kinds don't leak through poison and vice versa
    inj = FaultInjector('device_unavailable:1')
    assert inj.poison('device') == 0.0          # not a value fault
    with pytest.raises(DeviceUnavailableError):
        inj.fire('device', ('device_unavailable',))


def test_injected_faults_look_transient():
    try:
        FaultInjector('device_stall:1').fire('device', ('device_stall',))
    except Exception as exc:
        assert is_transient(exc)
    assert is_transient(RuntimeError(
        "Unable to initialize backend 'tpu': UNAVAILABLE"))
    assert not is_transient(ValueError('shape mismatch'))


@pytest.mark.parametrize('message', [
    'INTERNAL: Mosaic failed to compile TPU kernel: failed to legalize '
    'operation \'vector.extract_strided_slice\'',
    'RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of '
    'memory in memory space vmem. Used 23.5M of 16.0M',
    'INTERNAL: Core halted unexpectedly',
    # a status code that would read as transient, raised by a compile
    'DEADLINE_EXCEEDED: while compiling module jit_step',
])
def test_compile_shaped_errors_are_never_transient(message):
    """XLA:TPU and Mosaic report a refused program as INTERNAL / a
    program that does not fit as RESOURCE_EXHAUSTED: deterministic, so
    never retried and never served around (docs/RESILIENCE.md)."""
    exc = RuntimeError(message)
    assert not is_transient(exc)
    calls = []

    def build():
        calls.append(1)
        raise exc

    with pytest.raises(RuntimeError) as ei:
        Retry(max_attempts=3, sleep=lambda s: None).call(build)
    assert ei.value is exc and len(calls) == 1


# ---------------------------------------------------------------------------
# acquire_backend
# ---------------------------------------------------------------------------

def test_acquire_backend_recovers_from_scripted_device_loss():
    inj = FaultInjector('device_unavailable:2')
    st = acquire_backend(
        injector=inj,
        retry=Retry(max_attempts=3, jitter=0.0, sleep=lambda s: None))
    # conftest pins the cpu platform, so a healthy acquire is the
    # typed cpu-fallback state — usable but flagged degraded
    assert st.state == 'cpu-fallback' and st.usable and st.degraded
    assert st.attempts == 3 and st.device_count >= 1
    assert st.error is None


def test_acquire_backend_reports_unavailable_not_raise():
    inj = FaultInjector('device_unavailable')   # persistent outage
    st = acquire_backend(
        injector=inj,
        retry=Retry(max_attempts=2, jitter=0.0, sleep=lambda s: None))
    assert st.state == 'unavailable' and not st.usable
    assert 'UNAVAILABLE' in st.error
    d = st.as_dict()
    assert sorted(d) == ['attempts', 'device_count', 'device_kind',
                         'error', 'platform', 'state']


# ---------------------------------------------------------------------------
# Atomic checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_state_roundtrip_and_magic(tmp_path):
    path = str(tmp_path / 's.ckpt')
    save_state(path, {'epoch': 3, 'w': np.arange(4.0)})
    state = load_state(path)
    assert state['epoch'] == 3
    np.testing.assert_array_equal(state['w'], np.arange(4.0))
    with open(str(tmp_path / 'junk.ckpt'), 'wb') as f:
        f.write(b'not a checkpoint')
    with pytest.raises(ValueError):
        load_state(str(tmp_path / 'junk.ckpt'))


def test_checkpoint_kill_between_write_keeps_last_good(tmp_path,
                                                       monkeypatch):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(0, {'epoch': 0, 'v': 'good'})
    # simulate a kill between fsync and rename: the commit-site fault
    # fires exactly there (resilience/checkpoint.py atomic_replace)
    monkeypatch.setenv('MXNET_TPU_FAULT',
                       'worker_crash@checkpoint.commit:1')
    with pytest.raises(WorkerCrashError):
        mgr.save(1, {'epoch': 1, 'v': 'torn'})
    monkeypatch.setenv('MXNET_TPU_FAULT', '')
    step, state = mgr.latest()
    assert step == 0 and state['v'] == 'good'
    # a torn newer file on disk is skipped with a warning, not fatal
    with open(mgr.path_for(2), 'wb') as f:
        f.write(b'MXTPUCKPT1\ngarbage-after-magic')
    with pytest.warns(UserWarning):
        step, state = mgr.latest()
    assert step == 0 and state['v'] == 'good'


def test_checkpoint_crc_catches_silent_corruption(tmp_path):
    """A flipped byte mid-payload can still unpickle (silently wrong
    optimizer state); the v2 CRC32 header catches it and latest()
    falls back to the previous valid checkpoint."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(0, {'epoch': 0, 'w': np.arange(64.0)})
    path1 = mgr.save(1, {'epoch': 1, 'w': np.arange(64.0) * 2})
    raw = bytearray(open(path1, 'rb').read())
    raw[-13] ^= 0xFF          # flip a byte inside the numpy payload
    with open(path1, 'wb') as f:
        f.write(raw)
    with pytest.raises(ValueError, match='CRC32 mismatch'):
        load_state(path1)
    with pytest.warns(UserWarning, match='skipping unreadable'):
        step, state = mgr.latest()
    assert step == 0 and state['epoch'] == 0
    # truncation (torn tail) is also caught, not just bit flips
    path2 = mgr.save(2, {'epoch': 2, 'w': np.arange(64.0)})
    with open(path2, 'r+b') as f:
        f.truncate(os.path.getsize(path2) - 40)
    with pytest.raises(ValueError):
        load_state(path2)


def test_checkpoint_v1_legacy_files_still_load(tmp_path):
    """Pre-CRC (v1 magic) checkpoints written by earlier builds stay
    readable."""
    import pickle
    path = str(tmp_path / 'old.ckpt')
    with open(path, 'wb') as f:
        f.write(b'MXTPUCKPT1\n' + pickle.dumps({'epoch': 7}))
    assert load_state(path)['epoch'] == 7


def test_checkpoint_manager_prunes_and_sweeps(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    # a dead writer's leftover (pid beyond pid_max is never alive) is
    # swept; a LIVE process's in-flight temp is not
    dead = str(tmp_path / 'ckpt-00000009.ckpt.tmp.4100100')
    live = str(tmp_path / ('ckpt-00000008.ckpt.tmp.%d' % os.getpid()))
    for p in (dead, live):
        with open(p, 'wb') as f:
            f.write(b'writer leftovers')
    for step in range(4):
        mgr.save(step, {'epoch': step})
    assert mgr._steps() == [2, 3]
    assert not os.path.exists(dead)
    assert os.path.exists(live)
    assert mgr.latest()[0] == 3
    os.unlink(live)


# ---------------------------------------------------------------------------
# Checkpoint-resume == uninterrupted training (acceptance criterion)
# ---------------------------------------------------------------------------

def _mlp_and_trainer():
    np.random.seed(7)   # initializer draws (Xavier) use numpy's RNG
    mx.random.seed(7)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation='relu'), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 8)))   # materialize deferred init under the seed
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1, 'momentum': 0.9})
    return net, trainer


def _run_epoch(net, trainer, X, Y, loss_fn, crash_after=None):
    last = None
    for b in range(0, X.shape[0], 8):
        if crash_after is not None and b // 8 >= crash_after:
            raise WorkerCrashError('worker_crash', 'train.step',
                                   'injected mid-epoch crash')
        x, y = nd.array(X[b:b + 8]), nd.array(Y[b:b + 8])
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(1)
        last = float(loss.asscalar())
    return last


def test_checkpoint_resume_matches_uninterrupted(tmp_path):
    rs = np.random.RandomState(3)
    X = rs.randn(32, 8).astype('float32')
    Y = rs.randint(0, 4, (32,)).astype('float32')
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # uninterrupted reference: 4 epochs straight through
    net_a, tr_a = _mlp_and_trainer()
    for epoch in range(4):
        loss_a = _run_epoch(net_a, tr_a, X, Y, loss_fn)

    # faulted run: checkpoint each epoch boundary, crash mid-epoch 2
    net_b, tr_b = _mlp_and_trainer()
    mgr = CheckpointManager(str(tmp_path), prefix='fit')
    for epoch in range(2):
        _run_epoch(net_b, tr_b, X, Y, loss_fn)
        mgr.save(epoch, snapshot_gluon(net_b, tr_b, epoch=epoch))
    with pytest.raises(WorkerCrashError):
        _run_epoch(net_b, tr_b, X, Y, loss_fn, crash_after=2)

    # resume in a FRESH process-analog: new net + trainer objects
    net_c, tr_c = _mlp_and_trainer()
    step, state = mgr.latest()
    resumed_epoch = restore_gluon(state, net_c, tr_c)
    assert resumed_epoch == 1
    for epoch in range(resumed_epoch + 1, 4):
        loss_c = _run_epoch(net_c, tr_c, X, Y, loss_fn)

    assert abs(loss_a - loss_c) <= 1e-5
    # prefixes differ between the two nets (auto-incremented name
    # scopes); compare in sorted architecture order
    for (_, pa), (_, pc) in zip(sorted(net_a.collect_params().items()),
                                sorted(net_c.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pc.data().asnumpy(),
                                   rtol=0, atol=1e-6)


def test_module_fit_resumes_from_checkpoint_dir(tmp_path):
    """module-level wiring: fit(checkpoint_dir=...) resumes from the
    newest epoch-boundary checkpoint instead of restarting."""
    from mxnet_tpu import io as mxio, sym

    rs = np.random.RandomState(0)
    X = rs.randn(24, 6).astype('float32')
    Y = rs.randint(0, 3, (24,)).astype('float32')

    def build():
        data = sym.Variable('data')
        out = sym.FullyConnected(data, num_hidden=3, name='fc')
        net = sym.SoftmaxOutput(out, name='softmax')
        return mx.mod.Module(net, context=mx.cpu())

    def data_iter():
        return mxio.NDArrayIter(X, Y, batch_size=8)

    ckdir = str(tmp_path / 'modfit')
    m1 = build()
    m1.fit(data_iter(), num_epoch=2, checkpoint_dir=ckdir,
           optimizer_params=(('learning_rate', 0.05),))
    mgr = CheckpointManager(ckdir, prefix='fit')
    assert mgr.latest()[0] == 1

    # second fit in a fresh module resumes at epoch 2, trains 2 more
    m2 = build()
    m2.fit(data_iter(), num_epoch=4, checkpoint_dir=ckdir,
           optimizer_params=(('learning_rate', 0.05),))
    assert mgr.latest()[0] == 3
    # and the resumed params differ from a fresh init (training moved)
    args, _ = m2.get_params()
    assert float(np.abs(args['fc_weight'].asnumpy()).sum()) > 0


# ---------------------------------------------------------------------------
# DataLoader worker-crash restart
# ---------------------------------------------------------------------------

def test_dataloader_restarts_crashed_worker_task(monkeypatch):
    monkeypatch.setenv('MXNET_TPU_FAULT',
                       'worker_crash@dataloader.worker:1')
    X = np.arange(64, dtype='float32').reshape(16, 4)
    ds = gluon.data.ArrayDataset(X)
    dl = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                               thread_pool=True)
    with pytest.warns(UserWarning, match='resubmitting'):
        batches = [b.asnumpy() for b in dl]
    got = np.concatenate(batches)
    np.testing.assert_array_equal(np.sort(got.ravel()), X.ravel())


def test_dataloader_restart_budget_exhausts(monkeypatch):
    monkeypatch.setenv('MXNET_TPU_FAULT', 'worker_crash')  # persistent
    X = np.zeros((8, 2), dtype='float32')
    dl = gluon.data.DataLoader(gluon.data.ArrayDataset(X), batch_size=4,
                               num_workers=1, thread_pool=True)
    with pytest.warns(UserWarning, match='resubmitting'):
        with pytest.raises(WorkerCrashError):
            list(dl)


# ---------------------------------------------------------------------------
# KVStore resilience
# ---------------------------------------------------------------------------

def test_kvstore_dist_init_error_is_typed(monkeypatch):
    from mxnet_tpu.kvstore import KVStoreInitError
    monkeypatch.setenv('MXNET_TPU_FAULT',
                       'device_unavailable@kvstore.init')
    with pytest.raises(KVStoreInitError) as ei:
        mx.kv.create('dist_sync')
    assert ei.value.attempts == 3
    assert 'UNAVAILABLE' in str(ei.value)
    assert 'dist_sync' in str(ei.value)


def test_kvstore_collectives_retry_transient(monkeypatch):
    from mxnet_tpu.kvstore import KVStore
    from mxnet_tpu.resilience.policy import get_injector
    kv = KVStore('dist_sync')
    # pretend we're one of two workers so the collective paths engage
    # (the underlying jax collectives are identities for one process)
    monkeypatch.setattr(KVStore, 'num_workers',
                        property(lambda self: 2))
    monkeypatch.setenv('MXNET_TPU_FAULT',
                       'device_stall@kvstore.push:1,'
                       'device_stall@kvstore.pull:1')
    kv.init('w', nd.ones((3,)))
    kv.push('w', nd.full((3,), 2.0))   # first allreduce stalls, retried
    kv._barrier()                      # first sync stalls, retried
    # both scripted stalls were consumed by successful retries
    assert not get_injector().pending('kvstore.push', ('device_stall',))
    assert not get_injector().pending('kvstore.pull', ('device_stall',))


def test_kvstore_worker_crash_rejoins_instead_of_failing(monkeypatch):
    """A dist worker that dies mid-handshake rejoins: the join is
    re-run from scratch instead of surfacing KVStoreInitError
    (reference: ps-lite re-registered dead workers)."""
    # 4 scripted crashes: 3 exhaust the first join's retries, the
    # rejoin consumes the 4th and succeeds on its second attempt
    monkeypatch.setenv('MXNET_TPU_FAULT',
                       'worker_crash@kvstore.init:4')
    with pytest.warns(UserWarning, match='rejoin'):
        kv = mx.kv.create('dist_sync')
    assert kv.type == 'dist_sync'
    # non-crash-shaped init failure still raises the typed error
    from mxnet_tpu.kvstore import KVStoreInitError
    monkeypatch.setenv('MXNET_TPU_FAULT',
                       'device_unavailable@kvstore.init')
    with pytest.raises(KVStoreInitError):
        mx.kv.create('dist_sync')


def test_kvstore_collective_retry_exhaustion_is_typed(monkeypatch):
    """A PERSISTENT mid-collective fault exhausts the bounded retry
    and surfaces RetryExhausted with the attempt count — the
    _comm_retry path under injection (vs the recovering case in
    test_kvstore_collectives_retry_transient)."""
    from mxnet_tpu.kvstore import KVStore
    from mxnet_tpu.resilience.policy import DeviceStallError
    kv = KVStore('dist_sync')
    monkeypatch.setattr(KVStore, 'num_workers',
                        property(lambda self: 2))
    monkeypatch.setenv('MXNET_TPU_FAULT', 'device_stall@kvstore.push')
    kv.init('w', nd.ones((3,)))
    with pytest.raises(RetryExhausted) as ei:
        kv.push('w', nd.full((3,), 2.0))
    assert ei.value.attempts == 3
    assert isinstance(ei.value.last_error, DeviceStallError)
    # a mid-collective crash is NOT healable by per-process rejoin
    # (docs/RESILIENCE.md): only the init handshake honors
    # worker_crash, push exhaustion stays typed
    monkeypatch.setenv('MXNET_TPU_FAULT', 'device_stall@kvstore.pull')
    with pytest.raises(RetryExhausted):
        kv._barrier()


# ---------------------------------------------------------------------------
# Degraded-mode artifact contract
# ---------------------------------------------------------------------------

def test_artifact_schema_is_status_invariant(tmp_path):
    ok = artifact_record('bench', 'ok', error=None,
                         payload={'metrics': [1]})
    down = artifact_record('bench', 'unavailable', error='dead',
                           payload={'metrics': []})
    assert sorted(ok) == sorted(down)
    assert sorted(ok['backend']) == sorted(down['backend'])
    path = str(tmp_path / 'a.json')
    write_artifact(path, ok)
    assert json.load(open(path))['status'] == 'ok'


@pytest.mark.slow
def test_bench_faulted_subprocess_exits_zero(tmp_path):
    """End-to-end acceptance: MXNET_TPU_FAULT=device_unavailable makes
    bench.py write an 'unavailable' artifact and exit 0 instead of
    ending in a backend-init traceback."""
    out = str(tmp_path / 'BENCH.json')
    env = dict(os.environ, MXNET_TPU_FAULT='device_unavailable',
               JAX_PLATFORMS='cpu')
    env['PYTHONPATH'] = ROOT + os.pathsep + env.get('PYTHONPATH', '')
    r = subprocess.run([sys.executable, os.path.join(ROOT, 'bench.py'),
                        '--out', out], capture_output=True, text=True,
                       timeout=300, env=env, cwd=ROOT)
    assert r.returncode == 0, (r.stdout, r.stderr)
    art = json.load(open(out))
    assert art['status'] == 'unavailable'
    assert art['payload']['metrics'] == []
    # every bench artifact now also carries its telemetry summary
    # block (docs/OBSERVABILITY.md) — even an unavailable-backend run
    assert 'telemetry' in art['payload']
    assert art['backend']['state'] == 'unavailable'
