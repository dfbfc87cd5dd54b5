"""Preemption-tolerant elastic training (docs/RESILIENCE.md
"Preemption & elasticity"): graceful SIGTERM drain + resumable exit
code, step-granular fit resume (bit-identical mid-epoch), elastic
mesh-shrink planning + grad-accumulation resume, the stall watchdog,
and the kvstore worker-rejoin handshake.
"""
import os
import signal

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import (
    CheckpointManager, DeviceLossError, ElasticPlan, FaultInjector,
    MeshShrinkError, Preempted, PreemptionHandler, PreemptionSignal,
    STALL_SCHEMA, DeviceStallError, Watchdog, available_devices,
    mesh_meta, resumable_exit_code, shrink_plan)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


# ---------------------------------------------------------------------------
# PreemptionHandler
# ---------------------------------------------------------------------------

def test_preempt_handler_real_signal_sets_flag():
    handler = PreemptionHandler()
    with handler:
        assert not handler.stop_requested
        os.kill(os.getpid(), signal.SIGTERM)
        assert handler.stop_requested
        assert 'SIGTERM' in handler.reason
    # uninstalled: the old disposition is back (sending SIGTERM now
    # would kill pytest, so just verify the bookkeeping)
    assert not handler._installed


def test_preempt_handler_chains_previous_handler():
    seen = []
    old = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        with PreemptionHandler() as handler:
            os.kill(os.getpid(), signal.SIGTERM)
            assert handler.stop_requested
            assert seen == [signal.SIGTERM]   # launcher hook still ran
    finally:
        signal.signal(signal.SIGTERM, old)


def test_preempt_handler_scripted_fault_step_qualified():
    inj = FaultInjector('preempt@train.step.4:1')
    handler = PreemptionHandler(injector=inj)
    assert not handler.check(3)       # wrong step: silent
    assert handler.check(4)           # fires exactly at step 4
    assert handler.check(5)           # stays latched
    assert 'SIGTERM' in handler.reason or 'preempt' in handler.reason


def test_preempted_is_resumable_systemexit(tmp_path):
    handler = PreemptionHandler(injector=FaultInjector('preempt:1'))
    assert handler.check(0)
    path = handler.drain(lambda: str(tmp_path / 'emergency.ckpt'))
    assert path.endswith('emergency.ckpt')
    with pytest.raises(SystemExit) as ei:
        handler.exit(step=7)
    exc = ei.value
    assert isinstance(exc, Preempted)
    assert exc.code == resumable_exit_code() == 75
    assert exc.step == 7 and exc.checkpoint == path


def test_preempt_drain_grace_budget_warns():
    clock = FakeClock()
    handler = PreemptionHandler(grace_s=5.0, clock=clock)

    def slow_save():
        clock.sleep(9.0)
        return 'late.ckpt'

    with pytest.warns(UserWarning, match='grace budget'):
        assert handler.drain(slow_save) == 'late.ckpt'


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------

def test_watchdog_budget_math_and_artifact(tmp_path):
    clock = FakeClock()
    stall = str(tmp_path / 'STALL.json')
    wd = Watchdog(budgets={'step': 10.0}, artifact_path=stall,
                  clock=clock, injector=FaultInjector(''))
    wd.beat(0, phase='step')
    clock.sleep(9.0)
    wd.check()                       # inside budget: no-op
    wd.beat(1)
    clock.sleep(11.0)
    with pytest.raises(DeviceStallError) as ei:
        wd.check()
    assert 'stalled' in str(ei.value)
    import json
    art = json.load(open(stall))
    assert art['schema'] == STALL_SCHEMA
    assert art['phase'] == 'step' and art['step'] == 1
    assert art['waited_s'] > art['budget_s'] == 10.0
    assert 'MainThread' in art['thread_stacks']


def test_watchdog_phase_budgets_differ():
    clock = FakeClock()
    wd = Watchdog(budgets={'compile': 100.0, 'step': 5.0},
                  clock=clock, injector=FaultInjector(''))
    wd.beat(0, phase='compile')
    clock.sleep(50.0)
    assert wd.stalled() is None      # compile budget is larger
    wd.phase('step')
    clock.sleep(6.0)
    assert wd.stalled() is not None


def test_watchdog_hang_injection_ages_heartbeat(tmp_path):
    inj = FaultInjector('hang@train.step.3:1')
    wd = Watchdog(budgets={'step': 300.0},
                  artifact_path=str(tmp_path / 's.json'), injector=inj)
    wd.beat(2, phase='step')
    assert wd.stalled() is None
    wd.beat(3)                       # scripted hang at step 3
    hit = wd.stalled()
    assert hit is not None
    waited, budget, phase, step = hit
    assert step == 3 and waited > budget


def test_watchdog_background_monitor_calls_on_stall(tmp_path):
    import time as _time
    fired = []
    wd = Watchdog(budgets={'step': 0.02},
                  artifact_path=str(tmp_path / 's.json'),
                  injector=FaultInjector(''), on_stall=fired.append,
                  poll_s=0.01)
    with wd:
        wd.beat(5, phase='step')
        deadline = _time.monotonic() + 5.0
        while not fired and _time.monotonic() < deadline:
            _time.sleep(0.01)
    assert fired and fired[0]['step'] == 5
    assert os.path.exists(str(tmp_path / 's.json'))


# ---------------------------------------------------------------------------
# Elastic planning
# ---------------------------------------------------------------------------

def test_shrink_plan_halves_dp_with_accumulation():
    plan = shrink_plan({'axes': {'dp': 8}, 'device_count': 8}, 4)
    assert isinstance(plan, ElasticPlan)
    assert plan.new_axes == {'dp': 4} and plan.accum_steps == 2
    assert plan.changed
    d = plan.as_dict()
    assert d['old_axes'] == {'dp': 8} and d['accum_steps'] == 2


def test_shrink_plan_intact_mesh_is_identity():
    plan = shrink_plan({'axes': {'dp': 8}, 'device_count': 8}, 8)
    assert not plan.changed and plan.accum_steps == 1


def test_shrink_plan_preserves_model_parallel_axes():
    meta = {'axes': {'dp': 4, 'tp': 2}, 'device_count': 8}
    plan = shrink_plan(meta, 4)
    assert plan.new_axes == {'dp': 2, 'tp': 2}
    assert plan.accum_steps == 2
    # below the tp product, or not a multiple of it: refuse loudly
    with pytest.raises(MeshShrinkError):
        shrink_plan(meta, 1)
    with pytest.raises(MeshShrinkError):
        shrink_plan(meta, 6)


def test_shrink_plan_rejects_indivisible_shrink():
    with pytest.raises(MeshShrinkError, match='divide'):
        shrink_plan({'axes': {'dp': 8}, 'device_count': 8}, 3)
    with pytest.raises(MeshShrinkError, match='batch'):
        shrink_plan({'axes': {'dp': 8}, 'device_count': 8}, 4,
                    global_batch=12)   # 12 % (4*2) != 0


def test_available_devices_honors_device_loss():
    import jax
    n = len(jax.devices())
    inj = FaultInjector('device_loss@elastic.restart:1')
    devs = available_devices(injector=inj)
    assert len(devs) == max(1, n // 2)
    # consumed: the next probe sees the full slice again
    assert len(available_devices(injector=inj)) == n


# ---------------------------------------------------------------------------
# ParallelTrainer: checkpoint / resume / accumulation
# ---------------------------------------------------------------------------

def _fresh_pt(mesh=None, lr=0.1):
    import jax
    if mesh is None:
        mesh = parallel.create_mesh({'dp': 1},
                                    devices=jax.devices()[:1])
    np.random.seed(5)
    mx.random.seed(5)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation='relu'), nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 6)))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    pt = parallel.ParallelTrainer(
        net, loss, 'sgd', {'learning_rate': lr, 'momentum': 0.9},
        mesh)
    return net, pt


def _bat(step, batch=8):
    rs = np.random.RandomState(100 + step)
    return (nd.array(rs.randn(batch, 6).astype('float32')),
            nd.array(rs.randint(0, 3, (batch,)).astype('float32')))


def _params_np(net):
    return {k: p.data().asnumpy()
            for k, p in sorted(net.collect_params().items())}


def test_parallel_trainer_checkpoint_resume_bit_identical(tmp_path):
    # uninterrupted: 6 steps
    net_a, pt_a = _fresh_pt()
    x0, y0 = _bat(0)
    pt_a.build(x0, y0)
    for s in range(6):
        pt_a.step(*_bat(s))

    # interrupted: 3 steps, checkpoint, then a FRESH process-analog
    # trainer resumes and finishes
    net_b, pt_b = _fresh_pt()
    pt_b.build(x0, y0)
    mgr = CheckpointManager(str(tmp_path), prefix='pt')
    for s in range(3):
        pt_b.step(*_bat(s))
    pt_b.save_checkpoint(mgr)
    state = mgr.latest()[1]
    assert state['mesh'] == mesh_meta(pt_b._mesh)

    net_c, pt_c = _fresh_pt()
    pt_c.build(x0, y0)
    step, plan = pt_c.resume(mgr)
    assert step == 3 and plan is None
    for s in range(3, 6):
        pt_c.step(*_bat(s))

    pa, pc = _params_np(net_a), _params_np(net_c)
    for (ka, va), (kc, vc) in zip(sorted(pa.items()),
                                  sorted(pc.items())):
        assert np.array_equal(va, vc), \
            'param %s/%s not bit-identical after resume' % (ka, kc)


def test_parallel_trainer_attached_checkpoint_and_preempt(tmp_path):
    mgr = CheckpointManager(str(tmp_path), prefix='pt')
    inj = FaultInjector('preempt@train.step.4:1')
    net, pt = _fresh_pt()
    x0, y0 = _bat(0)
    pt.build(x0, y0)
    pt.attach_preemption(PreemptionHandler(injector=inj))
    pt.attach_checkpointing(mgr, every_n=2)
    with pytest.raises(SystemExit) as ei:
        for s in range(8):
            pt.step(*_bat(s))
    exc = ei.value
    assert isinstance(exc, Preempted) and exc.step == 4
    # periodic checkpoints at 2 and 4 (the step-4 one is the drain)
    assert exc.checkpoint == mgr.path_for(4)
    assert mgr.latest()[0] == 4


def test_step_accum_matches_single_step_to_fp_tolerance():
    net, pt = _fresh_pt()
    x, y = _bat(1, batch=8)
    pt.build(x, y)
    snap = pt.snapshot()
    loss_one = float(pt.step(x, y).asnumpy())
    params_one = _params_np(net)
    pt.restore(snap)
    loss_acc = float(pt.step_accum(x, y, 2).asnumpy())
    params_acc = _params_np(net)
    assert abs(loss_one - loss_acc) < 1e-5
    for k in params_one:
        np.testing.assert_allclose(params_one[k], params_acc[k],
                                   rtol=1e-5, atol=1e-6)
    assert pt.num_update == 1    # one optimizer advance either way


def test_elastic_shrink_resume_tracks_loss_trajectory(tmp_path):
    """8-replica run checkpointed mid-stream, resumed on a 4-replica
    mesh with accum=2: the remaining losses match to fp32 tolerance
    (the in-process analog of the fault_smoke elastic leg)."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip('needs the 8-device virtual mesh')
    mesh8 = parallel.create_mesh({'dp': 8})
    net_a, pt_a = _fresh_pt(mesh=mesh8)
    x0, y0 = _bat(0, batch=16)
    pt_a.build(x0, y0)
    mgr = CheckpointManager(str(tmp_path), prefix='pt')
    for s in range(3):
        pt_a.step(*_bat(s, batch=16))
    pt_a.save_checkpoint(mgr)
    ref = [float(pt_a.step(*_bat(s, batch=16)).asnumpy())
           for s in range(3, 6)]

    mesh4 = parallel.create_mesh({'dp': 4},
                                 devices=jax.devices()[:4])
    net_b, pt_b = _fresh_pt(mesh=mesh4)
    xm, ym = _bat(0, batch=16)
    pt_b.build(xm[:8], ym[:8])      # microbatch shapes
    step, plan = pt_b.resume(mgr)
    assert step == 3
    assert plan is not None and plan.accum_steps == 2
    got = [float(pt_b.step_accum(*_bat(s, batch=16), 2).asnumpy())
           for s in range(3, 6)]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_resume_refuses_shrink_when_elastic_disabled(tmp_path):
    import jax
    if len(jax.devices()) < 2:
        pytest.skip('needs >= 2 devices')
    mesh2 = parallel.create_mesh({'dp': 2},
                                 devices=jax.devices()[:2])
    net_a, pt_a = _fresh_pt(mesh=mesh2)
    x0, y0 = _bat(0, batch=8)
    pt_a.build(x0, y0)
    pt_a.step(x0, y0)
    mgr = CheckpointManager(str(tmp_path), prefix='pt')
    pt_a.save_checkpoint(mgr)

    mesh1 = parallel.create_mesh({'dp': 1},
                                 devices=jax.devices()[:1])
    net_b, pt_b = _fresh_pt(mesh=mesh1)
    pt_b.build(x0[:4], y0[:4])
    with pytest.raises(MeshShrinkError, match='disabled'):
        pt_b.resume(mgr, elastic=False)
    step, plan = pt_b.resume(mgr, elastic=True)
    assert plan.accum_steps == 2


# ---------------------------------------------------------------------------
# Module.fit: step-granular resume == uninterrupted, bit for bit
# ---------------------------------------------------------------------------

def _fit_module():
    from mxnet_tpu import sym
    np.random.seed(3)     # initializer draws use numpy's RNG
    mx.random.seed(3)
    data = sym.Variable('data')
    out = sym.FullyConnected(data, num_hidden=3, name='fc')
    net = sym.SoftmaxOutput(out, name='softmax')
    return mx.mod.Module(net, context=mx.cpu())


def _fit_data():
    from mxnet_tpu import io as mxio
    rs = np.random.RandomState(0)
    X = rs.randn(24, 6).astype('float32')
    Y = rs.randint(0, 3, (24,)).astype('float32')
    return mxio.NDArrayIter(X, Y, batch_size=8)


def test_fit_step_granular_resume_bit_identical(tmp_path,
                                                monkeypatch):
    opt_args = {'optimizer_params': (('learning_rate', 0.05),
                                     ('momentum', 0.9))}
    # uninterrupted reference: 2 epochs (6 batches)
    mx.random.seed(3)
    m1 = _fit_module()
    m1.fit(_fit_data(), num_epoch=2, **opt_args)
    ref_args, _ = m1.get_params()

    # preempted run: step checkpoints every 2 batches, scripted
    # preemption after global step 5 (mid-epoch 1) -> Preempted with
    # the resumable rc and an emergency step checkpoint
    ckdir = str(tmp_path / 'fit')
    mx.random.seed(3)
    m2 = _fit_module()
    monkeypatch.setenv('MXNET_TPU_FAULT', 'preempt@train.step.5:1')
    with pytest.raises(SystemExit) as ei:
        m2.fit(_fit_data(), num_epoch=2, checkpoint_dir=ckdir,
               checkpoint_every_n_steps=2, preempt=True, **opt_args)
    assert isinstance(ei.value, Preempted)
    assert ei.value.code == resumable_exit_code()
    monkeypatch.setenv('MXNET_TPU_FAULT', '')

    # restart, same command: fast-forwards the sampler into epoch 1
    # and finishes with params BIT-IDENTICAL to the uninterrupted run
    mx.random.seed(3)
    m3 = _fit_module()
    m3.fit(_fit_data(), num_epoch=2, checkpoint_dir=ckdir,
           checkpoint_every_n_steps=2, preempt=True, **opt_args)
    got_args, _ = m3.get_params()
    for k in ref_args:
        assert np.array_equal(ref_args[k].asnumpy(),
                              got_args[k].asnumpy()), \
            'param %s not bit-identical after mid-epoch resume' % k


def test_fit_epoch_checkpoint_still_wins_over_stale_step(tmp_path):
    """A step checkpoint from an EARLIER epoch than the newest epoch
    checkpoint is stale progress and must not rewind training."""
    from mxnet_tpu.resilience.checkpoint import save_state
    ckdir = str(tmp_path / 'fit')
    mx.random.seed(3)
    m1 = _fit_module()
    m1.fit(_fit_data(), num_epoch=2, checkpoint_dir=ckdir)
    mgr = CheckpointManager(ckdir, prefix='fit')
    assert mgr.latest()[0] == 1
    # forge a stale mid-epoch-0 step checkpoint
    state = dict(mgr.latest()[1])
    state.update(epoch=0, nbatch=1, global_step=2)
    save_state(os.path.join(ckdir, 'fitstep-00000002.ckpt'), state)
    m2 = _fit_module()
    m2.fit(_fit_data(), num_epoch=4, checkpoint_dir=ckdir)
    assert mgr.latest()[0] == 3   # resumed at epoch 2, not epoch 0


# ---------------------------------------------------------------------------
# gluon Trainer attachments
# ---------------------------------------------------------------------------

def test_gluon_trainer_watchdog_and_preempt():
    from mxnet_tpu import autograd
    np.random.seed(2)
    mx.random.seed(2)
    net = nn.Dense(2)
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 4)))
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    clock = FakeClock()
    wd = Watchdog(budgets={'step': 50.0}, clock=clock,
                  injector=FaultInjector(''))
    trainer.attach_watchdog(wd)
    trainer.attach_preemption(
        PreemptionHandler(injector=FaultInjector(
            'preempt@train.step.2:1')))
    loss_fn = gluon.loss.L2Loss()
    x = nd.ones((4, 4))
    y = nd.zeros((4, 2))
    with pytest.raises(SystemExit):
        for _ in range(4):
            with autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            trainer.step(4)
    assert trainer._step_count == 2   # steps 0 and 1 completed
