"""Compiled SPMD training step over a mesh.

Reference analog: the whole §3.3 loop — DataParallelExecutorGroup batch
slicing + kvstore push/pull + server-side optimizer — fused into ONE
jit-compiled function: forward, backward, gradient reduction (XLA-inserted
psum over 'dp'), and the optimizer update run on-device under GSPMD.
Notably sync-BatchNorm falls out for free: batch statistics are computed on
the logical (global) batch (vs the reference's dedicated
contrib/sync_batch_norm.cc).

The optimizer update is built by tracing the optimizer's OWN update() code
(same machinery as optimizer.fused.FusedUpdater), so the full optimizer zoo
runs under the mesh — not a hardcoded sgd/adam pair.

Numerical guardrails (docs/GUARDRAILS.md): with ``guardrail=`` enabled the
SAME compiled program also (a) scales the loss by the dynamic loss scale,
(b) reduces an all-finite + grad-global-norm sentinel into one packed
replicated scalar — fused by XLA into the backward, no extra pass and no
host transfer — and (c) guards the optimizer update behind ``lax.cond`` on
the verdict: an overflow step leaves params and optimizer state
bit-identical, halves the scale, and surfaces a skip event; the host-side
anomaly policy escalates persistent/spiking behavior to a checkpoint
rollback (guardrail/rollback.py). The skip/scale decision is computed on
the LOGICAL gradients, so every replica takes the same branch in lockstep
by construction.
"""
from __future__ import annotations

import time as _time

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import autograd
from .. import observability as _obs
from .. import random as _random
from ..ndarray import NDArray
from .mesh import current_mesh
from .sharding import (ShardingRules, infer_param_sharding,
                       zero_update_spec)

__all__ = ['ParallelTrainer', 'pure_forward_fn']


def pure_forward_fn(block, training=True):
    """Extract a pure jax function from a HybridBlock.

    Returns fn(key, param_arrays, input_arrays) ->
        (out_arrays_tuple, aux_arrays_tuple), and a meta dict filled at
    first trace with 'aux_params' (Parameters receiving moving-stat
    updates, e.g. BatchNorm). This is the same machinery CachedOp jits;
    exposed for the parallel layer to compose with grad/optimizer.
    """
    from ..gluon.block import _TraceScope, _flatten
    from ..ops import traceknobs as _traceknobs

    params = block._cached_op_params
    meta = {}
    # build-time knob snapshot installed over every trace of fn
    # (docs/ANALYSIS.md trace-purity contract)
    knobs = _traceknobs.snapshot()

    def fn(key, param_arrays, input_arrays):
        prev_train = autograd.set_training(training)
        try:
            with _random.key_override(key), _traceknobs.scope(knobs), \
                    _TraceScope() as scope:
                nd_in = [NDArray(a) if a is not None else None
                         for a in input_arrays]
                nd_params = [NDArray(a) for a in param_arrays]
                for p, v in zip(params, nd_params):
                    p._trace_data = v
                try:
                    out = block._forward_impl(*nd_in)
                finally:
                    for p in params:
                        p._trace_data = None
                flat_out, fmt = _flatten(out, 'output')
                meta['fmt'] = fmt
                meta['aux_params'] = [p for (p, _) in scope.updates]
                return (tuple(o._data for o in flat_out),
                        tuple(a for (_, a) in scope.updates))
        finally:
            autograd.set_training(prev_train)

    return fn, meta, params


def _as_list(x):
    return list(x) if isinstance(x, (list, tuple)) else [x]


# per-process construction counter: trainers are built in the same
# order on every process of a pod, so the id doubles as the broadcast
# namespace for this trainer's RNG base key
import itertools as _itertools
_trainer_ids = _itertools.count()


def _resolve_guardrail(guardrail):
    """None → env knob; True/config → fresh Guardrail; instance → it."""
    from ..guardrail import Guardrail, GuardrailConfig
    if guardrail is None:
        from ..config import get as _cfg
        if not _cfg('MXNET_TPU_GUARDRAIL'):
            return None
        guardrail = True
    if guardrail is False:
        return None
    if guardrail is True:
        return Guardrail(GuardrailConfig.from_env())
    if isinstance(guardrail, GuardrailConfig):
        return Guardrail(guardrail)
    return guardrail


class ParallelTrainer:
    """Gluon-style trainer whose step is ONE pjit-compiled program.

    Usage:
        mesh = parallel.create_mesh({'dp': 4, 'tp': 2})
        pt = ParallelTrainer(net, loss, 'sgd', {'learning_rate': 0.1}, mesh)
        loss = pt.step(x, y)     # NDArrays; sharded + compiled underneath

    ``loss`` may be a Gluon loss Block (called as loss(pred, label)) or a
    callable ``fn(outputs, labels) -> NDArray`` receiving the network's
    outputs and the label list — multi-output models (BERT: MLM + NSP
    heads) compose their objective there. ``x``/``y`` may each be one
    NDArray or a list (multi-input networks).

    Any registered optimizer works: the fused program is built by tracing
    the optimizer's own update() with traced lr/wd/t/rescale scalars (the
    FusedUpdater machinery), under the parameter shardings.

    ``guardrail`` opts into the in-jit numerical guardrail (see module
    docstring): None reads ``MXNET_TPU_GUARDRAIL``; True/GuardrailConfig
    builds a fresh :class:`~mxnet_tpu.guardrail.Guardrail`; an instance is
    used as-is (drivers share one across trainers for unified reporting).

    ``zero`` opts into the ZeRO-sharded weight update (docs/PARALLEL.md;
    PAPERS "Automatic Cross-Replica Sharding of Weight Update in
    Data-Parallel Training"): None reads ``MXNET_TPU_ZERO``. When active
    (and the mesh has dp > 1), optimizer state is created under a
    dp-sharded NamedSharding — each replica owns 1/dp of every state
    tensor — gradients reach the update through a reduce-scatter instead
    of an all-reduce, and the updated param shards are all-gathered back
    to their (replicated or model-sharded) layout, all inside the ONE
    compiled step so XLA fuses/overlaps the collectives. Contract: at
    dp-only shapes the loss/params are bit-identical to the replicated
    update (the grad reduction sums the same values in the same order;
    the per-shard update math is elementwise), including through the
    guardrail's ``lax.cond`` skip branch and a preempt→resume cycle.
    ``step_n`` matches only to fp tolerance: inside the scanned
    program the partitioner keeps the carried params dp-sharded across
    iterations and re-lays-out the loop body around the shards, which
    re-orders cross-replica sums (a documented divergence like the
    ``step_accum`` one — see docs/PARALLEL.md). On XLA:CPU the logical
    reduce-scatter lowers as all-reduce + dynamic-slice; TPU emits a
    true reduce-scatter.

    vs gluon.Trainer (eager, op-at-a-time): this compiles forward+backward+
    allreduce+update into one XLA program — the CachedOp-static_alloc analog
    extended through the optimizer (reference fuses at best per-op).
    """

    def __init__(self, net, loss, optimizer='sgd', optimizer_params=None,
                 mesh=None, rules=None, guardrail=None, zero=None,
                 amp=None):
        from ..optimizer import optimizer as _optmod
        from ..amp import resolve as _amp_resolve
        self._net = net
        self._loss = loss
        self._opt_params = dict(optimizer_params or {})
        self._mesh = mesh or current_mesh()
        self._rules = rules or ShardingRules()
        self._zero_arg = zero
        self._zero = False
        self._zero_shardings = None
        if isinstance(optimizer, str):
            self._opt = _optmod.Optimizer.create_optimizer(
                optimizer, **self._opt_params)
        else:
            self._opt = optimizer
        self._amp_policy = _amp_resolve(amp)
        self._guard = _resolve_guardrail(guardrail)
        if self._amp_policy is not None and \
                self._amp_policy.loss_scaling and self._guard is None:
            if guardrail is False:
                import logging
                logging.warning(
                    'amp=%s needs dynamic loss scaling but guardrail '
                    'is explicitly disabled — fp16 gradients WILL '
                    'underflow unscaled (docs/PRECISION.md)',
                    self._amp_policy.name)
            else:
                # fp16's 5 exponent bits underflow real gradients; the
                # PR 2 in-jit guardrail IS the loss-scaling machinery,
                # so the fp16 policy turns it on by default
                self._guard = _resolve_guardrail(True)
        self._gstate = None
        # cross-host runtime (docs/DISTRIBUTED.md): resolved at build —
        # a mesh spanning processes switches every placement below to
        # the dist.topology helpers and checkpoint writes to the
        # rank-0-behind-a-barrier protocol
        self._multiproc = False
        self._coord = None
        self._gather_cache = {}
        self._dist_name = 'pt%d' % next(_trainer_ids)
        self._preempt = None
        self._watchdog = None
        self._ckpt_mgr = None
        self._ckpt_every = 0
        self._jitted_accum = {}
        self._jitted = None
        self._data_shardings = None
        self._params = None
        self._param_arrays = None
        self._state_leaves = None
        self._templates = None
        self._sig = None
        self._base_key = None
        self.num_update = 0

    @property
    def learning_rate(self):
        opt = self._opt
        return opt.lr_scheduler(self.num_update) if opt.lr_scheduler \
            else opt.lr

    @property
    def guardrail(self):
        """The attached host-side Guardrail (None when disabled)."""
        return self._guard

    @property
    def zero(self):
        """True when the built step shards the weight update across dp
        (resolved from the ``zero=`` arg / ``MXNET_TPU_ZERO`` at build;
        False before the first build and on dp=1 meshes)."""
        return self._zero

    @property
    def amp(self):
        """Active AMP policy name ('bf16' | 'fp16' | 'off'),
        resolved from the ``amp=`` arg / ``MXNET_TPU_AMP`` knob at
        construction (docs/PRECISION.md)."""
        return self._amp_policy.name if self._amp_policy is not None \
            else 'off'

    def optimizer_state_bytes(self):
        """Optimizer-state memory accounting of the built step:
        ``(per_device_bytes, logical_bytes)``. ``per_device_bytes`` is
        what one device actually stores (shard shapes under the leaf
        shardings); ``logical_bytes`` is the full unsharded state — the
        replicated footprint. Their ratio is the ZeRO memory win
        (~1/dp with the knob on, 1.0 replicated), the quantity
        bench_scaling records and the sharding selftest gates."""
        if self._jitted is None:
            raise RuntimeError('optimizer_state_bytes() before the step '
                               'is compiled; call build(x, y) first')
        per_dev = logical = 0
        for a in self._state_leaves:
            item = a.dtype.itemsize
            logical += int(onp.prod(a.shape, dtype=onp.int64)) * item \
                if a.ndim else item
            shard = a.sharding.shard_shape(a.shape)
            per_dev += int(onp.prod(shard, dtype=onp.int64)) * item \
                if a.ndim else item
        return per_dev, logical

    def set_learning_rate(self, lr):
        self._opt.set_learning_rate(lr)

    # -- resilience attachments (docs/RESILIENCE.md) -----------------------

    def attach_preemption(self, handler):
        """Attach a :class:`~mxnet_tpu.resilience.PreemptionHandler`:
        every step boundary polls it; a pending stop (signal or
        scripted ``preempt`` fault) drains an emergency checkpoint
        through the attached manager and raises
        :class:`~mxnet_tpu.resilience.Preempted` (resumable rc)."""
        self._preempt = handler
        return self

    def attach_watchdog(self, watchdog):
        """Attach a :class:`~mxnet_tpu.resilience.Watchdog`: each step
        heartbeats before the compiled dispatch (phase ``compile`` for
        the first build, ``step`` after) and checks the stall budget
        after it — a stalled/hung step (scripted ``hang`` fault, or a
        real overrun seen by the background monitor) surfaces as a
        structured stall artifact + ``DeviceStallError``."""
        self._watchdog = watchdog
        return self

    def attach_checkpointing(self, manager, every_n=None):
        """Attach a resilience ``CheckpointManager``: the trainer
        checkpoints itself every ``every_n`` steps (default: the
        ``MXNET_TPU_CKPT_EVERY_N_STEPS`` knob) and is the drain target
        for an attached preemption handler."""
        if every_n is None:
            from ..config import get as _cfg
            every_n = int(_cfg('MXNET_TPU_CKPT_EVERY_N_STEPS') or 0)
        self._ckpt_mgr = manager
        self._ckpt_every = int(every_n)
        return self

    def _boundary_pre(self):
        """Step-boundary protocol, before any build/dispatch:
        preemption drain first (a preempted process must not start
        another step), then the watchdog heartbeat arming the upcoming
        phase."""
        if self._preempt is not None and \
                self._preempt.check(self.num_update):
            if self._ckpt_mgr is not None and self._jitted is not None:
                self._preempt.drain(
                    lambda: self.save_checkpoint(self._ckpt_mgr))
            self._preempt.exit(step=self.num_update)
        if self._watchdog is not None:
            self._watchdog.beat(
                self.num_update,
                phase='compile' if self._jitted is None else 'step')

    def _boundary_post(self):
        if self._watchdog is not None:
            self._watchdog.check()
        if self._ckpt_mgr is not None and self._ckpt_every and \
                self.num_update % self._ckpt_every == 0:
            self.save_checkpoint(self._ckpt_mgr)

    def save_checkpoint(self, manager=None, extra=None):
        """Atomic step-granular checkpoint: the full :meth:`snapshot`
        plus the mesh layout and global RNG chain, numbered by
        ``num_update`` — everything a restarted process (same or
        smaller mesh) needs for a deterministic resume."""
        from ..resilience.elastic import mesh_meta
        from .. import random as _random
        manager = manager or self._ckpt_mgr
        if manager is None:
            raise ValueError('no CheckpointManager attached or given')
        state = self.snapshot()
        state['mesh'] = mesh_meta(self._mesh)
        state['zero'] = bool(self._zero)
        state['amp'] = self.amp
        state['rng'] = _random.get_state()
        state['process_count'] = 1
        if extra:
            state.update(extra)
        if self._multiproc:
            # pod protocol (docs/DISTRIBUTED.md): every host gathers
            # its logical state (the snapshot above ran the all-gather
            # collectively — all ranks MUST reach this point), then
            # rank 0 alone writes, then a closing barrier holds peers
            # until the artifact is durable so no survivor resumes
            # from a half-written file
            coord = self._coordinator()
            state['process_count'] = coord.process_count
            coord.barrier(self._dist_name + '/ckpt_pre')
            path = None
            if coord.process_id == 0:
                with _obs.span('checkpoint'):
                    path = manager.save(self.num_update, state)
            coord.barrier(self._dist_name + '/ckpt_post')
            return path
        # CheckpointManager.save itself counts the write + flight
        # event; the span attributes the wall time to this driver
        with _obs.span('checkpoint'):
            return manager.save(self.num_update, state)

    def resume(self, manager=None, elastic=None):
        """Restore the newest valid checkpoint into this (built)
        trainer; returns ``(step, plan)`` or None when the directory
        has no checkpoint.

        When the checkpoint's mesh had more devices than this
        trainer's, the elastic path engages (``MXNET_TPU_ELASTIC``, or
        the explicit ``elastic=`` override): the logical arrays are
        re-placed under the smaller mesh's shardings and the returned
        :class:`~mxnet_tpu.resilience.ElasticPlan` tells the driver
        how many microbatches to accumulate per step
        (:meth:`step_accum`) to preserve the global batch. A mismatch
        with elasticity disabled — or a shrink that cannot preserve
        semantics — raises
        :class:`~mxnet_tpu.resilience.MeshShrinkError`.
        """
        from ..resilience import elastic as _elastic
        from .. import random as _random
        manager = manager or self._ckpt_mgr
        if manager is None:
            raise ValueError('no CheckpointManager attached or given')
        latest = manager.latest()
        if latest is None:
            return None
        step, state = latest
        plan = None
        meta = state.get('mesh')
        here = _elastic.mesh_meta(self._mesh)
        if meta is not None and meta['device_count'] != \
                here['device_count']:
            if elastic is None:
                from ..config import get as _cfg
                elastic = bool(_cfg('MXNET_TPU_ELASTIC'))
            if not elastic:
                raise _elastic.MeshShrinkError(
                    'checkpoint mesh %s != trainer mesh %s and elastic '
                    'resume is disabled (MXNET_TPU_ELASTIC=0)'
                    % (meta, here))
            plan = _elastic.shrink_plan(meta, here['device_count'])
            if plan.new_axes != here['axes']:
                raise _elastic.MeshShrinkError(
                    'elastic plan wants mesh axes %s but the trainer '
                    'was built on %s — rebuild the mesh from the plan'
                    % (plan.new_axes, here['axes']))
        if state.get('rng') is not None:
            _random.set_state(state['rng'])
        if state.get('zero') is not None and \
                bool(state['zero']) != bool(self._zero):
            # placement-only difference: checkpoints hold LOGICAL
            # arrays, so a ZeRO checkpoint restores onto a replicated
            # trainer (and vice versa) bit-identically — worth a log
            # line because the memory footprint changes
            import logging
            logging.warning(
                'resume: checkpoint was written with zero=%s, trainer '
                'is built with zero=%s — state re-placed under the '
                "trainer's layout (values unchanged)",
                state['zero'], self._zero)
        if state.get('amp') is not None and state['amp'] != self.amp:
            # compute-precision-only difference: checkpoints hold the
            # fp32 masters either way, so the restored VALUES are
            # bit-identical — but the loss trajectory ahead will follow
            # the new compute precision
            import logging
            logging.info(
                'resume: checkpoint was written with amp=%s, trainer '
                'runs amp=%s — fp32 masters restored unchanged',
                state['amp'], self.amp)
        self.restore(state)
        return step, plan

    # -- cross-host placement (docs/DISTRIBUTED.md) ------------------------

    def _put_full(self, a, sharding):
        """Place a LOGICAL (full) host array — params, optimizer
        state, guardrail scalars, restored checkpoints — under a
        sharding of a possibly multi-process mesh."""
        if not self._multiproc:
            return jax.device_put(a, sharding)
        from ..dist import topology as _topo
        return _topo.put_global(a, sharding)

    def _put_data(self, a, sharding):
        """Place one step operand. Single-process: the full batch via
        device_put. Multi-process: ``a`` is this host's LOCAL shard of
        the global batch (dist.topology.host_shard names the rows) and
        the global array is assembled from the process-local shards."""
        if not self._multiproc:
            return jax.device_put(a, sharding)
        from ..dist import topology as _topo
        return _topo.put_local_shard(a, sharding)

    def _to_logical(self, arrays):
        """Host numpy copies of step state for snapshot/checkpoint.
        Replicated arrays fetch directly; dp-sharded ZeRO leaves on a
        multi-process mesh are first gathered to the replicated layout
        inside ONE jitted identity program (an all-gather over DCN) —
        no per-array host loops over non-addressable shards."""
        need_gather = [a for a in arrays
                       if self._multiproc and
                       not a.sharding.is_fully_replicated]
        if not need_gather:
            return [onp.asarray(a) for a in arrays]
        repl = NamedSharding(self._mesh, P())
        # per-trainer cached gather program (keyed on the leaf layout)
        # so a checkpoint cadence never recompiles it
        key = tuple((a.shape, a.dtype.name, a.sharding)
                    for a in need_gather)
        fn = self._gather_cache.get(key)
        if fn is None:
            fn = jax.jit(lambda xs: xs,
                         out_shardings=tuple(repl
                                             for _ in need_gather))
            self._gather_cache[key] = fn
        gathered = fn(tuple(need_gather))
        it = iter(gathered)
        return [onp.asarray(next(it))
                if (self._multiproc and
                    not a.sharding.is_fully_replicated)
                else onp.asarray(a) for a in arrays]

    def _coordinator(self):
        if self._coord is None:
            from ..dist import get_coordinator
            self._coord = get_coordinator()
        return self._coord

    def _build(self, xs, ys):
        from ..gluon.block import ensure_initialized
        from ..optimizer.fused import (_HyperPatch, _flatten_state,
                                       apply_traced_updates)
        ensure_initialized(self._net, *[NDArray(a) if a is not None else None
                                        for a in xs])
        mesh = self._mesh
        from ..dist import topology as _topo
        self._multiproc = _topo.spans_processes(mesh)
        fwd, meta, params = pure_forward_fn(self._net, training=True)
        self._params = params
        opt = self._opt
        opt._index_update_count = dict(opt._index_update_count)
        if not getattr(opt, 'idx2name', None):
            opt.idx2name = {i: p.name for i, p in enumerate(params)}
        loss_obj = self._loss
        n = len(params)
        indices = list(range(n))
        none_pat = tuple(a is None for a in xs)
        xs_live = [a for a in xs if a is not None]

        from ..amp.policy import scope as _amp_scope
        from ..ops import traceknobs as _traceknobs
        amp_policy = self._amp_policy
        # build-time snapshot of the knobs op bodies consult under
        # trace; installed around the traced forward/loss and the
        # traced optimizer update (docs/ANALYSIS.md trace-purity)
        knobs = _traceknobs.snapshot()

        def loss_of(key, param_arrays, data_arrays, label_arrays):
            # re-insert the None placeholders (optional masks etc.) that
            # were stripped from the jit operand list
            full_in, it = [], iter(data_arrays)
            for is_none in none_pat:
                full_in.append(None if is_none else next(it))
            # AMP (docs/PRECISION.md): under the policy scope every op
            # traced below — the forward AND the loss — recasts its
            # operands per class: matmul-family ops compute on low-
            # precision copies of the fp32 masters (cast inside THIS
            # program), softmax/loss ops widen back to f32. The grads
            # value_and_grad returns are w.r.t. the fp32 masters (the
            # astype vjp widens cotangents at each param boundary), so
            # the update below runs in float32 exactly as without AMP.
            with _traceknobs.scope(knobs), _amp_scope(amp_policy):
                outs, auxs = fwd(key, list(param_arrays), full_in)
                nd_outs = [NDArray(o) for o in outs]
                nd_labels = [NDArray(a) for a in label_arrays]
                prev = autograd.set_training(True)
                try:
                    with _random.key_override(key):
                        if callable(loss_obj) and \
                                not hasattr(loss_obj, '_forward_impl'):
                            loss = loss_obj(
                                nd_outs if len(nd_outs) > 1
                                else nd_outs[0],
                                nd_labels if len(nd_labels) > 1 else
                                nd_labels[0])
                        else:
                            loss = loss_obj._forward_impl(nd_outs[0],
                                                          nd_labels[0])
                finally:
                    autograd.set_training(prev)
            loss_val = loss._data
            if amp_policy is not None:
                # the mean (and the guardrail's scaled-loss product)
                # accumulate in f32 even for a custom low-precision
                # loss callable; no-op when the loss is already f32
                loss_val = loss_val.astype(jnp.float32)
            return jnp.mean(loss_val), auxs

        # optimizer states (created eagerly; leaves become jit operands)
        param_arrays = tuple(p.data()._data for p in params)
        leaves = []
        templates = []
        for i, (w, p) in enumerate(zip(param_arrays, params)):
            if p.grad_req == 'null':
                templates.append(('const', None))
                continue
            st = opt.create_state_multi_precision(i, NDArray(w))
            templates.append(_flatten_state(st, leaves))
        self._templates = templates
        leaf_arrays = tuple(l._data for l in leaves)
        skip_idx = {i for i in range(n) if params[i].grad_req == 'null'}

        self._loss_of = loss_of

        param_shardings = tuple(infer_param_sharding(params, mesh,
                                                     self._rules))
        repl = NamedSharding(mesh, P())
        zero = self._zero_arg
        if zero is None:
            from ..config import get as _cfg
            zero = bool(_cfg('MXNET_TPU_ZERO'))
        # ZeRO update sharding (docs/PARALLEL.md): each param's update
        # state lives dp-sharded; the dp=1 (or knob-off) mesh keeps the
        # replicated layout so single-chip stays the degenerate case
        self._zero = bool(zero) and int(mesh.shape.get('dp', 1)) > 1
        if self._zero:
            zero_shardings = tuple(
                NamedSharding(mesh, zero_update_spec(sh.spec, w.shape,
                                                     mesh))
                for sh, w in zip(param_shardings, param_arrays))
        else:
            zero_shardings = param_shardings
        self._zero_shardings = zero_shardings
        zero_live = self._zero

        def run_update(key, lrs, wds, ts, rescale_eff, param_arrays,
                       state_leaves, grads, auxs):
            """Traced optimizer application + BN-aux merge (shared by
            the plain step and the guarded step's healthy branch).

            In ZeRO mode the gradients are constrained to the dp-sharded
            update layout BEFORE the optimizer math (GSPMD turns the
            grad psum into a reduce-scatter) and the updated params are
            constrained to the same shards AFTER it, so the optimizer
            arithmetic runs on 1/dp of each tensor; the jit's param
            out-shardings then insert the closing all-gather."""
            if zero_live:
                grads = tuple(
                    g if i in skip_idx else
                    jax.lax.with_sharding_constraint(g,
                                                     zero_shardings[i])
                    for i, g in enumerate(grads))
            with _random.key_override(key), _traceknobs.scope(knobs), \
                    _HyperPatch(opt, indices, lrs, wds, ts, rescale_eff):
                new_params, new_leaves = apply_traced_updates(
                    opt, indices, list(param_arrays), list(grads),
                    templates, list(state_leaves), skip=skip_idx)
            if zero_live:
                new_params = [
                    w if i in skip_idx else
                    jax.lax.with_sharding_constraint(w,
                                                     zero_shardings[i])
                    for i, w in enumerate(new_params)]
            aux_idx = {id(p): i for i, p in enumerate(params)}
            for p, a in zip(meta.get('aux_params', []), auxs):
                i = aux_idx.get(id(p))
                if i is not None:
                    new_params[i] = a.astype(new_params[i].dtype)
            return tuple(new_params), tuple(new_leaves)

        self._run_update = run_update

        def step(key, hyper, param_arrays, state_leaves, data_arrays,
                 label_arrays):
            lrs, wds, ts, rescale = hyper
            (loss, auxs), grads = jax.value_and_grad(
                lambda ps: loss_of(key, ps, data_arrays, label_arrays),
                has_aux=True)(tuple(param_arrays))
            new_params, new_leaves = run_update(
                key, lrs, wds, ts, rescale, param_arrays, state_leaves,
                grads, auxs)
            return new_params, new_leaves, loss

        def guarded_step(key, hyper, guard_in, param_arrays, state_leaves,
                         data_arrays, label_arrays):
            """step() + loss scaling + fused sentinel + cond-guarded
            update. Extra outputs: (packed health, scale, good-steps) —
            all replicated scalars, no host transfer. The same cond
            carries the ZeRO-sharded update: the skip branch returns
            the dp-sharded state leaves untouched, so an overflow step
            leaves the sharded state bit-identical by construction
            (sentinel.poison_grads is spelled partitioner-safe — see
            its docstring — so the injection point survives grads
            being resharded for the sharded update)."""
            from ..guardrail import scaling as _scaling
            from ..guardrail import sentinel as _sentinel
            cfg = self._guard.config
            lrs, wds, ts, rescale = hyper
            poison, scale, good = guard_in

            def scaled_loss(ps):
                l, auxs = loss_of(key, ps, data_arrays, label_arrays)
                return l * scale, (l, auxs)

            (_, (loss, auxs)), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(tuple(param_arrays))
            grads = _sentinel.poison_grads(list(grads), poison)
            # overflow detection on the SCALED grads; norm unscaled
            # before it leaves the program (exact: power-of-two scale)
            health = _sentinel.grad_health(grads, loss=loss)
            healthy = health >= 0
            inv = jnp.float32(1.0) / scale
            new_params, new_leaves = jax.lax.cond(
                healthy,
                lambda ops: run_update(key, lrs, wds, ts, rescale * inv,
                                       ops[0], ops[1], grads, auxs),
                # skip branch: params, optimizer state AND BatchNorm
                # moving stats stay bit-identical — the whole batch is
                # quarantined, matching AMP skip semantics
                lambda ops: (tuple(ops[0]), tuple(ops[1])),
                (tuple(param_arrays), tuple(state_leaves)))
            new_scale, new_good = _scaling.update_scale(
                scale, good, healthy,
                growth_interval=cfg.growth_interval,
                min_scale=cfg.min_scale, max_scale=cfg.max_scale)
            return (new_params, new_leaves, loss,
                    (_sentinel.rescale_packed(health, inv), new_scale,
                     new_good))

        hyper0 = self._hyper(indices, opt, advance=False)
        guard0 = None
        if self._guard is not None:
            guard0 = (onp.float32(0.0),
                      onp.float32(self._guard.config.init_scale),
                      onp.int32(0))
        # abstract probe fills meta['aux_params'] without running compute
        if self._guard is None:
            jax.eval_shape(step, jax.random.PRNGKey(0), hyper0,
                           param_arrays, leaf_arrays, tuple(xs_live),
                           tuple(ys))
        else:
            jax.eval_shape(guarded_step, jax.random.PRNGKey(0), hyper0,
                           guard0, param_arrays, leaf_arrays,
                           tuple(xs_live), tuple(ys))

        # a state leaf shaped like its parameter shards like its param's
        # UPDATE layout (the param sharding, or the dp-sharded ZeRO
        # layout when the knob is on — each replica owning 1/dp of every
        # state tensor is the memory win of PAPERS 2004.13336); anything
        # else (scalars, counters) replicates
        def count_leaves(tt):
            if tt[0] == 'leaf':
                return 1
            if tt[0] == 'seq':
                return sum(count_leaves(s) for s in tt[2])
            return 0

        leaf_shardings = []
        li = 0
        for i, t in enumerate(templates):
            for _ in range(count_leaves(t)):
                leaf = leaf_arrays[li]
                if leaf.shape == param_arrays[i].shape:
                    leaf_shardings.append(zero_shardings[i])
                else:
                    leaf_shardings.append(repl)
                li += 1
        leaf_shardings = tuple(leaf_shardings)

        def dshard(a):
            spec = [None] * a.ndim
            if 'dp' in mesh.axis_names and a.ndim:
                spec[0] = 'dp'
            return NamedSharding(mesh, P(*spec))

        data_shardings = tuple(dshard(a) for a in xs_live)
        label_shardings = tuple(dshard(a) for a in ys)
        self._sig = (none_pat, len(ys))

        if self._guard is None:
            self._jitted = jax.jit(
                step,
                in_shardings=(repl, (repl, repl, repl, repl),
                              param_shardings, leaf_shardings,
                              data_shardings, label_shardings),
                out_shardings=(param_shardings, leaf_shardings, repl),
                donate_argnums=(2, 3))
            self._step_fn = step
        else:
            self._jitted = jax.jit(
                guarded_step,
                in_shardings=(repl, (repl, repl, repl, repl),
                              (repl, repl, repl), param_shardings,
                              leaf_shardings, data_shardings,
                              label_shardings),
                out_shardings=(param_shardings, leaf_shardings, repl,
                               (repl, repl, repl)),
                donate_argnums=(3, 4))
            self._step_fn = guarded_step
            self._gstate = (
                self._put_full(onp.float32(self._guard.config.init_scale),
                               repl),
                self._put_full(onp.int32(0), repl))
        self._param_arrays = tuple(
            self._put_full(w, sh) for w, sh in zip(param_arrays,
                                                   param_shardings))
        self._state_leaves = tuple(
            self._put_full(a, sh) for a, sh in zip(leaf_arrays,
                                                   leaf_shardings))
        self._data_shardings = (data_shardings, label_shardings)
        self._abstract_io = (
            tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in xs_live),
            tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ys))
        self._shardings = (repl, param_shardings, leaf_shardings,
                           data_shardings, label_shardings)
        self._jitted_multi = None

    def _build_multi(self):
        """One XLA program running N sequential fused steps via
        lax.scan (N inferred from the stacked operands; jit re-keys on
        shapes) — the per-launch dispatch overhead amortizes across the
        scan. Per-step hyper
        arrays are stacked operands, so lr schedules and Adam bias
        correction advance exactly as in the single-step path. With the
        guardrail on, the loss-scale state threads through the scan
        carry and per-step poison/health/scale ride the stacked
        operands/outputs."""
        step = self._step_fn
        repl, param_sh, leaf_sh, data_sh, label_sh = self._shardings
        lead_data, lead_label = self._lead_shardings()

        if self._guard is None:
            def multi(keys, hypers, param_arrays, state_leaves, xs, ys):
                def body(carry, inp):
                    ps, ls = carry
                    key, hyper, x, y = inp
                    p2, l2, loss = step(key, hyper, ps, ls, x, y)
                    return (p2, l2), loss
                (ps, ls), losses = jax.lax.scan(
                    body, (param_arrays, state_leaves),
                    (keys, hypers, xs, ys))
                return ps, ls, losses

            return jax.jit(
                multi,
                in_shardings=(repl, (repl, repl, repl, repl), param_sh,
                              leaf_sh, lead_data, lead_label),
                out_shardings=(param_sh, leaf_sh, repl),
                donate_argnums=(2, 3))

        def multi_g(keys, hypers, poisons, gstate, param_arrays,
                    state_leaves, xs, ys):
            def body(carry, inp):
                ps, ls, sc, gd = carry
                key, hyper, poi, x, y = inp
                p2, l2, loss, (health, sc2, gd2) = step(
                    key, hyper, (poi, sc, gd), ps, ls, x, y)
                return (p2, l2, sc2, gd2), (loss, health, sc2)
            (ps, ls, sc, gd), (losses, healths, scales) = jax.lax.scan(
                body, (param_arrays, state_leaves) + tuple(gstate),
                (keys, hypers, poisons, xs, ys))
            return ps, ls, (sc, gd), losses, healths, scales

        return jax.jit(
            multi_g,
            in_shardings=(repl, (repl, repl, repl, repl), repl,
                          (repl, repl), param_sh, leaf_sh,
                          lead_data, lead_label),
            out_shardings=(param_sh, leaf_sh, (repl, repl), repl, repl,
                           repl),
            donate_argnums=(4, 5))

    def _build_accum(self, accum):
        """One XLA program: ``accum`` microbatch gradient passes whose
        mean feeds a SINGLE optimizer update — the elastic mesh-shrink
        resume path (docs/RESILIENCE.md): after dp shrinks k-fold, k
        microbatches per step keep the logical global batch (and so
        the loss trajectory, to fp tolerance) unchanged. The loop is
        unrolled in the trace: ``accum`` is the small dp shrink
        factor, not a schedule length."""
        loss_of, run_update = self._loss_of, self._run_update
        repl, param_sh, leaf_sh, data_sh, label_sh = self._shardings
        lead_data, lead_label = self._lead_shardings()

        def accum_step(key, hyper, param_arrays, state_leaves, xs, ys):
            lrs, wds, ts, rescale = hyper
            gsum, auxs, losses = None, None, []
            for i in range(accum):
                # distinct threefry key per microbatch (dropout et al.)
                mkey = jnp.stack([key[0],
                                  key[1] ^ jnp.uint32(0x9e3779b9 + i)])
                x_i = tuple(a[i] for a in xs)
                y_i = tuple(a[i] for a in ys)
                (loss, aux_i), grads = jax.value_and_grad(
                    lambda ps, k=mkey, xi=x_i, yi=y_i:
                        loss_of(k, ps, xi, yi),
                    has_aux=True)(tuple(param_arrays))
                gsum = grads if gsum is None else tuple(
                    a + b for a, b in zip(gsum, grads))
                # BatchNorm moving stats follow the LAST microbatch —
                # the documented fp-level divergence of an elastic
                # resume (stats batch is the microbatch, not the
                # global batch)
                auxs = aux_i
                losses.append(loss)
            grads = tuple(g / accum for g in gsum)
            new_params, new_leaves = run_update(
                key, lrs, wds, ts, rescale, param_arrays, state_leaves,
                grads, auxs)
            return new_params, new_leaves, jnp.mean(jnp.stack(losses))

        return jax.jit(
            accum_step,
            in_shardings=(repl, (repl, repl, repl, repl), param_sh,
                          leaf_sh, lead_data, lead_label),
            out_shardings=(param_sh, leaf_sh, repl),
            donate_argnums=(2, 3))

    def step_accum(self, x, y, accum):
        """One optimizer update from ``accum`` microbatches in a single
        compiled program; returns the mean (replicated scalar) loss.

        ``x``/``y`` carry the FULL global batch; the leading dim is
        split into ``accum`` equal microbatches. Exactly one
        lr-schedule / update-count advance happens, so an
        elastic-shrunk resume (:meth:`resume` returning a plan with
        ``accum_steps > 1``) walks the same optimizer trajectory as
        the original mesh."""
        accum = int(accum)
        if accum <= 1:
            return self.step(x, y)
        if self._guard is not None:
            raise NotImplementedError(
                'step_accum does not compose with the in-jit guardrail '
                'yet — run the elastic-shrunk resume unguarded '
                '(docs/RESILIENCE.md)')
        self._boundary_pre()
        xs, ys = self._normalize(x, y)

        def split(a):
            if a.shape[0] % accum:
                raise ValueError(
                    'global batch %d does not split into %d '
                    'microbatches' % (a.shape[0], accum))
            return a.reshape((accum, a.shape[0] // accum) + a.shape[1:])

        xs_s = [None if a is None else split(a) for a in xs]
        ys_s = [split(a) for a in ys]
        tel = _obs.enabled()
        first = self._jitted is None
        t0 = _time.perf_counter() if tel else 0.0
        if first:
            with _obs.span('compile'):
                self._build([None if a is None else a[0] for a in xs_s],
                            [a[0] for a in ys_s])
        sig = (tuple(a is None for a in xs), len(ys))
        if sig != self._sig:
            raise ValueError(
                'step_accum called with input signature %r but the '
                'compiled step was built for %r' % (sig, self._sig))
        if accum not in self._jitted_accum:
            self._jitted_accum[accum] = self._build_accum(accum)
        opt = self._opt
        indices = list(range(len(self._params)))
        hyper = self._hyper(indices, opt, advance=True)
        key = onp.asarray(
            [self._next_base_key()[0],
             self._base_key[1] ^ onp.uint32(self.num_update + 1)],
            dtype=onp.uint32)
        live = tuple(a for a in xs_s if a is not None)
        if self._multiproc:
            lead = self._lead_shardings()
            live = tuple(self._put_data(a, sh)
                         for a, sh in zip(live, lead[0]))
            ys_s = [self._put_data(a, sh)
                    for a, sh in zip(ys_s, lead[1])]
        self._param_arrays, self._state_leaves, loss = \
            self._jitted_accum[accum](key, hyper, self._param_arrays,
                                      self._state_leaves, live,
                                      tuple(ys_s))
        self.num_update += 1
        for p, w in zip(self._params, self._param_arrays):
            p.data()._data = w
        if tel:
            self._record_step_telemetry(
                first, t0, int(ys[0].shape[0]) if ys else 0)
        self._boundary_post()
        return NDArray(loss)

    def _normalize(self, x, y):
        xs = [a._data if isinstance(a, NDArray) else
              (None if a is None else jnp.asarray(a)) for a in _as_list(x)]
        ys = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
              for a in _as_list(y)]
        return xs, ys

    def prefetch_iter(self, batches, depth=None):
        """Stage ``(x, y)`` batches onto this trainer's input shardings
        ahead of :meth:`step` (docs/PERFORMANCE.md).

        A background thread pulls from ``batches`` and issues the
        host→device transfers under the compiled step's input
        shardings, so the next batch's DMA overlaps the current step's
        device compute; :meth:`step`'s own ``device_put`` then
        short-circuits on the already-placed arrays. Batches pulled
        before the first build (no shardings yet) pass through
        unstaged. Returns a :class:`~mxnet_tpu.io.DevicePrefetcher`
        (``close()`` it when abandoning the iterator mid-stream); a
        stalled staging thread degrades to synchronous transfers
        without dropping a batch.
        """
        from ..io.staging import DevicePrefetcher

        def placer(item):
            # _data_shardings lands LAST in _build: a None read here
            # also covers the window where _jitted exists but the
            # shardings do not yet (the staging thread races the
            # first build)
            shardings = self._data_shardings
            if shardings is None:
                return item
            x, y = item
            xs, ys = self._normalize(x, y)
            live = [a for a in xs if a is not None]
            data_sh, label_sh = shardings
            if self._multiproc:
                # multi-process staging goes through the local-shard
                # assembly path (the batch fed here is this host's
                # slice, same as step()'s contract)
                xd = iter(self._put_data(a, sh)
                          for a, sh in zip(live, data_sh))
                staged_x = [None if a is None else NDArray(next(xd))
                            for a in xs]
                staged_y = [NDArray(self._put_data(a, sh))
                            for a, sh in zip(ys, label_sh)]
                return (staged_x if len(staged_x) > 1 else staged_x[0],
                        staged_y if len(staged_y) > 1 else staged_y[0])
            xd = iter(jax.device_put(a, sh)
                      for a, sh in zip(live, data_sh))
            staged_x = [None if a is None else NDArray(next(xd))
                        for a in xs]
            staged_y = [NDArray(jax.device_put(a, sh))
                        for a, sh in zip(ys, label_sh)]
            return (staged_x if len(staged_x) > 1 else staged_x[0],
                    staged_y if len(staged_y) > 1 else staged_y[0])

        return DevicePrefetcher(batches, placer=placer, depth=depth,
                                name='trainer-prefetch')

    def build(self, x, y):
        """Compile the step for these operand shapes without running it.

        Guarded drivers prime here so a step-0 last-good snapshot can be
        taken before any batch — and any scripted fault — is consumed."""
        xs, ys = self._normalize(x, y)
        if self._jitted is None:
            self._build(xs, ys)
        return self

    def step_n(self, x, y):
        """Run one fused step per leading-dim slice of ``x``/``y`` in a
        SINGLE compiled program; returns the per-step losses as one
        array. Semantically identical to calling step() n times.

        Step-boundary resilience (preempt drain / watchdog) runs once
        per *window*: the scanned steps are one XLA dispatch, so there
        is no host boundary inside to stop at."""
        self._boundary_pre()
        xs, ys = self._normalize(x, y)
        live = [a for a in xs if a is not None]
        if not live or not ys:
            raise ValueError('step_n needs at least one data and one '
                             'label array')
        nsteps = int(live[0].shape[0])
        if nsteps == 0:
            raise ValueError('step_n called with a zero-length leading '
                             '(steps) dimension')
        tel = _obs.enabled()
        first = self._jitted is None
        t0 = _time.perf_counter() if tel else 0.0
        if first:
            with _obs.span('compile'):
                self._build([None if a is None else a[0] for a in xs],
                            [a[0] for a in ys])
        sig = (tuple(a is None for a in xs), len(ys))
        if sig != self._sig:
            raise ValueError(
                'step_n called with input signature %r but the compiled '
                'step was built for %r — input/label arity and '
                'None-positions must match the first call'
                % (sig, self._sig))
        xs = live
        opt = self._opt
        indices = list(range(len(self._params)))
        hypers = []
        for _ in range(nsteps):
            hypers.append(self._hyper(indices, opt, advance=True))
        stacked = tuple(onp.stack([h[k] for h in hypers])
                        for k in range(4))
        self._next_base_key()
        keys = onp.stack([
            onp.asarray([self._base_key[0],
                         self._base_key[1] ^
                         onp.uint32(self.num_update + 1 + i)],
                        dtype=onp.uint32) for i in range(nsteps)])
        if self._jitted_multi is None:
            self._jitted_multi = self._build_multi()
        jitted = self._jitted_multi
        if self._multiproc:
            lead = self._lead_shardings()
            xs = [self._put_data(a, sh) for a, sh in zip(xs, lead[0])]
            ys = [self._put_data(a, sh) for a, sh in zip(ys, lead[1])]
        start = self.num_update
        if self._guard is None:
            self._param_arrays, self._state_leaves, losses = jitted(
                keys, stacked, self._param_arrays, self._state_leaves,
                tuple(xs), tuple(ys))
        else:
            poisons = onp.asarray(
                [self._guard.next_poison() for _ in range(nsteps)],
                dtype=onp.float32)
            (self._param_arrays, self._state_leaves, self._gstate,
             losses, healths, scales) = jitted(
                keys, stacked, poisons, self._gstate,
                self._param_arrays, self._state_leaves, tuple(xs),
                tuple(ys))
        self.num_update += nsteps
        for p, w in zip(self._params, self._param_arrays):
            p.data()._data = w
        if tel:
            self._record_step_telemetry(
                first, t0, nsteps * int(ys[0].shape[1]) if ys else 0,
                nsteps=nsteps)
        if self._guard is not None:
            # one materialisation for the whole window (the scan already
            # synced at its end); feeds the host policy per step
            h_host = onp.asarray(healths)
            l_host = onp.asarray(losses)
            s_host = onp.asarray(scales)
            for i in range(nsteps):
                self._guard.record(start + i, float(h_host[i]),
                                   loss=float(l_host[i]),
                                   scale=float(s_host[i]))
        self._boundary_post()
        return NDArray(losses)

    def _lead_shardings(self):
        """Leading-dim-stacked data/label shardings (the step_n /
        step_accum operand layouts): P(None, *spec)."""
        data_sh, label_sh = self._data_shardings

        def lead(sh):
            return NamedSharding(sh.mesh, P(None, *sh.spec))

        return (tuple(lead(s) for s in data_sh),
                tuple(lead(s) for s in label_sh))

    def _next_base_key(self):
        """The per-trainer RNG base key, drawn once from the global
        chain. On a multi-process mesh process 0's draw is broadcast
        so dropout masks (and the guardrail's poison schedule keys)
        agree across hosts even when per-host RNG chains drifted."""
        if self._base_key is None:
            base = onp.asarray(_random.next_key(), dtype=onp.uint32)
            if self._multiproc:
                base = onp.asarray(self._coordinator().broadcast(
                    self._dist_name + '/base_key',
                    [int(base[0]), int(base[1])]), dtype=onp.uint32)
            self._base_key = base
        return self._base_key

    def _hyper(self, indices, opt, advance=True):
        """(lrs, wds, ts, rescale) scalar arrays for this step.

        Host numpy, not jnp: they enter the device as arguments of the
        one jitted step call instead of as four eager dispatches."""
        if advance:
            for idx in indices:
                opt._update_count(idx)
        ts = onp.asarray([float(opt._index_update_count.get(idx, 1))
                          for idx in indices], dtype=onp.float32)
        lrs = onp.asarray(opt._get_lrs(list(indices)), dtype=onp.float32)
        wds = onp.asarray(opt._get_wds(list(indices)), dtype=onp.float32)
        return (lrs, wds, ts, onp.float32(opt.rescale_grad))

    def step(self, x, y):
        """One fused train step; returns the (replicated) scalar loss.

        With the guardrail on, also records the step's sentinel event —
        processing at the configured cadence may raise
        :class:`~mxnet_tpu.guardrail.GuardrailTripped`, which guarded
        drivers convert into a rollback (guardrail/rollback.py).

        With resilience attachments (:meth:`attach_preemption` /
        :meth:`attach_watchdog` / :meth:`attach_checkpointing`), every
        call also runs the step-boundary protocol: preemption drain →
        watchdog heartbeat → dispatch → stall check → periodic
        checkpoint."""
        self._boundary_pre()
        xs, ys = self._normalize(x, y)
        tel = _obs.enabled()
        first = self._jitted is None
        t0 = _time.perf_counter() if tel else 0.0
        if first:
            with _obs.span('compile'):
                self._build(xs, ys)
        sig = (tuple(a is None for a in xs), len(ys))
        if sig != self._sig:
            raise ValueError(
                'ParallelTrainer.step called with input signature %r but '
                'the compiled step was built for %r — input/label arity '
                'and None-positions must match the first call' %
                (sig, self._sig))
        xs = [a for a in xs if a is not None]
        opt = self._opt
        indices = list(range(len(self._params)))
        hyper = self._hyper(indices, opt, advance=True)
        # per-step key built on the host (base drawn once from the global
        # chain): [base, base ^ step] is a fresh threefry key per step
        # without an eager random.split dispatch on the device
        key = onp.asarray(
            [self._next_base_key()[0],
             self._base_key[1] ^ onp.uint32(self.num_update + 1)],
            dtype=onp.uint32)
        with _obs.span('train.put_data'):
            xd = tuple(self._put_data(a, sh)
                       for a, sh in zip(xs, self._data_shardings[0]))
            yd = tuple(self._put_data(a, sh)
                       for a, sh in zip(ys, self._data_shardings[1]))
        if self._multiproc and first:
            # the program's operand shapes are GLOBAL; _build only saw
            # this host's local shard — re-record for compiled_step()
            self._abstract_io = (
                tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for a in xd),
                tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for a in yd))
        from .. import profiler as _profiler
        loss = None
        health = None
        with _profiler.op_span('fused_train_step',
                               lambda: loss.block_until_ready()), \
                _obs.span('train.dispatch'):
            if self._guard is None:
                self._param_arrays, self._state_leaves, loss = \
                    self._jitted(key, hyper, self._param_arrays,
                                 self._state_leaves, xd, yd)
            else:
                gin = (onp.float32(self._guard.next_poison()),
                       self._gstate[0], self._gstate[1])
                (self._param_arrays, self._state_leaves, loss,
                 (health, s2, g2)) = self._jitted(
                    key, hyper, gin, self._param_arrays,
                    self._state_leaves, xd, yd)
                self._gstate = (s2, g2)
        self.num_update += 1
        # keep the net's Parameters viewing the live sharded arrays
        for p, w in zip(self._params, self._param_arrays):
            p.data()._data = w
        if tel:
            self._record_step_telemetry(
                first, t0, int(ys[0].shape[0]) if ys else 0)
        if self._guard is not None:
            self._guard.record(self.num_update - 1, health, loss=loss,
                               scale=self._gstate[0])
        with _obs.span('train.boundary'):
            self._boundary_post()
        return NDArray(loss)

    def _record_step_telemetry(self, first, t0, examples, nsteps=1):
        """Per-dispatch telemetry (docs/OBSERVABILITY.md): step/compile
        timing histograms, step/example counters, cursor gauge, and a
        flight-recorder event. Host wall time only — no device sync is
        added, so the dispatch pipeline keeps its depth (the measured
        time is dispatch-to-dispatch; the XPlane trace holds device
        truth). Callers guard on ``observability.enabled()`` so the
        disabled path allocates nothing."""
        dt = _time.perf_counter() - t0
        inst = _obs.trainer_instruments()
        step = self.num_update - nsteps
        if first:
            inst.compile_seconds.observe(dt)
            _obs.record_event('compile', program='fused_step',
                              step=step, seconds=round(dt, 6))
            try:
                from ..config import get as _cfg
                if _cfg('MXNET_TPU_TELEMETRY_HLO'):
                    _obs.trainer_collective_stats(self)
            except Exception:
                pass      # accounting must never fail a training step
        else:
            inst.step_seconds.observe(dt)
        inst.steps.inc(nsteps)
        if examples:
            inst.examples.inc(examples)
        inst.global_step.set(self.num_update)
        _obs.record_event('step', step=step, n=nsteps,
                          seconds=round(dt, 6))

    # -- rollback contract (guardrail/rollback.py) -------------------------

    def snapshot(self):
        """Host capture of every step-evolving piece of trainer state:
        params, optimizer-state leaves, loss-scale state, step/hyper
        counters, and the per-step RNG base key. Feed to
        :meth:`restore` for a bit-exact rewind."""
        if self._jitted is None:
            raise RuntimeError('snapshot() before the step is compiled; '
                               'call build(x, y) (or one step) first')
        state = {
            'num_update': self.num_update,
            # _to_logical: replicated arrays fetch directly; on a
            # multi-process mesh dp-sharded ZeRO leaves are gathered
            # to the replicated layout in one jitted program first
            'params': self._to_logical(self._param_arrays),
            'leaves': self._to_logical(self._state_leaves),
            'base_key': None if self._base_key is None
            else onp.asarray(self._base_key),
            'update_counts': dict(self._opt._index_update_count),
            'opt_num_update': getattr(self._opt, 'num_update', 0),
        }
        if self._gstate is not None:
            state['scale'] = float(self._gstate[0])
            state['good'] = int(self._gstate[1])
        return state

    def restore(self, state):
        """Rewind to a :meth:`snapshot` capture (same built trainer)."""
        if self._jitted is None:
            raise RuntimeError('restore() on an un-built trainer')
        repl, param_sh, leaf_sh = self._shardings[:3]
        self._param_arrays = tuple(
            self._put_full(w, sh)
            for w, sh in zip(state['params'], param_sh))
        self._state_leaves = tuple(
            self._put_full(a, sh)
            for a, sh in zip(state['leaves'], leaf_sh))
        self.num_update = int(state['num_update'])
        self._base_key = None if state.get('base_key') is None \
            else onp.asarray(state['base_key'], dtype=onp.uint32)
        self._opt._index_update_count.clear()
        self._opt._index_update_count.update(state['update_counts'])
        if hasattr(self._opt, 'num_update'):
            self._opt.num_update = state.get('opt_num_update', 0)
        if self._gstate is not None and 'scale' in state:
            self._gstate = (
                self._put_full(onp.float32(state['scale']), repl),
                self._put_full(onp.int32(state['good']), repl))
        for p, w in zip(self._params, self._param_arrays):
            p.data()._data = w

    def compiled_step(self):
        """The compiled single-step executable (lower().compile();
        shapes only — nothing executes, nothing is donated). Exposes
        ``.as_text()`` (optimized HLO) and ``.cost_analysis()``."""
        if self._jitted is None:
            raise RuntimeError('compiled_step() before the step is '
                               'compiled; call build(x, y) first')
        indices = list(range(len(self._params)))
        hyper = self._hyper(indices, self._opt, advance=False)
        key = onp.zeros(2, onp.uint32)
        abstract_xs, abstract_ys = self._abstract_io
        args = [key, hyper]
        if self._guard is not None:
            args.append((onp.float32(0.0), self._gstate[0],
                         self._gstate[1]))
        args += [self._param_arrays, self._state_leaves, abstract_xs,
                 abstract_ys]
        return self._jitted.lower(*args).compile()

    def compiled_text(self):
        """Optimized HLO of the compiled single-step program. Used by
        the bench guard-overhead A/B and the no-host-transfer
        structural tests."""
        return self.compiled_step().as_text()
