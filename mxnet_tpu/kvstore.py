"""KVStore: parameter synchronization store.

Reference parity: python/mxnet/kvstore.py (init/push/pull/row_sparse_pull
:116-314, set_gradient_compression :394, set_optimizer :450, _set_updater
:565, _barrier :606) over src/kvstore/ (§2.4: KVStoreLocal, CommCPU/Device/
DeviceTree, KVStoreNCCL, KVStoreDist + ps-lite).

TPU-native design (SURVEY.md §5.8): ALL single-process type strings
('local', 'device', 'device_sync', 'nccl', 'xla') alias one in-process
store — on a TPU there is one logical copy of each array and the
cross-device reduce is a lax.psum inside the compiled step, so the store's
job is aggregation semantics + optimizer hosting, not transport. Multi-host
types ('dist_sync', 'dist_device_sync', 'horovod') allreduce across
jax processes over DCN/ICI via jax collectives; 'dist_async' parameter-server
semantics have no XLA analog and run as sync (documented divergence).
"""
from __future__ import annotations

import pickle

import warnings

from .base import string_types
from . import ndarray as nd
from .ndarray import NDArray
from . import optimizer as opt
from .resilience.policy import (Retry, RetryExhausted, WorkerCrashError,
                                inject, is_transient)

__all__ = ['KVStore', 'KVStoreInitError', 'create']

_KV_FAULTS = ('device_unavailable', 'device_stall')
# the init handshake additionally honors worker_crash: a worker dying
# mid-handshake is recoverable by re-running the join from scratch
# (the restarted-worker rejoin path), unlike a mid-collective death
_KV_INIT_FAULTS = _KV_FAULTS + ('worker_crash',)


class KVStoreInitError(RuntimeError):
    """Distributed store init failed after bounded retries.

    Carries ``attempts`` and ``last_cause`` so launcher logs show a
    one-line diagnosis (coordinator unreachable, N attempts, last
    error) instead of a bare jax.distributed stack trace.
    """

    def __init__(self, kv_type, attempts, last_cause):
        super().__init__(
            'dist kvstore %r init failed after %d attempt(s); the '
            'coordinator is unreachable or the backend initialized '
            'first. Last cause: %s: %s'
            % (kv_type, attempts, type(last_cause).__name__, last_cause))
        self.kv_type = kv_type
        self.attempts = attempts
        self.last_cause = last_cause


def _on_comm_retry(attempt, exc, pause):
    """Telemetry tap for dist-collective retries: retry counter + a
    flight-recorder event (retries are exactly the history a stalled-
    collective post-mortem needs). Runs INSIDE Retry.call's recovery
    loop — a telemetry failure here must never abort the remaining
    retry attempts for the transient error being healed."""
    try:
        from . import observability as _obs
        if _obs.enabled():
            _obs.kv_instruments().retries.inc()
            _obs.record_event('retry', site='kvstore',
                              attempt=int(attempt),
                              error=str(exc)[:160],
                              pause_s=round(float(pause), 3))
    except Exception:
        pass


def _comm_retry():
    """Backoff policy for dist collectives (init/push/pull): transient
    transport errors get bounded retries; deterministic errors propagate.

    Caveat (docs/RESILIENCE.md): a collective retry is only safe when
    every participant fails and retries in lockstep — the common case
    for a slice-wide network outage, where the error surfaces on all
    workers. A partial failure (one worker errors while peers complete)
    cannot be healed by per-process retry; jax collectives give no
    abort-and-rejoin, so that case still ends in the runtime's own
    collective timeout. The deterministic parameters below (no jitter)
    keep retrying workers aligned."""
    return Retry(max_attempts=3, base_delay=1.0, max_delay=30.0,
                 jitter=0.0, predicate=is_transient,
                 on_retry=_on_comm_retry)


def _nbytes(value):
    """Logical payload size of one pushed/pulled NDArray (telemetry)."""
    data = getattr(value, '_data', value)
    nbytes = getattr(data, 'nbytes', None)
    if nbytes is not None:
        return int(nbytes)
    size = getattr(data, 'size', 0)
    itemsize = getattr(getattr(data, 'dtype', None), 'itemsize', 4)
    return int(size) * int(itemsize)


def _ctype_key_value(keys, vals):
    if isinstance(keys, (tuple, list)):
        assert len(keys) == len(vals)
        return list(keys), list(vals)
    # single key: a list value is that key's multi-device value group
    # (reference: kvstore.py _ctype_key_value single-key branch)
    return [keys], [vals]


class KVStore:
    """In-process key-value store with optimizer hosting."""

    def __init__(self, kv_type='local'):
        self._type = kv_type
        self._data = {}
        self._updater = None
        self._compression_params = None
        self._optimizer_states_updater = None

    # -- identity ----------------------------------------------------------
    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        import jax
        return jax.process_index()

    @property
    def num_workers(self):
        import jax
        return jax.process_count()

    # -- core ops ----------------------------------------------------------
    def init(self, key, value):
        """Initialize a key-value pair (single call per key;
        reference: kvstore.py:116)."""
        keys, vals = _ctype_key_value(key, value)
        for k, v in zip(keys, vals):
            if isinstance(v, (list, tuple)):
                v = v[0]
            self._data[k] = v.copy()

    def push(self, key, value, priority=0):
        """Push (accumulate) values (reference: kvstore.py push).

        Multiple device slices for one key are summed (Comm::Reduce parity);
        in dist mode the sum is allreduced across workers.
        """
        keys, vals = _ctype_key_value(key, value)
        from . import observability as _obs
        tel = _obs.kv_instruments() if _obs.enabled() else None
        for k, v in zip(keys, vals):
            if isinstance(v, (list, tuple)):
                merged = v[0]
                for x in v[1:]:
                    merged = merged + x
            else:
                merged = v
            merged = self._compress(k, merged)
            if tel is not None:
                tel.push_bytes.inc(_nbytes(merged))
            merged = self._allreduce(merged)
            if self._updater is not None:
                if k not in self._data:
                    # Training against a silently-created zero weight would
                    # mask a missing init() (reference kvstore errors here).
                    raise KeyError(
                        'push to key %r before init(); call kv.init first' % k)
                self._updater(_key_to_int(k), merged, self._data[k])
            else:
                self._data[k] = merged

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        """Pull values (weights if an updater is installed, else the last
        reduced push) into out (reference: kvstore.py pull)."""
        assert out is not None
        keys, outs = _ctype_key_value(key, out)
        from . import observability as _obs
        tel = _obs.kv_instruments() if _obs.enabled() else None
        for k, o in zip(keys, outs):
            src = self._data[k]
            if tel is not None:
                fanout = len(o) if isinstance(o, (list, tuple)) else 1
                tel.pull_bytes.inc(_nbytes(src) * fanout)
            if isinstance(o, (list, tuple)):
                for oo in o:
                    src.copyto(oo)
            else:
                src.copyto(o)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the rows in row_ids (reference: kvstore.py:230).

        Storage is dense (XLA; SURVEY.md §7 hard part 3) but the
        *contract* holds: rows outside row_ids come back zero, so sparse
        embedding training touches only the looked-up rows."""
        if row_ids is None:
            return self.pull(key, out, priority)
        import jax.numpy as jnp
        keys, outs = _ctype_key_value(key, out)
        rids = row_ids if isinstance(row_ids, (list, tuple)) \
            else [row_ids] * len(keys)
        for k, o, rid in zip(keys, outs, rids):
            src = self._data[k]
            idx = rid._data.astype(jnp.int32) if isinstance(rid, NDArray) \
                else jnp.asarray(rid, jnp.int32)
            mask = jnp.zeros((src.shape[0],), bool).at[idx].set(True)
            rows = jnp.where(mask[(slice(None),) + (None,) *
                                  (src._data.ndim - 1)], src._data, 0)
            targets = o if isinstance(o, (list, tuple)) else [o]
            for oo in targets:
                oo._data = rows.astype(oo._data.dtype)

    # -- distributed reduce ------------------------------------------------
    def _allreduce(self, value):
        if self.num_workers <= 1 or not self._type.startswith(('dist', 'horovod')):
            return value

        def _reduce():
            # scripted-fault hook: lets tests drive the retry path
            # without a real network outage (docs/RESILIENCE.md)
            inject('kvstore.push', _KV_FAULTS)
            from jax.experimental import multihost_utils
            return multihost_utils.process_allgather(value._data)
        arr = _comm_retry().call(_reduce)
        return NDArray(arr.sum(axis=0))

    def _barrier(self):
        """Global barrier across workers (reference: kvstore.py:606)."""
        if self.num_workers > 1:
            def _sync():
                inject('kvstore.pull', _KV_FAULTS)
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices('kvstore_barrier')
            _comm_retry().call(_sync)

    def rejoin(self):
        """Re-run the init/barrier handshake after a worker restart.

        The reference's ps-lite re-registered a dead worker with the
        scheduler transparently; here a restarted worker process calls
        this (or simply ``create()`` again — which takes the same path
        on a worker-crash-shaped init failure) to re-enter the
        ``jax.distributed`` cluster and re-synchronize at a barrier
        before touching any collective. Store contents are untouched:
        the restarted worker re-pulls weights through the normal
        ``pull`` path after the barrier."""
        if self._type.startswith(('dist', 'horovod')):
            _join_distributed(self._type, rejoin=True)
            self._barrier()
        from . import observability as _obs
        if _obs.enabled():
            _obs.kv_instruments().rejoins.inc()
            _obs.dist_instruments().rejoins.inc()
            _obs.record_event('kv_rejoin', kv_type=self._type)
            _obs.record_event('dist_rejoin', kv_type=self._type)
        return self

    # -- optimizer hosting -------------------------------------------------
    def set_optimizer(self, optimizer):
        """Run this optimizer inside the store (server-side in the
        reference: kvstore.py:450 pickles it to PS servers; here the store
        is in-process so it simply installs an Updater)."""
        self._set_updater(opt.get_updater(optimizer))

    def _set_updater(self, updater):
        self._updater = updater

    def set_gradient_compression(self, compression_params):
        """Enable 2-bit gradient compression with error feedback
        (reference: src/kvstore/gradient_compression.cc). Each pushed
        gradient is quantized to {-threshold, 0, +threshold} after adding
        the residual from previous rounds; the residual keeps what the
        quantizer dropped, so updates stay unbiased over time."""
        params = dict(compression_params)
        ctype = params.get('type', 'none')
        if ctype not in ('none', '2bit'):
            raise ValueError('unsupported gradient compression type %r'
                             % ctype)
        self._compression_params = params
        self._residuals = {}

    def _compress(self, key, grad):
        params = getattr(self, '_compression_params', None)
        if not params or params.get('type', 'none') == 'none':
            return grad
        import jax.numpy as jnp
        thr = float(params.get('threshold', 0.5))
        res = self._residuals.get(key)
        acc = grad._data + (res if res is not None else 0)
        q = jnp.where(acc >= thr, thr,
                      jnp.where(acc <= -thr, -thr, 0.0)).astype(acc.dtype)
        self._residuals[key] = acc - q
        return NDArray(q)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, 'Cannot save states for distributed training'
        with open(fname, 'wb') as fout:
            fout.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        assert self._updater is not None, 'Cannot load states for distributed training'
        with open(fname, 'rb') as f:
            self._updater.set_states(f.read())


def _key_to_int(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k


_SINGLE_TYPES = ('local', 'local_allreduce_cpu', 'local_allreduce_device',
                 'device', 'device_sync', 'nccl', 'xla')
_DIST_TYPES = ('dist_sync', 'dist_device_sync', 'dist_async',
               'dist_sync_device', 'horovod')


def _join_distributed(kv_type, rejoin=False):
    """Run the dist join handshake under bounded retries.

    A worker-crash-shaped failure (the worker itself died
    mid-handshake, not the coordinator) is handled by resetting the
    join state and re-running the handshake once from scratch — the
    restarted-worker rejoin path. Anything else that exhausts the
    retries raises the typed :class:`KVStoreInitError`.
    """
    from . import _dist_init

    def _join():
        inject('kvstore.init', _KV_INIT_FAULTS)
        _dist_init.ensure_distributed()

    if rejoin:
        # a restarted worker's previous join state is void — re-run the
        # handshake from scratch (ensure_distributed is idempotent for
        # a live cluster membership, so this is safe when nothing died)
        _dist_init._initialized = False
    try:
        _comm_retry().call(_join)
    except RetryExhausted as exc:
        if isinstance(exc.last_error, WorkerCrashError) and not rejoin:
            warnings.warn(
                'dist worker died during the %r init handshake (%s); '
                're-running the join from scratch (worker rejoin) '
                'instead of failing with KVStoreInitError'
                % (kv_type, exc.last_error))
            return _join_distributed(kv_type, rejoin=True)
        raise KVStoreInitError(kv_type, exc.attempts, exc.last_error)


def create(name='local'):
    """Create a KVStore by type string (reference: src/kvstore/kvstore.cc:40).

    All single-process types alias the mesh-collective store; dist types
    join the multi-host runtime (launcher env -> jax.distributed) and
    enable the cross-process allreduce. 'dist_async' runs synchronously
    (documented divergence — no parameter server on TPU). A worker that
    died and restarted rejoins through the same call: a worker-crash
    failure during the handshake re-runs the join instead of raising
    :class:`KVStoreInitError` (docs/RESILIENCE.md).
    """
    if not isinstance(name, string_types):
        raise TypeError('name must be a string')
    if name.lower() not in _SINGLE_TYPES + _DIST_TYPES:
        raise ValueError('Unknown KVStore type %s' % name)
    if name.lower() in _DIST_TYPES:
        _join_distributed(name.lower())
    return KVStore(name.lower())
