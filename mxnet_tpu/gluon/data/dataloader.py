"""DataLoader: mini-batches from a Dataset with multiprocess workers.

Reference parity: python/mxnet/gluon/data/dataloader.py (worker pool,
shared-mem NDArray channel :42-125, default/mp batchify fns).

Worker model (TPU-native analog of the reference's fork + POSIX-shm
NDArray pickling over cpu_shared storage,
cpu_shared_storage_manager.h:52):
  * ``num_workers > 0`` forks worker PROCESSES via the spawn context —
    fork is unsafe once the XLA runtime is live — and ships each
    decoded batch back through ``multiprocessing.shared_memory`` (one
    segment per array, written once by the worker, adopted and
    unlinked by the main process). Only descriptors travel over the
    pipe, so batch bytes are never pickled.
  * workers batchify to host numpy (``default_mp_batchify_fn``); the
    main process does the single host→HBM device put per batch.
  * ``thread_pool=True`` keeps the GIL-releasing ThreadPool fallback
    (cv2/numpy-heavy decode also parallelizes there, without the
    spawn import cost).
"""
from __future__ import annotations

import multiprocessing
import multiprocessing.context
import os
import threading

import numpy as np

from ... import ndarray as nd
from ...ndarray import NDArray
from .sampler import SequentialSampler, RandomSampler, BatchSampler

__all__ = ['DataLoader', 'default_batchify_fn', 'default_mp_batchify_fn']


# ---------------------------------------------------------------------------
# shared-memory transport (worker -> main)
# ---------------------------------------------------------------------------

class _ShmSlot:
    """Descriptor for one array parked in a shared-memory segment."""

    __slots__ = ('name', 'shape', 'dtype')

    def __init__(self, name, shape, dtype):
        self.name, self.shape, self.dtype = name, shape, str(dtype)


def _shm_pack(obj):
    """Recursively move numpy arrays into shared memory, returning a
    descriptor tree (runs in the worker)."""
    if isinstance(obj, np.ndarray) and obj.nbytes:
        from multiprocessing import shared_memory, resource_tracker
        seg = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        view = np.ndarray(obj.shape, obj.dtype, buffer=seg.buf)
        view[...] = obj
        slot = _ShmSlot(seg.name, obj.shape, obj.dtype)
        # ownership transfers to the main process (which unlinks); stop
        # this process's resource tracker from reclaiming it early
        try:
            resource_tracker.unregister(seg._name, 'shared_memory')
        except Exception:
            pass
        seg.close()
        return slot
    if isinstance(obj, (list, tuple)):
        return type(obj)(_shm_pack(o) for o in obj)
    return obj


def _shm_unpack(obj):
    """Adopt a descriptor tree: copy arrays out and unlink the segments
    (runs in the main process)."""
    if isinstance(obj, _ShmSlot):
        from multiprocessing import shared_memory
        seg = shared_memory.SharedMemory(name=obj.name)
        try:
            arr = np.ndarray(obj.shape, np.dtype(obj.dtype),
                             buffer=seg.buf).copy()
        finally:
            seg.close()
            try:
                seg.unlink()
            except FileNotFoundError:
                pass
        return arr
    if isinstance(obj, (list, tuple)):
        return type(obj)(_shm_unpack(o) for o in obj)
    return obj


def default_batchify_fn(data):
    """Stack samples into a batch NDArray (reference: dataloader.py)."""
    if isinstance(data[0], NDArray):
        return nd.concatenate([d.expand_dims(0) for d in data], axis=0) \
            if data[0].ndim > 0 else nd.array([d.asscalar() for d in data])
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return nd.array(data, dtype=data.dtype if data.dtype != np.float64
                    else 'float32')


def default_mp_batchify_fn(data):
    """Worker-side batchify: keep numpy (cheap to pickle); main process
    moves to device."""
    if isinstance(data[0], NDArray):
        return np.stack([d.asnumpy() for d in data])
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_mp_batchify_fn(i) for i in data]
    return np.asarray(data)


def _as_nd(data):
    if isinstance(data, (list, tuple)):
        return [_as_nd(d) for d in data]
    if isinstance(data, np.ndarray):
        return nd.array(data, dtype=data.dtype if data.dtype != np.float64
                        else 'float32')
    return data


_worker_dataset = None

_spawn_env_lock = threading.Lock()


class _CpuSpawnProcess(multiprocessing.context.SpawnProcess):
    """A spawn worker that starts with ``JAX_PLATFORMS=cpu``.

    An accelerator belongs to one process. A worker re-imports the
    user's ``__main__`` and this package before it runs anything of
    ours, so an NDArray touched at module level, in a dataset or in a
    transform would open the parent's chip from the child and fail or
    hang. spawn gives the child the parent's environment as it stands
    at exec time, so the pin is set around ``start()`` — which also
    covers workers the pool respawns later."""

    def start(self):
        with _spawn_env_lock:
            saved = os.environ.get('JAX_PLATFORMS')
            os.environ['JAX_PLATFORMS'] = 'cpu'
            try:
                super().start()
            finally:
                if saved is None:
                    del os.environ['JAX_PLATFORMS']
                else:
                    os.environ['JAX_PLATFORMS'] = saved


class _CpuSpawnContext(multiprocessing.context.SpawnContext):
    Process = _CpuSpawnProcess


def _worker_initializer(dataset):
    """Initialize the dataset once per worker process (fork-shared)."""
    global _worker_dataset
    _worker_dataset = dataset


def _worker_fn(samples, batchify_fn, dataset=None):
    """Worker target: fetch samples and batchify."""
    from ...resilience.policy import inject
    inject('dataloader.worker', ('worker_crash',))
    global _worker_dataset
    ds = dataset if dataset is not None else _worker_dataset
    batch = batchify_fn([ds[i] for i in samples])
    return batch


_warned_device_batch = False


def _host_leaves(obj):
    """Convert NDArray leaves to host numpy (warning once): a custom
    batchify_fn ported from reference code may produce device arrays in
    the spawned worker, but the shm transport assumes numpy — and a
    device put inside a child process wastes a second XLA runtime."""
    global _warned_device_batch
    if isinstance(obj, NDArray):
        if not _warned_device_batch:
            _warned_device_batch = True
            import warnings
            warnings.warn(
                'DataLoader process worker produced a device NDArray batch '
                '(custom batchify_fn?). Converting to host numpy for the '
                'shared-memory channel; return numpy from batchify_fn (see '
                'default_mp_batchify_fn) to avoid a per-worker XLA runtime.')
        return obj.asnumpy()
    if isinstance(obj, (list, tuple)):
        return type(obj)(_host_leaves(o) for o in obj)
    return obj


def _proc_worker_fn(samples, batchify_fn, dataset=None):
    """Process-worker target: batchify to numpy, park the result in
    shared memory, return only descriptors."""
    return _shm_pack(_host_leaves(_worker_fn(samples, batchify_fn, dataset)))


class _MultiWorkerIter:
    """Iterator dispatching index batches to a process pool with
    out-of-order completion + in-order delivery (reference:
    dataloader.py _MultiWorkerIter)."""

    def __init__(self, worker_pool, batchify_fn, batch_sampler,
                 pin_memory=False, prefetch=0, dataset=None, loader=None,
                 use_shm=False, max_restarts=2, task_timeout=300.0):
        # pin the owning DataLoader: if the user iterates a temporary
        # (``for x in DataLoader(...)``) the loader must not be collected
        # mid-epoch — its __del__ terminates the worker pool
        self._loader = loader
        self._worker_pool = worker_pool
        self._batchify_fn = batchify_fn
        self._batch_sampler = batch_sampler
        self._data_buffer = {}
        self._rcvd_idx = 0
        self._sent_idx = 0
        self._iter = iter(self._batch_sampler)
        self._dataset = dataset
        self._use_shm = use_shm
        self._max_restarts = max(0, int(max_restarts))
        self._task_timeout = float(task_timeout or 0)  # 0 disables
        self._abandoned = []   # timed-out tasks pending shm adoption
        for _ in range(prefetch):
            self._push_next()

    def __len__(self):
        return len(self._batch_sampler)

    def _submit(self, samples):
        target = _proc_worker_fn if self._use_shm else _worker_fn
        # process pools ship the dataset once via the initializer; the
        # per-task dataset arg is only for the thread pool
        ds = None if self._use_shm else self._dataset
        return self._worker_pool.apply_async(
            target, (samples, self._batchify_fn, ds))

    def _push_next(self):
        r = next(self._iter, None)
        if r is None:
            return
        # keep the index batch so a crashed worker's task can be
        # resubmitted (crash-restart, docs/RESILIENCE.md)
        self._data_buffer[self._sent_idx] = (r, self._submit(r))
        self._sent_idx += 1

    def __next__(self):
        self._push_next()
        if self._rcvd_idx == self._sent_idx:
            assert not self._data_buffer, 'Data buffer should be empty at this moment'
            raise StopIteration
        assert self._rcvd_idx < self._sent_idx, \
            'rcvd_idx must be smaller than sent_idx'
        assert self._rcvd_idx in self._data_buffer, \
            'fatal error with _push_next, rcvd_idx missing'
        samples, ret = self._data_buffer.pop(self._rcvd_idx)
        batch = self._get_with_restart(samples, ret)
        if self._use_shm:
            batch = _shm_unpack(batch)
        self._rcvd_idx += 1
        return _as_nd(batch)

    def _get_with_restart(self, samples, ret):
        """Fetch one task result, resubmitting the same index batch
        when the worker crashed — a dead decode worker costs one
        warning and a re-run, not the epoch. Raised exceptions cover
        in-process crashes; the get() timeout covers hard process
        death (OOM-kill/segfault), where the pool respawns the worker
        but the in-flight AsyncResult would otherwise never complete.
        Deterministic bugs re-raise after the restart budget so they
        stay visible."""
        import multiprocessing
        attempt = 0
        while True:
            try:
                return ret.get(self._task_timeout) \
                    if self._task_timeout else ret.get()
            except Exception as exc:
                if isinstance(exc, multiprocessing.TimeoutError) and \
                        self._use_shm:
                    # the stalled task may still finish later and park
                    # its batch in shm; keep the result so close() can
                    # adopt-and-unlink instead of leaking the segments
                    self._abandoned.append(ret)
                if attempt >= self._max_restarts:
                    raise
                attempt += 1
                import warnings
                warnings.warn(
                    'DataLoader worker task failed (attempt %d/%d); '
                    'resubmitting the batch to the pool'
                    % (attempt, self._max_restarts))
                ret = self._submit(samples)

    def close(self, drain_timeout=30):
        """Drain in-flight batches so their shared-memory segments get
        unlinked (workers unregistered them from their resource
        tracker, so an abandoned iterator would leak /dev/shm).

        ``drain_timeout`` bounds the per-batch wait; the GC path uses a
        short bound so an abandoned iterator cannot stall interpreter
        shutdown for minutes while the pool finishes prefetched work."""
        while self._use_shm and self._data_buffer:
            _, (_, ret) = self._data_buffer.popitem()
            try:
                _shm_unpack(ret.get(timeout=drain_timeout))
            except Exception:
                pass
        while self._use_shm and self._abandoned:
            try:
                _shm_unpack(self._abandoned.pop().get(
                    timeout=drain_timeout))
            except Exception:
                pass
        self._data_buffer = {}

    def __del__(self):
        # only adopt batches that are (nearly) ready — see close()
        self.close(drain_timeout=1)

    def next(self):
        return self.__next__()

    def __iter__(self):
        return self


class DataLoader:
    """Loads data from a Dataset, returning mini-batches
    (reference: dataloader.py DataLoader)."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=False, device_prefetch=False):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._thread_pool = thread_pool
        # host->device staging on top of the worker-pool decode
        # prefetch: True uses the MXNET_TPU_PREFETCH depth, an int sets
        # it explicitly (docs/PERFORMANCE.md). The workers overlap
        # DECODE with the step; this additionally overlaps the
        # device transfer, so data_wait is a queue pop.
        self._device_prefetch = device_prefetch
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError('batch_size must be specified unless '
                                 'batch_sampler is specified')
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError('shuffle must not be specified if sampler '
                                 'is specified')
            batch_sampler = BatchSampler(
                sampler, batch_size, last_batch if last_batch else 'keep')
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError('batch_size, shuffle, sampler and last_batch '
                             'must not be specified if batch_sampler is '
                             'specified.')
        self._batch_sampler = batch_sampler
        self._num_workers = num_workers if num_workers >= 0 else 0
        self._worker_pool = None
        self._prefetch = max(0, int(prefetch) if prefetch is not None
                             else 2 * self._num_workers)
        if self._num_workers > 0:
            if self._thread_pool:
                # GIL-releasing decode (cv2, numpy) parallelizes on
                # threads without the spawn import cost
                from multiprocessing.pool import ThreadPool
                self._worker_pool = ThreadPool(self._num_workers)
            else:
                # spawn (NOT fork: the XLA runtime is not fork-safe once
                # live); the dataset ships to each worker exactly once
                # via the initializer, batches come back through
                # shared memory (_shm_pack/_shm_unpack).
                # NOTE: spawn requires (a) a picklable dataset — lambdas
                # in transforms fall back to threads below — and (b) an
                # ``if __name__ == '__main__'`` guard in user scripts
                # (Python re-imports __main__ in each worker).
                import pickle as _pickle
                try:
                    # everything that crosses the spawn boundary must
                    # pickle: the dataset (shipped once per worker) AND
                    # a user-supplied batchify_fn (shipped per task)
                    _pickle.dumps(dataset)
                    if batchify_fn is not None:
                        _pickle.dumps(batchify_fn)
                    picklable = True
                except Exception:
                    picklable = False
                ctx = _CpuSpawnContext()
                if picklable:
                    self._worker_pool = ctx.Pool(
                        self._num_workers,
                        initializer=_worker_initializer,
                        initargs=(dataset,))
                else:
                    import warnings
                    warnings.warn(
                        'DataLoader(num_workers=%d): dataset or '
                        'batchify_fn is not picklable (lambda?); falling '
                        'back to the GIL-releasing thread pool. Use named '
                        'functions / picklable callables for process '
                        'workers, and note process workers also require '
                        'an ``if __name__ == "__main__"`` guard in the '
                        'launching script.' % self._num_workers,
                        stacklevel=2)
                    from multiprocessing.pool import ThreadPool
                    self._worker_pool = ThreadPool(self._num_workers)
                    self._thread_pool = True
                # tear the pool down before interpreter shutdown breaks
                # the queue pickler (noisy Pool.__del__ otherwise)
                import atexit
                import weakref
                atexit.register(DataLoader._shutdown_pool,
                                weakref.ref(self))
        if batchify_fn is None:
            if self._num_workers > 0 and not self._thread_pool:
                # workers must batchify to host numpy; the device put
                # happens once per batch in the main process (_as_nd)
                self._batchify_fn = default_mp_batchify_fn
            else:
                self._batchify_fn = default_batchify_fn
        else:
            self._batchify_fn = batchify_fn

    def __iter__(self):
        if self._num_workers == 0:
            def same_process_iter():
                for batch in self._batch_sampler:
                    ret = self._batchify_fn([self._dataset[idx]
                                             for idx in batch])
                    yield _as_nd(ret) if not isinstance(ret, (NDArray, list)) \
                        else ret
            return self._maybe_stage(same_process_iter())
        from ...config import get as _cfg
        return self._maybe_stage(_MultiWorkerIter(
            self._worker_pool, self._batchify_fn, self._batch_sampler,
            pin_memory=self._pin_memory, prefetch=self._prefetch,
            dataset=self._dataset, loader=self,
            use_shm=not self._thread_pool,
            max_restarts=_cfg('MXNET_TPU_WORKER_RESTARTS'),
            task_timeout=_cfg('MXNET_TPU_WORKER_TIMEOUT_S')))

    def _maybe_stage(self, it):
        if not self._device_prefetch:
            return it
        from ...io.staging import DevicePrefetcher
        depth = None if self._device_prefetch is True \
            else int(self._device_prefetch)
        return DevicePrefetcher(it, depth=depth,
                                name='dataloader-prefetch')

    def __len__(self):
        return len(self._batch_sampler)

    @staticmethod
    def _shutdown_pool(ref):
        loader = ref()
        if loader is not None:
            loader.__del__()

    def __del__(self):
        pool, self._worker_pool = self._worker_pool, None
        if pool:
            try:
                pool.terminate()
                pool.join()
            except Exception:
                pass  # interpreter-shutdown races in pool teardown
