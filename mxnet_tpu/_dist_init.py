"""Multi-host runtime join — must run before ANY jax backend touch, so
this module has no package dependencies and is imported first by
mxnet_tpu/__init__.py (reference analog: kvstore_dist.h PS connect at
van startup, driven by the DMLC_* env that tools/launch.py exports).

The higher-level runtime (mesh construction across processes, named
barriers, heartbeats, elastic host loss) lives in :mod:`mxnet_tpu.dist`
(docs/DISTRIBUTED.md); this module owns only the one thing that must
happen pre-backend: ``jax.distributed.initialize``.

Knobs (read straight from the environment — the config registry is not
importable this early):

  * ``MXNET_TPU_DIST_INIT_TIMEOUT_S`` — join handshake budget
    (default 300 s). A missing/unreachable coordinator surfaces as a
    typed :class:`DistInitError` when it expires instead of the
    indefinite block ``jax.distributed.initialize`` defaults to.
"""
from __future__ import annotations

import os
import warnings

_initialized = False
# (process_id, process_count) cached at join so later callers —
# including jax-free ones like the flight recorder's rank-suffixed
# dump path — never have to touch a backend to learn who they are
_info = None

_DEFAULT_INIT_TIMEOUT_S = 300.0


class DistInitError(RuntimeError):
    """The multi-host join handshake failed or timed out.

    Carries ``coordinator`` and ``timeout_s`` so launcher logs show a
    one-line diagnosis (which address, how long we waited) instead of a
    bare grpc DEADLINE_EXCEEDED stack."""

    def __init__(self, message, coordinator=None, timeout_s=None):
        super().__init__(message)
        self.coordinator = coordinator
        self.timeout_s = timeout_s


def _init_timeout_s():
    raw = os.environ.get('MXNET_TPU_DIST_INIT_TIMEOUT_S')
    if not raw:
        return _DEFAULT_INIT_TIMEOUT_S
    try:
        return float(raw)
    except ValueError:
        warnings.warn('ignoring malformed MXNET_TPU_DIST_INIT_TIMEOUT_S'
                      ' (%r)' % raw)
        return _DEFAULT_INIT_TIMEOUT_S


def _env_request():
    """(coordinator, num_workers, worker_id) from the launcher env, or
    None when not requested / malformed (malformed warns, never breaks
    plain `import mxnet_tpu`)."""
    role = os.environ.get('DMLC_ROLE')
    if role not in (None, '', 'worker'):
        # the reference tracker also spawns scheduler/server roles; the
        # TPU runtime has no parameter server, so those processes must
        # NOT join the worker cluster (a scheduler mis-joined as a
        # worker shifts every real worker's rank and hangs the join)
        return None
    uri = os.environ.get('DMLC_PS_ROOT_URI')
    raw_n = os.environ.get('DMLC_NUM_WORKER', '1')
    try:
        nworker = int(raw_n)
        wid = int(os.environ.get('DMLC_WORKER_ID', '0'))
    except ValueError:
        warnings.warn('ignoring malformed DMLC_NUM_WORKER/DMLC_WORKER_ID '
                      '(%r / %r)' % (raw_n,
                                     os.environ.get('DMLC_WORKER_ID')))
        return None
    if not uri or nworker <= 1:
        return None
    port = os.environ.get('DMLC_PS_ROOT_PORT', '9091')
    return '%s:%s' % (uri, port), nworker, wid


def is_initialized():
    """True once this process joined (or confirmed membership in) a
    multi-process jax.distributed runtime via :func:`ensure_distributed`."""
    return _initialized


def process_info():
    """``(process_id, process_count)`` without touching a jax backend.

    After a join the values come from the live runtime; before one (or
    in a plain single-process run) they come from the launcher env —
    so observability paths can stamp artifacts with the rank even when
    jax itself is the thing that crashed."""
    if _info is not None:
        return _info
    req = _env_request()
    if req is not None:
        _coord, nworker, wid = req
        return (wid, nworker)
    return (0, 1)


def _await_coordinator(coordinator, wid, timeout_s):
    """Typed pre-flight: block until the coordinator's TCP port
    accepts, or raise :class:`DistInitError` at the timeout.

    Needed because ``jax.distributed.initialize`` does not raise on a
    connect timeout — the XLA client LogFatal-aborts the process
    (client.h "Terminating process...") — so the only way to surface a
    missing coordinator as a typed Python error is to probe before
    handing control to it. Worker 0 hosts the service itself and skips
    the probe."""
    if wid == 0:
        return
    import socket
    import time
    host, _, port = coordinator.rpartition(':')
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            socket.create_connection((host, int(port)),
                                     timeout=1.0).close()
            return
        except OSError as exc:
            last = exc
            time.sleep(0.25)
    raise DistInitError(
        'coordinator %s not reachable within %.0fs '
        '(MXNET_TPU_DIST_INIT_TIMEOUT_S): is worker 0 running? '
        'Last error: %s' % (coordinator, timeout_s, last),
        coordinator=coordinator, timeout_s=timeout_s)


def _enable_cpu_collectives():
    """Select the Gloo cross-process collectives for the CPU client.

    Without this a multi-process CPU run joins fine but the first
    collective dies with "Multiprocess computations aren't implemented
    on the CPU backend" — the Gloo layer must be picked before the
    backend client is created. Harmless on TPU (the TPU client ignores
    the CPU knob)."""
    import jax
    jax.config.update('jax_cpu_collectives_implementation', 'gloo')


def _initialize(timeout_s, **kwargs):
    import jax
    jax.distributed.initialize(
        initialization_timeout=int(max(1.0, timeout_s)), **kwargs)


def _record_info():
    global _info
    import jax
    _info = (int(jax.process_index()), int(jax.process_count()))


def ensure_distributed():
    """Idempotent: join jax.distributed per the launcher env.

    DMLC_PS_ROOT_URI/PORT + DMLC_NUM_WORKER + DMLC_WORKER_ID (reference
    contract) map to coordinator/num_processes/process_id; native
    JAX_COORDINATOR_ADDRESS env is honored directly. A requested
    multi-worker join that cannot happen (the JAX backend was already
    initialized) is an ERROR — degrading to single-process would
    silently drop the cross-worker allreduce. A join that exceeds
    ``MXNET_TPU_DIST_INIT_TIMEOUT_S`` raises :class:`DistInitError`."""
    global _initialized
    if _initialized:
        return
    req = _env_request()
    timeout_s = _init_timeout_s()
    if req is not None:
        coordinator, nworker, wid = req
        import time as _time
        t0 = _time.monotonic()
        _await_coordinator(coordinator, wid, timeout_s)
        # the probe consumed part of the budget; the handshake gets
        # the REMAINDER so the end-to-end join never exceeds the knob
        remaining = max(1.0, timeout_s - (_time.monotonic() - t0))
        import jax
        _enable_cpu_collectives()
        try:
            _initialize(remaining, coordinator_address=coordinator,
                        num_processes=nworker, process_id=wid)
        except RuntimeError as e:
            if jax.process_count() >= nworker:
                pass  # already joined (re-import after initialize)
            elif 'DEADLINE_EXCEEDED' in str(e) or 'timed out' in str(e) \
                    or 'timeout' in str(e).lower():
                raise DistInitError(
                    'multi-worker join (DMLC_NUM_WORKER=%d, worker %d) '
                    'timed out after %.0fs waiting for coordinator %s '
                    '(MXNET_TPU_DIST_INIT_TIMEOUT_S). Is worker 0 '
                    'running and reachable? Cause: %s'
                    % (nworker, wid, timeout_s, coordinator, e),
                    coordinator=coordinator, timeout_s=timeout_s)
            else:
                raise DistInitError(
                    'multi-worker launch requested (DMLC_NUM_WORKER=%d) '
                    'but jax.distributed.initialize failed: %s. Import '
                    'mxnet_tpu (or call jax.distributed.initialize) '
                    'before any other JAX backend use.' % (nworker, e),
                    coordinator=coordinator, timeout_s=timeout_s)
        if jax.process_count() < nworker:
            # initialize() can "succeed" without taking effect when a
            # backend (e.g. an eagerly-registered accelerator plugin)
            # initialized first — fail LOUDLY instead of silently
            # dropping the cross-worker allreduce
            raise DistInitError(
                'multi-worker join requested (DMLC_NUM_WORKER=%d) but '
                'jax.process_count() is still %d: a JAX backend '
                'initialized before the distributed client. Pin the '
                'platform (JAX_PLATFORMS / jax.config.update) before '
                'importing mxnet_tpu in worker processes.'
                % (nworker, jax.process_count()),
                coordinator=coordinator, timeout_s=timeout_s)
        _record_info()
        _initialized = True
    elif os.environ.get('JAX_COORDINATOR_ADDRESS'):
        import jax
        _enable_cpu_collectives()
        try:
            _initialize(timeout_s)
        except RuntimeError as e:
            if jax.process_count() > 1:
                pass              # already joined
            elif 'DEADLINE_EXCEEDED' in str(e) or \
                    'timeout' in str(e).lower():
                raise DistInitError(
                    'join via JAX_COORDINATOR_ADDRESS=%s timed out '
                    'after %.0fs: %s'
                    % (os.environ['JAX_COORDINATOR_ADDRESS'],
                       timeout_s, e),
                    coordinator=os.environ['JAX_COORDINATOR_ADDRESS'],
                    timeout_s=timeout_s)
            else:
                raise
        _record_info()
        _initialized = True
