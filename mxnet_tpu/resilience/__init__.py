"""Fault-tolerance layer: retry/backoff, timeouts, circuit breaking,
deterministic fault injection, degraded-mode artifacts, and atomic
checkpoint/resume.

The reference got much of this implicitly — the dependency engine
retried lazily-scheduled ops and ps-lite re-registered dead workers.
The JAX-native stack compiles whole programs against one backend, so a
transient device fault surfaces as a raised RuntimeError at whatever
layer touched the backend first. This package makes the recovery paths
explicit and composable (docs/RESILIENCE.md):

  * ``policy``      — Retry / Timeout / CircuitBreaker primitives plus
                      the scripted FaultInjector (``MXNET_TPU_FAULT``).
  * ``device``      — ``acquire_backend()``: backend init under retry,
                      returning a typed BackendStatus instead of letting
                      RuntimeError escape.
  * ``checkpoint``  — atomic (write-temp + fsync + rename) save/resume
                      of parameter/optimizer/step state.
  * ``artifact``    — degraded-mode JSON artifact contract for bench /
                      probe instruments (``"status": "ok" | "degraded"
                      | "unavailable"``, exit 0 on degraded).
  * ``preempt``     — graceful SIGTERM/SIGINT drain: stop at the next
                      step boundary, emergency checkpoint, resumable
                      exit code (75 = EX_TEMPFAIL).
  * ``watchdog``    — per-phase stall budgets for compiled steps /
                      collectives; structured ``mxnet_tpu.stall.v1``
                      artifact + DeviceStallError escalation.
  * ``elastic``     — mesh-shrink resume: re-place checkpointed
                      logical state on fewer devices, preserving the
                      global batch via gradient accumulation.

Dependency-free by design: nothing here imports jax (or any other
mxnet_tpu module) at import time, so the layer stays usable for
diagnosing the very backend failures it guards against.
"""
from __future__ import annotations

from .policy import (Retry, Timeout, Deadline, CircuitBreaker,
                     FaultInjector, get_injector, inject,
                     ResilienceError, RetryExhausted, TimeoutExpired,
                     CircuitOpenError, InjectedFault,
                     DeviceUnavailableError, DeviceStallError,
                     WorkerCrashError, PreemptionSignal, HangError,
                     DeviceLossError, is_transient)
from .device import BackendStatus, acquire_backend
from .checkpoint import (atomic_write_bytes, atomic_replace,
                         save_state, load_state, CheckpointManager,
                         snapshot_gluon, restore_gluon)
from .artifact import (SCHEMA, write_artifact, artifact_record,
                       run_instrument)
from .preempt import Preempted, PreemptionHandler, resumable_exit_code
from .watchdog import STALL_SCHEMA, Watchdog, stall_record
from .elastic import (MeshShrinkError, ElasticPlan, shrink_plan,
                      host_loss_plan, available_devices, mesh_meta)

__all__ = [
    'Retry', 'Timeout', 'Deadline', 'CircuitBreaker', 'FaultInjector',
    'get_injector', 'inject', 'ResilienceError', 'RetryExhausted',
    'TimeoutExpired', 'CircuitOpenError', 'InjectedFault',
    'DeviceUnavailableError', 'DeviceStallError', 'WorkerCrashError',
    'PreemptionSignal', 'HangError', 'DeviceLossError',
    'is_transient', 'BackendStatus', 'acquire_backend',
    'atomic_write_bytes', 'atomic_replace', 'save_state', 'load_state',
    'CheckpointManager', 'snapshot_gluon', 'restore_gluon',
    'SCHEMA', 'write_artifact', 'artifact_record', 'run_instrument',
    'Preempted', 'PreemptionHandler', 'resumable_exit_code',
    'STALL_SCHEMA', 'Watchdog', 'stall_record',
    'MeshShrinkError', 'ElasticPlan', 'shrink_plan', 'host_loss_plan',
    'available_devices', 'mesh_meta',
]
