"""Degraded-mode artifact contract for bench/probe instruments.

Every instrument run — healthy, degraded, or facing a dead backend —
produces the SAME JSON shape and exits 0, so snapshot automation
records a data point instead of a traceback. Only a non-transient
error (a real bug, a refused compile) propagates with a non-zero exit.

Artifact schema (docs/RESILIENCE.md):

    {
      "schema":  "mxnet_tpu.instrument.v2",
      "name":    "<instrument>",
      "status":  "ok" | "degraded" | "unavailable",
      "backend": {state, platform, device_kind, device_count,
                  attempts, error},
      "resumable": {preempted, reason, exit_code},
      "error":   null | "<one-line cause>",
      "payload": null | <instrument-specific JSON>
    }

``status`` semantics: ok = accelerator measured at full fidelity;
degraded = the instrument ran but its numbers are not claims (CPU
fallback, partial failure); unavailable = no backend, payload null.

``resumable`` (v2) records the preemption outcome: an instrument cut
short by SIGTERM reports ``preempted: true`` and the resumable rc it
exits with (``MXNET_TPU_PREEMPT_EXIT_CODE``) — the supervising
launcher restarts it; a normal run reports ``preempted: false`` and
``exit_code: 0``.
"""
from __future__ import annotations

import json

from .checkpoint import atomic_write_bytes
from .device import acquire_backend
from .policy import InjectedFault, is_transient

__all__ = ['SCHEMA', 'artifact_record', 'write_artifact',
           'run_instrument']

SCHEMA = 'mxnet_tpu.instrument.v2'


def _resumable_record(handler=None):
    """Fixed-shape preemption outcome (same keys in every run)."""
    if handler is not None and handler.stop_requested:
        return {'preempted': True, 'reason': handler.reason,
                'exit_code': handler.exit_code}
    return {'preempted': False, 'reason': None, 'exit_code': 0}


def artifact_record(name, status, backend=None, error=None,
                    payload=None, preempt=None):
    """Build the fixed-shape artifact dict (every key always present).

    ``preempt`` is an optional PreemptionHandler whose drain state
    fills the ``resumable`` record."""
    assert status in ('ok', 'degraded', 'unavailable'), status
    return {
        'schema': SCHEMA,
        'name': name,
        'status': status,
        'backend': backend.as_dict() if hasattr(backend, 'as_dict')
        else (backend or {'state': 'unavailable', 'platform': None,
                          'device_kind': None, 'device_count': 0,
                          'attempts': 0, 'error': error}),
        'resumable': _resumable_record(preempt),
        'error': error,
        'payload': payload,
    }


def write_artifact(path, record):
    """Atomically write the artifact JSON (a torn artifact would be as
    useless as the crash it replaces)."""
    atomic_write_bytes(
        path, (json.dumps(record, indent=1, sort_keys=True,
                          default=str) + '\n').encode())
    return record


def run_instrument(name, run, out=None):
    """Drive one instrument under the degraded-mode contract.

    ``run(status)`` receives the :class:`BackendStatus` and returns a
    JSON-serializable payload (or None). Returns a process exit code:
    0 for ok/degraded/unavailable, the resumable rc when the run was
    preempted (SIGTERM drain — the artifact's ``resumable`` record
    says so), non-zero only when ``run`` raised a non-transient
    (bug-shaped) error — which is re-raised, so the traceback stays
    visible.
    """
    from .preempt import Preempted, PreemptionHandler
    out = out or ('%s.json' % name.upper())
    handler = PreemptionHandler().install()
    try:
        status = acquire_backend()
        if not status.usable:
            print('%s: backend unavailable after %d attempt(s): %s — '
                  'writing degraded artifact to %s'
                  % (name, status.attempts, status.error, out),
                  flush=True)
            write_artifact(out, artifact_record(
                name, 'unavailable', backend=status,
                error=status.error, preempt=handler))
            return 0

        verdict = 'ok' if status.state == 'tpu' else 'degraded'
        error = status.error
        payload = None
        try:
            payload = run(status)
        except Preempted as exc:
            # run() drove its own PreemptionHandler (Module.fit /
            # ParallelTrainer attachment): mirror the stop into this
            # handler so the artifact's resumable record and the
            # returned rc reflect the preemption
            handler.request_stop(exc.reason or str(exc))
            verdict = 'degraded'
            error = str(exc)
            print('%s: preempted mid-run (%s) — recording resumable '
                  'artifact' % (name, error), flush=True)
        except Exception as exc:
            if not (isinstance(exc, InjectedFault) or
                    is_transient(exc)):
                # real bug: record it, then let the traceback escape
                write_artifact(out, artifact_record(
                    name, 'degraded', backend=status,
                    error='%s: %s' % (type(exc).__name__, exc),
                    preempt=handler))
                raise
            verdict = 'degraded'
            error = '%s: %s' % (type(exc).__name__, exc)
            print('%s: transient failure mid-run (%s) — recording '
                  'degraded artifact' % (name, error), flush=True)
        if handler.stop_requested:
            verdict = 'degraded'
        write_artifact(out, artifact_record(
            name, verdict, backend=status, error=error,
            payload=payload, preempt=handler))
        print('%s: status=%s artifact=%s' % (name, verdict, out),
              flush=True)
        return handler.exit_code if handler.stop_requested else 0
    finally:
        handler.uninstall()
