"""Stall watchdog: detect hung compiled steps and collectives.

A hung collective is the nastiest TPU failure mode: the compiled step
blocks inside the runtime forever, no exception, no progress, the job
burns budget silently until an external timeout kills it with zero
diagnostics. This watchdog makes the stall a *structured, budgeted*
event instead:

  * drivers :meth:`Watchdog.beat` at every step boundary (phase-tagged:
    ``compile`` gets a much larger budget than ``step`` — first-program
    XLA compiles legitimately take minutes);
  * :meth:`Watchdog.check` compares the heartbeat age against the
    current phase's stall budget (``MXNET_TPU_WATCHDOG_*_S`` knobs);
    a breach writes the structured stall artifact
    (``mxnet_tpu.stall.v1``: phase, step, waited/budget seconds, and a
    stack dump of every live thread) and raises
    :class:`~.policy.DeviceStallError` — which ``is_transient`` and
    therefore flows into the existing degraded-mode path
    (bench/instrument artifacts record ``status: degraded`` and exit 0
    instead of hanging until an opaque external kill);
  * :meth:`Watchdog.start` optionally runs the same check on a daemon
    thread (for drivers blocked *inside* the runtime — the thread
    cannot raise into the blocked caller, so it writes the artifact,
    logs, and calls ``on_stall``).

Deterministic testing: the scripted fault kind ``hang``
(``MXNET_TPU_FAULT=hang@train.step.3:1``) makes :meth:`beat` at step 3
age the heartbeat past the budget instead of refreshing it — the
detection, artifact, and escalation paths run on CPU with an untouched
wall clock (tools/fault_smoke.py, tests/test_elastic.py).

Lock hierarchy (enforced by ``mxnet_tpu.analysis.locklint``): ONE lock
— ``self._lock`` — guarding only the heartbeat/phase/step fields.
Everything that can run foreign code stays OUTSIDE it: the fault
injector, the ``on_stall`` user callback, artifact writes, and every
flight-recorder/metrics emit. Methods snapshot the fields they need
under the lock and act on the copies.
"""
from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
import traceback

from .policy import HangError, DeviceStallError, inject

__all__ = ['STALL_SCHEMA', 'Watchdog', 'stall_record']

STALL_SCHEMA = 'mxnet_tpu.stall.v1'

# phase -> config knob with its default stall budget (seconds)
_BUDGET_KNOBS = {
    'compile': ('MXNET_TPU_WATCHDOG_COMPILE_S', 1800.0),
    'step': ('MXNET_TPU_WATCHDOG_STEP_S', 300.0),
    'collective': ('MXNET_TPU_WATCHDOG_COLLECTIVE_S', 600.0),
}


def _knob(name, default):
    try:
        from ..config import get as _cfg
        v = _cfg(name)
        return default if v is None else float(v)
    except (ImportError, KeyError):
        return default


def _thread_stacks():
    """One formatted stack per live thread — the diagnostic a hung
    collective otherwise takes a gdb session to produce."""
    names = {t.ident: t.name for t in threading.enumerate()}
    stacks = {}
    for ident, frame in sys._current_frames().items():
        stacks[names.get(ident, 'thread-%d' % ident)] = \
            ''.join(traceback.format_stack(frame))
    return stacks


def stall_record(phase, step, waited_s, budget_s, name='train'):
    """The structured stall artifact payload (schema
    ``mxnet_tpu.stall.v1``; every key always present)."""
    return {
        'schema': STALL_SCHEMA,
        'name': name,
        'phase': phase,
        'step': None if step is None else int(step),
        'waited_s': round(float(waited_s), 3),
        'budget_s': round(float(budget_s), 3),
        'pid': os.getpid(),
        'thread_stacks': _thread_stacks(),
    }


class Watchdog:
    """Heartbeat-vs-budget stall detector for one training process.

    ``budgets`` overrides the per-phase stall budgets (seconds); the
    defaults come from the ``MXNET_TPU_WATCHDOG_*_S`` knobs. ``clock``
    is injectable so the budget math is testable with a fake clock and
    zero real waiting.
    """

    def __init__(self, budgets=None, artifact_path=None, name='train',
                 clock=time.monotonic, injector=None, on_stall=None,
                 poll_s=None, site='train.step'):
        self.budgets = {ph: _knob(*kn) for ph, kn in
                        _BUDGET_KNOBS.items()}
        self.budgets.update(budgets or {})
        self.site = site        # fault-injection site beats fire at
                                # ('serving.infer' for the inference
                                # engine, docs/SERVING.md)
        self.artifact_path = artifact_path or os.path.join(
            os.getcwd(), 'STALL.json')
        self.name = name
        self._clock = clock
        self._injector = injector
        self._on_stall = on_stall
        self._poll_s = poll_s
        self._lock = threading.Lock()
        self._phase = 'compile'     # first beat covers the first build
        self._step = None
        self._last = None           # None = not armed yet
        self._stop = threading.Event()
        self._thread = None
        self.last_record = None

    # -- heartbeat ---------------------------------------------------------

    def budget_for(self, phase):
        return float(self.budgets.get(phase,
                                      self.budgets.get('step', 300.0)))

    def beat(self, step=None, phase=None):
        """Refresh the heartbeat at a step boundary.

        A scripted ``hang`` fault for this site/step does the opposite:
        it ages the heartbeat one full budget into the past, simulating
        a step that stopped making progress — the next :meth:`check`
        (or the monitor thread) then takes the real detection path.
        """
        now = self._clock()
        # the injector is callback machinery (module lock hierarchy):
        # fire it before taking the lock, fold the verdict in after
        hang = False
        try:
            inject(self.site, ('hang',), injector=self._injector,
                   step=step)
        except HangError:
            hang = True
        with self._lock:
            if phase is not None:
                self._phase = phase
            cur_phase = self._phase
            self._step = step
            self._last = (now - self.budget_for(cur_phase) - 1.0) \
                if hang else now
        if not hang:
            self._telemetry_beat(step, cur_phase)

    def phase(self, phase):
        """Switch phase (``compile`` / ``step`` / ``collective``) and
        refresh the heartbeat under the new budget."""
        with self._lock:
            step = self._step
        self.beat(step=step, phase=phase)

    # -- detection ---------------------------------------------------------

    def _telemetry_beat(self, step, phase):
        """Heartbeat telemetry (lazy import: this layer stays jax-free):
        age gauge back to zero + a flight-recorder heartbeat event, so
        a post-stall dump shows exactly where the beats stopped. The
        phase arrives as the caller's locked snapshot — this runs
        outside the lock and must not re-read shared fields."""
        try:
            from .. import observability as _obs
            if _obs.enabled():
                _obs.trainer_instruments().heartbeat_age.set(0.0)
                _obs.record_event('watchdog_heartbeat', step=step,
                                  phase=phase)
        except Exception:
            pass

    def stalled(self):
        """(waited_s, budget_s, phase, step) when the heartbeat is
        older than the phase budget, else None."""
        with self._lock:
            if self._last is None:
                return None
            waited = self._clock() - self._last
            budget = self.budget_for(self._phase)
            phase, step = self._phase, self._step
        try:        # heartbeat-age gauge (docs/OBSERVABILITY.md)
            from .. import observability as _obs
            if _obs.enabled():
                _obs.trainer_instruments().heartbeat_age.set(waited)
        except Exception:
            pass
        if waited <= budget:
            return None
        return waited, budget, phase, step

    def check(self):
        """Raise :class:`DeviceStallError` (after writing the stall
        artifact) when the current phase overran its budget; no-op
        otherwise. Drivers call this right after the blocking call a
        :meth:`beat` preceded."""
        hit = self.stalled()
        if hit is None:
            return
        waited, budget, phase, step = hit
        self._emit(waited, budget, phase, step)
        raise DeviceStallError(
            'device_stall', 'watchdog',
            'watchdog: %s phase stalled %.1fs (budget %.1fs) at step '
            '%s — stall artifact at %s'
            % (phase, waited, budget, step, self.artifact_path))

    def _emit(self, waited, budget, phase, step):
        self.last_record = stall_record(phase, step, waited, budget,
                                        name=self.name)
        try:
            from .checkpoint import atomic_write_bytes
            atomic_write_bytes(
                self.artifact_path,
                (json.dumps(self.last_record, indent=1, sort_keys=True)
                 + '\n').encode())
        except OSError as exc:   # diagnostics must not mask the stall
            logging.error('watchdog: could not write stall artifact '
                          '%s: %s', self.artifact_path, exc)
        try:
            # flight-recorder escalation (docs/OBSERVABILITY.md): the
            # stall event lands in the ring, then the whole ring dumps
            # as a mxnet_tpu.flight.v1 artifact — the last N seconds of
            # run history next to the stall record
            from .. import observability as _obs
            _obs.record_event('stall', phase=phase,
                              step=None if step is None else int(step),
                              waited_s=round(float(waited), 3),
                              budget_s=round(float(budget), 3))
            _obs.flight_dump(reason='stall')
        except Exception:
            pass      # telemetry must never mask the stall itself
        logging.error('watchdog: %s phase stalled %.1fs (budget %.1fs) '
                      'at step %s; artifact: %s', phase, waited, budget,
                      step, self.artifact_path)

    # -- background monitor ------------------------------------------------

    def start(self):
        """Run the stall check on a daemon thread (for drivers blocked
        inside the runtime). The thread cannot raise into the blocked
        caller: it writes the artifact, logs, calls ``on_stall(record)``
        once, and keeps watching (a later beat re-arms it)."""
        if self._thread is not None:
            return self
        poll = self._poll_s if self._poll_s is not None \
            else _knob('MXNET_TPU_WATCHDOG_POLL_S', 10.0)
        self._stop.clear()

        def monitor():
            fired_at = None
            while not self._stop.wait(poll):
                hit = self.stalled()
                if hit is None:
                    fired_at = None
                    continue
                waited, budget, phase, step = hit
                with self._lock:
                    beat_id = self._last
                if fired_at == beat_id:
                    continue          # one artifact per distinct stall
                fired_at = beat_id
                self._emit(waited, budget, phase, step)
                if self._on_stall is not None:
                    try:
                        self._on_stall(self.last_record)
                    except Exception:
                        logging.exception('watchdog on_stall callback '
                                          'failed')

        self._thread = threading.Thread(target=monitor, daemon=True,
                                        name='mxnet-tpu-watchdog')
        self._thread.start()
        return self

    def stop(self):
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=5.0)
        self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
