"""Host spans: one named interval of host work, written where each
reader looks (docs/OBSERVABILITY.md "Step-phase spans").

Attribution of step time is the visibility problem: a slow run looks
identical from the outside whether the input pipeline is starving the
chip, the compiled step regressed, checkpointing is blocking the loop,
or the decode scheduler's own Python is holding the device back. A
span times one occurrence of one phase and lands it in up to four
places:

  * the profiler's clock, always: a ``jax.profiler.TraceAnnotation`` of
    the span's own name, so any ``jax.profiler.start_trace`` session
    (the benchmark's, ``chip_smoke.py``'s, an operator's) shows the
    phase on the host thread beside the device's operations. With no
    session running the annotation is one atomic flag read;
  * the ``mxnet_tpu_phase_seconds`` histogram family (labeled by
    phase) when telemetry is enabled, so a run's phase split is
    readable from any exporter with zero trace tooling;
  * the legacy ``mx.profiler`` chrome trace as a ``phase:<name>`` row
    when that profiler is running;
  * the request-trace span buffer when a trace context is bound to
    this thread (``trace.activate``).

With telemetry off, ``mx.profiler`` idle and no context bound, a span
is the annotation and three flag reads.
"""
from __future__ import annotations

import time

from . import metrics as _metrics
from . import trace as _trace

__all__ = ['PHASES', 'span', 'phase_histogram']

# The names in use (docs/OBSERVABILITY.md lists who reads each). Any
# name works; these are the ones the package opens itself.
PHASES = (
    # training loops (Module.fit, ParallelTrainer)
    'data_wait', 'step', 'sync', 'checkpoint', 'compile',
    'train.put_data', 'train.dispatch', 'train.boundary',
    # the decode scheduler's worker thread (serving/decode/engine.py)
    'eng.wait_work', 'eng.tick', 'eng.tick.retire', 'eng.tick.migrate',
    'eng.tick.admit', 'eng.tick.prefix_register',
    'eng.tick.page_faults', 'eng.tick.release_window',
    'eng.tick.build_inputs',
    'eng.tick.dispatch', 'eng.tick.read_tokens', 'eng.tick.after_call',
    'eng.tick.emit', 'eng.tick.telemetry',
)

_hist_family = None
_children = {}
_annotation = None      # jax.profiler.TraceAnnotation; False = no jax


def phase_histogram(phase):
    """The histogram child for one phase (cached; hot paths hold it)."""
    global _hist_family
    child = _children.get(phase)
    if child is None:
        if _hist_family is None:
            _hist_family = _metrics.histogram(
                'mxnet_tpu_phase_seconds',
                help='wall seconds per step phase', labels=('phase',))
        child = _hist_family.labels(phase=phase)
        _children[phase] = child
    return child


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, imported on the first span so
    the package stays import-light; False where jax cannot be
    imported (the span goes on without the profiler's clock)."""
    global _annotation
    if _annotation is None:
        try:
            import jax
            _annotation = jax.profiler.TraceAnnotation
        except ImportError:
            _annotation = False
    return _annotation


def _legacy_profiler():
    """``mx.profiler`` when it is running, else None."""
    try:
        from .. import profiler as _profiler
    except ImportError:
        return None
    return _profiler if _profiler.is_running() else None


class span:
    """Context manager timing one phase occurrence.

        with span('data_wait'):
            batch = next(feed)
        with span('eng.tick', step=n, active=len(active)):
            ...

    Keyword arguments go to the profiler annotation only (they show as
    the event's stats in the trace viewer); the histogram's one label
    is the phase."""

    __slots__ = ('phase', '_args', '_ann', '_t0', '_w0', '_prof')

    def __init__(self, phase, **args):
        self.phase = phase
        self._args = args
        self._ann = None
        self._t0 = None
        self._w0 = None
        self._prof = None

    def __enter__(self):
        cls = _annotation_class()
        if cls:
            self._ann = cls(self.phase, **self._args)
            self._ann.__enter__()
        self._prof = _legacy_profiler()
        tracing = _trace.current() is not None
        if _metrics.enabled() or self._prof is not None or tracing:
            self._t0 = time.perf_counter()
            if tracing:
                self._w0 = time.time()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if self._t0 is None:
            return
        t1 = time.perf_counter()
        if self._prof is not None:
            self._prof.record_span('phase:%s' % self.phase, self._t0, t1)
            self._prof = None
        if _metrics.enabled():
            phase_histogram(self.phase).observe(t1 - self._t0)
        if self._w0 is not None:
            _trace.emit_phase(self.phase, self._w0, time.time())
            self._w0 = None
        self._t0 = None
