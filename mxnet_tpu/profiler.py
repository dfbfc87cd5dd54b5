"""Profiler: chrome://tracing output + scoped annotations.

Reference parity: python/mxnet/profiler.py (set_config/set_state/dump,
ProfileTask/Event/Counter scopes) over src/profiler/ (chrome trace JSON,
profiler.h:88,438; SURVEY.md §5.1).

TPU-native design: wraps jax.profiler (XPlane/TensorBoard trace) behind the
MXNet-shaped API, and additionally keeps a lightweight in-process chrome
trace of user scopes so `dump()` always produces a chrome://tracing file
even without TensorBoard.
"""
from __future__ import annotations

import json
import os
import threading
import time

__all__ = ['set_config', 'profiler_set_config', 'set_state',
           'profiler_set_state', 'dump', 'dumps', 'aggregate_stats',
           'pause', 'resume', 'Task', 'Frame', 'Event', 'Counter',
           'Marker', 'scope']

_config = {'filename': 'profile.json', 'profile_all': False,
           'profile_symbolic': True, 'profile_imperative': True,
           'profile_memory': False, 'profile_api': False,
           'aggregate_stats': False}
_state = {'running': False, 'jax_dir': None}
_events = []
_lock = threading.Lock()


def set_config(**kwargs):
    """Configure the profiler (reference: profiler.py set_config;
    env autostart via MXNET_PROFILER_AUTOSTART)."""
    _config.update(kwargs)


profiler_set_config = set_config


def set_state(state='stop', profile_process='worker'):
    """Start/stop profiling (reference: profiler.py set_state). 'run'
    starts a jax.profiler trace when a trace dir is configured."""
    if state == 'run':
        _state['running'] = True
        fname = _config.get('filename', 'profile.json')
        trace_dir = os.path.splitext(fname)[0] + '_xplane'
        try:
            import jax
            jax.profiler.start_trace(trace_dir)
            _state['jax_dir'] = trace_dir
        except Exception:
            _state['jax_dir'] = None
    elif state == 'stop':
        if _state.get('jax_dir'):
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            _state['jax_dir'] = None
        _state['running'] = False
    else:
        raise ValueError("state must be 'run' or 'stop'")


profiler_set_state = set_state


def pause(profile_process='worker'):
    _state['running'] = False


def resume(profile_process='worker'):
    _state['running'] = True


def _emit(ph, name, cat, ts, dur=None, args=None):
    ev = {'ph': ph, 'name': name, 'cat': cat, 'pid': os.getpid(),
          'tid': threading.get_ident(), 'ts': ts * 1e6}
    if dur is not None:
        ev['dur'] = dur * 1e6
    if args:
        ev['args'] = args
    with _lock:
        _events.append(ev)


def aggregate_stats(reset=False):
    """Per-scope aggregate {name: {category, count, total_ms, min_ms,
    max_ms, avg_ms}} from the event buffer (reference:
    src/profiler/aggregate_stats.cc AggregateStats)."""
    with _lock:
        table = {}
        for ev in _events:
            if ev['ph'] != 'X':
                continue
            dur = ev.get('dur', 0.0) / 1e3
            rec = table.get(ev['name'])
            if rec is None:
                table[ev['name']] = rec = {
                    'category': ev.get('cat', 'user'), 'count': 0,
                    'total_ms': 0.0, 'min_ms': dur, 'max_ms': dur}
            rec['count'] += 1
            rec['total_ms'] += dur
            rec['min_ms'] = min(rec['min_ms'], dur)
            rec['max_ms'] = max(rec['max_ms'], dur)
        for rec in table.values():
            rec['avg_ms'] = rec['total_ms'] / max(rec['count'], 1)
        if reset:
            _events.clear()
    return table


_SORT_KEYS = {'total': 'total_ms', 'avg': 'avg_ms', 'min': 'min_ms',
              'max': 'max_ms', 'count': 'count'}


def dumps(reset=False, format='table', sort_by='total', ascending=False):
    """Aggregate stats as text (or JSON with ``format='json'``)
    (reference: profiler.py dumps / MXAggregateProfileStatsPrint at
    src/c_api/c_api_profile.cc:305; sort options match)."""
    table = aggregate_stats(reset=reset)
    if format == 'json':
        return json.dumps(table, sort_keys=True)
    if sort_by not in _SORT_KEYS:
        raise ValueError('sort_by must be one of %s'
                         % sorted(_SORT_KEYS))
    key = _SORT_KEYS[sort_by]
    rows = sorted(table.items(), key=lambda kv: kv[1][key],
                  reverse=not ascending)
    lines = ['%-40s %-10s %8s %12s %10s %10s %10s'
             % ('Name', 'Category', 'Calls', 'Total ms', 'Min ms',
                'Max ms', 'Avg ms')]
    for name, r in rows:
        lines.append('%-40s %-10s %8d %12.3f %10.3f %10.3f %10.3f'
                     % (name, r['category'], r['count'], r['total_ms'],
                        r['min_ms'], r['max_ms'], r['avg_ms']))
    return '\n'.join(lines)


def record_op(name, start, stop):
    """Hot-path hook for the eager dispatcher: record one operator span
    when the profiler is running (profile_imperative parity)."""
    if _state['running'] and _config.get('profile_imperative', True):
        _emit('X', name, 'operator', start, stop - start)


def record_span(name, start, stop):
    """A finished host interval from ``observability.spans`` (which has
    already put it on the jax profiler's clock itself): one 'user' row
    of the chrome trace while the profiler is running."""
    if _state['running']:
        _emit('X', name, 'user', start, stop - start)


def is_running():
    return _state['running']


class op_span:
    """Tiny timing guard used by the dispatch hot paths: no-op when the
    profiler is idle; otherwise times the block, calling ``sync`` (a
    device fence) before the stop stamp so the span covers execution,
    not just async dispatch (block_until_ready is a true fence). The
    XPlane trace remains the ground truth for device time."""

    __slots__ = ('name', 'sync', '_t0')

    def __init__(self, name, sync=None):
        self.name, self.sync = name, sync

    def __enter__(self):
        self._t0 = time.perf_counter() if _state['running'] else None
        return self

    def __exit__(self, *exc):
        if self._t0 is None:
            return
        if exc[0] is None and self.sync is not None:
            try:
                self.sync()
            except Exception:
                pass
        record_op(self.name, self._t0, time.perf_counter())


def dump(finished=True, profile_process='worker'):
    """Write the chrome://tracing JSON (reference: profiler.py dump).

    ``finished=True`` (the default, matching the reference semantics)
    ENDS collection: profiling stops (including any live jax trace)
    and the event buffer is cleared, so a later ``dump(False)`` mid-run
    does not re-emit this run's events. ``finished=False`` snapshots
    without disturbing collection."""
    fname = _config.get('filename', 'profile.json')
    with _lock:
        snapshot = list(_events)
    data = {'traceEvents': snapshot, 'displayTimeUnit': 'ms'}
    with open(fname, 'w') as f:
        json.dump(data, f)
    if finished:
        # only after a successful write: a failed dump (full disk,
        # bad path) must leave the buffer intact for a re-dump. Drop
        # exactly the events written — appends that raced the write
        # survive for the next dump
        with _lock:
            del _events[:len(snapshot)]
        if _state['running']:
            set_state('stop')
    return fname


class _Scoped:
    """Base for named profiling objects with start/stop."""

    _cat = 'user'

    def __init__(self, name):
        self.name = name
        self._start = None

    def start(self):
        self._start = time.perf_counter()

    def stop(self):
        if self._start is None:
            return
        now = time.perf_counter()
        _emit('X', self.name, self._cat, self._start, now - self._start)
        self._start = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Task(_Scoped):
    """Profile a task (reference: profiler.py Task)."""
    _cat = 'task'

    def __init__(self, domain=None, name='task'):
        super().__init__(name)


class Frame(_Scoped):
    _cat = 'frame'

    def __init__(self, domain=None, name='frame'):
        super().__init__(name)


class Event(_Scoped):
    _cat = 'event'

    def __init__(self, name='event'):
        super().__init__(name)


class Counter:
    """Profile a numeric counter (reference: profiler.py Counter).

    Thread-safe: documented as usable from dispatch hot paths, so
    ``increment``/``decrement`` must not lose updates under
    concurrency — the read-modify-write of ``_value`` happens under a
    per-counter lock (the chrome-trace emit stays outside it; event
    ordering across threads is the trace viewer's job)."""

    def __init__(self, domain=None, name='counter', value=0):
        self.name = name
        self._vlock = threading.Lock()
        self._value = value
        self.set_value(value)

    def set_value(self, value):
        with self._vlock:
            self._value = value
        _emit('C', self.name, 'counter', time.perf_counter(),
              args={'value': value})

    def increment(self, delta=1):
        with self._vlock:
            self._value = value = self._value + delta
        _emit('C', self.name, 'counter', time.perf_counter(),
              args={'value': value})
        return self     # __iadd__ alias must rebind to the Counter

    def decrement(self, delta=1):
        return self.increment(-delta)

    __iadd__ = increment
    __isub__ = decrement


class Marker:
    """Instant marker (reference: profiler.py Marker)."""

    def __init__(self, domain=None, name='marker'):
        self.name = name

    def mark(self, scope='process'):
        _emit('i', self.name, 'marker', time.perf_counter())


class scope(_Scoped):
    """Context manager annotating a region; also forwards to
    jax.profiler.TraceAnnotation so scopes appear in XPlane traces."""

    def __init__(self, name='scope'):
        super().__init__(name)
        self._jax_ann = None

    def __enter__(self):
        super().__enter__()
        try:
            import jax
            self._jax_ann = jax.profiler.TraceAnnotation(self.name)
            self._jax_ann.__enter__()
        except Exception:
            self._jax_ann = None
        return self

    def __exit__(self, *exc):
        if self._jax_ann is not None:
            self._jax_ann.__exit__(*exc)
        super().__exit__(*exc)
