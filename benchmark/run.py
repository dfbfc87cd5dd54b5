"""The benchmark's one command.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It reads ``BENCHMARK.json`` for the cell, the cell's
configuration file, its traffic file (``benchmark/traffic/<traffic>.json``)
and its limits (``benchmark/limits/<cell>.json``), refuses anything but the
chips the cell asks for, hands over to the entry the configuration names
(``benchmark/entry/<entry>.py``), and prints the result as the last line of
standard output. Nothing here knows a cell, a model or a metric by name:
a later PR adds files and ``BENCHMARK.json`` entries, and edits nothing.
"""
import argparse
import importlib
import json
import os
import sys
import time

STARTED = time.time()          # process start, as near as Python sees it
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Refused(Exception):
    """The run cannot measure what it was asked to: exit non-zero, no
    result line."""


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


class Context:
    """What an entry gets: the cell's data, the devices, and the clock."""

    def __init__(self, root, bench, cell, seed, seconds, trace,
                 require_chip=True):
        self.root, self.bench, self.cell = root, bench, cell
        self.name = cell['name']
        self.seed, self.trace = int(seed), bool(trace)
        self.chips = int(cell['chips'])
        entry = next(c for c in bench['configs']
                     if c['name'] == cell['config'])
        self.config = load_json(root, entry['file'])
        self.traffic = load_json(root, 'benchmark', 'traffic',
                                 cell['traffic'] + '.json')
        self.limits = load_json(root, 'benchmark', 'limits',
                                self.name + '.json')
        # a traced run measures the traced stretch and nothing else
        self.seconds = float(seconds) if not self.trace else min(
            float(seconds), float(self.traffic.get('trace_seconds', 3.0)))
        self.started = STARTED
        self.require_chip = require_chip
        self.trace_dir = os.path.join(root, '.bench_trace', self.name)
        self.devices = None
        self.peaks = None
        self.compiles = None

    def mark(self, what):
        """Where set-up's seconds go, on standard error as they pass."""
        print('[bench] +%.1fs %s' % (time.time() - self.started, what),
              file=sys.stderr, flush=True)

    def metrics_of(self, group):
        """The cell's metrics of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.bench[group]
                if self.name in m.get('workloads', [self.name])]

    def attach_devices(self):
        import jax
        devices = jax.devices()
        kind = devices[0].device_kind
        peaks = load_json(self.root, 'benchmark', 'peaks.json')
        if self.require_chip:
            if jax.default_backend() != 'tpu':
                raise Refused('backend is %r, not tpu: no accelerator, no '
                              'measurement' % jax.default_backend())
            if kind not in peaks:
                raise Refused('device kind %r is not in benchmark/'
                              'peaks.json' % kind)
            if len(devices) < self.chips:
                raise Refused('the cell asks for %d chips, JAX sees %d'
                              % (self.chips, len(devices)))
        self.devices = devices[:self.chips]
        self.peaks = peaks.get(kind) or next(iter(peaks.values()))
        self.compiles = CompileCounter.shared()


class CompileCounter:
    """Counts programs lowered in this process (a jit cache miss lowers
    whether or not the persistent cache then spares the compile)."""

    EVENT = '/jax/core/compile/jaxpr_to_mlir_module_duration'

    _shared = None

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def shared(cls):
        """One listener a process, however many contexts it makes."""
        if cls._shared is None:
            cls._shared = cls()
        return cls._shared

    def _on(self, event, _seconds, **_kw):
        if event == self.EVENT:
            self.count += 1


def find_cell(bench, name):
    for cell in bench['workloads']:
        if cell['name'] == name:
            return cell
    raise Refused('no workload %r in BENCHMARK.json' % name)


def read_layer_metrics(ctx, facts):
    """Each of the cell's per-layer metrics through its own reader
    (``benchmark/metrics/<name>.json`` names it); a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in ctx.metrics_of('per_layer'):
        spec = load_json(ctx.root, 'benchmark', 'metrics',
                         m['name'] + '.json')
        reader = importlib.import_module('benchmark.readers.'
                                         + spec['reader'])
        value = reader.read(facts, **spec.get('args', {}))
        if value is not None:
            out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out


def execute(name, seed, seconds, trace, require_chip=True, root=ROOT):
    """One run of one cell; returns the result line as a dict. Tests pass
    ``root`` (a tree of data files) and ``require_chip=False``; the
    command line has neither."""
    bench = load_json(root, 'BENCHMARK.json')
    ctx = Context(root, bench, find_cell(bench, name), seed, seconds, trace,
                  require_chip)
    import mxnet_tpu  # noqa: F401  (places the compile cache, PR 21's path)
    import jax
    if require_chip:
        # the cache keeps sub-second programs too: a warm set-up then loads
        # every program, the hundreds of small eager ones with it
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    ctx.attach_devices()
    entry = importlib.import_module('benchmark.entry.'
                                    + ctx.config['entry'])
    facts = entry.run(ctx)
    if trace:
        metrics = read_layer_metrics(ctx, facts)
    else:
        metrics = {m['name']: {'value': float(facts['end_to_end'][m['name']]),
                               'unit': m['unit']}
                   for m in ctx.metrics_of('end_to_end')}
    dev = ctx.devices[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(ctx.devices),
              'memory_peak_bytes': int(facts['memory_peak_bytes'])}
    line = {'correct': bool(facts['verdict'].correct),
            'attempted': int(facts['attempted']),
            'failed': int(facts['failed']),
            'metrics': metrics, 'device': device}
    if trace:
        xp = facts['xplane']
        device['busy_s'], device['window_s'] = xp['busy_s'], xp['window_s']
        line['breakdown'] = {'device_ops': xp['device_ops'][:10],
                             'idle_gaps': xp['idle_gaps'][:10]}
    line['compared'] = facts['verdict'].report()
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = execute(args.workload, args.seed, args.seconds, args.trace)
    except Refused as exc:
        print('refused: %s' % exc, file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
