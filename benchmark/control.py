"""Reads the controls of a cell on the chip, several seeds in one process:

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it prints one JSON line of readings: the reference in the
program's place at the configuration's control precision, and the faults a
cell of that kind can have. The benchmark's own runs never run this; the
limits in ``benchmark/limits/`` were set between these readings and the
program's (``PERF.md``).
"""
import argparse
import importlib
import json
import sys

from . import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT, 'BENCHMARK.json')
    cell = run.find_cell(bench, args.workload)
    import mxnet_tpu  # noqa: F401
    for seed in args.seeds.split(','):
        ctx = run.Context(run.ROOT, bench, cell, int(seed), args.seconds, 0)
        try:
            ctx.attach_devices()
        except run.Refused as exc:
            print('refused: %s' % exc, file=sys.stderr)
            return 3
        entry = importlib.import_module('benchmark.entry.'
                                        + ctx.config['entry'])
        print(json.dumps({'workload': args.workload, 'seed': int(seed),
                          'readings': entry.control(ctx)}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
