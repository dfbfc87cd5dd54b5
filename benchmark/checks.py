"""The comparison that decides ``correct``: the arithmetic of each number
compared, and the one place that prints every number beside its limit."""
import statistics
import sys

import numpy as np


def norm_gaps(program, reference):
    """Worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero).
    Returns (worst gap, its leaf)."""
    ref = {k: float(np.linalg.norm(np.asarray(v, 'float64')))
           for k, v in reference.items()}
    median = statistics.median(ref.values())
    worst, where = 0.0, None
    for name, r in ref.items():
        p = float(np.linalg.norm(np.asarray(program[name], 'float64')))
        gap = abs(p - r) / max(r, median)
        if not gap <= worst:           # a NaN is the worst reading
            worst, where = gap, name
    return worst, where


def logit_gaps(ref_logits, tokens):
    """For each position, how far the served token's logit lies below the
    reference's best: ``ref_logits`` (n, V), ``tokens`` (n,)."""
    ref_logits = np.asarray(ref_logits)
    picked = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return ref_logits.max(-1) - picked


class Verdict:
    """Numbers compared, each with its limit; ``correct`` only if every one
    is within it and nothing failed."""

    def __init__(self):
        self.rows = {}

    def hold(self, name, value, limit):
        value = float(value)
        self.rows[name] = {'value': value, 'limit': limit,
                           'ok': bool(value <= limit)}

    def note(self, name, value):
        """A reading printed beside the others and not judged."""
        self.rows[name] = {'value': value, 'limit': None, 'ok': True}

    @property
    def correct(self):
        return all(r['ok'] for r in self.rows.values())

    def report(self):
        """The compared numbers as the last lines of standard error."""
        for name, r in self.rows.items():
            print('compared %s = %r limit %r %s'
                  % (name, r['value'], r['limit'],
                     'ok' if r['ok'] else 'OVER'), file=sys.stderr)
        sys.stderr.flush()
        return {k: {'value': r['value'], 'limit': r['limit']}
                for k, r in self.rows.items()}
