"""A percentile of one of the entry's series of milliseconds."""
from ..loadgen import percentile


def read(facts, series, q):
    return percentile(facts.get('series', {}).get(series) or [], q)
