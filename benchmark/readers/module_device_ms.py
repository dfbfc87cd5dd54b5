"""Device milliseconds per call of an XLA module. The program names every
jitted function alike (``jit_fn(<fingerprint>)``), so among the modules
whose name matches, the one that ran most often is taken: the step runs
every tick, a prefill bucket only when a request of its length arrives."""
import re


def read(facts, module):
    hits = [(c, t) for name, (t, c) in facts['xplane']['modules'].items()
            if re.search(module, name)]
    if not hits:
        return None
    calls, seconds = max(hits)
    return 1e3 * seconds / calls
