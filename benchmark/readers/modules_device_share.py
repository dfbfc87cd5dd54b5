"""Share of the device's busy time that went to the XLA modules whose name
matches: the programs have names of their own (``jit_prefill_b128``,
``jit_fn_step``, ``jit_page_copy``), so a pattern picks a kind of program
and every call of every one it matches is counted."""
import re


def read(facts, module):
    xp = facts.get('xplane') or {}
    hits = [t for name, (t, _calls) in xp.get('modules', {}).items()
            if re.search(module, name)]
    if not hits or not xp.get('busy_s'):
        return None
    return 100.0 * sum(hits) / xp['busy_s']
