"""A kernel's share of its roofline: the least time the chip could take for
one call of it (``benchmark/flops/<family>.<work>(config, active sequences,
live cached tokens)`` for the step the window ran on average: the larger of
operations over the peak and bytes over the bandwidth) over the device time
of one call. A step calls the kernel once a layer, and each layer's call is
an operation of its own among ``xplane.device_ops``: every row whose name
matches ``kernel`` is one layer's calls over the window, as many as the step
module (``module``, the one that ran most often) was called. Only the ten
longest operations reach ``facts``, so the rows found may be fewer than the
layers: the share is of the calls found, each against its own least time."""
import importlib
import re


def read(facts, kernel, module, work):
    xp = facts.get('xplane') or {}
    rows = [t for name, t in xp.get('device_ops') or []
            if re.search(kernel, name)]
    calls = max([c for name, (_t, c) in (xp.get('modules') or {}).items()
                 if re.search(module, name)], default=0)
    if not rows or not calls or not facts.get('steps'):
        return None
    flops = importlib.import_module('benchmark.flops.'
                                    + facts['config']['family'])
    ops, byts = getattr(flops, work)(facts['config'],
                                     facts['active_per_step'],
                                     facts['live_kv_tokens_per_step'])
    least = max(ops / facts['peaks']['bf16_flops_per_s'],
                byts / facts['peaks']['hbm_bytes_per_s'])
    return 100.0 * least * calls * len(rows) / sum(rows)
