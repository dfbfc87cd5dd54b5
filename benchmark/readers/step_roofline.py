"""The least time the chip could take for the step the window ran on
average (``benchmark/flops/<family>.decode_step``: the larger of operations
over the peak and bytes over the bandwidth) over the step module's device
time per call."""
import importlib

from . import module_device_ms


def read(facts, module):
    ms = module_device_ms.read(facts, module)
    if not ms or not facts.get('steps'):
        return None
    flops = importlib.import_module('benchmark.flops.'
                                    + facts['config']['family'])
    ops, byts = flops.decode_step(facts['config'], facts['active_per_step'],
                                  facts['live_kv_tokens_per_step'])
    least = max(ops / facts['peaks']['bf16_flops_per_s'],
                byts / facts['peaks']['hbm_bytes_per_s'])
    return 100.0 * least * 1e3 / ms
