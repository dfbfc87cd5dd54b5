"""Whole-step share of the chips' peak: the work the configuration needs
per unit (``benchmark/flops/<family>.py``) times the units finished per
second, over chips times the bf16 peak."""
import importlib


def read(facts, rate, work):
    flops = importlib.import_module('benchmark.flops.'
                                    + facts['config']['family'])
    per_unit = getattr(flops, work)(facts['config'], facts['traffic'])
    peak = facts['chips'] * facts['peaks']['bf16_flops_per_s']
    return 100.0 * facts['end_to_end'][rate] * per_unit / peak
