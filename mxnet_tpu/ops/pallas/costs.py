"""Roofline flop models for the Pallas kernel custom-calls.

Mosaic kernels appear in TPU HLO as ``custom-call`` instructions
(``custom_call_target="tpu_custom_call"``): XLA's text gives their
operand/result shapes but no flop count, so without a cost model a
Pallas-kernelized program would look *more* memory-bound in the
fusion audit than the unfused program it replaced — the kernel's
internal GEMMs would count as zero flops. This module registers a
flop model per kernel family into
``observability.roofline.CUSTOM_CALL_COSTS`` (the per-call-target
registry); the audit then attributes kernel calls like fusions:
operand+result bytes from the shapes, flops from here.

Pure text-level shape arithmetic — no jax import, safe for the
roofline's lazy load on any rig.
"""
from __future__ import annotations

__all__ = ['register_all', 'KERNEL_TAGS', 'PAGED_WALK_TAG']

# kernel function names (what lands in the custom-call metadata /
# payload) by family — also what the hlolint HLO-PALLAS rules match
KERNEL_TAGS = {
    'attention': ('mxnet_tpu_flash_attention_fwd',
                  'mxnet_tpu_flash_attention_dq',
                  'mxnet_tpu_flash_attention_dkv',
                  'mxnet_tpu_flash_decode_fwd'),
    'epilogue': ('mxnet_tpu_bn_act_fwd', 'mxnet_tpu_bn_act_bwd',
                 'mxnet_tpu_act_fwd', 'mxnet_tpu_act_bwd',
                 'mxnet_tpu_add_act_fwd'),
    'xent': ('mxnet_tpu_softmax_xent_fwd',
             'mxnet_tpu_softmax_xent_bwd'),
}


# the paged decode walk (attention.flash_paged_decode_attention): behind
# no knob, so in no family above. The paged step calls it wherever it is
# placed on a TPU, and hlolint takes it as the step's read through the
# page table
PAGED_WALK_TAG = 'mxnet_tpu_paged_decode_walk'


def _dims(instr, idx):
    """Operand ``idx``'s dims as ints (0s for malformed text)."""
    if idx >= len(instr.operands):
        return []
    dims = instr.operands[idx][1].replace(' ', '').split(',')
    return [int(d) for d in dims if d]


def _elems(instr, idx):
    n = 1
    for d in _dims(instr, idx):
        n *= d
    return n


def _attention_flops(gemms):
    """2 * BH * Sq * Sk * D per GEMM over the score/context shapes,
    read off the q (BH, Sq, D) and k (BH, Sk, D) operands."""
    def fn(instr):
        q = _dims(instr, 0)
        k = _dims(instr, 1)
        if len(q) < 3 or len(k) < 3:
            return 0
        bh, sq, d = q[-3], q[-2], q[-1]
        sk = k[-2]
        return gemms * 2 * bh * sq * sk * d + 5 * bh * sq * sk
    return fn


def _decode_flops(instr):
    # q (slots, 8, U) vs cache (slots, L, U): 2 GEMM-equivalents over
    # the real query row only
    q = _dims(instr, 0)
    k = _dims(instr, 1)
    if len(q) < 3 or len(k) < 3:
        return 0
    slots, u = q[-3], q[-1]
    length = k[-2]
    return 4 * slots * length * u + 5 * slots * length


def _paged_walk_flops(instr):
    # operands: tables (slots * max_pages,), positions, the chain of
    # live slots, q (slots, heads a group, width), K pool, V pool (pages,
    # page_size, width). What the walk multiplies follows the positions,
    # which the text does not hold: a full table, the most it can be.
    # Two GEMM-equivalents over the real query rows, as _decode_flops
    tables, q, pool = _dims(instr, 0), _dims(instr, 3), _dims(instr, 4)
    if len(tables) != 1 or len(q) < 3 or len(pool) < 3:
        return 0
    slots, rep, width = q[-3], q[-2], q[-1]
    rows = tables[0] // max(slots, 1) * pool[-2]
    return 4 * slots * rows * rep * width + 5 * slots * rows * rep


def _elementwise_flops(per_elem):
    def fn(instr):
        return per_elem * _elems(instr, 0)
    return fn


def register_all(registry):
    """Install every kernel family's flop model into ``registry``
    (tag -> fn(Instruction) -> flops)."""
    registry.setdefault('mxnet_tpu_flash_attention_fwd',
                        _attention_flops(2))
    registry.setdefault('mxnet_tpu_flash_attention_dq',
                        _attention_flops(3))
    registry.setdefault('mxnet_tpu_flash_attention_dkv',
                        _attention_flops(4))
    registry.setdefault('mxnet_tpu_flash_decode_fwd', _decode_flops)
    registry.setdefault(PAGED_WALK_TAG, _paged_walk_flops)
    for tag in KERNEL_TAGS['epilogue']:
        registry.setdefault(tag, _elementwise_flops(3))
    # xent: max + exp + sum + log + pick over the (B, V) block
    registry.setdefault('mxnet_tpu_softmax_xent_fwd',
                        _elementwise_flops(4))
    registry.setdefault('mxnet_tpu_softmax_xent_bwd',
                        _elementwise_flops(3))
    # the seed-era NMS kernel: O(n_iter * N) VPU work; approximate
    # with one sweep over the packed rows per iteration is not
    # recoverable from text — count one elementwise pass
    registry.setdefault('_nms_kernel', _elementwise_flops(1))
    return registry
