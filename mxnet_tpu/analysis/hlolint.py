"""Compiled-program invariant verifier over optimized HLO text.

The contracts the runtime promises are all visible in the program
artifact (the same ``lower().compile().as_text()`` the roofline audit
reads — one shared instruction iterator,
:func:`mxnet_tpu.observability.hlo.iter_instructions`):

  * ``amp='bf16'`` — no float32-operand dot/convolution may survive
    (on XLA:CPU, which rewrites bf16 matmuls into f32-compute wrapped
    in converts, the compensating check is that the program still
    carries bf16 buffers at the cast sites — docs/PRECISION.md);
  * ``amp='off'`` — no low-precision buffer anywhere (the amp-off
    byte-identity contract);
  * ``dp=1`` — zero collectives (a collective in a single-replica
    program is a partitioner bug and a silent perf cliff);
  * ``dp>1`` — at least one collective (the gradient reduction must
    exist);
  * ``zero=True`` — a reduce-scatter (TPU) or its XLA:CPU lowering
    (all-reduce + dynamic-slice) must implement the sharded update;
  * ``donation=True`` — the jit-level buffer donation must survive to
    ``input_output_alias`` (donation silently dropped = double HBM
    residency);
  * ``no_outfeed`` — no outfeed/infeed/send/recv: the step makes no
    host transfer, guardrail idle or not (docs/GUARDRAILS.md);
  * ``paged_decode`` — the paged decode-step contract
    (docs/SERVING.md "Paged KV cache"): the per-slot K/V view must
    read through the page table (a gather, or on a TPU the paged
    walk's kernel call, must be present) and no
    instruction may materialize an O(pool)-sized ``copy`` of the KV
    pool (``pool_bytes`` sets the threshold) — cache updates stay
    O(rows) writes in place on donated pool buffers.

``check(hlo_text, expect)`` returns :class:`~mxnet_tpu.analysis.Finding`
records; ``expect`` keys: ``amp`` ('bf16'|'fp16'|'off'), ``dp`` (int),
``zero`` (bool), ``donation`` (bool), ``platform`` ('cpu'|'tpu'),
``no_outfeed`` (bool, default True), ``pallas`` (list of kernel
families that must appear as Mosaic custom-calls in a TPU dump — [] =
none may appear; None/absent skips). Absent keys skip their rules.
``registry.expect_from_config`` maps a committed fusion-audit config
block (FUSION_BASELINE.json) to an expect dict so the verifier runs
against the exact programs the fusion gate audits.
"""
from __future__ import annotations

import re

from . import Finding, fingerprint
from ..observability.hlo import COLLECTIVES, iter_instructions

__all__ = ['check', 'ALL_COLLECTIVES']

ALL_COLLECTIVES = tuple(COLLECTIVES) + ('collective-broadcast',
                                        'ragged-all-to-all')
_HOST_TRANSFER = ('outfeed', 'infeed', 'send', 'recv')
_ALIAS_RE = re.compile(r'input_output_alias=\{\s*([^}]*)\}')
_RESULT_SHAPE_RE = re.compile(r'=\s*([a-z0-9]+)\[([0-9,]*)\]')
_DTYPE_BYTES = {'f64': 8, 'f32': 4, 'f16': 2, 'bf16': 2, 's64': 8,
                'u64': 8, 's32': 4, 'u32': 4, 's16': 2, 'u16': 2,
                's8': 1, 'u8': 1, 'pred': 1}


def _result_bytes(line):
    """Byte size of an instruction's result buffer (0 when the line
    carries no parseable array type)."""
    m = _RESULT_SHAPE_RE.search(line)
    if m is None:
        return 0
    n = _DTYPE_BYTES.get(m.group(1), 4)
    for d in m.group(2).split(','):
        if d.strip():
            n *= int(d)
    return n


def _finding(rule, program, message, instr=None, severity='error'):
    return Finding(rule, severity, program, 0, message,
                   instr=instr,
                   fp=fingerprint(rule, program, instr=instr or ''))


def check(hlo_text, expect, program='program'):
    """Verify one compiled program's invariants; returns Findings
    (empty = every asserted invariant holds)."""
    findings = []
    platform = (expect.get('platform') or 'tpu').lower()
    instrs = list(iter_instructions(hlo_text))
    bases = {}
    for i in instrs:
        bases.setdefault(i.base, []).append(i)

    amp = str(expect.get('amp', '') or '').lower()
    if amp in ('bf16', 'fp16'):
        # fp16 needs the lookbehind: a plain 'f16[' substring would
        # also match 'bf16[' and let bf16-only programs satisfy the
        # fp16 invariants
        if amp == 'bf16':
            def has_low(text):
                return 'bf16[' in text
        else:
            def has_low(text):
                return bool(re.search(r'(?<!b)f16\[', text))
        if platform == 'cpu':
            # XLA:CPU rewrites low-precision dots to f32 compute
            # wrapped in converts — assert the program still CARRIES
            # the low-precision buffers the policy casts created
            if not any(has_low(i.line) for i in instrs):
                findings.append(_finding(
                    'HLO-AMP-NOT-LOW', program,
                    "amp=%s program carries no %s buffer anywhere — "
                    "the policy's casts did not reach the compiled "
                    'program' % (amp, amp.replace('fp', 'f'))))
        else:
            for i in bases.get('dot', []) + bases.get('convolution',
                                                      []):
                if 'f32[' in i.operands_text and \
                        not has_low(i.operands_text):
                    findings.append(_finding(
                        'HLO-AMP-F32-MATMUL', program,
                        '%s consumes f32 operands in an amp=%s '
                        'program — the cast-to-compute policy was '
                        'bypassed (docs/PRECISION.md)'
                        % (i.opcode, amp), instr=i.name))
    elif amp in ('off', 'none', 'false', '0'):
        for i in instrs:
            if 'bf16[' in i.line or re.search(r'(?<!b)f16\[', i.line):
                findings.append(_finding(
                    'HLO-AMP-OFF-LOW', program,
                    'amp=off program carries a low-precision buffer '
                    '(%s) — violates the amp-off byte-identity '
                    'contract' % i.opcode, instr=i.name))
                break

    if 'dp' in expect:
        dp = int(expect['dp'] or 1)
        coll = [i for b in ALL_COLLECTIVES for i in bases.get(b, ())]
        if dp <= 1:
            for i in coll:
                findings.append(_finding(
                    'HLO-DP1-COLLECTIVE', program,
                    '%s in a dp=1 program — single-replica programs '
                    'must contain no collectives' % i.opcode,
                    instr=i.name))
        elif not coll:
            findings.append(_finding(
                'HLO-DPN-NO-COLLECTIVE', program,
                'dp=%d program contains no collective — the '
                'cross-replica gradient reduction is missing' % dp))

    if expect.get('zero'):
        has_rs = bool(bases.get('reduce-scatter'))
        cpu_lowered = platform == 'cpu' and \
            bool(bases.get('all-reduce')) and \
            bool(bases.get('dynamic-slice'))
        if not has_rs and not cpu_lowered:
            findings.append(_finding(
                'HLO-ZERO-NO-RS', program,
                'ZeRO program has no reduce-scatter%s — the update '
                'is not running on shards (docs/PARALLEL.md)'
                % (' (nor its XLA:CPU all-reduce + dynamic-slice '
                   'lowering)' if platform == 'cpu' else '')))

    if expect.get('donation'):
        m = _ALIAS_RE.search(hlo_text)
        if m is None or not m.group(1).strip():
            findings.append(_finding(
                'HLO-DONATION-DROPPED', program,
                'donate_argnums did not survive to '
                'input_output_alias — donated inputs are double-'
                'resident in HBM'))

    if expect.get('no_outfeed', True):
        for b in _HOST_TRANSFER:
            for i in bases.get(b, ()):
                findings.append(_finding(
                    'HLO-HOST-TRANSFER', program,
                    '%s in a step program — the compiled step must '
                    'not transfer to the host mid-step' % i.opcode,
                    instr=i.name))

    if expect.get('paged_decode'):
        # the paged decode-step contract (docs/SERVING.md): the page-
        # table indirection must actually be a gather, and the pool
        # must never be copied whole — a silent fallback to a dense
        # per-slot cache (or a partitioner materializing the pool)
        # would reintroduce the memory wall the layout removes
        # (placed on a TPU the one-token step reads through the table
        # inside the walk's kernel, which takes the tables as operands)
        from ..ops.pallas.costs import PAGED_WALK_TAG
        walks = any(PAGED_WALK_TAG in i.line
                    for i in bases.get('custom-call', ()))
        if not walks and not bases.get('gather') \
                and not bases.get('dynamic-gather'):
            findings.append(_finding(
                'HLO-DECODE-PAGED', program,
                'paged decode-step program contains no gather and no '
                'page-table walk — the per-slot K/V history is not '
                'read through the page table (docs/SERVING.md "Paged '
                'KV cache")'))
        # the no-O(pool)-copy half is accelerator-only: XLA:CPU
        # ignores donation and lowers the in-place row update as a
        # functional whole-buffer copy — exactly the traffic donation
        # removes on TPU, and why the donated-alias rule exists
        pool_bytes = int(expect.get('pool_bytes') or 0)
        if pool_bytes and platform != 'cpu':
            for i in bases.get('copy', ()):
                if _result_bytes(i.line) >= pool_bytes:
                    findings.append(_finding(
                        'HLO-DECODE-PAGED', program,
                        'O(pool)-sized copy materializes the whole KV '
                        'pool (%d+ bytes) — paged cache updates must '
                        'stay O(rows) writes in place on the '
                        'donated pool buffers' % pool_bytes,
                        instr=i.name))

    if expect.get('pallas') is not None:
        # MXNET_TPU_PALLAS invariants (docs/PERFORMANCE.md): Mosaic
        # kernels are custom-calls in TPU HLO, so a TPU dump must
        # carry the enabled families' kernel calls (a silent fallback
        # to the XLA path leaves the knob claiming speed it does not
        # deliver) and a knob-off program must carry none. On the CPU
        # rig the interpreter inlines kernels — no custom-call — so
        # the presence rule is TPU-only; the absence rule runs
        # everywhere.
        from ..ops.pallas.costs import KERNEL_TAGS
        wanted = tuple(expect['pallas'] or ())
        present = {}
        for i in bases.get('custom-call', ()):
            for family, tags in KERNEL_TAGS.items():
                if any(t in i.line for t in tags):
                    present.setdefault(family, []).append(i)
        if platform != 'cpu':
            for family in wanted:
                if family not in present:
                    findings.append(_finding(
                        'HLO-PALLAS-MISSING', program,
                        "pallas family '%s' is enabled but no %s "
                        'kernel custom-call is present — the program '
                        'silently fell back to the XLA path '
                        '(docs/PERFORMANCE.md fallback rules)'
                        % (family, family)))
        for family, calls in sorted(present.items()):
            if family not in wanted:
                findings.append(_finding(
                    'HLO-PALLAS-UNEXPECTED', program,
                    "pallas family '%s' kernel custom-call present "
                    'but the family is not enabled — a knob-off '
                    'program must be byte-identical to the pre-'
                    'kernel build' % family, instr=calls[0].name))

    return findings
