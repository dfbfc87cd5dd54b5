"""Static-analysis gate: trace-purity + lock-order + program
invariants (docs/ANALYSIS.md).

Legs, each independently reportable:

  1. selftest   — embedded known-bad fixtures must fire every rule
                  family and the known-good respellings must stay
                  quiet (the lint lints itself before lint results
                  are trusted);
  2. source     — tracelint + locklint over the repo, diffed against
                  the committed LINT_BASELINE.json: NEW findings fail
                  (rule id + file:line printed), suppressed findings
                  pass, stale suppressions warn;
  3. programs   — hlolint invariants against freshly built compiled
                  step programs on the virtual CPU mesh: dp=1 amp-off
                  (no collectives, donation survives, no host
                  transfer, no low-precision buffer), dp=1 amp=bf16
                  (the policy's casts reach the program), dp=8 plain
                  (gradient all-reduce present), dp=8 ZeRO
                  (reduce-scatter or its CPU lowering). ``--no-build``
                  skips this leg (pure-AST mode, no jax import).

Usage:
  python -m mxnet_tpu.analysis [--baseline LINT_BASELINE.json]
      [--out FINDINGS.jsonl] [--write-baseline] [--no-build]
      [--devices 8]
  python -m mxnet_tpu.analysis --hlo dump.txt --amp bf16 --dp 1 \\
      --platform tpu          # audit an external HLO dump
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# virtual device count must land in XLA_FLAGS before jax initializes
# (same pattern as parallel/__main__); harmless when --no-build
_n = '8'
if '--devices' in sys.argv[:-1]:
    _n = sys.argv[sys.argv.index('--devices') + 1]
else:
    for _a in sys.argv[1:]:
        if _a.startswith('--devices='):
            _n = _a.split('=', 1)[1]
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=%s'
        % _n).strip()
from .. import config as _config  # noqa: E402
_config.cpu_rig('analysis')


# -- selftest fixtures ------------------------------------------------------

_BAD_TRACE = '''\
import os
import time
import random
import numpy as onp
from mxnet_tpu.config import get as _cfg


def bad_kernel(data, scale):
    mode = os.environ.get('SOME_KNOB', 'fast')
    t0 = time.time()
    jitter = random.random()
    noise = onp.random.randn()
    host = float(data)
    if scale > 0:
        data = data * scale
    for _ in range(scale):
        data = data + 1
    return data, mode, t0, jitter, noise, host


def bad_knob(data):
    return data * float(_cfg('MXNET_TPU_LOSS_SCALE'))
'''

_GOOD_TRACE = '''\
import jax
import jax.numpy as jnp


def good_kernel(data, scale, *, mode='fast'):
    if mode == 'fast':                      # host attr branch: fine
        data = jnp.tanh(data)
    out = jax.lax.cond(scale[0] > 0,
                       lambda d: d * scale, lambda d: d, data)
    out = jnp.where(out >= 0, out, 0.0)
    if data is None:                        # identity test: fine
        return out
    total = jnp.zeros(())
    for g in (data, out):                   # host-list iteration: fine
        total = total + jnp.sum(g)
    return total
'''

_BAD_CONFIG = '''\
import os
from os import environ
from mxnet_tpu import config as _config


def _knob(name, default):
    try:
        return _config.get(name)
    except Exception:
        return default


def unregistered_reads():
    a = os.environ.get('MXNET_TPU_PHANTOM_KNOB', '1')
    b = environ['MXNET_TPU_GHOST_KNOB']
    c = os.getenv('MXNET_TPU_SHADOW_KNOB')
    d = _knob('MXNET_TPU_LOCAL_HELPER_KNOB', 4)
    e = _config.get('MXNET_TPU_DIRECT_KNOB')
    return a, b, c, d, e
'''

_GOOD_CONFIG = '''\
import os
from mxnet_tpu import config as _config

DOC_TABLE = {'MXNET_TPU_UNRELATED_MENTION': 'mentions are fine'}


def registered_reads():
    a = os.environ.get('MXNET_TPU_REGISTERED_KNOB', '1')
    b = _config.get('MXNET_TPU_REGISTERED_KNOB')
    c = os.environ.get('SOME_OTHER_PREFIX', 'x')
    return a, b, c, DOC_TABLE
'''

_BAD_LOCK = '''\
import threading


def record_event(kind, **fields):
    pass


class Bad:
    def __init__(self, on_done=None):
        self._a = threading.Lock()
        self._b = threading.Lock()
        self._on_done = on_done
        self.depth = 0

    def ab(self):
        with self._a:
            with self._b:
                self.depth += 1

    def ba(self, fut):
        with self._b:
            with self._a:
                self.depth -= 1
            fut.set_exception(RuntimeError('x'))
            self._on_done(self.depth)
            record_event('bad', depth=self.depth)

    def reenter(self):
        with self._a:
            self.helper()

    def helper(self):
        with self._a:
            return self.depth

    def racy(self):
        self.depth = 41
'''

_GOOD_LOCK = '''\
import threading


def record_event(kind, **fields):
    pass


class Good:
    """Lock-then-copy-then-callback: the blessed shape."""

    def __init__(self, on_done=None):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._on_done = on_done
        self._items = []

    def push(self, item):
        with self._lock:
            self._items.append(item)
            self._cv.notify()

    def drain(self):
        with self._lock:
            taken, self._items = self._items, []
        for item in taken:
            self._on_done(item)
        record_event('drained', n=len(taken))
'''

_BAD_HLO = '''\
HloModule jit_step, is_scheduled=true

ENTRY %main.1 (p0: f32[8,8], p1: bf16[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = bf16[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %all-reduce.2 = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %dot.1), replica_groups={}, to_apply=%add
  %outfeed.3 = token[] outfeed(f32[8,8]{1,0} %all-reduce.2, token[] %tok)
  ROOT %add.4 = f32[8,8]{1,0} add(f32[8,8]{1,0} %dot.1, f32[8,8]{1,0} %all-reduce.2)
}
'''

# synthetic TPU-style dump carrying one Mosaic (Pallas) kernel
# custom-call — how a flash-attention kernel appears in real TPU HLO
_PALLAS_HLO = '''\
HloModule jit_step, is_scheduled=true

ENTRY %main.1 (p0: f32[8,16,8], p1: f32[8,16,8], p2: f32[8,16,8]) -> f32[8,16,8] {
  %p0 = f32[8,16,8]{2,1,0} parameter(0)
  %p1 = f32[8,16,8]{2,1,0} parameter(1)
  %p2 = f32[8,16,8]{2,1,0} parameter(2)
  %custom-call.1 = f32[8,16,8]{2,1,0} custom-call(f32[8,16,8]{2,1,0} %p0, f32[8,16,8]{2,1,0} %p1, f32[8,16,8]{2,1,0} %p2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call[name=mxnet_tpu_flash_attention_fwd]" source_file="attention.py" source_line=120}
  ROOT %add.2 = f32[8,16,8]{2,1,0} add(f32[8,16,8]{2,1,0} %custom-call.1, f32[8,16,8]{2,1,0} %p0)
}
'''


# paged decode-step fixtures (HLO-DECODE-PAGED): the good dump reads
# the pool through a page-table gather and updates one row in place;
# the bad dump materializes a pool-sized copy and never gathers
_PAGED_HLO_GOOD = '''\
HloModule jit_step, is_scheduled=true

ENTRY %main.1 (p0: f32[33,16,32], p1: s32[4,2], p2: f32[4,32]) -> f32[33,16,32] {
  %p0 = f32[33,16,32]{2,1,0} parameter(0)
  %p1 = s32[4,2]{1,0} parameter(1)
  %p2 = f32[4,32]{1,0} parameter(2)
  %gather.1 = f32[4,2,16,32]{3,2,1,0} gather(f32[33,16,32]{2,1,0} %p0, s32[4,2]{1,0} %p1), offset_dims={1,2,3}
  %reshape.2 = f32[1,1,32]{2,1,0} reshape(f32[4,32]{1,0} %p2)
  ROOT %dynamic-update-slice.3 = f32[33,16,32]{2,1,0} dynamic-update-slice(f32[33,16,32]{2,1,0} %p0, f32[1,1,32]{2,1,0} %reshape.2, s32[] %c0, s32[] %c0, s32[] %c0)
}
'''

_PAGED_HLO_BAD = '''\
HloModule jit_step, is_scheduled=true

ENTRY %main.1 (p0: f32[33,16,32], p1: f32[4,32]) -> f32[33,16,32] {
  %p0 = f32[33,16,32]{2,1,0} parameter(0)
  %p1 = f32[4,32]{1,0} parameter(1)
  %copy.1 = f32[33,16,32]{2,1,0} copy(f32[33,16,32]{2,1,0} %p0)
  ROOT %add.2 = f32[33,16,32]{2,1,0} add(f32[33,16,32]{2,1,0} %copy.1, f32[33,16,32]{2,1,0} %p0)
}
'''


def _selftest():
    """The lint must catch the bad fixtures and pass the good ones."""
    import tempfile
    from . import configlint, hlolint
    from .locklint import analyze_module
    from .tracelint import ProjectIndex, TraceLinter
    failures = []

    with tempfile.TemporaryDirectory() as td:
        pkg = os.path.join(td, 'fix')
        os.makedirs(pkg)
        for name, src in (('bad_trace.py', _BAD_TRACE),
                          ('good_trace.py', _GOOD_TRACE),
                          ('bad_config.py', _BAD_CONFIG),
                          ('good_config.py', _GOOD_CONFIG),
                          ('bad_lock.py', _BAD_LOCK),
                          ('good_lock.py', _GOOD_LOCK)):
            with open(os.path.join(pkg, name), 'w') as f:
                f.write(src)
        index = ProjectIndex(root=td, package='fix')
        entries = [('fix/bad_trace.py', 'bad_kernel',
                    {'taint': 'positional'}),
                   ('fix/bad_trace.py', 'bad_knob',
                    {'taint': 'positional'}),
                   ('fix/good_trace.py', 'good_kernel',
                    {'taint': 'positional'})]
        fs = TraceLinter(index, entries=entries,
                         defvjp_modules=[]).run()
        rules = {f.rule for f in fs}
        for want in ('TRACE-ENV', 'TRACE-TIME', 'TRACE-RANDOM',
                     'TRACE-HOST-SYNC', 'TRACE-PY-BRANCH',
                     'TRACE-SHAPE-LOOP'):
            if want not in rules:
                failures.append('tracelint selftest: %s did not fire '
                                'on the bad fixture' % want)
        good = [f for f in fs if f.file.endswith('good_trace.py')]
        if good:
            failures.append('tracelint selftest: false positives on '
                            'the good fixture: %r' % good)

        registered = {'MXNET_TPU_REGISTERED_KNOB'}
        fs = configlint.run(index, registered=registered)
        bad = {f.message.split()[0] for f in fs
               if f.file.endswith('bad_config.py')}
        for want in ('MXNET_TPU_PHANTOM_KNOB', 'MXNET_TPU_GHOST_KNOB',
                     'MXNET_TPU_SHADOW_KNOB',
                     'MXNET_TPU_LOCAL_HELPER_KNOB',
                     'MXNET_TPU_DIRECT_KNOB'):
            if want not in bad:
                failures.append('configlint selftest: unregistered '
                                'read of %s not flagged' % want)
        good = [f for f in fs if f.file.endswith('good_config.py')]
        if good:
            failures.append('configlint selftest: false positives on '
                            'the good fixture: %r' % good)

        fs = analyze_module(os.path.join(pkg, 'bad_lock.py'))
        rules = {f.rule for f in fs}
        for want in ('LOCK-ORDER', 'LOCK-REENTRY', 'LOCK-CALLBACK',
                     'LOCK-EMIT', 'LOCK-UNGUARDED-WRITE'):
            if want not in rules:
                failures.append('locklint selftest: %s did not fire '
                                'on the bad fixture' % want)
        fs = analyze_module(os.path.join(pkg, 'good_lock.py'))
        if fs:
            failures.append('locklint selftest: false positives on '
                            'the good fixture: %r' % fs)

    fs = hlolint.check(_BAD_HLO, {'amp': 'bf16', 'dp': 1,
                                  'donation': True,
                                  'platform': 'tpu'},
                       program='selftest')
    rules = {f.rule for f in fs}
    for want in ('HLO-AMP-F32-MATMUL', 'HLO-DP1-COLLECTIVE',
                 'HLO-HOST-TRANSFER', 'HLO-DONATION-DROPPED'):
        if want not in rules:
            failures.append('hlolint selftest: %s did not fire on '
                            'the bad fixture' % want)

    # HLO-PALLAS rules: the synthetic TPU dump carries one flash-
    # attention kernel custom-call
    fs = hlolint.check(_PALLAS_HLO, {'pallas': ['attention'],
                                     'platform': 'tpu',
                                     'no_outfeed': True},
                       program='selftest-pallas')
    if fs:
        failures.append('hlolint selftest: false positives on the '
                        'pallas-on fixture: %r' % fs)
    fs = hlolint.check(_PALLAS_HLO, {'pallas': [], 'platform': 'tpu',
                                     'no_outfeed': True},
                       program='selftest-pallas')
    if 'HLO-PALLAS-UNEXPECTED' not in {f.rule for f in fs}:
        failures.append('hlolint selftest: HLO-PALLAS-UNEXPECTED did '
                        'not fire on a knob-off expectation')
    fs = hlolint.check(_PALLAS_HLO, {'pallas': ['attention', 'xent'],
                                     'platform': 'tpu',
                                     'no_outfeed': True},
                       program='selftest-pallas')
    if 'HLO-PALLAS-MISSING' not in {f.rule for f in fs}:
        failures.append('hlolint selftest: HLO-PALLAS-MISSING did '
                        'not fire for the absent xent family')
    fs = hlolint.check(_BAD_HLO, {'pallas': ['attention'],
                                  'platform': 'cpu'},
                       program='selftest-pallas-cpu')
    if any(f.rule == 'HLO-PALLAS-MISSING' for f in fs):
        failures.append('hlolint selftest: HLO-PALLAS-MISSING must '
                        'not fire on a CPU (interpreter-mode) dump')

    # HLO-DECODE-PAGED: page-table gather required, O(pool) copy
    # forbidden (pool here is 33 pages x 16 rows x 32 f32 = 67584 B)
    paged_expect = {'paged_decode': True, 'pool_bytes': 33 * 16 * 32
                    * 4, 'no_outfeed': True, 'platform': 'tpu'}
    fs = hlolint.check(_PAGED_HLO_GOOD, paged_expect,
                       program='selftest-paged')
    if fs:
        failures.append('hlolint selftest: false positives on the '
                        'good paged-decode fixture: %r' % fs)
    fs = hlolint.check(_PAGED_HLO_BAD, paged_expect,
                       program='selftest-paged')
    rules = [f.rule for f in fs]
    if rules.count('HLO-DECODE-PAGED') < 2:
        failures.append('hlolint selftest: HLO-DECODE-PAGED must fire '
                        'for BOTH the missing gather and the O(pool) '
                        'copy (got %r)' % rules)
    return failures


# -- fresh program builds ---------------------------------------------------


def _build_program(devices, amp, zero):
    """One tiny Dense-net ParallelTrainer step program (the same build
    path the fusion audit drives), returning its optimized HLO."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.gluon import nn
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation='relu'), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    mesh = parallel.create_mesh({'dp': devices},
                                devices=jax.devices()[:devices])
    pt = parallel.ParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.1, 'momentum': 0.9}, mesh,
        zero=zero, amp=amp, guardrail=False)
    x = nd.array(np.random.randn(8, 8).astype('float32'))
    y = nd.array(np.random.randint(0, 4, (8,)).astype('float32'))
    pt.build(x, y)
    return pt.compiled_text()


def _build_paged_decode():
    """The paged decode-step program (the serving hot loop): its HLO
    must read the KV pool through the page-table gather."""
    from mxnet_tpu.serving.decode import (PagedDecodeProgram,
                                          init_transformer_lm)
    model, params = init_transformer_lm(vocab=32, units=16, hidden=24,
                                        layers=1, heads=2, max_len=32)
    prog = PagedDecodeProgram(model, params, slots=2,
                              prefill_buckets=(8,), page_size=8)
    return prog.compile_step().as_text()


def _program_legs(devices):
    """(program_label, expect, hlo_text) for the fresh-build legs."""
    import jax
    platform = jax.default_backend()
    n = min(devices, len(jax.devices()))
    legs = [
        ('step_dp1_fp32',
         {'amp': 'off', 'dp': 1, 'donation': True, 'zero': False,
          'platform': platform},
         lambda: _build_program(1, False, False)),
        ('step_dp1_bf16',
         {'amp': 'bf16', 'dp': 1, 'donation': True,
          'platform': platform},
         lambda: _build_program(1, 'bf16', False)),
        # paged decode-step contract: page-table gather present (the
        # O(pool)-copy half self-gates to non-CPU platforms — XLA:CPU
        # lowers the undonated in-place update as a functional copy)
        ('decode_step_paged',
         {'paged_decode': True,
          'pool_bytes': 9 * 8 * 16 * 4,      # pages x ps x units x 4
          'platform': platform, 'no_outfeed': True},
         _build_paged_decode),
    ]
    if n > 1:
        legs.append(
            ('step_dp%d' % n,
             {'amp': 'off', 'dp': n, 'donation': True,
              'platform': platform},
             lambda: _build_program(n, False, False)))
        legs.append(
            ('step_dp%d_zero' % n,
             {'dp': n, 'zero': True, 'platform': platform},
             lambda: _build_program(n, False, True)))
    return legs


# -- driver -----------------------------------------------------------------


def main(argv=None):
    from . import (apply_baseline, baseline_payload, load_baseline,
                   repo_root, write_jsonl)
    from . import configlint, hlolint, locklint, tracelint
    from .registry import expect_from_config

    ap = argparse.ArgumentParser(
        prog='python -m mxnet_tpu.analysis',
        description=__doc__.split('\n\n')[0])
    ap.add_argument('--baseline', default=None,
                    help='suppression file (default: LINT_BASELINE.'
                         'json at the repo root)')
    ap.add_argument('--out', default=None,
                    help='write every finding (new + suppressed) as '
                         'mxnet_tpu.lint.v1 JSONL')
    ap.add_argument('--write-baseline', action='store_true',
                    help='rewrite the baseline from current findings '
                         '(keeps existing reasons by fingerprint)')
    ap.add_argument('--no-build', action='store_true',
                    help='skip the fresh-compile hlolint legs (pure '
                         'AST mode, no jax import)')
    ap.add_argument('--devices', type=int, default=8,
                    help='virtual device count for the dp>1 legs')
    ap.add_argument('--root', default=None,
                    help='source root to lint (default: the checkout '
                         'this package runs from)')
    ap.add_argument('--hlo', default=None,
                    help='audit ONE external HLO dump instead of the '
                         'repo (combine with --amp/--dp/--zero/'
                         '--platform/--no-donation)')
    ap.add_argument('--amp', default=None)
    ap.add_argument('--dp', type=int, default=None)
    ap.add_argument('--zero', action='store_true')
    ap.add_argument('--platform', default=None)
    ap.add_argument('--no-donation', action='store_true')
    args = ap.parse_args(argv)

    root = args.root or repo_root()

    # external-dump mode: one program, explicit expectations
    if args.hlo:
        expect = {'platform': args.platform}
        if args.amp is not None:
            expect['amp'] = args.amp
        if args.dp is not None:
            expect['dp'] = args.dp
        if args.zero:
            expect['zero'] = True
        if not args.no_donation:
            expect['donation'] = True
        with open(args.hlo) as f:
            findings = hlolint.check(f.read(), expect,
                                     program=os.path.basename(
                                         args.hlo))
        for f in findings:
            print(repr(f))
        print('%d finding(s)' % len(findings))
        return 1 if findings else 0

    print('== selftest', flush=True)
    failures = _selftest()
    for msg in failures:
        print('  FAIL %s' % msg)
    if not failures:
        print('  ok: every rule fires on bad fixtures, none on good')

    print('== source lint (tracelint + locklint + configlint)',
          flush=True)
    index = tracelint.ProjectIndex(root=root)
    findings = tracelint.TraceLinter(index).run()
    findings += locklint.LockLinter(index).run()
    findings += configlint.run(index)

    if not args.no_build:
        print('== program invariants (fresh builds, %s virtual '
              'devices)' % args.devices, flush=True)
        for label, expect, build in _program_legs(args.devices):
            try:
                text = build()
            except Exception as exc:   # noqa: BLE001 - report, not die
                findings.append(hlolint._finding(
                    'HLO-BUILD-FAILED', label,
                    'program build failed: %r' % (exc,)))
                continue
            fs = hlolint.check(text, expect, program=label)
            print('  %-16s %s  (%s)' % (
                label, 'FAIL' if fs else 'ok',
                ', '.join(sorted('%s=%r' % kv
                                 for kv in expect.items()))))
            findings += fs

    baseline_path = args.baseline or os.path.join(root,
                                                  'LINT_BASELINE.json')
    baseline = load_baseline(baseline_path)
    new, suppressed, stale = apply_baseline(findings, baseline)

    if args.out:
        write_jsonl(findings, args.out)
        print('findings written to %s' % args.out)

    if args.write_baseline:
        reasons = {fp: ent.get('reason')
                   for fp, ent in baseline.items()
                   if ent.get('reason')}
        payload = baseline_payload(findings, reasons)
        with open(baseline_path, 'w') as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write('\n')
        print('baseline rewritten: %s (%d suppressions)'
              % (baseline_path, len(payload['suppressions'])))
        return 0 if not failures else 1

    print('-' * 60)
    print('findings: %d total, %d suppressed by baseline, %d NEW'
          % (len(findings), len(suppressed), len(new)))
    for ent in stale:
        print('  stale suppression (fixed? prune it): %s %s %s'
              % (ent.get('rule'), ent.get('file'),
                 ent.get('fingerprint')))
    for f in new:
        print('  NEW %s' % repr(f))
    if new or failures:
        print('FAIL: %d new finding(s), %d selftest failure(s) — fix '
              'them or suppress with an annotated entry in %s'
              % (len(new), len(failures), baseline_path))
        return 1
    print('OK: no new findings')
    return 0


if __name__ == '__main__':
    sys.exit(main())
