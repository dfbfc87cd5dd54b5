"""Hand-written Pallas TPU kernels for the memory-bound roofline top.

The per-fusion roofline audit (observability/roofline.py, PR 7) ranks
the step programs' byte movers, and the top of the ranking has been
stable since bench round 3: attention softmax chains, normalization
epilogues, and the softmax+cross-entropy loss head — exactly the
memory-intensive clusters PAPERS "FusionStitching" and "Operator
Fusion in XLA" show XLA's fusion heuristics leave un-stitched. This
package spends that ranking on kernels: each cluster becomes ONE
Mosaic kernel that keeps its intermediates in VMEM instead of round-
tripping activation-sized buffers through HBM.

Kernel families (each with a ``jax.custom_vjp`` backward and an
interpreter-mode CPU path — the NMS pattern: the same kernel logic is
exercised everywhere, Mosaic-compiled only on TPU):

  * :mod:`.attention` — blockwise online-softmax flash attention
    (never materializes the (S, S) scores matrix), the single-token
    decode variant that reads the slot KV cache in place, and the
    paged decode walk that reads a sequence's live pages through its
    page table (not behind the knob: the paged step runs it wherever
    it is placed on a TPU);
  * :mod:`.epilogue` — fused normalize/activation/residual-add
    elementwise epilogues (BatchNorm apply, activation save-output
    cores, add+relu);
  * :mod:`.xent` — one-pass fused softmax + cross-entropy head
    (max / exp-sum / label pick in a single read of the logits),
    composing with the saved-log-probs vjp;
  * :mod:`.nms` — the seed-era greedy NMS kernel (moved here from
    ``ops/pallas_kernels.py``; that module remains as a shim).

Build-time knob (docs/PERFORMANCE.md "Hand-written kernels")::

    MXNET_TPU_PALLAS=attention,epilogue,xent   # pick families
    MXNET_TPU_PALLAS=1                         # all families
    MXNET_TPU_PALLAS=0                         # (default) off

The knob is snapshotted through :mod:`mxnet_tpu.ops.traceknobs` and
folded into every jit cache key (the PR 10 contract): op bodies and
gluon blocks consult :func:`enabled` — snapshot first, live config
only as the bare-``jax.jit`` fallback — so flipping the knob re-jits
bit-identically instead of being latched by whichever program traced
first. Knob-off programs are byte-identical to pre-kernel builds.

AMP composition: every kernel accepts bf16/fp16 inputs and
accumulates in float32 inside the kernel (the MXU contract), emitting
the input dtype. Mesh composition: kernels are per-shard pure
functions — safe under shard_map / pjit partitioning.
"""
from __future__ import annotations

__all__ = ['KINDS', 'parse_spec', 'resolve_spec', 'enabled',
           'interpret_mode', 'flash_attention', 'flash_decode_attention',
           'flash_paged_decode_attention', 'paged_walk_fits',
           'online_softmax_block', 'fused_bn_apply', 'fused_act',
           'fused_add_act', 'fused_softmax_xent_rows', 'greedy_nms_keep',
           'selftest']

# the three audit-ranked kernel families the knob can enable
KINDS = ('attention', 'epilogue', 'xent')

_TRUE = frozenset(('1', 'true', 'all', 'on', 'yes'))
_FALSE = frozenset(('', '0', 'false', 'off', 'none', 'no'))


def parse_spec(spec):
    """Parse a ``MXNET_TPU_PALLAS`` value into a sorted tuple of
    enabled kernel families. Accepts ``1``/``0`` style booleans or a
    comma list of family names; unknown names raise (a typo must not
    silently disable a kernel)."""
    if spec is None:
        return ()
    if isinstance(spec, (tuple, list, frozenset, set)):
        kinds = set(str(s).strip().lower() for s in spec)
    else:
        text = str(spec).strip().lower()
        if text in _TRUE:
            return tuple(KINDS)
        if text in _FALSE:
            return ()
        kinds = set(p.strip() for p in text.split(',') if p.strip())
    bad = kinds - set(KINDS)
    if bad:
        raise ValueError(
            'MXNET_TPU_PALLAS: unknown kernel family %s (valid: %s, '
            'or 1/0)' % (sorted(bad), ', '.join(KINDS)))
    return tuple(k for k in KINDS if k in kinds)


def resolve_spec(spec=None):
    """Canonical string form of the knob ('off' or a comma list) —
    what the fusion-audit config block and manifests record."""
    kinds = parse_spec(spec) if spec is not None else _live_kinds()
    return ','.join(kinds) if kinds else 'off'


def _live_kinds():
    """HOST-time read of the live knob (build-time only — never call
    under trace; trace-time callers go through :func:`enabled`)."""
    from .. import traceknobs
    snap = traceknobs.current()
    if snap is not None:
        return snap.pallas
    from ...config import get as _cfg
    return parse_spec(_cfg('MXNET_TPU_PALLAS'))


def enabled(kind):
    """True when the ``kind`` kernel family is enabled. Consults the
    trace entry point's build-time :mod:`~mxnet_tpu.ops.traceknobs`
    snapshot first (the trace-purity contract, docs/ANALYSIS.md); the
    live config read only remains as the fallback for bare ``jax.jit``
    over raw ops where no snapshot scope is installed."""
    if kind not in KINDS:
        raise ValueError('unknown pallas kernel family %r' % (kind,))
    from .. import traceknobs
    snap = traceknobs.current()
    if snap is not None:
        return kind in snap.pallas
    from ...config import get as _cfg
    return kind in parse_spec(_cfg('MXNET_TPU_PALLAS'))


def interpret_mode():
    """False exactly when the computation being traced is placed on a
    TPU: there a kernel is Mosaic-compiled, never interpreted and never
    replaced by its reference. Everywhere else (the CPU test rig, and
    the serving CPU replay that runs under ``jax.default_device(cpu)``
    while the default backend is still ``tpu``) the same kernel logic
    runs through the Pallas interpreter."""
    import jax
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend() != 'tpu'
    return getattr(dev, 'platform', dev) != 'tpu'


# re-exports: the kernel families — LAZY (module __getattr__), so the
# knob-off gating calls (`enabled()` from every Activation/BatchNorm/
# loss trace) never pay the jax.experimental.pallas import; kernel
# modules load on first actual kernel use
_LAZY_EXPORTS = {
    'flash_attention': '.attention',
    'flash_decode_attention': '.attention',
    'flash_paged_decode_attention': '.attention',
    'paged_walk_fits': '.attention',
    'online_softmax_block': '.attention',
    'fused_bn_apply': '.epilogue',
    'fused_act': '.epilogue',
    'fused_add_act': '.epilogue',
    'fused_softmax_xent_rows': '.xent',
    'greedy_nms_keep': '.nms',
}


def __getattr__(name):
    mod = _LAZY_EXPORTS.get(name)
    if mod is None:
        raise AttributeError('module %r has no attribute %r'
                             % (__name__, name))
    import importlib
    return getattr(importlib.import_module(mod, __name__), name)


def selftest(out=None):
    """Interpreter-mode kernel equivalence selftest (the ``kernels``
    CI stage): every kernel family's forward and backward against its
    reference XLA math. See :mod:`.__main__`."""
    from .__main__ import run_selftest
    return run_selftest(out=out)
