"""DecodeProgram: a model frozen into AOT prefill + decode-step
executables over a preallocated slot cache.

The TVM-style phase separation freeze.py applies to one-shot
inference, applied to generation: all tracing and compilation happens
at freeze/warmup time, request time only *runs*. Two program kinds:

  * **prefill** — one AOT executable per prompt-length bucket
    (powers-of-two ladder, ``MXNET_TPU_SERVE_PREFILL_BUCKETS``); a
    request's prompt pads up to its bucket, computes the sequence
    state/KV prefix, and lands it in one cache slot
    (``lax.dynamic_update_slice``), emitting the first generated
    token.
  * **decode step** — exactly ONE fixed-shape executable: every
    in-flight slot advances one token against the donated cache (a
    paged step up to ``MAX_RUN`` tokens a call, as many as a runtime
    operand asks). The shape never depends on which sequences are live
    or how many steps a call runs, so continuous batching joins/leaves
    without a single retrace. Total programs for any workload:
    ``len(prefill ladder) + 1``.

Cache buffers are donated on accelerator backends — XLA updates the
KV/state arrays in place instead of copying ``slots × max_len × units``
floats per token. ``trace_counts`` ticks only while jax traces, so
the selftest proves request-time zero-retrace the same way freeze.py
does, including after an artifact reload in a fresh process.

Persistence rides the ``mxnet_tpu.frozen.v1`` schema with
``kind: "decode"`` (``load_frozen`` dispatches): MANIFEST + params.npz
+ serialized prefill/step executables; a jax-version/platform mismatch
re-jits and records ``retraced_buckets``.

The CPU fallback (:meth:`fallback_generate`) replays the SAME cell /
attention math eagerly on the CPU backend through a single-slot cache
— degraded-mode tokens are bit-identical to accelerator tokens, so a
breaker trip changes latency, never output.
"""
from __future__ import annotations

import json
import os
import pickle
import threading
import time

import numpy as onp

from ...observability.spans import span as _span
from ..bucket import BucketPolicy, default_buckets
from .cache import cache_avals, cache_bytes, init_cache
from .model import (DecodeModel, FamilyUnsupported, from_gluon_rnn_lm,
                    model_from_config)
from .paged import (TRASH_PAGE, init_pool, pages_for, pool_avals,
                    pool_bytes, slot_state_bytes, write_prefill_pages)
from . import paged as _paged

# per thread: ``hook``, a callable that :meth:`DecodeProgram._call` runs
# once a program is enqueued and before it blocks on the tokens. The
# engine's scheduler thread hands its streams their tokens there.
while_device_runs = threading.local()

__all__ = ['DecodeProgram', 'PagedDecodeProgram', 'freeze_decode',
           'load_decode', 'MAX_RUN']

_DECODE_KIND = 'decode'

# the most decode steps one call of a paged step program runs
# (PagedDecodeProgram.run_steps)
MAX_RUN = 4


def _knob(name, default):
    try:
        from ... import config as _config
        v = _config.get(name)
        return default if v is None else v
    except Exception:
        return default


def _pallas_resolve():
    """Canonical MXNET_TPU_PALLAS value at build time ('off' or a
    comma list) — recorded in the manifest for provenance. One
    canonicalization rule for the manifest, the program keys, and the
    fusion-audit config block: ops.pallas.resolve_spec."""
    from ...ops.pallas import resolve_spec
    return resolve_spec()


def _instrument_compile(key, seconds):
    try:
        from ... import observability as _obs
        if _obs.enabled():
            _obs.serving_instruments().compiles.inc()
            _obs.record_event('serve_compile', bucket=key,
                              seconds=round(seconds, 4))
    except Exception:
        pass


class DecodeProgram:
    """AOT prefill/step programs + slot cache for one decode model.

    Every token-emitting program has one signature: its last operand
    is ``extras``, a dict pytree of fixed-shape arrays. Temperature,
    top-p and a PRNG key a row are always in it (``temps == 0`` rows
    are greedy, byte for byte the argmax). ``logit_mask`` additionally
    compiles a per-slot additive ``(slots, vocab)`` grammar/JSON mask
    argument at the same point (``MXNET_TPU_SERVE_SAMPLE_MASK``; off
    by default — it is vocab-sized per-step traffic). ``adapter_spec``
    (an :class:`~..adapters.AdapterSpec`) sizes a low-rank adapter pool
    argument plus per-slot int32 indices so one program serves every
    resident fine-tune — switching adapters is an array-value change,
    never a retrace. Both are recorded in the manifest; loading an
    artifact reconstructs the signature it was compiled with.
    """

    # wall seconds its callers have spent in the blocking reads of
    # ``_call``: the decode engine's slow-tick record takes a tick's share
    read_s = 0.0

    def __init__(self, model, params, slots=None, prefill_buckets=None,
                 name=None, donate=None, emit_logits=True,
                 logit_mask=None, adapter_spec=None):
        import jax
        import jax.numpy as jnp
        if not isinstance(model, DecodeModel):
            raise TypeError('DecodeProgram wraps a DecodeModel; got %s'
                            % type(model).__name__)
        self.model = model
        self.name = name or '%s-decoder' % model.family
        self.slots = int(slots if slots is not None
                         else _knob('MXNET_TPU_SERVE_DECODE_SLOTS', 8))
        if self.slots < 1:
            raise ValueError('slots must be >= 1')
        if prefill_buckets is None:
            spec = _knob('MXNET_TPU_SERVE_PREFILL_BUCKETS', None)
            prefill_buckets = spec or default_buckets(
                min(int(_knob('MXNET_TPU_SERVE_MAX_PREFILL', 64)),
                    model.max_len - 1))
        # BucketPolicy validates the ladder; batch ladder unused here
        self.policy = BucketPolicy(buckets=prefill_buckets)
        if self.policy.max_batch >= model.max_len:
            raise ValueError(
                'top prefill bucket %d leaves no room to generate '
                'within max_len %d'
                % (self.policy.max_batch, model.max_len))
        self.max_len = model.max_len
        # leaves that arrive on the device stay there (a host copy of
        # them is made only where one is asked for: save())
        self._params_host = {k: v for k, v in params.items()
                             if isinstance(v, onp.ndarray)}
        self._params = {k: jnp.asarray(v) for k, v in params.items()}
        try:
            self._spec = model.cache_spec()
        except FamilyUnsupported:
            # a family with no slot cache serves paged only, and has no
            # single-slot CPU replay (fallback_generate)
            if not self.paged:
                raise
            self._spec = None
        if donate is None:
            donate = jax.default_backend() != 'cpu'
        self._donate = bool(donate)
        self.emit_logits = bool(emit_logits)
        self.logit_mask = bool(
            logit_mask if logit_mask is not None
            else _knob('MXNET_TPU_SERVE_SAMPLE_MASK', False))
        self.adapter_spec = adapter_spec
        self._zero_apool_cached = None
        self._compiled = {}          # key -> jax Compiled
        self._loaded = {}            # key -> deserialized Compiled
        self._cpu_params = None
        self._build_lock = threading.Lock()
        self.trace_counts = {}       # key -> python traces observed
        self.compile_seconds = {}
        self.retraced_buckets = []

    # -- program construction ----------------------------------------------

    @property
    def _params_np(self):
        """The parameters as host arrays."""
        return {k: self._params_host[k] if k in self._params_host
                else onp.asarray(v) for k, v in self._params.items()}

    @property
    def prefill_buckets(self):
        return self.policy.buckets

    @property
    def compile_count(self):
        return len(set(self._compiled) | set(self._loaded))

    # the slot cache reserves slots × max_len whether a sequence uses
    # it or not; PagedDecodeProgram overrides `paged` and the cache
    # accounting/aval hooks below
    paged = False

    def cache_bytes(self):
        """Static per-engine cache footprint (docs/SERVING.md) — the
        REAL device residency: slot programs preallocate
        ``slots × max_len`` rows, paged programs report the pool."""
        return cache_bytes(self._spec, self.slots)

    def per_sequence_bytes(self, seq_len=None):
        """Worst-case cache bytes one sequence reserves: the whole
        per-slot allocation regardless of its actual length (the
        memory wall the paged layout breaks)."""
        del seq_len
        return cache_bytes(self._spec, 1)

    def new_cache(self):
        """Fresh preallocated device cache for ``slots`` sequences."""
        return init_cache(self._spec, self.slots)

    def _cache_avals(self):
        return cache_avals(self._spec, self.slots)

    # -- sampling / adapter extras (one dict pytree, the last operand
    # of every token-emitting program) --------------------------------------

    def _extra_avals(self, kind):
        """Aval pytree of the ``extras`` argument for one program
        kind ('prefill' | 'step' | 'verify'). A feature that was not
        compiled in is an absent key: no mask without ``logit_mask``,
        no adapter arrays without an ``adapter_spec``."""
        import jax
        extras = {}
        S, V = self.slots, self.model.vocab
        rows = 1 if kind == 'prefill' else S
        extras['temps'] = jax.ShapeDtypeStruct((rows,), 'float32')
        extras['top_ps'] = jax.ShapeDtypeStruct((rows,), 'float32')
        extras['keys'] = jax.ShapeDtypeStruct(self._keys_shape(kind),
                                              'uint32')
        if self.logit_mask:
            extras['masks'] = jax.ShapeDtypeStruct((rows, V), 'float32')
        if self.adapter_spec is not None:
            extras['apool'] = self.adapter_spec.avals()
            extras['aidx'] = jax.ShapeDtypeStruct(
                () if kind == 'prefill' else (S,), 'int32')
        return extras

    def _zero_apool(self):
        """All-zero device adapter pool — the default when no
        AdapterPool is attached (every slot gathers the zero base)."""
        with self._build_lock:
            if self._zero_apool_cached is None:
                import jax.numpy as jnp
                self._zero_apool_cached = {
                    k: (jnp.asarray(a), jnp.asarray(b))
                    for k, (a, b) in
                    self.adapter_spec.zero_tree().items()}
            return self._zero_apool_cached

    def _extra_args(self, kind, temps=None, top_ps=None, keys=None,
                    masks=None, apool=None, aidx=None):
        """Concrete ``extras`` for one call; None fields take the
        neutral value (greedy, no mask, base adapter)."""
        extras = {}
        S, V = self.slots, self.model.vocab
        rows = 1 if kind == 'prefill' else S
        extras['temps'] = (
            onp.zeros((rows,), 'float32') if temps is None
            else onp.asarray(temps, 'float32').reshape(rows))
        extras['top_ps'] = (
            onp.ones((rows,), 'float32') if top_ps is None
            else onp.asarray(top_ps, 'float32').reshape(rows))
        kshape = self._keys_shape(kind)
        extras['keys'] = (
            onp.zeros(kshape, 'uint32') if keys is None
            else onp.asarray(keys, 'uint32').reshape(kshape))
        if self.logit_mask:
            extras['masks'] = (
                onp.zeros((rows, V), 'float32') if masks is None
                else onp.asarray(masks, 'float32').reshape(rows, V))
        if self.adapter_spec is not None:
            extras['apool'] = apool if apool is not None \
                else self._zero_apool()
            if kind == 'prefill':
                extras['aidx'] = onp.int32(0 if aidx is None else aidx)
            else:
                extras['aidx'] = (
                    onp.zeros((S,), 'int32') if aidx is None
                    else onp.asarray(aidx, 'int32').reshape(S))
        return extras

    def _keys_shape(self, kind):
        """A call's PRNG keys: one a row, and one a position of the
        verify's chunk."""
        if kind == 'verify':
            return (self.slots, self.spec_k + 1, 2)
        return (1 if kind == 'prefill' else self.slots, 2)

    @staticmethod
    def _gather_ad(extras):
        """Per-call adapter view for the model: pool rows selected by
        the (scalar or per-slot) indices — a 2-D (r, in)/(out, r)
        pair at prefill, per-slot 3-D stacks at step/verify."""
        if 'apool' not in extras:
            return None
        aidx = extras['aidx']
        return {k: (a[aidx], b[aidx])
                for k, (a, b) in extras['apool'].items()}

    # verify programs exist on the paged subclass; the base class
    # only needs the attribute for _extra_avals' key-shape arithmetic
    spec_k = 0

    def _prefill_fn(self, key):
        from .sampling import sample_tokens
        counts = self.trace_counts
        model, emit = self.model, self.emit_logits
        gather = self._gather_ad
        # the adapter operand exists only when an adapter_spec was
        # compiled in (never for families without lora_targets, e.g.
        # RNNLM, whose prefill/step take no ad argument)
        ad_on = self.adapter_spec is not None

        def fn(params, cache, tokens, length, slot, extras):
            counts[key] = counts.get(key, 0) + 1
            if ad_on:
                cache, logits = model.prefill(params, cache, tokens,
                                              length, slot,
                                              gather(extras))
            else:
                cache, logits = model.prefill(params, cache, tokens,
                                              length, slot)
            tok = sample_tokens(logits[None], extras['temps'],
                                extras['top_ps'], extras['keys'],
                                extras.get('masks'))[0]
            return (cache, tok, logits) if emit else (cache, tok)
        return fn

    def _step_fn(self, key):
        from .sampling import sample_tokens
        counts = self.trace_counts
        model, emit = self.model, self.emit_logits
        gather = self._gather_ad
        ad_on = self.adapter_spec is not None

        def fn(params, cache, tokens, positions, extras):
            counts[key] = counts.get(key, 0) + 1
            if ad_on:
                cache, logits = model.step(params, cache, tokens,
                                           positions, gather(extras))
            else:
                cache, logits = model.step(params, cache, tokens,
                                           positions)
            tok = sample_tokens(logits, extras['temps'],
                                extras['top_ps'], extras['keys'],
                                extras.get('masks'))
            return (cache, tok, logits) if emit else (cache, tok)
        return fn

    def _param_avals(self):
        import jax
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in self._params.items()}

    def _program_key(self, base):
        """Compiled-program key, extended with the Pallas kernel knob
        (the PR 10 contract: build-time snapshot folded into cache
        keys so a flip re-jits instead of latching). The plain base
        key at knob-off keeps old artifacts' program names stable."""
        tag = _pallas_resolve()
        return base if tag == 'off' else '%s:pallas-%s' % (base, tag)

    # prefix of every XLA module name this program compiles; the engine
    # sets 'draft_' on the program it is given as a speculative draft
    module_prefix = ''

    def _build(self, key, name, fn, *avals):
        """jit -> lower -> compile with the freeze.py accounting.
        ``name`` becomes the XLA module's (``jit_<name>``): what a
        profiler trace and the benchmark's readers tell the programs
        apart by. It is no part of ``key``, which names the program in
        ``_compiled`` and in a saved artifact."""
        import time
        import jax
        from ...ops import traceknobs as _traceknobs
        prog = self._compiled.get(key) or self._loaded.get(key)
        if prog is not None:
            return prog
        with self._build_lock:
            prog = self._compiled.get(key) or self._loaded.get(key)
            if prog is not None:
                return prog
            t0 = time.perf_counter()
            knobs = _traceknobs.snapshot()
            fn.__name__ = fn.__qualname__ = self.module_prefix + name
            jitted = jax.jit(fn, donate_argnums=(1,)) if self._donate \
                else jax.jit(fn)
            with _traceknobs.scope(knobs):
                prog = jitted.lower(
                    self._param_avals(),
                    self._cache_avals(),
                    *avals).compile()
            self.compile_seconds[key] = time.perf_counter() - t0
            self._compiled[key] = prog
        _instrument_compile(key, self.compile_seconds[key])
        return prog

    def _step_name(self):
        """The step is the one module whose name matches ``^jit_fn``
        (the benchmark's step metrics read that pattern); a draft's
        step is ``jit_draft_step``."""
        return 'step' if self.module_prefix else 'fn_step'

    def compile_prefill(self, bucket):
        import jax
        key = self._program_key('prefill:%d' % bucket)
        return self._build(key, 'prefill_b%d' % bucket,
                           self._prefill_fn(key),
                           jax.ShapeDtypeStruct((1, bucket), 'int32'),
                           jax.ShapeDtypeStruct((), 'int32'),
                           jax.ShapeDtypeStruct((), 'int32'),
                           self._extra_avals('prefill'))

    def compile_step(self):
        import jax
        key = self._program_key('step')
        return self._build(key, self._step_name(), self._step_fn(key),
                           jax.ShapeDtypeStruct((self.slots,), 'int32'),
                           jax.ShapeDtypeStruct((self.slots,), 'int32'),
                           self._extra_avals('step'))

    def warmup(self, buckets=None):
        """Compile the whole ladder + the step program (server start,
        not first request): exactly ``len(ladder) + 1`` programs."""
        for b in (buckets or self.policy.buckets):
            self.compile_prefill(b)
        self.compile_step()
        return self

    # -- execution ---------------------------------------------------------

    def _unpack(self, out):
        if self.emit_logits:
            return out
        cache, tok = out
        return cache, tok, None

    def _call(self, prog, *args):
        """One compiled call and the read of what it emitted: (cache',
        tokens np, logits np | None). Two host spans on the profiler's
        clock, for the scheduler thread that calls this (engine.py):
        ``eng.tick.dispatch`` up to the call's return (arguments
        parsed and put, program enqueued) and ``eng.tick.read_tokens``
        for the blocking read, during which the device is busy. What
        the calling thread left in ``while_device_runs.hook`` runs
        between the two: host work the device need not wait for."""
        with _span('eng.tick.dispatch'):
            cache, toks, logits = self._unpack(prog(self._params, *args))
            hook = getattr(while_device_runs, 'hook', None)
            if hook is not None:
                hook()
        with _span('eng.tick.read_tokens'):
            t0 = time.perf_counter()
            toks = onp.asarray(toks)
            self.read_s += time.perf_counter() - t0
        return cache, toks, \
            None if logits is None else onp.asarray(logits)

    def run_prefill(self, cache, tokens, slot, temps=None,
                    top_ps=None, keys=None, masks=None, apool=None,
                    aidx=None):
        """Pad ``tokens`` (1-D int prompt) to its bucket and land the
        prefix in ``slot``. Returns (cache', first_token int, logits
        np (V,) | None). Sampling/adapter kwargs are optional array
        values for the compiled ``extras`` argument; omitted fields
        take the neutral value (greedy, base adapter)."""
        tokens = onp.asarray(tokens, 'int32').reshape(-1)
        n = tokens.shape[0]
        if n < 1:
            raise ValueError('empty prompt')
        bucket = self.policy.bucket_for(n)   # ValueError when too long
        padded = onp.zeros((1, bucket), 'int32')
        padded[0, :n] = tokens
        prog = self.compile_prefill(bucket)
        cache, tok, logits = self._call(
            prog, cache, padded, onp.int32(n), onp.int32(slot),
            self._extra_args('prefill', temps, top_ps, keys, masks,
                             apool, aidx))
        return cache, int(tok), logits

    def run_step(self, cache, tokens, positions, temps=None,
                 top_ps=None, keys=None, masks=None, apool=None,
                 aidx=None):
        """Advance every slot one token. Returns (cache', tokens np
        (slots,), logits np (slots, V) | None)."""
        prog = self.compile_step()
        return self._call(
            prog, cache,
            onp.asarray(tokens, 'int32').reshape(self.slots),
            onp.asarray(positions, 'int32').reshape(self.slots),
            self._extra_args('step', temps, top_ps, keys, masks,
                             apool, aidx))

    def max_prompt_len(self):
        return self.policy.max_batch

    # -- live migration (seqstate export/import) ----------------------------

    def export_slot_state(self, cache, slot):
        """Host snapshot of one slot's O(1) recurrent state, keyed by
        cache entry name. Migration is a rare path: a plain host read,
        no compiled program, zero impact on the step program's
        zero-retrace contract."""
        return {name: onp.asarray(arr[int(slot)])
                for name, arr in cache.items()}

    def import_slot_state(self, cache, state, slot):
        """Land a host snapshot from :meth:`export_slot_state` into
        ``slot`` of this engine's cache. Returns the new cache."""
        import jax.numpy as jnp
        from .cache import write_slot
        out = dict(cache)
        for name, arr in cache.items():
            if name not in state:
                raise ValueError('slot state missing cache entry %r'
                                 % (name,))
            row = onp.asarray(state[name])
            if tuple(row.shape) != tuple(arr.shape[1:]):
                raise ValueError(
                    'slot state entry %r shape %r != per-slot shape %r'
                    % (name, tuple(row.shape), tuple(arr.shape[1:])))
            out[name] = write_slot(arr, jnp.asarray(
                row.astype(arr.dtype, copy=False)), int(slot))
        return out

    # -- CPU fallback (degraded serving) ------------------------------------

    def fallback_generate(self, tokens, max_new, eos_id=None,
                          temperature=0.0, top_p=1.0, seed=0,
                          ad=None):
        """Eagerly decode on the CPU backend through a single-slot
        cache — the degraded path sequences complete on when the
        accelerator program is the thing that died. Same math, same
        emission rule (greedy at ``temperature == 0``; otherwise the
        position-keyed sampler), so the tokens are bit-identical to
        the accelerator path. ``ad`` is an optional 2-D adapter tree
        ``{target: (A, B)}`` — the degraded path for adapter
        traffic."""
        import jax
        import jax.numpy as jnp
        from .sampling import key_for, sample_tokens
        if self._spec is None:
            raise FamilyUnsupported(
                self.model.family, 'the CPU fallback (fallback_generate '
                'replays through a single-slot cache, which the family '
                'does not have)')
        cpu = jax.devices('cpu')[0]
        with self._build_lock:
            if self._cpu_params is None:
                self._cpu_params = {k: jax.device_put(v, cpu)
                                    for k, v in self._params.items()}
        tokens = [int(t) for t in onp.asarray(tokens).reshape(-1)]
        temperature = float(temperature)

        def pick(row, pos):
            if temperature <= 0:
                return int(jnp.argmax(row))
            return int(sample_tokens(
                jnp.asarray(row)[None],
                onp.asarray([temperature], 'float32'),
                onp.asarray([top_p], 'float32'),
                key_for(seed, pos)[None])[0])

        # RNN families take no adapter argument; only thread ``ad``
        # through when one was actually supplied
        adarg = (ad,) if ad is not None else ()
        out = []
        with jax.default_device(cpu):
            cache = init_cache(self._spec, 1)
            prompt = jnp.asarray([tokens], 'int32')
            cache, logits = self.model.prefill(
                self._cpu_params, cache, prompt,
                jnp.asarray(len(tokens), 'int32'),
                jnp.asarray(0, 'int32'), *adarg)
            tok = pick(logits, len(tokens) - 1)
            pos = len(tokens)
            while True:
                out.append(tok)
                if (eos_id is not None and tok == eos_id) \
                        or len(out) >= max_new \
                        or pos + 1 >= self.max_len:
                    break
                cache, logits = self.model.step(
                    self._cpu_params, cache,
                    jnp.asarray([tok], 'int32'),
                    jnp.asarray([pos], 'int32'), *adarg)
                tok = pick(logits[0], pos)
                pos += 1
        return out

    # -- persistence (mxnet_tpu.frozen.v1, kind=decode) ---------------------

    def save(self, path, include_programs=True):
        """Write the decode artifact::

            <path>/MANIFEST.json           schema + kind=decode +
                                           model config + ladders
            <path>/params.npz              model parameters
            <path>/programs/prefill_<S>.bin
            <path>/programs/step.bin       serialized executables
        """
        import jax
        from ...resilience.checkpoint import atomic_write_bytes
        from ..freeze import FROZEN_SCHEMA
        os.makedirs(path, exist_ok=True)
        import io as _io
        buf = _io.BytesIO()
        onp.savez(buf, **self._params_np)
        atomic_write_bytes(os.path.join(path, 'params.npz'),
                           buf.getvalue())
        programs = {}
        if include_programs:
            from jax.experimental import serialize_executable
            os.makedirs(os.path.join(path, 'programs'), exist_ok=True)
            for key in sorted(set(self._compiled) | set(self._loaded)):
                prog = self._compiled.get(key) or self._loaded.get(key)
                fname = 'programs/%s.bin' % key.replace(':', '_')
                try:
                    blob = pickle.dumps(
                        serialize_executable.serialize(prog))
                except Exception:
                    continue     # artifact still loads; key re-jits
                atomic_write_bytes(os.path.join(path, fname), blob)
                programs[key] = fname
        manifest = {
            'schema': FROZEN_SCHEMA,
            'kind': _DECODE_KIND,
            'name': self.name,
            'family': self.model.family,
            'config': self.model.config,
            'slots': self.slots,
            'prefill_buckets': list(self.policy.buckets),
            'emit_logits': self.emit_logits,
            'donate': self._donate,
            # what the extras operand holds beside the sampling law:
            # load() must reconstruct it exactly or the serialized
            # executables stop matching
            'logit_mask': self.logit_mask,
            'adapter': (None if self.adapter_spec is None
                        else self.adapter_spec.to_manifest()),
            'cache_bytes': self.cache_bytes(),
            'jax_version': jax.__version__,
            'platform': jax.default_backend(),
            # provenance: the Pallas kernel knob the programs were
            # built under (the program keys carry it too)
            'pallas': _pallas_resolve(),
            'programs': programs,
        }
        manifest.update(self._manifest_extra())
        atomic_write_bytes(
            os.path.join(path, 'MANIFEST.json'),
            (json.dumps(manifest, indent=1, sort_keys=True)
             + '\n').encode())
        return path

    def _manifest_extra(self):
        """Layout-specific manifest fields (paged artifacts record
        their page geometry so `load` re-dispatches)."""
        return {}

    @classmethod
    def load(cls, path):
        """Reload a decode artifact; executables deserialize when jax
        version, platform and program signature match, else the key
        re-jits on first use and lands in ``retraced_buckets``.
        Dispatches on the manifest: paged artifacts reload as
        :class:`PagedDecodeProgram`."""
        import jax
        with open(os.path.join(path, 'MANIFEST.json')) as f:
            manifest = json.load(f)
        from ..freeze import FROZEN_SCHEMA, load_executable
        if manifest.get('schema') != FROZEN_SCHEMA or \
                manifest.get('kind') != _DECODE_KIND:
            raise ValueError(
                'not a %s decode artifact: schema=%r kind=%r at %s'
                % (FROZEN_SCHEMA, manifest.get('schema'),
                   manifest.get('kind'), path))
        params = {}
        with onp.load(os.path.join(path, 'params.npz')) as z:
            for key in z.files:
                params[key] = z[key]
        model = model_from_config(manifest['family'],
                                  manifest['config'])
        kwargs = {}
        if manifest.get('paged'):
            target = PagedDecodeProgram
            kwargs = {'page_size': manifest['page_size'],
                      'pages': manifest['pages'],
                      'spec_k': manifest.get('spec_k', 0)}
        else:
            target = DecodeProgram
        aspec = None
        if manifest.get('adapter'):
            from ..adapters import AdapterSpec
            aspec = AdapterSpec.from_manifest(manifest['adapter'])
        prog = target(model, params, slots=manifest['slots'],
                      prefill_buckets=manifest['prefill_buckets'],
                      name=manifest.get('name'),
                      donate=manifest.get('donate'),
                      emit_logits=manifest.get('emit_logits', True),
                      logit_mask=manifest.get('logit_mask', False),
                      adapter_spec=aspec,
                      **kwargs)
        # an artifact from before every program took ``extras``: its
        # manifest says ``sample_args: false`` or, older still, has no
        # key of the signature at all. Its executables were compiled
        # for a signature that is gone, so they take the road of a
        # jax-version mismatch: not loaded, re-jitted on first use
        env_ok = (manifest.get('jax_version') == jax.__version__
                  and manifest.get('platform') == jax.default_backend()
                  and manifest.get('sample_args',
                                   'logit_mask' in manifest))
        # a paged step compiled for another run length, or before the
        # ``steps`` operand, has another signature
        step_ok = not manifest.get('paged') \
            or manifest.get('max_run') == prog.max_run
        for key, fname in (manifest.get('programs') or {}).items():
            if not env_ok or (key.split(':')[0] == 'step'
                              and not step_ok):
                prog.retraced_buckets.append(key)
                continue
            try:
                prog._loaded[key] = load_executable(
                    os.path.join(path, fname), prog._params)
            except Exception:
                prog.retraced_buckets.append(key)
        return prog


class PagedDecodeProgram(DecodeProgram):
    """AOT prefill/step/verify programs over a paged KV pool
    (docs/SERVING.md "Paged KV cache, prefix sharing, speculative
    decoding").

    Same compiled-program discipline as the slot cache — one fixed
    shape per program kind, zero retraces after warmup — with the
    cache replaced by a page pool plus per-sequence page tables
    carried as plain ``int32`` array arguments:

      * **prefill** per bucket: writes the prompt K/V page by page to
        the host-allocated page ids (trailing padding pages hit the
        reserved trash page);
      * **step** (ONE program): every slot advances one token; its
        K/V view is a gather through its page table, its row write is
        ``(table[pos // page_size], pos % page_size)``;
      * **copy_page** (ONE program): the copy-on-write primitive —
        O(page), host decides when;
      * **verify** (ONE program, only when ``spec_k > 0``): the
        speculative-decoding target pass — ``spec_k + 1`` tokens per
        slot advance in one call, logits at every position.

    Total executables: ``len(ladder) + 2`` (+1 with speculation).
    Page allocation/free/refcounting/prefix-sharing live on the host,
    in the scheduler's :class:`~.paged.PageOwner`; this class only
    compiles and runs fixed shapes — page churn costs zero retraces.
    Page ids and tables arrive as the owner hands them out: bare where
    the model has one kind of layer, ``{'full': ..., 'window': ...}``
    where it has two (:meth:`_by_kind`). A model with recurrent state
    (``page_spec.slot_entries``) keeps it in ``(slots, ...)`` arrays of
    the same donated pytree: a prefill's page ids then carry
    ``'slot'``, the row it rewrites, and the step takes no table for
    them.
    """

    paged = True

    def __init__(self, model, params, slots=None, prefill_buckets=None,
                 name=None, donate=None, emit_logits=True,
                 page_size=None, pages=None, spec_k=None,
                 logit_mask=None, adapter_spec=None):
        if not getattr(model, 'supports_paging', False):
            raise TypeError(
                'family %r does not support a paged cache (an RNN '
                'carries O(1) state per slot — there is no KV history '
                'to page); use DecodeProgram' % (model.family,))
        super().__init__(model, params, slots=slots,
                         prefill_buckets=prefill_buckets, name=name,
                         donate=donate, emit_logits=emit_logits,
                         logit_mask=logit_mask,
                         adapter_spec=adapter_spec)
        self.page_size = int(
            page_size if page_size is not None
            else _knob('MXNET_TPU_SERVE_PAGE_SIZE', 16))
        self.page_spec = model.paged_spec(self.page_size)
        self.max_pages = self.page_spec.max_pages
        # two kinds of layer (paged.PagedCacheSpec): a sliding-window
        # layer's table is a ring of ``window_pages`` columns and its
        # pools hold every slot's ring plus the trash page; 0 where
        # the model has one kind
        self.window_pages = self.page_spec.window_pages
        self.window_pool_pages = self.slots * self.window_pages + 1 \
            if self.window_pages else 0
        self._n_stats = len(getattr(model, 'step_stats', ()))
        self.last_step_stats = {}
        # host-side counts of the last prefill (model.prefill_counts:
        # a function of its bucket), for the scheduler to book
        self.last_prefill_stats = {}
        if pages is None:
            # default pool = the slot cache's worst-case capacity
            # (every slot filling max_len) + the trash page; shrink it
            # to trade capacity for HBM, grow it to admit more
            # sequences at the same per-sequence risk
            pages = self.slots * self.max_pages + 1
        self.pages = int(pages)
        if self.pages < 2:
            raise ValueError('pool needs >= 2 pages (page 0 is the '
                             'reserved trash page)')
        self.spec_k = int(spec_k if spec_k is not None
                          else _knob('MXNET_TPU_SERVE_SPEC_K', 0))
        if self.spec_k < 0:
            raise ValueError('spec_k must be >= 0')
        if self.spec_k and (self.window_pages
                            or self.page_spec.slot_entries):
            # at freeze time, not at the first verify call
            raise FamilyUnsupported(
                model.family, 'paged_verify (speculative decoding, '
                'spec_k > 0) over a window layer\'s ring of pages or a '
                'recurrent state, which a rejected token cannot leave')
        # decode steps one call of the step program may run: one where a
        # longer call would lose what is one step's (the logits it emits,
        # the mask it is given) or where the host gives a ring's pages
        # back as the positions advance
        self.max_run = 1 if (self.window_pages or self.emit_logits
                             or self.logit_mask) else MAX_RUN
        self._steps_operands = {}     # (n, devices) -> _uniform_steps

    # -- accounting (the satellite fix: report POOL bytes, not the
    # slots × max_len worst case the slot cache reserved) ------------------

    def cache_bytes(self):
        return pool_bytes(self.page_spec, self.pages,
                          self.window_pool_pages, self.slots)

    def page_bytes(self):
        """Bytes one page holds across every cache entry."""
        return pool_bytes(self.page_spec, 1, 1)

    def per_sequence_bytes(self, seq_len=None):
        """Amortized cache bytes for a sequence of ``seq_len`` tokens
        (default: the worst case, max_len): pages are the granularity,
        so a 12-token sequence at page_size 16 holds ONE page, not
        max_len rows. A window layer never holds more than its ring,
        and recurrent state costs the same at every length.
        """
        n = self.model.max_len if seq_len is None else int(seq_len)
        held = pages_for(n, self.page_size)
        return pool_bytes(self.page_spec, held,
                          min(held, self.window_pages), 1)

    def new_cache(self):
        """Fresh zeroed page pool (and slot state)."""
        return init_pool(self.page_spec, self.pages,
                         self.window_pool_pages, self.slots)

    def _cache_avals(self):
        return pool_avals(self.page_spec, self.pages,
                          self.window_pool_pages, self.slots)

    def _manifest_extra(self):
        out = {'paged': True, 'page_size': self.page_size,
               'pages': self.pages, 'spec_k': self.spec_k,
               'max_run': self.max_run,
               'max_pages': self.max_pages,
               'page_bytes': self.page_bytes()}
        if self.window_pages:
            out.update(window_pages=self.window_pages,
                       window_pool_pages=self.window_pool_pages)
        if self.page_spec.slot_entries:
            out['state_bytes_per_slot'] = slot_state_bytes(self.page_spec)
        return out

    @property
    def pool_pages(self):
        """Pool size by kind of layer (what a ``PageOwner`` is built
        over)."""
        out = {'full': self.pages}
        if self.window_pages:
            out['window'] = self.window_pool_pages
        return out

    def _by_kind(self, full, window, slot=None):
        """Avals of page ids or tables as the programs take them: the
        full layers' alone where the model has one kind of layer,
        ``{'full': ..., 'window': ...}`` where it has two; a prefill's
        page ids also name the ``slot`` whose state it rewrites, where
        the cache has slot entries."""
        out = {'full': full}
        if self.window_pages:
            out['window'] = window
        if slot is not None and self.page_spec.slot_entries:
            out['slot'] = slot
        return out if len(out) > 1 else full

    def _keys_shape(self, kind):
        """The step's keys: one a row and a step of the run."""
        if kind == 'step':
            return (self.slots, self.max_run, 2)
        return super()._keys_shape(kind)

    @staticmethod
    def _each_kind(arg, fn):
        """``fn`` over page ids or tables in that form, as a
        ``PageOwner`` hands them out (a ``slot`` beside them is a
        scalar)."""
        if isinstance(arg, dict):
            return {k: onp.int32(v) if k == 'slot' else fn(v)
                    for k, v in arg.items()}
        return fn(arg)

    # -- program construction ----------------------------------------------

    def _paged_prefill_fn(self, key):
        from .sampling import sample_tokens
        counts = self.trace_counts
        model, emit = self.model, self.emit_logits
        gather = self._gather_ad

        def fn(params, pool, tokens, length, page_ids, extras):
            counts[key] = counts.get(key, 0) + 1
            pool, logits = model.paged_prefill(params, pool, tokens,
                                               length, page_ids,
                                               gather(extras))
            tok = sample_tokens(logits[None], extras['temps'],
                                extras['top_ps'], extras['keys'],
                                extras.get('masks'))[0]
            return (pool, tok, logits) if emit else (pool, tok)
        return fn

    def _paged_step_fn(self, key):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from .sampling import sample_tokens
        counts = self.trace_counts
        model, emit = self.model, self.emit_logits
        gather = self._gather_ad
        run, slots, n_stats = self.max_run, self.slots, self._n_stats

        def one(params, pool, tokens, positions, tables, extras, k):
            pool, logits, *stats = model.paged_step(
                params, pool, tokens, positions, tables, gather(extras))
            tok = sample_tokens(logits, extras['temps'],
                                extras['top_ps'], extras['keys'][:, k],
                                extras.get('masks'))
            return pool, tok, logits, stats

        def fn(params, pool, tokens, positions, tables, steps, extras):
            counts[key] = counts.get(key, 0) + 1
            if run == 1:
                pool, tok, logits, stats = one(params, pool, tokens,
                                               positions, tables,
                                               extras, 0)
                out = tok[None]
            else:
                # up to ``run`` steps, as many as ``steps['n']`` asks:
                # step k feeds the token step k - 1 produced, or the
                # prompt token ``forced[k]`` of a slot still extending;
                # a slot past its budget steps as an empty slot does,
                # at position 0 through the trash page
                def body(k, carry):
                    pool, prev, out, total = carry
                    live = k < steps['budgets']
                    fed = steps['forced'][k]
                    pool, tok, _logits, stats = one(
                        params, pool, jnp.where(fed >= 0, fed, prev),
                        jnp.where(live, positions + k, 0),
                        jax.tree.map(lambda t: jnp.where(
                            live[:, None], t, TRASH_PAGE), tables),
                        extras, k)
                    out = lax.dynamic_update_index_in_dim(out, tok, k, 0)
                    if stats:
                        total = total + stats[0]
                    return pool, tok, out, total

                # one step straight, several in a loop: a loop has the
                # compiler convert the weights it reads to bfloat16 once
                # a call, ahead of it, which GPT-1's one step at low
                # occupancy pays 0.5 ms for and a run of steps pays back
                pool, _tok, out, total = lax.cond(
                    steps['n'] > 1,
                    lambda carry: lax.fori_loop(0, steps['n'], body, carry),
                    lambda carry: body(0, carry),
                    (pool, tokens, jnp.zeros((run, slots), 'int32'),
                     jnp.zeros((n_stats,), 'int32')))
                stats = [total] if n_stats else []
            tok = out.reshape(-1)
            if stats:
                # a family's device-side counts (model.step_stats), summed
                # over the run's steps, ride behind the tokens: one
                # array, one read a call
                tok = jnp.concatenate([tok, stats[0]])
            return (pool, tok, logits) if emit else (pool, tok)
        return fn

    def _verify_fn(self, key):
        import jax.numpy as jnp
        from .sampling import sample_tokens
        counts = self.trace_counts
        model, emit = self.model, self.emit_logits
        gather = self._gather_ad

        def fn(params, pool, tokens, positions, tables, extras):
            counts[key] = counts.get(key, 0) + 1
            pool, logits = model.paged_verify(params, pool, tokens,
                                              positions, tables,
                                              gather(extras))
            # one sampler row per (slot, chunk-position): the row at
            # (s, c) uses the SAME key the plain path would at that
            # absolute position, so verify-emitted tokens are
            # bit-identical to unspeculated sampling
            S, C, V = logits.shape
            masks = extras.get('masks')
            if masks is not None:
                masks = jnp.repeat(masks, C, axis=0)
            tok = sample_tokens(
                logits.reshape(S * C, V),
                jnp.repeat(extras['temps'], C),
                jnp.repeat(extras['top_ps'], C),
                extras['keys'].reshape(S * C, 2),
                masks).reshape(S, C)
            return (pool, tok, logits) if emit else (pool, tok)
        return fn

    def _copy_fn(self, key):
        counts = self.trace_counts

        windowed = self.page_spec.window_entries
        paged = self.page_spec.entries    # slot entries have no pages

        def fn(params, pool, src, dst):
            counts[key] = counts.get(key, 0) + 1
            del params
            if not windowed:
                return {name: _paged.copy_page(arr, src, dst)
                        if name in paged else arr
                        for name, arr in pool.items()}
            # each kind of layer copies within its own pools
            kind = {name: 'window' if name in windowed else 'full'
                    for name in pool}
            return {name: _paged.copy_page(arr, src[kind[name]],
                                           dst[kind[name]])
                    for name, arr in pool.items()}
        return fn

    def compile_prefill(self, bucket):
        import jax
        key = self._program_key('prefill:%d' % bucket)
        npages = pages_for(bucket, self.page_size)
        ids = jax.ShapeDtypeStruct((npages,), 'int32')
        return self._build(key, 'prefill_b%d' % bucket,
                           self._paged_prefill_fn(key),
                           jax.ShapeDtypeStruct((1, bucket), 'int32'),
                           jax.ShapeDtypeStruct((), 'int32'),
                           self._by_kind(
                               ids, ids,
                               jax.ShapeDtypeStruct((), 'int32')),
                           self._extra_avals('prefill'))

    def compile_step(self):
        import jax
        key = self._program_key('step')
        return self._build(
            key, self._step_name(), self._paged_step_fn(key),
            jax.ShapeDtypeStruct((self.slots,), 'int32'),
            jax.ShapeDtypeStruct((self.slots,), 'int32'),
            self._by_kind(
                jax.ShapeDtypeStruct((self.slots, self.max_pages),
                                     'int32'),
                jax.ShapeDtypeStruct((self.slots, self.window_pages),
                                     'int32')),
            {'n': jax.ShapeDtypeStruct((), 'int32'),
             'budgets': jax.ShapeDtypeStruct((self.slots,), 'int32'),
             'forced': jax.ShapeDtypeStruct((self.max_run, self.slots),
                                            'int32')},
            self._extra_avals('step'))

    def compile_verify(self):
        import jax
        if not self.spec_k:
            raise ValueError('verify program needs spec_k > 0')
        key = self._program_key('verify:%d' % (self.spec_k + 1))
        return self._build(
            key, 'verify_k%d' % self.spec_k, self._verify_fn(key),
            jax.ShapeDtypeStruct((self.slots, self.spec_k + 1), 'int32'),
            jax.ShapeDtypeStruct((self.slots,), 'int32'),
            jax.ShapeDtypeStruct((self.slots, self.max_pages), 'int32'),
            self._extra_avals('verify'))

    def compile_copy_page(self):
        import jax
        key = self._program_key('copy')
        page = jax.ShapeDtypeStruct((), 'int32')
        return self._build(key, 'page_copy', self._copy_fn(key),
                           self._by_kind(page, page),
                           self._by_kind(page, page))

    def warmup(self, buckets=None):
        """Ladder + step + copy_page (+ verify under speculation):
        every program the engine can ever run, compiled up front."""
        for b in (buckets or self.policy.buckets):
            self.compile_prefill(b)
        self.compile_step()
        self.compile_copy_page()
        if self.spec_k:
            self.compile_verify()
        return self

    # -- execution ---------------------------------------------------------

    def run_prefill(self, pool, tokens, page_ids, temps=None,
                    top_ps=None, keys=None, masks=None, apool=None,
                    aidx=None):
        """Pad ``tokens`` to its bucket and land its K/V in the
        host-allocated ``page_ids``: a list, one id a prompt page
        (padded here with the trash page to the bucket's page count),
        or such a list for each kind of layer, where a window layer's
        holds the trash page for each page already behind the window
        and ``'slot'`` names the row of the slot entries it rewrites.
        Returns (pool', first_token, logits | None)."""
        tokens = onp.asarray(tokens, 'int32').reshape(-1)
        n = tokens.shape[0]
        if n < 1:
            raise ValueError('empty prompt')
        bucket = self.policy.bucket_for(n)
        npages = pages_for(bucket, self.page_size)

        def padded_ids(ids):
            ids = list(ids)
            if len(ids) > npages:
                raise ValueError('%d page ids for a %d-page bucket'
                                 % (len(ids), npages))
            return onp.asarray(ids + [TRASH_PAGE] * (npages - len(ids)),
                               'int32')

        padded = onp.zeros((1, bucket), 'int32')
        padded[0, :n] = tokens
        prog = self.compile_prefill(bucket)
        pool, tok, logits = self._call(
            prog, pool, padded, onp.int32(n),
            self._each_kind(page_ids, padded_ids),
            self._extra_args('prefill', temps, top_ps, keys, masks,
                             apool, aidx))
        counts = getattr(self.model, 'prefill_counts', None)
        if counts is not None:
            self.last_prefill_stats = counts(bucket)
        return pool, int(tok), logits

    def _tables_arg(self, tables):
        return self._each_kind(
            tables,
            lambda t: onp.asarray(t, 'int32').reshape(self.slots, -1))

    def run_step(self, pool, tokens, positions, tables, temps=None,
                 top_ps=None, keys=None, masks=None, apool=None,
                 aidx=None):
        """Advance every slot one token through its page table
        (``tables``: one (slots, columns) array, or one for each kind
        of layer; ``keys`` (slots, 2)): :meth:`run_steps` at ``n`` 1.
        Returns (pool', tokens np (slots,), logits np | None)."""
        if keys is not None:
            keys = onp.asarray(keys, 'uint32').reshape(self.slots, 1, 2)
        pool, toks, logits = self.run_steps(
            pool, tokens, positions, tables, 1, temps=temps,
            top_ps=top_ps, keys=keys, masks=masks, apool=apool,
            aidx=aidx)
        return pool, toks[0], logits

    def run_steps(self, pool, tokens, positions, tables, n=1,
                  budgets=None, forced=None, temps=None, top_ps=None,
                  keys=None, masks=None, apool=None, aidx=None):
        """Advance every slot up to ``n`` tokens (1 to ``max_run``) in
        one call of the step program, each step fed the token the one
        before it produced. ``budgets`` (slots,) is the steps each slot
        takes (default ``n``): a slot past its budget steps as an empty
        one, through the trash page. ``forced`` (max_run, slots) feeds
        step k a prompt token wherever it is not negative (default
        none). ``keys`` (slots, up to max_run, 2) is one key a step, for
        the position that step reads. A family's device-side counts
        come back behind the tokens, summed over the steps, and are
        left in ``last_step_stats``. Returns (pool', tokens np (n,
        slots), logits np | None)."""
        n = int(n)
        if not 1 <= n <= self.max_run:
            raise ValueError('a call runs 1 to %d steps, not %d'
                             % (self.max_run, n))
        S, R = self.slots, self.max_run
        if keys is not None:
            keys = onp.asarray(keys, 'uint32').reshape(S, -1, 2)
            keys = onp.concatenate(
                [keys, onp.zeros((S, R - keys.shape[1], 2), 'uint32')],
                axis=1)
        if budgets is None and forced is None:
            steps = self._uniform_steps(n, pool)
        else:
            steps = {
                'n': onp.int32(n),
                'budgets': (onp.full(S, n, 'int32') if budgets is None
                            else onp.asarray(budgets, 'int32').reshape(S)),
                'forced': (onp.full((R, S), -1, 'int32') if forced is None
                           else onp.asarray(forced, 'int32').reshape(R, S))}
        prog = self.compile_step()
        pool, toks, logits = self._call(
            prog, pool,
            onp.asarray(tokens, 'int32').reshape(S),
            onp.asarray(positions, 'int32').reshape(S),
            self._tables_arg(tables), steps,
            self._extra_args('step', temps, top_ps, keys, masks,
                             apool, aidx))
        if self._n_stats:
            self.last_step_stats = dict(zip(
                self.model.step_stats,
                (int(v) for v in toks[R * S:])))
            toks = toks[:R * S]
        return pool, toks.reshape(R, S)[:n], logits

    def _uniform_steps(self, n, pool):
        """:meth:`run_steps`' operand where every slot takes ``n`` steps
        and no prompt token is forced, put once on the device that holds
        the pool: each host array a call carries is a transfer the call
        waits for, which a call of one step at low occupancy feels."""
        import jax
        devices = jax.tree.leaves(pool)[0].devices()
        key = (n, frozenset(devices))
        steps = self._steps_operands.get(key)
        if steps is None:
            steps = {'n': onp.int32(n),
                     'budgets': onp.full(self.slots, n, 'int32'),
                     'forced': onp.full((self.max_run, self.slots), -1,
                                        'int32')}
            if len(devices) == 1:
                steps = jax.device_put(steps, next(iter(devices)))
            self._steps_operands[key] = steps
        return steps

    def run_verify(self, pool, tokens, positions, tables, temps=None,
                   top_ps=None, keys=None, masks=None, apool=None,
                   aidx=None):
        """Speculative verify: (slots, spec_k+1) tokens in, emitted
        tokens (slots, spec_k+1) out; K/V rows written for every
        position (rejected rows stay masked until overwritten).
        ``keys`` is (slots, spec_k+1, 2): one key per verify row at
        its absolute position, matching the plain path's keys."""
        prog = self.compile_verify()
        return self._call(
            prog, pool,
            onp.asarray(tokens, 'int32').reshape(self.slots,
                                                 self.spec_k + 1),
            onp.asarray(positions, 'int32').reshape(self.slots),
            self._tables_arg(tables),
            self._extra_args('verify', temps, top_ps, keys, masks,
                             apool, aidx))

    def run_copy_page(self, pool, src, dst):
        """Copy-on-write: duplicate page ``src`` into ``dst``, or, of
        a model with two kinds of layer, ``src[kind]`` into
        ``dst[kind]`` within each kind's pools (the trash page onto
        itself where only one kind copies)."""
        prog = self.compile_copy_page()
        return prog(self._params, pool, self._each_kind(src, onp.int32),
                    self._each_kind(dst, onp.int32))

    # -- live migration (seqstate export/import) ----------------------------

    def _one_page_list(self, what):
        """Refuse ``what`` for a cache that is more than one page list
        a sequence."""
        if self.window_pages or self.page_spec.slot_entries:
            raise FamilyUnsupported(
                self.model.family, '%s: the seqstate payload carries one '
                'page list a sequence, not a ring or a recurrent state '
                'beside it' % what)

    def export_pages(self, pool, page_ids):
        """Gather ``page_ids`` from the pool to host rows, keyed by
        cache entry name: ``{name: (len(page_ids)*page_size, *row)}``.
        The gather runs on device (only the requested pages cross to
        host, not the pool); migration is rare, so eager ops — the
        step program's zero-retrace contract is untouched."""
        import jax.numpy as jnp
        self._one_page_list('export_pages (live migration)')
        ids = onp.asarray(list(page_ids), 'int32')
        out = {}
        for name, arr in pool.items():
            rows = onp.asarray(jnp.take(arr, ids, axis=0))
            out[name] = rows.reshape(
                (rows.shape[0] * rows.shape[1],) + rows.shape[2:])
        return out

    def import_pages(self, pool, rows, page_ids):
        """Land host rows from :meth:`export_pages` (possibly
        re-chunked to THIS engine's page size) into freshly allocated
        ``page_ids``. ``rows[name]`` must be ``(len(page_ids) *
        page_size, *row)`` — pad a partial tail page with zeros, which
        is exactly the pool's init state (additive masks keep unused
        rows inert). Returns the new pool."""
        import jax.numpy as jnp
        self._one_page_list('import_pages (live migration)')
        ids = onp.asarray(list(page_ids), 'int32')
        want = ids.shape[0] * self.page_size
        out = dict(pool)
        for name, arr in pool.items():
            if name not in rows:
                raise ValueError('page rows missing cache entry %r'
                                 % (name,))
            chunk = onp.asarray(rows[name])
            if chunk.shape[0] != want or \
                    tuple(chunk.shape[1:]) != tuple(arr.shape[2:]):
                raise ValueError(
                    'page rows for %r are %r, want (%d, *%r)'
                    % (name, tuple(chunk.shape), want,
                       tuple(arr.shape[2:])))
            out[name] = write_prefill_pages(
                arr, jnp.asarray(chunk.astype(
                    str(arr.dtype), copy=False)), ids)
        return out


def freeze_decode(obj, params=None, slots=None, prefill_buckets=None,
                  max_len=None, name=None, donate=None,
                  emit_logits=True, paged=None, page_size=None,
                  pages=None, spec_k=None, logit_mask=None,
                  adapter_rank=None, adapter_slots=None):
    """Freeze a generation model into a :class:`DecodeProgram`.

    ``obj`` — one of:

      * a :class:`~.model.DecodeModel` with ``params`` given
        explicitly;
      * a ``(embedding, rnn, decoder)`` triple of trained gluon blocks
        (``nn.Embedding``, ``rnn.LSTM/GRU/RNN``, ``nn.Dense``);
      * a word_lm-style object exposing those three as attributes
        (``.embedding``, ``.lstm``/``.rnn``, ``.decoder``).

    ``max_len`` caps prompt + generated tokens per sequence (the KV
    cache length; ``MXNET_TPU_SERVE_MAX_SEQ_LEN``).

    ``paged`` selects the block/paged KV cache
    (:class:`PagedDecodeProgram`): default (None) reads
    ``MXNET_TPU_SERVE_PAGED`` and applies it to families that support
    paging (transformers; RNN state is O(1) per slot already —
    requesting ``paged=True`` for one is a typed error).
    ``page_size`` / ``pages`` / ``spec_k`` configure the pool and the
    speculative-verify program (``MXNET_TPU_SERVE_PAGE_SIZE`` /
    ``MXNET_TPU_SERVE_PAGES`` / ``MXNET_TPU_SERVE_SPEC_K``).

    ``adapter_rank`` > 0 (``MXNET_TPU_SERVE_ADAPTER_RANK``) compiles a
    low-rank adapter pool of ``adapter_slots`` resident variants
    (``MXNET_TPU_SERVE_ADAPTER_SLOTS``) into every program — LoRA
    families only. ``logit_mask`` adds the mask operand (see
    :class:`DecodeProgram`).
    """
    if max_len is None:
        max_len = int(_knob('MXNET_TPU_SERVE_MAX_SEQ_LEN', 256))
    if isinstance(obj, DecodeModel):
        if params is None:
            raise ValueError('params required when freezing a '
                             'DecodeModel directly')
        model = obj
    else:
        if isinstance(obj, tuple) and len(obj) == 3:
            embedding, rnn, decoder = obj
        else:
            embedding = getattr(obj, 'embedding', None)
            rnn = getattr(obj, 'lstm', None) or getattr(obj, 'rnn',
                                                        None)
            decoder = getattr(obj, 'decoder', None)
            if embedding is None or rnn is None or decoder is None:
                raise TypeError(
                    'cannot freeze %r for decoding: need a DecodeModel'
                    ' + params, an (embedding, rnn, decoder) gluon'
                    ' triple, or an object with those attributes'
                    % (type(obj).__name__,))
        model, params = from_gluon_rnn_lm(embedding, rnn, decoder,
                                          max_len=max_len)
    if paged is None:
        paged = bool(_knob('MXNET_TPU_SERVE_PAGED', True)) \
            and getattr(model, 'supports_paging', False)
    if adapter_rank is None:
        adapter_rank = int(
            _knob('MXNET_TPU_SERVE_ADAPTER_RANK', 0) or 0)
    adapter_spec = None
    if adapter_rank > 0:
        if not hasattr(model, 'lora_targets'):
            raise TypeError(
                'family %r has no LoRA targets — adapter_rank > 0 '
                'needs a model exposing lora_targets()'
                % (model.family,))
        from ..adapters import AdapterSpec
        if adapter_slots is None:
            adapter_slots = int(
                _knob('MXNET_TPU_SERVE_ADAPTER_SLOTS', 8))
        adapter_spec = AdapterSpec.for_model(model, adapter_rank,
                                             adapter_slots)
    if paged:
        if pages is None:
            knob_pages = int(_knob('MXNET_TPU_SERVE_PAGES', 0) or 0)
            pages = knob_pages or None
        return PagedDecodeProgram(
            model, params, slots=slots,
            prefill_buckets=prefill_buckets, name=name, donate=donate,
            emit_logits=emit_logits, page_size=page_size, pages=pages,
            spec_k=spec_k, logit_mask=logit_mask,
            adapter_spec=adapter_spec)
    return DecodeProgram(model, params, slots=slots,
                         prefill_buckets=prefill_buckets, name=name,
                         donate=donate, emit_logits=emit_logits,
                         logit_mask=logit_mask,
                         adapter_spec=adapter_spec)


def load_decode(path):
    """Module-level alias of :meth:`DecodeProgram.load`."""
    return DecodeProgram.load(path)
