"""Freezing: trained model -> AOT-compiled inference program + artifact.

Training binds a symbol to a mutable executor; serving wants the
opposite — an immutable pure function over fixed parameters, compiled
ahead of time for every shape bucket it will ever run, with nothing
left to trace at request time. :func:`freeze` takes a trained
``Module`` / gluon ``Block`` / ``FeedForward`` (or a raw
``(symbol, arg_params, aux_params)`` triple) and produces a
:class:`FrozenProgram`:

  * the symbol graph re-materialized as a pure
    ``fn(params, data) -> outputs`` (executor.py's ``_build_graph_fn``
    in inference mode: no grads, no aux mutation, dropout keys fixed);
  * one ``jax.jit(...).lower(...).compile()`` executable per batch
    bucket, input buffers donated on accelerator backends (the padded
    request batch is dead after the call — XLA reuses its memory for
    activations);
  * a persistent on-disk artifact (``mxnet_tpu.frozen.v1``: manifest +
    params.npz + symbol.json + serialized per-bucket executables) so a
    server restart deserializes compiled programs instead of
    re-tracing — cold start becomes file I/O.

Retracing is observable: ``trace_counts`` ticks only when jax actually
traces the python function, so the selftest can PROVE a reloaded
artifact served without tracing (``python -m mxnet_tpu.serving``).
When executable deserialization is impossible (different jax version
or platform), load falls back to re-jit per bucket — correct, just
cold — and records which buckets retraced; jax's persistent
compilation cache (``config.configure_compile_cache``) still skips the
XLA compile in that case.
"""
from __future__ import annotations

import json
import os
import pickle
import threading

import numpy as onp

from .bucket import BucketPolicy, unpad_axis0

__all__ = ['FROZEN_SCHEMA', 'FrozenProgram', 'freeze', 'load_frozen']

FROZEN_SCHEMA = 'mxnet_tpu.frozen.v1'


def _as_numpy(arr):
    if hasattr(arr, 'asnumpy'):
        return arr.asnumpy()
    return onp.asarray(arr)


def load_executable(path, params):
    """Deserialize one AOT executable onto the device ``params`` live
    on — the device it was compiled for. (Left to its default, jax
    loads it across every device of the backend, and a one-device
    program then refuses its arguments on a multi-device host.)"""
    from jax.experimental import serialize_executable
    with open(path, 'rb') as f:
        ser, in_tree, out_tree = pickle.load(f)
    devices = sorted(next(iter(params.values())).devices(),
                     key=lambda d: d.id)
    return serialize_executable.deserialize_and_load(
        ser, in_tree, out_tree, execution_devices=devices)


class FrozenProgram:
    """Immutable inference program: params + per-bucket compiled
    executables over one symbol graph.

    ``data_descs`` — ``[(name, per_example_shape, dtype)]`` for the
    request inputs (no batch axis). Every other symbol argument is a
    parameter (frozen) or an inference-irrelevant input (labels of
    training heads) that is zero-filled per bucket at compile time.
    """

    def __init__(self, symbol, arg_params, aux_params, data_descs,
                 policy=None, name='model', donate=None):
        import jax
        import jax.numpy as jnp
        self._symbol = symbol
        self.name = name
        self.policy = policy if isinstance(policy, BucketPolicy) else \
            BucketPolicy(buckets=policy) if policy is not None else \
            BucketPolicy()
        self.data_descs = [(str(n), tuple(int(d) for d in s),
                            str(dt)) for n, s, dt in data_descs]
        self.data_names = [d[0] for d in self.data_descs]
        self._arg_np = {k: _as_numpy(v) for k, v in arg_params.items()}
        self._aux_np = {k: _as_numpy(v) for k, v in aux_params.items()}
        # one device-resident pytree for the compiled call's first arg
        self._params = {k: jnp.asarray(v) for k, v in
                        {**self._arg_np, **self._aux_np}.items()}
        known = set(self._params) | set(self.data_names)
        self._extra_names = [a for a in symbol.list_arguments()
                             if a not in known]
        if donate is None:
            donate = jax.default_backend() != 'cpu'
        self._donate = bool(donate)
        self._compiled = {}          # bucket -> jax Compiled
        self._loaded = {}            # bucket -> deserialized Compiled
        self._fallback_fns = {}      # bucket -> eager CPU-path fn
        self._cpu_params = None      # CPU-resident param tree (lazy)
        # build lock: infer_batch() runs on caller threads concurrently
        # with the batcher worker — without it, two threads racing
        # compile() for one bucket would double-compile and double-tick
        # trace_counts (breaking the zero-retrace/bounded-recompile
        # accounting the selftest and bench assert on)
        self._build_lock = threading.Lock()
        self.trace_counts = {}       # bucket -> python traces observed
        self.compile_seconds = {}    # bucket -> wall seconds to build
        self.retraced_buckets = []   # buckets that fell back to re-jit
        self._n_outputs = len(symbol.list_outputs())

    # -- program construction ----------------------------------------------

    def _bucket_shapes(self, bucket):
        """{input/extra name: full shape at this bucket}."""
        shapes = {n: (bucket,) + s for n, s, _ in self.data_descs}
        if self._extra_names:
            known = dict(shapes)
            known.update({k: tuple(v.shape)
                          for k, v in self._arg_np.items()})
            known.update({k: tuple(v.shape)
                          for k, v in self._aux_np.items()})
            inferred = {}
            try:
                plan, _, _ = self._symbol._var_shape_plan(known)
                inferred = plan or {}
            except Exception:
                inferred = {}
            for name in self._extra_names:
                s = inferred.get(name)
                shapes[name] = tuple(s) if s else (bucket,)
        return shapes

    def _creation_shapes(self, bucket):
        """Unknown-dim creation-op resolutions (executor.py idiom)."""
        known = self._bucket_shapes(bucket)
        known.update({k: tuple(v.shape) for k, v in self._arg_np.items()})
        known.update({k: tuple(v.shape) for k, v in self._aux_np.items()})
        try:
            _, node_out_shapes, _ = self._symbol._var_shape_plan(known)
            return node_out_shapes.get('creation_shapes', {})
        except Exception:
            return {}

    def _make_fn(self, bucket, count_key=None):
        import jax
        import jax.numpy as jnp
        from ..executor import _build_graph_fn
        graph_fn = _build_graph_fn(self._symbol, False,
                                   self._creation_shapes(bucket))
        shapes = self._bucket_shapes(bucket)
        extras = {n: jnp.zeros(shapes[n], 'float32')
                  for n in self._extra_names}
        key = jax.random.PRNGKey(0)
        counts = self.trace_counts
        count_key = bucket if count_key is None else count_key

        def fn(params, data):
            # trace-time tick: the body runs only while jax traces, so
            # this counter proves (or disproves) request-time retracing
            counts[count_key] = counts.get(count_key, 0) + 1
            vals = dict(params)
            vals.update(extras)
            vals.update(data)
            outs, _aux = graph_fn(vals, key)
            return tuple(outs)
        return fn

    def _data_avals(self, bucket):
        import jax
        return {n: jax.ShapeDtypeStruct((bucket,) + s, dt)
                for n, s, dt in self.data_descs}

    def compile(self, bucket):
        """AOT-build the executable for one bucket (idempotent,
        thread-safe)."""
        prog = self._compiled.get(bucket) or self._loaded.get(bucket)
        if prog is not None:
            return prog
        import time
        import jax
        with self._build_lock:
            prog = self._compiled.get(bucket) or \
                self._loaded.get(bucket)
            if prog is not None:
                return prog
            t0 = time.perf_counter()
            fn = self._make_fn(bucket)
            jitted = jax.jit(fn, donate_argnums=(1,)) if self._donate \
                else jax.jit(fn)
            prog = jitted.lower(
                {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in self._params.items()},
                self._data_avals(bucket)).compile()
            self.compile_seconds[bucket] = time.perf_counter() - t0
            self._compiled[bucket] = prog
        try:
            from .. import observability as _obs
            if _obs.enabled():
                inst = _obs.serving_instruments()
                inst.compiles.inc()
                _obs.record_event('serve_compile', bucket=bucket,
                                  seconds=round(
                                      self.compile_seconds[bucket], 4))
        except Exception:
            pass
        return prog

    def warmup(self, buckets=None):
        """Pre-compile every bucket (server start, not first request)."""
        for b in (buckets or self.policy.buckets):
            self.compile(b)
        return self

    @property
    def compile_count(self):
        """Distinct programs built or loaded so far — the quantity the
        bucket ladder bounds."""
        return len(set(self._compiled) | set(self._loaded))

    # -- execution ---------------------------------------------------------

    def run(self, arrays, n=None):
        """Run ``arrays`` (one stacked numpy array per data input)
        through the bucketed compiled program; returns a list of numpy
        outputs with the bucket padding stripped back to ``n`` rows.
        Batches larger than the top bucket run as max-bucket chunks
        (the bulk/offline path; concurrent request batching is the
        micro-batcher's job)."""
        import jax.numpy as jnp
        arrays = [onp.asarray(a) for a in arrays]
        if n is None:
            n = arrays[0].shape[0]
        top = self.policy.max_batch
        if n > top:
            chunks = [self.run([a[i:i + top] for a in arrays])
                      for i in range(0, n, top)]
            return [onp.concatenate([c[j] for c in chunks], axis=0)
                    for j in range(len(chunks[0]))]
        padded, n = self.policy.pad(arrays, n)
        bucket = padded[0].shape[0]
        prog = self.compile(bucket)
        data = {name: jnp.asarray(a.astype(dt, copy=False))
                for (name, _s, dt), a in zip(self.data_descs, padded)}
        outs = prog(self._params, data)
        return [unpad_axis0(onp.asarray(o), n) for o in outs]

    def run_fallback(self, arrays, n=None):
        """Degraded-path execution: the same graph, un-jitted, pinned
        to the CPU backend — correctness preserved when the accelerator
        program is the thing that died (server.py circuit breaker)."""
        import jax
        import jax.numpy as jnp
        arrays = [onp.asarray(a) for a in arrays]
        if n is None:
            n = arrays[0].shape[0]
        padded, n = self.policy.pad(arrays, n)
        bucket = padded[0].shape[0]
        cpu = jax.devices('cpu')[0]
        # sustained breaker-open serving runs every batch here: cache
        # the per-bucket eager fn and the CPU param copies so a
        # degraded fleet pays graph rebuild + parameter transfer once,
        # not per batch
        with self._build_lock:
            fn = self._fallback_fns.get(bucket)
            if fn is None:
                fn = self._make_fn(bucket,  # eager: never a jit trace
                                   count_key='fallback:%d' % bucket)
                self._fallback_fns[bucket] = fn
            if self._cpu_params is None:
                self._cpu_params = {k: jax.device_put(v, cpu)
                                    for k, v in self._params.items()}
        with jax.default_device(cpu):
            data = {name: jnp.asarray(a.astype(dt, copy=False))
                    for (name, _s, dt), a in zip(self.data_descs,
                                                 padded)}
            outs = fn(self._cpu_params, data)
        return [unpad_axis0(onp.asarray(o), n) for o in outs]

    # -- persistence (mxnet_tpu.frozen.v1) ---------------------------------

    def save(self, path, include_programs=True):
        """Write the frozen artifact directory::

            <path>/MANIFEST.json     schema + shapes + buckets + env
            <path>/params.npz        arg:/aux:-prefixed weights
            <path>/symbol.json       the inference graph
            <path>/programs/b<N>.bin serialized executables (optional)

        Executables serialize per bucket via jax's AOT persistence;
        the manifest records the jax version + platform they are valid
        for, so :func:`load_frozen` knows when it must re-jit instead.
        """
        import jax
        from ..resilience.checkpoint import atomic_write_bytes
        os.makedirs(path, exist_ok=True)
        table = {('arg:%s' % k): v for k, v in self._arg_np.items()}
        table.update({('aux:%s' % k): v
                      for k, v in self._aux_np.items()})
        import io as _io
        buf = _io.BytesIO()
        onp.savez(buf, **table)
        atomic_write_bytes(os.path.join(path, 'params.npz'),
                           buf.getvalue())
        self._symbol.save(os.path.join(path, 'symbol.json'))
        programs = {}
        if include_programs:
            from jax.experimental import serialize_executable
            os.makedirs(os.path.join(path, 'programs'), exist_ok=True)
            for bucket in sorted(set(self._compiled)
                                 | set(self._loaded)):
                prog = self._compiled.get(bucket) or \
                    self._loaded.get(bucket)
                fname = 'programs/b%d.bin' % bucket
                try:
                    blob = pickle.dumps(
                        serialize_executable.serialize(prog))
                except Exception:
                    continue        # artifact still loads; bucket re-jits
                atomic_write_bytes(os.path.join(path, fname), blob)
                programs[str(bucket)] = fname
        manifest = {
            'schema': FROZEN_SCHEMA,
            'name': self.name,
            'data_descs': [[n, list(s), dt]
                           for n, s, dt in self.data_descs],
            'buckets': list(self.policy.buckets),
            'seq_buckets': list(self.policy.seq_buckets)
            if self.policy.seq_buckets else None,
            'n_outputs': self._n_outputs,
            'donate': self._donate,
            'jax_version': jax.__version__,
            'platform': jax.default_backend(),
            'programs': programs,
        }
        atomic_write_bytes(
            os.path.join(path, 'MANIFEST.json'),
            (json.dumps(manifest, indent=1, sort_keys=True)
             + '\n').encode())
        return path

    @classmethod
    def load(cls, path):
        """Reload a frozen artifact. Serialized executables
        deserialize when jax version + platform match the manifest;
        buckets that cannot are re-jit on first use and recorded in
        ``retraced_buckets``."""
        import jax
        from .. import symbol as sym_mod
        with open(os.path.join(path, 'MANIFEST.json')) as f:
            manifest = json.load(f)
        if manifest.get('schema') != FROZEN_SCHEMA:
            raise ValueError('not a %s artifact: %r at %s'
                             % (FROZEN_SCHEMA, manifest.get('schema'),
                                path))
        arg_params, aux_params = {}, {}
        with onp.load(os.path.join(path, 'params.npz')) as z:
            for key in z.files:
                tag, _, name = key.partition(':')
                (arg_params if tag == 'arg' else aux_params)[name] = \
                    z[key]
        symbol = sym_mod.load(os.path.join(path, 'symbol.json'))
        prog = cls(symbol, arg_params, aux_params,
                   [(n, tuple(s), dt)
                    for n, s, dt in manifest['data_descs']],
                   policy=BucketPolicy(
                       buckets=manifest['buckets'],
                       seq_buckets=manifest.get('seq_buckets')),
                   name=manifest.get('name', 'model'),
                   donate=manifest.get('donate'))
        env_ok = (manifest.get('jax_version') == jax.__version__
                  and manifest.get('platform') == jax.default_backend())
        for bucket_s, fname in (manifest.get('programs') or {}).items():
            bucket = int(bucket_s)
            if not env_ok:
                prog.retraced_buckets.append(bucket)
                continue
            try:
                prog._loaded[bucket] = load_executable(
                    os.path.join(path, fname), prog._params)
            except Exception:
                prog.retraced_buckets.append(bucket)
        return prog


def _module_descs(mod):
    """Per-example data descs from a bound Module's data_shapes."""
    descs = []
    for d in mod.data_shapes:
        shape = tuple(int(x) for x in d.shape)
        # DataDesc.dtype may be an np.dtype, a dtype CLASS
        # (np.float32 — the tuple-bind default), or a string;
        # onp.dtype normalizes all three to a parseable name
        try:
            dtype = str(onp.dtype(getattr(d, 'dtype', None)
                                  or 'float32'))
        except TypeError:
            dtype = 'float32'
        descs.append((d.name, shape[1:], dtype))
    return descs


def freeze(obj, data_shapes=None, buckets=None, max_batch=None,
           seq_buckets=None, name=None, donate=None):
    """Freeze a trained model into a :class:`FrozenProgram`.

    ``obj`` — a bound+initialized ``Module``, a fitted ``FeedForward``,
    a hybridized gluon ``Block`` (run at least once), or a
    ``(symbol, arg_params, aux_params)`` triple. ``data_shapes`` —
    per-example input shapes (no batch axis), either
    ``[(name, shape)]`` or ``[(name, shape, dtype)]``; defaults to the
    Module's bound shapes. ``buckets`` — explicit batch ladder;
    defaults to powers of two up to ``max_batch``
    (``MXNET_TPU_SERVE_MAX_BATCH``).
    """
    from .. import config as _config
    from ..model import FeedForward
    from ..module.base_module import BaseModule

    symbol = arg_params = aux_params = None
    descs = None
    if isinstance(obj, tuple) and len(obj) == 3:
        symbol, arg_params, aux_params = obj
    elif isinstance(obj, FeedForward):
        mod = obj._module
        if mod is None:
            raise ValueError('FeedForward not fitted; freeze the '
                             '(symbol, arg_params, aux_params) triple '
                             'from FeedForward.load instead')
        symbol = mod._symbol
        arg_params, aux_params = mod.get_params()
        descs = _module_descs(mod)
    elif isinstance(obj, BaseModule):
        symbol = obj.symbol
        arg_params, aux_params = obj.get_params()
        descs = _module_descs(obj)
    elif hasattr(obj, 'collect_params'):     # gluon Block
        import tempfile
        from ..model import load_checkpoint
        with tempfile.TemporaryDirectory() as tmp:
            prefix = os.path.join(tmp, 'frozen')
            obj.export(prefix)
            symbol, arg_params, aux_params = load_checkpoint(prefix, 0)
    else:
        raise TypeError('cannot freeze %r' % (type(obj).__name__,))

    if data_shapes is not None:
        descs = []
        for d in data_shapes:
            if len(d) == 3 and not isinstance(d[1], (int, float)):
                n, s, dt = d
            else:
                n, s, dt = d[0], d[1], 'float32'
            descs.append((n, tuple(int(x) for x in s), str(dt)))
    if descs is None:
        raise ValueError('data_shapes required when freezing a %s '
                         '(per-example shapes, no batch axis)'
                         % type(obj).__name__)

    if buckets is None:
        spec = _config.get('MXNET_TPU_SERVE_BUCKETS')
        if spec:
            buckets = spec
    if max_batch is None:
        max_batch = int(_config.get('MXNET_TPU_SERVE_MAX_BATCH') or 64)
    policy = BucketPolicy(buckets=buckets, max_batch=max_batch,
                          seq_buckets=seq_buckets)
    return FrozenProgram(symbol, arg_params or {}, aux_params or {},
                         descs, policy=policy,
                         name=name or getattr(obj, 'name', None)
                         or 'model', donate=donate)


def load_frozen(path):
    """Load any ``mxnet_tpu.frozen.v1`` artifact: dispatches on the
    manifest ``kind`` — one-shot inference programs load as
    :class:`FrozenProgram`, generation artifacts (``kind: decode``,
    prefill + decode-step executables) as
    :class:`~.decode.DecodeProgram`; decode manifests carrying
    ``paged: true`` (page-pool geometry + copy/verify programs)
    re-dispatch once more to :class:`~.decode.PagedDecodeProgram`
    inside ``DecodeProgram.load``. ``mxnet_tpu.adapter.v1``
    artifacts (LoRA weight deltas, not programs) load as digest-
    verified :class:`~.adapters.Adapter` objects."""
    try:
        with open(os.path.join(path, 'MANIFEST.json')) as f:
            doc = json.load(f)
        kind, schema = doc.get('kind'), doc.get('schema')
    except OSError:
        kind = schema = None
    from .adapters import ADAPTER_SCHEMA
    if schema == ADAPTER_SCHEMA or kind == 'adapter':
        from .adapters import load_adapter
        return load_adapter(path)
    if kind == 'decode':
        from .decode import DecodeProgram
        return DecodeProgram.load(path)
    return FrozenProgram.load(path)
