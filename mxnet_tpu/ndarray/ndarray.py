"""NDArray: imperative tensor over jax.Array with MXNet semantics.

Reference parity: include/mxnet/ndarray.h:82 + python/mxnet/ndarray/ndarray.py.
The reference NDArray is a handle into the async dependency engine; here the
backing store is a jax.Array whose dispatch is already async in XLA —
``wait_to_read`` maps to ``block_until_ready`` (SURVEY.md §1 L2 "TPU
mapping"). In-place mutation (``x[:]=v``, ``+=``) is presented to the user
while the functional backend swaps the underlying buffer (XLA donates/aliases
where it can).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import np_dtype, numeric_types, integer_types
from ..context import Context, current_context
from .. import autograd
from ..autograd import Entry, TapeNode
from ..ops import registry as _registry
from ..amp.policy import current_policy as _amp_current
from .. import random as _random

__all__ = ['NDArray', 'array', 'zeros', 'ones', 'full', 'empty', 'arange',
           'invoke', 'concatenate', 'moveaxis', 'maximum', 'minimum',
           'save', 'load', 'waitall', 'imports_done']


def _is_float(x):
    return jnp.issubdtype(x.dtype, jnp.floating)


class NDArray:
    """Multi-dimensional array with deferred (async) execution."""

    __slots__ = ('_data', '_ctx', '_grad', '_grad_req', '_entry',
                 '_grad_fresh', '__weakref__')

    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            data = jnp.asarray(data)
        self._data = data
        self._ctx = ctx
        self._grad = None
        self._grad_req = 'null'
        self._entry = None
        self._grad_fresh = False

    # -- basic properties --------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return onp.dtype(self._data.dtype) if self._data.dtype != jnp.bfloat16 \
            else self._data.dtype

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        if self._ctx is not None:
            return self._ctx
        try:
            dev = list(self._data.devices())[0]
            if dev.platform == 'cpu':
                return Context('cpu', dev.id)
            return Context('tpu', dev.id)
        except Exception:
            return current_context()

    ctx = context

    @property
    def stype(self):
        return 'default'

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return self.transpose()

    # -- engine semantics --------------------------------------------------
    def wait_to_read(self):
        """Block until the value is computed (reference: ndarray.h:361
        WaitToRead; XLA analog = block_until_ready, which also raises
        the error of a failed asynchronous computation)."""
        self._data.block_until_ready()

    def wait_to_write(self):
        self.wait_to_read()

    # -- conversion --------------------------------------------------------
    def asnumpy(self):
        out = onp.asarray(self._data)
        return out

    def asscalar(self):
        if self.size != 1:
            raise ValueError('The current array is not a scalar')
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError('The truth value of an NDArray with multiple '
                         'elements is ambiguous.')

    def __len__(self):
        if self.ndim == 0:
            raise TypeError('len() of unsized object')
        return self.shape[0]

    def __repr__(self):
        return '%s\n<NDArray %s @%s>' % (
            str(self.asnumpy()), 'x'.join(str(s) for s in self.shape),
            self.context)

    def astype(self, dtype, copy=True):
        dt = np_dtype(dtype)
        if not copy and self._data.dtype == dt:
            return self
        return invoke('Cast', [self], {'dtype': dtype})

    def copy(self):
        return invoke('_copy', [self], {})

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = jnp.asarray(self._data, dtype=other._data.dtype) \
                if other._data.dtype != self._data.dtype else self._data
            if other._ctx is not None:
                other._data = jax.device_put(other._data,
                                             other._ctx.jax_device())
            return other
        if isinstance(other, Context):
            return self.as_in_context(other)
        raise TypeError('copyto target must be NDArray or Context')

    def as_in_context(self, context):
        if context == self.context:
            return self
        out = NDArray(jax.device_put(self._data, context.jax_device()),
                      ctx=context)
        out._entry = self._entry
        return out

    as_in_ctx = as_in_context

    def as_nd_ndarray(self):
        return self

    def tostype(self, stype):
        """Cast to a storage type (reference: ndarray.py tostype /
        cast_storage.cc). Sparse stypes return the dense-backed facade
        classes so downstream .stype dispatch (lazy optimizer updates,
        row_sparse_pull) sees the right type."""
        if stype == 'default':
            return self
        from .sparse import CSRNDArray, RowSparseNDArray
        if stype == 'csr':
            return CSRNDArray(self._data)
        if stype == 'row_sparse':
            return RowSparseNDArray(self._data)
        raise ValueError('unknown storage type %r' % stype)

    # -- autograd ----------------------------------------------------------
    def attach_grad(self, grad_req='write', stype=None):
        """Attach a gradient buffer (reference: ndarray.py attach_grad)."""
        self._grad = zeros(self.shape, dtype=self._data.dtype,
                           ctx=self.context if self._ctx else None)
        self._grad_req = grad_req
        self._entry = Entry(variable=self)

    def detach(self):
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- indexing ----------------------------------------------------------
    def _index(self, key):
        if isinstance(key, NDArray):
            return key._data
        if isinstance(key, tuple):
            return tuple(k._data if isinstance(k, NDArray) else k for k in key)
        return key

    def __getitem__(self, key):
        idx = self._index(key)
        if autograd.is_recording() and self._entry is not None:
            return invoke('_getitem', [self], {'_key': idx})
        return NDArray(self._data[idx])

    def __setitem__(self, key, value):
        idx = self._index(key)
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(idx, slice) and idx == slice(None) and \
                not isinstance(value, jax.Array):
            if onp.isscalar(value):
                self._data = jnp.full_like(self._data, value)
            else:
                new = jnp.asarray(value, self._data.dtype)
                try:
                    # keep the buffer's placement — including a multi-device
                    # sharding — rather than silently migrating it to the
                    # default device (or collapsing a sharded param onto one
                    # chip)
                    new = jax.device_put(new, self._data.sharding)
                except Exception:
                    pass
                self._data = new
            return
        self._data = self._data.at[idx].set(
            jnp.asarray(value, self._data.dtype)
            if not isinstance(value, jax.Array) else value.astype(self._data.dtype))

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # -- arithmetic (routed through the op registry so autograd records) ---
    def _binary(self, opname, other, reflect=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reflect else (self, other)
            return invoke(opname, [a, b], {})
        if isinstance(other, numeric_types):
            sname = {'broadcast_add': '_plus_scalar',
                     'broadcast_sub': '_rminus_scalar' if reflect else '_minus_scalar',
                     'broadcast_mul': '_mul_scalar',
                     'broadcast_div': '_rdiv_scalar' if reflect else '_div_scalar',
                     'broadcast_mod': '_rmod_scalar' if reflect else '_mod_scalar',
                     'broadcast_power': '_rpower_scalar' if reflect else '_power_scalar',
                     'broadcast_equal': '_equal_scalar',
                     'broadcast_not_equal': '_not_equal_scalar',
                     'broadcast_greater': '_lesser_scalar' if reflect else '_greater_scalar',
                     'broadcast_greater_equal': '_lesser_equal_scalar' if reflect else '_greater_equal_scalar',
                     'broadcast_lesser': '_greater_scalar' if reflect else '_lesser_scalar',
                     'broadcast_lesser_equal': '_greater_equal_scalar' if reflect else '_lesser_equal_scalar',
                     'broadcast_maximum': '_maximum_scalar',
                     'broadcast_minimum': '_minimum_scalar',
                     }[opname]
            return invoke(sname, [self], {'scalar': float(other)})
        if isinstance(other, (onp.ndarray, list, tuple)):
            return self._binary(opname, array(other), reflect)
        if isinstance(other, jax.Array) or isinstance(other, jax.core.Tracer):
            # raw jax value (e.g. a traced lr under the fused-step trace)
            return self._binary(opname, NDArray(jnp.asarray(other)), reflect)
        return NotImplemented

    def __add__(self, o): return self._binary('broadcast_add', o)
    def __radd__(self, o): return self._binary('broadcast_add', o)
    def __sub__(self, o): return self._binary('broadcast_sub', o)
    def __rsub__(self, o): return self._binary('broadcast_sub', o, True)
    def __mul__(self, o): return self._binary('broadcast_mul', o)
    def __rmul__(self, o): return self._binary('broadcast_mul', o)
    def __truediv__(self, o): return self._binary('broadcast_div', o)
    def __rtruediv__(self, o): return self._binary('broadcast_div', o, True)
    def __mod__(self, o): return self._binary('broadcast_mod', o)
    def __rmod__(self, o): return self._binary('broadcast_mod', o, True)
    def __pow__(self, o): return self._binary('broadcast_power', o)
    def __rpow__(self, o): return self._binary('broadcast_power', o, True)
    def __eq__(self, o): return self._binary('broadcast_equal', o)
    def __ne__(self, o): return self._binary('broadcast_not_equal', o)
    def __gt__(self, o): return self._binary('broadcast_greater', o)
    def __ge__(self, o): return self._binary('broadcast_greater_equal', o)
    def __lt__(self, o): return self._binary('broadcast_lesser', o)
    def __le__(self, o): return self._binary('broadcast_lesser_equal', o)
    def __neg__(self): return invoke('negative', [self], {})
    def __abs__(self): return invoke('abs', [self], {})
    def __hash__(self): return id(self)

    def __iadd__(self, o):
        out = self._binary('broadcast_add', o)
        self._data = out._data
        if out._entry is not None:
            self._entry = out._entry
        return self

    def __isub__(self, o):
        out = self._binary('broadcast_sub', o)
        self._data = out._data
        if out._entry is not None:
            self._entry = out._entry
        return self

    def __imul__(self, o):
        out = self._binary('broadcast_mul', o)
        self._data = out._data
        if out._entry is not None:
            self._entry = out._entry
        return self

    def __itruediv__(self, o):
        out = self._binary('broadcast_div', o)
        self._data = out._data
        if out._entry is not None:
            self._entry = out._entry
        return self

    # -- method sugar delegating to ops ------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return invoke('Reshape', [self], {'shape': shape, **kwargs})

    def reshape_like(self, other):
        return invoke('reshape_like', [self, other], {})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return invoke('transpose', [self], {'axes': axes if axes else None})

    def flatten(self):
        return invoke('Flatten', [self], {})

    def expand_dims(self, axis):
        return invoke('expand_dims', [self], {'axis': axis})

    def squeeze(self, axis=None):
        return invoke('squeeze', [self], {'axis': axis})

    def swapaxes(self, dim1, dim2):
        return invoke('SwapAxis', [self], {'dim1': dim1, 'dim2': dim2})

    def flip(self, axis):
        return invoke('reverse', [self], {'axis': axis})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke('SliceChannel', [self],
                      {'num_outputs': num_outputs, 'axis': axis,
                       'squeeze_axis': squeeze_axis})

    def slice(self, begin, end, step=None):
        return invoke('slice', [self], {'begin': begin, 'end': end,
                                        'step': step})

    def slice_axis(self, axis, begin, end):
        return invoke('slice_axis', [self],
                      {'axis': axis, 'begin': begin, 'end': end})

    def take(self, indices, axis=0, mode='clip'):
        return invoke('take', [self, indices], {'axis': axis, 'mode': mode})

    def one_hot(self, depth, **kw):
        return invoke('one_hot', [self], {'depth': depth, **kw})

    def clip(self, a_min, a_max):
        return invoke('clip', [self], {'a_min': a_min, 'a_max': a_max})

    def tile(self, reps):
        return invoke('tile', [self], {'reps': reps})

    def broadcast_to(self, shape):
        return invoke('broadcast_to', [self], {'shape': shape})

    def broadcast_like(self, other):
        return invoke('broadcast_like', [self, other], {})

    def pad(self, mode='constant', pad_width=None, constant_value=0.0):
        return invoke('Pad', [self], {'mode': mode, 'pad_width': pad_width,
                                      'constant_value': constant_value})

    def topk(self, **kw):
        return invoke('topk', [self], kw)

    def argsort(self, **kw):
        return invoke('argsort', [self], kw)

    def sort(self, **kw):
        return invoke('sort', [self], kw)


def _unary_method(name, opname=None):
    opname = opname or name

    def _m(self, *, axis=None, keepdims=False, **kw):
        attrs = dict(kw)
        op = _registry.get(opname)
        if 'axis' in op.attr_names:
            attrs['axis'] = axis
        if 'keepdims' in op.attr_names:
            attrs['keepdims'] = keepdims
        return invoke(opname, [self], attrs)
    _m.__name__ = name
    return _m


for _n in ['abs', 'sqrt', 'square', 'exp', 'log', 'sigmoid', 'relu', 'tanh',
           'sin', 'cos', 'sign', 'round', 'rint', 'floor', 'ceil',
           'sum', 'mean', 'prod', 'max', 'min', 'argmax', 'argmin', 'norm']:
    setattr(NDArray, _n, _unary_method(_n))
setattr(NDArray, 'softmax', _unary_method('softmax'))
setattr(NDArray, 'log_softmax', _unary_method('log_softmax'))


# ---------------------------------------------------------------------------
# op invocation — the Imperative::Invoke analog (imperative.cc:89)
# ---------------------------------------------------------------------------


def _getitem_fn(data, *, _key=None):
    return data[_key]


_registry.register('_getitem')(_getitem_fn)


def _attr_hashable(v):
    if isinstance(v, jax.core.Tracer):
        # a traced attr (e.g. lr under the fused-step trace) must not be
        # baked into the jit cache — force the direct-dispatch path
        raise TypeError('traced attr')
    if isinstance(v, (list, tuple)):
        return tuple(_attr_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _attr_hashable(x)) for k, x in v.items()))
    return v


# Compiled-dispatch cache: (op id, frozen attrs, recording) -> jitted
# callable. This is the engine-bulking analog (reference: InitOpSegs,
# graph_executor.cc:1275): every eager op call is one cached XLA program
# instead of a chain of unfused primitive dispatches; jit itself re-keys
# on shapes/dtypes. The recorded variant returns jax.vjp's pullback — a
# jax.tree_util.Partial, i.e. a pytree — so record() costs one dispatch
# and backward() another (_PULLBACK_APPLY) with no per-step retracing.
# LRU-bounded: step-varying scalar attrs (e.g. Adam's bias-corrected lr on
# the eager path) would otherwise accumulate one compiled program per step.
import collections as _collections

_INVOKE_JIT_CACHE_MAX = 1024
_invoke_jit_cache = _collections.OrderedDict()

# jit-cache telemetry (docs/OBSERVABILITY.md): pre-bound counters so a
# cache hit pays one lazy-global read + one guarded inc
_dispatch_inst = None


def _dinst():
    global _dispatch_inst
    if _dispatch_inst is None:
        from ..observability import dispatch_instruments
        _dispatch_inst = dispatch_instruments()
    return _dispatch_inst


class _TimedFirstCall:
    """Wraps a fresh jit so its FIRST invocation — the one that traces
    and compiles — lands in the compile-seconds histogram and the
    flight recorder; then the raw jitted fn is swapped back into the
    cache, so steady-state dispatch pays nothing."""

    __slots__ = ('fn', 'op', 'key')

    def __init__(self, fn, op, key):
        self.fn = fn
        self.op = op
        self.key = key

    def __call__(self, *args):
        import time as _t
        t0 = _t.perf_counter()
        ret = self.fn(*args)
        dt = _t.perf_counter() - t0
        # un-wrap: later hits dispatch straight to the jitted fn
        if _invoke_jit_cache.get(self.key, (None,))[0] is self:
            _invoke_jit_cache[self.key] = (self.fn, self.op)
        try:
            from ..observability import (enabled, record_event,
                                         trainer_instruments)
            if enabled():
                trainer_instruments().compile_seconds.observe(dt)
                record_event('compile', op=getattr(self.op, 'name',
                                                   str(self.op)),
                             seconds=round(dt, 6))
        except Exception:
            pass
        return ret


def _get_jitted(op, attrs, recording, variadic):
    """Return (jitted_fn, dyn_names): step-varying attrs listed in
    op.dynamic_attrs (e.g. Adam's bias-corrected lr) are excluded from the
    cache key and passed as traced scalar operands, so schedulers never
    force a recompile.

    Trace-purity (docs/ANALYSIS.md): the knobs op bodies consult under
    trace (vjp rescheduling, internal conv layout) are snapshotted HERE
    — on the host, at program-build time — installed over the trace via
    traceknobs.scope, and folded into the cache key, so flipping a knob
    re-jits instead of silently reusing the other setting's program."""
    from ..ops import traceknobs as _tknobs
    knobs = _tknobs.snapshot()
    dyn_names = () if op.needs_rng else tuple(
        n for n in op.dynamic_attrs
        if isinstance(attrs.get(n), (int, float))
        and not isinstance(attrs.get(n), bool))
    static = {k: v for k, v in attrs.items() if k not in dyn_names}
    key = (id(op), tuple(sorted((k, _attr_hashable(v))
                                for k, v in static.items())),
           dyn_names, bool(recording), bool(op.needs_rng),
           knobs.cache_key)
    cached = _invoke_jit_cache.get(key)
    if cached is not None:
        _invoke_jit_cache.move_to_end(key)
        _dinst().jit_hits.inc()
        return cached[0], dyn_names
    base_fn = op.bind_attrs(**static)
    nd_ = len(dyn_names)

    def call(dyn_vals, arrs):
        kw = dict(zip(dyn_names, dyn_vals))
        if variadic:
            return base_fn(list(arrs), **kw)
        return base_fn(*arrs, **kw)

    if op.needs_rng:  # dyn_names is () on this path
        if variadic:
            raw = lambda key_, *arrs: base_fn(key_, list(arrs))
        else:
            raw = base_fn
        if recording:
            def jfn(key_, *arrs):
                return jax.vjp(lambda *a: raw(key_, *a), *arrs)
        else:
            jfn = raw
    else:
        if recording:
            def jfn(*a):
                return jax.vjp(lambda *arrs: call(a[:nd_], arrs), *a[nd_:])
        else:
            def jfn(*a):
                return call(a[:nd_], a[nd_:])

    def scoped(*a, _jfn=jfn):
        with _tknobs.scope(knobs):
            return _jfn(*a)

    jitted = jax.jit(scoped)
    inst = _dinst()
    inst.jit_misses.inc()
    from ..observability import enabled as _obs_enabled
    if _obs_enabled():
        jitted = _TimedFirstCall(jitted, op, key)
    # pin the Operator alongside the compiled fn: the key holds id(op),
    # so the op must stay alive while the entry does (a recycled id would
    # alias a different op onto this entry)
    _invoke_jit_cache[key] = (jitted, op)
    while len(_invoke_jit_cache) > _INVOKE_JIT_CACHE_MAX:
        _invoke_jit_cache.popitem(last=False)
    return jitted, dyn_names


_PULLBACK_APPLY = jax.jit(lambda pb, cts: pb(cts))


def invoke(opname, nd_inputs, attrs, out=None):
    """Invoke a registered op eagerly on NDArrays, recording on the autograd
    tape when inside autograd.record() (Imperative::Invoke + RecordOp).

    When the profiler is running, each dispatch is recorded as an
    'operator' span, fenced with block_until_ready so the span covers
    execution rather than async dispatch (profile_imperative parity;
    reference: profiler.h:438 — the reference profiler also serializes
    the engine while profiling)."""
    from .. import profiler as _profiler
    if not _profiler.is_running():
        return _invoke_impl(opname, nd_inputs, attrs, out=out)
    ret = None

    def _fence():
        for leaf in (ret if isinstance(ret, (list, tuple)) else [ret]):
            if isinstance(leaf, NDArray):
                leaf._data.block_until_ready()

    with _profiler.op_span(
            opname if isinstance(opname, str) else opname.name, _fence):
        ret = _invoke_impl(opname, nd_inputs, attrs, out=out)
    return ret


def _invoke_impl(opname, nd_inputs, attrs, out=None):
    op = _registry.get(opname) if isinstance(opname, str) else opname
    variadic = op.num_inputs == -1
    flat_inputs = list(nd_inputs)
    arrays = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
              for x in flat_inputs]
    attrs = {k: v for k, v in attrs.items() if v is not None or k in ('axis',)}
    if 'training' in op.attr_names and 'training' not in attrs:
        attrs['training'] = autograd.is_training()

    recording = autograd.is_recording() and any(
        isinstance(x, NDArray) and x._entry is not None for x in flat_inputs)

    # Under an outer trace (CachedOp/pjit) inputs are tracers: call the
    # pure fn directly so the captured graph stays flat for XLA fusion.
    traced = any(isinstance(a, jax.core.Tracer) for a in arrays)

    if traced:
        # AMP (docs/PRECISION.md): an active policy scope recasts this
        # op's floating operands — matmul-family ops down to the
        # compute dtype (the fp32 master becomes an in-program compute
        # copy), softmax/loss/reduction ops up to f32. Trace-time only:
        # eager dispatches below never consult the scope.
        _amp_policy = _amp_current()
        if _amp_policy is not None:
            arrays = _amp_policy.cast_op_inputs(op.name, arrays)

    from ..config import naive_engine as _naive, bulk_exec as _bulk
    naive = not traced and _naive()

    jitted = None
    dyn_names = ()
    if not traced and not op.nojit and not naive and \
            _bulk(autograd.is_training()):
        try:
            jitted, dyn_names = _get_jitted(op, attrs, recording, variadic)
        except TypeError:  # unhashable attr — fall back to direct dispatch
            jitted = None

    if jitted is not None:
        # weak-typed scalars (no explicit dtype) so a traced lr does not
        # promote bf16 weights to f32, matching python-float semantics
        call_args = [jnp.asarray(float(attrs[n]))
                     for n in dyn_names] + arrays
        if op.needs_rng:
            used_key = _random.next_key()
            call_args = [used_key] + call_args
        else:
            used_key = None
        if recording:
            out_arrays, vjp_fn = jitted(*call_args)
        else:
            out_arrays = jitted(*call_args)
            vjp_fn = None
    else:
        base_fn = op.bind_attrs(**attrs)
        used_key = None
        if op.needs_rng:
            key = used_key = _random.next_key()
            if variadic:
                fn = lambda *arrs: base_fn(key, list(arrs))
            else:
                fn = lambda *arrs: base_fn(key, *arrs)
        elif variadic:
            fn = lambda *arrs: base_fn(list(arrs))
        else:
            fn = base_fn
        if recording and op.nojit and op.bwd is not None:
            # dynamic-shape op: forward runs eagerly (untraceable), the
            # registered hand-written pullback supplies the gradient
            out_arrays = fn(*arrays)
            single_out = not isinstance(out_arrays, (tuple, list))

            def vjp_fn(cts, _in=tuple(arrays), _out=out_arrays,
                       _single=single_out):
                cts_t = (cts,) if _single else tuple(cts)
                outs_t = (_out,) if _single else tuple(_out)
                return op.bwd(_in, outs_t, cts_t, **attrs)
        elif recording:
            out_arrays, vjp_fn = jax.vjp(fn, *arrays)
        else:
            out_arrays = fn(*arrays)
            vjp_fn = None

    single = not isinstance(out_arrays, (tuple, list))
    outs_raw = [out_arrays] if single else list(out_arrays)
    if naive:
        # NaiveEngine debug mode (env_var.md:104): synchronous execution,
        # so failures surface at the faulting op with a python traceback
        outs_raw = [jax.block_until_ready(a) for a in outs_raw]
    outputs = [NDArray(a) for a in outs_raw]

    if recording:
        in_entries = [x._entry if isinstance(x, NDArray) else None
                      for x in flat_inputs]
        if jitted is not None:
            # Route the pullback (a jax.tree_util.Partial pytree) through
            # the shared jitted applier so backward() is one compiled
            # dispatch per node instead of an eager primitive walk. Only
            # for jit-produced pullbacks: an eager jax.vjp Partial has
            # fresh identity per call and would retrace _PULLBACK_APPLY
            # every backward.
            apply_fn = (lambda cts, _pb=vjp_fn: _PULLBACK_APPLY(_pb, cts))
        else:
            apply_fn = vjp_fn
        node = TapeNode(apply_fn, in_entries, len(outputs),
                        [o.shape for o in outputs],
                        [o._data.dtype for o in outputs],
                        op_ref=(op, dict(attrs), tuple(arrays), used_key)
                        if op.bwd is None else None)
        for i, o in enumerate(outputs):
            o._entry = Entry(node=node, index=i)

    # in-place update semantics for optimizer/mutating ops
    if out is not None:
        out_list = out if isinstance(out, (list, tuple)) else [out]
        for tgt, src in zip(out_list, outputs):
            if tgt is not None:
                tgt._data = src._data
                # preserve leaf (variable) entries on in-place writes outside
                # recording — optimizer updates must not demote parameters
                # from autograd leaves (reference: engine write on a var
                # keeps its autograd entry)
                if src._entry is not None:
                    tgt._entry = src._entry
        first = out_list[0] if out_list else outputs[0]
        return out if not isinstance(out, (list, tuple)) else out_list
    if op.mutate_idx and not recording:
        for out_i, in_i in enumerate(op.mutate_idx):
            if in_i < len(flat_inputs) and isinstance(flat_inputs[in_i], NDArray):
                flat_inputs[in_i]._data = outputs[out_i]._data
        return outputs[0] if single or len(outputs) == 1 else tuple(outputs)
    return outputs[0] if single else tuple(outputs)


def _wrap_outputs(arrays):
    return [NDArray(a) for a in arrays]


# ---------------------------------------------------------------------------
# creation / io
# ---------------------------------------------------------------------------


def _place(data, ctx):
    if ctx is not None:
        data = jax.device_put(data, ctx.jax_device())
    return data


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        data = source_array._data
        if dtype is not None:
            data = data.astype(np_dtype(dtype))
        return NDArray(_place(data, ctx), ctx=ctx)
    if dtype is None:
        # MXNet rule: numpy sources keep their dtype (float64→float32 since
        # the default build has no fp64 path); python lists default float32.
        if isinstance(source_array, onp.ndarray):
            arr = source_array
            if arr.dtype == onp.float64:
                arr = arr.astype(onp.float32)
            elif arr.dtype == onp.int64:
                arr = arr.astype(onp.int64)
        else:
            arr = onp.asarray(source_array, dtype=onp.float32)
    else:
        arr = onp.asarray(source_array, dtype=np_dtype(dtype))
    return NDArray(_place(jnp.asarray(arr), ctx), ctx=ctx)


def zeros(shape, ctx=None, dtype='float32', **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.zeros(shape, np_dtype(dtype)), ctx), ctx=ctx)


def ones(shape, ctx=None, dtype='float32', **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.ones(shape, np_dtype(dtype)), ctx), ctx=ctx)


def full(shape, val, ctx=None, dtype='float32', **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.full(shape, val, np_dtype(dtype)), ctx), ctx=ctx)


def empty(shape, ctx=None, dtype='float32'):
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype='float32'):
    out = jnp.arange(start, stop, step, dtype=np_dtype(dtype))
    if repeat > 1:
        out = jnp.repeat(out, int(repeat))
    return NDArray(_place(out, ctx), ctx=ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return invoke('Concat', list(arrays), {'dim': axis,
                                           'num_args': len(arrays)})


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination))


def maximum(lhs, rhs):
    """Elementwise max with scalar/broadcast handling
    (reference: python/mxnet/ndarray/ndarray.py maximum)."""
    if isinstance(lhs, NDArray):
        return lhs._binary('broadcast_maximum', rhs)
    if isinstance(rhs, NDArray):
        return rhs._binary('broadcast_maximum', lhs)
    return max(lhs, rhs)


def minimum(lhs, rhs):
    """Elementwise min (reference twin of maximum)."""
    if isinstance(lhs, NDArray):
        return lhs._binary('broadcast_minimum', rhs)
    if isinstance(rhs, NDArray):
        return rhs._binary('broadcast_minimum', lhs)
    return min(lhs, rhs)


def waitall():
    """Block on all outstanding async work (reference: MXNDArrayWaitAll).

    A device runs its programs in dispatch order, so waiting on one
    fresh trivial computation per local device drains everything
    enqueued before it; effects_barrier() flushes host callbacks.
    Device errors propagate to the caller."""
    jax.effects_barrier()
    for dev in jax.local_devices():
        (jax.device_put(onp.zeros((), 'float32'), dev) + 1) \
            .block_until_ready()


def imports_done():
    return True


# ---------------------------------------------------------------------------
# save / load — the REAL MXNet NDArray container format
# (reference: src/ndarray/ndarray.cc:1578 NDArray::Save / :1695 Load,
# list container :1781 kMXAPINDArrayListMagic). Little-endian layout:
#   uint64 0x112 magic, uint64 reserved,
#   uint64 count, count x [uint32 0xF993FAC9, int32 stype(0=dense),
#       int32 ndim + ndim x int64 shape, int32 dev_type + int32 dev_id,
#       int32 type_flag, raw bytes],
#   uint64 name count, names as (uint64 len + bytes).
# Files written here load in reference MXNet and vice versa (dense
# arrays; bf16 is stored as f32 — the reference has no bf16 type flag).
# The pre-round-2 private npz container is still read for back-compat.
# ---------------------------------------------------------------------------

_NDARRAY_MAGIC = 0x112745F8          # legacy private container
_MX_LIST_MAGIC = 0x112               # kMXAPINDArrayListMagic
_MX_V2_MAGIC = 0xF993FAC9            # NDARRAY_V2_MAGIC

# mshadow TypeFlag <-> numpy (reference: mshadow/base.h TypeFlag)
_MX_TYPE_FLAGS = {0: 'float32', 1: 'float64', 2: 'float16', 3: 'uint8',
                  4: 'int32', 5: 'int8', 6: 'int64'}
_MX_FLAG_OF = {v: k for k, v in _MX_TYPE_FLAGS.items()}


def _mx_save_one(f, arr):
    import struct
    a = onp.ascontiguousarray(arr.asnumpy())
    if a.dtype.name not in _MX_FLAG_OF:
        a = a.astype(onp.float32)    # bf16 etc.: no reference type flag
    f.write(struct.pack('<Ii', _MX_V2_MAGIC, 0))          # magic, dense
    f.write(struct.pack('<i', a.ndim))
    f.write(struct.pack('<%dq' % a.ndim, *a.shape))
    f.write(struct.pack('<ii', 1, 0))                      # cpu:0
    f.write(struct.pack('<i', _MX_FLAG_OF[a.dtype.name]))
    f.write(a.tobytes())


def _mx_load_one(f):
    import struct
    magic, = struct.unpack('<I', f.read(4))
    if magic != _MX_V2_MAGIC:
        # legacy V1/V0: magic is the V1 marker or the raw ndim
        if magic == 0xF993FAC8:
            ndim, = struct.unpack('<i', f.read(4))
            shape = struct.unpack('<%dq' % ndim, f.read(8 * ndim))
        else:
            ndim = magic
            shape = struct.unpack('<%dI' % ndim, f.read(4 * ndim))
    else:
        stype, = struct.unpack('<i', f.read(4))
        if stype not in (-1, 0):
            raise ValueError('sparse .params entries are not supported '
                             '(storage type %d)' % stype)
        ndim, = struct.unpack('<i', f.read(4))
        shape = struct.unpack('<%dq' % ndim, f.read(8 * ndim))
    f.read(8)                                              # context
    type_flag, = struct.unpack('<i', f.read(4))
    dtype = onp.dtype(_MX_TYPE_FLAGS[type_flag])
    n = int(onp.prod(shape)) if shape else 1
    data = onp.frombuffer(f.read(n * dtype.itemsize), dtype=dtype)
    return NDArray(jnp.asarray(data.reshape(shape)))


def save(fname, data):
    """Save NDArrays in the reference MXNet .params container."""
    import struct
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    else:
        names = []
        arrays = list(data)
    with open(fname, 'wb') as f:
        f.write(struct.pack('<QQ', _MX_LIST_MAGIC, 0))
        f.write(struct.pack('<Q', len(arrays)))
        for a in arrays:
            _mx_save_one(f, a)
        f.write(struct.pack('<Q', len(names)))
        for n in names:
            nb = n.encode('utf-8')
            f.write(struct.pack('<Q', len(nb)))
            f.write(nb)


def load(fname):
    """Load a .params file — reference MXNet format or the legacy private
    npz container from earlier rounds."""
    with open(fname, 'rb') as f:
        return load_fobj(f, what=fname)


def load_fobj(f, what='<buffer>'):
    """Parse the .params container from any binary file object (the
    in-memory MXNDArrayLoadFromBuffer path reads a BytesIO)."""
    import struct
    magic, _ = struct.unpack('<QQ', f.read(16))
    if magic == _MX_LIST_MAGIC:
        count, = struct.unpack('<Q', f.read(8))
        arrays = [_mx_load_one(f) for _ in range(count)]
        nname, = struct.unpack('<Q', f.read(8))
        names = []
        for _ in range(nname):
            ln, = struct.unpack('<Q', f.read(8))
            names.append(f.read(ln).decode('utf-8'))
    elif magic == _NDARRAY_MAGIC:
        return _load_legacy_npz(f)
    else:
        raise ValueError('invalid NDArray file %s' % what)
    if names:
        return dict(zip(names, arrays))
    return arrays


def _load_legacy_npz(f):
    import io as _io
    import struct
    count, = struct.unpack('<Q', f.read(8))
    nname, = struct.unpack('<Q', f.read(8))
    names = []
    for _ in range(nname):
        ln, = struct.unpack('<Q', f.read(8))
        names.append(f.read(ln).decode('utf-8'))
    blen, = struct.unpack('<Q', f.read(8))
    npz = onp.load(_io.BytesIO(f.read(blen)))
    arrays = [NDArray(jnp.asarray(npz[str(i)])) for i in range(count)]
    if names:
        return dict(zip(names, arrays))
    return arrays
