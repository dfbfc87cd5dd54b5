"""Device context, TPU-native analog of mxnet.context.

Reference parity: python/mxnet/context.py (Context class, current-context
stack) and include/mxnet/base.h:548 (Context dev_type/dev_id). On TPU the
device taxonomy collapses: ``tpu(i)`` maps to ``jax.devices()[i]``; ``cpu()``
maps to the host platform. ``gpu(i)`` is accepted as an alias for the
accelerator so reference scripts run unmodified (BASELINE north star:
"run unmodified ... by selecting ctx=mx.tpu()").
"""
from __future__ import annotations

import threading

import jax

_DEVTYPE_NAMES = {1: 'cpu', 2: 'gpu', 3: 'cpu_pinned', 5: 'cpu_shared', 6: 'tpu'}
_DEVTYPE_IDS = {v: k for k, v in _DEVTYPE_NAMES.items()}


def _local(devs):
    """On a multi-process runtime, contexts resolve to THIS process's
    devices — a peer host's device is not addressable for eager work
    (docs/DISTRIBUTED.md). Single-process runs see every device, as
    before."""
    if jax.process_count() <= 1:
        return devs
    me = jax.process_index()
    mine = [d for d in devs if d.process_index == me]
    return mine or devs


class Context:
    """A device context.

    Unlike the reference (where Context selects among heterogeneous backends,
    src/storage/storage.cc:63-100), all accelerator contexts resolve to XLA
    devices; ``cpu*`` resolves to the host.
    """

    _default_ctx = threading.local()
    devtype2str = _DEVTYPE_NAMES
    devstr2type = _DEVTYPE_IDS

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in _DEVTYPE_IDS:
                raise ValueError('unknown device type %s' % device_type)
            self.device_typeid = _DEVTYPE_IDS[device_type]
            self.device_id = device_id if device_id is not None else 0

    @property
    def device_type(self):
        return _DEVTYPE_NAMES[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return '%s(%d)' % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, 'value'):
            Context._default_ctx.value = default_device()
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- XLA resolution ----------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax device.

        Invalid device ids raise, matching the reference's engine behavior
        on a bad dev_id (CUDA error surfaced at first use) rather than
        silently clamping to another device.
        """
        if self.device_type.startswith('cpu'):
            try:
                devs = _local(jax.devices('cpu'))
            except RuntimeError:
                # no cpu platform registered (JAX_PLATFORMS=tpu) — fall
                # back to the default backend rather than crash host-side
                # staging paths
                return _local(jax.devices())[0]
            if self.device_id >= len(devs):
                raise ValueError(
                    '%s: only %d cpu device(s) available' % (self, len(devs)))
            return devs[self.device_id]
        devs = _local(jax.devices())
        accel = [d for d in devs if d.platform != 'cpu'] or devs
        if self.device_id >= len(accel):
            raise ValueError(
                '%s: only %d accelerator device(s) available (platform=%s)'
                % (self, len(accel), accel[0].platform if accel else 'none'))
        return accel[self.device_id]

    def empty_cache(self):
        """Reference parity: Context.empty_cache (pooled GPU memory).

        XLA owns the allocator; this is a no-op hook kept for API compat.
        """

    @classmethod
    def default_ctx(cls):
        if not hasattr(cls._default_ctx, 'value'):
            cls._default_ctx.value = default_device()
        return cls._default_ctx.value


def cpu(device_id=0):
    """Return a CPU (host) context."""
    return Context('cpu', device_id)


def cpu_pinned(device_id=0):
    return Context('cpu_pinned', device_id)


def gpu(device_id=0):
    """Accelerator alias — resolves to the XLA accelerator (TPU here)."""
    return Context('gpu', device_id)


def tpu(device_id=0):
    """Return a TPU context backed by ``jax.devices()[device_id]``."""
    return Context('tpu', device_id)


def num_gpus():
    return len([d for d in jax.devices() if d.platform != 'cpu'])


def num_tpus():
    return num_gpus()


def current_context():
    """The context on top of the with-statement stack (default cpu(0))."""
    return Context.default_ctx()


def default_device():
    """Best available compute context: tpu(0) if an accelerator exists.

    TPU-native divergence from the reference: this is also the initial
    default context (the reference defaults to cpu(0) and makes scripts
    pass ctx=mx.gpu() everywhere), so eager creation ops land where the
    compiled programs run. A host with no accelerator resolves to
    cpu(0); a backend that fails to initialise raises — it is never
    turned into a CPU default."""
    return tpu(0) if num_gpus() > 0 else cpu(0)
