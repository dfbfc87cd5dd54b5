"""Backend acquisition that degrades instead of crashing.

``jax.devices()`` / ``jax.default_backend()`` raise RuntimeError when
the accelerator backend cannot initialise its device (another process
holds the chip, the runtime is mid-restart). ``acquire_backend`` wraps that
first backend touch in a bounded-retry policy and always returns a
typed :class:`BackendStatus`:

    tpu           — an accelerator answered; run at full fidelity
    cpu-fallback  — accelerator unreachable (or absent) but the CPU
                    backend works; callers run degraded
    unavailable   — no backend at all; callers emit a structured
                    artifact and exit 0, not a stack trace

Retry knobs come from the environment (MXNET_TPU_ACQUIRE_ATTEMPTS /
_BACKOFF_S / _DEADLINE_S, docs/ENV_VARS.md) so the driver can shape
outage behavior without code changes. Injected faults skip the backoff
sleep (InjectedFault.no_backoff), keeping fault-injected CI fast.
"""
from __future__ import annotations

from .policy import (Retry, RetryExhausted, DeviceUnavailableError,
                     DeviceStallError, get_injector, is_transient)

__all__ = ['BackendStatus', 'acquire_backend']

_DEVICE_FAULTS = ('device_unavailable', 'device_stall')


class BackendStatus:
    """Typed outcome of backend acquisition."""

    __slots__ = ('state', 'platform', 'device_kind', 'device_count',
                 'attempts', 'error')

    def __init__(self, state, platform=None, device_kind=None,
                 device_count=0, attempts=1, error=None):
        assert state in ('tpu', 'cpu-fallback', 'unavailable'), state
        self.state = state
        self.platform = platform
        self.device_kind = device_kind
        self.device_count = device_count
        self.attempts = attempts
        self.error = error

    @property
    def usable(self):
        return self.state != 'unavailable'

    @property
    def degraded(self):
        return self.state != 'tpu'

    def as_dict(self):
        """Stable-schema dict for JSON artifacts (every key always
        present, so ok/degraded/unavailable runs are schema-identical)."""
        return {'state': self.state, 'platform': self.platform,
                'device_kind': self.device_kind,
                'device_count': self.device_count,
                'attempts': self.attempts, 'error': self.error}

    def __repr__(self):
        return ('BackendStatus(state=%r, platform=%r, devices=%d, '
                'attempts=%d, error=%r)'
                % (self.state, self.platform, self.device_count,
                   self.attempts, self.error))


def _default_retry():
    # knobs resolve through the typed mx.config registry (set() override
    # > env > default) — one source of truth with docs/ENV_VARS.md
    from ..config import get as _cfg
    return Retry(
        max_attempts=int(_cfg('MXNET_TPU_ACQUIRE_ATTEMPTS')),
        base_delay=_cfg('MXNET_TPU_ACQUIRE_BACKOFF_S'),
        max_delay=60.0,
        deadline=_cfg('MXNET_TPU_ACQUIRE_DEADLINE_S'),
        predicate=is_transient)


def acquire_backend(retry=None, injector=None, allow_cpu_fallback=True):
    """Initialize the JAX backend under a retry policy; never raises
    for infrastructure failure.

    Returns a :class:`BackendStatus`. Deterministic (non-transient)
    errors — a real bug in backend setup — still propagate: hiding
    those behind 'unavailable' would turn product regressions into
    quiet degraded runs.
    """
    retry = retry or _default_retry()
    injector = injector if injector is not None else get_injector()
    attempts = [0]

    def _probe(platform=None):
        attempts[0] += 1
        injector.fire('device' if platform is None else 'device.fallback',
                      _DEVICE_FAULTS)
        import jax
        devs = jax.devices() if platform is None else jax.devices(platform)
        if not devs:
            raise DeviceUnavailableError(
                'device_unavailable', 'device',
                'backend returned an empty device list')
        return devs

    primary_error = None
    try:
        devs = retry.call(_probe)
    except RetryExhausted as exc:
        primary_error = exc
    except RuntimeError as exc:
        # Retry re-raised without retrying (its predicate rejected the
        # error). jax wraps both outages and config bugs in
        # RuntimeError; only infrastructure signatures degrade — a
        # deterministic bug must stay a loud crash, per the contract
        if not is_transient(exc):
            raise
        primary_error = RetryExhausted(str(exc), attempts=attempts[0],
                                       last_error=exc)
    if primary_error is None:
        platform = devs[0].platform
        state = 'tpu' if platform not in ('cpu',) else 'cpu-fallback'
        return BackendStatus(state, platform=platform,
                             device_kind=devs[0].device_kind,
                             device_count=len(devs),
                             attempts=attempts[0])

    if allow_cpu_fallback:
        try:
            devs = _probe('cpu')
        except (RuntimeError, DeviceStallError):
            pass
        else:
            return BackendStatus(
                'cpu-fallback', platform='cpu',
                device_kind=devs[0].device_kind,
                device_count=len(devs), attempts=attempts[0],
                error=str(primary_error.last_error or primary_error))

    return BackendStatus(
        'unavailable', attempts=attempts[0],
        error=str(primary_error.last_error or primary_error))
