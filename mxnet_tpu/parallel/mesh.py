"""Device mesh management.

Reference analog: there is none — MXNet enumerates GPUs into a ctx list and
wires Comm objects between them (src/kvstore/comm.h). Here the device
topology is a named Mesh and placement is declarative (scaling-book recipe:
pick a mesh, annotate shardings, let XLA insert collectives).
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import jax
import numpy as onp
from jax.sharding import Mesh

__all__ = ['create_mesh', 'current_mesh', 'local_mesh']

_state = threading.local()

# 'model' is the first-class tensor-parallel axis the sharding rules and
# gluon/Module annotations target (docs/PARALLEL.md); 'tp' remains as the
# legacy Megatron-style alias. Elasticity shrinks only 'dp' — every other
# axis is tied to program structure (resilience/elastic.py).
AXES = ('dp', 'model', 'pp', 'tp', 'sp', 'ep')


def create_mesh(axes=None, devices=None):
    """Create a named device mesh.

    Parameters
    ----------
    axes : dict name->size (e.g. {'dp': 4, 'model': 2}) or None for pure
        DP over all devices. Sizes must multiply to the device count; a
        -1 size is inferred (so {'dp': -1, 'model': 2} spans whatever
        devices exist with a fixed 2-way model axis).
    devices : explicit device list (defaults to jax.devices()).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {'dp': n}
    axes = OrderedDict(axes)
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(onp.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
        axes = OrderedDict(zip(axes.keys(), sizes))
    total = int(onp.prod(list(axes.values())))
    assert total == n, 'mesh axes %s do not cover %d devices' % (dict(axes), n)
    arr = onp.asarray(devices).reshape(tuple(axes.values()))
    mesh = Mesh(arr, tuple(axes.keys()))
    _state.mesh = mesh
    return mesh


def current_mesh():
    """The most recently created mesh (or a 1-device default)."""
    m = getattr(_state, 'mesh', None)
    if m is None:
        m = create_mesh({'dp': 1}, devices=jax.devices()[:1])
    return m


def local_mesh(n_devices=None, axes=None):
    """Mesh over the first n local devices (testing helper; the reference
    analog is the local-process fake cluster, SURVEY.md §4 fixtures)."""
    devs = jax.devices()[:n_devices] if n_devices else jax.devices()
    return create_mesh(axes or {'dp': len(devs)}, devices=devs)


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """shard_map with per-output replication checking off. One spelling
    for every parallel module."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
