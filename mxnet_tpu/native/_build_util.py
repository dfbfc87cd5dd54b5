"""Shared compile-on-first-use machinery for the native libraries
(recio / predict ABI / core C API). One place owns the g++ command,
the tmp-file + atomic-replace dance, provenance, and the
compile-failure diagnostics, so the per-library loaders can't drift.

Provenance: a built ``.so`` is loaded only when the ``.src`` stamp
written next to it holds the SHA-256 of the source it is asked to
stand for. A binary without a matching stamp — copied in from another
tree, left over from an older source, of unknown origin — is rebuilt,
never loaded (file times say nothing once a tree has been copied).
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import sysconfig
import warnings

__all__ = ['build_so', 'load_library']


def build_so(src, so_path, link_python=False):
    """Compile ``src`` into ``so_path`` (atomic replace; per-process
    tmp file so concurrent builders never clobber each other)."""
    os.makedirs(os.path.dirname(so_path), exist_ok=True)
    tmp = '%s.tmp.%d' % (so_path, os.getpid())
    cmd = ['g++', '-O2', '-std=c++17', '-shared', '-fPIC', '-pthread']
    if link_python:
        cmd.append('-I' + sysconfig.get_path('include'))
    cmd += [src, '-o', tmp]
    if link_python:
        libdir = sysconfig.get_config_var('LIBDIR') or ''
        if libdir:
            cmd += ['-L' + libdir, '-Wl,-rpath,' + libdir]
        cmd.append('-lpython%d.%d'
                   % __import__('sys').version_info[:2])
    subprocess.run(cmd, check=True, capture_output=True, timeout=180)
    os.replace(tmp, so_path)
    with open(so_path + '.src', 'w') as f:
        f.write(_source_digest(src))


def _source_digest(src):
    with open(src, 'rb') as f:
        return hashlib.sha256(f.read()).hexdigest()


def _stale(src, so_path):
    try:
        with open(so_path + '.src') as f:
            return not os.path.exists(so_path) \
                or f.read().strip() != _source_digest(src)
    except OSError:
        return True


def load_library(src, so_path, bind, link_python=False, name=None):
    """Compile (when missing or not built from ``src``), then ``bind`` the
    library. ``bind`` must raise OSError/AttributeError on an
    ABI-stale .so — the loader rebuilds once. Returns the bound
    library or None (with a warning carrying the g++ stderr)."""
    name = name or os.path.basename(so_path)
    try:
        if _stale(src, so_path):
            build_so(src, so_path, link_python=link_python)
        try:
            return bind(so_path)
        except (OSError, AttributeError):
            build_so(src, so_path, link_python=link_python)
            return bind(so_path)
    except subprocess.CalledProcessError as e:
        warnings.warn('%s build failed:\n%s'
                      % (name, (e.stderr or b'').decode('utf-8',
                                                        'replace')[-2000:]),
                      stacklevel=2)
    except Exception as e:
        warnings.warn('%s unavailable: %s' % (name, e), stacklevel=2)
    return None
