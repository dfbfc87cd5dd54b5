"""Native runtime bindings (the L8 bindings story, SURVEY.md §1).

The reference's IO runtime is C++ (dmlc recordio + src/io/ threaded
iterators); this package compiles the TPU-native equivalent
(native/src/recio.cc) with the in-image g++ on first use and binds it
via ctypes — no pybind11 needed. The library is always built from that
source (``_build_util`` never loads a binary it cannot tie to it);
when the toolchain or build is unavailable everything degrades to the
pure-Python paths, with a warning, and ``native.available()`` reports
which path is live.
"""
from __future__ import annotations

import ctypes
import os
import threading

import numpy as np

from ._build_util import load_library

__all__ = ['available', 'lib', 'scan_offsets', 'read_batch', 'RecReader']

_lock = threading.Lock()
_lib = None
_tried = False

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), 'native', 'src',
    'recio.cc')
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          '_build')
_SO = os.path.join(_BUILD_DIR, 'librecio.so')

_ABI = 2


def _bind(path):
    so = ctypes.CDLL(path)
    so.recio_abi_version.restype = ctypes.c_int
    if so.recio_abi_version() != _ABI:
        raise OSError('stale librecio ABI')
    i64 = ctypes.c_int64
    p64 = ctypes.POINTER(ctypes.c_int64)
    so.recio_scan.restype = i64
    so.recio_scan.argtypes = [ctypes.c_char_p, p64, p64, i64]
    so.recio_read_batch.restype = i64
    so.recio_read_batch.argtypes = [ctypes.c_char_p, p64, p64, i64,
                                    ctypes.c_char_p, i64]
    so.recio_reader_create.restype = ctypes.c_void_p
    so.recio_reader_create.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                       ctypes.c_int, ctypes.c_uint64,
                                       ctypes.c_int]
    so.recio_reader_num_records.restype = i64
    so.recio_reader_num_records.argtypes = [ctypes.c_void_p]
    so.recio_reader_next.restype = i64
    so.recio_reader_next.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     i64, p64]
    so.recio_reader_reset.argtypes = [ctypes.c_void_p]
    so.recio_reader_free.argtypes = [ctypes.c_void_p]
    return so


def lib():
    """The loaded native library, building it on first call; None when
    the native path is unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _lib = load_library(_SRC, _SO, _bind, name='librecio')
    return _lib


def available():
    return lib() is not None


class MultiChunkRecords(Exception):
    """File contains cflag!=0 split records: use the python reader,
    which reassembles them."""


def scan_offsets(path):
    """(offsets, lengths) int64 arrays for every record in a .rec file.

    Raises IOError on corrupt framing (matching the python reader's
    magic assertion) and MultiChunkRecords for split-record files."""
    so = lib()
    n = so.recio_scan(path.encode(), None, None, 0)
    while True:
        if n == -3:
            raise MultiChunkRecords(path)
        if n < 0:
            raise IOError('corrupt or unreadable .rec file %s' % path)
        offs = np.zeros(n, np.int64)
        lens = np.zeros(n, np.int64)
        got = so.recio_scan(
            path.encode(),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n)
        if got == n:
            return offs, lens
        n = got  # file changed between scans: retry at the new count


def read_batch(path, offsets, lengths):
    """Payload bytes for the given record slots, as a list of bytes."""
    so = lib()
    offs = np.ascontiguousarray(offsets, np.int64)
    lens = np.ascontiguousarray(lengths, np.int64)
    total = int(lens.sum())
    buf = ctypes.create_string_buffer(max(total, 1))
    w = so.recio_read_batch(
        path.encode(),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(offs), buf, total)
    if w != total:
        raise IOError('short read from %s' % path)
    out = []
    pos = 0
    base = ctypes.addressof(buf)
    for ln in lens:
        # string_at slices straight from the packed buffer (no full-
        # buffer intermediate copy like buf.raw)
        out.append(ctypes.string_at(base + pos, int(ln)))
        pos += int(ln)
    return out


class RecReader:
    """Background-thread prefetching batch reader over a .rec file
    (native analog of PrefetcherIter; shuffling per epoch)."""

    def __init__(self, path, batch_size, shuffle=False, seed=0,
                 prefetch=4):
        so = lib()
        if so is None:
            raise RuntimeError('native recio unavailable')
        self._so = so
        self._path = path
        self._batch = batch_size
        self._h = so.recio_reader_create(path.encode(), batch_size,
                                         1 if shuffle else 0, seed,
                                         prefetch)
        if not self._h:
            raise IOError('cannot open %s' % path)
        self.num_records = so.recio_reader_num_records(self._h)
        # capacity: generous per-batch buffer, grown on demand
        self._cap = 1 << 20

    def _check_open(self):
        if not self._h:
            raise RuntimeError('RecReader is closed')

    def next_batch(self):
        """List of raw record payloads, or None at epoch end."""
        self._check_open()
        sizes = np.zeros(self._batch, np.int64)
        while True:
            buf = ctypes.create_string_buffer(self._cap)
            n = self._so.recio_reader_next(
                self._h, buf, self._cap,
                sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
            if n == 0:
                return None
            if n < 0:
                self._cap = max(-int(n), self._cap * 2)
                continue
            out = []
            pos = 0
            base = ctypes.addressof(buf)
            for i in range(n):
                ln = int(sizes[i])
                out.append(ctypes.string_at(base + pos, ln))
                pos += ln
            return out

    def reset(self):
        self._check_open()
        self._so.recio_reader_reset(self._h)

    def close(self):
        if self._h:
            self._so.recio_reader_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
