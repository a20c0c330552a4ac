"""ctypes loader/builder for the native runtime components (csrc/).

The reference reached native code through the mgzip wheel (кластер.py:51,62);
here the native components are part of the framework:

- ``libdwz.so`` (csrc/wire.cc): block-parallel deflate codec with a C ABI.
  ``load()`` returns a wrapper exposing ``compress``/``decompress`` with the
  exact signature wire.py expects; any failure returns None and wire.py
  stays on its pure-Python zlib path.
- ``libdwbatch.so`` (csrc/batch.cc): fused gather–cast–pack batch assembly
  for the ShardedLoader host input path.  ``load_batch()`` returns a
  :class:`NativeBatch` (or None), and data/loader.py falls back to the
  byte-identical numpy path — same discipline as the wire codec.

Each ``load*()`` runs ``make`` for its library on first use (a no-op when
the ``.so`` is newer than its source); failures are cached so a missing
toolchain costs one probe, not one per call.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Dict, Optional

import numpy as np

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "csrc")
_LIB = os.path.join(_CSRC, "libdwz.so")
_BATCH_LIB = os.path.join(_CSRC, "libdwbatch.so")
_MAX_THREADS = min(12, os.cpu_count() or 1)  # reference thread=12 (кластер.py:51)

_lock = threading.Lock()
_cached: Dict[str, object] = {}
_failed: Dict[str, bool] = {}


class NativeWire:
    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.dwz_compress.restype = ctypes.c_int
        lib.dwz_compress.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.dwz_decompress.restype = ctypes.c_int
        lib.dwz_decompress.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.dwz_free.restype = None
        lib.dwz_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]

    def _take(self, out, out_len) -> bytes:
        try:
            return ctypes.string_at(out, out_len.value)
        finally:
            self._lib.dwz_free(out)

    def compress(self, data: bytes, level: int, block_size: int) -> bytes:
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_size_t()
        rc = self._lib.dwz_compress(
            data, len(data), level, block_size, _MAX_THREADS,
            ctypes.byref(out), ctypes.byref(out_len),
        )
        if rc != 0:
            raise RuntimeError(f"dwz_compress failed with code {rc}")
        return self._take(out, out_len)

    def decompress(self, data: bytes) -> bytes:
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_size_t()
        rc = self._lib.dwz_decompress(
            data, len(data), _MAX_THREADS, ctypes.byref(out), ctypes.byref(out_len)
        )
        if rc == -5:
            raise ValueError("bad wire magic; not a DWZ1 frame")
        if rc == -6:
            raise ValueError("truncated frame")
        if rc == -7:
            raise ValueError("trailing garbage in frame")
        if rc != 0:
            raise ValueError(f"corrupt frame (dwz_decompress code {rc})")
        return self._take(out, out_len)


def check_label_range(lo, hi) -> None:
    """The compact-cast label contract, shared verbatim by the numpy paths
    (data/loader.py) and the kernel's rc=-3 translation below: int8 labels
    with the -1 void sentinel.  One site owns the bounds and the message."""
    if lo < -1 or hi > 127:
        raise ValueError(
            f"compact=True needs labels in [-1, 127] for int8, "
            f"got range [{lo}, {hi}]"
        )


class NativeBatch:
    """Fused gather(+compact cast)+pack into caller-owned buffers
    (csrc/batch.cc).  One memory pass, tiles fanned over a thread pool;
    ctypes releases the GIL for the duration of the call."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.dwb_gather_pack.restype = ctypes.c_int
        lib.dwb_gather_pack.argtypes = [
            ctypes.c_void_p,  # images fp32 [n_src, img_elems]
            ctypes.c_void_p,  # labels int32 [n_src, lab_elems]
            ctypes.c_void_p,  # indices int64 [n_out]
            ctypes.c_size_t,  # n_out
            ctypes.c_size_t,  # n_src
            ctypes.c_size_t,  # img_elems
            ctypes.c_size_t,  # lab_elems
            ctypes.c_int,     # compact
            ctypes.c_void_p,  # img_out
            ctypes.c_void_p,  # lab_out
            ctypes.POINTER(ctypes.c_int32),  # lab_range[2]
            ctypes.c_int,     # max_threads
        ]

    def gather_pack(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        indices: np.ndarray,
        img_out: np.ndarray,
        lab_out: np.ndarray,
        compact: bool,
    ) -> None:
        """Gather ``images[indices]``/``labels[indices]`` into the
        preallocated outputs, casting bf16/int8 when ``compact``.  The
        caller (data/loader.py) validates dtypes/contiguity — this wrapper
        only asserts the invariants cheaply and translates error codes to
        the numpy path's exceptions."""
        n_out = len(indices)
        n_src = images.shape[0]
        img_elems = int(np.prod(images.shape[1:], dtype=np.int64))
        lab_elems = int(np.prod(labels.shape[1:], dtype=np.int64))
        # Hard raises, not asserts: these guard raw-pointer writes in C —
        # under python -O an assert vanishes and a size mismatch becomes
        # silent out-of-bounds memory corruption instead of an exception.
        if not (indices.dtype == np.int64 and indices.flags.c_contiguous):
            raise ValueError("indices must be a C-contiguous int64 array")
        if img_out.size != n_out * img_elems or lab_out.size != n_out * lab_elems:
            raise ValueError(
                f"destination sizes ({img_out.size}, {lab_out.size}) do not "
                f"match {n_out} tiles of ({img_elems}, {lab_elems}) elements"
            )
        lab_range = (ctypes.c_int32 * 2)()
        rc = self._lib.dwb_gather_pack(
            images.ctypes.data, labels.ctypes.data, indices.ctypes.data,
            n_out, n_src, img_elems, lab_elems, int(compact),
            img_out.ctypes.data, lab_out.ctypes.data, lab_range,
            _MAX_THREADS,
        )
        if rc == -3:
            check_label_range(lab_range[0], lab_range[1])
        if rc == -2:
            raise IndexError(
                f"gather index out of range for dataset of {n_src} tiles"
            )
        if rc != 0:
            raise RuntimeError(f"dwb_gather_pack failed with code {rc}")


def _build(target: str) -> bool:
    """``make -s <target>`` in csrc/: builds a missing library, rebuilds
    one older than its source, and is a no-op when current.  False when
    make or the compiler is missing, or the build fails."""
    try:
        subprocess.run(
            ["make", "-s", target],
            cwd=_CSRC,
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (OSError, subprocess.SubprocessError):
        return False
    return os.path.exists(os.path.join(_CSRC, target))


def _load(path: str, wrapper):
    """Shared make-then-load-once core; failures cached per library.

    make runs on every first load, not only when the file is missing: the
    ``.so`` files are git-ignored build outputs, and a stale one must not
    outlive an edit to ``csrc/*.cc``."""
    name = os.path.basename(path)
    with _lock:
        if name in _cached:
            return _cached[name]
        if _failed.get(name):
            return None
        if not _build(name):
            _failed[name] = True
            return None
        try:
            _cached[name] = wrapper(ctypes.CDLL(path))
        except (OSError, AttributeError):
            _failed[name] = True
            return None
        return _cached[name]


def load() -> Optional[NativeWire]:
    """The loaded native wire codec, (re)built on first use; None on
    failure (wire.py stays on its pure-Python zlib path)."""
    return _load(_LIB, NativeWire)


def load_batch() -> Optional[NativeBatch]:
    """The loaded native batch-assembly kernel, (re)built on first use;
    None on failure (data/loader.py logs once and stays on numpy)."""
    return _load(_BATCH_LIB, NativeBatch)
