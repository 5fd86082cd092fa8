"""ctypes bindings for the native (C++) data-loading runtime.

``libdtfio.so`` (see ``dtf_tpu/native/dtfio.cpp``) does mmap'd IDX parsing,
deterministic per-epoch shuffling, u8→f32 normalization, and batch assembly
on a background prefetch thread with a double buffer — the successor of the
reference era's C++ FIFOQueue/queue-runner input machinery (SURVEY.md §2b
N7). Python's only per-batch work is a memcpy into a numpy array.

Builds on demand with g++ (cached next to the source); falls back cleanly if
no compiler is available — callers should use :func:`native_available` and
fall back to :class:`dtf_tpu.data.mnist.MnistData`.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Iterator

import numpy as np

log = logging.getLogger("dtf_tpu")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libdtfio.so")
_lib = None
_lib_lock = threading.Lock()


def _stale() -> bool:
    """Build unless the library is strictly newer than BOTH its source and
    the Makefile. The library is not committed: a fresh checkout has none,
    and a copied tree keeps no mtimes worth trusting — its ``-march=native``
    code would not travel between machines anyway."""
    if not os.path.exists(_SO_PATH):
        return True
    built = os.path.getmtime(_SO_PATH)
    return any(os.path.getmtime(os.path.join(_NATIVE_DIR, f)) >= built
               for f in ("dtfio.cpp", "Makefile"))


def _build() -> bool:
    # built under a name of this process's own, then renamed into place:
    # several test workers may find the library stale at the same moment,
    # and none may ever load a half-written one
    tmp = f"libdtfio.{os.getpid()}.tmp.so"
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, f"TARGET={tmp}"],
                       check=True, capture_output=True, text=True)
        os.replace(os.path.join(_NATIVE_DIR, tmp), _SO_PATH)
        return True
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        out = getattr(e, "stderr", "")
        log.warning("native dtfio build failed: %s %s", e, out)
        return False


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _stale() and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            # A present-but-unloadable .so (stale copy, wrong arch) must take
            # the documented clean fallback, not crash the availability probe.
            log.warning("native dtfio load failed: %s", e)
            return None
        lib.dtfio_loader_create.restype = ctypes.c_void_p
        lib.dtfio_loader_create.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_uint64, ctypes.c_size_t, ctypes.c_size_t]
        lib.dtfio_item_size.restype = ctypes.c_size_t
        lib.dtfio_item_size.argtypes = [ctypes.c_void_p]
        lib.dtfio_num_items.restype = ctypes.c_size_t
        lib.dtfio_num_items.argtypes = [ctypes.c_void_p]
        lib.dtfio_loader_next.restype = None
        lib.dtfio_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32)]
        lib.dtfio_loader_destroy.restype = None
        lib.dtfio_loader_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


class NativeIdxData:
    """Prefetching IDX batch loader backed by libdtfio.

    Same contract as :class:`dtf_tpu.data.mnist.MnistData` (host-sharded,
    reshuffled epochs, f32 images in [0,1)), but assembly runs in native code
    one batch ahead of the consumer. The shuffle is splitmix64-based, so
    batch order differs from the numpy loader at equal seeds (both are
    deterministic in themselves).
    """

    def __init__(self, images_path: str, labels_path: str, batch_size: int,
                 *, seed: int = 0, host_index: int = 0, host_count: int = 1):
        lib = _load()
        if lib is None:
            raise RuntimeError("libdtfio.so unavailable (no compiler?)")
        if batch_size % host_count:
            raise ValueError(
                f"global batch {batch_size} not divisible by {host_count} hosts")
        self._lib = lib
        self.local_batch = batch_size // host_count
        self._h = lib.dtfio_loader_create(
            images_path.encode(), labels_path.encode(), self.local_batch,
            seed, host_index, host_count)
        if not self._h:
            raise ValueError(
                f"dtfio could not open {images_path}/{labels_path} "
                "(bad IDX, mismatched item counts, or batch > shard)")
        self.item_size = lib.dtfio_item_size(self._h)
        self.num_items = lib.dtfio_num_items(self._h)
        #: explicit offset cursor (the streaming-tier resume hook): the
        #: native shuffle is deterministic in (seed, host), so "batches
        #: consumed" fully addresses the stream position — :meth:`seek`
        #: replays to it after a restore.
        self.batches_consumed = 0

    def next_batch(self) -> dict:
        if not self._h:
            raise RuntimeError("NativeIdxData used after close()")
        images = np.empty((self.local_batch, self.item_size), np.float32)
        labels = np.empty((self.local_batch,), np.int32)
        self._lib.dtfio_loader_next(
            self._h,
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        self.batches_consumed += 1
        return {"image": images, "label": labels}

    def seek(self, n_batches: int) -> None:
        """Advance the cursor to ``n_batches`` consumed (resume-by-replay).

        The native library exposes no random access — its shuffle state
        lives inside the prefetch thread — but the stream IS deterministic,
        so a fresh loader replays ``n`` draws to land exactly where the
        checkpointed one stood. Cost is host-side assembly only (no device
        work); restore-time, not per-step. Rewinding needs a fresh loader.
        """
        if n_batches < self.batches_consumed:
            raise ValueError(
                f"cannot seek backwards ({self.batches_consumed} -> "
                f"{n_batches}); construct a fresh loader")
        while self.batches_consumed < n_batches:
            self.next_batch()

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()

    def close(self):
        if self._h:
            self._lib.dtfio_loader_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
