"""ctypes bridge to the native threaded dataloader (csrc/dataloader.cc).

Groups the model's `SingleDataLoader`s into ONE native loader so the sample
permutation stays consistent across input and label arrays (the reference
shares one `SampleIdxs` argmap across its loaders —
flexflow_dataloader.h:88-141). Worker threads gather shuffled batch slices
into a ring of prefetch slots, overlapping host-side batch assembly with
device compute.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.runtime import locks

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "csrc",
                    "dataloader.cc")
_lib = None
_lib_lock = locks.make_lock("native-loader")


def load_lib():
    """Build (keyed by the hash of dataloader.cc) and load the native
    loader; returns None, with the reason logged, when it cannot be built.
    Failures are cached (sentinel False) so fit() doesn't re-spawn g++ every
    call."""
    global _lib
    with _lib_lock:
        if _lib is False:
            return None
        if _lib is not None:
            return _lib
        from flexflow_tpu._native import build_native_lib
        from flexflow_tpu.logger import fflogger
        from flexflow_tpu.runtime.resilience import retry

        # a concurrent process can race the build (dlopen of a just-
        # replaced .so, transient fs errors) — retry once before giving
        # up; "no g++ at all" (FileNotFoundError) is permanent, not
        # retryable, and must fall through to the Python loader fast
        @retry(attempts=2, base_delay=0.1,
               retryable=lambda e: isinstance(
                   e, (OSError, subprocess.CalledProcessError))
               and not isinstance(e, FileNotFoundError),
               name="native dataloader build")
        def _build_and_open():
            return ctypes.CDLL(
                build_native_lib(_SRC, "libffdl", ("-pthread",)))

        try:
            lib = _build_and_open()
        except (OSError, subprocess.CalledProcessError) as e:
            fflogger.warning(
                "native dataloader unavailable (%s: %s) — batches come "
                "from the Python loader", type(e).__name__, e)
            _lib = False
            return None
        fflogger.info("native dataloader: %s", lib._name)
        lib.ffdl_create.restype = ctypes.c_void_p
        lib.ffdl_create.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int, ctypes.c_int]
        lib.ffdl_num_batches.restype = ctypes.c_int64
        lib.ffdl_num_batches.argtypes = [ctypes.c_void_p]
        lib.ffdl_next.restype = ctypes.c_int
        lib.ffdl_next.argtypes = [ctypes.c_void_p]
        lib.ffdl_buffer.restype = ctypes.c_void_p
        lib.ffdl_buffer.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        lib.ffdl_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ffdl_reset.argtypes = [ctypes.c_void_p]
        lib.ffdl_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


class NativeBatchLoader:
    """One prefetching loader over N parallel (name, array) streams."""

    def __init__(self, arrays: Sequence[Tuple[str, np.ndarray]],
                 batch_size: int, shuffle: bool = False, seed: int = 0,
                 prefetch_slots: int = 3, num_threads: int = 2):
        lib = load_lib()
        if lib is None:
            raise RuntimeError("native dataloader unavailable (no g++?)")
        self._lib = lib
        self.names = [n for n, _ in arrays]
        # keep C-contiguous copies alive for the lifetime of the loader — the
        # C++ side reads them directly
        self.arrays = [np.ascontiguousarray(a) for _, a in arrays]
        ns = {a.shape[0] for a in self.arrays}
        if len(ns) != 1:
            raise ValueError(f"arrays disagree on num_samples: {ns}")
        self.num_samples = ns.pop()
        self.batch_size = batch_size
        self.sample_shapes = [a.shape[1:] for a in self.arrays]
        self.dtypes = [a.dtype for a in self.arrays]

        n = len(self.arrays)
        ptrs = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in self.arrays])
        sbytes = (ctypes.c_int64 * n)(
            *[int(np.prod(s, dtype=np.int64)) * d.itemsize
              for s, d in zip(self.sample_shapes, self.dtypes)])
        self._h = lib.ffdl_create(
            n, ptrs, sbytes, self.num_samples, batch_size,
            1 if shuffle else 0, seed, prefetch_slots, num_threads)
        if not self._h:
            raise RuntimeError("ffdl_create failed (batch_size > num_samples?)")
        self.num_batches = int(lib.ffdl_num_batches(self._h))
        self._served = 0

    def reset(self):
        self._lib.ffdl_reset(self._h)
        self._served = 0

    def next_batch(self) -> Optional[Dict[str, np.ndarray]]:
        """Next prefetched batch as {name: array}; None at end of epoch.
        Arrays are copies — safe to hand to jax.device_put on any backend
        (the CPU backend may alias numpy buffers)."""
        if self._h is None:
            raise RuntimeError("loader destroyed")
        slot = self._lib.ffdl_next(self._h)
        if slot < 0:
            return None
        out = {}
        for i, name in enumerate(self.names):
            ptr = self._lib.ffdl_buffer(self._h, slot, i)
            nbytes = (self.batch_size
                      * int(np.prod(self.sample_shapes[i], dtype=np.int64))
                      * self.dtypes[i].itemsize)
            buf = (ctypes.c_char * nbytes).from_address(ptr)
            arr = np.frombuffer(buf, dtype=self.dtypes[i]).reshape(
                (self.batch_size,) + tuple(self.sample_shapes[i])).copy()
            out[name] = arr
        self._lib.ffdl_release(self._h, slot)
        self._served += 1
        return out

    def close(self):
        if self._h is not None:
            self._lib.ffdl_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def group_loader_for(model) -> Optional[NativeBatchLoader]:
    """Build one NativeBatchLoader over the model's attached dataloaders, or
    None when unavailable / heterogeneous."""
    cfg = model.config
    if not getattr(cfg, "native_dataloader", False) or not model._dataloaders:
        return None
    sizes = {dl.batch_size for dl in model._dataloaders}
    ns = {dl.num_samples for dl in model._dataloaders}
    if len(sizes) != 1 or len(ns) != 1:
        return None
    try:
        return NativeBatchLoader(
            [(dl.name, dl.data[:dl.num_samples]) for dl in model._dataloaders],
            batch_size=sizes.pop(),
            shuffle=getattr(cfg, "dataloader_shuffle", False),
            seed=getattr(cfg, "seed", 0),
            prefetch_slots=getattr(cfg, "dataloader_prefetch_slots", 3),
            num_threads=getattr(cfg, "dataloader_threads", 2))
    except (RuntimeError, ValueError):
        return None
