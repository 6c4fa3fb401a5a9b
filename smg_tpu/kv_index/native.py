"""ctypes binding for the native C++ radix index (csrc/radix_index.cpp).

Reference analogue: the Rust ``crates/kv_index`` backing the gateway's
routing hot path.  ``libsmg_native.so`` is a build product of
``csrc/radix_index.cpp`` and of nothing else: it is (re)built with ``make``
on first use whenever it is missing or older than its sources, and a library
that cannot be brought up to date is not loaded.  Without a toolchain the
pure-Python ``RadixTree`` serves instead; it has the same interface, so the
cache_aware policy can swap implementations (``SMG_NATIVE_RADIX=0`` forces
Python).

Measured on a CPU host (a harness from before the chip, since removed): at small trees the FFI boundary makes
the implementations comparable; at 30k sequences x 64-512 tokens the native
tree leads (insert 0.69s vs 0.86s, match 35.5k vs 33.9k ops/s) and its
memory stays flat where Python dict nodes bloat — the gap widens with tree
size, which is exactly the long-running-gateway regime.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

from smg_tpu.utils import get_logger

logger = get_logger("kv_index.native")

_CSRC = os.path.join(os.path.dirname(__file__), "..", "..", "csrc")
_LIB_PATH = os.path.abspath(os.path.join(_CSRC, "libsmg_native.so"))
_SOURCES = ("radix_index.cpp", "Makefile")
_lib = None  # the loaded CDLL; False once loading has failed in this process
_lib_lock = threading.Lock()


def _out_of_date() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    built = os.path.getmtime(_LIB_PATH)
    return any(
        os.path.getmtime(os.path.join(_CSRC, src)) > built for src in _SOURCES
    )


def _load_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib or None
        if os.environ.get("SMG_NATIVE_RADIX") == "0":
            return None
        _lib = False
        if _out_of_date():
            try:
                subprocess.run(
                    ["make", "-B", "-C", os.path.abspath(_CSRC)],
                    check=True, capture_output=True, timeout=120,
                )
            except (OSError, subprocess.SubprocessError) as e:
                logger.warning("native radix build failed (%s); using Python tree", e)
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            logger.warning("native radix load failed (%s); using Python tree", e)
            return None
        lib.rt_new.restype = ctypes.c_void_p
        lib.rt_new.argtypes = [ctypes.c_size_t]
        lib.rt_free.argtypes = [ctypes.c_void_p]
        lib.rt_insert.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.c_uint32,
        ]
        lib.rt_match.restype = ctypes.c_size_t
        lib.rt_match.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_size_t,
        ]
        lib.rt_remove_worker.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        lib.rt_size.restype = ctypes.c_size_t
        lib.rt_size.argtypes = [ctypes.c_void_p]
        _lib = lib
        logger.info("native radix index loaded (%s)", _LIB_PATH)
        return _lib


def native_available() -> bool:
    return _load_lib() is not None


class NativeRadixTree:
    """Same interface as ``smg_tpu.kv_index.RadixTree`` — str/token sequences
    in, per-worker matched lengths out — backed by the C++ tree."""

    MAX_WORKERS = 1024

    def __init__(self, max_size: int = 2**20):
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native radix library unavailable")
        self._lib = lib
        self._tree = lib.rt_new(max_size)
        self._worker_ids: dict[str, int] = {}
        self._worker_names: dict[int, str] = {}
        self._lock = threading.Lock()
        # reused output buffers (per-call ctypes allocation measured hot)
        self._out_w = (ctypes.c_uint32 * self.MAX_WORKERS)()
        self._out_l = (ctypes.c_uint32 * self.MAX_WORKERS)()

    def __del__(self):
        tree = getattr(self, "_tree", None)
        if tree:
            self._lib.rt_free(tree)
            self._tree = None

    def _wid(self, worker: str) -> int:
        with self._lock:
            wid = self._worker_ids.get(worker)
            if wid is None:
                wid = len(self._worker_ids) + 1
                self._worker_ids[worker] = wid
                self._worker_names[wid] = worker
            return wid

    @staticmethod
    def _encode(seq):
        """Marshal a str/int sequence to a C uint32 pointer.  numpy-backed:
        per-element ctypes construction dominated the call cost (measured 5x
        slower than the pure-Python tree before this)."""
        import numpy as np

        if isinstance(seq, str):
            arr = np.frombuffer(seq.encode("utf-32-le"), dtype=np.uint32)
        elif isinstance(seq, np.ndarray):
            arr = np.ascontiguousarray(seq, dtype=np.uint32)
        else:
            arr = np.fromiter(seq, dtype=np.uint32, count=len(seq))
        return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(arr), arr

    def insert(self, seq, worker_id: str) -> None:
        ptr, n, _keepalive = self._encode(seq)
        self._lib.rt_insert(self._tree, ptr, n, self._wid(worker_id))

    def prefix_match(self, seq) -> dict[str, int]:
        ptr, n, _keepalive = self._encode(seq)
        with self._lock:
            count = self._lib.rt_match(
                self._tree, ptr, n, self._out_w, self._out_l, self.MAX_WORKERS
            )
            result = {}
            for i in range(count):
                name = self._worker_names.get(self._out_w[i])
                if name is not None:
                    result[name] = self._out_l[i]
        return result

    def remove_worker(self, worker_id: str) -> None:
        with self._lock:
            wid = self._worker_ids.get(worker_id)
        if wid is not None:
            self._lib.rt_remove_worker(self._tree, wid)

    @property
    def size(self) -> int:
        return self._lib.rt_size(self._tree)

    def stats(self) -> dict:
        """Python-tree-compatible stats; the C++ tree exposes element count
        only (node/eviction counters stay None — collectors skip them)."""
        return {
            "elements": self.size,
            "nodes": None,
            "evicted_elements": None,
            "max_size": None,
        }


_announced = False


def make_radix_tree(max_size: int = 2**20):
    """Factory for the cache_aware policy: native tree when available,
    Python tree otherwise; says which, once per process."""
    global _announced
    native = native_available()
    if not _announced:
        _announced = True
        logger.info("cache_aware prefix index: %s tree",
                    "native" if native else "python")
    if native:
        return NativeRadixTree(max_size)
    from smg_tpu.kv_index.radix_tree import RadixTree

    return RadixTree(max_size)
