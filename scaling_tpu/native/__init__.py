"""Native (C++) hot paths with transparent Python fallbacks.

The reference framework's native surface is all imported (NCCL, flash-attn,
torch internals — reference SURVEY §2.3); here the compute hot path is
XLA/Pallas and the *runtime* hot paths (data indexing) are first-party C++,
compiled on demand with the system toolchain and loaded via ctypes. Without
a compiler the callers use their Python implementations, and say so once.
"""

from __future__ import annotations

import ctypes
import subprocess
from pathlib import Path
from typing import Optional

import numpy as np

from ..logging import logger

_SRC_DIR = Path(__file__).parent
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    """Build ``pack_index.cpp`` beside its source when the library is
    missing or older, and load it. Only "no compiler" and "the build
    failed" mean the Python implementation serves instead, and the reason
    is logged; anything else (a library that does not load, a missing
    symbol) is a defect and raises."""
    src = _SRC_DIR / "pack_index.cpp"
    lib_path = _SRC_DIR / "libpack_index.so"
    if not lib_path.exists() or lib_path.stat().st_mtime < src.stat().st_mtime:
        try:
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", str(lib_path), str(src)],
                check=True, capture_output=True, timeout=120,
            )
        except FileNotFoundError:
            logger.warning("native pack index: no g++ on this machine; "
                           "the Python implementation serves")
            return None
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
            detail = getattr(e, "stderr", b"") or b""
            logger.warning(
                f"native pack index: building {src.name} failed ({e}); the "
                f"Python implementation serves. {detail.decode()[-500:]}"
            )
            return None
    lib = ctypes.CDLL(str(lib_path))
    lib.build_pack_index.restype = ctypes.c_int64
    lib.build_pack_index.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    return lib


def _lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if not _TRIED:
        _LIB = _build_and_load()
        _TRIED = True
    return _LIB


def native_available() -> bool:
    return _lib() is not None


def build_pack_index(
    doc_sizes: np.ndarray, sequence_length: int, allow_incomplete_every_n: int
) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """(starts, ends) spans for only_full_sequences packing, or None if the
    native library is unavailable (caller falls back to Python)."""
    lib = _lib()
    if lib is None:
        return None
    sizes = np.ascontiguousarray(doc_sizes, dtype=np.int64)
    total = int(sizes.sum())
    L = int(sequence_length)
    # upper bound: every doc boundary plus every mid-doc cut
    max_spans = len(sizes) + total // max(L, 1) + 2
    starts = np.empty(max_spans, dtype=np.int64)
    ends = np.empty(max_spans, dtype=np.int64)
    n = lib.build_pack_index(
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(sizes), L, int(allow_incomplete_every_n),
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        max_spans,
    )
    return starts[:n].copy(), ends[:n].copy()
