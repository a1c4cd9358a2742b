"""Native (C++) runtime components, built on demand.

The reference is 100% Python (SURVEY §2 — no native layer exists to port),
but a full framework wants its host-side hot paths native. This package
compiles `codec.cpp` with the system g++ the first time it's imported
(cached as a .so next to the source, keyed by a hash of the source text
and the build flags — a binary built from other source can never load)
and binds it
via ctypes — no pybind11 required. Every entry point has a pure-Python
fallback producing bit-identical results, so the framework degrades
gracefully on hosts without a toolchain.

API:
    crc32c(data: bytes|memoryview|ndarray, seed=0) -> int
    bf16_to_f32(ndarray[bfloat16|uint16]) -> ndarray[float32]
    f32_to_bf16(ndarray[float32]) -> ndarray[bfloat16]
    native_available() -> bool
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
from typing import Optional

import numpy as np

log = logging.getLogger("dnn_tpu.native")

_SRC = os.path.join(os.path.dirname(__file__), "codec.cpp")
_LOADER_SRC = os.path.join(os.path.dirname(__file__), "loader.cpp")
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_LOADER_LIB: Optional[ctypes.CDLL] = None
_LOADER_TRIED = False


def _build_src(src: str, stem: str, extra_flags=()) -> Optional[str]:
    """Compile (or locate the cached) .so for `src`; None means 'use the
    Python fallback'. ANY environment problem — missing source in a wheel
    install, read-only site-packages, missing g++ — must degrade, not
    raise."""
    tmp = None
    try:
        # key the cache on the source TEXT (and the flags it is built
        # with): an mtime says nothing in a fresh checkout or a copied
        # tree, where a stale binary of the same name could load
        src_dir = os.path.dirname(src)
        flags = ["-O3", "-shared", "-fPIC", "-std=c++17", *extra_flags]
        with open(src, "rb") as f:
            tag = hashlib.sha256(
                " ".join(flags).encode() + b"\0" + f.read()).hexdigest()[:16]
        so = os.path.join(src_dir, f"_{stem}_{tag}.so")
        if os.path.exists(so):
            return so
        # stale caches from earlier source versions
        for name in os.listdir(src_dir):
            if name.startswith(f"_{stem}_") and name.endswith(".so"):
                try:
                    os.unlink(os.path.join(src_dir, name))
                except OSError:
                    pass
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=src_dir)
        os.close(fd)
        cmd = ["g++", *flags, src, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return so
    except (subprocess.SubprocessError, OSError) as e:
        log.info("native %s build unavailable (%s); using Python fallback",
                 stem, e)
        try:
            if tmp and os.path.exists(tmp):
                os.unlink(tmp)
        except OSError:
            pass
        return None


def _build() -> Optional[str]:
    return _build_src(_SRC, "codec")


def _lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    so = _build()
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        log.info("native codec load failed (%s); using Python fallback", e)
        return None
    lib.dnn_crc32c.restype = ctypes.c_uint32
    lib.dnn_crc32c.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint32]
    lib.dnn_bf16_to_f32.restype = None
    lib.dnn_bf16_to_f32.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
    lib.dnn_f32_to_bf16.restype = None
    lib.dnn_f32_to_bf16.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
    _LIB = lib
    return _LIB


def native_available() -> bool:
    return _lib() is not None


def loader_lib() -> Optional[ctypes.CDLL]:
    """The async-loader library (loader.cpp), or None -> Python fallback.
    Built separately from the codec (needs -pthread)."""
    global _LOADER_LIB, _LOADER_TRIED
    if _LOADER_TRIED:
        return _LOADER_LIB
    _LOADER_TRIED = True
    so = _build_src(_LOADER_SRC, "loader", extra_flags=("-pthread",))
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        log.info("native loader load failed (%s); using Python fallback", e)
        return None
    lib.dnn_loader_create.restype = ctypes.c_void_p
    lib.dnn_loader_create.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_uint64, ctypes.c_int, ctypes.c_uint64,
    ]
    lib.dnn_loader_next.restype = ctypes.c_int
    lib.dnn_loader_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.dnn_loader_destroy.restype = None
    lib.dnn_loader_destroy.argtypes = [ctypes.c_void_p]
    _LOADER_LIB = lib
    return _LOADER_LIB


def loader_available() -> bool:
    return loader_lib() is not None


# ----------------------------------------------------------------------
# crc32c
# ----------------------------------------------------------------------

_PY_TABLE: Optional[list] = None


def _py_table():
    global _PY_TABLE
    if _PY_TABLE is None:
        poly = 0x82F63B78
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (poly ^ (c >> 1)) if (c & 1) else (c >> 1)
            table.append(c)
        _PY_TABLE = table
    return _PY_TABLE


def _as_buffer(data) -> memoryview:
    """-> a C-contiguous uint8 memoryview over `data` WITHOUT copying
    when the input is already contiguous (the comm hot path checksums
    MB-scale activation views — a bytes() materialization here would be
    a hidden full payload copy per direction, defeating the zero-copy
    wire codec). Only non-contiguous inputs materialize."""
    if isinstance(data, np.ndarray):
        a = data if data.flags.c_contiguous else np.ascontiguousarray(data)
        # uint8 reinterpret-view: also covers dtypes the buffer
        # protocol rejects (ml_dtypes bfloat16)
        return memoryview(a.reshape(-1).view(np.uint8))
    view = memoryview(data)
    if not view.c_contiguous:
        view = memoryview(bytes(view))
    return view.cast("B") if view.ndim else view.cast("B", (1,))


def crc32c_table(data, seed: int = 0) -> int:
    """CRC32C of `data` (bytes, or any iterable of byte values) by the
    table alone, in Python: what `crc32c` gives without the compiled
    codec and, bit for bit, with it. For a payload of a few bytes it is
    cheaper than the native call's pointer set-up, and it never releases
    the interpreter lock (wirecodec.make_token_tensor)."""
    table = _py_table()
    crc = (~seed) & 0xFFFFFFFF
    for b in data:
        crc = table[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return (~crc) & 0xFFFFFFFF


def crc32c(data, seed: int = 0) -> int:
    """CRC32C (Castagnoli) checksum. Native slice-by-8 when the compiled
    codec is available; table-driven Python otherwise (bit-identical)."""
    buf = _as_buffer(data)
    lib = _lib()
    if lib is not None:
        # pointer pass-through (ctypes won't convert a memoryview to
        # c_void_p itself; frombuffer is a zero-copy view)
        ptr = np.frombuffer(buf, np.uint8).ctypes.data if len(buf) else 0
        return int(lib.dnn_crc32c(ptr, len(buf), ctypes.c_uint32(seed)))
    return crc32c_table(buf, seed)


# ----------------------------------------------------------------------
# bf16 conversion
# ----------------------------------------------------------------------

def bf16_to_f32(arr: np.ndarray) -> np.ndarray:
    """bfloat16 (or its uint16 bit-pattern) -> float32, exact."""
    src = np.ascontiguousarray(arr)
    if src.dtype.name == "bfloat16":
        src = src.view(np.uint16)
    elif src.dtype != np.uint16:
        raise TypeError(f"expected bfloat16/uint16, got {arr.dtype}")
    out = np.empty(src.shape, np.float32)
    lib = _lib()
    if lib is not None and src.size:
        lib.dnn_bf16_to_f32(
            src.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            src.size,
        )
    else:
        out[...] = (src.astype(np.uint32) << 16).view(np.float32)
    return out


def f32_to_bf16(arr: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 with round-to-nearest-even (XLA semantics)."""
    import ml_dtypes

    src = np.ascontiguousarray(arr, dtype=np.float32)
    lib = _lib()
    if lib is None or not src.size:
        return src.astype(ml_dtypes.bfloat16)
    out = np.empty(src.shape, np.uint16)
    lib.dnn_f32_to_bf16(
        src.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        src.size,
    )
    return out.view(ml_dtypes.bfloat16)
