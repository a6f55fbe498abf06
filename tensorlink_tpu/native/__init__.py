"""Native (C++) runtime components, bound via ctypes.

The compute path is JAX/XLA/Pallas; the runtime around it gets native
code where it earns its keep. First component: the wire codec
(wirecodec.cpp) — CRC-32C frame integrity and single-pass gather+checksum
for tensor blobs on the DCN hop. The reference's transport was pure
Python with no integrity checking (src/p2p/connection.py:39-151).

The shared library is built on demand with g++ (baked into the image) and
cached next to the source; every entry point has a pure-Python fallback
so the package works without a toolchain — callers use `crc32c()` /
`gather()` and never see which implementation ran. `have_native()`
reports which one is live.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libwirecodec.so")
_SRC = os.path.join(_DIR, "wirecodec.cpp")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _load() -> "ctypes.CDLL | None":
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            if not os.path.exists(_SO) or os.path.getmtime(_SO) < os.path.getmtime(_SRC):
                # compile to a per-process temp path and os.replace into
                # place: concurrent worker processes racing a shared
                # output path could CDLL a half-written .so and latch the
                # Python fallback forever (review finding)
                tmp = f"{_SO}.{os.getpid()}.tmp"
                base = ["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
                        "-o", tmp, _SRC]
                try:
                    try:  # hardware CRC32C when the target supports it
                        subprocess.run(
                            base[:1] + ["-msse4.2"] + base[1:],
                            check=True, capture_output=True, timeout=120,
                        )
                    except subprocess.SubprocessError:
                        subprocess.run(
                            base, check=True, capture_output=True, timeout=120
                        )
                    os.replace(tmp, _SO)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
            lib = ctypes.CDLL(_SO)
            lib.tl_crc32c.restype = ctypes.c_uint32
            lib.tl_crc32c.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32,
            ]
            lib.tl_gather.restype = ctypes.c_uint32
            lib.tl_gather.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.c_size_t,
                ctypes.c_int,
            ]
            lib.tl_abi_version.restype = ctypes.c_int
            if lib.tl_abi_version() != 1:
                raise OSError("wirecodec ABI mismatch")
            _lib = lib
        except (OSError, subprocess.SubprocessError, FileNotFoundError):
            _build_failed = True
    return _lib


def have_native() -> bool:
    return _load() is not None


# ------------------------------------------------------ python fallback

_PY_TABLE: "np.ndarray | None" = None


def _py_table() -> np.ndarray:
    global _PY_TABLE
    if _PY_TABLE is None:
        poly = 0x82F63B78
        t = np.zeros(256, np.uint32)
        for i in range(256):
            c = i
            for _ in range(8):
                c = (poly ^ (c >> 1)) if (c & 1) else (c >> 1)
            t[i] = c
        _PY_TABLE = t
    return _PY_TABLE


def _py_crc32c(data: bytes, crc0: int = 0) -> int:
    table = _py_table()
    crc = ~crc0 & 0xFFFFFFFF
    for b in data:
        crc = int(table[(crc ^ b) & 0xFF]) ^ (crc >> 8)
    return ~crc & 0xFFFFFFFF


# ------------------------------------------------------------- public API


def crc32c(data: bytes | bytearray | memoryview, crc0: int = 0) -> int:
    """CRC-32C (Castagnoli) — chainable via ``crc0``."""
    buf = data if isinstance(data, bytes) else bytes(data)
    lib = _load()
    if lib is not None:
        return int(lib.tl_crc32c(buf, len(buf), crc0))
    return _py_crc32c(buf, crc0)


def gather(buffers: list[np.ndarray], with_crc: bool = True) -> tuple[bytearray, int]:
    """Concatenate contiguous byte views of ``buffers`` into one blob,
    computing the CRC-32C in the same memory pass. Returns (blob, crc)."""
    views = [np.ascontiguousarray(b).view(np.uint8).reshape(-1) for b in buffers]
    total = sum(v.nbytes for v in views)
    out = bytearray(total)
    lib = _load()
    if lib is not None and views:
        # zero extra copies: source pointers come straight from the numpy
        # buffers (kept alive by `views` for the duration of the call)
        n = len(views)
        srcs = (ctypes.c_void_p * n)(*[v.ctypes.data for v in views])
        lens = (ctypes.c_size_t * n)(*[v.nbytes for v in views])
        dst = (ctypes.c_char * total).from_buffer(out)
        crc = int(lib.tl_gather(
            ctypes.addressof(dst), srcs, lens, n, 1 if with_crc else 0
        ))
        return out, crc
    off = 0
    crc = 0
    for v in views:
        raw = v.tobytes()
        out[off : off + len(raw)] = raw
        off += len(raw)
    if with_crc:
        crc = crc32c(bytes(out))
    return out, crc
