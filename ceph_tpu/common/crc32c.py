"""crc32c (Castagnoli) with the reference's raw-seed chaining semantics.

`crc32c(seed, data)` behaves like the reference's `ceph_crc32c(seed,
buf, len)` (behavioral ref: src/common/crc32c.h, table impl
src/common/sctp_crc32.c): the seed is the running crc — no implicit
pre/post inversion — so cumulative shard hashes (ECUtil HashInfo) chain
calls directly.  Validated against the reference's published vectors
(src/test/common/test_crc32c.cc:18-45).

Fast path: the native library (native/crc32c.c), compiled on demand
with the system compiler and cached next to the package.  It picks the
CPU's CRC32C instruction at load (`sse42`, `armv8`) or its slice-by-8
table; `implementation()` names the choice.  Fallback: a numpy table
walk (correct, slower) so the package works without a toolchain.

The library is loaded with ctypes.PyDLL, so a call keeps the
interpreter lock: at the instruction's speed a 1 MiB hash takes ~45 us,
less than releasing and winning back the lock costs among a cluster's
busy threads (measured in PERF.md).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import NamedTuple

from .lockdep import make_lock

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "crc32c.c")
_LIB_DIR = os.path.join(_REPO_ROOT, "ceph_tpu", "_native")
_LIB = os.path.join(_LIB_DIR, "libceph_tpu_native.so")

_lock = make_lock("crc32c.native")
_native = None
_native_tried = False


class _Native(NamedTuple):
    one: object                # (seed, ptr, len) -> crc
    table: object              # the same on slice-by-8, whatever the CPU
    impl: str                  # "sse42", "armv8" or "table"


def _build_native() -> str | None:
    if not os.path.exists(_SRC):
        return None
    os.makedirs(_LIB_DIR, exist_ok=True)
    if (os.path.exists(_LIB)
            and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC)):
        return _LIB
    # compile to a temp name + atomic rename so a concurrent process
    # never dlopens a half-written .so
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    for cc in ("cc", "gcc", "clang"):
        try:
            subprocess.run(
                [cc, "-O3", "-shared", "-fPIC", _SRC, "-o", tmp],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, _LIB)
            return _LIB
        except (OSError, subprocess.SubprocessError):
            continue
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
    return None


def _load_native():
    global _native, _native_tried
    if _native_tried:
        return _native
    with _lock:
        if _native_tried:
            return _native
        try:
            path = _build_native()
        except OSError:
            path = None
        if path is not None:
            # PyDLL: the interpreter lock stays held across each call.
            # A library without these symbols raises here, never falls
            # back to the Python walk unnoticed.
            lib = ctypes.PyDLL(path)
            one = lib.ceph_tpu_crc32c
            table = lib.ceph_tpu_crc32c_table
            for fn in (one, table):
                fn.restype = ctypes.c_uint32
                fn.argtypes = [ctypes.c_uint32, ctypes.c_void_p,
                               ctypes.c_size_t]
            lib.ceph_tpu_crc32c_impl.argtypes = []
            lib.ceph_tpu_crc32c_impl.restype = ctypes.c_char_p
            _native = _Native(one, table,
                              lib.ceph_tpu_crc32c_impl().decode())
        _native_tried = True
    from .log import dout
    dout("crc32c", 1).write(
        "crc32c: implementation %s",
        _native.impl if _native is not None else "python")
    return _native


def implementation() -> str:
    """The crc32c in use: `sse42`, `armv8` or `table` (native), or
    `python` where the native library could not be built."""
    lib = _load_native()
    return lib.impl if lib is not None else "python"


def _make_table() -> np.ndarray:
    poly = np.uint64(0x82F63B78)
    tbl = np.zeros(256, dtype=np.uint64)
    for i in range(256):
        c = np.uint64(i)
        for _ in range(8):
            c = (c >> np.uint64(1)) ^ poly if c & np.uint64(1) \
                else c >> np.uint64(1)
        tbl[i] = c
    return tbl.astype(np.uint32)


_TABLE = _make_table().tolist()


def _crc32c_py(seed: int, data: bytes) -> int:
    crc = seed & 0xFFFFFFFF
    tbl = _TABLE
    for b in data:
        crc = tbl[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc


def _buffer(data) -> tuple:
    """(owner, pointer argument, length) of a bytes-like or ndarray,
    without a copy where it is contiguous; keep `owner` alive across
    the native call."""
    if isinstance(data, bytes):
        return data, data, len(data)
    if isinstance(data, np.ndarray):
        arr = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
    elif isinstance(data, memoryview) and not data.c_contiguous:
        arr = np.frombuffer(data.tobytes(), dtype=np.uint8)
    else:
        arr = np.frombuffer(data, dtype=np.uint8)
    return arr, arr.__array_interface__["data"][0], arr.nbytes


def _as_bytes(data) -> bytes:
    if isinstance(data, np.ndarray):
        return data.tobytes()
    return bytes(data)


def crc32c(seed: int, data) -> int:
    """Running crc32c over data; chain by passing the previous result
    as the next seed.  data: bytes-like or ndarray (its raw bytes)."""
    lib = _load_native()
    if lib is None:
        return _crc32c_py(seed, _as_bytes(data))
    owner, ptr, n = _buffer(data)
    return lib.one(seed & 0xFFFFFFFF, ptr, n)


def crc32c_table(seed: int, data) -> int:
    """The slice-by-8 table implementation whatever the CPU offers (the
    hardware paths are checked against it)."""
    lib = _load_native()
    if lib is None:
        return _crc32c_py(seed, _as_bytes(data))
    owner, ptr, n = _buffer(data)
    return lib.table(seed & 0xFFFFFFFF, ptr, n)
