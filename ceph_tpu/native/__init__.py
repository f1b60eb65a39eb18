"""Native C++ host runtime, loaded via ctypes.

The reference keeps its data-plane utilities native (crc32c:
src/common/crc32c.cc + sctp_crc32.c; region XOR:
src/erasure-code/isa/xor_op.cc).  We do the same: a small C++ library
compiled on first use with g++ (no pip deps), with pure-Python
fallbacks so the package works before/without a toolchain.

Public API:
  crc32c(data, seed=-1)          -- reference ceph_crc32c semantics
  crc32c_chunks(data, chunk)     -- every chunk's crc, packed, in one call
  crc_backend()                  -- which code computes it on this host
  crc32c_zeros(length, seed=-1)  -- crc of `length` zero bytes
  xor_region(dst, src)           -- dst ^= src in place (uint8 arrays)
  available()                    -- True when the .so is loaded
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger("ceph_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO_STEM = "_libceph_tpu_native"
_SRCS = ["crc32c.cc", "crush_hash.cc"]

_lib = None
_lock = threading.Lock()
_build_failed = False


def _load():
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        srcs = [os.path.join(_HERE, s) for s in _SRCS]
        try:
            # the binary's name carries its sources' content hash: a
            # copied or checked-out tree can never load a stale .so
            # (mtimes do not survive a copy; the sources' bytes do)
            digest = hashlib.sha256()
            for src in srcs:
                with open(src, "rb") as f:
                    digest.update(f.read())
            so = os.path.join(
                _HERE, f"{_SO_STEM}.{digest.hexdigest()[:16]}.so")
            if not os.path.exists(so):
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp]
                    + srcs,
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, so)
                for old in glob.glob(os.path.join(_HERE, f"{_SO_STEM}*.so")):
                    if old != so:
                        os.unlink(old)
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.CalledProcessError) as exc:
            log.warning(
                "native runtime unavailable (%s): crc32c/xor/straw2 run "
                "on the pure-Python fallbacks", exc)
            _build_failed = True
            return None
        # the same function twice: through ``lib`` ctypes lets go of
        # the GIL around the call, through the PyDLL handle it keeps it
        # (see _GIL_KEPT_BELOW)
        lib.crc32c_gil_kept = ctypes.PyDLL(so).ceph_tpu_crc32c
        for fn in (lib.ceph_tpu_crc32c, lib.crc32c_gil_kept):
            fn.restype = ctypes.c_uint32
            fn.argtypes = [
                ctypes.c_uint32, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_int,
            ]
        lib.crc32c_chunks_gil_kept = ctypes.PyDLL(so).ceph_tpu_crc32c_chunks
        for fn in (lib.ceph_tpu_crc32c_chunks, lib.crc32c_chunks_gil_kept):
            fn.restype = None
            fn.argtypes = [
                ctypes.c_uint32, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_size_t, ctypes.c_void_p, ctypes.c_int,
            ]
        lib.ceph_tpu_crc_backend.restype = ctypes.c_char_p
        lib.ceph_tpu_crc_backend.argtypes = []
        lib.ceph_tpu_xor_region.restype = None
        lib.ceph_tpu_xor_region.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
        ]
        u32 = ctypes.c_uint32
        for name, nargs in [("ceph_tpu_hash32", 1), ("ceph_tpu_hash32_2", 2),
                            ("ceph_tpu_hash32_3", 3), ("ceph_tpu_hash32_4", 4),
                            ("ceph_tpu_hash32_5", 5)]:
            fn = getattr(lib, name)
            fn.restype = u32
            fn.argtypes = [u32] * nargs
        lib.ceph_tpu_straw2_choose.restype = ctypes.c_int32
        lib.ceph_tpu_straw2_choose.argtypes = [
            u32, u32, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
        ]
        lib.ceph_tpu_set_ln_tables.restype = None
        lib.ceph_tpu_set_ln_tables.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        # inject the crush_ln LUTs (single table of truth lives in the
        # generated Python module)
        from ceph_tpu.crush._ln_tables import LL_TBL, RH_LH_TBL

        rh = np.ascontiguousarray(RH_LH_TBL, dtype=np.int64)
        ll = np.ascontiguousarray(LL_TBL, dtype=np.int64)
        assert rh.size == 258 and ll.size == 256
        lib.ceph_tpu_set_ln_tables(rh.ctypes.data, ll.ctypes.data)
        _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


# -- pure-python fallback ---------------------------------------------------

_PY_TABLE: np.ndarray | None = None


def _py_table() -> np.ndarray:
    global _PY_TABLE
    if _PY_TABLE is None:
        t = np.zeros(256, dtype=np.uint32)
        for i in range(256):
            c = i
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if (c & 1) else (c >> 1)
            t[i] = c
        _PY_TABLE = t
    return _PY_TABLE


def _py_crc32c(data: bytes, seed: int) -> int:
    t = _py_table()
    crc = seed & 0xFFFFFFFF
    for b in data:
        crc = int(t[(crc ^ b) & 0xFF]) ^ (crc >> 8)
    return crc


# -- public API -------------------------------------------------------------

# A crc of fewer bytes than this is over in a few microseconds, and is
# made without letting go of the GIL.  The event loop checks some 300
# frame preambles and small segments per 4 MiB EC write; each release
# hands the GIL to a store or launch thread, and the loop then waits
# for it (up to the interpreter's switch interval) before it goes on.
_GIL_KEPT_BELOW = 64 * 1024


def _crc_fn(lib, n: int):
    return lib.crc32c_gil_kept if n < _GIL_KEPT_BELOW else lib.ceph_tpu_crc32c


def crc32c(data, seed: int = 0xFFFFFFFF, *, table: bool = False) -> int:
    """Reference ceph_crc32c(seed, data, len): reflected CRC32C update,
    no init/final inversion (sctp_crc32.c:update_crc32).  ``table``
    forces the slice-by-8 path, the oracle of the hardware one."""
    lib = _load()
    if lib is None:
        return _py_crc32c(
            data if isinstance(data, (bytes, bytearray, memoryview))
            else np.asarray(data, dtype=np.uint8).tobytes(), seed)
    seed &= 0xFFFFFFFF
    # the buffer's address, with no numpy array on the way: bytes go to
    # ctypes as they are, a writable buffer (bytearray, a received
    # segment's memoryview) lends its memory for the call
    if isinstance(data, bytes):
        n = len(data)
        return _crc_fn(lib, n)(seed, data, n, table)
    if isinstance(data, bytearray) or (
            isinstance(data, memoryview) and not data.readonly
            and data.c_contiguous):
        n = data.nbytes if isinstance(data, memoryview) else len(data)
        if n == 0:
            return seed
        # held for the call: it pins the buffer against a resize
        first = ctypes.c_char.from_buffer(data)
        return _crc_fn(lib, n)(seed, ctypes.byref(first), n, table)
    arr = np.ascontiguousarray(
        np.frombuffer(data, dtype=np.uint8)
        if isinstance(data, memoryview)
        else np.asarray(data, dtype=np.uint8).reshape(-1)
    )
    return _crc_fn(lib, arr.nbytes)(seed, arr.ctypes.data, arr.nbytes, table)


def crc32c_chunks(data, chunk: int, seed: int = 0xFFFFFFFF, *,
                  table: bool = False) -> bytes:
    """The crc32c of every ``chunk`` bytes of ``data`` (``bytes`` or a
    contiguous buffer; the last chunk may be short), each from ``seed``,
    packed little-endian, 4 bytes a chunk: one call and one pass
    however many chunks, with the GIL rule of :func:`crc32c`."""
    view = memoryview(data).cast("B")
    n = view.nbytes
    count = -(-n // chunk)
    lib = _load()
    if lib is None:
        return b"".join(
            _py_crc32c(view[at:at + chunk], seed).to_bytes(4, "little")
            for at in range(0, n, chunk))
    if count == 0:
        return b""
    out = (ctypes.c_uint32 * count)()
    fn = (lib.crc32c_chunks_gil_kept if n < _GIL_KEPT_BELOW
          else lib.ceph_tpu_crc32c_chunks)
    if isinstance(data, bytes):
        fn(seed & 0xFFFFFFFF, data, n, chunk, out, table)
    else:
        arr = np.frombuffer(view, dtype=np.uint8)
        fn(seed & 0xFFFFFFFF, arr.ctypes.data, n, chunk, out, table)
    return bytes(out)


def crc_backend() -> str:
    """"sse4.2" / "armv8" (the CPU's CRC32C instruction), "table"
    (slice-by-8 in the native library) or "python" (no library)."""
    lib = _load()
    return "python" if lib is None else lib.ceph_tpu_crc_backend().decode()


def crc32c_zeros(length: int, seed: int = 0xFFFFFFFF) -> int:
    """crc32c of `length` zero bytes (reference crc32c.cc:216)."""
    lib = _load()
    if lib is not None:
        return lib.ceph_tpu_crc32c(seed & 0xFFFFFFFF, None, length, 0)
    t = _py_table()
    crc = seed & 0xFFFFFFFF
    for _ in range(length):
        if crc == 0:
            break
        crc = int(t[crc & 0xFF]) ^ (crc >> 8)
    return crc


def straw2_lib():
    """The raw ctypes lib if the native straw2 choose is usable (LUTs
    injected), else None.  mapper.py binds the per-bucket call itself
    to keep the hot path free of Python-level indirection."""
    lib = _load()
    if lib is not None and lib.ceph_tpu_ln_tables_ready():
        return lib
    return None


def xor_region(dst: np.ndarray, src: np.ndarray) -> None:
    """dst ^= src in place (both uint8, same length).  ``dst`` must be
    C-contiguous — a strided view would silently XOR into a copy."""
    assert dst.dtype == np.uint8 and src.dtype == np.uint8
    assert dst.flags.c_contiguous, "xor_region dst must be contiguous"
    assert dst.nbytes == src.nbytes
    lib = _load()
    if lib is not None:
        src = np.ascontiguousarray(src)
        lib.ceph_tpu_xor_region(dst.ctypes.data, src.ctypes.data, dst.nbytes)
    else:
        np.bitwise_xor(dst, src, out=dst)
