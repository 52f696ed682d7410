"""Compile + load the native library (g++ → shared object → ctypes).

The build is lazy and cached: sources are hashed, the .so lands in
``$OPENDHT_TPU_CACHE`` (default ``~/.cache/opendht_tpu``), and a rebuild
only happens when the sources change.  No toolchain / failed build ⇒
``get_lib()`` returns None and callers use their Python fallbacks.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

log = logging.getLogger("opendht_tpu.native")

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("xor_engine.cpp", "udp_engine.cpp")

_lock = threading.Lock()
_lib: "ctypes.CDLL | None" = None
_tried = False


def _cache_dir() -> str:
    d = os.environ.get("OPENDHT_TPU_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "opendht_tpu")
    os.makedirs(d, exist_ok=True)
    return d


def _src_hash() -> str:
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_SRC_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _build() -> Optional[str]:
    out = os.path.join(_cache_dir(), "libdht_native_%s.so" % _src_hash())
    if os.path.exists(out):
        return out
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    # a temporary of this process's own: several processes may build at
    # once (the workers of a test run on a fresh cache), and each has to
    # rename a whole file of its own into place
    tmp = "%s.%d.tmp" % (out, os.getpid())
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-o", tmp] + srcs
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return out
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", b"")
        log.warning("native build failed: %s %s", e,
                    detail.decode(errors="replace") if detail else "")
        try:
            os.remove(tmp)      # what a failed or timed-out g++ left
        except OSError:
            pass
        return None


def _declare(lib: ctypes.CDLL) -> None:
    u8p, i32p = ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.dht_xor_cmp.restype = ctypes.c_int
    lib.dht_xor_cmp.argtypes = [u8p, u8p, u8p]
    lib.dht_common_bits.restype = ctypes.c_int
    lib.dht_common_bits.argtypes = [u8p, u8p]
    lib.dht_cmp.restype = ctypes.c_int
    lib.dht_cmp.argtypes = [u8p, u8p]
    lib.dht_sort_ids.restype = None
    lib.dht_sort_ids.argtypes = [u8p, i32p, ctypes.c_int64]
    lib.dht_lower_bound.restype = ctypes.c_int64
    lib.dht_lower_bound.argtypes = [u8p, ctypes.c_int64, u8p]
    lib.dht_sorted_closest.restype = None
    lib.dht_sorted_closest.argtypes = [u8p, ctypes.c_int64, u8p,
                                       ctypes.c_int64, ctypes.c_int32,
                                       ctypes.c_int32, i32p]
    lib.dht_scan_closest.restype = None
    lib.dht_scan_closest.argtypes = [u8p, ctypes.c_int64, u8p,
                                     ctypes.c_int64, ctypes.c_int32, i32p]
    lib.dht_udp_create.restype = ctypes.c_void_p
    lib.dht_udp_create.argtypes = [ctypes.c_uint16, ctypes.c_uint32,
                                   ctypes.c_uint32, ctypes.c_uint32,
                                   ctypes.c_int32, ctypes.c_int32]
    lib.dht_udp_port.restype = ctypes.c_uint16
    lib.dht_udp_port.argtypes = [ctypes.c_void_p]
    lib.dht_udp_has_v6.restype = ctypes.c_int32
    lib.dht_udp_has_v6.argtypes = [ctypes.c_void_p]
    lib.dht_udp_destroy.restype = None
    lib.dht_udp_destroy.argtypes = [ctypes.c_void_p]
    lib.dht_udp_send.restype = ctypes.c_int
    lib.dht_udp_send.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint32,
                                 u8p, ctypes.c_int32, ctypes.c_uint16]
    lib.dht_udp_poll.restype = ctypes.c_int32
    lib.dht_udp_poll.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint64,
                                 ctypes.c_int32, u64p]
    lib.dht_udp_pending.restype = ctypes.c_int32
    lib.dht_udp_pending.argtypes = [ctypes.c_void_p]
    lib.dht_udp_wait.restype = ctypes.c_int32
    lib.dht_udp_wait.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.dht_udp_stats.restype = None
    lib.dht_udp_stats.argtypes = [ctypes.c_void_p, u64p]


def get_lib() -> "ctypes.CDLL | None":
    """The loaded native library, building it on first call; None when
    unavailable (callers fall back to Python)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
            _declare(lib)
            _lib = lib
        except OSError as e:
            log.warning("native load failed: %s", e)
        return _lib


def available() -> bool:
    return get_lib() is not None
