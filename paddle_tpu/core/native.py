"""Build + load the native C++ runtime library.

The reference's runtime core is native C++ (SURVEY.md §2.1: allocator
facade, TCPStore, shm transfer). Ours is too: paddle_tpu/csrc/*.cc compiles
into one libpaddle_tpu_rt-<hash>.so at first use (g++ -O2 -shared; no
network, no extra deps) and binds via ctypes. The library is never
committed (``*.so`` is git-ignored) and its name carries a hash of the
sources and the compile command, so a library built from other sources
— one that travelled with a copy of the tree, whatever its mtime — is
never loaded. Everything degrades gracefully: if no toolchain is
available, ``lib()`` returns None and pure-Python fallbacks take over
(callers must check).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_LIB_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_CSRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "csrc")
_SOURCES = ["allocator.cc", "shm_ring.cc", "tcp_store.cc"]
_CXX = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread"]


def _so_path() -> str:
    """Library path keyed on what it is built FROM: source bytes +
    compile command."""
    h = hashlib.sha256(" ".join(_CXX).encode())
    for s in _SOURCES:
        with open(os.path.join(_CSRC, s), "rb") as f:
            h.update(f.read())
    return os.path.join(_CSRC, f"libpaddle_tpu_rt-{h.hexdigest()[:16]}.so")


def _build() -> Optional[str]:
    so = _so_path()
    if os.path.exists(so):
        return so
    # a temporary name of this process's own: several test workers
    # build at once, and a shared ".tmp" is overwritten mid-link
    tmp = f"{so[:-3]}.{os.getpid()}.so.tmp"
    cmd = [*_CXX, *(os.path.join(_CSRC, s) for s in _SOURCES),
           "-lrt", "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=240)
        os.replace(tmp, so)
        return so
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError, OSError) as e:
        err = getattr(e, "stderr", b"")
        if os.environ.get("PADDLE_TPU_NATIVE_REQUIRED"):
            raise RuntimeError(
                f"native runtime build failed: {err!r}") from e
        return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64, i64, vp, cp = (ctypes.c_uint64, ctypes.c_int64, ctypes.c_void_p,
                        ctypes.c_char_p)
    sigs = {
        "pt_alloc_create": ([u64], vp),
        "pt_alloc_destroy": ([vp], None),
        "pt_alloc_malloc": ([vp, u64], vp),
        "pt_alloc_free": ([vp, vp], ctypes.c_int),
        "pt_alloc_stats": ([vp, ctypes.POINTER(u64)], None),
        "pt_alloc_reset_peak": ([vp], None),
        "pt_ring_create": ([cp, u64], vp),
        "pt_ring_attach": ([cp], vp),
        "pt_ring_push": ([vp, vp, u64, i64], ctypes.c_int),
        "pt_ring_next_size": ([vp], i64),
        "pt_ring_pop": ([vp, vp, u64, i64], i64),
        "pt_ring_close": ([vp], None),
        "pt_ring_capacity": ([vp], u64),
        "pt_ring_wait_space": ([vp, u64, i64], ctypes.c_int),
        "pt_ring_destroy": ([vp], None),
        "pt_store_server_start": ([ctypes.c_int], vp),
        "pt_store_server_stop": ([vp], None),
        "pt_store_connect": ([cp, ctypes.c_int, ctypes.c_int], vp),
        "pt_store_disconnect": ([vp], None),
        "pt_store_set": ([vp, cp, vp, ctypes.c_uint32], ctypes.c_int),
        "pt_store_get": ([vp, cp, vp, ctypes.c_uint32], i64),
        "pt_store_add": ([vp, cp, i64], i64),
        "pt_store_wait": ([vp, cp], ctypes.c_int),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    return lib


def lib() -> Optional[ctypes.CDLL]:
    """The native runtime library, building it on first call (None if no
    toolchain and PADDLE_TPU_NATIVE_REQUIRED is unset)."""
    global _LIB, _TRIED
    with _LIB_LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        so = _build()
        if so is not None:
            _LIB = _bind(ctypes.CDLL(so))
        return _LIB


def available() -> bool:
    return lib() is not None
