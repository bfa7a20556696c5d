"""Native (C++) runtime components, loaded via ctypes.

The reference implements its data/runtime plumbing natively
(`paddle/gserver/dataproviders`, the RecordIO chunks the Go master
dispatches, `paddle/utils/Queue.h`); this package is the TPU build's
equivalent — see ``src/native.cc``. ``load_library()`` compiles the
shared object on first use with the host toolchain (g++) and caches it
next to the sources; ``available()`` reports whether the native path can
be used (every consumer has a pure-Python fallback). The object is
build output (git-ignored) named after its source's digest: file times
mean nothing after a checkout or a copy, so "built from THIS source" is
a name that exists, never an mtime compare.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src", "native.cc")

_lock = threading.Lock()
_lib = None
_failed = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libpaddle_tpu_native.{digest}.so")


def _build(so: str) -> bool:
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
           "-o", so + ".tmp", _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(so + ".tmp", so)
        for old in glob.glob(os.path.join(_DIR, "libpaddle_tpu_native*.so")):
            if old != so:  # objects of other source versions
                os.remove(old)
        return True
    except (subprocess.SubprocessError, OSError) as e:
        import logging
        logging.getLogger("paddle_tpu").warning(
            "native build failed (%s); using pure-Python fallbacks", e)
        return False


def load_library():
    """The ctypes library, building it if necessary; None if unavailable."""
    global _lib, _failed
    with _lock:
        if _lib is not None or _failed:
            return _lib
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            _failed = True
            return None
        lib = ctypes.CDLL(so)
        lib.ptr_writer_open.restype = ctypes.c_void_p
        lib.ptr_writer_open.argtypes = [ctypes.c_char_p]
        lib.ptr_writer_append.restype = ctypes.c_int
        lib.ptr_writer_append.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32]
        lib.ptr_writer_close.restype = ctypes.c_int
        lib.ptr_writer_close.argtypes = [ctypes.c_void_p]
        lib.ptr_reader_open.restype = ctypes.c_void_p
        lib.ptr_reader_open.argtypes = [ctypes.c_char_p]
        lib.ptr_reader_next.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.ptr_reader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
        lib.ptr_reader_close.restype = None
        lib.ptr_reader_close.argtypes = [ctypes.c_void_p]
        lib.ptr_pool_create.restype = ctypes.c_void_p
        lib.ptr_pool_create.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_uint64]
        lib.ptr_pool_next.restype = ctypes.c_int64
        lib.ptr_pool_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)]
        lib.ptr_pool_destroy.restype = None
        lib.ptr_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.ptr_vmsg_open.restype = ctypes.c_void_p
        lib.ptr_vmsg_open.argtypes = [ctypes.c_char_p]
        lib.ptr_vmsg_next.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.ptr_vmsg_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
        lib.ptr_vmsg_close.restype = None
        lib.ptr_vmsg_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return load_library() is not None
