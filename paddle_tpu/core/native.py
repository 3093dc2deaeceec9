"""ctypes loader for the native C++ runtime (csrc/).

Builds csrc/libpaddle_tpu_native.so from csrc/*.cpp on first use (plain C
ABI, g++ + make; no pybind11).  The binary is not kept in git: it is built
when absent or when the sources' content hash differs from the one the
last build recorded (mtimes mean nothing in a fresh copy of the tree).
Every consumer has a pure-Python fallback; which of the two is in use is
said once on stderr.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading

_lib = None
_lock = threading.Lock()
_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SO = os.path.join(_CSRC, "libpaddle_tpu_native.so")
_STAMP = _SO + ".srchash"
_SOURCES = ("tcp_store.cpp", "shm_queue.cpp", "Makefile")


def _source_hash() -> str:
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_CSRC, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _built_hash():
    try:
        with open(_STAMP) as f:
            return f.read().strip()
    except OSError:
        return None


def _build_and_open():
    want = _source_hash()
    if not os.path.exists(_SO) or _built_hash() != want:
        tmp = "%s.%d.tmp" % (os.path.basename(_SO), os.getpid())
        subprocess.run(["make", "-s", "-B", "-C", _CSRC, "OUT=" + tmp],
                       check=True, capture_output=True, timeout=120)
        os.replace(os.path.join(_CSRC, tmp), _SO)
        with open(_STAMP + ".tmp.%d" % os.getpid(), "w") as f:
            f.write(want)
        os.replace(f.name, _STAMP)
    return ctypes.CDLL(_SO)


def load():
    """Return the loaded library or None when unavailable."""
    global _lib
    if _lib is not None:
        return _lib if _lib is not False else None
    with _lock:
        if _lib is not None:
            return _lib if _lib is not False else None
        try:
            lib = _build_and_open()
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", None) or e
            if isinstance(detail, bytes):
                detail = detail.decode(errors="replace")
            print("paddle_tpu: native runtime unavailable (%s: %s); using "
                  "the pure-Python store and data queues"
                  % (type(e).__name__, str(detail).strip()[-300:]),
                  file=sys.stderr)
            _lib = False
            return None
        print("paddle_tpu: native runtime loaded from %s" % _SO,
              file=sys.stderr)
        # signatures
        lib.tcp_store_server_create.restype = ctypes.c_void_p
        lib.tcp_store_server_create.argtypes = [ctypes.c_int]
        lib.tcp_store_server_port.restype = ctypes.c_int
        lib.tcp_store_server_port.argtypes = [ctypes.c_void_p]
        lib.tcp_store_server_destroy.argtypes = [ctypes.c_void_p]
        lib.tcp_store_client_create.restype = ctypes.c_void_p
        lib.tcp_store_client_create.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.tcp_store_client_create_t.restype = ctypes.c_void_p
        lib.tcp_store_client_create_t.argtypes = [ctypes.c_char_p,
                                                  ctypes.c_int, ctypes.c_int]
        lib.tcp_store_client_destroy.argtypes = [ctypes.c_void_p]
        lib.tcp_store_set.restype = ctypes.c_int
        lib.tcp_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p, ctypes.c_int]
        lib.tcp_store_get.restype = ctypes.c_longlong
        lib.tcp_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p, ctypes.c_longlong,
                                      ctypes.c_int]
        lib.tcp_store_add.restype = ctypes.c_longlong
        lib.tcp_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_longlong]
        lib.shm_queue_create.restype = ctypes.c_void_p
        lib.shm_queue_create.argtypes = [ctypes.c_char_p, ctypes.c_longlong]
        lib.shm_queue_open.restype = ctypes.c_void_p
        lib.shm_queue_open.argtypes = [ctypes.c_char_p]
        lib.shm_queue_push.restype = ctypes.c_int
        lib.shm_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_longlong]
        lib.shm_queue_pop.restype = ctypes.c_longlong
        lib.shm_queue_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_longlong]
        lib.shm_queue_size.restype = ctypes.c_longlong
        lib.shm_queue_size.argtypes = [ctypes.c_void_p]
        lib.shm_queue_close.argtypes = [ctypes.c_void_p]
        lib.shm_queue_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


def available() -> bool:
    return load() is not None
