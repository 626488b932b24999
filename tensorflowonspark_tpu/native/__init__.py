"""Native (C++) runtime components and their ctypes bindings.

Two components, both from-scratch C++ replacing engine capabilities the
reference delegated to external native code (SURVEY.md §2.2):

- ``tfrecord.cc`` — TFRecord framing codec with masked crc32c (replaces
  the Java tensorflow-hadoop connector consumed by ``dfutil.py``).
- ``shmring.cc`` — shared-memory SPSC ring buffer, the same-host feed
  fast path (replaces the reference's pickle+socket proxy hot loop,
  SURVEY.md §3.2).

The library is compiled on demand with the toolchain's ``g++`` and cached
next to the sources under a name that carries a hash of those sources
and of the compile command: a product built from anything else — a stale
copy whose mtime a file copy made newer than the sources, say — is
never loaded. Callers must tolerate ``load_library()`` returning None —
every user has a pure-Python fallback, so the framework works without a
C++ toolchain.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("tfrecord.cc", "shmring.cc")
_HEADERS = ("crc32c.h",)  # part of the build key; not on the compile line

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_failed = False


def _build_dir() -> str:
    d = os.environ.get("TFOS_NATIVE_BUILD_DIR") or os.path.join(_DIR, "build")
    os.makedirs(d, exist_ok=True)
    return d


def build_key(paths, cmd) -> str:
    """Short hash of the source files' bytes and the compile command —
    the part of a build product's name that ties it to what it was
    built from."""
    h = hashlib.sha256(" ".join(cmd).encode())
    for path in paths:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:12]


def _compile_cmd() -> list[str]:
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O3",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-Wall",
    ]
    if platform.machine() in ("x86_64", "AMD64"):
        cmd.append("-msse4.2")  # hardware crc32c
    cmd += [os.path.join(_DIR, s) for s in _SOURCES]
    return cmd


def _compile(cmd: list[str], lib_path: str) -> None:
    cmd = cmd + ["-o", lib_path, "-lrt", "-pthread"]
    logger.info("building native library: %s", " ".join(cmd))
    subprocess.run(cmd, check=True, capture_output=True, text=True)


def load_library() -> ctypes.CDLL | None:
    """Build (unless this very source was built already) and dlopen the
    native library; None on failure."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            cmd = _compile_cmd()
            key = build_key(
                [os.path.join(_DIR, s) for s in _SOURCES + _HEADERS], cmd
            )
            lib_path = os.path.join(_build_dir(), f"libtfos_native-{key}.so")
            if not os.path.exists(lib_path):
                tmp = lib_path + f".tmp.{os.getpid()}"
                _compile(cmd, tmp)
                os.replace(tmp, lib_path)  # atomic vs concurrent builders
            lib = ctypes.CDLL(lib_path)
            _bind(lib)
            _lib = lib
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            logger.warning(
                "native library unavailable, using pure-Python fallbacks: %s",
                detail.strip()[:500],
            )
            _load_failed = True
    return _lib


def _bind(lib: ctypes.CDLL) -> None:
    c = ctypes
    u8p, u64, i64, u32 = (
        c.POINTER(c.c_uint8),
        c.c_uint64,
        c.c_int64,
        c.c_uint32,
    )
    # tfrecord
    lib.tfr_writer_open.restype = c.c_void_p
    lib.tfr_writer_open.argtypes = [c.c_char_p]
    lib.tfr_writer_append.restype = c.c_int
    lib.tfr_writer_append.argtypes = [c.c_void_p, c.c_char_p, u64]
    lib.tfr_writer_flush.restype = c.c_int
    lib.tfr_writer_flush.argtypes = [c.c_void_p]
    lib.tfr_writer_close.restype = c.c_int
    lib.tfr_writer_close.argtypes = [c.c_void_p]
    lib.tfr_reader_open.restype = c.c_void_p
    lib.tfr_reader_open.argtypes = [c.c_char_p]
    lib.tfr_reader_next.restype = i64
    lib.tfr_reader_next.argtypes = [
        c.c_void_p,
        c.POINTER(c.POINTER(c.c_uint8)),
        c.POINTER(c.c_int),
    ]
    lib.tfr_reader_close.restype = None
    lib.tfr_reader_close.argtypes = [c.c_void_p]
    lib.tfr_masked_crc32c.restype = u32
    lib.tfr_masked_crc32c.argtypes = [c.c_char_p, u64]
    lib.tfr_index_file.restype = i64
    lib.tfr_index_file.argtypes = [
        c.c_char_p,
        c.POINTER(c.POINTER(c.c_uint64)),
    ]
    lib.tfr_index_free.restype = None
    lib.tfr_index_free.argtypes = [c.POINTER(c.c_uint64)]
    # shmring
    lib.shmring_create.restype = c.c_void_p
    lib.shmring_create.argtypes = [c.c_char_p, u64]
    lib.shmring_open.restype = c.c_void_p
    lib.shmring_open.argtypes = [c.c_char_p]
    lib.shmring_push.restype = c.c_int
    lib.shmring_push.argtypes = [c.c_void_p, c.c_char_p, u64, i64]
    lib.shmring_peek_len.restype = i64
    lib.shmring_peek_len.argtypes = [c.c_void_p, i64]
    lib.shmring_pop.restype = i64
    lib.shmring_pop.argtypes = [c.c_void_p, u8p, u64]
    lib.shmring_close_write.restype = None
    lib.shmring_close_write.argtypes = [c.c_void_p]
    lib.shmring_is_closed.restype = c.c_int
    lib.shmring_is_closed.argtypes = [c.c_void_p]
    lib.shmring_size.restype = u64
    lib.shmring_size.argtypes = [c.c_void_p]
    lib.shmring_capacity.restype = u64
    lib.shmring_capacity.argtypes = [c.c_void_p]
    lib.shmring_detach.restype = None
    lib.shmring_detach.argtypes = [c.c_void_p]
    lib.shmring_unlink.restype = c.c_int
    lib.shmring_unlink.argtypes = [c.c_char_p]
    # shmring columnar zero-copy extensions
    lib.shmring_avail.restype = i64
    lib.shmring_avail.argtypes = [c.c_void_p, u64, i64]
    lib.shmring_payload_ptr.restype = c.c_void_p
    lib.shmring_payload_ptr.argtypes = [c.c_void_p, u64, u64]
    lib.shmring_read_at.restype = None
    lib.shmring_read_at.argtypes = [c.c_void_p, u64, u8p, u64]
    lib.shmring_tail.restype = u64
    lib.shmring_tail.argtypes = [c.c_void_p]
    lib.shmring_set_tail.restype = None
    lib.shmring_set_tail.argtypes = [c.c_void_p, u64]
    lib.shmring_pushv.restype = c.c_int
    lib.shmring_pushv.argtypes = [
        c.c_void_p,
        c.POINTER(c.c_void_p),
        c.POINTER(u64),
        u64,
        i64,
    ]


def available() -> bool:
    return load_library() is not None
