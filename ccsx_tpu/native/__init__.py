"""Native (C++) IO layer loader.

Builds ``libccsx_io.so`` from the tracked sources in this directory on
first use (``make``, under a file lock so concurrent processes do not
race one build), loads it via ctypes, and exposes ``lib()``.  Callers
check ``available()`` and fall back to the pure-Python parsers
(ccsx_tpu.io.fastx / ccsx_tpu.io.bam) only when there is no compiler,
and say so loudly.  A build that fails with a compiler present, or a
built library that will not load, raises.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import shutil
import subprocess
import sys
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libccsx_io.so")
_LOG = os.path.join(_DIR, "build.log")
_LOCK = os.path.join(_DIR, ".build.lock")
_lock = threading.Lock()
_lib = None
_tried = False
_build_error: "str | None" = None


def _build() -> bool:
    """Run ``make``; False (with one loud line, remembered for Metrics as
    native_build_error) when there is no toolchain to run it with."""
    global _build_error
    missing = [t for t in ("make", os.environ.get("CXX", "g++"))
               if shutil.which(t) is None]
    if missing:
        _build_error = f"no {' / '.join(missing)} on PATH"
        print(f"[ccsx-tpu] WARNING: cannot build the native IO library "
              f"({_build_error}) — using the pure-Python parsers (same "
              f"bytes, slower ingest)", file=sys.stderr)
        return False
    r = subprocess.run(["make", "-s", "-C", _DIR], check=False,
                       capture_output=True, timeout=300, text=True)
    if r.returncode != 0 or not os.path.exists(_SO):
        out = (r.stdout or "") + (r.stderr or "")
        try:
            with open(_LOG, "w", encoding="utf-8") as f:
                f.write(out)
        except OSError:
            pass
        raise RuntimeError(
            f"native IO library build failed (make rc {r.returncode}; "
            f"compiler log: {_LOG}): {out.strip()[-400:]}")
    return True


def build_error() -> "str | None":
    """One-line summary of why this process could not build the native
    library (None when the native path loaded or was never needed).
    Read by Metrics.snapshot() so every metrics event carries the
    degradation."""
    return _build_error


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.ccsx_open.restype = c.c_void_p
    lib.ccsx_open.argtypes = [c.c_char_p, c.c_int]
    lib.ccsx_set_filter.restype = None
    lib.ccsx_set_filter.argtypes = [c.c_void_p, c.c_int32, c.c_int64,
                                    c.c_int64]
    lib.ccsx_next_zmw.restype = c.c_int
    lib.ccsx_next_zmw.argtypes = [
        c.c_void_p,
        c.POINTER(c.c_char_p), c.POINTER(c.c_char_p),
        c.POINTER(c.POINTER(c.c_uint8)), c.POINTER(c.c_int64),
        c.POINTER(c.POINTER(c.c_int32)), c.POINTER(c.c_int32),
    ]
    lib.ccsx_next_record.restype = c.c_int
    lib.ccsx_next_record.argtypes = [
        c.c_void_p,
        c.POINTER(c.c_char_p), c.POINTER(c.c_char_p),
        c.POINTER(c.POINTER(c.c_uint8)), c.POINTER(c.c_int64),
        c.POINTER(c.POINTER(c.c_uint8)), c.POINTER(c.c_int64),
    ]
    lib.ccsx_error.restype = c.c_char_p
    lib.ccsx_error.argtypes = [c.c_void_p]
    for name in ("ccsx_filter_counts", "ccsx_prefetch_filter_counts"):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [c.c_void_p] + [c.POINTER(c.c_int64)] * 3
    lib.ccsx_set_salvage.restype = None
    lib.ccsx_set_salvage.argtypes = [c.c_void_p, c.c_int, c.c_int64]
    lib.ccsx_prefetch_open_s.restype = c.c_void_p
    lib.ccsx_prefetch_open_s.argtypes = [
        c.c_char_p, c.c_int, c.c_int32, c.c_int64, c.c_int64,
        c.c_int32, c.c_int, c.c_int64]
    for name in ("ccsx_error_reason", "ccsx_prefetch_error_reason",
                 "ccsx_corrupt_summary", "ccsx_prefetch_corrupt_summary"):
        fn = getattr(lib, name)
        fn.restype = c.c_char_p
        fn.argtypes = [c.c_void_p]
    for name in ("ccsx_corrupt_events", "ccsx_prefetch_corrupt_events",
                 "ccsx_corrupt_exempt", "ccsx_prefetch_corrupt_exempt"):
        fn = getattr(lib, name)
        fn.restype = c.c_int64
        fn.argtypes = [c.c_void_p]
    lib.ccsx_close.restype = None
    lib.ccsx_close.argtypes = [c.c_void_p]
    for name in ("ccsx_encode", "ccsx_revcomp_ascii", "ccsx_revcomp_codes"):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [c.POINTER(c.c_uint8), c.c_int64, c.POINTER(c.c_uint8)]
    lib.ccsx_prefetch_open.restype = c.c_void_p
    lib.ccsx_prefetch_open.argtypes = [c.c_char_p, c.c_int, c.c_int32,
                                       c.c_int64, c.c_int64, c.c_int32]
    lib.ccsx_prefetch_next.restype = c.c_int
    lib.ccsx_prefetch_next.argtypes = lib.ccsx_next_zmw.argtypes
    lib.ccsx_prefetch_error.restype = c.c_char_p
    lib.ccsx_prefetch_error.argtypes = [c.c_void_p]
    lib.ccsx_prefetch_close.restype = None
    lib.ccsx_prefetch_close.argtypes = [c.c_void_p]
    lib.ccsx_writer_open.restype = c.c_void_p
    lib.ccsx_writer_open.argtypes = [c.c_char_p, c.c_int]
    lib.ccsx_writer_put_fasta.restype = c.c_int
    lib.ccsx_writer_put_fasta.argtypes = [c.c_void_p, c.c_char_p,
                                          c.POINTER(c.c_uint8), c.c_int64]
    lib.ccsx_writer_put_fastq.restype = c.c_int
    lib.ccsx_writer_put_fastq.argtypes = [c.c_void_p, c.c_char_p,
                                          c.POINTER(c.c_uint8),
                                          c.POINTER(c.c_uint8), c.c_int64]
    lib.ccsx_writer_close.restype = c.c_int
    lib.ccsx_writer_close.argtypes = [c.c_void_p]
    lib.ccsx_bgzf_pool_bench.restype = c.c_double
    lib.ccsx_bgzf_pool_bench.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.ccsx_align_scalar.restype = c.c_int
    lib.ccsx_align_scalar.argtypes = [
        c.POINTER(c.c_uint8), c.c_int64, c.POINTER(c.c_uint8), c.c_int64,
        c.c_int, c.c_int, c.c_int, c.c_int, c.c_int,
        c.POINTER(c.c_int64), c.POINTER(c.c_uint8), c.c_int64,
        c.POINTER(c.c_int64),
    ]
    return lib


def lib():
    """The loaded native library, or None when unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        import glob

        srcs = glob.glob(os.path.join(_DIR, "*.cpp"))
        try:
            lockf = open(_LOCK, "w")
        except OSError:          # read-only install: nothing to build
            lockf = None
        try:
            if lockf is not None:
                fcntl.flock(lockf, fcntl.LOCK_EX)  # one build at a time
            if not os.path.exists(_SO) or any(
                os.path.getmtime(_SO) < os.path.getmtime(s) for s in srcs
            ):
                if not _build():
                    return None
        finally:
            if lockf is not None:
                lockf.close()
        try:
            _lib = _bind(ctypes.CDLL(_SO))
        except OSError as e:
            # e.g. a leftover TSAN/ASAN instrumented build
            raise RuntimeError(
                f"libccsx_io.so failed to load ({e}); rebuild it with "
                f"`make -C {_DIR} clean all`") from e
    return _lib


def available() -> bool:
    return lib() is not None
