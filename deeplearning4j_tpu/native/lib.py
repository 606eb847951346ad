"""Lazy build + load of the native library."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings
from pathlib import Path
from typing import Optional

_ROOT = Path(__file__).resolve().parent.parent.parent
_SRC = _ROOT / "native" / "dl4jtpu_native.cpp"
_BUILD_DIR = _ROOT / "native" / "build"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _so_path() -> Path:
    """The library for THIS source: the name carries a hash of the .cpp, so
    a stale binary can never be loaded for an edited source, and a copied
    tree (which does not preserve mtimes) rebuilds exactly when it must."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD_DIR / f"libdl4jtpu_{digest}.so"


def _build(out: Path) -> bool:
    out.parent.mkdir(parents=True, exist_ok=True)
    # built under a private name and renamed into place, so a concurrent
    # process never dlopens a half-written file
    tmp = out.with_name(f".{out.name}.{os.getpid()}")
    base = ["g++", "-O3", "-std=c++17", "-fPIC", "-Wall", "-pthread",
            "-shared", "-o", str(tmp), str(_SRC)]
    # preferred: with the native JPEG/PNG decode front; without the
    # libjpeg/libpng dev files a codec-less build (the Python layer then
    # decodes via PIL)
    err = ""
    for cmd in (base + ["-DDL4J_WITH_CODECS", "-ljpeg", "-lpng"], base):
        try:
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=300)
        except (FileNotFoundError, subprocess.TimeoutExpired) as e:
            err = f"{type(e).__name__}: {e}"
            break
        if res.returncode == 0:
            os.replace(tmp, out)
            for old in out.parent.glob("libdl4jtpu_*.so"):
                if old != out:          # builds of earlier sources
                    old.unlink(missing_ok=True)
            return True
        err = res.stderr
    tmp.unlink(missing_ok=True)
    warnings.warn(f"native build failed; the pure-Python paths are used:\n"
                  f"{err[-2000:]}")
    return False


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.dl4j_ws_create.restype = c.c_void_p
    lib.dl4j_ws_create.argtypes = [c.c_size_t]
    lib.dl4j_ws_alloc.restype = c.c_void_p
    lib.dl4j_ws_alloc.argtypes = [c.c_void_p, c.c_size_t, c.c_size_t]
    lib.dl4j_ws_reset.argtypes = [c.c_void_p]
    lib.dl4j_ws_used.restype = c.c_size_t
    lib.dl4j_ws_used.argtypes = [c.c_void_p]
    lib.dl4j_ws_peak.restype = c.c_size_t
    lib.dl4j_ws_peak.argtypes = [c.c_void_p]
    lib.dl4j_ws_spilled.restype = c.c_size_t
    lib.dl4j_ws_spilled.argtypes = [c.c_void_p]
    lib.dl4j_ws_destroy.argtypes = [c.c_void_p]

    lib.dl4j_pipe_create.restype = c.c_void_p
    lib.dl4j_pipe_create.argtypes = [c.c_char_p, c.c_char_p, c.c_long,
                                     c.c_long, c.c_long, c.c_long, c.c_int,
                                     c.c_uint, c.c_int, c.c_int]
    lib.dl4j_pipe_next.restype = c.c_int
    lib.dl4j_pipe_next.argtypes = [c.c_void_p, c.POINTER(c.c_float),
                                   c.POINTER(c.c_float)]
    lib.dl4j_pipe_reset.argtypes = [c.c_void_p]
    lib.dl4j_pipe_batches_per_epoch.restype = c.c_long
    lib.dl4j_pipe_batches_per_epoch.argtypes = [c.c_void_p]
    lib.dl4j_pipe_destroy.argtypes = [c.c_void_p]

    lib.dl4j_imgpipe_create.restype = c.c_void_p
    lib.dl4j_imgpipe_create.argtypes = [c.c_char_p, c.c_char_p, c.c_long,
                                        c.c_long, c.c_long, c.c_long,
                                        c.c_long, c.c_long, c.c_long,
                                        c.c_long, c.c_int, c.c_int, c.c_uint,
                                        c.POINTER(c.c_float),
                                        c.POINTER(c.c_float), c.c_int,
                                        c.c_int, c.c_int]
    lib.dl4j_imgpipe_next.restype = c.c_int
    lib.dl4j_imgpipe_next.argtypes = [c.c_void_p, c.POINTER(c.c_float),
                                      c.POINTER(c.c_float)]
    lib.dl4j_imgpipe_next_u8.restype = c.c_int
    lib.dl4j_imgpipe_next_u8.argtypes = [c.c_void_p, c.POINTER(c.c_uint8),
                                         c.POINTER(c.c_float)]
    lib.dl4j_imgpipe_reset.argtypes = [c.c_void_p]
    lib.dl4j_imgpipe_batches_per_epoch.restype = c.c_long
    lib.dl4j_imgpipe_batches_per_epoch.argtypes = [c.c_void_p]
    lib.dl4j_imgpipe_destroy.argtypes = [c.c_void_p]

    lib.dl4j_csv_parse.restype = c.c_void_p
    lib.dl4j_csv_parse.argtypes = [c.c_char_p, c.c_char, c.c_int, c.c_int]
    lib.dl4j_csv_rows.restype = c.c_long
    lib.dl4j_csv_rows.argtypes = [c.c_void_p]
    lib.dl4j_csv_bad_fields.restype = c.c_long
    lib.dl4j_csv_bad_fields.argtypes = [c.c_void_p]
    lib.dl4j_csv_cols.restype = c.c_long
    lib.dl4j_csv_cols.argtypes = [c.c_void_p]
    lib.dl4j_csv_copy.argtypes = [c.c_void_p, c.POINTER(c.c_float)]
    lib.dl4j_csv_free.argtypes = [c.c_void_p]

    lib.dl4j_cache_trim.restype = c.c_long
    lib.dl4j_cache_trim.argtypes = [c.c_char_p, c.c_long]

    lib.dl4j_wc_create.restype = c.c_void_p
    lib.dl4j_wc_create.argtypes = [c.c_char_p, c.c_int]
    lib.dl4j_wc_bytes.restype = c.c_long
    lib.dl4j_wc_bytes.argtypes = [c.c_void_p]
    lib.dl4j_wc_dump.argtypes = [c.c_void_p, c.c_char_p]
    lib.dl4j_wc_destroy.argtypes = [c.c_void_p]

    lib.dl4j_w2v_create.restype = c.c_void_p
    lib.dl4j_w2v_create.argtypes = [c.c_char_p, c.c_char_p, c.c_long,
                                    c.POINTER(c.c_float),
                                    c.POINTER(c.c_float), c.c_int, c.c_int,
                                    c.c_long, c.c_uint, c.c_int, c.c_int]
    lib.dl4j_w2v_next.restype = c.c_int
    lib.dl4j_w2v_next.argtypes = [c.c_void_p, c.POINTER(c.c_int32),
                                  c.POINTER(c.c_int32), c.POINTER(c.c_int32)]
    lib.dl4j_w2v_reset.argtypes = [c.c_void_p]
    lib.dl4j_w2v_words.restype = c.c_long
    lib.dl4j_w2v_words.argtypes = [c.c_void_p]
    lib.dl4j_w2v_pairs.restype = c.c_long
    lib.dl4j_w2v_pairs.argtypes = [c.c_void_p]
    lib.dl4j_w2v_destroy.argtypes = [c.c_void_p]

    if hasattr(lib, "dl4j_image_decode"):     # codec build present
        lib.dl4j_image_probe.restype = c.c_int
        lib.dl4j_image_probe.argtypes = [c.c_char_p, c.POINTER(c.c_long),
                                         c.POINTER(c.c_long)]
        lib.dl4j_image_decode.restype = c.c_int
        lib.dl4j_image_decode.argtypes = [c.c_char_p,
                                          c.POINTER(c.c_uint8), c.c_long,
                                          c.c_long, c.c_long]
        lib.dl4j_image_stage.restype = c.c_int
        lib.dl4j_image_stage.argtypes = [c.c_char_p, c.c_long, c.c_char_p,
                                         c.c_long, c.c_long, c.c_long,
                                         c.c_int]
    return lib


def native_csv_parse(path, delimiter: str = ",", skip_header: bool = False,
                     n_threads: int = 4):
    """Parse a numeric CSV into a float32 [rows, cols] array using the
    multi-threaded native parser; None if the native lib is unavailable or
    the file can't be parsed (caller falls back to Python)."""
    import numpy as np

    lib = load_native_lib()
    if lib is None:
        return None
    h = lib.dl4j_csv_parse(str(path).encode(), delimiter.encode(),
                           int(skip_header), n_threads)
    if not h:
        return None
    try:
        if lib.dl4j_csv_bad_fields(h):
            # non-numeric content: refuse rather than return silent zeros —
            # the Python fallback will raise (or parse strings) consistently
            return None
        rows, cols = lib.dl4j_csv_rows(h), lib.dl4j_csv_cols(h)
        out = np.empty((rows, cols), np.float32)
        lib.dl4j_csv_copy(h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        return out
    finally:
        lib.dl4j_csv_free(h)


def trim_compile_cache(cache_dir: str, cap_bytes: int) -> int:
    """LRU-trim a persistent XLA compilation cache directory down to
    cap_bytes (PJRT executable-cache management; libnd4j GraphHolder analog).
    Returns bytes evicted (0 if under cap), -1 on error/no native lib."""
    lib = load_native_lib()
    if lib is None or not os.path.isdir(cache_dir):
        return -1
    return int(lib.dl4j_cache_trim(str(cache_dir).encode(), int(cap_bytes)))


def load_native_lib() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the native library; None if unavailable.
    One attempt per process — success and failure are both cached, so a
    failed build warns exactly once."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _so_path()
        if so.exists() or _build(so):
            _lib = _declare(ctypes.CDLL(str(so)))
        return _lib


def native_available() -> bool:
    return load_native_lib() is not None
