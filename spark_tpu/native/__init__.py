"""Native (C++) runtime kernels, loaded via ctypes.

The reference's equivalent tier is JVM-native code: UTF8String.java
byte-twiddling, Janino-compiled predicates, JNI codecs (SURVEY.md §2
[NATIVE-EQ] rows). Here the device compute path is XLA/Pallas; the
*host* runtime tier — dictionary-table string predicates feeding the
trace — is C++ compiled on first use with the toolchain g++ and bound
with ctypes (no pybind11 in this image).

Degradation contract: if no compiler is present or the build fails,
``available()`` is False and every caller keeps its pure-Python path.
The build is cached next to the source and rebuilt when the source
changes (mtime check).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

from spark_tpu import locks

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "strkernels.cpp")
_SO = os.path.join(_DIR, "_strkernels.so")

_lock = locks.named_lock("native.registry")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    # a temp name of this process's own: several processes may build at
    # once (xdist workers importing tests/test_native.py on a fresh
    # checkout), and each must replace the target with a whole file
    tmp = os.path.join(_DIR, f"_strkernels.{os.getpid()}.so.tmp")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
    except (OSError, subprocess.SubprocessError):
        if os.path.exists(tmp):
            os.unlink(tmp)
        return False
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("SPARK_TPU_NATIVE", "1") == "0":
            return None
        fresh = os.path.exists(_SO) and \
            os.path.getmtime(_SO) >= os.path.getmtime(_SRC)
        if not fresh and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.like_table.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8)]
        lib.predicate_table.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8)]
        lib.hash_table64.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64)]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _arrow_buffers(strings: Sequence[str]):
    """Dictionary -> (data bytes, int64 offsets) in Arrow large_string
    layout. pyarrow does the UTF-8 encode in C, so the only Python-level
    loop anywhere on this path is pyarrow's sequence ingestion."""
    import pyarrow as pa

    arr = pa.array(strings, type=pa.large_string())
    bufs = arr.buffers()  # [validity, offsets, data]
    offsets = np.frombuffer(bufs[1], dtype=np.int64,
                            count=len(strings) + 1)
    data = bufs[2]
    return (bytes(data) if data is not None else b""), offsets


def like_table(dictionary: Sequence[str], pattern: str) -> np.ndarray:
    """bool[n]: SQL LIKE over every dictionary entry (semantics match
    expr/compiler._like_to_regex: % any run, _ one codepoint)."""
    lib = _load()
    assert lib is not None
    data, offsets = _arrow_buffers(dictionary)
    n = len(dictionary)
    out = np.zeros(n, dtype=np.uint8)
    pat = pattern.encode("utf-8")
    lib.like_table(
        data, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, pat, len(pat),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out.astype(bool)


_PRED_OPS = {"contains": 0, "startswith": 1, "endswith": 2}


def predicate_table(dictionary: Sequence[str], op: str,
                    needle: str) -> np.ndarray:
    lib = _load()
    assert lib is not None
    data, offsets = _arrow_buffers(dictionary)
    n = len(dictionary)
    out = np.zeros(n, dtype=np.uint8)
    nd = needle.encode("utf-8")
    lib.predicate_table(
        data, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, nd, len(nd), _PRED_OPS[op],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out.astype(bool)


def hash_table64(dictionary: Sequence[str], seed: int = 42) -> np.ndarray:
    lib = _load()
    assert lib is not None
    data, offsets = _arrow_buffers(dictionary)
    n = len(dictionary)
    out = np.zeros(n, dtype=np.uint64)
    lib.hash_table64(
        data, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, ctypes.c_uint64(seed),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    return out
