"""Native (C++) runtime components, loaded via ctypes.

The reference keeps result delivery native-fast (server/util.go
dumpTextRow is pure Go on the hot path); our analog compiles
rowcodec.cpp once per checkout with the baked-in g++ and falls back to
the pure-Python encoder when that fails — saying so once, with the reason,
in the log; `encoder()` names the one in use. No pybind11 in the image, so
the ABI is a C struct array + raw numpy pointers.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import List, Optional, Tuple

import numpy as np

from tidb_tpu.util import timeline

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "rowcodec.cpp")
_LIB = os.path.join(_DIR, "_rowcodec.so")

_lock = timeline.named_lock("rowcodec")
_lib = None
_tried = False


class _Col(ctypes.Structure):
    _fields_ = [
        ("kind", ctypes.c_int32),
        ("scale", ctypes.c_int32),
        ("values", ctypes.c_void_p),
        ("valid", ctypes.c_void_p),
        ("strbuf", ctypes.c_char_p),
        ("stroff", ctypes.c_void_p),
    ]


def _build() -> str:
    if os.path.exists(_LIB) and \
            os.path.getmtime(_LIB) >= os.path.getmtime(_SRC):
        return _LIB
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", _SRC,
             "-o", _LIB + ".tmp"],
            check=True, capture_output=True, timeout=120)
    except subprocess.CalledProcessError as e:
        raise OSError(f"g++ exited {e.returncode}: "
                      f"{e.stderr.decode(errors='replace')[-400:]}") from e
    os.replace(_LIB + ".tmp", _LIB)
    return _LIB


def get_lib():
    """The compiled library, or None (callers fall back to Python)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            lib = ctypes.CDLL(_build())
            lib.encode_text_rows.restype = ctypes.c_longlong
            lib.encode_text_rows.argtypes = [
                ctypes.POINTER(_Col), ctypes.c_int32, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
            _lib = lib
        except (OSError, subprocess.SubprocessError) as e:
            # no toolchain / unloadable .so → python encoder; said once
            # (_tried), never silently
            logging.getLogger("tidb_tpu.native").warning(
                "native row codec unavailable, using the Python encoder: "
                "%s: %s", type(e).__name__, e)
            _lib = None
        return _lib


def encoder() -> str:
    """Which text-row encoder this process serves with."""
    return "native" if get_lib() is not None else "python"


# column kind tags (must match rowcodec.cpp)
K_INT, K_FLOAT, K_DECIMAL, K_DATE, K_DATETIME, K_STR = range(6)


def encode_text_rows(chunk, ftypes, seq: int) -> Optional[Tuple[bytes, int]]:
    """Whole-chunk MySQL text-row packets → (bytes, next_seq), or None
    when a column shape isn't supported (caller uses the Python path)."""
    from tidb_tpu.types import TypeKind
    lib = get_lib()
    if lib is None or chunk.num_rows == 0:
        return None
    n = chunk.num_rows
    cols = (_Col * chunk.num_cols)()
    keepalive: List[np.ndarray] = []
    str_bytes = 0
    for i, (col, ft) in enumerate(zip(chunk.columns, ftypes)):
        c = cols[i]
        c.scale = ft.scale
        valid = col.validity
        if valid is not None:
            v8 = np.ascontiguousarray(valid, dtype=np.uint8)
            keepalive.append(v8)
            c.valid = v8.ctypes.data_as(ctypes.c_void_p)
        else:
            c.valid = None
        k = ft.kind
        vals = col.values
        if k.is_string:
            encoded = [str(x).encode("utf-8") for x in vals]
            offs = np.zeros(n + 1, dtype=np.int64)
            np.cumsum([len(b) for b in encoded], out=offs[1:])
            buf = b"".join(encoded)
            str_bytes += len(buf)
            keepalive.append(offs)
            c.kind = K_STR
            c.strbuf = buf
            keepalive.append(buf)  # type: ignore[arg-type]
            c.stroff = offs.ctypes.data_as(ctypes.c_void_p)
            continue
        if k is TypeKind.DECIMAL:
            c.kind = K_DECIMAL
            arr = np.ascontiguousarray(vals, dtype=np.int64)
        elif k is TypeKind.DATE:
            c.kind = K_DATE
            arr = np.ascontiguousarray(vals, dtype=np.int32)
        elif k in (TypeKind.DATETIME, TypeKind.TIMESTAMP):
            c.kind = K_DATETIME
            arr = np.ascontiguousarray(vals, dtype=np.int64)
        elif k.is_float:
            c.kind = K_FLOAT
            arr = np.ascontiguousarray(vals, dtype=np.float64)
        elif k.is_integer:
            c.kind = K_INT
            arr = np.ascontiguousarray(vals, dtype=np.int64)
        else:
            return None           # TIME etc: python path
        keepalive.append(arr)
        c.values = arr.ctypes.data_as(ctypes.c_void_p)
    # capacity: UTF-8 BYTES (already summed) + framing + numeric worst case
    cap = 64 + str_bytes
    for ft in ftypes:
        cap += (9 if ft.kind.is_string else 40) * n
    out = (ctypes.c_uint8 * cap)()
    seq_io = ctypes.c_uint8(seq)
    written = lib.encode_text_rows(cols, chunk.num_cols, n,
                                   ctypes.byref(seq_io), out, cap)
    if written < 0:
        return None
    return ctypes.string_at(out, written), seq_io.value
