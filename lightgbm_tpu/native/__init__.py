"""Native host-side kernels (C++17 + OpenMP), loaded via ctypes.

The shared library is compiled on demand with g++ into a per-user cache dir (no
pip/pybind dependency); every entry point has a NumPy fallback so the framework works
without a toolchain.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Optional

import numpy as np

from ..utils.log import log_debug, log_warning

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_SRC = Path(__file__).parent / "binner.cpp"


def _build_lib() -> Optional[ctypes.CDLL]:
    # per-user 0700 cache dir: a predictable world-writable path would let another
    # local user pre-plant a .so that we'd dlopen
    default = Path(tempfile.gettempdir()) / f"lgbt_native_{os.getuid()}"
    cache_dir = Path(os.environ.get("LIGHTGBM_TPU_CACHE", default))
    cache_dir.mkdir(parents=True, exist_ok=True, mode=0o700)
    st = cache_dir.stat()
    if st.st_uid != os.getuid():
        log_warning(f"native cache dir {cache_dir} is not owned by this user; "
                    "refusing to load native code from it (NumPy fallback)")
        return None
    src = _SRC.read_bytes()
    tag = hashlib.sha256(src).hexdigest()[:16]
    so_path = cache_dir / f"libbinner_{tag}.so"
    if not so_path.exists():
        cmd = ["g++", "-O3", "-march=native", "-std=c++17", "-shared", "-fPIC",
               "-fopenmp", str(_SRC), "-o", str(so_path)]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        except Exception as e:  # noqa: BLE001 — any toolchain failure -> fallback
            log_warning(f"native binner build failed ({e}); using NumPy fallback")
            return None
    try:
        lib = ctypes.CDLL(str(so_path))
    except OSError as e:
        log_warning(f"native binner load failed ({e}); using NumPy fallback")
        return None
    lib.lgbt_rows_cols.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char,
                                   ctypes.c_int,
                                   ctypes.POINTER(ctypes.c_int64),
                                   ctypes.POINTER(ctypes.c_int64)]
    lib.lgbt_parse_csv.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char,
                                   ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
                                   ctypes.POINTER(ctypes.c_double)]
    lib.lgbt_value_to_bin.argtypes = [ctypes.POINTER(ctypes.c_double),
                                      ctypes.c_int64,
                                      ctypes.POINTER(ctypes.c_double),
                                      ctypes.c_int32, ctypes.c_int32,
                                      ctypes.c_int32, ctypes.c_int32,
                                      ctypes.POINTER(ctypes.c_uint16)]
    pd = ctypes.POINTER(ctypes.c_double)
    pi = ctypes.POINTER(ctypes.c_int32)
    for fn, ptr in ((lib.lgbt_bin_rows_f32, ctypes.POINTER(ctypes.c_float)),
                    (lib.lgbt_bin_rows_f64, pd)):
        fn.argtypes = [ptr, ctypes.c_int64, ctypes.c_int64, pi,
                       ctypes.c_int32, pd, ctypes.POINTER(ctypes.c_int64),
                       pi, pi, ctypes.POINTER(ctypes.c_uint8)]
    lib.lgbt_predict_row.argtypes = [
        pd, pi, ctypes.c_int32, pi, pd, pi,
        ctypes.POINTER(ctypes.c_uint8), pi, pi, pi, pd, pi,
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_int32, pd]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
            _LIB = None
        else:
            _LIB = _build_lib()
    return _LIB


def parse_csv(path: str, delim: str = ",", skip_header: bool = False
              ) -> Optional[np.ndarray]:
    """Parse a delimited file natively; returns None if the library is unavailable."""
    return parse_csv_bytes(Path(path).read_bytes(), delim, skip_header)


def parse_csv_bytes(buf: bytes, delim: str = ",", skip_header: bool = False
                    ) -> Optional[np.ndarray]:
    """Parse an in-memory delimited blob (e.g. one rank's file shard) with
    the same native parser as parse_csv, so distributed and single-process
    loads produce bit-identical doubles."""
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    lib.lgbt_rows_cols(buf, len(buf), delim.encode()[0:1], int(skip_header),
                       ctypes.byref(rows), ctypes.byref(cols))
    if rows.value <= 0 or cols.value <= 0:
        return None
    out = np.empty((rows.value, cols.value), np.float64)
    lib.lgbt_parse_csv(buf, len(buf), delim.encode()[0:1], int(skip_header),
                       rows.value, cols.value,
                       out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return out


def value_to_bin(values: np.ndarray, upper_bounds: np.ndarray, missing_type: int,
                 num_bins: int, default_bin: int) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, np.float64)
    ub = np.ascontiguousarray(upper_bounds, np.float64)
    out = np.empty(len(values), np.uint16)
    lib.lgbt_value_to_bin(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(values),
        ub.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(ub),
        int(missing_type), int(num_bins), int(default_bin),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)))
    return out


def bin_rows(data: np.ndarray, col_of_group, upper_bounds, missing_types,
             num_bins) -> Optional[np.ndarray]:
    """(N, G) uint8 bins of a row-major float32 / float64 table, group g
    from column col_of_group[g] by value_to_bin's search, all rows in one
    native call.  None where the library is unavailable or the table is not
    one it reads in place (the caller bins column by column instead)."""
    lib = get_lib()
    if (lib is None or not isinstance(data, np.ndarray) or data.ndim != 2
            or data.dtype not in (np.float32, np.float64)
            or data.strides[1] != data.itemsize
            or data.strides[0] % data.itemsize or data.strides[0] <= 0):
        return None
    cols = np.ascontiguousarray(col_of_group, np.int32)
    bounds = np.ascontiguousarray(np.concatenate(upper_bounds), np.float64)
    off = np.zeros(len(upper_bounds) + 1, np.int64)
    np.cumsum([len(u) for u in upper_bounds], out=off[1:])
    mt = np.ascontiguousarray(missing_types, np.int32)
    nb = np.ascontiguousarray(num_bins, np.int32)
    out = np.empty((data.shape[0], len(cols)), np.uint8)
    f32 = data.dtype == np.float32
    fn = lib.lgbt_bin_rows_f32 if f32 else lib.lgbt_bin_rows_f64
    pi = ctypes.POINTER(ctypes.c_int32)
    fn(data.ctypes.data_as(ctypes.POINTER(
           ctypes.c_float if f32 else ctypes.c_double)),
       data.shape[0], data.strides[0] // data.itemsize,
       cols.ctypes.data_as(pi), len(cols),
       bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
       off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
       mt.ctypes.data_as(pi), nb.ctypes.data_as(pi),
       out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out
