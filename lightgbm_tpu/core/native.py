"""ctypes loader for the native binning hot path (src/native/fastbin.cpp).

The reference keeps bin construction in C++ (bin.cpp:74-208); the Python
greedy loop costs ~0.4s per feature at the default 200k-row sample on a
single core, so dataset construction at HIGGS scale spent most of its time
here.  Built on demand with the system g++; everything degrades to the
pure-Python implementation when a compiler is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _source_path() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(here, "src", "native", "fastbin.cpp")


def _host_tag() -> str:
    """Short hash of this host's CPU capabilities: -march=native builds
    are keyed by it, so a checkout shared across heterogeneous hosts
    (NFS multi-machine training) rebuilds per ISA instead of SIGILLing
    on a foreign host's vectorized .so."""
    import hashlib
    import platform
    raw = platform.machine()
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith("flags"):
                    raw += line
                    break
    except OSError:
        pass
    return hashlib.md5(raw.encode()).hexdigest()[:8]


def _build(src: str, out: str) -> None:
    # -march=native vectorizes the quantizer's compare-count (the 8.8x
    # vs -O2); the output filename carries _host_tag() so the cache
    # never crosses ISAs.  Built under a per-process name and renamed
    # into place: processes racing on a fresh checkout (test workers)
    # must never load a half-written library.
    tmp = f"{out[:-len('.so')]}.{os.getpid()}.tmp.so"
    cmd = ["g++", "-O3", "-march=native", "-fPIC", "-shared",
           "-std=c++17", src, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-500:])
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it on first use; None when unavailable
    (no g++ / read-only tree) — callers fall back to Python."""
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    src = _source_path()
    if not os.path.exists(src):
        return None
    out = os.path.join(os.path.dirname(src),
                       f"libfastbin.{_host_tag()}.so")
    try:
        if (not os.path.exists(out)
                or os.path.getmtime(out) < os.path.getmtime(src)):
            _build(src, out)
        _lib = ctypes.CDLL(out)
        _lib.lgbmtpu_greedy_find_bin.restype = ctypes.c_int64
        _lib.lgbmtpu_greedy_find_bin.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double)]
        _lib.lgbmtpu_values_to_bins.restype = None
        _lib.lgbmtpu_values_to_bins.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32)]
    except Exception as e:  # noqa: BLE001 — binning must keep working
        from ..utils.log import log_warning
        log_warning(f"native fastbin unavailable ({type(e).__name__}: "
                    f"{str(e)[-200:]}); falling back to the (much slower) "
                    f"Python bin-bound loop")
        _lib = None
    return _lib


def greedy_find_bin_native(distinct_values: np.ndarray, counts: np.ndarray,
                           max_bin: int, total_cnt: int,
                           min_data_in_bin: int):
    """Native greedy_find_bin; returns a list of bounds or None when the
    library is unavailable."""
    L = lib()
    if L is None:
        return None
    dv = np.ascontiguousarray(distinct_values, dtype=np.float64)
    ct = np.ascontiguousarray(counts, dtype=np.int64)
    out = np.empty(max(int(max_bin), 1) + 1, dtype=np.float64)
    n = L.lgbmtpu_greedy_find_bin(
        dv.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ct.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(dv), int(max_bin), int(total_cnt), int(min_data_in_bin),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return list(out[:n])


_text_lib: Optional[ctypes.CDLL] = None
_text_tried = False


def text_lib() -> Optional[ctypes.CDLL]:
    """Native LibSVM tokenizer (src/native/textparse.cpp), built on first
    use like fastbin; None -> callers fall back to the Python parser."""
    global _text_lib, _text_tried
    if _text_tried:
        return _text_lib
    _text_tried = True
    src = os.path.join(os.path.dirname(_source_path()), "textparse.cpp")
    if not os.path.exists(src):
        return None
    out = os.path.join(os.path.dirname(src),
                       f"libtextparse.{_host_tag()}.so")
    try:
        if (not os.path.exists(out)
                or os.path.getmtime(out) < os.path.getmtime(src)):
            _build(src, out)
        _text_lib = ctypes.CDLL(out)
        _text_lib.lgbmtpu_libsvm_scan.restype = ctypes.c_int64
        _text_lib.lgbmtpu_libsvm_scan.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
        _text_lib.lgbmtpu_libsvm_fill.restype = ctypes.c_int64
        _text_lib.lgbmtpu_libsvm_fill.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.c_int64]
    except Exception as e:  # noqa: BLE001 — parsing must keep working
        from ..utils.log import log_warning
        log_warning(f"native textparse unavailable ({type(e).__name__}: "
                    f"{str(e)[-200:]}); falling back to the Python "
                    f"LibSVM parser")
        _text_lib = None
    return _text_lib


def parse_libsvm_native(data: bytes):
    """bytes -> dense [n, max_idx + 2] float64 (label in column 0), or
    None when the native tokenizer is unavailable."""
    L = text_lib()
    if L is None:
        return None
    n_rows = ctypes.c_int64(0)
    max_idx = ctypes.c_int64(-1)
    if L.lgbmtpu_libsvm_scan(data, len(data), ctypes.byref(n_rows),
                             ctypes.byref(max_idx)) != 0:
        return None
    out = np.zeros((n_rows.value, max(max_idx.value, -1) + 2),
                   dtype=np.float64)
    filled = L.lgbmtpu_libsvm_fill(
        data, len(data), out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        n_rows.value, out.shape[1])
    if filled != n_rows.value:
        return None
    return out


def _bind_quantize(L) -> bool:
    """Bind the quantizer symbols; False when the loaded .so predates
    them (stale build cache) — callers fall back to Python."""
    if getattr(L, "_quantize_bound", None) is not None:
        return L._quantize_bound
    try:
        L.lgbmtpu_quantize_rows
        L.lgbmtpu_quantize_rows_f32
    except AttributeError:
        L._quantize_bound = False
        return False
    L.lgbmtpu_quantize_rows.restype = None
    L.lgbmtpu_quantize_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64, ctypes.c_void_p]
    L.lgbmtpu_quantize_rows_f32.restype = None
    L.lgbmtpu_quantize_rows_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_void_p]
    L._quantize_bound = True
    return True


# threads the whole-matrix quantizer spreads a table of over 256 MB on
_QUANTIZE_THREADS = 4


def quantize_rows_native(data: np.ndarray, feat_idx, mappers,
                         out_dtype) -> Optional[np.ndarray]:
    """One native pass quantizing every NUMERICAL used column of a
    row-major float matrix (core/binning.value_to_bin semantics); None
    when unavailable or any column is categorical (caller falls back).

    ~10x the per-column numpy path at 10M rows: no strided column
    copies, bounds stay in cache, and the output is written once.
    """
    from .binning import BIN_TYPE_NUMERICAL
    L = lib()
    if L is None:
        return None
    if data.dtype == np.float32:
        is_f64 = 0
    elif data.dtype == np.float64:
        is_f64 = 1
    else:
        return None
    if any(mappers[f].bin_type != BIN_TYPE_NUMERICAL for f in feat_idx):
        return None
    if not _bind_quantize(L):
        return None
    # contiguity copy LAST: it is only worth the memory once the native
    # path is certain to run
    if not data.flags.c_contiguous:
        data = np.ascontiguousarray(data)
    n, f_total = data.shape
    n_used = len(feat_idx)
    bounds = []
    offs = np.zeros(n_used + 1, dtype=np.int64)
    mt = np.zeros(n_used, dtype=np.int32)
    nb = np.zeros(n_used, dtype=np.int32)
    for j, f in enumerate(feat_idx):
        m = mappers[f]
        n_search = m.num_bin - (1 if m.missing_type == 2 else 0)
        ub = np.asarray(m.bin_upper_bound,
                        dtype=np.float64)[:max(n_search - 1, 0)]
        bounds.append(ub)
        offs[j + 1] = offs[j] + len(ub)
        mt[j] = m.missing_type
        nb[j] = m.num_bin
    flat = (np.concatenate(bounds) if bounds
            else np.zeros(0, np.float64))
    fidx = np.asarray(feat_idx, dtype=np.int64)
    out = np.empty((n, n_used), dtype=out_dtype)
    max_nb = int(np.max(offs[1:] - offs[:-1], initial=0))

    def over_row_ranges(call):
        """``call(rows_in, n_rows, rows_out)`` over ranges of whole rows,
        on a few threads where the table is large (rows are independent
        and ctypes releases the GIL; the bytes written are the same)."""
        workers = min(_QUANTIZE_THREADS, os.cpu_count() or 1,
                      max(1, data.nbytes >> 28))
        if workers <= 1:
            call(data, n, out)
            return
        step = -(-n // workers)
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(workers) as pool:
            list(pool.map(lambda a: call(data[a:a + step],
                                         min(step, n - a), out[a:a + step]),
                          range(0, n, step)))

    if is_f64 == 0 and out_dtype == np.uint8 and max_nb <= 128:
        # f32 fast path with EXACT thresholds: t[b] = smallest float
        # whose f64 value is > ub[b]; then ub[b] < (double)v  <=>
        # v >= t[b] because v's f64 image is exact and t[b] is the
        # least representable value past the bound
        t = flat.astype(np.float32)
        not_past = t.astype(np.float64) <= flat
        t = np.where(not_past, np.nextafter(t, np.float32(np.inf)), t)
        t = np.ascontiguousarray(t, dtype=np.float32)
        over_row_ranges(lambda rows, n_rows, dst: L.lgbmtpu_quantize_rows_f32(
            rows.ctypes.data_as(ctypes.c_void_p), n_rows, f_total,
            fidx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n_used,
            t.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            mt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            nb.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.c_void_p)))
        return out
    over_row_ranges(lambda rows, n_rows, dst: L.lgbmtpu_quantize_rows(
        rows.ctypes.data_as(ctypes.c_void_p), is_f64, n_rows, f_total,
        fidx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n_used,
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        mt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        nb.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        1 if out_dtype == np.uint16 else 0,
        dst.ctypes.data_as(ctypes.c_void_p)))
    return out
