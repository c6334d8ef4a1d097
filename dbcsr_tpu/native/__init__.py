"""Native (C++) host index engine with lazy build + ctypes binding.

Build-on-first-use: compiles `index_engine.cpp` with g++ (-O3 -fopenmp)
into the package directory.  Every entry point has a NumPy fallback, so
the library is optional; set ``DBCSR_TPU_NATIVE=0`` to force Python.
This plays the role of the reference's compiled host machinery (the
Fortran index kernels under src/mm + src/block).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_SRCS = [
    os.path.join(os.path.dirname(__file__), "index_engine.cpp"),
    os.path.join(os.path.dirname(__file__), "host_smm.cpp"),
]


def _isa_tag() -> str:
    """CPU-capability + SOURCE tag baked into the .so filename: the
    build uses -march=native, so a binary cached on a shared filesystem
    must never be loaded by a rank on a CPU with different ISA
    extensions (SIGILL is not catchable), and a cached binary must
    never shadow edited sources.  Different flags or sources ->
    different file -> rebuild."""
    import hashlib

    h = hashlib.sha1()
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    h.update(line.encode())
                    break
    except OSError:
        h.update(b"generic")
    for src in _SRCS:
        try:
            with open(src, "rb") as fh:
                h.update(fh.read())
        except OSError:
            pass
    return h.hexdigest()[:8]


_SO = os.path.join(os.path.dirname(__file__),
                   f"libdbcsr_index.{_isa_tag()}.so")


def _build() -> Optional[str]:
    # compile to a process-private temp path, then rename atomically so
    # concurrent ranks never load a partially written .so
    tmp = f"{_SO}.{os.getpid()}.tmp"
    base = ["g++", "-O3", "-fPIC", "-shared", *_SRCS, "-o", tmp]
    cmds = [  # prefer vectorized + OpenMP, degrade gracefully
        base[:2] + ["-march=native", "-fopenmp"] + base[2:],
        base[:2] + ["-fopenmp"] + base[2:],
        base,
    ]
    for cmd in cmds:
        try:
            r = subprocess.run(cmd, capture_output=True, timeout=120)
            if r.returncode == 0:
                os.replace(tmp, _SO)
                return _SO
        except (OSError, subprocess.TimeoutExpired):
            continue
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return None


def _fresh() -> bool:
    try:
        return os.path.getmtime(_SO) >= max(map(os.path.getmtime, _SRCS))
    except OSError:
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, (re)building it when the source is
    newer than the shared object; None if unavailable or disabled."""
    global _LIB, _TRIED
    if os.environ.get("DBCSR_TPU_NATIVE", "1") == "0":
        return None
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        if _fresh():
            so = _SO
        else:
            # g++ on both sources: seconds, once per checkout and CPU
            from dbcsr_tpu.core.timings import booked

            with booked("native_build"):
                so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        f32p = ctypes.POINTER(ctypes.c_float)
        try:
            lib.dbcsr_symbolic_product.restype = ctypes.c_int64
            lib.dbcsr_symbolic_product.argtypes = [
                i64p, ctypes.c_int64, i32p, i64p, i32p,
                f32p, f32p, f32p, ctypes.c_int32,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, i64p, i64p, i64p, i64p,
            ]
            lib.dbcsr_coo_fill_blocks.restype = None
            lib.dbcsr_coo_fill_blocks.argtypes = [
                ctypes.c_int64, i64p, i64p, i64p,
                ctypes.c_void_p, ctypes.c_int64, i64p, i64p, ctypes.c_void_p,
            ]
            lib.dbcsr_group_sort_stacks.restype = None
            lib.dbcsr_group_sort_stacks.argtypes = [
                ctypes.c_int64, i64p, ctypes.c_int64, i32p, i64p, i64p, i64p,
            ]
            lib.dbcsr_host_smm.restype = ctypes.c_int32
            lib.dbcsr_host_smm.argtypes = [
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, i32p, i32p, i32p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_double, ctypes.c_double,
            ]
        except AttributeError:
            # stale library missing an expected symbol -> NumPy fallback
            return None
        _LIB = lib
        return _LIB


def _i64(a):
    return np.ascontiguousarray(a, np.int64)


def _ptr(a, typ):
    return a.ctypes.data_as(ctypes.POINTER(typ)) if a is not None else None


def symbolic_product(
    a_row_ptr, a_cols, b_row_ptr, b_cols,
    a_norms2=None, b_norms2=None, row_eps2=None,
    sym_c=False, fr=None, lr=None, fc=None, lc=None, fk=None, lk=None,
):
    """Native candidate expansion; returns (i, j, a_ent, b_ent) or None
    when the native library is unavailable (caller falls back)."""
    lib = get_lib()
    if lib is None:
        return None
    a_row_ptr = _i64(a_row_ptr)
    b_row_ptr = _i64(b_row_ptr)
    a_cols = np.ascontiguousarray(a_cols, np.int32)
    b_cols = np.ascontiguousarray(b_cols, np.int32)
    norms = [
        np.ascontiguousarray(x, np.float32) if x is not None else None
        for x in (a_norms2, b_norms2, row_eps2)
    ]
    if any(x is None for x in norms):
        norms = [None, None, None]
    lim = [(-1 if v is None else int(v)) for v in (fr, lr, fc, lc, fk, lk)]
    nrows = len(a_row_ptr) - 1
    args_common = (
        _ptr(a_row_ptr, ctypes.c_int64), nrows, _ptr(a_cols, ctypes.c_int32),
        _ptr(b_row_ptr, ctypes.c_int64), _ptr(b_cols, ctypes.c_int32),
        _ptr(norms[0], ctypes.c_float), _ptr(norms[1], ctypes.c_float),
        _ptr(norms[2], ctypes.c_float), int(bool(sym_c)), *lim,
    )
    n = lib.dbcsr_symbolic_product(*args_common, 0, None, None, None, None)
    out_i = np.empty(n, np.int64)
    out_j = np.empty(n, np.int64)
    out_a = np.empty(n, np.int64)
    out_b = np.empty(n, np.int64)
    wrote = lib.dbcsr_symbolic_product(
        *args_common, n,
        _ptr(out_i, ctypes.c_int64), _ptr(out_j, ctypes.c_int64),
        _ptr(out_a, ctypes.c_int64), _ptr(out_b, ctypes.c_int64),
    )
    assert wrote == n, (wrote, n)
    return out_i, out_j, out_a, out_b


def group_sort_stacks(group, ngroups, c_slot, a_ent):
    """Native stack ordering: permutation sorted by (group, c_slot,
    a_ent) plus group boundaries; None -> caller falls back to lexsort."""
    lib = get_lib()
    if lib is None:
        return None
    group = _i64(group)
    c_slot = np.ascontiguousarray(c_slot, np.int32)
    a_ent = _i64(a_ent)
    n = len(group)
    if n and not (0 <= group.min() and group.max() < ngroups):
        raise ValueError("group ids out of [0, ngroups) — would corrupt memory")
    order = np.empty(n, np.int64)
    bounds = np.empty(ngroups + 1, np.int64)
    lib.dbcsr_group_sort_stacks(
        n, _ptr(group, ctypes.c_int64), int(ngroups),
        _ptr(c_slot, ctypes.c_int32), _ptr(a_ent, ctypes.c_int64),
        _ptr(order, ctypes.c_int64), _ptr(bounds, ctypes.c_int64),
    )
    return order, bounds


def coo_fill_blocks(blk_of_entry, local_row, local_col, values,
                    blk_buf_offset, blk_ncols, out_flat) -> bool:
    """Native element scatter into block buffers; False -> caller falls
    back to the Python loop."""
    lib = get_lib()
    if lib is None:
        return False
    values = np.ascontiguousarray(values)
    lib.dbcsr_coo_fill_blocks(
        len(values),
        _ptr(_i64(blk_of_entry), ctypes.c_int64),
        _ptr(_i64(local_row), ctypes.c_int64),
        _ptr(_i64(local_col), ctypes.c_int64),
        values.ctypes.data_as(ctypes.c_void_p),
        values.dtype.itemsize,
        _ptr(_i64(blk_buf_offset), ctypes.c_int64),
        _ptr(_i64(blk_ncols), ctypes.c_int64),
        out_flat.ctypes.data_as(ctypes.c_void_p),
    )
    return True


def host_smm(c_np, a_np, b_np, ai, bi, ci, alpha) -> bool:
    """Native host stack processing: ``c[ci] += alpha * a[ai] @ b[bi]``
    in-place over a sorted param stack (the reference's CPU stack driver,
    `dbcsr_mm_hostdrv.F:90` / tools/build_libsmm).  ``c_np`` must be a
    writable contiguous array; returns False when the native library is
    unavailable or the dtype is unsupported (caller falls back)."""
    lib = get_lib()
    if lib is None:
        return False
    from dbcsr_tpu.core import kinds

    try:
        code = kinds.enum_of(c_np.dtype)
    except KeyError:
        return False
    if not (c_np.flags.c_contiguous and c_np.flags.writeable):
        raise ValueError("c_np must be C-contiguous and writable")
    a_np = np.ascontiguousarray(a_np)
    b_np = np.ascontiguousarray(b_np)
    if a_np.dtype != c_np.dtype or b_np.dtype != c_np.dtype:
        return False  # the C++ kernel reinterprets raw pointers by code
    ai = np.ascontiguousarray(ai, np.int32)
    bi = np.ascontiguousarray(bi, np.int32)
    ci = np.ascontiguousarray(ci, np.int32)
    alpha = complex(alpha)
    m, k = a_np.shape[1], a_np.shape[2]
    n = b_np.shape[2]
    rc = lib.dbcsr_host_smm(
        code,
        c_np.ctypes.data_as(ctypes.c_void_p),
        a_np.ctypes.data_as(ctypes.c_void_p),
        b_np.ctypes.data_as(ctypes.c_void_p),
        _ptr(ai, ctypes.c_int32), _ptr(bi, ctypes.c_int32),
        _ptr(ci, ctypes.c_int32), len(ai), m, n, k,
        alpha.real, alpha.imag,
    )
    return rc == 0


def sort_order(group, ngroups, c_slot, a_ent, return_bounds: bool = False):
    """Permutation sorting stack entries by (group, c_slot, a_ent) —
    native when available, `np.lexsort` otherwise.  The ONE place the
    sort-key contract (bit-reproducible stack order) lives; the
    single-chip stack builder and the mesh `_fill_stacks` both use it.
    ``return_bounds`` also returns the ngroups+1 group boundaries."""
    ns = group_sort_stacks(group, ngroups, c_slot, a_ent)
    if ns is not None:
        return ns if return_bounds else ns[0]
    order = np.lexsort((a_ent, c_slot, group))
    if not return_bounds:
        return order
    counts = np.bincount(np.ascontiguousarray(group, np.int64),
                         minlength=ngroups)
    bounds = np.empty(ngroups + 1, np.int64)
    bounds[0] = 0
    np.cumsum(counts, out=bounds[1:])
    return order, bounds
