"""Loader for the native runtime library (``sparse_tpu/src/sparse_tpu_native.cc``).

Reference analog: ``sparse/config.py:21-58`` (``LegateSparseLib`` loading
``liblegate_sparse.so`` and exposing its C ABI through CFFI). Here the native
surface is small — host-side work outside the XLA compute path (bitset BFS
expansion, MatrixMarket tokenizing) — and is bound with ctypes. The library
is compiled on first use with g++ -O3 (no -march=native: the binary must
load on whatever machine a copy of this tree lands on) into the package
directory; every
caller must handle ``lib() is None`` (pure-numpy fallback), so missing
toolchains degrade gracefully.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_lock = threading.Lock()
_lib = None
_tried = False

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
# source ships as package data so pip-installed copies can rebuild the
# native library for the local toolchain
_SRC = os.path.join(_PKG_DIR, "src", "sparse_tpu_native.cc")
_SO = os.path.join(_PKG_DIR, "_sparse_tpu_native.so")


def _build() -> str | None:
    if not os.path.exists(_SRC):
        return None
    if os.path.exists(_SO) and os.path.getmtime(_SO) >= os.path.getmtime(_SRC):
        return _SO
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", _SO, _SRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return _SO
    except (OSError, subprocess.SubprocessError):
        return None


def lib():
    """The loaded CDLL, or None when no native library is available."""
    global _lib, _tried
    if _tried:
        return _lib
    with _lock:
        if _tried:
            return _lib
        path = os.environ.get("SPARSE_TPU_NATIVE_LIB") or _build()
        if path and os.path.exists(path):
            try:
                cdll = ctypes.CDLL(path)
                _declare(cdll)
                _lib = cdll
            except (OSError, AttributeError):
                # AttributeError: an older library (e.g. via
                # SPARSE_TPU_NATIVE_LIB) missing newer symbols — keep the
                # documented None fallback instead of crashing callers
                _lib = None
        _tried = True
    return _lib


def _declare(cdll) -> None:
    i64 = ctypes.c_int64
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    cdll.ind_sets_count.restype = i64
    cdll.ind_sets_count.argtypes = [u64p, i64, i64]
    cdll.ind_sets_expand.restype = None
    cdll.ind_sets_expand.argtypes = [u64p, u64p, u64p, i64, i64, i64, u64p, u64p]
    cdll.mtx_parse_body.restype = i64
    cdll.mtx_parse_body.argtypes = [
        ctypes.c_char_p, i64, i64, ctypes.c_int32, i64p, i64p, f64p, f64p,
    ]
    cdll.mtx_parse_dense.restype = i64
    cdll.mtx_parse_dense.argtypes = [ctypes.c_char_p, i64, i64, f64p]
    cdll.spgemm_count.restype = i64
    cdll.spgemm_count.argtypes = [i64, i64, i64p, i64p, i64p, i64p, i64p]
    cdll.spgemm_fill.restype = None
    cdll.spgemm_fill.argtypes = [
        i64, i64, i64p, i64p, f64p, i64p, i64p, f64p, i64p, i64p, f64p,
    ]
    cdll.ilu0_csr.restype = i64
    cdll.ilu0_csr.argtypes = [i64, i64p, i64p, f64p]
    cdll.ic0_csr.restype = i64
    cdll.ic0_csr.argtypes = [i64, i64p, i64p, f64p]
    cdll.splu_factor.restype = ctypes.c_void_p
    cdll.splu_factor.argtypes = [i64, i64p, i64p, f64p, i64p]
    cdll.ilut_factor.restype = ctypes.c_void_p
    cdll.ilut_factor.argtypes = [
        i64, i64p, i64p, f64p, ctypes.c_double, i64, i64p,
    ]
    cdll.splu_lnnz.restype = i64
    cdll.splu_lnnz.argtypes = [ctypes.c_void_p]
    cdll.splu_unnz.restype = i64
    cdll.splu_unnz.argtypes = [ctypes.c_void_p]
    cdll.splu_get.restype = None
    cdll.splu_get.argtypes = [
        ctypes.c_void_p, i64p, i64p, f64p, i64p, i64p, f64p, i64p,
    ]
    cdll.splu_free.restype = None
    cdll.splu_free.argtypes = [ctypes.c_void_p]


def _as_u64p(a):
    import numpy as np

    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def expand_level(sets, queues, comp_gt, n):
    """Native BFS level expansion; raises if the library is unavailable."""
    import numpy as np

    L = lib()
    if L is None:
        raise RuntimeError("native library unavailable")
    S, W = queues.shape
    sets = np.ascontiguousarray(sets)
    queues = np.ascontiguousarray(queues)
    comp_gt = np.ascontiguousarray(comp_gt)
    count = L.ind_sets_count(_as_u64p(queues), S, W)
    new_sets = np.empty((count, W), dtype=np.uint64)
    new_queues = np.empty((count, W), dtype=np.uint64)
    L.ind_sets_expand(
        _as_u64p(sets), _as_u64p(queues), _as_u64p(comp_gt),
        S, W, n, _as_u64p(new_sets), _as_u64p(new_queues),
    )
    return new_sets, new_queues


def parse_mtx_body(body: bytes, nnz: int, kind: int):
    """Native coordinate-body parse -> (rows, cols, re, im) or None.

    Parses with room for one extra entry so a body that declares nnz entries
    but holds more is rejected (matching the numpy fallback) instead of
    silently truncated.
    """
    import numpy as np

    L = lib()
    if L is None:
        return None
    cap = nnz + 1
    rows = np.empty(cap, dtype=np.int64)
    cols = np.empty(cap, dtype=np.int64)
    re = np.empty(cap, dtype=np.float64)
    im = np.zeros(cap, dtype=np.float64)
    got = L.mtx_parse_body(
        body, len(body), cap, kind,
        rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        cols.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        re.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        im.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
    )
    if got != nnz:
        return None  # wrong entry count: caller raises the clear error
    return rows[:nnz], cols[:nnz], re[:nnz], im[:nnz]


def parse_mtx_dense(body: bytes, count: int):
    import numpy as np

    L = lib()
    if L is None:
        return None
    out = np.empty(count, dtype=np.float64)
    got = L.mtx_parse_dense(
        body, len(body), count,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
    )
    if got != count:
        return None
    return out


def _as_i64p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _as_f64p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def spgemm_host(Ap, Aj, Ax, Bp, Bj, Bx, m: int, n: int):
    """Native 2-pass Gustavson C = A @ B on host arrays (the reference's
    CPU SpGEMM task pair, src/sparse/array/csr/spgemm_csr_csr_csr.cc).

    Inputs are numpy-coercible CSR parts; values are computed in f64 and
    the caller casts back. Returns (indptr, indices, data) as numpy
    int64/int64/float64, canonical (sorted, deduplicated) — or None when
    the native library is unavailable.
    """
    import numpy as np

    L = lib()
    if L is None:
        return None
    Ap = np.ascontiguousarray(Ap, dtype=np.int64)
    Aj = np.ascontiguousarray(Aj, dtype=np.int64)
    Ax = np.ascontiguousarray(Ax, dtype=np.float64)
    Bp = np.ascontiguousarray(Bp, dtype=np.int64)
    Bj = np.ascontiguousarray(Bj, dtype=np.int64)
    Bx = np.ascontiguousarray(Bx, dtype=np.float64)
    Cp = np.empty(m + 1, dtype=np.int64)
    nnz = L.spgemm_count(m, n, _as_i64p(Ap), _as_i64p(Aj),
                         _as_i64p(Bp), _as_i64p(Bj), _as_i64p(Cp))
    Cj = np.empty(nnz, dtype=np.int64)
    Cx = np.empty(nnz, dtype=np.float64)
    L.spgemm_fill(m, n, _as_i64p(Ap), _as_i64p(Aj), _as_f64p(Ax),
                  _as_i64p(Bp), _as_i64p(Bj), _as_f64p(Bx),
                  _as_i64p(Cp), _as_i64p(Cj), _as_f64p(Cx))
    return Cp, Cj, Cx


def ilu0_host(indptr, indices, data, n: int):
    """In-place-style ILU(0) on canonical CSR host arrays (f64).

    Returns the factored data array (L strict-lower with implicit unit
    diagonal + U upper, on A's pattern), falling back to a pure-numpy
    row loop when the native library is unavailable. Raises
    ``RuntimeError`` on a missing structural diagonal or zero pivot.
    """
    import numpy as np

    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    out = np.array(data, dtype=np.float64, copy=True)
    L = lib()
    if L is not None:
        rc = L.ilu0_csr(n, _as_i64p(indptr), _as_i64p(indices), _as_f64p(out))
        if rc != 0:
            raise RuntimeError(
                f"ILU(0): zero/missing pivot at row {-rc - 1}"
            )
        return out
    # numpy fallback: same IKJ recurrence, python row loop (setup-phase
    # only; fine to ~1e5 rows — the native path covers the big cases)
    diag = np.full(n, -1, dtype=np.int64)
    for i in range(n):
        seg = indices[indptr[i]:indptr[i + 1]]
        d = np.nonzero(seg == i)[0]
        if d.size == 0:
            raise RuntimeError(f"ILU(0): zero/missing pivot at row {i}")
        diag[i] = indptr[i] + d[0]
    pos = np.full(n, -1, dtype=np.int64)
    for i in range(n):
        p0, p1 = indptr[i], indptr[i + 1]
        pos[indices[p0:p1]] = np.arange(p0, p1)
        for p in range(p0, p1):
            k = indices[p]
            if k >= i:
                break
            ukk = out[diag[k]]
            if ukk == 0.0:
                raise RuntimeError(f"ILU(0): zero/missing pivot at row {k}")
            lik = out[p] / ukk
            out[p] = lik
            q0, q1 = diag[k] + 1, indptr[k + 1]
            pj = pos[indices[q0:q1]]
            ok = pj >= 0
            out[pj[ok]] -= lik * out[q0:q1][ok]
        pos[indices[p0:p1]] = -1
        if out[diag[i]] == 0.0:
            raise RuntimeError(f"ILU(0): zero/missing pivot at row {i}")
    return out


def ic0_host(indptr, indices, data, n: int):
    """IC(0) on the lower-triangular CSR of an SPD matrix (diagonal last
    per row). Returns L's data with A ~= L @ L.T on the lower pattern;
    numpy fallback mirrors the native kernel. Raises ``RuntimeError`` on
    a non-positive pivot (not SPD enough for IC(0))."""
    import numpy as np

    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    out = np.array(data, dtype=np.float64, copy=True)
    L = lib()
    if L is not None:
        rc = L.ic0_csr(n, _as_i64p(indptr), _as_i64p(indices), _as_f64p(out))
        if rc != 0:
            raise RuntimeError(
                f"IC(0): non-positive/missing pivot at row {-rc - 1}"
            )
        return out
    for i in range(n):
        p0, p1 = indptr[i], indptr[i + 1]
        if p1 <= p0 or indices[p1 - 1] != i:
            raise RuntimeError(f"IC(0): non-positive/missing pivot at row {i}")
        for p in range(p0, p1):
            j = indices[p]
            a, b = p0, indptr[j]
            b1 = indptr[j + 1] - 1
            s = 0.0
            while a < p and b < b1:
                ca, cb = indices[a], indices[b]
                if ca == cb:
                    s += out[a] * out[b]
                    a += 1
                    b += 1
                elif ca < cb:
                    a += 1
                else:
                    b += 1
            if j < i:
                ljj = out[indptr[j + 1] - 1]
                if ljj == 0.0:
                    raise RuntimeError(
                        f"IC(0): non-positive/missing pivot at row {j}"
                    )
                out[p] = (out[p] - s) / ljj
            else:
                v = out[p] - s
                if v <= 0.0:
                    raise RuntimeError(
                        f"IC(0): non-positive/missing pivot at row {i}"
                    )
                out[p] = v ** 0.5
    return out


def _lu_extract(L, h, n: int):
    """Copy a factor handle's CSC parts out and free it."""
    import numpy as np

    try:
        lnnz = L.splu_lnnz(h)
        unnz = L.splu_unnz(h)
        Lp = np.empty(n + 1, dtype=np.int64)
        Li = np.empty(max(lnnz, 1), dtype=np.int64)
        Lx = np.empty(max(lnnz, 1), dtype=np.float64)
        Up = np.empty(n + 1, dtype=np.int64)
        Ui = np.empty(max(unnz, 1), dtype=np.int64)
        Ux = np.empty(max(unnz, 1), dtype=np.float64)
        perm = np.empty(n, dtype=np.int64)
        L.splu_get(h, _as_i64p(Lp), _as_i64p(Li), _as_f64p(Lx),
                   _as_i64p(Up), _as_i64p(Ui), _as_f64p(Ux), _as_i64p(perm))
    finally:
        L.splu_free(h)
    return Lp, Li[:lnnz], Lx[:lnnz], Up, Ui[:unnz], Ux[:unnz], perm


def ilut_host(indptr, indices, data, n: int, droptol: float, lfil: int):
    """ILUT(p, tau) on host CSC arrays via the Gilbert-Peierls core: drop
    |entry| < droptol * ||A(:,j)||_2 (pivot kept), keep the ``lfil``
    largest per column in each of L and off-diagonal U (0 = unlimited).
    Same return contract as :func:`splu_host`; ``None`` without the
    native library. Reference analog: scipy's SuperLU ILUT behind
    ``spilu(drop_tol, fill_factor)``.
    """
    import numpy as np

    L = lib()
    if L is None:
        return None
    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    data = np.ascontiguousarray(data, dtype=np.float64)
    info = np.zeros(1, dtype=np.int64)
    h = L.ilut_factor(n, _as_i64p(indptr), _as_i64p(indices),
                      _as_f64p(data), float(droptol), int(lfil),
                      _as_i64p(info))
    if not h:
        raise RuntimeError(
            f"ilut: matrix is singular (column {-int(info[0]) - 1})"
        )
    return _lu_extract(L, h, n)


def splu_host(indptr, indices, data, n: int):
    """Sparse LU with partial pivoting on host CSC arrays: P A = L U.

    Gilbert-Peierls left-looking factorization (native C++; reference
    analog: the vendor/scipy factorizations behind the reference's direct
    solves). Inputs are the CSC parts of a square A; values factor in
    f64. Returns ``(Lp, Li, Lx, Up, Ui, Ux, perm)`` — L unit-lower
    (implicit diagonal) and U upper, both CSC over pivot row ids, with
    ``perm[k]`` the original row chosen as pivot k — or ``None`` when the
    native library is unavailable (callers keep their dense path).
    Raises ``RuntimeError`` on a singular column.
    """
    import numpy as np

    L = lib()
    if L is None:
        return None
    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    data = np.ascontiguousarray(data, dtype=np.float64)
    info = np.zeros(1, dtype=np.int64)
    h = L.splu_factor(n, _as_i64p(indptr), _as_i64p(indices),
                      _as_f64p(data), _as_i64p(info))
    if not h:
        raise RuntimeError(
            f"splu: matrix is singular (column {-int(info[0]) - 1})"
        )
    return _lu_extract(L, h, n)
