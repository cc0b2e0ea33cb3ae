"""DIA (diagonal) sparse array.

Reference analog: ``sparse/dia.py`` (class at dia.py:65; vectorized DIA->CSC
conversion dia.py:222-249; transpose dia.py:178). Layout matches scipy:
``data[k, j]`` holds ``A[j - offsets[k], j]`` (column-indexed diagonals).

TPU note: DIA -> other formats is a fully dense-shaped masked gather (one
[n_diags, L] plane) followed by one compaction — no per-diagonal loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .base import SparseArray
from .utils import asjnp, host_int


@jax.tree_util.register_pytree_node_class
class dia_array(SparseArray):
    format = "dia"

    def __init__(self, arg, shape=None, dtype=None, copy=False):
        if isinstance(arg, dia_array):
            data, offsets, shape = arg.data, arg.offsets, arg.shape
        elif isinstance(arg, tuple) and len(arg) == 2 and not np.isscalar(arg[0]):
            data, offsets = arg
            data = asjnp(data)
            offsets = np.atleast_1d(np.asarray(offsets, dtype=np.int64))
            if shape is None:
                raise ValueError("dia_array((data, offsets)) requires shape=")
        elif isinstance(arg, SparseArray) or hasattr(arg, "tocoo"):
            c = arg.tocoo()
            data, offsets, shape = _coo_to_dia(c)
        else:
            d = asjnp(arg)
            from .coo import coo_array

            c = coo_array(d)
            data, offsets, shape = _coo_to_dia(c)
        if dtype is not None:
            data = data.astype(dtype)
        self.data = asjnp(data)
        # offsets stay on host: they define static structure (like shapes)
        self.offsets = np.asarray(offsets, dtype=np.int64)
        self._shape = (int(shape[0]), int(shape[1]))
        self._dtype = np.dtype(self.data.dtype)

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.data,), (tuple(self.offsets.tolist()), self._shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        offsets, shape = aux
        obj = object.__new__(cls)
        obj.data = children[0]
        obj.offsets = np.asarray(offsets, dtype=np.int64)
        obj._shape = shape
        obj._dtype = np.dtype(obj.data.dtype)
        return obj

    # ----------------------------------------------------------------------
    @property
    def nnz(self) -> int:
        """Count of stored entries that fall inside the matrix bounds."""
        m, n = self.shape
        L = self.data.shape[1]
        total = 0
        for off in self.offsets:
            lo = max(0, off)
            hi = min(n, m + off, L)
            total += max(0, int(hi - lo))
        return total

    def _data_array(self):
        return self.data

    def _with_data(self, data):
        return dia_array((data, self.offsets), shape=self.shape)

    # -- conversions -------------------------------------------------------
    def tocoo(self):
        from .coo import coo_array

        m, n = self.shape
        nd, L = self.data.shape
        cols = jnp.arange(L, dtype=jnp.int32)[None, :].repeat(nd, axis=0)
        rows = cols - jnp.asarray(self.offsets, dtype=jnp.int32)[:, None]
        valid = (rows >= 0) & (rows < m) & (cols < n) & (self.data != 0)
        cnt = host_int(valid.sum())
        take = jnp.nonzero(valid.ravel(), size=cnt)[0]
        out = coo_array(
            (
                self.data.ravel()[take],
                (rows.ravel()[take], cols.ravel()[take]),
            ),
            shape=self.shape,
        )
        # one slot per (diagonal, column): duplicate-free by construction
        # (diagonal-major order though — not scipy-canonical)
        out._duplicate_free = True
        return out

    def _direct_parts(self, by_row: bool):
        """Sort-FREE host conversion to CSR (by_row) or CSC parts.

        DIA is already ordered: within a row, entries at ascending
        offsets have ascending columns (col = row + offset); within a
        column, entries at DESCENDING offsets have ascending rows
        (row = col - offset). So both compressed forms fall out of a
        masked transpose — no 20M-entry sort (the COO route cost 35 s
        at 2000^2 on the CPU backend; this is milliseconds). Matches
        the reference's vectorized conversion (dia.py:222-249) in
        spirit, minus its sort. Returns (indptr, indices, data) numpy.
        """
        from .types import index_dtype_for

        m, n = self.shape
        data = np.asarray(self.data)
        offsets = np.asarray(self.offsets)
        nd, L = data.shape
        if by_row:
            order = np.argsort(offsets, kind="stable")
            d = offsets[order][:, None]                  # [D, 1]
            i = np.arange(m)[None, :]                    # [1, m]
            pos = i + d                                  # columns; also the
            lines = m                                    # data column index
        else:
            order = np.argsort(-offsets, kind="stable")
            d = offsets[order][:, None]
            j = np.arange(n)[None, :]
            pos = j - d                                  # rows
            lines = n
        # value source: data[k, column]; column is pos (by_row) or j (csc)
        src = pos if by_row else np.broadcast_to(
            np.arange(n)[None, :], pos.shape
        )
        valid = (pos >= 0) & (pos < (n if by_row else m)) & (src < L)
        gathered = np.take_along_axis(
            data[order], np.clip(src, 0, max(L - 1, 0)), axis=1
        )
        valid &= gathered != 0
        validT = valid.T                                 # [lines, D]
        indices = pos.T[validT]
        vals = gathered.T[validT]
        idt = index_dtype_for(self.shape, len(vals))
        counts = valid.sum(axis=0)  # one count per line (row/column)
        indptr = np.zeros(lines + 1, dtype=idt)
        indptr[1:] = np.cumsum(counts).astype(idt)
        return indptr, indices.astype(idt), vals

    def tocsr(self):
        from .utils import in_trace

        if in_trace():
            return self.tocoo().tocsr()
        from .csr import csr_array

        indptr, indices, vals = self._direct_parts(by_row=True)
        return csr_array.from_parts(vals, indices, indptr, self.shape)

    def tocsc(self):
        """Reference fast path dia.py:222-249 — here fully sort-free."""
        from .utils import in_trace

        if in_trace():
            return self.tocoo().tocsc()
        from .csc import csc_array

        indptr, indices, vals = self._direct_parts(by_row=False)
        return csc_array.from_parts(vals, indices, indptr, self.shape)

    def todia(self):
        return self

    def toarray(self):
        return self.tocoo().toarray()

    def transpose(self, axes=None):
        """offsets -> -offsets with a per-diagonal shift (dia.py:178)."""
        if axes is not None:
            raise ValueError("transpose with axes != None is unsupported")
        m, n = self.shape
        L = self.data.shape[1]
        Lt = max(m, L)
        nd = self.data.shape[0]
        # dataT[k, j] = data[k, j + offsets[k]] on the transposed shape (n, m)
        j = jnp.arange(Lt, dtype=jnp.int32)[None, :]
        src = j + jnp.asarray(self.offsets, dtype=jnp.int32)[:, None]
        ok = (src >= 0) & (src < L)
        src_c = jnp.clip(src, 0, L - 1)
        gathered = self.data[jnp.arange(nd)[:, None], src_c]
        dataT = jnp.where(ok, gathered, jnp.zeros((), dtype=self.data.dtype))
        return dia_array((dataT, -self.offsets), shape=(n, m))

    @property
    def T(self):
        return self.transpose()

    # -- arithmetic --------------------------------------------------------
    def dot(self, other):
        """SpMV stays in DIA: the diagonal layout needs no gathers at all
        (ops.dia_spmv — shifted vector adds). Everything else routes
        through CSR."""
        x = other
        if not isinstance(x, SparseArray):
            x = asjnp(x)
            # fast path requires scipy-width data planes (data.shape[1] == n);
            # transpose of a non-square matrix can leave wider planes
            if (
                x.ndim == 1
                and x.shape[0] == self.shape[1]
                and self.data.shape[1] == self.shape[1]
            ):
                from .config import settings

                offs = tuple(int(o) for o in self.offsets)
                if settings.spmv_mode == "pallas":
                    from .kernels.dia_spmv import cached_prepared_spmv

                    y = cached_prepared_spmv(
                        self, "_prepared", self.data, offs, self.shape, x
                    )
                    if y is not None:  # None: band too wide for VMEM
                        return y
                from .ops.dia_spmv import dia_spmv_xla

                return dia_spmv_xla(self.data, offs, x, self.shape)
        return self.tocsr().dot(other)

    def _rdot(self, other):
        return self.tocsr()._rdot(other)

    def __add__(self, other):
        return self.tocsr() + other

    def __mul__(self, other):
        if np.isscalar(other) or getattr(other, "ndim", 1) == 0:
            return self._with_data(self.data * other)
        return self.tocsr().multiply(other)

    def multiply(self, other):
        return self.__mul__(other)

    def sum(self, axis=None):
        return self.tocsr().sum(axis=axis)

    def diagonal(self, k=0):
        m, n = self.shape
        out_len = min(m + min(k, 0), n - max(k, 0))
        if out_len <= 0:
            return jnp.zeros((0,), dtype=self.dtype)
        hits = np.nonzero(self.offsets == k)[0]
        if hits.size == 0:
            return jnp.zeros((out_len,), dtype=self.dtype)
        row = self.data[int(hits[0])]
        lo = max(0, k)
        seg = row[lo : lo + out_len]
        if seg.shape[0] < out_len:
            seg = jnp.pad(seg, (0, out_len - seg.shape[0]))
        return seg

    def __str__(self):
        return (
            f"<{self.shape[0]}x{self.shape[1]} DIA array,"
            f" ndiags={self.data.shape[0]}, dtype={self.dtype}>"
        )

    __repr__ = __str__


def few_diagonals(n_diags: int, n: int, nnz: int) -> bool:
    """The banded rule, in one place: an operator with ``nnz`` entries on
    ``n_diags`` distinct diagonals of length ``n`` is laid out as planes by
    ``csr_array._maybe_dia`` (one chip) and ``parallel.dist.shard_csr`` (a
    mesh) when the diagonals are few and their planes are not mostly fill.
    Each caller counts the diagonals where its arrays live."""
    from .config import settings

    return (n_diags <= settings.dia_max_diags
            and n_diags * n <= settings.dia_max_fill * nnz)


def banded_offsets(off: np.ndarray, n: int):
    """``(offsets, plane)``: the distinct diagonals (column - row, ascending)
    of a host CSR whose per-entry ``off`` is given, and each entry's index
    into them, when the operator is banded by the rule above
    (:func:`few_diagonals`); else None. The diagonals are counted on the
    host, where ``parallel.dist.shard_csr`` and ``batch.SparsityPattern``
    hold their arrays. ``off`` is the caller's scratch: it is overwritten."""
    from .config import settings

    nnz = off.shape[0]
    if nnz == 0:
        return None
    # a general matrix is turned away by a strided sample of its entries
    sample = off[:: max(nnz // 8192, 1)]
    if len(np.unique(sample)) > settings.dia_max_diags:
        return None
    lo, hi = int(off.min()), int(off.max())
    table = hi - lo < (1 << 22)  # a table over the band: two passes, no sort
    if table:
        off -= lo
        seen = np.bincount(off, minlength=hi - lo + 1) > 0
        offs = np.flatnonzero(seen) + lo
    else:
        offs, plane = np.unique(off, return_inverse=True)
    if not few_diagonals(len(offs), n, nnz):
        return None
    if table:
        plane = (np.cumsum(seen) - 1)[off]
    return offs, plane


def _coo_to_dia(c):
    """COO -> (data, offsets, shape). Host-syncs the distinct-offset set."""
    m, n = c.shape
    # offsets lie in [-m, n]: int32-exact for any dims that fit int32
    # (an int64 request under no-x64 warns and truncates anyway)
    odt = jnp.int64 if max(m, n) > 2**31 - 1 else jnp.int32
    offs_dev = c.col.astype(odt) - c.row.astype(odt)
    offsets = np.unique(np.asarray(offs_dev))
    L = n
    nd = int(offsets.shape[0])
    data = jnp.zeros((max(nd, 1), L), dtype=c.data.dtype)
    if c.nnz:
        k = jnp.searchsorted(jnp.asarray(offsets), offs_dev)
        data = data.at[k, c.col].add(c.data)
    if nd == 0:
        offsets = np.zeros((1,), dtype=np.int64)
    return data, offsets, (m, n)
