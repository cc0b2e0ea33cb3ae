"""CSR/CSC SpMV and SpMM kernels (single-device compute path).

Reference analog: the CSR_SPMV_ROW_SPLIT / CSR_SPMV_COL_SPLIT / CSC_SPMV_COL_SPLIT /
SPMM_* task families (``src/sparse/array/csr/spmv.*``, ``spmm.*`` — SURVEY §2b).
The cuSPARSE calls become pure-XLA gather/segment-reduce pipelines here, with a
padded-row (ELL) fast path that turns SpMV into gathers + dense reductions — the
shape TPUs like (no scatter in the hot loop). Which form a matrix takes is
``csr._LAYOUTS``; a banded one has a Pallas kernel (``kernels.dia_spmv``).

All functions are jit-safe: static shapes, no host syncs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .coords import expand_rows


def csr_spmv_segment(indptr, indices, data, x, m: int, acc_dtype=None):
    """y = A @ x via gather + sorted segment-sum. General path, any row profile.

    ``acc_dtype`` is the mixed-precision widening hook (ISSUE 15): with
    reduced-width values (bf16/f32 storage), products and the segment
    reduction accumulate at ``acc_dtype`` instead of the storage dtype —
    the converts fuse into the gather consumers, so HBM still moves
    half-width values while the arithmetic stays wide. ``None`` (the
    default) keeps the historic result-type behavior byte-identical."""
    nnz = data.shape[0]
    out_dt = acc_dtype or jnp.result_type(data.dtype, x.dtype)
    if nnz == 0:
        return jnp.zeros((m,), dtype=out_dt)
    rows = expand_rows(indptr, nnz)
    if acc_dtype is not None:
        prod = data.astype(out_dt) * x[indices].astype(out_dt)
    else:
        prod = data * x[indices]
    return jax.ops.segment_sum(prod, rows, num_segments=m, indices_are_sorted=True)


# Max ELL width unrolled into the trace; wider matrices take a fori_loop so
# the program size stays O(1) in the row degree.
ELL_UNROLL_MAX = 32


def csr_spmv_ell(ell_indices, ell_data, x):
    """y = A @ x on the padded-row (ELL) layout: k 1-D gathers + VPU adds.

    For banded/bounded-degree matrices (every reference benchmark: 5-pt/9-pt
    Laplacians, 11-diag SpMV microbench) this is pure gather + VPU reduce —
    no scatter, no segment ids. The k planes are processed as separate [m]
    gathers: a single [m, k] fancy-index gather acquires a trailing
    length-1 index dim that TPU tiles to (8, 128) — an ~128x padded s32
    buffer in HBM — while 1-D gathers lay out exactly. Small k is unrolled;
    large k runs the same plane-gather under lax.fori_loop.
    """
    k = ell_data.shape[1]
    if k <= ELL_UNROLL_MAX:
        acc = ell_data[:, 0] * x[ell_indices[:, 0]]
        for kk in range(1, k):
            acc = acc + ell_data[:, kk] * x[ell_indices[:, kk]]
        return acc
    idx_t, dat_t = ell_indices.T, ell_data.T  # [k, m]: plane-major slices

    def body(kk, acc):
        return acc + dat_t[kk] * x[idx_t[kk]]

    out_dt = jnp.result_type(ell_data.dtype, x.dtype)
    acc0 = jnp.zeros((ell_data.shape[0],), dtype=out_dt)
    return jax.lax.fori_loop(0, k, body, acc0)


def _sell_slab_spmv(idx_t, val_t, x, acc_dtype=None):
    """y_slab = A_slab @ x on one SELL slab: [K, R] plane-major index/value
    planes (rows of equal padded width K). Same gather-shaped op as
    :func:`csr_spmv_ell`, stored plane-major so each plane is a contiguous
    1-D gather; small K unrolls, large K runs under ``fori_loop``.

    ``acc_dtype`` widens every plane product before the accumulate
    (ISSUE 15): value planes stream at their storage width (bf16/f32),
    the per-row reduction runs at ``acc_dtype``. ``None`` = historic
    result-type accumulation, byte-identical."""
    K = idx_t.shape[0]
    out_dt = acc_dtype or jnp.result_type(val_t.dtype, x.dtype)
    if K == 0:
        return jnp.zeros((idx_t.shape[1],), dtype=out_dt)

    def plane(kk):
        if acc_dtype is not None:
            return val_t[kk].astype(out_dt) * x[idx_t[kk]].astype(out_dt)
        return val_t[kk] * x[idx_t[kk]]

    if K <= ELL_UNROLL_MAX:
        acc = plane(0)
        for kk in range(1, K):
            acc = acc + plane(kk)
        return acc.astype(out_dt)

    def body(kk, acc):
        return acc + plane(kk)

    acc0 = jnp.zeros((idx_t.shape[1],), dtype=out_dt)
    return jax.lax.fori_loop(0, K, body, acc0)


def csr_spmv_sell_packed(slabs, x, zero_rows: int, out_dtype=None,
                         acc_dtype=None):
    """A @ x on the SELL-C-sigma layout (see ``kernels.sell_spmv``), left in
    the pack's own row order: the slabs' outputs one after another, then
    the ``zero_rows`` all-empty rows. The one slab loop of the vector
    products.

    ``slabs`` is a static tuple of plane-major ``(idx_t, val_t)`` pairs
    ([K_s, R_s] each — rows degree-sorted within sigma-windows, chunked into
    C-row chunks padded to each chunk's max degree, chunks grouped by padded
    width). Every step is a contiguous 1-D gather + VPU add — no scatter,
    no segment ids, and near-zero pad waste even under row-length skew (vs.
    ELL's global-max padding). ``x`` is indexed by the slabs' indices as
    they stand: the caller's column numbering for :func:`csr_spmv_sell`,
    packed positions for a caller that keeps its vectors in the pack's
    order (``batch.operator._PackOrder``). A slab's alignment pad rows
    (``ROW_ALIGN``) come out zero.
    """
    x = jnp.asarray(x)  # numpy x would fail the fori-loop gather branch
    out_dt = out_dtype or acc_dtype or jnp.result_type(
        slabs[0][1].dtype if slabs else x.dtype, x.dtype
    )
    parts = [
        _sell_slab_spmv(it, vt, x, acc_dtype=acc_dtype).astype(out_dt)
        for it, vt in slabs
    ]
    if zero_rows:
        parts.append(jnp.zeros((zero_rows,), dtype=out_dt))
    if not parts:  # empty matrix
        return jnp.zeros((0,), dtype=out_dt)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def csr_spmv_sell(slabs, pos, x, zero_rows: int, out_dtype=None,
                  acc_dtype=None):
    """y = A @ x on the SELL-C-sigma layout, in the caller's row order:
    :func:`csr_spmv_sell_packed`, then one gather through ``pos``
    (original row -> position in the packed output), which also drops the
    slabs' pad rows. ``pos=None`` leaves the result in the pack's order.
    The one form of the prepared general SpMV
    (``kernels.sell_spmv.PreparedCSR`` packs for it).
    """
    packed = csr_spmv_sell_packed(slabs, x, zero_rows, out_dtype,
                                  acc_dtype=acc_dtype)
    if pos is None or not packed.shape[0]:  # empty matrix: pos is empty too
        return packed
    return packed[pos]


def csr_spmm_sell(slabs, pos, B, zero_rows: int, out_dtype=None):
    """C = A @ B (dense [n, nB]) on the SELL layout: per-slab row-gathers of
    B + fused accumulate, then one row-gather back to original order."""
    B = jnp.asarray(B)
    out_dt = out_dtype or jnp.result_type(
        slabs[0][1].dtype if slabs else B.dtype, B.dtype
    )
    nB = B.shape[1]

    def slab(it, vt):
        K = it.shape[0]
        if K == 0:
            return jnp.zeros((it.shape[1], nB), dtype=out_dt)
        if K <= ELL_UNROLL_MAX:
            acc = vt[0][:, None] * B[it[0]]
            for kk in range(1, K):
                acc = acc + vt[kk][:, None] * B[it[kk]]
            return acc.astype(out_dt)

        def body(kk, acc):
            return acc + vt[kk][:, None] * B[it[kk]]

        return jax.lax.fori_loop(
            0, K, body, jnp.zeros((it.shape[1], nB), dtype=out_dt)
        )

    parts = [slab(it, vt) for it, vt in slabs]
    if zero_rows:
        parts.append(jnp.zeros((zero_rows, nB), dtype=out_dt))
    if not parts:
        return jnp.zeros((pos.shape[0], nB), dtype=out_dt)
    packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return packed[pos]


def csr_spmv_sell_batched(idx_slabs, val_slabs, pos, X, zero_rows: int,
                          out_dtype=None, acc_dtype=None):
    """Y[b] = A_b @ X[b] on the SELL layout with one SHARED sparsity
    pattern: ``idx_slabs`` (and ``pos``/``zero_rows``) are pattern state
    packed once, ``val_slabs`` is a tuple of stacked ``[B, K, R]`` value
    planes — the vmap-compatible XLA path of the batched subsystem
    (``sparse_tpu.batch``). Every lane rides the same contiguous 1-D
    gathers as :func:`csr_spmv_sell`, its closing ``pos`` gather (one of
    the whole ``[B, m]`` stack a product) included; XLA batches them for
    free. ``pos=None`` leaves ``Y`` in the pack's row order: with
    ``idx_slabs`` renumbered into packed positions and ``X`` held in that
    order it is the whole product of a Krylov loop that runs in the
    pack's order (``batch.operator._PackOrder``), no ``pos`` gather a
    product.

    ``acc_dtype`` is the storage/accumulation split (ISSUE 15): value
    planes may be stored bf16/f32 while every plane product and the
    per-row reduction run at ``acc_dtype`` — the mixed-precision inner
    sweep's matvec."""
    X = jnp.asarray(X)

    def one(vts, x):
        return csr_spmv_sell(
            tuple(zip(idx_slabs, vts)), pos, x, zero_rows, out_dtype,
            acc_dtype=acc_dtype,
        )

    return jax.vmap(one)(tuple(val_slabs), X)


def csr_spmm_sell_batched(idx_slabs, val_slabs, pos, X, zero_rows: int,
                          out_dtype=None):
    """C[b] = A_b @ X[b] (dense ``[B, n, k]``) on the shared-pattern SELL
    layout — the batched counterpart of :func:`csr_spmm_sell`."""
    X = jnp.asarray(X)

    def one(vts, x):
        return csr_spmm_sell(
            tuple(zip(idx_slabs, vts)), pos, x, zero_rows, out_dtype
        )

    return jax.vmap(one)(tuple(val_slabs), X)


def csr_spmv_segment_batched(indptr, indices, values, X, m: int):
    """Y[b] = A_b @ X[b] via the general segment path, values ``[B, nnz]``
    over one shared pattern — the trace-safe fallback of the batched
    subsystem (no host-side pack required)."""
    return jax.vmap(
        lambda d, x: csr_spmv_segment(indptr, indices, d, x, m)
    )(values, jnp.asarray(X))


def csr_spmm_segment(indptr, indices, data, B, m: int):
    """C = A @ B with B dense [k, n]. Reference: SPMM_CSR_DENSE row-split."""
    nnz = data.shape[0]
    n = B.shape[1]
    out_dt = jnp.result_type(data.dtype, B.dtype)
    if nnz == 0:
        return jnp.zeros((m, n), dtype=out_dt)
    rows = expand_rows(indptr, nnz)
    prod = data[:, None] * B[indices]
    return jax.ops.segment_sum(prod, rows, num_segments=m, indices_are_sorted=True)


def csr_spmm_ell(ell_indices, ell_data, B):
    """C = A @ B on the ELL layout: k row-gathers of B + fused accumulate.
    Unrolled over small static ELL widths (same TPU-layout reason as
    csr_spmv_ell), fori_loop above ELL_UNROLL_MAX."""
    k = ell_data.shape[1]
    if k <= ELL_UNROLL_MAX:
        acc = ell_data[:, 0, None] * B[ell_indices[:, 0]]
        for kk in range(1, k):
            acc = acc + ell_data[:, kk, None] * B[ell_indices[:, kk]]
        return acc
    idx_t, dat_t = ell_indices.T, ell_data.T  # [k, m]

    def body(kk, acc):
        return acc + dat_t[kk][:, None] * B[idx_t[kk]]

    out_dt = jnp.result_type(ell_data.dtype, B.dtype)
    acc0 = jnp.zeros((ell_data.shape[0], B.shape[1]), dtype=out_dt)
    return jax.lax.fori_loop(0, k, body, acc0)


def csr_spmv_colsplit(indptr, indices, data, x, m: int, nblocks: int):
    """y = A @ x with the contraction (column) dimension split into
    ``nblocks`` equal domains, each reduced separately, then summed.

    Reference: CSR_SPMV_COL_SPLIT (``src/sparse/array/csr/spmv.cu:126-153``,
    driven by ``spmv_domain_part`` at csr.py:869-927) — the column-domain
    partition with ADD-reduction into y. On one chip the partials live as a
    [nblocks, m] plane reduced on-device; on the mesh the same structure is
    ``parallel.dist.DistCSRCol`` where the reduction is a psum_scatter.
    """
    nnz = data.shape[0]
    if nnz == 0:
        return jnp.zeros((m,), dtype=jnp.result_type(data.dtype, x.dtype))
    n = x.shape[0]
    idt = jnp.int32
    if max(n, m) * nblocks > np.iinfo(np.int32).max:
        # int32 would wrap in `indices * nblocks` / `block * m + rows` and
        # silently misroute segments (jnp truncates int64 under x32) — fail
        # loudly like ops.coords.require_x64_index.
        if not jax.config.jax_enable_x64:
            raise ValueError(
                f"column-split SpMV on shape ({m}, {n}) with {nblocks} "
                "blocks needs int64 segment keys; enable them with "
                "jax.config.update('jax_enable_x64', True)"
            )
        idt = jnp.int64
    rows = expand_rows(indptr, nnz)
    block = (indices.astype(idt) * nblocks) // max(n, 1)
    seg = block * m + rows.astype(idt)
    part = jax.ops.segment_sum(
        data * x[indices], seg, num_segments=nblocks * m
    )
    return part.reshape(nblocks, m).sum(axis=0)


def csc_spmv(indptr, indices, data, x, m: int):
    """y = A @ x with A in CSC: gather x by column-segments, scatter-add to rows.

    Reference: CSC_SPMV_COL_SPLIT (``src/sparse/array/csc/spmv.*``) — the
    reduction-accessor variant. Here: per-nnz products with the column id taken
    from the compressed axis, segment-summed by the (unsorted) row indices.
    """
    nnz = data.shape[0]
    n = indptr.shape[0] - 1
    if nnz == 0:
        return jnp.zeros((m,), dtype=jnp.result_type(data.dtype, x.dtype))
    cols = expand_rows(indptr, nnz)  # compressed axis of CSC = columns
    prod = data * x[cols]
    return jax.ops.segment_sum(prod, indices, num_segments=m)


def rspmm(indptr, indices, data, B, n: int):
    """C = B @ A with A CSR [m, n], B dense [p, m] (dense x sparse).

    Reference: SPMM_DENSE_CSR k-split with ADD reduction into a replicated C
    (csr.py:1209-1240). Here: C[:, col] += B[:, row] * val as a segment-sum of
    per-nnz [p]-vectors keyed by column id.
    """
    nnz = data.shape[0]
    p = B.shape[0]
    out_dt = jnp.result_type(data.dtype, B.dtype)
    if nnz == 0:
        return jnp.zeros((p, n), dtype=out_dt)
    rows = expand_rows(indptr, nnz)
    contrib = B.T[rows] * data[:, None]  # [nnz, p]
    out = jax.ops.segment_sum(contrib, indices, num_segments=n)  # [n, p]
    return out.T
