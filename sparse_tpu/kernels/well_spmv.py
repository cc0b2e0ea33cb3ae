"""Windowed padded-row SpMV: a Pallas kernel that gathers from x held in VMEM.

XLA's gather on the TPU costs the same whatever its indices (8.6 ns an
element on the v5e: PERF.md section 5), so the padded-row product
(``ops.spmv.csr_spmv_ell``) pays one full-price gather a plane. The one
gather Mosaic lowers is the single-tile ``jnp.take_along_axis`` (operand and
indices one ``(8, 128)`` tile, along lanes). This layout makes a product out
of that by keeping each row tile's columns inside a short **window** of x:

* the matrix is reordered once, on the host, by a bandwidth-reducing
  symmetric permutation (``csgraph.band_order``: Cuthill-McKee done a level
  at a time in numpy, reversed), so that a row's columns lie near the row;
* the permuted matrix's padded rows are stored plane-major, ``[k, rows]``,
  rows cut into tiles of ``TILE`` = 8 x 128 (one vreg a plane: sublane s
  holds the tile's rows 128 s to 128 s + 127);
* a tile's window is a list of **steps**. A step is eight consecutive
  128-lane chunks of x, one a sublane (an ``(8, 128)`` load at a dynamic
  sublane offset): sublane s of step d reads chunk d + s. In a band, rows
  128 s further on read columns 128 s further on, so one step serves all
  eight sublanes, and a window with holes (the levels before, of and after
  a row's own) lists only the chunks that hold an entry;
* the kernel walks a tile's steps: the step's eight chunks are lane-gathered
  through ``idx & 127`` and kept where ``idx >> 7`` names this step.

x stays in VMEM whole for the product (4 bytes a row) and the step lists in
SMEM (the layout is offered only where both fit, ``csr_array._maybe_well``),
and the CG runs in the permuted, padded space so that the two permutations
are paid once a solve (``linalg._cg_general`` through ``csr.form_space``).
That space is ``[LEAD zeros | the n permuted entries | zeros]``: the lead
and the tail keep every step's eight chunks inside the vector.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE = 8 * LANES  # rows a tile: one (8, 128) vreg a plane
LEAD = TILE  # zeros before the first row: sublane s of a step reads chunk d + s
STEP_TILES = 8  # tiles one grid step multiplies (1 to 16 read the same on the chip)
PLANE_GROUP = 12  # planes gathered in one pass over a tile's window
def symmetric_pattern(indptr, indices, n: int) -> bool:
    """Whether every stored (i, j) has a stored (j, i): the strictly upper
    entries' keys against the sorted keys of the strictly lower ones,
    transposed."""
    indptr = np.asarray(indptr)
    cols = np.asarray(indices).astype(np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    up, lo = rows < cols, rows > cols
    if int(up.sum()) != int(lo.sum()):
        return False
    return bool(np.array_equal(np.sort(rows[up] * n + cols[up]),
                               np.sort(cols[lo] * n + rows[lo])))


class WellLayout(NamedTuple):
    """The windowed padded-row layout of one matrix: the arrays the kernel
    and the two permutations take (``arrays``: ``ptr``, ``starts``, ``idx``,
    ``val``, ``perm``, ``inv_perm``: the place in the padded space of every
    index of the caller's), the hashable geometry (``meta`` = rows, padded
    rows) and what the reordering left (``stats``)."""

    arrays: dict
    meta: tuple
    stats: dict


def permuted_csr(indptr, indices, data, order):
    """``(indptr, rows, cols, data, rank)`` of P A P^T for the ordering
    ``order`` (new row i is old row ``order[i]``): columns relabelled, a
    row's entries in their stored order, ``rows`` the new row of every
    entry, ``rank`` the new position of every old index. Rows and columns
    are int32: numpy's int64 shifts are the slow pass of ``windows``."""
    n = order.shape[0]
    rank = np.empty(n, dtype=np.int32)
    rank[order] = np.arange(n, dtype=np.int32)
    indptr = np.asarray(indptr, dtype=np.int64)
    counts = np.diff(indptr)[order]
    new_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=new_ptr[1:])
    rows = np.repeat(np.arange(n, dtype=np.int32), counts)
    take = np.arange(new_ptr[-1]) + (indptr[order] - new_ptr[:-1])[rows]
    return new_ptr, rows, rank[np.asarray(indices)[take]], data[take], rank


def windows(new_ptr, rows, cols, n: int, n_pad: int):
    """Per tile of ``TILE`` rows of the padded space (row r sits at ``LEAD +
    r``): the steps its entries read. Returns ``(ptr, starts, step, stats)``:
    tile t's steps are ``starts[ptr[t]:ptr[t + 1]]`` (the chunk its sublane
    0 reads; ascending), ``step`` is every entry's place in its tile's list,
    ``stats`` the reordering's figures for the span ``layout.reorder``."""
    n_tiles = n_pad // TILE
    at = rows + LEAD
    tile = at >> 10  # TILE rows
    diag = ((cols + LEAD) >> 7) - ((at >> 7) & 7)
    # a tile's entries are consecutive: its lowest and highest step
    edge = new_ptr[np.clip(np.arange(n_tiles + 1) * TILE - LEAD, 0, n)]
    live = edge[1:] > edge[:-1]
    lo = np.zeros(n_tiles, dtype=np.int64)
    width = np.zeros(n_tiles, dtype=np.int64)
    if live.any():
        lo[live] = np.minimum.reduceat(diag, edge[:-1][live])
        width[live] = np.maximum.reduceat(diag, edge[:-1][live]) - lo[live] + 1
    # one flag a tile and step of its span; the steps are the flags set
    off = np.cumsum(width) - width
    flat = (off - lo)[tile] + diag
    used = np.zeros(int(width.sum()), dtype=bool)
    used[flat] = True
    owner = np.repeat(np.arange(n_tiles), width)
    starts = np.flatnonzero(used) + (lo - off)[owner[used]]
    count = np.bincount(owner[used], minlength=n_tiles)
    ptr = np.zeros(n_tiles + 1, dtype=np.int64)
    np.cumsum(count, out=ptr[1:])
    # a flag's place in its tile's list: its rank among the flags set,
    # less the tile's first
    place = np.cumsum(used) - 1 - ptr[owner]
    away = cols - rows
    stats = {
        "bandwidth": int(max(away.max(), -int(away.min()))) if cols.shape[0] else 0,
        "window_chunks_max": int(count.max()),
        "window_chunks_mean": float(count[live].mean()) if live.any() else 0.0,
        "steps": int(ptr[-1]),
        "tile": TILE,
    }
    return ptr, starts, place[flat], stats


def padded_rows(new_ptr, rows, cols, data, step, n_pad: int):
    """The permuted matrix's padded rows, plane-major ``[k, n_pad / 128,
    128]``: an entry's index is its step in the tile's list and its lane; a
    padding slot has value 0 and reads lane 0 of the first step."""
    k = max(int(np.diff(new_ptr).max()), 1)
    slot = np.arange(rows.shape[0]) - new_ptr[rows]
    idx = np.zeros((k, n_pad), dtype=np.int32)
    val = np.zeros((k, n_pad), dtype=data.dtype)
    idx[slot, rows + LEAD] = step * LANES + (cols & (LANES - 1))  # LEAD is whole chunks
    val[slot, rows + LEAD] = data
    shape = (k, n_pad // LANES, LANES)
    return idx.reshape(shape), val.reshape(shape)


def padded_size(n: int) -> int:
    """The padded space's length: the lead, the rows and a tail of eight
    chunks, up to whole grid steps."""
    step = TILE * STEP_TILES
    return -(-(LEAD + n + TILE) // step) * step


def _kernel(ptr_ref, starts_ref, x_ref, idx_ref, val_ref, out_ref, *, k):
    grid_step = pl.program_id(0)
    for t in range(STEP_TILES):
        tile = grid_step * STEP_TILES + t
        lo = ptr_ref[tile]
        count = ptr_ref[tile + 1] - lo
        rows = pl.ds(t * 8, 8)
        q = jnp.zeros((8, LANES), jnp.float32)
        for g0 in range(0, k, PLANE_GROUP):
            planes = range(g0, min(g0 + PLANE_GROUP, k))
            idx = [idx_ref[p, rows, :] for p in planes]
            # bit operations: `%` recurses in Mosaic under x64
            lane = [i & (LANES - 1) for i in idx]
            step = [i >> 7 for i in idx]

            def body(c, accs, lane=lane, step=step, lo=lo):
                x8 = x_ref[pl.ds(starts_ref[lo + c], 8), :]
                return tuple(
                    jnp.where(st == c, jnp.take_along_axis(x8, ln, axis=1), a)
                    for ln, st, a in zip(lane, step, accs))

            accs = jax.lax.fori_loop(
                0, count, body,
                tuple(jnp.zeros((8, LANES), jnp.float32) for _ in planes))
            for p, a in zip(planes, accs):
                q = q + val_ref[p, rows, :] * a
        out_ref[rows, :] = q


@functools.partial(jax.jit, static_argnames=("interpret",))
def well_spmv(ptr, starts, idx, val, x2, interpret=False):
    """y = (P A P^T) x in the layout's space: ``x2`` is the permuted, padded
    vector as ``(n_pad / 128, 128)`` float32; the result has its shape."""
    k, chunks, _ = idx.shape
    rows = STEP_TILES * 8
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((chunks, LANES), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(chunks // rows,),
            in_specs=[
                # x whole and resident: brought in once a product
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec((k, rows, LANES), lambda i, *_: (0, i, 0)),
                pl.BlockSpec((k, rows, LANES), lambda i, *_: (0, i, 0)),
            ],
            out_specs=pl.BlockSpec((rows, LANES), lambda i, *_: (i, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(4 * chunks * LANES) + (16 << 20),
        ),
        name="well_spmv",
        interpret=interpret,
    )(ptr, starts, x2, idx, val)
