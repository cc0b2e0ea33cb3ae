"""Windowed step-major SpMV: a Pallas kernel that gathers from x held in VMEM.

XLA's gather on the TPU costs the same whatever its indices (8.6 ns an
element on the v5e: PERF.md section 5), so the padded-row product
(``ops.spmv.csr_spmv_ell``) pays one full-price gather a plane. The one
gather Mosaic lowers is the single-tile ``jnp.take_along_axis`` (operand and
indices one ``(8, 128)`` tile, along lanes). This layout makes a product out
of that by keeping each row tile's columns inside a short **window** of x:

* the matrix is reordered once, on the host, by a bandwidth-reducing
  symmetric permutation (``csgraph.band_order``: Cuthill-McKee done a level
  at a time in numpy, reversed), so that a row's columns lie near the row;
* the permuted matrix's rows are cut into tiles of ``TILE`` = 8 x 128 (one
  vreg: sublane s holds the tile's rows 128 s to 128 s + 127);
* a tile's window is a list of **steps**. A step is eight consecutive
  128-lane chunks of x, one a sublane (an ``(8, 128)`` load at a dynamic
  sublane offset): sublane s of step d reads chunk d + s. In a band, rows
  128 s further on read columns 128 s further on, so one step serves all
  eight sublanes, and a window with holes (the levels before, of and after
  a row's own) lists only the chunks that hold an entry;
* a tile's entries are stored step-major, as a list of **units**. A unit is
  one ``(8, 128)`` vreg of values and one of lanes that belongs to one
  step: a step has as many units as its fullest row has entries there, and
  unit j of a step holds, for every row of the tile, the row's j-th entry
  at that step (value 0, lane 0 where the row has fewer);
* the kernel walks a tile's units, ``GROUP`` a trip: a unit's step's eight
  chunks are lane-gathered through the unit's lanes, multiplied by its
  values and added. Every gather's result is used as it is, and a trip's
  gathers are independent, so that their latency (85 ns on the v5e, where
  a unit's 8 KB from HBM take 11) is paid once a trip.

x stays in VMEM whole for the product (4 bytes a row) and the unit lists in
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
TILE = 8 * LANES  # rows a tile: one (8, 128) vreg a unit
LEAD = TILE  # zeros before the first row: sublane s of a step reads chunk d + s
STEP_TILES = 16  # tiles one grid step multiplies
GRID_ROWS = TILE * STEP_TILES  # rows of the padded space a grid step
UNIT_BYTES = 2 * 4 * TILE  # a stored unit: a vreg of lanes and one of values
GROUP = 16  # units whose gathers the kernel keeps in flight together (a power of two)


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
    """The windowed step-major layout of one matrix: the arrays the kernel
    and the two permutations take (``arrays``: ``uptr``, ``ustart``,
    ``lane``, ``val``, ``perm``, ``inv_perm``: the place in the padded space
    of every index of the caller's), the hashable geometry (``meta`` = rows,
    padded rows) and what the reordering left (``stats``)."""

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
    r``): the steps its entries read and the units that hold them. Returns
    ``(uptr, ustart, unit, stats)``: tile t's units are ``uptr[t]:uptr[t +
    1]``, step after step (a step's chunks ascend in a tile) and a step's by
    depth; unit u reads from chunk ``ustart[u]`` (the chunk its sublane 0
    reads); ``unit`` is every entry's unit (an entry's depth is its rank
    among its row's entries at its step, so a step has as many units as the
    most entries one row of the tile has there); ``stats`` the reordering's
    figures for the span ``layout.reorder``."""
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
    steps = np.bincount(owner[used], minlength=n_tiles)
    ptr = np.zeros(n_tiles + 1, dtype=np.int64)
    np.cumsum(steps, out=ptr[1:])
    # an entry's step: its flag's rank among the flags set
    step = (np.cumsum(used) - 1)[flat]
    depth = _depths(rows, step - ptr[tile], int(steps.max()))
    # a step's depths are 0 to its units less one: one flag a step and depth
    held = np.zeros((int(ptr[-1]), int(depth.max(initial=0)) + 1), dtype=bool)
    held[step, depth] = True
    count = held.sum(axis=1)
    first = np.concatenate([[0], np.cumsum(count)])  # a step's first unit
    uptr = first[ptr]
    away = cols - rows
    stats = {
        "bandwidth": int(max(away.max(), -int(away.min()))) if cols.shape[0] else 0,
        "window_chunks_max": int(steps.max()),
        "window_chunks_mean": float(steps[live].mean()) if live.any() else 0.0,
        "steps": int(ptr[-1]),
        "units": int(uptr[-1]),
        "units_stored": _block(uptr) * (n_pad // GRID_ROWS),
        "tile": TILE,
    }
    return uptr, np.repeat(starts, count), first[step] + depth, stats


def _block(uptr) -> int:
    """The units of the fullest grid step (n_pad is whole grid steps)."""
    return max(int(np.diff(uptr[::STEP_TILES]).max()), 1)


def _depths(rows, place, places: int):
    """Every entry's rank among its row's entries at its step (``place``:
    the step's place in its tile's list, below ``places``). A row's entries
    are consecutive, so the stable sort by (row, place) moves an entry
    within its row alone."""
    nnz = rows.shape[0]
    # numpy sorts int32 keys five times faster than int64 ones
    small = nnz and (int(rows[-1]) + 1) * places < 2**31
    kt = np.int32 if small else np.int64
    key = rows.astype(kt) * kt(places) + place.astype(kt)
    order = np.argsort(key, kind="stable")
    key = key[order]
    at = np.arange(nnz, dtype=np.int32)
    first = np.ones(nnz, dtype=bool)
    np.not_equal(key[1:], key[:-1], out=first[1:])
    depth = np.empty(nnz, dtype=np.int32)
    depth[order] = at - np.maximum.accumulate(np.where(first, at, 0))
    return depth


def step_units(uptr, rows, cols, data, unit):
    """The stored units ``(lane, val)``, each ``[grid steps * block, 8,
    128]``: the units of one grid step's ``STEP_TILES`` tiles stand together
    at the head of a block of as many units as the fullest grid step holds
    (what the kernel's pipeline moves a grid step; the block's rest is never
    read). A slot without an entry has value 0 and reads lane 0."""
    head, block = uptr[::STEP_TILES], _block(uptr)
    at = rows + LEAD
    # a grid step's first stored unit, less its first unit
    shift = np.arange(head.shape[0] - 1) * block - head[:-1]
    stored = unit + shift[(at >> 10) // STEP_TILES]
    lane = np.zeros(((head.shape[0] - 1) * block, TILE), dtype=np.int32)
    val = np.zeros(lane.shape, dtype=data.dtype)
    lane[stored, at & (TILE - 1)] = cols & (LANES - 1)  # LEAD is whole chunks
    val[stored, at & (TILE - 1)] = data
    return lane.reshape(-1, 8, LANES), val.reshape(-1, 8, LANES)


def padded_size(n: int) -> int:
    """The padded space's length: the lead, the rows and a tail of eight
    chunks, up to whole grid steps."""
    return -(-(LEAD + n + TILE) // GRID_ROWS) * GRID_ROWS


def _kernel(uptr_ref, ustart_ref, x_ref, lane_ref, val_ref, out_ref):
    tile0 = pl.program_id(0) * STEP_TILES
    head = uptr_ref[tile0]  # the block's first unit

    def tile(t, carry):
        lo, hi = uptr_ref[tile0 + t], uptr_ref[tile0 + t + 1]

        def trip(i, acc):
            # GROUP units a trip, their gathers in flight together: one
            # alone waits out the lane gather's latency (88 ns; PERF.md
            # section 6, PR 48). Past the tile's last unit a slot reads
            # that unit again and adds nothing.
            terms = []
            for j in range(GROUP):
                u = lo + i * GROUP + j
                at = jnp.minimum(u, hi - 1)
                x8 = x_ref[pl.ds(ustart_ref[at], 8), :]
                stored = at - head
                term = val_ref[stored] * jnp.take_along_axis(
                    x8, lane_ref[stored], axis=1)
                terms.append(jnp.where(u < hi, term, 0.0))
            while len(terms) > 1:  # pairwise: no chain of GROUP adds
                terms = [a + b for a, b in zip(terms[::2], terms[1::2])]
            return acc + terms[0]

        # GROUP is a power of two: a shift (`%` recurses in Mosaic under x64)
        trips = (hi - lo + (GROUP - 1)) >> (GROUP.bit_length() - 1)
        out_ref[pl.ds(pl.multiple_of(t * 8, 8), 8), :] = jax.lax.fori_loop(
            0, trips, trip, jnp.zeros((8, LANES), jnp.float32))
        return carry

    jax.lax.fori_loop(0, STEP_TILES, tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def well_spmv(uptr, ustart, lane, val, x2, interpret=False):
    """y = (P A P^T) x in the layout's space: ``x2`` is the permuted, padded
    vector as ``(n_pad / 128, 128)`` float32; the result has its shape."""
    chunks = x2.shape[0]
    rows = STEP_TILES * 8
    block = lane.shape[0] // (chunks // rows)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((chunks, LANES), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(chunks // rows,),
            in_specs=[
                # x whole and resident: brought in once a product
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec((block, 8, LANES), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((block, 8, LANES), lambda i, *_: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((rows, LANES), lambda i, *_: (i, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # x, and two blocks of lanes and of values in flight
            vmem_limit_bytes=4 * chunks * LANES + 2 * UNIT_BYTES * block + (16 << 20),
        ),
        name="well_spmv",
        interpret=interpret,
    )(uptr, ustart, x2, lane, val)
