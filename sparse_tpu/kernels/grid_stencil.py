"""Pallas TPU kernel: the 5-point stencil with scalar coefficients on an
``[n, n]`` grid, its shifted reads kept on the ``(8, 128)`` tile.

``gmg_grid.stencil_apply`` is a pad of the grid to ``[n + 2, n + 2]`` and five
slices of it, four of which start one row or one column off the tile: at
4480^2 on the v5e XLA's fusion of them runs at 3.8 times the time its two
grids take at HBM's peak (PERF.md section 5, PR 40). Here the grid is cut
into blocks of ``block_rows(n)`` rows. A grid step gets its block and, as two
more operands, the eight-row tiles above and below it (the halo); inside,
the block is walked in groups of eight rows, and a group's four neighbours
reach the sum as rotations in VMEM:

* rows: the group above (below) hands its last (first) row over under a
  sublane select, and one sublane rotation puts every row under its
  neighbour;
* columns: each 128-lane vreg is rotated by one lane, and lane 0 (lane 127)
  takes the rotated neighbour vreg's: that is the carry between vregs and,
  where there is no neighbour vreg, the grid's edge, which reads zero;
* the grid's first and last rows read a zero tile where the halo would be,
  or, where the grid is one shard's row block of a grid laid over a mesh, the
  two rows its neighbours sent (``halo``: one row of ``n`` each, zero at the
  mesh's two ends; ``gmg_grid._fine_stencil`` exchanges them under
  ``shard_map``).

The consumer's arithmetic is inside, one ``form`` a use of the fine level
(``gmg_grid._Cycle.level``, ``_GridApply``), so that no use makes a pass over
a grid that XLA's fusion did not make:

=============  ======================  ==========================
``form``       result                  grids touched (read+write)
=============  ======================  ==========================
``"apply"``    ``A x``                 2
``"residual"`` ``x - A (w x)``         2
``"smooth"``   ``x + w (r - A x)``     3
=============  ======================  ==========================

The five coefficients and ``w`` are one SMEM operand, not constants: another
hierarchy of the same sizes runs the same program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
FORMS = ("apply", "residual", "smooth")
# the offsets of a 5-point stencil, (row, column) of the neighbour read
FIVE_POINT = frozenset({(0, 0), (-1, 0), (1, 0), (0, -1), (0, 1)})
# what a grid step's blocks may take of the 16 MiB a kernel gets by default:
# three block operands (x, r, the result), each buffered twice
_BLOCK_BYTES = 15 * 1024 * 1024 // 6


def block_rows(n: int, m: int | None = None) -> int:
    """Rows of a grid step's block on an ``[m, n]`` grid (``m`` None: ``n``):
    128, halved (down to two row groups) while a float32 block of ``n``
    columns would not fit the default scoped VMEM six times over (``smooth``
    has three block operands, double-buffered) or does not divide ``m`` (a
    shard's rows). 128 at 4480; 64 on the 1600 rows of a shard of 6400."""
    m = n if m is None else m
    rows = 128
    while rows > 2 * SUBLANES and (rows * n * 4 > _BLOCK_BYTES or m % rows):
        rows //= 2
    return rows


def _lane_neighbours(x):
    """``(left, right)`` of an ``(8, n)`` row group: ``left[:, j]`` is
    ``x[:, j - 1]`` and ``right[:, j]`` is ``x[:, j + 1]``, zero off the
    grid. A vreg at a time: the rotation wraps a vreg's far lane round to
    the lane its neighbour vreg needs."""
    nv = x.shape[1] // LANES
    vregs = [x[:, c * LANES:(c + 1) * LANES] for c in range(nv)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    first, last = lane == 0, lane == LANES - 1
    back = [pltpu.roll(v, 1, 1) for v in vregs]  # lane j: v[j - 1]; lane 0: v[127]
    fwd = [pltpu.roll(v, LANES - 1, 1) for v in vregs]  # lane 127: v[0]
    zero = jnp.zeros_like(vregs[0])
    left = [jnp.where(first, back[c - 1] if c else zero, back[c])
            for c in range(nv)]
    right = [jnp.where(last, fwd[c + 1] if c + 1 < nv else zero, fwd[c])
             for c in range(nv)]
    return jnp.concatenate(left, axis=1), jnp.concatenate(right, axis=1)


def _kernel(s_ref, x_ref, above_ref, below_ref, *rest, form, offsets, blocks,
            halo):
    # the rows beyond the grid's first and last: the neighbour shards'
    # (one row each, read into every sublane of a tile), else zero
    top = bottom = 0.0
    if halo:
        top_ref, bottom_ref, *rest = rest
        top, bottom = (jnp.broadcast_to(ref[...], (SUBLANES, x_ref.shape[1]))
                       for ref in (top_ref, bottom_ref))
    r_ref, o_ref = rest if form == "smooth" else (None, *rest)
    i = pl.program_id(0)
    groups = x_ref.shape[0] // SUBLANES
    coef = {d: s_ref[k] for k, d in enumerate(offsets)}
    w = None if form == "apply" else s_ref[len(offsets)]
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, x_ref.shape[1]), 0)

    def rows(ref, g):
        return ref[pl.ds(pl.multiple_of(g * SUBLANES, SUBLANES), SUBLANES), :]

    def emit(g, prev, nxt):
        """Group ``g``'s rows of the result, from the groups above and below."""
        cur = rows(x_ref, g)
        # the row above row 0 is prev's last; one rotation shifts all eight
        up = jnp.where(sub == SUBLANES - 1, prev, cur)
        down = jnp.where(sub == 0, nxt, cur)
        x = cur
        if form == "residual":  # the stencil is applied to w x
            x, up, down = w * cur, w * up, w * down
        left, right = _lane_neighbours(x)
        shifted = {
            (0, 0): x, (0, -1): left, (0, 1): right,
            (-1, 0): pltpu.roll(up, 1, 0),
            (1, 0): pltpu.roll(down, SUBLANES - 1, 0),
        }
        ax = None  # the sum in the stencil's own order, as stencil_apply's
        for d in offsets:
            term = coef[d] * shifted[d]
            ax = term if ax is None else ax + term
        if form == "residual":
            ax = cur - ax
        elif form == "smooth":
            ax = cur + w * (rows(r_ref, g) - ax)
        o_ref[pl.ds(pl.multiple_of(g * SUBLANES, SUBLANES), SUBLANES), :] = ax

    above = jnp.where(i > 0, above_ref[...], top)
    below = jnp.where(i < blocks - 1, below_ref[...], bottom)
    emit(0, above, rows(x_ref, 1))

    def body(g, carry):
        emit(g, rows(x_ref, g - 1), rows(x_ref, g + 1))
        return carry

    jax.lax.fori_loop(1, groups - 1, body, 0)
    emit(groups - 1, rows(x_ref, groups - 2), below)


@partial(jax.jit, static_argnames=("form", "offsets", "interpret"))
def stencil5(scalars, x, r=None, *, form: str, offsets: tuple,
             interpret: bool = False, halo=None):
    """One use of the 5-point stencil ``A`` on the ``[m, n]`` float32 grid
    ``x`` (``n`` a multiple of 128, ``m`` of 16), by ``form`` (the module's
    table).

    ``scalars`` holds the coefficients in the order of ``offsets`` (the five
    of :data:`FIVE_POINT`, a neighbour's (row, column) each, as
    ``gmg_grid.stencil_apply`` reads them) and then, but for ``"apply"``,
    ``w``. ``r`` is ``"smooth"``'s second grid. ``halo``: the ``[1, n]`` rows
    above ``x``'s first and below its last, where ``x`` is a row block of a
    larger grid; None: ``x`` is the whole grid, and zero lies beyond it."""
    m, n = x.shape
    assert form in FORMS and set(offsets) == FIVE_POINT and len(offsets) == 5
    assert n % LANES == 0 and x.dtype == jnp.float32
    assert (r is not None) == (form == "smooth")
    assert scalars.shape == (5 + (form != "apply"),)
    tr = block_rows(n, m)
    assert m % tr == 0, (m, tr)
    blocks, tiles = m // tr, tr // SUBLANES
    block = pl.BlockSpec((tr, n), lambda i: (i, 0))
    # the eight-row tile that ends above the block, the one that starts
    # below it; at the grid's edge any tile, read as zero
    above = pl.BlockSpec(
        (SUBLANES, n), lambda i: (jnp.maximum(i * tiles - 1, 0), 0))
    below = pl.BlockSpec(
        (SUBLANES, n),
        lambda i: (jnp.minimum((i + 1) * tiles, m // SUBLANES - 1), 0))
    row = pl.BlockSpec((1, n), lambda i: (0, 0))
    halo = () if halo is None else tuple(halo)
    grids = (x, x, x, *halo) if r is None else (x, x, x, *halo, r)
    return pl.pallas_call(
        partial(_kernel, form=form, offsets=offsets, blocks=blocks,
                halo=bool(halo)),
        name=f"grid_stencil5_{form}",
        grid=(blocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), block, above, below]
        + [row] * len(halo) + [block] * (r is not None),
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(scalars, *grids)
