"""Pallas TPU kernel: the rows of one colour of HPCG's stored 27-point
operator in the colour-major order of ``models/hpcg_grid.py``, the colour a
value the kernel is handed and not a constant of it.

A row of colour ``c`` (its parities ``(pz, py, px)``) at half-grid index
``(k, j, i)`` reads, for every offset ``(oz, oy, ox)``, the block of colour
``c ^ m`` (``m`` the mask of the offset's odd components) at an index that is
its own along an axis whose offset is 0, and along an odd one either its own
or one further in the direction ``s = 2 p - 1``: of the two offsets ``-1`` and
``+1`` of an axis the one equal to ``s`` reads the moved block, the other the
block as it lies. So the 27 terms are, whatever the colour, the eight masks
``m`` and for each the subsets ``u`` of its odd axes that are moved
(:data:`TERMS`); the colour decides which stored plane a term multiplies by
(an index of the plane's ``BlockSpec``, read from the colour's prefetched row
of parameters) and in which direction it moves (a select between the two
rotations). ``hpcg_grid._row_sum`` unrolls the same sum with the colour a
Python constant, and XLA's program for a cycle of four levels then holds 105
different fusions of 26 terms each (77 s to compile at 256^3); here a level
has one kernel a use, and a sweep is a loop over the colours.

Grid step ``(g, k)`` makes ``tz`` slices of the block of colour
``colours[g]``. It is brought the 27 planes' ``tz`` slices, each source
block's ``tz`` slices and, for the four sources moved along z, the one slice
beyond them; the moves along y and x are rotations in VMEM, whose wrap the
stored zero cuts off as it cuts the grid's edge off (and the clamped slice
beyond the grid's last likewise). The parameters say which blocks of ``x``
hold anything: a dead block's terms keep the block index of step
``(g, 0)``, so the pipeline brings their planes and their zeros once and not
``hz / tz`` times. That is HPCG's symmetric step from a zero start, which
reads only what it has written.

=============  ==============================================  ===========
``mode``       result for a colour's rows                      ``r``
=============  ==============================================  ===========
``"update"``   ``(r - sum_{d != diagonal} a_d x_d) / a_diag``  read
               or, where the colour's parameters ask for the
               residual, ``r - sum_d a_d x_d``: one kernel a
               level for the sweeps and the cycle's residual
``"product"``  ``sum_d a_d x_d``                               none
=============  ==============================================  ===========
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MODES = ("update", "product")
# (m, u): the mask of the offset's odd axes (z, y, x) and, of those, the ones
# along which the source block is read moved; 27 in all, the diagonal first
TERMS = tuple(
    (m, u)
    for m in itertools.product((0, 1), repeat=3)
    for u in itertools.product(*[(0, 1) if bit else (0,) for bit in m]))
MASKS = tuple(itertools.product((0, 1), repeat=3))
# what a grid step's blocks may take of VMEM, each buffered twice
_STEP_BYTES = 20 * 1024 * 1024
VMEM_LIMIT = 48 * 1024 * 1024


def _parities(c):
    return (c >> 2) & 1, (c >> 1) & 1, c & 1


def _mask_int(m: tuple) -> int:
    return 4 * m[0] + 2 * m[1] + m[2]


def plane_index(c: int, m: tuple, u: tuple) -> int:
    """The stored plane (its index among the 27 offsets, z slowest) that the
    term ``(m, u)`` of colour ``c`` multiplies by: along an odd axis the
    offset is ``s = 2 p - 1`` where the block is read moved and ``-s`` where
    it is not."""
    d = 0
    for p, bit, moved in zip(_parities(c), m, u):
        s = 2 * p - 1
        d = 3 * d + 1 + bit * (s if moved else -s)
    return d


# what the index maps read of a colour, one row of int32 a grid row ``g``:
# the colour, its 27 terms' planes, its eight sources' blocks and whether
# each holds anything (:func:`colour_params`). Worked out on the host once a
# colour: as arithmetic on the prefetched colour inside 41 index maps a
# kernel it was three quarters of the program's tracing time
_PLANE, _BLOCK, _LIVE = 1, 1 + len(TERMS), 9 + len(TERMS)
_RESIDUAL, PARAMS = 17 + len(TERMS), 18 + len(TERMS)


def colour_params(colour: int, live: int = 2 ** 8 - 1,
                  residual: bool = False) -> np.ndarray:
    """The kernel's row of parameters for one colour: ``live`` says which
    blocks of ``x`` hold anything its rows read, a bit a block; ``residual``
    asks ``"update"`` for the rows of ``r - A x`` instead."""
    blocks = [colour ^ _mask_int(m) for m in MASKS]
    return np.asarray(
        [colour] + [plane_index(colour, m, u) for m, u in TERMS] + blocks
        + [live >> b & 1 for b in blocks] + [int(residual)], np.int32)


def slices_a_step(hz: int, hy: int, hx: int, itemsize: int = 4) -> int:
    """``tz``: the largest power of two that divides ``hz`` at which a grid
    step's blocks (27 planes, eight sources, ``r`` and the result, a slice
    padded to the (8, 128) tile) fit :data:`_STEP_BYTES` twice over."""
    padded = -(-hy // 8) * 8 * -(-hx // 128) * 128 * itemsize
    tz = 1
    while hz % (2 * tz) == 0 and 2 * 37 * 2 * tz * padded <= _STEP_BYTES:
        tz *= 2
    return tz


def _kernel(params_ref, *refs, mode: str, tz: int):
    planes, refs = refs[:len(TERMS)], refs[len(TERMS):]
    sources, refs = dict(zip(MASKS, refs[:8])), refs[8:]
    beyond = dict(zip([m for m in MASKS if m[0]], refs[:4]))
    rest = refs[4:]
    r_ref, o_ref = rest if mode != "product" else (None, *rest)
    mine = pl.program_id(0)
    pz, py, px = _parities(params_ref[mine, 0])
    residual = params_ref[mine, _RESIDUAL] == 1

    def moved(x, axis: int, p):
        """``x`` read one further along ``axis`` in the direction 2 p - 1."""
        n = x.shape[axis]
        if n == 1:
            return x  # its neighbours lie outside the grid
        return jnp.where(p == 1, pltpu.roll(x, n - 1, axis), pltpu.roll(x, 1, axis))

    def slices(j, carry):
        read = {}  # (m, u) -> the source's slice as the term reads it

        def source(m, u):
            if (m, u) in read:
                return read[m, u]
            uz, uy, ux = u
            if ux:
                out = moved(source(m, (uz, uy, 0)), 1, px)
            elif uy:
                out = moved(source(m, (uz, 0, 0)), 0, py)
            elif uz:
                jj = j + 2 * pz - 1
                inside = (jj >= 0) & (jj < tz)
                out = jnp.where(inside, sources[m][jnp.clip(jj, 0, tz - 1)],
                                beyond[m][0])
            else:
                out = sources[m][j]
            read[m, u] = out
            return out

        total = None
        for t, (m, u) in enumerate(TERMS):
            if mode == "update" and not any(m):
                continue  # the diagonal divides, or is subtracted last
            term = planes[t][j] * source(m, u)
            total = term if total is None else total + term
        if mode == "update":
            rest, diagonal = r_ref[j] - total, planes[0][j]
            total = jnp.where(residual, rest - diagonal * source(*TERMS[0]),
                              rest / diagonal)
        o_ref[j] = total
        return carry

    jax.lax.fori_loop(0, tz, slices, 0)


@partial(jax.jit, static_argnames=("mode", "interpret"))
def colour_rows(planes, x, r, params, *, mode: str, interpret: bool = False):
    """``[G, hz, hy, hx]``: for each ``g`` the rows of the colour that
    ``params[g]`` names (:func:`colour_params`: the colour and which blocks
    of ``x`` hold anything; the others must hold zeros, which are read at
    step 0's slices alone) by ``mode`` (the module's table), from the
    level's stored planes ``[8, 27, hz, hy, hx]`` and the blocks ``x`` and
    ``r`` (``[8, hz, hy, hx]``; ``r`` None for ``"product"``). ``params`` is
    a value and not a constant: another colour runs the same kernel."""
    assert mode in MODES and (r is None) == (mode == "product")
    hz, hy, hx = x.shape[1:]
    assert planes.shape == (8, len(TERMS), hz, hy, hx) and x.shape[0] == 8
    G = params.shape[0]
    assert params.shape == (G, PARAMS)
    tz = slices_a_step(hz, hy, hx, x.dtype.itemsize)

    def plane(t: int, m: tuple):
        # the diagonal is read whatever x holds: an update divides by it
        def index(g, k, p):
            kk = k * p[g, _LIVE + _mask_int(m)] if any(m) else k
            return p[g, 0], p[g, _PLANE + t], kk, 0, 0

        return pl.BlockSpec((None, None, tz, hy, hx), index)

    def source(m: tuple):
        # step 0's slices of a block that holds nothing to read
        at = _mask_int(m)
        return pl.BlockSpec(
            (None, tz, hy, hx),
            lambda g, k, p: (p[g, _BLOCK + at], k * p[g, _LIVE + at], 0, 0))

    def beyond(m: tuple):
        at = _mask_int(m)

        def index(g, k, p):
            kk = k * p[g, _LIVE + at]
            # the slice after the step's last (pz = 1) or before its first
            edge = jnp.where((p[g, 0] >> 2) & 1, (kk + 1) * tz, kk * tz - 1)
            return p[g, _BLOCK + at], jnp.clip(edge, 0, hz - 1), 0, 0

        return pl.BlockSpec((None, 1, hy, hx), index)

    operands = [planes] * len(TERMS) + [x] * 12
    in_specs = ([plane(t, m) for t, (m, _u) in enumerate(TERMS)]
                + [source(m) for m in MASKS]
                + [beyond(m) for m in MASKS if m[0]])
    if r is not None:
        operands.append(r)
        in_specs.append(pl.BlockSpec((None, tz, hy, hx),
                                     lambda g, k, p: (p[g, 0], k, 0, 0)))
    return pl.pallas_call(
        partial(_kernel, mode=mode, tz=tz),
        name=f"hpcg_colour_{mode}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, hz // tz),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, tz, hy, hx),
                                   lambda g, k, p: (g, k, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((G, hz, hy, hx), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(params, *operands)
