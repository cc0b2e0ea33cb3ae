"""HPCG's problem on one chip: a 27-point operator held as stored planes, a
four-level multigrid V-cycle with a multicolour symmetric Gauss-Seidel
smoother, both in a colour-major order of the unknowns.

Reference analog: HPCG 3.1 (``GenerateProblem_ref``, ``ComputeSPMV_ref``,
``ComputeSYMGS_ref``, ``ComputeMG_ref``, ``ComputeRestriction_ref``,
``ComputeProlongation_ref``): 26 on the diagonal and -1 to each of the up to
26 neighbours of an nx x ny x nz grid, every coarser level the same rule on
the grid of half the side (no Galerkin product), restriction by injection of
``r - A x`` at the even points, prolongation by adding the coarse correction
at those points, one symmetric Gauss-Seidel step before and one after on
every level but the coarsest, which runs one step and no solve.

What is forced on this chip, and how it is laid out:

* the matrix is STORED: per level ``planes[c, d]`` (``[8, 27, nz/2, ny/2,
  nx/2]``), the coefficient of every point of colour ``c`` towards its
  neighbour at offset ``OFFSETS[d]``, zero where that neighbour lies outside
  the grid (the entry a sparse format would not hold). The product and the
  sweep read every coefficient they use from there, the diagonal they divide
  by included: nothing below knows that the entries are 26 and -1 but
  :func:`_level_planes`, the generator. The format holds no index array;
* a sweep in row order is one dependent step a row. The colour of a point is
  the parity of (z, y, x): no two points of one colour are neighbours in a
  27-point stencil, so a colour's points update together and eight updates
  are one sweep: lexicographic Gauss-Seidel on the matrix permuted colour by
  colour in the sweep's order (``FORWARD``: the even points last, so that
  the coarse correction they carry is read before it is overwritten);
* a colour of a lexicographic array is a stride-2 slice along the lane axis,
  a gather on the TPU. So the unknowns are stored colour-major, ``[8, nz/2,
  ny/2, nx/2]``: the neighbour of a point of colour ``c`` at any offset is
  one whole block of another colour moved by 0 or +-1 an axis
  (:func:`_source`), and the move wraps around (``jnp.roll``) because the
  stored zero is what cuts the wrap off, as it cuts the grid's edge off;
* the caller's vectors are lexicographic (``[nz, ny, nx]`` flattened, HPCG's
  own numbering). Both operators declare the space they multiply in
  (``apply.space``, a :class:`ColourMajor`), and ``linalg.cg`` crosses into
  it once a solve, inside its one compiled program ``jit_pcg``: no product
  and no cycle reorders a vector of the fine level. Outside a solver
  (``A @ v``, ``M @ r``) each operator crosses on its own;
* a level's even points are its block 0, which is the next level's grid in
  lexicographic order: the restricted residual is computed on block 0 alone
  and re-coloured for the next level (one eighth of the level's points, each
  way, a cycle), the only reordering the cycle holds. The lane axis is parted
  by a 0/1 matrix on the matrix unit (:func:`_lanes_apart`): a reshape to
  ``[.., nx/2, 2]`` is laid out with its last axis padded from 2 lanes to
  128, 4.3 GB for one vector of 256^3;
* the colour is a value, not a constant. :func:`_row_sum` unrolls a colour's
  26 terms with the colour a Python constant; a cycle of four levels is then
  105 different fusions whose rotations XLA materialises (77 s to compile for
  a v5e at 256^3, 11.5 GB of temporaries). That form is the definition, and
  what the CPU, float64 and the tests' oracles run. On a TPU
  (:func:`_kernel_applies`, read off the hierarchy's arrays where the
  operators are declared, by no setting) a level's colour updates and its
  residual at the even points are one kernel and the product another,
  ``kernels/hpcg_colour.py``, which takes the colour as a prefetched value,
  and a symmetric step is a 15-trip loop around it.

Why this is not ``gmg_grid``: that hierarchy is 2-D, keeps five scalars on
the fine level, computes its coarse operators by Galerkin products, moves
between levels by full weighting and smooths by a diagonal scaling; none of
its arithmetic is this one's. What the two share is how they meet the solver:
``LinearOperator(shape, apply=, operands=, describe=)`` over frozen
dataclasses, one ``jit_pcg`` a structure, a named scope a level.

Inside ``jit_pcg`` a level's ops stand under ``jax.named_scope("hpcg.l<k>")``
and inside it under ``hpcg.l<k>.symgs``, ``hpcg.l<k>.spmv`` (the cycle's
residual) or ``hpcg.l<k>.transfer``; the outer product under ``hpcg.spmv``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..kernels import hpcg_colour
from ..telemetry import _metrics

__all__ = [
    "OFFSETS",
    "COLOURS",
    "FORWARD",
    "ColourMajor",
    "build_hierarchy",
    "grid_operator",
    "make_vcycle",
]

# the 27 offsets (dz, dy, dx) in HPCG's own order of a row's entries; the
# diagonal is the 14th
OFFSETS = tuple((dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                for dx in (-1, 0, 1))
CENTRE = OFFSETS.index((0, 0, 0))
COLOURS = 8
# the order a forward sweep visits the colours in (block = 4 pz + 2 py + px):
# the points with three odd coordinates first, then two, then one, and the
# even points, block 0, last. The cycle adds the coarse correction to block 0
# and smooths next: a sweep that began with block 0 would overwrite the
# correction before any row had read it (an update never reads its own
# colour) and the coarser levels would be dead code, which XLA removes. Read
# with benchmark/tools/hpcg_opt_iters.py: this order reaches the reference's
# residual of 50 iterations in 50 to 52, descending 7..0 in 53, ascending 0..7
# (the correction unread) in 59 to 64
FORWARD = (7, 3, 5, 6, 1, 2, 4, 0)
# colour updates of one symmetric step: forward, then backward. The backward
# sweep's own update of the forward sweep's last colour reads what that one
# read, no other colour having changed between the two, and writes the same
# bits again: it is left out (tests/test_hpcg_grid.py holds the two forms to
# bit-equality)
_SWEEP = FORWARD + FORWARD[-2::-1]

_COLOUR_UPDATES = _metrics.counter(
    "hpcg.symgs.colour_updates",
    help="colour updates (one eighth of a level's points each) written into "
    "traced programs by models/hpcg_grid.py's symmetric Gauss-Seidel steps: "
    "counted at trace time, so a program found again adds none; a trace of "
    "jit_pcg adds one V-cycle's count (the loop's body), which the cg.solve "
    "span states as colour_updates")


def _source(colour: int, offset: tuple) -> tuple:
    """``(colour, shift)`` of the block that holds the neighbours at
    ``offset`` of the points of ``colour``: the neighbour of the point
    ``(k, j, i)`` of the one block is the point ``(k, j, i) + shift`` of the
    other. Along an axis a point of parity ``p`` at half-grid index ``k``
    sits at ``2 k + p``; its neighbour at ``2 k + p + o`` has parity
    ``(p + o) % 2`` and half-grid index ``k + (p + o) // 2``."""
    src, shift = 0, []
    for bit, o in zip((2, 1, 0), offset):
        p = (colour >> bit & 1) + o
        src = 2 * src + p % 2
        shift.append(p // 2)
    return src, tuple(shift)


def _moved(block, shift: tuple):
    """``out[k, j, i] = block[(k, j, i) + shift]``, wrapping around: where
    the wrap shows, the neighbour lies outside the grid and its stored
    coefficient is zero."""
    for axis, s in enumerate(shift):
        if s:
            block = jnp.roll(block, -s, axis)
    return block


def _row_sum(planes, blocks, colour: int, diagonal: bool):
    """``sum_d planes[colour, d] * x[neighbour d]`` over the rows of one
    colour, from the eight blocks of ``x`` (None: a block known to be zero,
    whose terms add nothing); with or without the diagonal's term. None
    where every term is left out."""
    out = None
    for d, offset in enumerate(OFFSETS):
        if d == CENTRE and not diagonal:
            continue
        src, shift = _source(colour, offset)
        if blocks[src] is None:
            continue
        term = planes[colour, d] * _moved(blocks[src], shift)
        out = term if out is None else out + term
    return out


def _sweep_live(from_zero: bool) -> list:
    """Per update of :data:`_SWEEP`, the blocks of ``x`` that hold anything
    its rows read, a bit each: all but the colour's own, and from a zero
    start those the sweep has written (HPCG's cycle smooths from zero, and
    such a step reads only what it has written)."""
    live, out = (0 if from_zero else 2 ** COLOURS - 1), []
    for c in _SWEEP:
        out.append(live & ~(1 << c))
        live |= 1 << c
    return out


def _symgs(planes, r, x=None):
    """One symmetric Gauss-Seidel step on ``A x = r``: every colour in turn
    ``x_c <- (r_c - sum_{d != diagonal} a_d x_neighbour) / a_diagonal`` from
    the newest blocks of the other colours (a row's off-diagonal neighbours
    are all of other colours), forward then backward. ``r`` and ``x`` are the
    eight blocks, ``x`` None from a zero start."""
    _COLOUR_UPDATES.inc(len(_SWEEP))
    blocks = [None] * COLOURS if x is None else list(x)
    for c, live in zip(_SWEEP, _sweep_live(x is None)):
        rest = _row_sum(planes, [b if live >> k & 1 else None
                                 for k, b in enumerate(blocks)], c, False)
        blocks[c] = (r[c] if rest is None else r[c] - rest) / planes[c, CENTRE]
    return jnp.stack(blocks)


# -- the same three uses through the kernel ------------------------------------
# the platform the kernel is compiled for. A test's CPU drive sets "cpu",
# and the kernel then runs interpreted
_KERNEL_PLATFORM = "tpu"


def _kernel_applies(hierarchy) -> bool:
    """Whether the colours' rows take ``kernels.hpcg_colour`` (the colour a
    value of one kernel a level) rather than :func:`_row_sum` (the colour a
    constant of 105 fusions a cycle), read off the hierarchy's own arrays
    where the operators are declared: float32 planes resident on one TPU.
    Another dtype, the CPU, numpy arrays and an outer trace read False."""
    try:
        devices = set().union(*(p.devices() for p in hierarchy))
    except (AttributeError, TypeError):  # a numpy array; a tracer
        return False
    return (all(p.dtype == jnp.float32 for p in hierarchy) and len(devices) == 1
            and {d.platform for d in devices} == {_KERNEL_PLATFORM})


def _rows(planes, x, r, params, mode: str):
    """``kernels.hpcg_colour.colour_rows`` for a test's CPU drive too."""
    return hpcg_colour.colour_rows(planes, x, r, params, mode=mode,
                                   interpret=jax.default_backend() != "tpu")


def _params(colours, lives=None, residual: bool = False):
    """The kernel's rows of parameters for ``colours`` in turn, every block
    of ``x`` live where ``lives`` says nothing."""
    lives = [2 ** COLOURS - 1] * len(colours) if lives is None else lives
    return jnp.asarray(np.stack([hpcg_colour.colour_params(c, live, residual)
                                 for c, live in zip(colours, lives)]))


def _symgs_kernel(planes, r, x=None):
    """:func:`_symgs` as a loop over the sweep's colours around one kernel:
    an update makes the colour's block, which takes its place among the
    eight."""
    _COLOUR_UPDATES.inc(len(_SWEEP))
    params = _params(_SWEEP, _sweep_live(x is None))

    def update(s, x):
        mine = jax.lax.dynamic_slice_in_dim(params, s, 1)
        new = _rows(planes, x, r, mine, "update")
        return jax.lax.dynamic_update_slice_in_dim(x, new, mine[0, 0], 0)

    return jax.lax.fori_loop(0, len(_SWEEP), update,
                             jnp.zeros_like(r) if x is None else x)


def _dims_at(dims: tuple, lvl: int) -> tuple:
    return tuple(d >> lvl for d in dims)


def _lanes_apart(nx: int, dtype):
    """The 0/1 matrix that takes the even entries of a row of ``nx`` to its
    first half and the odd ones to its second. A stride of two along the
    lane axis is a gather to the TPU's vector unit and a product of whole
    tiles to its matrix unit, which this program leaves idle."""
    to = jnp.arange(nx)
    source = 2 * (to % (nx // 2)) + to // (nx // 2)
    return (jnp.arange(nx)[:, None] == source[None, :]).astype(dtype)


def _exactly(a, b):
    """``a @ b`` where one side is 0/1: every entry of the other side comes
    through to the bit."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass(frozen=True)
class ColourMajor:
    """The order the operators of one grid multiply in: ``dims = (nz, ny,
    nx)``, a vector ``[8, nz/2, ny/2, nx/2]`` flattened, block ``4 pz + 2 py
    + px`` the points of parity ``(pz, py, px)``. Equal by value, so that it
    can be part of a compiled program's identity."""

    dims: tuple

    @property
    def half(self) -> tuple:
        return tuple(d // 2 for d in self.dims)

    def blocks(self, v):
        """A vector of this order as its ``[8, nz/2, ny/2, nx/2]`` blocks."""
        return v.reshape(COLOURS, *self.half)

    def enter(self, v):
        """A lexicographic vector (or grid) in this order, flat: the lane
        axis parted by :func:`_lanes_apart`, the two others by a transpose
        that leaves the lanes where they are."""
        hz, hy, hx = self.half
        rows = _exactly(v.reshape(-1, 2 * hx), _lanes_apart(2 * hx, v.dtype))
        return (rows.reshape(hz, 2, hy, 2, 2, hx).transpose(1, 3, 4, 0, 2, 5)
                .reshape(-1))

    def leave(self, v):
        """A vector of this order, lexicographic and flat."""
        hz, hy, hx = self.half
        rows = (v.reshape(2, 2, 2, hz, hy, hx).transpose(3, 0, 4, 1, 2, 5)
                .reshape(-1, 2 * hx))
        return _exactly(rows, _lanes_apart(2 * hx, v.dtype).T).reshape(-1)


class _InSpace:
    """What ``linalg._pcg`` reads off a declared ``apply`` that multiplies
    in an order of its own: ``space`` (``enter`` and ``leave`` of a vector)
    and ``within()``, the same ``apply`` for vectors already there."""

    @property
    def space(self) -> ColourMajor:
        return ColourMajor(self.dims)

    def within(self):
        return dataclasses.replace(self, lexicographic=False)

    def __call__(self, operands, v):
        if not self.lexicographic:
            return self.inside(operands, v)
        space = self.space
        return space.leave(self.inside(operands, space.enter(v)))


@dataclasses.dataclass(frozen=True)
class _Product(_InSpace):
    """``apply`` of a level's operator: the 27-plane product. ``kernel``:
    through ``kernels.hpcg_colour`` (:func:`_kernel_applies`, decided where
    the operator is declared)."""

    dims: tuple
    kernel: bool = False
    lexicographic: bool = True

    def inside(self, planes, v):
        x = self.space.blocks(v)
        with jax.named_scope("hpcg.spmv"):
            if self.kernel and v.dtype == jnp.float32:
                out = _rows(planes, x, None, _params(range(COLOURS)), "product")
            else:
                out = jnp.stack([_row_sum(planes, x, c, diagonal=True)
                                 for c in range(COLOURS)])
            return out.reshape(-1)


@dataclasses.dataclass(frozen=True)
class _Cycle(_InSpace):
    """``apply`` of the V-cycle over ``levels`` levels from the grid
    ``dims``; equal by value for two hierarchies of the same sizes.
    ``kernel`` as :class:`_Product`'s."""

    dims: tuple
    levels: int
    kernel: bool = False
    lexicographic: bool = True

    def level(self, arrays, r, lvl: int):
        """The cycle from level ``lvl`` down on the residual ``r`` (that
        level's eight blocks): the correction's eight blocks."""
        planes = arrays[lvl]
        scope = f"hpcg.l{lvl}"
        kernel = self.kernel and r.dtype == jnp.float32
        symgs = _symgs_kernel if kernel else _symgs
        with jax.named_scope(scope), jax.named_scope(scope + ".symgs"):
            x = symgs(planes, r)
        if lvl == self.levels - 1:
            return x
        coarse = ColourMajor(_dims_at(self.dims, lvl + 1))
        with jax.named_scope(scope):
            # the residual at the even points, the level's block 0, which is
            # the next level's grid in lexicographic order
            with jax.named_scope(scope + ".spmv"):
                if kernel:
                    r0 = _rows(planes, x, r, _params([0], residual=True),
                               "update")[0]
                else:
                    r0 = r[0] - _row_sum(planes, x, 0, diagonal=True)
            with jax.named_scope(scope + ".transfer"):
                rc = coarse.blocks(coarse.enter(r0))
        xc = self.level(arrays, rc, lvl + 1)
        with jax.named_scope(scope):
            with jax.named_scope(scope + ".transfer"):
                x0 = x[0] + coarse.leave(xc).reshape(x.shape[1:])
                x = jax.lax.dynamic_update_slice_in_dim(x, x0[None], 0, 0)
            with jax.named_scope(scope + ".symgs"):
                return symgs(planes, r, x)

    def colour_updates(self) -> int:
        """The colour updates one cycle holds: two steps a level above the
        coarsest, one there."""
        return len(_SWEEP) * (2 * (self.levels - 1) + 1)

    def inside(self, arrays, v):
        return self.level(arrays, self.space.blocks(v), 0).reshape(-1)


@partial(jax.jit, static_argnames=("dims", "dtype"))
def _level_planes(dims: tuple, dtype):
    """HPCG's rule on the grid ``dims = (nz, ny, nx)`` as the 27 stored
    planes of every colour, ``[8, 27, nz/2, ny/2, nx/2]``: 26 on the
    diagonal, -1 towards a neighbour inside the grid, 0 towards one outside.
    Made on the device from index vectors (as constants they would be in the
    program's text: ``gmg_grid.galerkin_stencil``)."""

    def inside(n):  # [parity, offset, half-grid index]
        at = (2 * jnp.arange(n // 2)[None, None, :]
              + jnp.arange(2)[:, None, None] + jnp.arange(-1, 2)[None, :, None])
        return (at >= 0) & (at < n)

    z, y, x = (inside(n) for n in dims)
    mask = (z[:, None, None, :, None, None, :, None, None]
            & y[None, :, None, None, :, None, None, :, None]
            & x[None, None, :, None, None, :, None, None, :])
    mask = mask.reshape(COLOURS, len(OFFSETS), *(n // 2 for n in dims))
    centre = (jnp.arange(len(OFFSETS)) == CENTRE)[None, :, None, None, None]
    return jnp.where(centre, 26, jnp.where(mask, -1, 0)).astype(dtype)


def build_hierarchy(nx: int, ny: int, nz: int, levels: int = 4,
                    dtype=jnp.float32) -> list:
    """``[planes of level 0, planes of level 1, ...]``: every level's
    operator by HPCG's rule on its own grid (level ``k``: the sides halved
    ``k`` times), as stored planes in the colour-major order
    (:func:`_level_planes`), generated on the device. Every side must be a
    multiple of ``2 ** levels``: each level is coloured, so each has even
    sides."""
    dims = (int(nz), int(ny), int(nx))
    levels = int(levels)
    if levels < 1 or any(d < 1 or d % (1 << levels) for d in dims):
        raise ValueError(
            f"a grid of {nx} x {ny} x {nz} cannot carry {levels} coloured "
            f"levels: every side must be a positive multiple of 2**levels = "
            f"{1 << levels}")
    with telemetry.span("hpcg.build_hierarchy", levels=levels,
                        colours=COLOURS) as sp:
        hier = [_level_planes(_dims_at(dims, lvl), jnp.dtype(dtype).name)
                for lvl in range(levels)]
        sp.set_sync(hier)
        sp.annotate(
            sizes=[list(reversed(_dims_at(dims, lvl))) for lvl in range(levels)],
            bytes=sum(int(p.size) * p.dtype.itemsize for p in hier))
    return hier


def _level_dims(planes) -> tuple:
    return tuple(2 * h for h in planes.shape[2:])


def _declare(apply, operands, dims: tuple, dtype, **describe):
    from ..linalg import LinearOperator

    n = int(np.prod(dims))
    return LinearOperator((n, n), dtype=np.dtype(dtype), apply=apply,
                          operands=operands, describe=describe)


def grid_operator(hierarchy, lvl: int = 0):
    """Level ``lvl``'s operator as a ``LinearOperator`` on lexicographic
    flat vectors that declares its planes: the ``A`` of ``linalg.cg(A, b,
    M=M)``, which multiplies in the colour-major order inside a solve."""
    planes = hierarchy[lvl]
    dims = _level_dims(planes)
    kernel = _kernel_applies([planes])
    return _declare(_Product(dims, kernel), planes, dims, planes.dtype)


def make_vcycle(hierarchy):
    """HPCG's V-cycle over ``hierarchy`` as a ``LinearOperator`` on
    lexicographic flat vectors (the ``M`` of ``linalg.cg``; also callable,
    ``M(r)``), the smoother the 8-colour symmetric Gauss-Seidel step. It
    declares every level's planes as its operands; the sizes are static."""
    dims = _level_dims(hierarchy[0])
    for lvl, planes in enumerate(hierarchy):
        if _level_dims(planes) != _dims_at(dims, lvl):
            raise ValueError(
                f"level {lvl} holds a grid of {_level_dims(planes)}, not "
                f"{_dims_at(dims, lvl)}: each level is half the side of the "
                "one above")
    cycle = _Cycle(dims, len(hierarchy), _kernel_applies(hierarchy))
    return _declare(cycle, tuple(hierarchy), dims, hierarchy[0].dtype,
                    precond="hpcg_mg", levels=len(hierarchy), colours=COLOURS,
                    smoother="symgs", colour_updates=cycle.colour_updates())
