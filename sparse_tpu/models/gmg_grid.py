"""Structured-grid geometric multigrid, entirely in 2-D grid space.

Reference analog: ``examples/gmg.py`` (the BASELINE.md "GMG" row — V-cycle
weighted-Jacobi preconditioned CG, Galerkin coarse operators A_c = R A P
computed with general SpGEMM tasks, gmg.py:289-381).

TPU-first redesign: on a structured grid every operator in the hierarchy is
a <=9-point stencil, so nothing needs a general sparse format at all —

* each level operator is a dict ``{(di, dj): [n, n] coefficient plane}``
  (the fine level's 5-point Poisson stencil: five scalars);
  applying it is pad + 9 shifted multiply-adds (:func:`stencil_apply`),
  VPU work that XLA fuses into one pass: no gather, no CSR indices. Its
  slices start one row or one column off the TPU's ``(8, 128)`` tile, which
  at the fine level's size costs 3.8 times the pass's HBM time, so the
  fine level's three applies of a preconditioned CG iteration (``A p``, the
  cycle's residual and post-smoothing) take a Pallas kernel with the shifts
  as rotations in VMEM, ``kernels/grid_stencil.py``, where the level's own
  arrays show that it applies (:func:`_fine_kernel`: five float32 scalars,
  a side that is a multiple of 128, one TPU or a mesh of them). No setting
  chooses: the coarse levels' planes, the builds here and the CPU keep
  :func:`stencil_apply`;
* a hierarchy laid over a mesh (:func:`shard_hierarchy_grid`: every level's
  grids in row blocks) is the one-chip solve plus its exchanges: each
  stencil apply and each transfer of a level whose arrays show that
  lay-out (:func:`_level_rows`) runs under ``shard_map`` on a shard's own
  rows and the one row a side its neighbours send (``ppermute``; zero at the
  mesh's ends): two exchanges an apply, one a transfer, the fine level's
  three applies the same kernel on a shard's block. The arithmetic of each
  piece has one definition, which reads its halo rows from the pad on one
  device and from the neighbours on a mesh;
* the Galerkin product R A P is computed EXACTLY by probing the composed
  operator with period-3 comb vectors — 9 grid applies per level instead
  of two SpGEMMs + sorts (the r3-measured init was 52 s at n=4000, almost
  all COO sorts and eager power iteration);
* restriction/prolongation are separable strided stencils; prolongation
  uses interleave-reshape (stack + reshape) rather than scatter-add —
  TPU has no fast scatter;
* the weighted-Jacobi omega power iteration is one jitted ``fori_loop``.

The whole V-cycle is traceable, and :func:`make_vcycle` and
:func:`grid_operator` return operators that DECLARE what they hold (the
hierarchy's planes and weights as ``operands``, the level sizes static), so
``linalg.cg(A, b, M=vcycle)`` runs ONE compiled program, ``jit_pcg``, with
the hierarchy as its arguments: the next solve, another right-hand side,
another hierarchy of the same sizes compile nothing. Inside it each level's
ops stand under ``jax.named_scope("gmg.l<k>")``.

Exactness: ``galerkin_stencil`` equals the explicit R @ A @ P product and
``prolong_grid``/``restrict_grid`` equal the explicit P/R SpMVs
(oracle-tested against scipy in tests/test_gmg_grid.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..kernels import grid_stencil
from ..telemetry import _metrics

__all__ = [
    "poisson_stencil",
    "stencil_apply",
    "restrict_grid",
    "prolong_grid",
    "galerkin_stencil",
    "build_hierarchy",
    "grid_operator",
    "make_vcycle",
    "shard_hierarchy_grid",
]


def poisson_stencil(n: int, dtype=jnp.float32) -> dict:
    """5-point Poisson stencil on an n x n grid, as SCALAR coefficients.

    Matches examples/gmg.py:poisson2D (4 on the diagonal, -1 to the four
    neighbors; couplings across the grid edge vanish via zero-padding at
    apply time). Scalars, not [n, n] planes: the coefficients are
    uniform, and the fine level dominates the V-cycle's HBM traffic — a
    plane-form apply reads 5 extra N-sized arrays per application.
    ``stencil_apply`` broadcasts either form.
    """
    del n  # the stencil is resolution-independent; kept for the API shape
    return {
        (0, 0): jnp.asarray(4.0, dtype),
        (-1, 0): jnp.asarray(-1.0, dtype),
        (1, 0): jnp.asarray(-1.0, dtype),
        (0, -1): jnp.asarray(-1.0, dtype),
        (0, 1): jnp.asarray(-1.0, dtype),
    }


_HALO_EXCHANGES = _metrics.counter(
    "gmg.mesh.halo_exchanges",
    help="halo exchanges (one ppermute of one grid row each) written into "
    "traced programs by the row-block forms of models/gmg_grid.py: counted "
    "at trace time, so a program found again adds none; a trace of jit_pcg "
    "adds twice an iteration's count (the start's product and cycle, then "
    "the loop's body), which the cg.solve span states as halo_exchanges")


@dataclasses.dataclass(frozen=True)
class _Rows:
    """A level's lay-out over a mesh: its ``[n, n]`` arrays in row blocks
    over ``axis``, ``n`` a multiple of the shards. Equal by value, as a
    ``Mesh`` is, so that it can be part of a compiled program's identity."""

    mesh: object
    axis: str

    @property
    def shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    def map(self, f, *args):
        """``f`` on every shard's own rows of the grids among ``args``
        (two-dimensional: a row block each; anything else whole); its
        result is the row block of the grid this returns."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import shard_map

        block = P(self.axis, None)
        specs = jax.tree_util.tree_map(
            lambda a: block if jnp.ndim(a) == 2 else P(), args)
        return shard_map(f, mesh=self.mesh, in_specs=specs, out_specs=block,
                         check_vma=False)(*args)

    def edge_rows(self, X, above: bool = True, below: bool = True):
        """Inside :meth:`map`: ``(the row above this block, the row below
        it)``, each ``[1, columns]`` and sent by the neighbour whose edge
        row it is (one exchange each; None where not asked for). The
        mesh's first and last shard receive nothing there, which reads
        zero: the grid's own boundary."""
        S = self.shards
        up = down = None
        if above:
            up = jax.lax.ppermute(
                X[-1:], self.axis, [(i, i + 1) for i in range(S - 1)])
        if below:
            down = jax.lax.ppermute(
                X[:1], self.axis, [(i + 1, i) for i in range(S - 1)])
        _HALO_EXCHANGES.inc(int(above) + int(below))
        return up, down

    def transfers(self, fn: int, cn: int) -> bool:
        """Whether the transfers between this level (side ``fn``) and the
        next (``cn``) stay on a shard's rows and one halo row: both sides
        in whole row blocks, two fine rows a coarse one."""
        return fn == 2 * cn and cn % self.shards == 0


def _stencil_sum(planes: dict, Xp, shape):
    """The stencil's sum for a grid (or row block) of ``shape`` from ``Xp``,
    the same with one more row and column on every side."""
    m, n = shape
    out = None
    for (di, dj), C in planes.items():
        term = C * jax.lax.slice(Xp, (1 + di, 1 + dj), (1 + di + m, 1 + dj + n))
        out = term if out is None else out + term
    return out


@partial(jax.jit, static_argnames=("rows",))
def stencil_apply(planes: dict, X, rows: _Rows | None = None):
    """y = A @ x with A in stencil form: (A x)[i,j] = sum_d C_d[i,j] *
    x[i+di, j+dj], x zero-padded at the boundary. ``rows``: the grid and the
    planes lie over a mesh in row blocks, and each shard applies the stencil
    to its own block between its neighbours' edge rows.

    Jitted (as are all public entry points here): the module's op mix
    triggers an XLA CPU *eager-mode* heap corruption on jax 0.9.0 at odd
    grid sizes; compiled execution is correct, and under an outer trace
    (the CG while_loop) the inner jit simply inlines."""
    if rows is None:
        return _stencil_sum(planes, jnp.pad(X, 1), X.shape)

    def block(planes, X):
        above, below = rows.edge_rows(X)
        Xp = jnp.pad(jnp.concatenate([above, X, below]), ((0, 0), (1, 1)))
        return _stencil_sum(planes, Xp, X.shape)

    return rows.map(block, planes, X)


def _full_weighting(Xp, rn: int, cn: int):
    """``rn`` coarse rows and ``cn`` coarse columns by [1,2,1]/4 an axis at
    stride 2 from ``Xp``, the fine grid (or row block) with one more row and
    column before its first."""

    def r1(Y, k):
        return (
            Y[0 : 2 * k : 2, :]
            + 2.0 * Y[1 : 2 * k + 1 : 2, :]
            + Y[2 : 2 * k + 2 : 2, :]
        ) * jnp.asarray(0.25, Y.dtype)

    return r1(r1(Xp, rn).T, cn).T


@partial(jax.jit, static_argnums=(1, 2), static_argnames=("rows",))
def restrict_grid(X, cn: int, gridop: str, rows: _Rows | None = None):
    """R @ r on the grid: full-weighting [1,2,1]/4 per axis at stride 2
    (or even-point injection). Equal to the explicit restriction matrix
    of examples/gmg.py:linear_operator / injection_operator. ``rows``: the
    fine grid lies over a mesh in row blocks; a shard's coarse rows then
    come from its own fine rows and the one above them (:meth:`_Rows.transfers`;
    otherwise the partitioner places what the slices need)."""
    if gridop == "injection":
        return X[0 : 2 * cn : 2, 0 : 2 * cn : 2]
    if rows is None or not rows.transfers(X.shape[0], cn):
        return _full_weighting(jnp.pad(X, 1), cn, cn)

    def block(X):
        above, _ = rows.edge_rows(X, below=False)
        # nothing is read past an even block's last row or the last column
        Xp = jnp.pad(jnp.concatenate([above, X]), ((0, 0), (1, 0)))
        return _full_weighting(Xp, X.shape[0] // 2, cn)

    return rows.map(block, X)


def _p1_interleave(Y, fn: int, cn: int, below=None):
    """1-D transposed full-weighting along axis 0, scatter-free.

    Fine row 2c gets 0.5*Y[c]; fine row 2c+1 gets 0.25*(Y[c] + Y[c+1])
    (Y[cn] treated as 0, or ``below``, the row after a row block's last)
    — assembled by interleaving the even/odd row
    planes with stack+reshape instead of at[...].add scatters.
    """
    half = jnp.asarray(0.5, Y.dtype)
    quarter = jnp.asarray(0.25, Y.dtype)
    evens = half * Y
    after = (jnp.pad(Y[1:, :], ((0, 1), (0, 0))) if below is None
             else jnp.concatenate([Y[1:, :], below]))
    odds = quarter * (Y + after)
    inter = jnp.stack([evens, odds], axis=1).reshape(2 * cn, Y.shape[1])
    return jnp.pad(inter, ((0, fn - 2 * cn), (0, 0)))


@partial(jax.jit, static_argnums=(1, 2, 3), static_argnames=("rows",))
def prolong_grid(Z, fn: int, cn: int, gridop: str, rows: _Rows | None = None):
    """P @ xc = R.T @ xc on the grid (transposed separable stencil).
    ``rows``: the fine grid lies over a mesh in row blocks; a shard's fine
    rows then come from its own coarse rows and the one below them (as
    :func:`restrict_grid`)."""
    if gridop == "injection":
        out = jnp.zeros((fn, fn), dtype=Z.dtype)
        return out.at[0 : 2 * cn : 2, 0 : 2 * cn : 2].set(Z)
    if rows is None or not rows.transfers(fn, cn):
        return _p1_interleave(_p1_interleave(Z, fn, cn).T, fn, cn).T

    def block(Z):
        mc = Z.shape[0]
        _, below = rows.edge_rows(Z, above=False)
        return _p1_interleave(_p1_interleave(Z, 2 * mc, mc, below).T, fn, cn).T

    return rows.map(block, Z)


@partial(jax.jit, static_argnums=(1, 2, 3))
def galerkin_stencil(planes: dict, fn: int, cn: int, gridop: str) -> dict:
    """Coarse Galerkin stencil A_c = R A P by comb probing.

    A_c has reach <= 1 in coarse units for both grid operators, so probing
    the composed map T = R \\circ A \\circ P with the 9 period-3 comb
    vectors separates every coefficient exactly:
        A_c[d][i, j] = (T comb_{a,b})[i, j]  where (a, b) = (i+di, j+dj) mod 3.
    Equal to the explicit R @ A @ P SpGEMM product (oracle-tested); costs
    9 grid applies instead of two unstructured SpGEMMs + sorts.
    """
    # the combs and the selections are made on the device from index
    # vectors: as numpy constants they were 9 combs and 18 index planes of
    # cn^2 entries in the program's text, which the compiler then folded
    # the whole probe pipeline over (3.8 GB of HLO and 105 s a build at
    # n = 4480 on a TPU v5e, too large for the compile cache to keep)
    ii = jnp.arange(cn)[:, None]
    jj = jnp.arange(cn)[None, :]
    dtype = next(iter(planes.values())).dtype

    def T(comb):
        return restrict_grid(
            stencil_apply(planes, prolong_grid(comb, fn, cn, gridop)), cn, gridop
        )

    probes = {
        (a, b): T(((ii % 3 == a) & (jj % 3 == b)).astype(dtype))
        for a in range(3) for b in range(3)
    }

    out = {}
    for di in (-1, 0, 1):
        for dj in (-1, 0, 1):
            if gridop == "injection" and (di, dj) != (0, 0):
                # injection Galerkin on a <=1-reach fine stencil couples
                # only even fine points two apart — identically zero
                # off-diagonal; drop the planes rather than carry zeros
                continue
            # plane[i,j] = probes[(i+di)%3, (j+dj)%3][i,j]
            plane = jnp.zeros((cn, cn), dtype)
            for (a, b), t in probes.items():
                mine = ((ii + di) % 3 == a) & ((jj + dj) % 3 == b)
                plane = jnp.where(mine, t, plane)
            out[(di, dj)] = plane
    return out


@partial(jax.jit, static_argnames=("offsets", "iters"))
def _power_rho(planes_tuple, offsets, D_inv, x0, iters: int):
    """rho(D^-1 A) by power iteration + Rayleigh quotient, one compiled
    fori_loop (the r3 host-loop form was ~38 s at n=2000 on CPU)."""
    planes = dict(zip(offsets, planes_tuple))

    def mv(v):
        return D_inv * stencil_apply(planes, v)

    def body(_, v):
        w = mv(v)
        return w / jnp.linalg.norm(w)

    v = jax.lax.fori_loop(0, iters, body, x0)
    return jnp.vdot(v, mv(v))


def _rho(planes: dict, D_inv, n: int, seed=0, iters=15):
    rng = np.random.default_rng(seed)
    x0 = jnp.asarray(rng.random((n, n)), dtype=jnp.asarray(D_inv).dtype)
    offsets = tuple(planes.keys())
    return float(
        _power_rho(tuple(planes.values()), offsets, D_inv, x0, iters)
    )


def build_hierarchy(
    n: int, levels: int, gridop: str = "linear", omega: float = 4.0 / 3.0,
    dtype=jnp.float32, planes: dict | None = None,
):
    """[(stencil planes, omega*D^-1 plane, grid size)] per level.

    The smoother weight follows the pyamg formula omega / rho(D^-1 A)
    (examples/gmg.py:WeightedJacobi), with rho from the jitted power
    iteration. ``planes`` overrides the level-0 operator (default:
    5-point Poisson).
    """
    st = poisson_stencil(n, dtype) if planes is None else planes
    out = []
    rhos = []
    # one span a build: what a first solve pays before its program
    # (the comb probes' and the power iterations' compiles are in it)
    with telemetry.span("gmg.build_hierarchy", levels=int(levels)) as sp:
        for lvl in range(levels):
            D_inv = 1.0 / st[(0, 0)]
            rhos.append(_rho(st, D_inv, n))
            w = jnp.asarray(omega / rhos[-1], dtype) * D_inv
            out.append((st, w, n))
            if lvl < levels - 1:
                cn = n // 2
                st = galerkin_stencil(st, n, cn, gridop)
                n = cn
        sp.set_sync(out)
        sp.annotate(
            sizes=[n for _, _, n in out], rho=[round(r, 6) for r in rhos],
            bytes=sum(int(a.size) * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(out)
                      if hasattr(a, "dtype")),
        )
    return out


def shard_hierarchy_grid(hierarchy, mesh, axis: str = "shards",
                         replicate_below: int = 1024):
    """Lay a grid hierarchy out over a device mesh in row blocks.

    Every level's [n, n] planes (and the solve vectors) get a row
    sharding ``P(axis, None)``; the scalars of the fine level are
    replicated over the mesh. :func:`grid_operator` and
    :func:`make_vcycle` read that lay-out off the arrays
    (:func:`_level_rows`) and run each stencil apply and transfer of such
    a level on a shard's own rows and one row from each neighbour
    (``shard_map`` and ``ppermute``: two exchanges an apply, one a
    transfer; read off the program compiled for four v5e chips, the
    partitioner's own answer to ``stencil_apply``'s pad and slices was
    five exchanges an apply and a gather of index vectors in the
    restriction); CG's own vector ops, its two dot products and the
    crossings between flat vectors and grids are the partitioner's.
    Levels with fewer than ``replicate_below`` total grid points
    (``n * n``, the flat vector length — so the default 1024 still
    shards a 64x64 level) are fully REPLICATED:
    the same zero-collective coarse tail that fixes the reference's
    weak-scaling collapse (SURVEY §6, parallel/multigrid.py), expressed
    as a sharding annotation instead of a gather/scatter pair.

    Returns ``(hierarchy, vec_sharding)``: a new hierarchy with
    identically-shaped, device-committed arrays, plus the sharding to
    apply to flat [n0*n0] solve vectors (row-block layout matching level
    0 — replicated when level 0 itself could not shard). Use with
    :func:`make_vcycle` / ``linalg.cg`` unchanged — computation follows
    data placement.

    A level row-shards only when its n divides the mesh size (GSPMD
    device_put rejects ragged dimension splits); everything else is
    replicated, which is also the intended coarse-tail layout.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    S = int(mesh.devices.size)
    row_sharded = NamedSharding(mesh, P(axis, None))
    replicated = NamedSharding(mesh, P())

    out = []
    vec_sharding = NamedSharding(mesh, P())
    for lvl, (st, w, n) in enumerate(hierarchy):
        shardable = n % S == 0 and n * n >= replicate_below
        sh = row_sharded if shardable else replicated
        if lvl == 0 and shardable:
            vec_sharding = NamedSharding(mesh, P(axis))
        st_s = {
            d: jax.device_put(p, sh if getattr(p, "ndim", 0) == 2 else replicated)
            for d, p in st.items()
        }
        w_s = jax.device_put(w, sh if getattr(w, "ndim", 0) == 2 else replicated)
        out.append((st_s, w_s, n))
    return out, vec_sharding


def _level_rows(st: dict, n: int, w=None) -> _Rows | None:
    """The mesh a level lies over in row blocks, read off its own arrays
    (what :func:`shard_hierarchy_grid` leaves there): its ``[n, n]`` planes
    and weight all under one ``NamedSharding`` whose spec cuts the rows over
    one axis and nothing else; a level of scalars alone (the fine level),
    replicated over a mesh of one axis, lies over that axis. None for one
    device, a replicated level, a side the shards do not divide, numpy
    arrays and an outer trace."""
    from jax.sharding import NamedSharding

    arrays = [*st.values()] + ([] if w is None else [w])
    grids = [a for a in arrays if getattr(a, "ndim", 0) == 2]
    try:
        shardings = {a.sharding for a in grids or arrays}
    except AttributeError:  # a numpy array; a tracer
        return None
    sharding = shardings.pop()
    if shardings or not isinstance(sharding, NamedSharding):
        return None
    mesh, spec = sharding.mesh, tuple(sharding.spec)
    if grids:  # the rows over one axis, the columns whole
        if not (spec and isinstance(spec[0], str)) or any(spec[1:]):
            return None
        axis = spec[0]
    elif any(spec) or len(mesh.axis_names) != 1:
        return None
    else:
        axis = mesh.axis_names[0]
    rows = _Rows(mesh, axis)
    return rows if rows.shards > 1 and n % rows.shards == 0 else None


def _fine_kernel(st: dict, n: int, w=None, rows: _Rows | None = None) -> bool:
    """Whether a level's applies take ``kernels.grid_stencil`` (the on-tile
    form) rather than :func:`stencil_apply`, read off the level's own arrays
    where the operator is declared: a 5-point stencil of float32 scalars
    (and a scalar weight, where the smoother reads one), a side of whole
    128-lane vregs, everything resident on one TPU or, for a level laid
    over a mesh (``rows``), on that mesh's TPUs with a shard's rows in
    whole blocks of two row groups. Plane coefficients (the coarse levels),
    another side or dtype, the CPU and an outer trace all read False and
    keep ``stencil_apply``."""
    scalars = [*st.values()] + ([] if w is None else [w])
    try:
        devices = set().union(*(a.devices() for a in scalars))
    except (AttributeError, TypeError):  # a numpy scalar; a tracer
        return False
    if rows is None:
        placed = len(devices) == 1
    else:
        placed = (devices == set(rows.mesh.devices.flat)
                  and (n // rows.shards) % (2 * grid_stencil.SUBLANES) == 0)
    return (
        n % grid_stencil.LANES == 0
        and set(st) == grid_stencil.FIVE_POINT
        and all(getattr(c, "shape", None) == () and c.dtype == jnp.float32
                for c in scalars)
        and placed and {d.platform for d in devices} == {_KERNEL_PLATFORM}
    )


# the platform the kernel is compiled for. A test's CPU drive sets "cpu",
# and the kernel then runs interpreted (_fine_stencil)
_KERNEL_PLATFORM = "tpu"


def _fine_stencil(form: str, offsets, planes, w, x, r=None,
                  rows: _Rows | None = None):
    """One use of the fine level's stencil through the kernel (the forms:
    ``kernels/grid_stencil.py``); over a mesh (``rows``) on each shard's own
    block, with its neighbours' edge rows as the kernel's halo."""
    scalars = jnp.stack([*planes] + ([] if w is None else [w]))
    kernel = partial(
        grid_stencil.stencil5, form=form, offsets=offsets,
        interpret=jax.default_backend() != "tpu")  # a test's CPU drive
    if rows is None:
        return kernel(scalars, x, r)
    return rows.map(
        lambda scalars, x, *r: kernel(scalars, x, *r, halo=rows.edge_rows(x)),
        scalars, x, *([] if r is None else [r]))


@dataclasses.dataclass(frozen=True)
class _Cycle:
    """``apply`` of the V-cycle operator: equal by value for two
    hierarchies of the same level sizes, offsets and grid operator, which
    is what lets ``linalg.cg`` find its compiled program again.
    ``fine_kernel``: level 0's two stencil applies are the kernel's
    (:func:`_fine_kernel`, decided where the operator is declared).
    ``rows``: per level the mesh it lies over in row blocks or None
    (:func:`_level_rows`; empty: no level does)."""

    static: tuple  # per level (n, offsets)
    gridop: str
    fine_kernel: bool = False
    rows: tuple = ()

    def level(self, arrays, r, lvl):
        (planes, w), (n, offsets) = arrays[lvl], self.static[lvl]
        st = dict(zip(offsets, planes))
        kernel = self.fine_kernel and lvl == 0 and r.dtype == jnp.float32
        rows = self.rows[lvl] if self.rows else None
        with jax.named_scope(f"gmg.l{lvl}"):
            x = w * r
            if lvl == len(self.static) - 1:
                return x
            cn = self.static[lvl + 1][0]
            residual = (_fine_stencil("residual", offsets, planes, w, r, rows=rows)
                        if kernel else r - stencil_apply(st, x, rows=rows))
            coarse_r = restrict_grid(residual, cn, self.gridop, rows=rows)
        coarse_x = self.level(arrays, coarse_r, lvl + 1)
        with jax.named_scope(f"gmg.l{lvl}"):
            x = x + prolong_grid(coarse_x, n, cn, self.gridop, rows=rows)
            if kernel:
                return _fine_stencil("smooth", offsets, planes, w, x, r, rows=rows)
            return x + w * (r - stencil_apply(st, x, rows=rows))

    def halo_exchanges(self) -> int:
        """The exchanges one cycle holds: two an apply and one a transfer
        of every level above the coarsest that lies over a mesh."""
        sides = [n for n, _ in self.static]
        return sum(
            4 + 2 * (self.gridop != "injection" and rows.transfers(n, cn))
            for rows, n, cn in zip(self.rows, sides, sides[1:]) if rows)

    def __call__(self, arrays, r_flat):
        n0 = self.static[0][0]
        return self.level(arrays, r_flat.reshape(n0, n0), 0).reshape(-1)


@dataclasses.dataclass(frozen=True)
class _GridApply:
    """``apply`` of one level's operator on flat vectors; ``fine_kernel``
    as :class:`_Cycle`'s, ``rows`` the mesh the level lies over or None."""

    n: int
    offsets: tuple
    fine_kernel: bool = False
    rows: _Rows | None = None

    def __call__(self, planes, v):
        x = v.reshape(self.n, self.n)
        if self.fine_kernel and v.dtype == jnp.float32:
            return _fine_stencil("apply", self.offsets, planes, None, x,
                                 rows=self.rows).reshape(-1)
        return stencil_apply(dict(zip(self.offsets, planes)), x,
                             rows=self.rows).reshape(-1)


def _declare(apply, operands, n: int, **describe):
    from ..linalg import LinearOperator

    dtype = jax.tree_util.tree_leaves(operands)[0].dtype
    return LinearOperator((n * n, n * n), dtype=np.dtype(dtype), apply=apply,
                          operands=operands, describe=describe)


def grid_operator(hierarchy, lvl: int = 0):
    """Level ``lvl``'s operator as a ``LinearOperator`` on flat [N] vectors
    that declares its planes: the ``A`` of ``linalg.cg(A, b, M=vcycle)``."""
    st, _, n = hierarchy[lvl]
    rows = _level_rows(st, n)
    kernel = _fine_kernel(st, n, rows=rows)
    # over a mesh the product is one apply: an exchange a side
    return _declare(_GridApply(n, tuple(st.keys()), kernel, rows),
                    tuple(st.values()), n, fine_stencil_kernels=int(kernel),
                    **({"halo_exchanges": 2} if rows else {}))


def make_vcycle(hierarchy, gridop: str = "linear"):
    """One V-cycle as a ``LinearOperator`` on flat [N] vectors (the ``M`` of
    ``linalg.cg``; also callable, ``vc(r)``): pre-smooth, restrict the
    residual, recurse, prolong-correct, post-smooth; the coarsest level
    applies the smoother once (examples/gmg.py:GMG._cycle). It declares the
    hierarchy's planes and weights as its operands, with the level sizes
    and ``gridop`` static."""
    # per level the planes in the stencil's own order, then the weight; the
    # grid size and the planes' offsets are the static rest
    arrays = tuple((tuple(st.values()), w) for st, w, _ in hierarchy)
    static = tuple((n, tuple(st.keys())) for st, _, n in hierarchy)
    rows = tuple(_level_rows(st, n, w) for st, w, n in hierarchy)
    st, w, n = hierarchy[0]
    kernel = len(hierarchy) > 1 and _fine_kernel(st, n, w, rows[0])
    cycle = _Cycle(static, gridop, kernel, rows if any(rows) else ())
    # an iteration's fine-level applies that take the kernel: the residual
    # and the post-smoothing here, the product in grid_operator; over a
    # mesh, the exchanges a cycle holds
    return _declare(cycle, arrays, n,
                    precond="gmg_grid", levels=len(hierarchy),
                    fine_stencil_kernels=2 * int(kernel),
                    **({"halo_exchanges": cycle.halo_exchanges()}
                       if cycle.rows else {}))
