"""The linear systems of XGC's collision step, thousands at a time, and
their plain reference: Jacobi-preconditioned BiCGStab, one system at a time.

The gyrokinetic particle-in-cell code XGC advances the nonlinear
Fokker-Planck-Landau collision operator at every mesh vertex and for every
species by backward Euler inside a Picard loop. One Picard iteration is, a
vertex and a species, one linear system on the species' 2-D velocity grid of
32 x 31 = 992 unknowns, a 9-point stencil, (3 * 32 - 2) * (3 * 31 - 2) =
8,554 stored entries, nonsymmetric values on a symmetric pattern; every
system has the same pattern and its own values, and all of them go to the
solver in one call (Kashi et al., IPDPS 2022: Ginkgo's batched BiCGStab with
a scalar Jacobi preconditioner). There is no network to fetch XGC's matrices,
so the pattern is built from those numbers and the **values are generated**:

    A = I - dt C,    C f = div (D grad f + F f)

on the grid ``v_par`` (32 points, the fast axis) x ``v_perp`` (31 points) in
units of the vertex's thermal speed, ``h = EXTENT / 32``, cell centres at
``(ix + 1/2) h - EXTENT / 2`` and ``(iy + 1/2) h`` (the Jacobian ``v_perp`` of
the cylindrical velocity space is left out: pattern, asymmetry and dominance
are what a solver sees). With ``w = v - (u, 0)`` and ``s = |w| / sqrt(theta)``:

- ``D = D_perp I + (D_par - D_perp) w w^T / |w|^2``, a symmetric
  positive-definite tensor with a cross term: ``D_perp = nu theta / (1 + s /
  2)``, ``D_par = D_perp (1 + s^2 / 8) / (1 + s^2 / 4)`` (between ``D_perp / 2``
  and ``D_perp``: diffusion along ``w`` falls off faster than across it, as
  the Landau tensor's does);
- ``F = (D_par / theta) w``: the drag toward the drifting Maxwellian of
  temperature ``theta`` and drift ``u``, which ``D grad f + F f = 0`` makes
  stationary along ``w``; it is what makes the values nonsymmetric;
- finite volumes on the 9-point graph: a cell exchanges with its eight
  neighbours along four families of edges (x, y and the two diagonals). The
  tensor is split onto them with weights that are never negative, ``D = k_x
  e_x e_x^T + k_y e_y e_y^T + k_p d_p d_p^T + k_m d_m d_m^T`` with ``d_p = (1,
  1)``, ``d_m = (1, -1)``, ``k_p = max(D_xy, 0) + e``, ``k_m = max(-D_xy, 0) +
  e``, ``k_x = D_xx - |D_xy| - 2 e``, ``k_y = D_yy - |D_xy| - 2 e``, ``e =
  D_perp / 6`` (the isotropic part on the nine-point Laplacian's weights 2/3
  and 1/6, so that all four corner entries are there; all >= 0 for the
  anisotropy above), every coefficient taken at the edge's midpoint; the drag
  rides the axis edges with the exponentially fitted weights of Chang and
  Cooper (Scharfetter-Gummel): the rate from cell j into cell i across an
  edge of conductance ``k`` is ``(k / h^2) Bern(-P)``, ``P = F . n_ij h / k``,
  ``Bern(x) = x / (e^x - 1)``, which is positive at every cell Peclet number;
- zero-flux boundaries: an edge that leaves the grid does not exist, so the
  rows at the grid's edge hold fewer entries (8,554 in all) and ``C``'s
  columns sum to zero (particles are conserved).

So every off-diagonal entry of A is <= 0, its columns sum to 1, and row i is
**strictly diagonally dominant by 1 - dt (div_h F)_i**, the discrete
divergence of the drag over the faces the cell has (``Bern(x) - Bern(-x) =
-x``): at most ``2 nu`` (at ``w = 0``), so the margin is at least ``1 - 2 dt
nu >= MARGIN`` (``dominance_margin`` measures it; tests/test_xgc_reference.py
holds it), ``||A^-1||_inf <= 1 / MARGIN``, and a residual of relative size r
bounds the error of x by ``r ||b||_inf / MARGIN`` in the maximum norm.

A **species is its dt nu**: ``DT_NU["ion"]`` and ``DT_NU["electron"]``, 20
times apart (the collision frequencies of deuterons and electrons at one
temperature differ by the root of the mass ratio, 60; the step is XGC's one
step for both), times the vertex's collisionality. The electrons' is as
large as the dominance margin allows, and ``EXTENT`` as small as a grid of a
Maxwellian can be (2.75 thermal speeds to a side), because what a system costs
Jacobi-BiCGStab is set by ``dt D / h^2 = dt nu theta / h^2`` (10.8 for the
electrons, a diagonal up to 48): and so is float32's floor, ``eps ||A||``, the
true relative residual under which no float32 answer gets (3e-6 to 5e-6 for
the electrons here). Read on the chip at 32,768 lanes (PR 55): ions 3 to 5
steps (median 4), electrons 13 to 33 (median 20), every lane's true residual
at most 1.27e-5 where 1e-5 was asked of the recurrence's. Stiffer electrons
(``EXTENT`` 4.5, before the isotropic part went onto nine points: 21 to 40
steps, median 29, a diagonal near 87) left answers at a true residual of
1.9e-5 (sandbox CPU, 512 lanes of two seeds), too near the guarantee's 2e-5.
A **vertex** (``systems /
2`` of them) draws from the configuration's ``mesh_seed`` (not from ``--seed``:
``run_draw`` says what a run's seed draws, the vertices' order and units, and
why), smooth in its index with a little noise: a
density and a temperature that fall together along a pedestal-like profile
over a decade (``n`` proportional to ``T^1.5`` to within +-20 %, so that the
collisionality ``n / T^1.5`` stays within [0.8, 1.25] of the species' own);
``theta`` in [0.85, 1.2], the temperature against the one its grid is
normalised by; a drift ``u`` in [-0.5, 0.5]. Lanes are interleaved as
vertices hold them: lane ``2 j`` is the run's j-th vertex's ion system, ``2 j
+ 1`` its electron system; nothing sorts them by species.

The right-hand side is the old state, ``b = f_old``: the vertex's density
times a Maxwellian whose drift and temperature are off the background's by
up to 0.3 and 25 % (so that collisions have something to do), times ``1 +
0.05 xi`` with ``xi`` standard normal a cell (a particle code's distribution is
noisy). The start is ``x0 = b``. The value stack is made on the device in one
program, in CSR order ``[systems, 8554]`` (sorted rows, sorted columns in a
row), as a user's assembly would hand it over.

Nothing here imports the program. The reference is textbook
right-preconditioned BiCGStab (van der Vorst 1992) for one system at a time
in ``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``:
the product as nine shifted multiply-adds on the padded vector from the
generator's own planes, Jacobi as a division by the diagonal plane it reads
itself, run ``REFERENCE_STEPS`` steps with no stopping test but the guard
against a zero denominator, which is past float32's floor for both species
(a tighter stop than the program's). Residuals are taken in float64 with
numpy over the lane's own float32 CSR values.
"""

from __future__ import annotations

import functools

import numpy as np

EXTENT = 5.5  # the grid spans [-2.75, 2.75] x [0, 5.33] thermal speeds
DT_NU = {"ion": 0.016, "electron": 0.32}
COLLISIONALITY = (0.8, 1.25)
MARGIN = 0.2  # 1 - 2 * 0.32 * 1.25
SPECIES = ("ion", "electron")
# Jacobi-BiCGStab on an electron system is at float32's floor (true relative
# residual 3e-6 to 5e-6) after 40 steps and stays there at 60, 80, 120 and
# 200; an ion system (1e-7) after 10 (sandbox CPU, PR 55: six lanes of two
# seeds; tests/test_xgc_reference.py holds 120 against 240)
REFERENCE_STEPS = 120


def offsets_of(grid) -> tuple:
    nx = int(grid[0])
    return (-nx - 1, -nx, -nx + 1, -1, 0, 1, nx - 1, nx, nx + 1)


OFFSETS = offsets_of((32, 31))  # the source's: -33, -32, -31, -1, 0, 1, 31, 32, 33


def counts(grid) -> tuple:
    """(rows, entries) of the 9-point pattern on an nx x ny grid."""
    nx, ny = (int(s) for s in grid)
    return nx * ny, (3 * nx - 2) * (3 * ny - 2)


def inside_grid(grid) -> np.ndarray:
    """``[9, ny, nx]`` booleans: whether a cell's neighbour in each of the nine
    directions (the order of the offsets) lies inside the grid."""
    nx, ny = (int(s) for s in grid)
    inside = np.ones((9, ny, nx), dtype=bool)
    for k, (dy, dx) in enumerate((dy, dx) for dy in (-1, 0, 1)
                                 for dx in (-1, 0, 1)):
        if dy < 0:
            inside[k, 0] = False
        if dy > 0:
            inside[k, -1] = False
        if dx < 0:
            inside[k, :, 0] = False
        if dx > 0:
            inside[k, :, -1] = False
    return inside


def pattern_of(grid) -> tuple:
    """``(indptr, indices, entry)`` of the 9-point pattern, rows and the
    columns of a row rising; ``entry`` is where a stored entry lies in the
    nine planes ``[9, rows]`` laid end to end."""
    n, nnz = counts(grid)
    inside = inside_grid(grid).reshape(9, n).T  # [n, 9]: a row's entries
    rows, ks = np.nonzero(inside)
    offsets = np.asarray(offsets_of(grid), dtype=np.int64)
    indptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(inside.sum(axis=1), out=indptr[1:])
    if int(indptr[-1]) != nnz:
        raise AssertionError("the pattern's count is not (3 nx - 2)(3 ny - 2)")
    return (indptr, (rows + offsets[ks]).astype(np.int32),
            (ks.astype(np.int64) * n + rows).astype(np.int32))


def lane_parameters(systems: int, mesh_seed: int) -> dict:
    """Every lane's numbers of the mesh ``mesh_seed`` draws, ``[systems]``
    float32 each, in the mesh's own order of vertices: the vertex's draws
    (smooth in its index, a little noise) and the species'."""
    systems = int(systems)
    vertices = (systems + 1) // 2
    rng = np.random.default_rng([int(mesh_seed), 55])
    t = (np.arange(vertices) + 0.5) / vertices
    phase = rng.uniform(0.0, 2 * np.pi, size=6)
    turns = rng.uniform(1.0, 3.0, size=6)

    def smooth(k, noise):
        return (np.sin(2 * np.pi * turns[k] * t + phase[k])
                + noise * rng.uniform(-1.0, 1.0, size=vertices)) / (1 + noise)

    profile = 10.0 ** (0.5 * smooth(0, 0.05))  # a decade along the profile
    temperature = profile ** (2.0 / 3.0)
    lo, hi = COLLISIONALITY
    coll = np.exp(0.5 * (np.log(hi) - np.log(lo)) * smooth(1, 0.3)
                  + 0.5 * (np.log(hi) + np.log(lo)))
    density = profile * coll
    vertex = {
        "density": density, "temperature": temperature,
        "collisionality": density / temperature ** 1.5,
        "theta": 1.025 + 0.175 * smooth(2, 0.3),
        "drift": 0.5 * smooth(3, 0.3),
        "drift_old": 0.3 * smooth(4, 0.5),
        "heat_old": 0.25 * smooth(5, 0.5),
    }
    lane_vertex = np.arange(systems) // 2
    out = {k: v[lane_vertex].astype(np.float32) for k, v in vertex.items()}
    species = np.arange(systems) % 2  # 0 ion, 1 electron: interleaved
    base = np.where(species == 0, DT_NU["ion"], DT_NU["electron"])
    out["dt_nu"] = (base * out["collisionality"]).astype(np.float32)
    out["species"] = species.astype(np.int8)
    return out


def run_draw(systems: int, seed: int) -> tuple:
    """What a run's seed draws: ``(order, unit)``, ``[systems]`` each. Lane
    ``2 j + s`` of the run is the mesh's lane ``order[2 j + s] = 2 perm[j] +
    s`` (the order in which the code's partition hands its vertices over: a
    permutation of the vertices), and its density is in units of ``unit``, a
    power of two from 1/8 to 8 a vertex (the reference density the vertex's
    distribution is normalised by). Both are exact in floating point (a
    lane's arithmetic does not depend on where in the batch it stands, and a
    power of two scales every vector of its solve without rounding), so every
    run solves the same systems to the same bits in the same number of
    steps: the slowest of 16,384 electron lanes decides a call's length, a
    fresh draw of the mesh moves it by a step or two (3.2 % of the call
    each: ``solve_s`` 0.3496, 0.3609, 0.3725 over six seeds, my chip runs,
    PR 55), and ``solve_s`` is held to 2 %."""
    systems = int(systems)
    if systems % 2:
        raise ValueError("two species a vertex: an even count of systems")
    rng = np.random.default_rng([int(seed), 58])
    perm = rng.permutation(systems // 2)
    order = (2 * perm[:, None] + np.arange(2)[None, :]).reshape(-1)
    unit = np.repeat(2.0 ** rng.integers(-3, 4, size=systems // 2), 2)
    return order, unit.astype(np.float32)


def run_parameters(sizes: dict, seed: int) -> tuple:
    """``(params, order)`` of a run: the mesh's lanes
    (``sizes["mesh_seed"]``) in the run's order and units."""
    order, unit = run_draw(sizes["systems"], seed)
    mesh = lane_parameters(sizes["systems"], sizes["mesh_seed"])
    params = {k: v[order] for k, v in mesh.items()}
    params["density"] = params["density"] * unit
    return params, order


@functools.lru_cache(maxsize=None)
def _programs(grid: tuple):
    """The generator's two programs on the default device: every lane's nine
    planes ``[B, 9, rows]`` (row layout: slot i of plane k holds ``A[i, i +
    o_k]``, zero where the neighbour is outside the grid), and the old state
    ``[B, rows]``."""
    import jax
    import jax.numpy as jnp

    nx, ny = grid
    h = EXTENT / 32.0  # the source's spacing, whatever grid a test asks for
    f32 = jnp.float32

    def bern(x):
        small = jnp.abs(x) < 1e-3
        safe = jnp.where(small, 1.0, x)
        return jnp.where(small, 1.0 - x / 2, safe / jnp.expm1(safe))

    def coefficients(px, py, dt_nu, theta, drift):
        """At the points (px, py) of the velocity plane: dt/h^2 times the
        four conductances, and the cell Peclet numbers along x and y."""
        wx, wy = px - drift, py
        w2 = wx * wx + wy * wy
        s2 = w2 / theta
        s = jnp.sqrt(s2)
        d_perp = dt_nu * theta / (1 + s / 2)
        d_par = d_perp * (1 + s2 / 8) / (1 + s2 / 4)
        gap = (d_par - d_perp) / jnp.maximum(w2, 1e-12)
        dxx, dyy, dxy = d_perp + gap * wx * wx, d_perp + gap * wy * wy, gap * wx * wy
        e = d_perp / 6
        kx, ky = dxx - jnp.abs(dxy) - 2 * e, dyy - jnp.abs(dxy) - 2 * e
        kp, km = jnp.maximum(dxy, 0.0) + e, jnp.maximum(-dxy, 0.0) + e
        # P = F . n h / k with F = (d_par / theta) w
        pecx = d_par / theta * wx * h / kx
        pecy = d_par / theta * wy * h / ky
        return kx / h**2, ky / h**2, kp / h**2, km / h**2, pecx, pecy

    def planes(dt_nu, theta, drift):
        lane = (slice(None), None, None)
        dt_nu, theta, drift = (a.astype(f32)[lane] for a in (dt_nu, theta, drift))
        cx = ((jnp.arange(nx, dtype=f32) + 0.5) * h - EXTENT / 2)[None, None, :]
        cy = ((jnp.arange(ny, dtype=f32) + 0.5) * h)[None, :, None]
        ix = jnp.arange(nx)[None, None, :]
        iy = jnp.arange(ny)[None, :, None]
        east, north = ix < nx - 1, iy < ny - 1
        # the edges a cell owns: towards +x, +y, (+x, +y) and (-x, +y)
        kx, _, _, _, pecx, _ = coefficients(cx + h / 2, cy + 0 * cx, dt_nu, theta, drift)
        _, ky, _, _, _, pecy = coefficients(cx + 0 * cy, cy + h / 2, dt_nu, theta, drift)
        _, _, kp, _, _, _ = coefficients(cx + h / 2, cy + h / 2, dt_nu, theta, drift)
        _, _, _, km, _, _ = coefficients(cx - h / 2, cy + h / 2, dt_nu, theta, drift)
        zero = jnp.zeros((), f32)
        # the rate into a cell from its +x neighbour and into that one from it
        from_e = jnp.where(east, kx * bern(-pecx), zero)
        to_e = jnp.where(east, kx * bern(pecx), zero)
        from_n = jnp.where(north, ky * bern(-pecy), zero)
        to_n = jnp.where(north, ky * bern(pecy), zero)
        ne = jnp.where(east & north, kp, zero)  # both ways: no drag on it
        nw = jnp.where((ix > 0) & north, km, zero)

        def shifted(a, dy, dx):
            """``a`` of the cell at (iy + dy, ix + dx), zero outside."""
            a = jnp.pad(a, ((0, 0), (1, 1), (1, 1)))
            return a[:, 1 + dy:1 + dy + ny, 1 + dx:1 + dx + nx]

        from_w, to_w = shifted(to_e, 0, -1), shifted(from_e, 0, -1)
        from_s, to_s = shifted(to_n, -1, 0), shifted(from_n, -1, 0)
        sw, se = shifted(ne, -1, -1), shifted(nw, -1, 1)
        leaves = to_e + to_w + to_n + to_s + ne + nw + sw + se
        stack = jnp.stack([-sw, -from_s, -se, -from_w, 1.0 + leaves,
                           -from_e, -nw, -from_n, -ne], axis=1)
        return stack.reshape(stack.shape[0], 9, ny * nx)

    def old_state(density, theta, drift, drift_old, heat_old, noise):
        lane = (slice(None), None, None)
        density, theta, drift, drift_old, heat_old = (
            a.astype(f32)[lane] for a in (density, theta, drift, drift_old, heat_old))
        cx = ((jnp.arange(nx, dtype=f32) + 0.5) * h - EXTENT / 2)[None, None, :]
        cy = ((jnp.arange(ny, dtype=f32) + 0.5) * h)[None, :, None]
        th = theta * (1 + heat_old)
        wx = cx - (drift + drift_old)
        f = density / (2 * np.pi * th) * jnp.exp(-(wx * wx + cy * cy) / (2 * th))
        return (f.reshape(f.shape[0], -1) * (1 + 0.05 * noise)).astype(f32)

    def csr_values(planes_, entry):
        flat = planes_.reshape(planes_.shape[0], -1)
        # rows of the transposed stack: whole lanes' worth a row, contiguous
        return jnp.take(flat.T, entry, axis=0).T

    return jax.jit(planes), jax.jit(old_state), jax.jit(csr_values)


def planes_of(grid, params: dict, lanes=None):
    """The nine planes ``[len(lanes), 9, rows]`` of the lanes (all: None), on
    the device."""
    planes, _old, _csr = _programs(tuple(int(s) for s in grid))
    pick = slice(None) if lanes is None else np.asarray(lanes)
    return planes(*(params[k][pick] for k in ("dt_nu", "theta", "drift")))


def _csr_values(grid, params: dict, entry):
    """``[systems, nnz]`` float32 on the device, in CSR order."""
    return _programs(tuple(grid))[2](planes_of(grid, params), entry)


def value_stack(sizes: dict, seed: int):
    """What another assembly on the same pattern hands the program (the next
    Picard iteration's values): the value stack of a mesh of its own, drawn
    from ``seed``."""
    grid = tuple(int(s) for s in sizes["velocity_grid"])
    return _csr_values(grid, lane_parameters(int(sizes["systems"]), seed),
                       pattern_of(grid)[2])


def make(sizes: dict, seed: int) -> dict:
    """One run's data: the pattern on the host, the value stack and the old
    states on the device, every lane's parameters, and the sampled lanes."""
    import jax.numpy as jnp

    grid = tuple(int(s) for s in sizes["velocity_grid"])
    n, nnz = counts(grid)
    B = int(sizes["systems"])
    if (n, nnz) != (int(sizes["rows"]), int(sizes["nnz"])):
        raise AssertionError(f"sizes say {sizes['rows']} rows and "
                             f"{sizes['nnz']} entries; the grid has {n}, {nnz}")
    indptr, indices, entry = pattern_of(grid)
    params, order = run_parameters(sizes, seed)
    values = _csr_values(grid, params, entry)
    old = _programs(grid)[1]
    # the mesh's noise, a lane's own, in the run's order
    noise = np.random.default_rng([int(sizes["mesh_seed"]), 56]).standard_normal(
        (B, n), dtype=np.float32)[order]
    b = old(*(params[k] for k in ("density", "theta", "drift", "drift_old",
                                  "heat_old")), jnp.asarray(noise))
    tol_rel = float(sizes["tol_rel"])
    return {
        "seed": int(seed), "grid": grid, "rows": n, "nnz": nnz,
        "diags": len(offsets_of(grid)), "systems": B,
        "indptr": indptr, "indices": indices, "entry": entry,
        "values": values, "b": b,
        "params": params, "order": order, "species": params["species"],
        "tol_rel": tol_rel, "maxiter": int(sizes["maxiter"]),
        "conv_test_iters": int(sizes["conv_test_iters"]),
        "check_sample": int(sizes["check_sample"]),
    }


def lane_planes(data: dict, lane: int) -> np.ndarray:
    """Lane ``lane``'s nine planes ``[9, rows]`` in the row layout, zero
    where the neighbour is outside the grid: the generator's own, read back
    from the lane's row of the value stack, entry for entry."""
    planes = np.zeros(data["diags"] * data["rows"], dtype=np.float32)
    planes[data["entry"]] = np.asarray(data["values"][lane])
    return planes.reshape(data["diags"], data["rows"])


def sample_lanes(data: dict) -> np.ndarray:
    """The compared lanes: ``check_sample`` drawn from the seed, half of each
    species, and the last lane."""
    B, k = data["systems"], min(int(data["check_sample"]), data["systems"])
    rng = np.random.default_rng([data["seed"], 57])
    ions = np.arange(0, B, 2)
    electrons = np.arange(1, B, 2)
    picks = np.concatenate([
        rng.choice(ions, size=min(k - k // 2, len(ions)), replace=False),
        rng.choice(electrons, size=min(k // 2, len(electrons)), replace=False),
        [B - 1]]).astype(np.int64)
    return np.unique(picks)


def dominance_margin(data: dict, lanes) -> np.ndarray:
    """``min_i (|a_ii| - sum_j |a_ij|)`` of the lanes, in float64 from their
    float32 CSR values."""
    vals = np.abs(np.asarray(data["values"][np.asarray(lanes)], dtype=np.float64))
    rows = np.repeat(np.arange(data["rows"]), np.diff(data["indptr"]))
    diag = data["indices"] == rows
    off = np.add.reduceat(np.where(diag, 0.0, vals), data["indptr"][:-1], axis=1)
    return (vals[:, diag] - off).min(axis=1)


def apply_f64(data: dict, values, x) -> np.ndarray:
    """A x in float64 for one lane's float32 CSR ``values``. Every row holds
    its diagonal, so ``reduceat`` meets no empty row."""
    x = np.asarray(x, dtype=np.float64)
    prod = np.asarray(values, dtype=np.float64) * x[data["indices"]]
    return np.add.reduceat(prod, data["indptr"][:-1])


def true_relres(data: dict, values, x, b) -> float:
    b64 = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(data, values, x))
                 / np.linalg.norm(b64))


@functools.lru_cache(maxsize=None)
def _reference_program(offsets: tuple, steps: int, dtype: str):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    left, right = max(-min(offsets), 0), max(max(offsets), 0)
    centre = offsets.index(0)

    def product(planes, v):
        vp = jnp.pad(v, (left, right))
        n = v.shape[0]
        out = jnp.zeros_like(v)
        for k, o in enumerate(offsets):
            out = out + planes[k] * vp[left + o:left + o + n]
        return out

    def guarded(num, den):
        return num / jnp.where(den == 0, jnp.ones((), dt), den)

    def solve(planes, b, x0):
        planes, b, x = planes.astype(dt), b.astype(dt), x0.astype(dt)
        diagonal = planes[centre]
        r = b - product(planes, x)
        rt = r
        one = jnp.ones((), dt)

        def step(_, st):
            x, r, p, v, rho, alpha, omega = st
            rho_new = jnp.vdot(rt, r)
            beta = guarded(rho_new, rho) * guarded(alpha, omega)
            p = r + beta * (p - omega * v)
            ph = p / diagonal
            v = product(planes, ph)
            alpha = guarded(rho_new, jnp.vdot(rt, v))
            s = r - alpha * v
            sh = s / diagonal
            t = product(planes, sh)
            omega = guarded(jnp.vdot(t, s), jnp.vdot(t, t))
            x = x + alpha * ph + omega * sh
            r = s - omega * t
            return x, r, p, v, rho_new, alpha, omega

        z = jnp.zeros_like(b)
        st = (x, r, z, z, one, one, one)
        return jax.lax.fori_loop(0, steps, step, st)[0]

    return jax.jit(solve)


def reference_bicgstab(data: dict, lane: int, steps: int = REFERENCE_STEPS,
                       dtype: str = "float32") -> np.ndarray:
    """The converged solution of lane ``lane``'s system from ``x0 = b``: the
    textbook recurrence, ``steps`` steps. ``bfloat16`` is the control: the
    nearest precision below the float32 the configuration states."""
    import jax

    planes = lane_planes(data, lane)
    b = data["b"][lane]
    with jax.default_matmul_precision("highest"):
        x = _reference_program(offsets_of(data["grid"]), int(steps), dtype)(
            planes, b, b)
    return np.asarray(x, dtype=np.float32)


def species_counts(data: dict, iters) -> dict:
    """``{species: (min, median, max)}`` of the lanes' iteration counts."""
    iters = np.asarray(iters)
    out = {}
    for k, name in enumerate(SPECIES):
        mine = iters[np.asarray(data["species"]) == k]
        if mine.size:
            out[name] = (int(mine.min()), float(np.median(mine)), int(mine.max()))
    return out


def check(data: dict, answers: list, limits: dict, say=print) -> list:
    """The kept calls' answers against the plain reference on the sampled
    lanes, and the two exact guarantees a call's own counts decide.
    ``answers``: dicts with ``x [B, rows]``, ``iters_lanes [B]`` and
    ``converged [B]``."""
    lanes = sample_lanes(data)
    values = np.asarray(data["values"][lanes])
    bs = np.asarray(data["b"][lanes])
    refs = [reference_bicgstab(data, int(lane)) for lane in lanes]
    worst = {"x_vs_reference": 0.0, "relres_over_asked": 0.0}
    unconverged = mix_lost = 0
    for a in answers:
        x = np.asarray(a["x"])[lanes]
        for j in range(len(lanes)):
            if not np.all(np.isfinite(x[j])):
                worst = dict.fromkeys(worst, float("inf"))
                continue
            x64, r64 = x[j].astype(np.float64), refs[j].astype(np.float64)
            worst["x_vs_reference"] = max(
                worst["x_vs_reference"],
                float(np.linalg.norm(x64 - r64) / np.linalg.norm(r64)))
            worst["relres_over_asked"] = max(
                worst["relres_over_asked"],
                true_relres(data, values[j], x64, bs[j]) / data["tol_rel"])
        unconverged += int(np.size(a["converged"])
                           - np.count_nonzero(a["converged"]))
        by = species_counts(data, a["iters_lanes"])
        say(f"  call {a.get('index')}: iterations (min, median, max) {by}")
        if len(by) == 2 and by["electron"][1] < 3 * by["ion"][1]:
            mix_lost += 1
    say(f"  {len(answers)} answers, {len(lanes)} lanes of each compared")
    out = [{"name": k, "value": v, "limit": float(limits[k]),
            "ok": v <= float(limits[k])} for k, v in worst.items()]
    out += [{"name": k, "value": float(v), "limit": 0.0, "ok": v == 0}
            for k, v in (("lanes_unconverged", unconverged),
                         ("mix_lost", mix_lost))]
    return out


def control_answers(data: dict, answers: list) -> list:
    """The control: the reference put in the program's place on the sampled
    lanes, computed in bfloat16 (the nearest precision below float32); the
    lanes' counts are the program's own."""
    x = np.array(answers[0]["x"], dtype=np.float32)
    for lane in sample_lanes(data):
        x[lane] = reference_bicgstab(data, int(lane), dtype="bfloat16")
    return [dict(answers[0], x=x)]
