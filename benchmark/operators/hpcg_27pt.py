"""HPCG's problem and its plain reference.

HPCG 3.1 (Heroux, Dongarra, Luszczek, SAND2013-8752) as this benchmark knows
it, with no network to read it from:

- the operator of an nx x ny x nz grid: 26 on the diagonal, -1 to each of the
  up to 26 neighbours that exist, a row of the grid's faces, edges and
  corners the shorter; the unknowns numbered with x fastest, then y, then z
  (``[nz, ny, nx]`` flattened);
- preconditioned CG from x = 0, a fixed count of iterations;
- the preconditioner, one V-cycle over ``levels`` levels: each coarser level
  the same rule on the grid of half the side; one symmetric Gauss-Seidel step
  from zero, then ``r - A x`` injected at the even points, the next level's
  answer added at those points, one more symmetric step; the coarsest level
  one step and no solve;
- a symmetric step: every row in turn ``x_i <- (r_i - sum_{j != i} a_ij x_j)
  / a_ii`` from the newest values, then the same backwards.

Departures, all stated by the configuration: float32 where HPCG is float64;
the sweep visits the rows colour by colour (colour = 4 (z % 2) + 2 (y % 2) +
x % 2; forward 7, 3, 5, 6, 1, 2, 4, 0: three odd coordinates, then two, then
one, then the even points, which carry the coarse correction and so must be
read before they are rewritten; backward the reverse; within a colour no row
reads another, so a colour is one simultaneous update), the departure from the
reference's row order that every accelerator implementation makes, paid for
as the specification says in ``iterations`` (``tools/hpcg_opt_iters.py``);
b = A (1 + (u - 1/2) / 2) with u ``default_rng(seed).random(N)`` where HPCG's
is A 1, so that the seed reaches the data and nothing else.

Nothing here imports the program. The reference is straightforward
``jax.numpy`` on lexicographic ``[nz, ny, nx]`` arrays: its own generator of
the 27 planes, the product as 27 shifted multiply-adds on a zero-padded
array, a colour's update as one masked whole pass (``where(colour == c,
update, x)``), injection as ``[::2, ::2, ::2]`` and ``.at[::2, ::2,
::2].add``, a textbook preconditioned CG as a ``fori_loop`` over exactly
``iterations``. Residuals are taken in float64 with numpy.
"""

from __future__ import annotations

import functools

import numpy as np

OFFSETS = tuple((dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                for dx in (-1, 0, 1))
CENTRE = OFFSETS.index((0, 0, 0))
FORWARD = (7, 3, 5, 6, 1, 2, 4, 0)
# a symmetric step's passes: forward, then backward from the last colour but
# one (the backward sweep's first pass would repeat the forward sweep's last
# on the same values, since a colour's update reads no point of that colour)
ORDER = FORWARD + FORWARD[-2::-1]


def make(sizes: dict, seed: int) -> dict:
    """Host data of one run: the right-hand side drawn from the seed. The
    operator and the hierarchy are functions of the sizes alone."""
    nx, ny, nz = (int(s) for s in sizes["grid"])
    dims = (nz, ny, nx)
    u = np.random.default_rng(seed).random(nx * ny * nz).astype(np.float32)
    x_true = 1.0 + 0.5 * (u.astype(np.float64) - 0.5)
    b = apply_f64(x_true, dims).astype(np.float32)
    return {"grid": [nx, ny, nz], "dims": dims, "rows": nx * ny * nz,
            "levels": int(sizes["levels"]),
            "iterations": int(sizes["iterations"]), "b": b}


# -- float64 on the host: the judge's own operator ---------------------------
def apply_f64(u: np.ndarray, dims: tuple) -> np.ndarray:
    """A u in float64. The judge may know what the program may not: every
    off-diagonal entry is -1, so A u = 27 u - (the sum over the 3 x 3 x 3
    box around a point), and the box's sum is three sums of three."""
    u = np.asarray(u, dtype=np.float64).reshape(dims)
    box = u
    for axis in range(3):
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[axis], hi[axis] = slice(None, -1), slice(1, None)
        s = box.copy()
        s[tuple(hi)] += box[tuple(lo)]
        s[tuple(lo)] += box[tuple(hi)]
        box = s
    return (27.0 * u - box).reshape(-1)


def true_relres(x, b, dims: tuple) -> float:
    b64 = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(x, dims)) / np.linalg.norm(b64))


# -- the pieces, in jax.numpy ---------------------------------------------------
def planes_of(dims: tuple, dtype):
    """The 27 planes ``[27, nz, ny, nx]`` of the grid ``dims``: plane ``d``
    holds every row's entry towards its neighbour at ``OFFSETS[d]``."""
    import jax.numpy as jnp

    nz, ny, nx = dims
    z = jnp.arange(nz)[:, None, None]
    y = jnp.arange(ny)[None, :, None]
    x = jnp.arange(nx)[None, None, :]
    out = []
    for dz, dy, dx in OFFSETS:
        if (dz, dy, dx) == (0, 0, 0):
            out.append(jnp.full(dims, 26.0, dtype))
            continue
        there = ((z + dz >= 0) & (z + dz < nz) & (y + dy >= 0) & (y + dy < ny)
                 & (x + dx >= 0) & (x + dx < nx))
        out.append(jnp.where(there, -1.0, 0.0).astype(dtype))
    return jnp.stack(out)


def colours_of(dims: tuple):
    import jax.numpy as jnp

    nz, ny, nx = dims
    return (4 * (jnp.arange(nz) % 2)[:, None, None]
            + 2 * (jnp.arange(ny) % 2)[None, :, None]
            + (jnp.arange(nx) % 2)[None, None, :])


def off_diagonal(planes, u):
    """sum over the 26 neighbours d of planes[d] u[. + d], u zero outside."""
    import jax.numpy as jnp

    nz, ny, nx = u.shape
    up = jnp.pad(u, 1)
    out = jnp.zeros_like(u)
    for d, (dz, dy, dx) in enumerate(OFFSETS):
        if d != CENTRE:
            out = out + planes[d] * up[1 + dz:1 + dz + nz, 1 + dy:1 + dy + ny,
                                       1 + dx:1 + dx + nx]
    return out


def apply_planes(planes, u):
    return planes[CENTRE] * u + off_diagonal(planes, u)


def symgs(planes, colour, r, x):
    """One symmetric step in the configuration's order: fifteen masked whole
    passes, a colour each."""
    import jax
    import jax.numpy as jnp

    order = jnp.asarray(ORDER)

    def one(i, x):
        update = (r - off_diagonal(planes, x)) / planes[CENTRE]
        return jnp.where(colour == order[i], update, x)

    return jax.lax.fori_loop(0, len(ORDER), one, x)


def vcycle(hier, r, lvl: int = 0):
    """One V-cycle on the residual ``r`` of level ``lvl``; ``hier`` per level
    ``(planes, colour)``."""
    import jax.numpy as jnp

    planes, colour = hier[lvl]
    x = symgs(planes, colour, r, jnp.zeros_like(r))
    if lvl == len(hier) - 1:
        return x
    coarse = vcycle(hier, (r - apply_planes(planes, x))[::2, ::2, ::2], lvl + 1)
    x = x.at[::2, ::2, ::2].add(coarse)
    return symgs(planes, colour, r, x)


def hierarchy(dims: tuple, levels: int, dtype="float32") -> list:
    """Per level ``(planes, colour)``, made by one compiled program: op by
    op the generator is several hundred dispatches of grid-sized arrays."""
    import jax
    import jax.numpy as jnp

    def build():
        return [(planes_of(tuple(d >> k for d in dims), jnp.dtype(dtype)),
                 colours_of(tuple(d >> k for d in dims))) for k in range(levels)]

    return jax.jit(build)()


@functools.lru_cache(maxsize=2)
def _reference_program(dims: tuple, levels: int, iterations: int, dtype: str):
    """The hierarchy in ``dtype`` and the jitted solve, kept for the next
    right-hand side of the same sizes."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    hier = hierarchy(dims, levels, dtype)

    def dot(u, v):
        return jnp.sum(u.astype(jnp.float32) * v.astype(jnp.float32))

    @jax.jit
    def solve(hier, b3):
        def body(_, state):
            x, r, p, rho = state
            q = apply_planes(hier[0][0], p)
            alpha = rho / dot(p, q)
            x = x + alpha.astype(dt) * p
            r = r - alpha.astype(dt) * q
            z = vcycle(hier, r)
            rho_new = dot(r, z)
            p = z + (rho_new / rho).astype(dt) * p
            return x, r, p, rho_new

        z = vcycle(hier, b3)
        x, _, _, _ = jax.lax.fori_loop(
            0, iterations, body, (jnp.zeros_like(b3), b3, z, dot(b3, z)))
        return x

    return hier, solve


def reference_cg(b: np.ndarray, dims: tuple, levels: int, iterations: int,
                 dtype="float32"):
    """V-cycle-preconditioned CG from x = 0 for exactly ``iterations``
    iterations, on one device. ``dtype`` is the storage and arithmetic type
    of vectors and planes; dot products accumulate in float32. ``bfloat16``
    is the control: the nearest precision below the float32 the
    configuration states. Returns x as a float32 host array."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        hier, solve = _reference_program(tuple(dims), levels, iterations,
                                         str(dtype))
        b3 = jnp.asarray(np.asarray(b).reshape(dims), dtype=jnp.dtype(dtype))
        x = solve(hier, b3)
        return np.asarray(x.astype(jnp.float32)).reshape(-1)


def compare(x, x_ref, rr_ref: float, b, dims: tuple) -> dict:
    """The numbers a run is judged by, for one answer ``x`` against the
    reference's ``x_ref`` (true relative residual ``rr_ref``) of the same
    right-hand side and iteration count."""
    x64 = np.asarray(x, dtype=np.float64)
    r64 = np.asarray(x_ref, dtype=np.float64)
    rr_x = true_relres(x64, b, dims)
    return {
        "x_vs_reference": float(np.linalg.norm(x64 - r64) / np.linalg.norm(r64)),
        "relres_gap": abs(rr_x - rr_ref) / rr_ref,
        "relres": rr_x,
        "relres_reference": rr_ref,
    }


def check(data: dict, answers: list, limits: dict, say=print) -> list:
    """Comparisons of every sampled answer of the window with the plain
    reference: [{name, value, limit, ok}]. ``answers`` are dicts with the
    host iterate ``x`` and the iteration count the program returned. An
    answer bit-identical to one already compared is not compared again."""
    import time

    dims, its = tuple(data["dims"]), data["iterations"]
    t0 = time.perf_counter()
    x_ref = reference_cg(data["b"], dims, data["levels"], its)
    t1 = time.perf_counter()
    rr_ref = true_relres(x_ref, data["b"], dims)
    say(f"  the reference's solve took {t1 - t0:.2f} s (with its hierarchy "
        f"and compile where they are new), its float64 residual "
        f"{time.perf_counter() - t1:.2f} s")
    worst = {"x_vs_reference": 0.0, "relres_gap": 0.0, "iterations_off": 0.0}
    compared: list = []
    for ans in answers:
        worst["iterations_off"] = max(worst["iterations_off"],
                                      float(abs(int(ans["iters"]) - its)))
        if any(np.array_equal(ans["x"], x) for x in compared):
            say(f"  answer of call {ans['index']}: bit-identical to one compared")
            continue
        compared.append(ans["x"])
        if not np.all(np.isfinite(ans["x"])):
            worst["x_vs_reference"] = float("inf")
            continue
        nums = compare(ans["x"], x_ref, rr_ref, data["b"], dims)
        say(f"  answer of call {ans['index']}: " + ", ".join(
            f"{k} {v:.6e}" for k, v in nums.items()))
        for k in ("x_vs_reference", "relres_gap"):
            worst[k] = max(worst[k], float(nums[k]))
    return [{"name": k, "value": v, "limit": float(limits[k]),
             "ok": v <= float(limits[k])} for k, v in worst.items()]


def control_answers(data: dict, answers: list) -> list:
    """The control: the reference put in the program's place, computed in
    bfloat16 (the nearest precision below the configuration's float32)."""
    x = reference_cg(data["b"], tuple(data["dims"]), data["levels"],
                     data["iterations"], dtype="bfloat16")
    return [{"x": x, "iters": data["iterations"], "index": 0, "request": 0}]
