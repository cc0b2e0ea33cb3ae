"""The upstream suite's geometric-multigrid row and its plain reference.

``examples/gmg.py`` of nv-legate/legate.sparse: conjugate gradients on the
5-point Poisson operator of an n x n grid (4 on the diagonal, -1 to the four
neighbours, zero outside the grid), preconditioned by one V-cycle an
iteration:

- smoothing by weighted Jacobi, x + w (r - A x) with w = omega / diag(A) and
  omega = (4/3) / rho(D^-1 A), rho the Rayleigh quotient after 15 steps of
  the power iteration on D^-1 A from ``default_rng(0).random((n, n))``;
- restriction R by full weighting, 1-2-1 over 4 an axis at stride 2:
  (R u)[c] = u[2c-1]/4 + u[2c]/2 + u[2c+1]/4 along each axis, u zero outside
  the grid, with ``coarse_n = fine_n // 2``; prolongation P = R^T;
- coarse operators by Galerkin's rule, A_c = R A P: nine coefficient planes
  a level, since R A P of a stencil of reach one has reach one;
- the cycle: pre-smooth from zero, restrict the residual, recurse, add the
  prolonged correction, post-smooth; the coarsest level smooths once.

Departures from upstream's ``gmg.py``, all stated by the configuration:
float32 where upstream is float64; the power iteration's start vector is
numpy's ``default_rng(0)`` (upstream: its own generator's); the right-hand
side is ``default_rng(seed).random(N)`` with the run's seed (upstream: seed
0), so that the seed reaches the data and nothing else.

Nothing here imports the program. The reference is the same preconditioned
CG in straightforward ``jax.numpy`` on 2-D arrays: the operators by shifted
slices, R and P from their definitions above, R A P by probing the
reference's own composed map with the nine period-3 comb vectors (tied to
scipy's explicit ``R @ A @ P`` by ``tests/test_gmg_reference.py``), a
Python-unrolled recursive cycle and a ``fori_loop`` over exactly
``iterations``. Residuals are taken in float64 with numpy.
"""

from __future__ import annotations

import functools

import numpy as np

OFFSETS = tuple((di, dj) for di in (-1, 0, 1) for dj in (-1, 0, 1))
POWER_STEPS = 15
OMEGA = 4.0 / 3.0


def make(sizes: dict, seed: int) -> dict:
    """Host data of one run: the right-hand side drawn from the seed. The
    operator and the hierarchy are functions of the sizes alone."""
    if sizes.get("gridop", "linear") != "linear":
        raise ValueError("the reference restricts by full weighting only")
    n = int(sizes["grid"])
    b = np.random.default_rng(seed).random(n * n).astype(np.float32)
    return {"grid": n, "rows": n * n, "levels": int(sizes["levels"]),
            "gridop": "linear", "iterations": int(sizes["iterations"]), "b": b}


# -- float64 on the host: the judge's own operator ---------------------------
def apply_f64(u: np.ndarray, n: int) -> np.ndarray:
    u = np.asarray(u, dtype=np.float64).reshape(n, n)
    out = 4.0 * u
    out[:, 1:] -= u[:, :-1]
    out[:, :-1] -= u[:, 1:]
    out[1:, :] -= u[:-1, :]
    out[:-1, :] -= u[1:, :]
    return out.reshape(-1)


def true_relres(x, b, n: int) -> float:
    b64 = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(x, n)) / np.linalg.norm(b64))


# -- the pieces, in jax.numpy ---------------------------------------------------
def apply_fine(u):
    """The 5-point operator on the grid, by shifted slices."""
    out = 4.0 * u
    out = out.at[:, 1:].add(-u[:, :-1])
    out = out.at[:, :-1].add(-u[:, 1:])
    out = out.at[1:, :].add(-u[:-1, :])
    out = out.at[:-1, :].add(-u[1:, :])
    return out


def apply_planes(planes, u):
    """A level's nine-point operator: (A u)[i, j] = sum over (di, dj) of
    planes[(di, dj)][i, j] u[i + di, j + dj], u zero outside the grid."""
    import jax.numpy as jnp

    n = u.shape[0]
    up = jnp.pad(u, 1)
    out = jnp.zeros_like(u)
    for (di, dj), c in planes.items():
        out = out + c * up[1 + di:1 + di + n, 1 + dj:1 + dj + n]
    return out


def _restrict_rows(u):
    import jax.numpy as jnp

    cn = u.shape[0] // 2
    up = jnp.pad(u, ((1, 1), (0, 0)))  # up[k] = u[k - 1]
    quarter, half = jnp.asarray(0.25, u.dtype), jnp.asarray(0.5, u.dtype)
    return (quarter * up[0:2 * cn:2] + half * up[1:2 * cn + 1:2]
            + quarter * up[2:2 * cn + 2:2])


def restrict(u):
    """R u: full weighting along the rows, then along the columns."""
    return _restrict_rows(_restrict_rows(u).T).T


def _prolong_rows(y, fn: int):
    """R^T along the rows: fine row 2c takes y[c]/2, fine row 2c+1 takes
    (y[c] + y[c+1])/4 with y past its end zero, and a last odd fine row
    that no coarse point reaches stays zero."""
    import jax.numpy as jnp

    cn = y.shape[0]
    here = jnp.repeat(y, 2, axis=0)  # here[f] = y[f // 2]
    after = jnp.repeat(jnp.pad(y[1:], ((0, 1), (0, 0))), 2, axis=0)
    odd = (jnp.arange(2 * cn) % 2 == 1)[:, None]
    quarter, half = jnp.asarray(0.25, y.dtype), jnp.asarray(0.5, y.dtype)
    out = jnp.where(odd, quarter * (here + after), half * here)
    return jnp.pad(out, ((0, fn - 2 * cn), (0, 0)))


def prolong(y, fn: int):
    """P y = R^T y on a fine grid of side ``fn``."""
    return _prolong_rows(_prolong_rows(y, fn).T, fn).T


def level_operator(planes):
    """A level's operator as a function of the grid: the 5-point function
    for the fine level (``planes`` None), else its nine planes applied."""
    return apply_fine if planes is None else functools.partial(apply_planes, planes)


def galerkin_planes(planes, fn: int):
    """The nine float32 planes of R A P for the level of side ``fn`` whose
    operator is ``level_operator(planes)``, by probing: R A P has reach one,
    so the comb of every third point in each axis, shifted by (a, b),
    excites at any coarse point exactly one of its nine neighbours, and the
    composed map's answer there is that neighbour's coefficient."""
    import jax.numpy as jnp

    op = level_operator(planes)
    cn = fn // 2
    i = jnp.arange(cn)[:, None]
    j = jnp.arange(cn)[None, :]
    probes = {}
    for a in range(3):
        for b in range(3):
            comb = ((i % 3 == a) & (j % 3 == b)).astype(jnp.float32)
            probes[(a, b)] = restrict(op(prolong(comb, fn)))
    out = {}
    for di, dj in OFFSETS:
        plane = jnp.zeros((cn, cn), jnp.float32)
        for (a, b), t in probes.items():
            mine = ((i + di) % 3 == a) & ((j + dj) % 3 == b)
            plane = jnp.where(mine, t, plane)
        out[(di, dj)] = plane
    return out


def _power_quotient(planes, d_inv, v):
    import jax
    import jax.numpy as jnp

    op = level_operator(planes)

    def step(_, v):
        w = d_inv * op(v)
        return w / jnp.sqrt(jnp.sum(w * w))

    v = jax.lax.fori_loop(0, POWER_STEPS, step, v)
    return jnp.sum(v * (d_inv * op(v)))


def spectral_radius(planes, d_inv, n: int) -> float:
    """rho(D^-1 A) by the configuration's rule: fifteen normalised power
    steps from numpy's ``default_rng(0).random((n, n))``, then the Rayleigh
    quotient of the unit vector they leave."""
    import jax
    import jax.numpy as jnp

    v0 = jnp.asarray(np.random.default_rng(0).random((n, n)), jnp.float32)
    return float(jax.jit(_power_quotient)(planes, d_inv, v0))


def hierarchy(n: int, levels: int) -> list:
    """Per level ``(planes or None, weight)`` in float32: the fine level's
    operator is the 5-point function (``planes`` None, its weight a scalar),
    every coarser level has nine planes and the weight plane omega / diag.
    A level's side is its arrays': ``n``, then half of it, rounded down."""
    import jax
    import jax.numpy as jnp

    out = []
    planes = None
    with jax.default_matmul_precision("highest"):
        for lvl in range(levels):
            diag = (jnp.asarray(4.0, jnp.float32) if planes is None
                    else planes[(0, 0)])
            d_inv = 1.0 / diag
            rho = spectral_radius(planes, d_inv, n)
            out.append((planes, jnp.asarray(OMEGA / rho, jnp.float32) * d_inv))
            if lvl < levels - 1:
                planes = jax.jit(galerkin_planes, static_argnums=1)(planes, n)
                n //= 2
    return out


def vcycle(hier, r, lvl: int = 0):
    """One V-cycle on the residual ``r`` of level ``lvl``."""
    planes, w = hier[lvl]
    if lvl == len(hier) - 1:
        return w * r
    op = level_operator(planes)
    x = w * r
    coarse = vcycle(hier, restrict(r - op(x)), lvl + 1)
    x = x + prolong(coarse, r.shape[0])
    return x + w * (r - op(x))


@functools.lru_cache(maxsize=2)
def _reference_program(n: int, levels: int, iterations: int, dtype: str):
    """The hierarchy (built in float32, stored in ``dtype``) and the jitted
    solve, kept for the next right-hand side of the same sizes."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    hier = [(None if p is None else {d: c.astype(dt) for d, c in p.items()},
             w.astype(dt)) for p, w in hierarchy(n, levels)]

    def dot(u, v):
        return jnp.sum(u.astype(jnp.float32) * v.astype(jnp.float32))

    @jax.jit
    def solve(hier, b2):
        def body(_, state):
            x, r, p, rho = state
            q = apply_fine(p)
            alpha = rho / dot(p, q)
            x = x + alpha.astype(dt) * p
            r = r - alpha.astype(dt) * q
            z = vcycle(hier, r)
            rho_new = dot(r, z)
            p = z + (rho_new / rho).astype(dt) * p
            return x, r, p, rho_new

        z = vcycle(hier, b2)
        x, _, _, _ = jax.lax.fori_loop(
            0, iterations, body, (jnp.zeros_like(b2), b2, z, dot(b2, z)))
        return x

    return hier, solve


def reference_cg(b: np.ndarray, n: int, levels: int, iterations: int,
                 dtype="float32"):
    """V-cycle-preconditioned CG from x = 0 for exactly ``iterations``
    iterations, on one device. ``dtype`` is the storage and arithmetic type
    of vectors, planes and weights; dot products accumulate in float32.
    ``bfloat16`` is the control: the nearest precision below the float32 the
    configuration states. Returns x as a float32 host array."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        hier, solve = _reference_program(n, levels, iterations, str(dtype))
        b2 = jnp.asarray(np.asarray(b).reshape(n, n), dtype=jnp.dtype(dtype))
        x = solve(hier, b2)
        return np.asarray(x.astype(jnp.float32)).reshape(-1)


def compare(x, x_ref, rr_ref: float, b, n: int) -> dict:
    """The numbers a run is judged by, for one answer ``x`` against the
    reference's ``x_ref`` (true relative residual ``rr_ref``) of the same
    right-hand side and iteration count."""
    x64 = np.asarray(x, dtype=np.float64)
    r64 = np.asarray(x_ref, dtype=np.float64)
    rr_x = true_relres(x64, b, n)
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
    n, its = data["grid"], data["iterations"]
    x_ref = reference_cg(data["b"], n, data["levels"], its)
    rr_ref = true_relres(x_ref, data["b"], n)
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
        nums = compare(ans["x"], x_ref, rr_ref, data["b"], n)
        say(f"  answer of call {ans['index']}: " + ", ".join(
            f"{k} {v:.6e}" for k, v in nums.items()))
        for k in ("x_vs_reference", "relres_gap"):
            worst[k] = max(worst[k], float(nums[k]))
    return [{"name": k, "value": v, "limit": float(limits[k]),
             "ok": v <= float(limits[k])} for k, v in worst.items()]


def control_answers(data: dict, answers: list) -> list:
    """The control: the reference put in the program's place, computed in
    bfloat16 (the nearest precision below the configuration's float32)."""
    x = reference_cg(data["b"], data["grid"], data["levels"],
                     data["iterations"], dtype="bfloat16")
    return [{"x": x, "iters": data["iterations"], "index": 0, "request": 0}]
