"""The upstream suite's PDE operator and its plain reference.

``examples/pde.py`` of nv-legate/legate.sparse (``d2_mat_dirichlet_2d``): the
5-point Laplacian with Dirichlet boundary on an (n+2)^2 grid, n^2 unknowns,

    (A u)[i, j] = c u[i, j] + a (u[i, j-1] + u[i, j+1]) + g (u[i-1, j] + u[i+1, j])

with a = g = (n+1)^2, c = -2a - 2g, and zero outside the grid. The right-hand
side is upstream's ones times seeded U(0.5, 1.5) values, so that the seed
reaches the data.

Nothing here imports the program. The reference is a textbook CG on shifted
slices of the 2-D grid in ``jax.numpy``; residuals are taken in float64 with
numpy. (Copied from chip_smoke.py's ``pde_diagonals``, listed in PERF.md.)
"""

from __future__ import annotations

import numpy as np


def coefficients(n: int) -> tuple:
    """(a, g, c) rounded to float32, the values the program is given."""
    h = 1.0 / (n + 1)
    a = np.float32(1.0 / h**2)
    g = np.float32(1.0 / h**2)
    c = np.float32(-2.0 / h**2 - 2.0 / h**2)
    return a, g, c


def make(sizes: dict, seed: int) -> dict:
    """Host data of one run: the five diagonals as ``sparse.diags`` takes
    them, and the right-hand side drawn from the seed."""
    n = int(sizes["grid"])
    N = n * n
    a, g, c = coefficients(n)
    diag_a = np.full(N - 1, a, dtype=np.float32)
    diag_a[n - 1:: n] = 0.0
    diag_g = np.full(N - n, g, dtype=np.float32)
    diag_c = np.full(N, c, dtype=np.float32)
    rng = np.random.default_rng(seed)
    b = rng.uniform(0.5, 1.5, size=N).astype(np.float32)
    return {
        "grid": n,
        "rows": N,
        "nnz": 5 * N - 2 * n - 2 * (n - 1) - 2,
        "diagonals": [diag_g, diag_a, diag_c, diag_a, diag_g],
        "offsets": [-n, -1, 0, 1, n],
        "b": b,
        "iterations": int(sizes["iterations"]),
    }


def apply_f64(u: np.ndarray, n: int) -> np.ndarray:
    """A u in float64 on the grid, with the float32 coefficients."""
    a, g, c = (np.float64(v) for v in coefficients(n))
    u = np.asarray(u, dtype=np.float64).reshape(n, n)
    out = c * u
    out[:, 1:] += a * u[:, :-1]
    out[:, :-1] += a * u[:, 1:]
    out[1:, :] += g * u[:-1, :]
    out[:-1, :] += g * u[1:, :]
    return out.reshape(-1)


def true_relres(x, b, n: int) -> float:
    b64 = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(x, n)) / np.linalg.norm(b64))


def reference_cg(b: np.ndarray, n: int, iterations: int, dtype="float32"):
    """Textbook CG from x = 0 for exactly ``iterations`` iterations, on one
    device. ``dtype`` is the storage and arithmetic type of vectors and
    coefficients; dot products accumulate in float32. ``bfloat16`` is the
    control: the nearest precision below the float32 the configuration
    states. Returns x as a float32 host array."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    a, g, c = (jnp.asarray(v, dt) for v in coefficients(n))

    def A(u):
        out = c * u
        out = out.at[:, 1:].add(a * u[:, :-1])
        out = out.at[:, :-1].add(a * u[:, 1:])
        out = out.at[1:, :].add(g * u[:-1, :])
        out = out.at[:-1, :].add(g * u[1:, :])
        return out

    def dot(u, v):
        return jnp.sum(u.astype(jnp.float32) * v.astype(jnp.float32))

    @jax.jit
    def solve(b2):
        def body(_, state):
            x, r, p, rho = state
            q = A(p)
            alpha = rho / dot(p, q)
            x = x + alpha.astype(dt) * p
            r = r - alpha.astype(dt) * q
            rho_new = dot(r, r)
            p = r + (rho_new / rho).astype(dt) * p
            return x, r, p, rho_new

        x0 = jnp.zeros_like(b2)
        x, _, _, _ = jax.lax.fori_loop(
            0, iterations, body, (x0, b2, b2, dot(b2, b2)))
        return x

    with jax.default_matmul_precision("highest"):
        b2 = jnp.asarray(np.asarray(b).reshape(n, n), dtype=dt)
        x = solve(b2)
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
    x_ref = reference_cg(data["b"], n, its)
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
    x = reference_cg(data["b"], data["grid"], data["iterations"],
                     dtype="bfloat16")
    return [{"x": x, "iters": data["iterations"], "index": 0, "request": 0}]
