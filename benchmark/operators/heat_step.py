"""Implicit heat steps on one 5-point pattern, and their plain reference.

Each client integrates its own reaction-diffusion problem with a source,
u_t = Lap(u) - r(x) u + f(x), on a g x g grid (Dirichlet) by backward Euler
with a fixed step dt = h^2 / s. Multiplied through by h^2, one step is

    (diag(c) + L) u_next = s * u_prev + h^2 f,        c = s + h^2 r(x)

with L the 5-point pattern (4 on the diagonal, -1 to the four neighbours, zero
outside the grid). The client's coefficients c are drawn U(lo, hi) once, its
initial condition and its source h^2 f are standard normal, and every
right-hand side is ``carry`` (= s = lo) times the client's previous answer
plus the source; the previous answer is also the starting iterate it hands
the solver. All clients share L's sparsity pattern
and differ in values: the same-pattern batched regime, stepped in time
(ROADMAP R6; operator of examples/heat_implicit.py).

The eigenvalues of diag(c) + L lie in (lo, hi + 8), so its condition number
is below (hi + 8) / lo, and an answer whose true relative residual is at most
rho lies within kappa * rho of the solution.

Nothing here imports the program. The reference is a textbook CG from zero on
shifted slices of the grid in ``jax.numpy``, run to the float32 floor;
residuals are taken in float64 with numpy. (Pattern copied from
chip_smoke.py's ``five_point_pattern``, listed in PERF.md.)
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def five_point_pattern(g: int) -> sp.csr_matrix:
    T = sp.diags([-1.0, -1.0], [-1, 1], shape=(g, g))
    I = sp.identity(g)
    A = (sp.kron(I, T) + sp.kron(T, I) + 4.0 * sp.identity(g * g)).tocsr()
    A.sort_indices()
    return A


def make(sizes: dict, seed: int) -> dict:
    g = int(sizes["grid"])
    n = g * g
    clients = int(sizes["clients"])
    lo, hi = (float(v) for v in sizes["coefficient_range"])
    P = five_point_pattern(g)
    diag_pos = np.flatnonzero(
        P.indices == np.repeat(np.arange(n), np.diff(P.indptr)))
    base = P.data.astype(np.float32)
    rng = np.random.default_rng(seed)
    coef = lo + (hi - lo) * rng.random(size=(clients, n), dtype=np.float32)
    u0 = rng.standard_normal(size=(clients, n), dtype=np.float32)
    source = rng.standard_normal(size=(clients, n), dtype=np.float32)
    values = np.empty((clients, base.size), dtype=np.float32)
    for k in range(clients):  # row by row: a 2-D fancy index is ten times slower
        values[k] = base
        values[k, diag_pos] += coef[k]
    return {
        "grid": g, "rows": n, "nnz": int(P.nnz), "pattern": P,
        "values": values, "coef": coef, "initial": u0, "source": source,
        "carry": lo,
        "rel_tol": float(sizes["rel_tol"]), "clients": clients, "seed": seed,
        "check_sample": int(sizes["check_sample"]),
    }


def apply_f64(u, coef, g: int) -> np.ndarray:
    u = np.asarray(u, dtype=np.float64).reshape(g, g)
    out = (4.0 + np.asarray(coef, dtype=np.float64).reshape(g, g)) * u
    out[:, 1:] -= u[:, :-1]
    out[:, :-1] -= u[:, 1:]
    out[1:, :] -= u[:-1, :]
    out[:-1, :] -= u[1:, :]
    return out.reshape(-1)


def true_relres(x, coef, b, g: int) -> float:
    b64 = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(x, coef, g))
                 / np.linalg.norm(b64))


def reference_cg(coef, b, g: int, iterations: int = 60, dtype="float32"):
    """Textbook CG from x = 0 on a stack of requests, ``iterations`` steps
    (past the float32 floor at the default). ``bfloat16`` storage and
    arithmetic with float32 dot products is the control. Returns float32
    host arrays, one row per request."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def A(u, d):
        out = d * u
        out = out.at[:, :, 1:].add(-u[:, :, :-1])
        out = out.at[:, :, :-1].add(-u[:, :, 1:])
        out = out.at[:, 1:, :].add(-u[:, :-1, :])
        out = out.at[:, :-1, :].add(-u[:, 1:, :])
        return out

    def dot(u, v):
        return jnp.sum(u.astype(jnp.float32) * v.astype(jnp.float32),
                       axis=(1, 2), keepdims=True)

    @jax.jit
    def solve(d, b3):
        def body(_, state):
            x, r, p, rho = state
            q = A(p, d)
            pq = dot(p, q)
            alpha = jnp.where(pq == 0, 0, rho / jnp.where(pq == 0, 1, pq))
            x = x + alpha.astype(dt) * p
            r = r - alpha.astype(dt) * q
            rho_new = dot(r, r)
            beta = jnp.where(rho == 0, 0, rho_new / jnp.where(rho == 0, 1, rho))
            p = r + beta.astype(dt) * p
            return x, r, p, rho_new

        x, _, _, _ = jax.lax.fori_loop(
            0, iterations, body, (jnp.zeros_like(b3), b3, b3, dot(b3, b3)))
        return x

    k = len(b)
    with jax.default_matmul_precision("highest"):
        d = jnp.asarray(4.0 + np.asarray(coef, np.float32).reshape(k, g, g),
                        dtype=dt)
        b3 = jnp.asarray(np.asarray(b).reshape(k, g, g), dtype=dt)
        return np.asarray(solve(d, b3).astype(jnp.float32)).reshape(k, -1)


def compare(x, x_ref, coef, b, g: int) -> dict:
    x64 = np.asarray(x, dtype=np.float64)
    r64 = np.asarray(x_ref, dtype=np.float64)
    return {
        "x_vs_reference": float(np.linalg.norm(x64 - r64) / np.linalg.norm(r64)),
        "relres": true_relres(x64, coef, b, g),
    }


def check(data: dict, answers: list, limits: dict, say=print) -> list:
    """A sample of the window's answers, drawn from the seed and with the last
    one in it, against the plain reference. ``answers``: dicts with
    ``request`` (the client), ``b`` (the right-hand side it sent) and ``x``."""
    g = data["grid"]
    k = min(int(data["check_sample"]), len(answers))
    rng = np.random.default_rng(data["seed"])
    picks = set(rng.choice(len(answers), size=k, replace=False).tolist())
    sample = [a for i, a in enumerate(answers)
              if i in picks or i == len(answers) - 1]
    coef = [data["coef"][a["request"]] for a in sample]
    x_ref = reference_cg(np.stack(coef), np.stack([a["b"] for a in sample]),
                         g) if sample else []
    worst = {"x_vs_reference": 0.0, "relres_over_asked": 0.0}
    for a, c, ref in zip(sample, coef, x_ref):
        if not np.all(np.isfinite(a["x"])):
            worst["x_vs_reference"] = float("inf")
            continue
        nums = compare(a["x"], ref, c, a["b"], g)
        worst["x_vs_reference"] = max(worst["x_vs_reference"],
                                      nums["x_vs_reference"])
        worst["relres_over_asked"] = max(worst["relres_over_asked"],
                                         nums["relres"] / data["rel_tol"])
    say(f"  {len(answers)} answers, {len(sample)} of them compared")
    return [{"name": k, "value": v, "limit": float(limits[k]),
             "ok": v <= float(limits[k])} for k, v in worst.items()]


def control_answers(data: dict, answers: list) -> list:
    """The control: the reference put in the program's place for the same
    requests, computed in bfloat16 (the nearest precision below float32)."""
    coef = np.stack([data["coef"][a["request"]] for a in answers])
    xs = reference_cg(coef, np.stack([a["b"] for a in answers]), data["grid"],
                      dtype="bfloat16")
    return [{"x": x, "iters": 0, "request": a["request"], "b": a["b"]}
            for a, x in zip(answers, xs)]
