"""Implicit heat steps on one unstructured finite-element pattern, and their
plain reference.

Each client integrates its own transient thermal problem on one mesh,
M(x) u_t = -K u - R(x) u + f(x), by backward Euler with a fixed step. With
the lumped mass scaled out, one step is

    (diag(c) + K) u_next = s * u_prev + f,        c = s + r(x)

K is the stiffness matrix of ``operators/spd_unstructured.py`` (loaded by
path, neither copied nor edited): the pattern of the SuiteSparse collection's
Schmid/thermal2 class from ``sizes["pattern_seed"]`` (an s x s grid
triangulated by one diagonal a cell, under one random permutation), its edge
weights and its Dirichlet ring from ``--seed``. s = ``lo`` is the mass over
the step, and the client's reaction r(x) is drawn U(0, hi - lo) once from
``--seed``, a vertex at a time, so c ~ U(lo, hi): its own heat-capacity field
and so its own matrix values. Initial condition and source are standard
normal. Every right-hand side is ``carry`` (= s = lo) times the client's
previous answer plus its source, and the previous answer is the starting
iterate it hands the solver. All clients share K's sparsity pattern and
differ in the diagonal's values: the same-pattern batched regime, stepped in
time (ROADMAP R6), on a pattern that is no stencil.

K is a weighted graph Laplacian plus a non-negative diagonal, so each of its
rows' off-diagonal magnitudes sum to at most its diagonal, and by Gershgorin
the eigenvalues of diag(c) + K lie in [min c, max_i(c_i + 2 K_ii)]. ``make``
computes that bound on the condition number, ``kappa_bound``, from the
matrices it generated, over all clients; ``check`` holds it to the number the
configuration states (``limits["kappa_bound"]``), because the guarantee for x
is that number times the guarantee for the residual.

Nothing here imports the program. The reference is a textbook CG from zero
in ``jax.numpy`` whose product is ``c * u`` plus ``jax.ops.segment_sum`` of
``val * u[col]`` over K's COO triplets, for a stack of requests at once, laid
out (rows, ``STACK``) so that one gather fetches a row of the stack; residuals
are taken in float64 with numpy.
"""

from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

_STIFFNESS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "spd_unstructured.py")

# CG's error bound 2 ((sqrt(k) - 1) / (sqrt(k) + 1))^i with k = 19 falls under
# float32's 6e-8 at i = 37: 40 iterations are past the float32 floor, and each
# costs a gather and a scatter-add of a row of the stack for every entry of K
# (the costly part of a run's check)
REFERENCE_ITERATIONS = 40
# columns of the reference's stack, one lane each: at a full lane width the
# TPU's compiler keeps gather and scatter-add row-major; at 17 columns (and at
# 64 with 640,000 rows) it turns the scatter-add's operand around and a solve
# takes 30 to 48 s where it takes a few (PERF.md section 6, PR 35)
STACK = 128


def _stiffness_module():
    spec = importlib.util.spec_from_file_location("bench_operators_spd_unstructured",
                                                  _STIFFNESS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _coo_rows(indptr) -> np.ndarray:
    return np.repeat(np.arange(len(indptr) - 1, dtype=np.int32),
                     np.diff(indptr))


def make(sizes: dict, seed: int) -> dict:
    clients = int(sizes["clients"])
    lo, hi = (float(v) for v in sizes["coefficient_range"])
    K = _stiffness_module().make(
        {"side": sizes["side"], "pattern_seed": sizes["pattern_seed"],
         "iterations": 0}, seed)
    n, nnz = K["rows"], K["nnz"]
    P = sp.csr_matrix((K["data"], K["indices"], K["indptr"]), shape=(n, n))
    P.has_sorted_indices = True  # the generator sorts rows and columns
    diag_pos = np.flatnonzero(K["indices"] == _coo_rows(K["indptr"]))
    assert diag_pos.shape[0] == n  # every row stores its diagonal
    k_diag = K["data"][diag_pos]
    coef, u0, source = (np.empty((clients, n), dtype=np.float32)
                        for _ in range(3))
    values = np.empty((clients, nnz), dtype=np.float32)

    def client(k: int) -> tuple:
        # a stream of the client's own (the stiffness generator has walked
        # ``seed``'s), so that the clients are drawn side by side
        rng = np.random.default_rng([int(seed), 35, k])
        rng.random(out=coef[k], dtype=np.float32)
        coef[k] *= np.float32(hi - lo)
        coef[k] += np.float32(lo)
        rng.standard_normal(out=u0[k], dtype=np.float32)
        rng.standard_normal(out=source[k], dtype=np.float32)
        values[k] = K["data"]  # row by row: a 2-D fancy index is ten times slower
        values[k, diag_pos] += coef[k]
        return float((coef[k] + 2.0 * k_diag).max()), float(coef[k].min())

    # numpy's generators and copies release the GIL: in bulk, on the host's
    # cores (set-up: 128 clients of 921,600 rows are 3.3 GB of values)
    with ThreadPoolExecutor(max_workers=8) as pool:
        ends = list(pool.map(client, range(clients)))
    return {
        "side": int(sizes["side"]), "rows": n, "nnz": nnz, "pattern": P,
        "values": values, "coef": coef, "initial": u0, "source": source,
        "carry": lo,
        "kappa_bound": max(t for t, _ in ends) / min(b for _, b in ends),
        "rel_tol": float(sizes["rel_tol"]), "clients": clients, "seed": seed,
        "check_sample": int(sizes["check_sample"]),
    }


def apply_f64(data: dict, values, u) -> np.ndarray:
    """A u in float64 for one client's ``values`` (a row of ``data["values"]``:
    the float32 entries the program is given, the rounded sums on the
    diagonal included). Every row holds its diagonal, so ``reduceat`` meets no
    empty row."""
    P = data["pattern"]
    u = np.asarray(u, dtype=np.float64)
    prod = np.asarray(values, dtype=np.float64) * u[P.indices]
    return np.add.reduceat(prod, P.indptr[:-1])


def true_relres(data: dict, x, values, b) -> float:
    b64 = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(data, values, x))
                 / np.linalg.norm(b64))


def reference_cg(data: dict, coef, b, iterations: int = REFERENCE_ITERATIONS,
                 dtype="float32"):
    """Textbook CG from x = 0 on a stack of requests, ``iterations`` steps
    (past the float32 floor at the default). ``bfloat16`` storage and
    arithmetic with float32 dot products is the control. Returns float32
    host arrays, one row per request."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    P = data["pattern"]
    n = data["rows"]

    def dot(u, v):
        return jnp.sum(u.astype(jnp.float32) * v.astype(jnp.float32),
                       axis=0, keepdims=True)

    @jax.jit
    def solve(row, col, val, d, b2):
        def A(u):  # u: (rows, k); one gather fetches a row of k values
            return d * u + jax.ops.segment_sum(
                val[:, None] * u[col], row, num_segments=n,
                indices_are_sorted=True)

        def body(_, state):
            x, r, p, rho = state
            q = A(p)
            pq = dot(p, q)
            alpha = jnp.where(pq == 0, 0, rho / jnp.where(pq == 0, 1, pq))
            x = x + alpha.astype(dt) * p
            r = r - alpha.astype(dt) * q
            rho_new = dot(r, r)
            beta = jnp.where(rho == 0, 0, rho_new / jnp.where(rho == 0, 1, rho))
            p = r + beta.astype(dt) * p
            return x, r, p, rho_new

        x, _, _, _ = jax.lax.fori_loop(
            0, iterations, body, (jnp.zeros_like(b2), b2, b2, dot(b2, b2)))
        return x

    # the stack is padded with zero systems to a multiple of STACK columns:
    # the width at which the TPU's compiler lays a row of the stack along the
    # lanes, so that one gather fetches it (a zero right-hand side stays zero)
    k = len(b)
    wide = -(-k // STACK) * STACK
    d2 = np.ones((n, wide), dtype=np.float32)
    b2 = np.zeros((n, wide), dtype=np.float32)
    d2[:, :k] = np.asarray(coef, np.float32).T
    b2[:, :k] = np.asarray(b, np.float32).T
    with jax.default_matmul_precision("highest"):
        x = solve(jnp.asarray(_coo_rows(P.indptr)), jnp.asarray(P.indices),
                  jnp.asarray(P.data, dtype=dt), jnp.asarray(d2, dtype=dt),
                  jnp.asarray(b2, dtype=dt))
        return np.ascontiguousarray(
            np.asarray(x[:, :k].astype(jnp.float32)).T)


def compare(data: dict, x, x_ref, values, b) -> dict:
    x64 = np.asarray(x, dtype=np.float64)
    r64 = np.asarray(x_ref, dtype=np.float64)
    return {
        "x_vs_reference": float(np.linalg.norm(x64 - r64) / np.linalg.norm(r64)),
        "relres": true_relres(data, x64, values, b),
    }


def check(data: dict, answers: list, limits: dict, say=print) -> list:
    """A sample of the window's answers, drawn from the seed and with the last
    one in it, against the plain reference. ``answers``: dicts with
    ``request`` (the client), ``b`` (the right-hand side it sent) and ``x``."""
    k = min(int(data["check_sample"]), len(answers))
    rng = np.random.default_rng(data["seed"])
    picks = set(rng.choice(len(answers), size=k, replace=False).tolist())
    sample = [a for i, a in enumerate(answers)
              if i in picks or i == len(answers) - 1]
    coef = [data["coef"][a["request"]] for a in sample]
    x_ref = reference_cg(data, np.stack(coef),
                         np.stack([a["b"] for a in sample])) if sample else []
    def one(pair) -> tuple:
        a, ref = pair
        if not np.all(np.isfinite(a["x"])):
            return float("inf"), 0.0
        nums = compare(data, a["x"], ref, data["values"][a["request"]],
                       a["b"])
        return nums["x_vs_reference"], nums["relres"] / data["rel_tol"]

    # the float64 residuals are numpy over 8.6M entries each and release the
    # GIL: side by side, as ``make`` draws the clients
    with ThreadPoolExecutor(max_workers=8) as pool:
        nums = list(pool.map(one, zip(sample, x_ref)))
    worst = {"x_vs_reference": max((x for x, _ in nums), default=0.0),
             "relres_over_asked": max((r for _, r in nums), default=0.0)}
    worst["kappa_bound"] = data["kappa_bound"]
    say(f"  {len(answers)} answers, {len(sample)} of them compared")
    return [{"name": k, "value": v, "limit": float(limits[k]),
             "ok": v <= float(limits[k])} for k, v in worst.items()]


def control_answers(data: dict, answers: list) -> list:
    """The control: the reference put in the program's place for the same
    requests, computed in bfloat16 (the nearest precision below float32)."""
    coef = np.stack([data["coef"][a["request"]] for a in answers])
    xs = reference_cg(data, coef, np.stack([a["b"] for a in answers]),
                      dtype="bfloat16")
    return [{"x": x, "iters": 0, "request": a["request"], "b": a["b"]}
            for a, x in zip(answers, xs)]
