"""An unstructured SPD system of the SuiteSparse collection's thermal class,
and its plain reference.

The class (Schmid/thermal2: 1,228,045 rows, 8,580,313 entries, 6.99 a row,
longest row 11; an unstructured finite-element steady-state thermal problem)
is generated, because there is no network to fetch the file. The source is
ONE matrix of the collection, so the **pattern** is one too: it comes from the
``pattern_seed`` the configuration's file states, and ``--seed`` draws the
**values** and the right-hand side alone (data take the place of weights).

The pattern: the vertices of an s x s grid, each cell triangulated by one
diagonal whose direction is drawn from ``pattern_seed``, so that an inner
vertex has 4 to 8 neighbours; rows and columns under one random permutation
drawn from ``pattern_seed``, so that nothing of the grid's band is left. The
values, from the seed: edge weights w ~ U(0.5, 1.5); A = the weighted graph
Laplacian plus, on the outer ring's rows, the weights of the edges to an
eliminated Dirichlet ring (one ghost edge for each axis neighbour a ring
vertex lacks); the right-hand side b ~ U(0.5, 1.5). n = s^2 rows,
7 s^2 - 8 s + 2 entries, rows of 3 to 9 entries. The system is handed over as
the three CSR arrays a reader of a Matrix Market file would hold (sorted
rows, sorted columns in a row).

Both generators walk one stream in one order (diagonals, weights, the four
ring draws, permutation, b); the pattern's keeps the diagonals and the
permutation, the values' keeps the weights, the ring and b, and what each
does not keep it draws and drops. So the pattern of ``pattern_seed`` = P is,
entry for entry, the matrix this generator gave for ``--seed`` P when it
drew everything from the seed (PR 31 to PR 33), and with ``pattern_seed``
equal to the seed the values and b are that matrix's too, bit for bit:
every reading PERF.md has of a named seed is a reading of a pattern the
configuration can still state.

Nothing here imports the program. The reference is a textbook CG whose
product is the plain form over the COO triplets (``jax.ops.segment_sum`` of
``data * x[col]``); residuals are taken in float64 with numpy.
"""

from __future__ import annotations

import numpy as np


def _draws(seed: int, s: int) -> dict:
    """Every draw of the generator's one stream for side ``s``, in its one
    order. A caller keeps the draws that are its own and drops the others."""
    rng = np.random.default_rng(seed)
    n, edges = s * s, 2 * s * (s - 1) + (s - 1) * (s - 1)
    return {
        "flip": rng.integers(0, 2, size=(s - 1, s - 1)).astype(bool),
        "w": rng.uniform(0.5, 1.5, size=edges).astype(np.float32),
        "ring": [rng.uniform(0.5, 1.5, size=s).astype(np.float32)
                 for _ in range(4)],
        "perm": rng.permutation(n),
        "b": rng.uniform(0.5, 1.5, size=n).astype(np.float32),
    }


def make(sizes: dict, seed: int) -> dict:
    """Host data of one run: the pattern (triangulation and permutation) from
    ``sizes["pattern_seed"]``, the values (weights, ring) and b from ``seed``.

    A configuration states its pattern: every configuration file that names
    this operator holds ``pattern_seed`` (benchmark/tests pin that). A caller
    that gives none gets the pattern of ``seed``, which is what this
    generator gave before it had the key; ``tests/utils/spd.py`` still calls
    it so, and a PR that may edit it should make the missing key an error
    (PERF.md section 7)."""
    s = int(sizes["side"])
    n = s * s
    pattern = _draws(int(sizes.get("pattern_seed", seed)), s)
    values = _draws(int(seed), s)
    flip, perm = pattern["flip"], pattern["perm"]
    w, b = values["w"], values["b"]
    idx = np.arange(n, dtype=np.int64).reshape(s, s)
    # a cell's diagonal: (i, j)-(i+1, j+1), or, flipped, (i, j+1)-(i+1, j)
    u = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel(),
                        np.where(flip, idx[:-1, 1:], idx[:-1, :-1]).ravel()])
    v = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel(),
                        np.where(flip, idx[1:, :-1], idx[1:, 1:]).ravel()])
    ghost = np.zeros((s, s))
    for ring, draw in zip((ghost[0], ghost[-1], ghost[:, 0], ghost[:, -1]),
                          values["ring"]):
        ring += draw
    diag = (np.bincount(u, w, n) + np.bincount(v, w, n) + ghost.ravel())
    here = np.arange(n, dtype=np.int64)
    rows = perm[np.concatenate([u, v, here])]
    cols = perm[np.concatenate([v, u, here])]
    vals = np.concatenate([-w, -w, diag.astype(np.float32)])
    order = np.argsort(rows * n + cols)  # no entry is stored twice
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return {
        "side": s,
        "rows": n,
        "nnz": int(order.shape[0]),
        "indptr": indptr.astype(np.int32),
        "indices": cols[order].astype(np.int32),
        "data": vals[order],
        "b": b,
        "iterations": int(sizes["iterations"]),
    }


def coo_rows(data: dict) -> np.ndarray:
    return np.repeat(np.arange(data["rows"], dtype=np.int32),
                     np.diff(data["indptr"]))


def apply_f64(data: dict, x) -> np.ndarray:
    """A x in float64, with the float32 entries the program is given. Every
    row holds its diagonal, so ``reduceat`` meets no empty row."""
    x = np.asarray(x, dtype=np.float64)
    prod = data["data"].astype(np.float64) * x[data["indices"]]
    return np.add.reduceat(prod, data["indptr"][:-1])


def true_relres(data: dict, x) -> float:
    b64 = np.asarray(data["b"], dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(data, x)) / np.linalg.norm(b64))


def reference_cg(data: dict, iterations: int, dtype="float32"):
    """Textbook CG from x = 0 for exactly ``iterations`` iterations, on one
    device. ``dtype`` is the storage and arithmetic type of entries and
    vectors; dot products accumulate in float32. ``bfloat16`` is the
    control: the nearest precision below the float32 the configuration
    states. Returns x as a float32 host array."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = data["rows"]

    def dot(u, v):
        return jnp.sum(u.astype(jnp.float32) * v.astype(jnp.float32))

    @jax.jit
    def solve(row, col, val, b):
        def A(u):
            return jax.ops.segment_sum(val * u[col], row, num_segments=n,
                                       indices_are_sorted=True)

        def body(_, state):
            x, r, p, rho = state
            q = A(p)
            alpha = rho / dot(p, q)
            x = x + alpha.astype(dt) * p
            r = r - alpha.astype(dt) * q
            rho_new = dot(r, r)
            p = r + (rho_new / rho).astype(dt) * p
            return x, r, p, rho_new

        x, _, _, _ = jax.lax.fori_loop(
            0, iterations, body, (jnp.zeros_like(b), b, b, dot(b, b)))
        return x

    x = solve(jnp.asarray(coo_rows(data)), jnp.asarray(data["indices"]),
              jnp.asarray(data["data"], dtype=dt),
              jnp.asarray(data["b"], dtype=dt))
    return np.asarray(x.astype(jnp.float32))


def compare(x, x_ref, rr_ref: float, data: dict) -> dict:
    """The numbers a run is judged by, for one answer ``x`` against the
    reference's ``x_ref`` (true relative residual ``rr_ref``) of the same
    system and iteration count."""
    x64 = np.asarray(x, dtype=np.float64)
    r64 = np.asarray(x_ref, dtype=np.float64)
    rr_x = true_relres(data, x64)
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
    its = data["iterations"]
    x_ref = reference_cg(data, its)
    rr_ref = true_relres(data, x_ref)
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
        nums = compare(ans["x"], x_ref, rr_ref, data)
        say(f"  answer of call {ans['index']}: " + ", ".join(
            f"{k} {v:.6e}" for k, v in nums.items()))
        for k in ("x_vs_reference", "relres_gap"):
            worst[k] = max(worst[k], float(nums[k]))
    return [{"name": k, "value": v, "limit": float(limits[k]),
             "ok": v <= float(limits[k])} for k, v in worst.items()]


def control_answers(data: dict, answers: list) -> list:
    """The control: the reference put in the program's place, computed in
    bfloat16 (the nearest precision below the configuration's float32)."""
    x = reference_cg(data, data["iterations"], dtype="bfloat16")
    return [{"x": x, "iters": data["iterations"], "index": 0, "request": 0}]
