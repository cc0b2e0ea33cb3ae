"""A nonsymmetric CFD system on the pattern of SuiteSparse's
Bourchtein/atmosmodd, and its plain reference: restarted GMRES.

The matrix of the collection (an atmospheric model; real, nonsymmetric,
1,270,432 rows, 8,814,880 entries, 6.94 a row, pattern symmetric) has the
pattern of a 7-point stencil on a 148 x 148 x 58 box with the neighbours
outside the box dropped: 148 * 148 * 58 = 1,270,432 and 7 n - 2 (ab + bc +
ca) = 8,814,880, the collection's counts to the last entry (``counts``).
There is no network to fetch the file, so the pattern is built from those
numbers and the **values are generated**. Every departure from the file:

- the order: x fastest, then y, then z (offsets +-1, +-a, +-a*b); the
  collection's own order is not known here;
- the values: 6 on the diagonal and, from a row's point towards +x / -x,
  -(1 - g u) / -(1 + g u), likewise v along y and w along z, with g = 0.5
  and (u, v, w) three fields U(-1, 1) a point drawn from the run's seed: a
  central-difference advection-diffusion operator at cell Peclet number at
  most 0.5, an M-matrix, a_ij != a_ji wherever the wind differs between two
  neighbours, Dirichlet boundaries (atmosmodd's values are a real
  atmosphere's);
- the right-hand side U(0.5, 1.5) from the run's seed;
- float32 where the file and its users are float64.

The seed reaches the values and b and nothing else: the pattern, and so the
layout, the program and a solve's time, are functions of the box alone. The
system is handed over as the three CSR arrays a reader of a Matrix Market
file would hold (sorted rows, sorted columns in a row).

Nothing here imports the program. The reference is restarted GMRES in
straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")`` on 3-D arrays: the operator by shifted slices of the seven
coefficient fields; Arnoldi by modified Gram-Schmidt one vector at a time,
**orthogonalised twice** (part of the semantics: the program
re-orthogonalises once too), no masks and no Givens rotations; the small
least-squares problem min ||beta e1 - H y|| by ``numpy.linalg.lstsq`` in
float64 on the host once a cycle; x += V y; exactly ``cycles`` cycles of
``restart`` steps from x = 0. Residuals are taken in float64 with numpy from
the CSR arrays.
"""

from __future__ import annotations

import functools

import numpy as np

GAMMA = 0.5
DIAGONAL = 6.0


def counts(box) -> tuple:
    """(rows, entries) of the 7-point pattern on an a x b x c box."""
    a, b, c = (int(s) for s in box)
    n = a * b * c
    return n, 7 * n - 2 * (a * b + b * c + c * a)


def inside_box(box) -> np.ndarray:
    """``[7, c, b, a]`` booleans: whether a point's neighbour in each of the
    seven directions (the order of the offsets) lies inside the box."""
    a, b, c = (int(s) for s in box)
    inside = np.ones((7, c, b, a), dtype=bool)
    inside[0, 0], inside[6, -1] = False, False
    inside[1, :, 0], inside[5, :, -1] = False, False
    inside[2, :, :, 0], inside[4, :, :, -1] = False, False
    return inside


def fields_of(box, seed: int) -> tuple:
    """The seven coefficient fields ``[7, c, b, a]`` (towards -z, -y, -x, the
    diagonal, +x, +y, +z: the order of the offsets) with the coefficients
    towards a neighbour outside the box set to zero, and b ``[c, b, a]``,
    both float32, drawn from the seed in one order (u, v, w, b)."""
    a, b, c = (int(s) for s in box)
    rng = np.random.default_rng(int(seed))
    u, v, w = (rng.uniform(-1.0, 1.0, size=(c, b, a)).astype(np.float32)
               for _ in range(3))
    rhs = rng.uniform(0.5, 1.5, size=(c, b, a)).astype(np.float32)
    g = np.float32(GAMMA)
    f = np.empty((7, c, b, a), dtype=np.float32)
    f[0], f[6] = -(1 + g * w), -(1 - g * w)
    f[1], f[5] = -(1 + g * v), -(1 - g * v)
    f[2], f[4] = -(1 + g * u), -(1 - g * u)
    f[3] = DIAGONAL
    f[~inside_box(box)] = 0.0
    return f, rhs


def make(sizes: dict, seed: int) -> dict:
    """Host data of one run: the CSR arrays from the seven fields, and b."""
    a, b, c = box = tuple(int(s) for s in sizes["box"])
    n, nnz = counts(box)
    f, rhs = fields_of(box, seed)
    offsets = np.array([-a * b, -a, -1, 0, 1, a, a * b], dtype=np.int64)
    inside = inside_box(box).reshape(7, n).T  # [n, 7]: a row's entries, columns rising
    cols = np.arange(n, dtype=np.int64)[:, None] + offsets[None, :]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(inside.sum(axis=1), out=indptr[1:])
    data = f.reshape(7, n).T[inside]
    if (int(indptr[-1]), data.shape[0]) != (nnz, nnz):
        raise AssertionError(
            "the generator's counts are not 7 n - 2 (ab + bc + ca)")
    restart, cycles = int(sizes["restart"]), int(sizes["cycles"])
    return {
        "box": box, "rows": n, "nnz": nnz,
        "indptr": indptr.astype(np.int32),
        "indices": cols[inside].astype(np.int32),
        "data": data, "fields": f, "b": rhs.reshape(n),
        "restart": restart, "cycles": cycles, "iterations": restart * cycles,
    }


def apply_f64(data: dict, x) -> np.ndarray:
    """A x in float64, with the float32 entries the program is given. Every
    row holds its diagonal, so ``reduceat`` meets no empty row."""
    x = np.asarray(x, dtype=np.float64)
    prod = data["data"].astype(np.float64) * x[data["indices"]]
    return np.add.reduceat(prod, data["indptr"][:-1])


def true_relres(data: dict, x) -> float:
    b64 = np.asarray(data["b"], dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(data, x)) / np.linalg.norm(b64))


# -- the plain reference ------------------------------------------------------
def apply_box(f, u):
    """The operator on the box, by shifted slices of the seven fields."""
    out = f[3] * u
    out = out.at[1:].add(f[0, 1:] * u[:-1])
    out = out.at[:-1].add(f[6, :-1] * u[1:])
    out = out.at[:, 1:].add(f[1, :, 1:] * u[:, :-1])
    out = out.at[:, :-1].add(f[5, :, :-1] * u[:, 1:])
    out = out.at[:, :, 1:].add(f[2, :, :, 1:] * u[:, :, :-1])
    out = out.at[:, :, :-1].add(f[4, :, :, :-1] * u[:, :, 1:])
    return out


@functools.lru_cache(maxsize=4)
def _reference_programs(restart: int, dtype: str):
    """(cycle, update) of the reference, jitted: a cycle's Arnoldi process
    from the current iterate, and x += V y. ``dtype`` is the storage and
    arithmetic type of fields and vectors; inner products and the small
    matrix accumulate in float32."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def dot(p, q):
        return jnp.sum(p.astype(jnp.float32) * q.astype(jnp.float32))

    @jax.jit
    def cycle(f, x, b):
        r = b - apply_box(f, x)
        beta = jnp.sqrt(dot(r, r))
        V = jnp.zeros((restart + 1,) + b.shape, dtype=dt)
        V = V.at[0].set(r / beta.astype(dt))
        H = jnp.zeros((restart + 1, restart), dtype=jnp.float32)

        def step(j, state):
            V, H = state
            w = apply_box(f, V[j])

            def against(i, state):
                w, H = state
                h = dot(V[i], w)
                return w - h.astype(dt) * V[i], H.at[i, j].add(h)

            for _ in range(2):  # orthogonalised twice
                w, H = jax.lax.fori_loop(0, j + 1, against, (w, H))
            norm = jnp.sqrt(dot(w, w))
            return V.at[j + 1].set(w / norm.astype(dt)), H.at[j + 1, j].set(norm)

        V, H = jax.lax.fori_loop(0, restart, step, (V, H))
        return V, H, beta

    @jax.jit
    def update(x, V, y):
        return x + jnp.tensordot(y.astype(dt), V[:restart], axes=1)

    return cycle, update


def reference_gmres(data: dict, cycles: int | None = None,
                    dtype: str = "float32") -> np.ndarray:
    """Restarted GMRES from x = 0 for exactly ``cycles`` cycles (default: the
    data's) of ``restart`` steps, on one device. ``bfloat16`` is the control:
    the nearest precision below the float32 the configuration states.
    Returns x as a float32 host array of ``rows`` values."""
    import jax
    import jax.numpy as jnp

    restart = data["restart"]
    cycles = data["cycles"] if cycles is None else int(cycles)
    a, b, c = data["box"]
    with jax.default_matmul_precision("highest"):
        cycle, update = _reference_programs(restart, dtype)
        f = jnp.asarray(data["fields"], dtype=dtype)
        rhs = jnp.asarray(data["b"].reshape(c, b, a), dtype=dtype)
        x = jnp.zeros_like(rhs)
        for _ in range(cycles):
            V, H, beta = cycle(f, x, rhs)
            e1 = np.zeros(restart + 1)
            e1[0] = float(beta)
            y = np.linalg.lstsq(np.asarray(H, dtype=np.float64), e1,
                                rcond=None)[0]
            x = update(x, V, jnp.asarray(y, dtype=jnp.float32))
        return np.asarray(x.astype(jnp.float32)).reshape(-1)


def compare(x, x_ref, rr_ref: float, data: dict) -> dict:
    """The numbers a run is judged by, for one answer ``x`` against the
    reference's ``x_ref`` (true relative residual ``rr_ref``) of the same
    system and step count."""
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
    host iterate ``x`` and the step count the program returned. An answer
    bit-identical to one already compared is not compared again."""
    its = data["iterations"]
    x_ref = reference_gmres(data)
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
    x = reference_gmres(data, dtype="bfloat16")
    return [{"x": x, "iters": data["iterations"], "index": 0, "request": 0}]
