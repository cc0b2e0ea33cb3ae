"""Implicit advection-diffusion steps of an ensemble on the pattern of
SuiteSparse's Bourchtein/atmosmodd, and their plain reference.

Each member of the ensemble integrates its own transport problem on one grid,
u_t = -A_k u + f_k, by backward Euler with a fixed step. With the step scaled
out, one step is

    (s I + A_k) u_next = s * u_prev + f_k

A_k is member k's matrix of ``operators/cfd_7pt.py`` (loaded by path, neither
copied nor edited: the pattern of a 7-point stencil on the box, diagonal 6,
neighbours -(1 -+ g wind), g = 0.5, and its reference cycle), with the member's own wind: three fields U(-1, 1) a point, drawn from
``--seed`` and the member's number. ``s`` (``sizes["shift"]``) is one over the
step in the operator's units; the explicit limit of the scaled operator is a
step of 1/6. The initial condition is standard normal, a point and a member.
The source is standard normal too and **another draw at every step**
(:class:`Forcing`: a member's one field of noise, read from another point on at
each of its steps), as an ensemble's stochastic forcing is: under a source that
stayed what it was every member would settle into its steady state, each
step's start would be nearly its answer, and the solves of a window would fade
from 38 steps to 12 (read at 148 x 148 x 58, s = 0.125: PERF.md section 6,
PR 49). Every right-hand side is ``carry`` (= s) times the member's previous
answer plus its source of that step, and the previous answer is the starting
iterate it hands the solver. All members share the sparsity pattern and differ in the
values of every off-diagonal entry: the same-pattern batched regime, stepped
in time (ROADMAP R6), on matrices that are not symmetric (a_ij != a_ji
wherever the wind differs between two neighbours), so that CG does not apply.

Every row of s I + A_k is diagonally dominant by s (the two neighbours along
an axis sum to 2 whatever the wind), so its eigenvalues' real parts lie in
(s, s + 12) by Gershgorin and the maximum norm of its inverse is at most 1/s.
A bound on the 2-norm condition number does not follow for a matrix that is
not normal; ``kappa`` in the configuration is an estimate, and the limit on x
is placed from readings, not from a bound.

Nothing here imports the program. The reference is ``cfd_7pt``'s textbook
restarted GMRES (modified Gram-Schmidt one vector at a time, orthogonalised
twice, the small least-squares problem on the host in float64) on shifted
slices of the box in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, one system at a time from x = 0
for ``REFERENCE_CYCLES`` cycles: past float32's floor. Residuals are taken in
float64 with numpy from the CSR arrays the program is given.
"""

from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

_BASE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cfd_7pt.py")

# restarted GMRES(30) from zero on s I + A_k is at float32's floor after
# three cycles at s = 0.125 on the cell's box (true relative residual 7.9e-7
# after two, 1.18e-7 after three, four, five and six; the iterate moves by
# 1.2e-5 from two to three and by 1.6e-7 after: sandbox CPU, PR 49); four are
# past it, and each costs a run's comparison a cycle a compared answer
REFERENCE_CYCLES = 4


def _base_module():
    spec = importlib.util.spec_from_file_location("bench_operators_cfd_7pt",
                                                  _BASE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _base_module()


def member_seed(seed: int, member: int) -> int:
    """The seed of member ``member``'s wind: a stream of its own, so that the
    members are drawn side by side."""
    return int(np.random.SeedSequence([int(seed), 49, int(member)])
               .generate_state(1, dtype=np.uint64)[0])


def member_fields(data: dict, member: int) -> np.ndarray:
    """Member ``member``'s seven coefficient fields ``[7, c, b, a]`` with the
    shift on the diagonal: what its row of ``data["values"]`` was made from."""
    f, _ = base.fields_of(data["box"], member_seed(data["seed"], member))
    f[3] += np.float32(data["carry"])
    return f


class Forcing:
    """``data["source"]``: ``source[k]`` is member ``k``'s source at its next
    step, and reading it advances that member's clock. The member's one field
    of standard normal noise, read from ``STRIDE`` points further on (and round
    the end) at every step: white in space, and the draw of one step shares no
    point's value with the draw of another at the same point, so white in time
    over the steps a run makes. The right-hand side a member sent is kept with
    its answer, so the comparison needs no clock."""

    STRIDE = 104_729  # a prime; a run's steps stay far below rows / STRIDE turns

    def __init__(self, noise: np.ndarray):
        self.noise = noise
        self.steps = [0] * len(noise)

    def __len__(self) -> int:
        return len(self.noise)

    def __getitem__(self, member: int) -> np.ndarray:
        t = self.steps[member]
        self.steps[member] = t + 1
        return np.roll(self.noise[member], -((t * self.STRIDE)
                                             % self.noise.shape[1]))


def make(sizes: dict, seed: int) -> dict:
    """Host data of one run: the pattern, every member's values, initial
    condition and source."""
    box = tuple(int(v) for v in sizes["box"])
    clients = int(sizes["clients"])
    shift = float(sizes["shift"])
    a, b, _c = box
    n, nnz = base.counts(box)
    # base.make's pattern without its values (its order: a row's entries,
    # columns rising; the seven directions' offsets as base.inside_box and
    # base.fields_of order them), and where a CSR entry lies in the seven
    # fields laid end to end, as one index for every member
    rows, ks = np.nonzero(base.inside_box(box).reshape(7, n).T)
    offsets = np.array([-a * b, -a, -1, 0, 1, a, a * b], dtype=np.int64)
    indptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    P = sp.csr_matrix((np.ones(nnz, dtype=np.float32),
                       (rows + offsets[ks]).astype(np.int32), indptr),
                      shape=(n, n))
    P.has_sorted_indices = True
    entry = ks.astype(np.int64) * n + rows
    values = np.empty((clients, nnz), dtype=np.float32)
    u0, source = (np.empty((clients, n), dtype=np.float32) for _ in range(2))
    data = {"box": box, "seed": seed, "carry": shift}

    def client(k: int) -> None:
        np.take(member_fields(data, k).reshape(-1), entry, out=values[k])
        rng = np.random.default_rng([int(seed), 50, k])
        rng.standard_normal(out=u0[k], dtype=np.float32)
        rng.standard_normal(out=source[k], dtype=np.float32)

    # numpy's generators and copies release the GIL: in bulk, on the host's
    # cores (set-up: 64 members of 1,270,432 rows are 2.3 GB of values)
    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(client, range(clients)))
    data.update({
        "rows": n, "nnz": nnz, "pattern": P, "values": values,
        "initial": u0, "source": Forcing(source),
        "rel_tol": float(sizes["rel_tol"]),
        "clients": clients, "restart": int(sizes["restart"]),
        "check_sample": int(sizes["check_sample"]),
    })
    return data


def apply_f64(data: dict, values, u) -> np.ndarray:
    """(s I + A_k) u in float64 for one member's ``values`` (a row of
    ``data["values"]``: the float32 entries the program is given), by
    scipy's CSR product (``cfd_7pt.apply_f64``'s sums, forty times as
    fast: a run compares a dozen answers)."""
    P = data["pattern"]
    A = sp.csr_matrix((np.asarray(values, dtype=np.float64), P.indices,
                       P.indptr), shape=P.shape)
    return A @ np.asarray(u, dtype=np.float64)


def true_relres(data: dict, x, values, b) -> float:
    b64 = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b64 - apply_f64(data, values, x))
                 / np.linalg.norm(b64))


def reference_gmres(data: dict, member: int, b, cycles: int = REFERENCE_CYCLES,
                    dtype: str = "float32") -> np.ndarray:
    """The converged solution of member ``member``'s system for the
    right-hand side ``b``: ``cfd_7pt``'s restarted GMRES from x = 0,
    ``cycles`` cycles of ``restart`` steps (past float32's floor at the
    default). ``bfloat16`` is the control: the nearest precision below the
    float32 the configuration states."""
    return base.reference_gmres(
        {"fields": member_fields(data, member), "b": np.asarray(b),
         "box": data["box"], "restart": data["restart"], "cycles": cycles},
        dtype=dtype)


def compare(data: dict, x, x_ref, values, b) -> dict:
    x64 = np.asarray(x, dtype=np.float64)
    r64 = np.asarray(x_ref, dtype=np.float64)
    return {
        "x_vs_reference": float(np.linalg.norm(x64 - r64) / np.linalg.norm(r64)),
        "relres": true_relres(data, x64, values, b),
    }


def _sample(data: dict, answers: list) -> list:
    """``check_sample`` of the answers drawn from the seed, and the last."""
    k = min(int(data["check_sample"]), len(answers))
    rng = np.random.default_rng(data["seed"])
    picks = set(rng.choice(len(answers), size=k, replace=False).tolist())
    return [a for i, a in enumerate(answers)
            if i in picks or i == len(answers) - 1]


def check(data: dict, answers: list, limits: dict, say=print) -> list:
    """A sample of the window's answers against the plain reference.
    ``answers``: dicts with ``request`` (the member), ``b`` (the right-hand
    side it sent) and ``x``."""
    sample = _sample(data, answers)
    # the references one system at a time, on the device
    refs = [reference_gmres(data, a["request"], a["b"]) for a in sample]

    def one(pair) -> tuple:
        a, ref = pair
        if not np.all(np.isfinite(a["x"])):
            return float("inf"), 0.0
        nums = compare(data, a["x"], ref, data["values"][a["request"]],
                       a["b"])
        return nums["x_vs_reference"], nums["relres"] / data["rel_tol"]

    # the float64 residuals are numpy over 8.8M entries each and release the
    # GIL: side by side
    with ThreadPoolExecutor(max_workers=8) as pool:
        nums = list(pool.map(one, zip(sample, refs)))
    worst = {"x_vs_reference": max((x for x, _ in nums), default=0.0),
             "relres_over_asked": max((r for _, r in nums), default=0.0)}
    say(f"  {len(answers)} answers, {len(sample)} of them compared")
    return [{"name": k, "value": v, "limit": float(limits[k]),
             "ok": v <= float(limits[k])} for k, v in worst.items()]


def control_answers(data: dict, answers: list) -> list:
    """The control: the reference put in the program's place for the sampled
    requests, computed in bfloat16 (the nearest precision below float32)."""
    return [{"x": reference_gmres(data, a["request"], a["b"],
                                  dtype="bfloat16"),
             "iters": 0, "request": a["request"], "b": a["b"]}
            for a in _sample(data, answers)]
