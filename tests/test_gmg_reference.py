"""The plain reference of the benchmark's multigrid deployment
(``benchmark/operators/gmg_poisson.py``, which imports nothing of the
program) against scipy's explicit matrices, and the program against the
reference under the configuration's own limits (PR 40).

The explicit matrices are those of ``examples/gmg.py`` (``poisson2D``,
``linear_operator``) in scipy form, as ``tests/test_gmg_grid.py`` writes
them for the program's grid-space pipeline.
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from sparse_tpu import linalg
from sparse_tpu.models import gmg_grid as gg

from .test_gmg_grid import R_mat, poisson_sp
from .utils.spd import operator_module

ref = operator_module("gmg_poisson")
CONFIG = json.load(open(os.path.join(
    os.path.dirname(__file__), "..", "benchmark", "configs",
    "gmg-poisson-4500.json")))
LIMITS = CONFIG["limits"]
REHEARSE = CONFIG["rehearse"]["sizes"]
# grid side, levels, iterations: the configuration's rehearsal size, and 64
# with two and three levels at 12 iterations: short of convergence, as the
# deployment's fixed count leaves its system (by 25 a 64^2 grid under three
# levels is at float32's floor, where the residual's last bits are noise
# and relres_gap with them; at 50 the solve stops one short)
SOLVES = [(REHEARSE["grid"], REHEARSE["levels"], REHEARSE["iterations"]),
          (64, 2, 12), (64, 3, 12)]
SOLVE_IDS = ["rehearse", "n64-l2", "n64-l3"]


def _explicit(n):
    """(A, R, P) of one level as scipy matrices in float64."""
    R = R_mat(n, "linear")
    return poisson_sp(n), R, R.T.tocsr()


def _plane_of(Ac, cn, di, dj):
    """The coefficient plane of offset (di, dj) read off an explicit coarse
    operator: entry (i cn + j, (i + di) cn + j + dj), zero past the edge."""
    Ac = Ac.tocsr()
    out = np.zeros((cn, cn))
    i, j = np.meshgrid(np.arange(cn), np.arange(cn), indexing="ij")
    ok = (i + di >= 0) & (i + di < cn) & (j + dj >= 0) & (j + dj < cn)
    rows = (i * cn + j)[ok]
    cols = ((i + di) * cn + j + dj)[ok]
    out[ok] = np.asarray(Ac[rows, cols]).ravel()
    return out


@pytest.mark.parametrize("n", [17, 32, 96])
def test_reference_transfers_are_the_explicit_matrices(n):
    A, R, P = _explicit(n)
    cn = n // 2
    rng = np.random.default_rng(n)
    u, y = rng.random((n, n)), rng.random((cn, cn))
    got = np.asarray(ref.restrict(jnp.asarray(u, jnp.float32)))
    assert np.allclose(got.ravel(), R @ u.ravel(), rtol=0, atol=2e-6)
    got = np.asarray(ref.prolong(jnp.asarray(y, jnp.float32), n))
    assert np.allclose(got.ravel(), P @ y.ravel(), rtol=0, atol=2e-6)
    got = np.asarray(ref.apply_fine(jnp.asarray(u, jnp.float32)))
    assert np.allclose(got.ravel(), A @ u.ravel(), rtol=0, atol=1e-5)
    assert np.allclose(ref.apply_f64(u, n), A @ u.ravel(), rtol=0, atol=1e-12)
    assert ref.true_relres(np.zeros(n * n), u.ravel(), n) == pytest.approx(1.0)


@pytest.mark.parametrize("n", [17, 32, 96])
def test_reference_coarse_planes_equal_scipys_rap(n):
    """Both coarse levels: R A P of the 5-point operator, and R A_1 P of
    the nine-plane operator that gave."""
    hier = ref.hierarchy(n, 3)
    assert hier[0][0] is None and float(hier[0][1]) > 0
    A = poisson_sp(n)
    for planes, _w in hier[1:]:
        _, R, P = _explicit(n)
        A = (R @ A @ P).tocsr()
        n //= 2
        assert A.shape == (n * n, n * n)
        assert sorted(planes) == sorted(ref.OFFSETS)
        reach_one = sum(_plane_of(A, n, di, dj) for di, dj in ref.OFFSETS).sum()
        assert reach_one == pytest.approx(A.sum(), abs=1e-9)  # nothing farther out
        for (di, dj), plane in planes.items():
            assert np.allclose(np.asarray(plane), _plane_of(A, n, di, dj),
                               rtol=0, atol=2e-6), (n, di, dj)


@pytest.mark.parametrize("n,levels", [(33, 3), (64, 2), (64, 3), (96, 3)])
def test_the_rule_for_omega_gives_the_programs_weights(n, levels):
    mine = ref.hierarchy(n, levels)
    theirs = gg.build_hierarchy(n, levels)
    assert [int(w.shape[0]) if w.ndim else 0 for _, w in mine] == [
        0] + [n // 2 ** k for k in range(1, levels)]
    for (planes, w), (st, w2, _n) in zip(mine, theirs):
        assert np.allclose(np.asarray(w), np.asarray(w2), rtol=1e-5, atol=0)
        if planes is not None:
            for d in planes:
                assert np.allclose(np.asarray(planes[d]), np.asarray(st[d]),
                                   rtol=0, atol=2e-6)


def _solve(n, levels, its, seed):
    data = ref.make({"grid": n, "levels": levels, "iterations": its,
                     "gridop": "linear"}, seed)
    hier = gg.build_hierarchy(n, levels)
    x, iters = linalg.cg(gg.grid_operator(hier), jnp.asarray(data["b"]),
                         maxiter=its, M=gg.make_vcycle(hier))
    return data, [{"x": np.asarray(x), "iters": int(iters), "index": 0}]


@pytest.mark.parametrize("seed", [7, 2147483659])
@pytest.mark.parametrize("n,levels,its", SOLVES, ids=SOLVE_IDS)
def test_the_program_agrees_with_the_reference_under_the_limits(n, levels, its, seed):
    data, answers = _solve(n, levels, its, seed)
    checks = ref.check(data, answers, LIMITS, lambda *_: None)
    assert {c["name"] for c in checks} == set(LIMITS)
    assert all(c["ok"] for c in checks), checks
    # an answer that is the start, or off by a hundredth, is not
    for bad in (0.0, 1.01):
        wrong = [dict(answers[0], x=bad * answers[0]["x"])]
        assert not all(c["ok"] for c in ref.check(data, wrong, LIMITS,
                                                  lambda *_: None))


@pytest.mark.parametrize("n,levels,its", SOLVES, ids=SOLVE_IDS)
def test_the_control_in_bfloat16_fails_a_limit(n, levels, its):
    data, answers = _solve(n, levels, its, 11)
    control = ref.control_answers(data, answers)
    checks = ref.check(data, control, LIMITS, lambda *_: None)
    assert any(not c["ok"] for c in checks), checks
    assert next(c for c in checks if c["name"] == "iterations_off")["ok"]


def test_the_reference_solves_the_system():
    n = 48
    data = ref.make({"grid": n, "levels": 3, "iterations": 20,
                     "gridop": "linear"}, 5)
    assert data["b"].dtype == np.float32 and data["rows"] == n * n
    again = ref.make({"grid": n, "levels": 3, "iterations": 20}, 5)
    assert np.array_equal(data["b"], again["b"])
    assert not np.array_equal(data["b"], ref.make(
        {"grid": n, "levels": 3, "iterations": 20}, 6)["b"])
    x = ref.reference_cg(data["b"], n, 3, 20)
    A = poisson_sp(n)
    # a fixed count of iterations, as the deployment runs them: twenty
    # leave a 48^2 system short of float32's floor (past it the recurrence
    # divides 0 by 0, which no stopping rule is there to prevent)
    assert np.linalg.norm(A @ x - data["b"]) < 1e-3 * np.linalg.norm(data["b"])
    assert ref.true_relres(x, data["b"], n) < 1e-3
    with pytest.raises(ValueError):
        ref.make({"grid": n, "levels": 3, "iterations": 20,
                  "gridop": "injection"}, 5)
