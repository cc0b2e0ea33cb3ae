"""The plain reference of the benchmark's HPCG deployment
(``benchmark/operators/hpcg_27pt.py``, which imports nothing of the program)
against scipy's explicit matrices, and the program against the reference
under the configuration's own limits (PR 53).

The explicit matrices are those of ``tests/test_hpcg_grid.py``: HPCG's rule
as a CSR matrix, the colour-major permutation from its definition, injection
as a 0/1 matrix.
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from sparse_tpu import linalg
from sparse_tpu.models import hpcg_grid as hg

from .test_hpcg_grid import (FORWARD, colour_perm, cycle_oracle, hpcg_csr,
                             symgs_oracle)
from .utils.spd import operator_module

ref = operator_module("hpcg_27pt")
CONFIG = json.load(open(os.path.join(
    os.path.dirname(__file__), "..", "benchmark", "configs",
    "hpcg-27pt-256.json")))
LIMITS = CONFIG["limits"]
REHEARSE = CONFIG["rehearse"]["sizes"]
# grid, levels, iterations: the configuration's rehearsal size, and 16^3 and
# 32^3 under two, three and four levels at counts short of float32's floor
SOLVES = [(tuple(REHEARSE["grid"]), REHEARSE["levels"], REHEARSE["iterations"]),
          ((16, 16, 16), 2, 8), ((16, 16, 16), 3, 8), ((16, 16, 16), 4, 8),
          ((32, 32, 32), 2, 10), ((32, 32, 32), 3, 10), ((32, 32, 32), 4, 10),
          ((32, 16, 16), 3, 8)]
SOLVE_IDS = ["rehearse", "n16-l2", "n16-l3", "n16-l4", "n32-l2", "n32-l3",
             "n32-l4", "32x16x16-l3"]


@pytest.mark.parametrize("nx,ny,nz", [(8, 8, 8), (16, 8, 4)])
def test_reference_planes_and_product_are_hpcgs_matrix(nx, ny, nz):
    A = hpcg_csr(nx, ny, nz)
    dims = (nz, ny, nx)
    planes = ref.planes_of(dims, jnp.float64)
    assert planes.shape == (27, nz, ny, nx)
    assert np.count_nonzero(np.asarray(planes)) == A.nnz
    v = np.random.default_rng(3).standard_normal(A.shape[0])
    got = ref.apply_planes(planes, jnp.asarray(v.reshape(dims)))
    assert np.allclose(np.asarray(got).ravel(), A @ v, rtol=0, atol=1e-12)
    # the judge's float64 operator, which knows the entries, is the same
    assert np.allclose(ref.apply_f64(v, dims), A @ v, rtol=0, atol=1e-12)
    assert ref.true_relres(np.ones(A.shape[0]), A @ np.ones(A.shape[0]),
                           dims) < 1e-15


@pytest.mark.parametrize("nx,ny,nz", [(8, 8, 8), (16, 8, 4)])
def test_reference_sweep_is_two_triangular_solves_in_the_coloured_order(nx, ny, nz):
    A, perm = hpcg_csr(nx, ny, nz), colour_perm(nx, ny, nz, FORWARD)
    assert ref.FORWARD == FORWARD
    dims = (nz, ny, nx)
    rng = np.random.default_rng(6)
    r, x0 = rng.standard_normal((2, A.shape[0]))
    got = ref.symgs(ref.planes_of(dims, jnp.float64), ref.colours_of(dims),
                    jnp.asarray(r.reshape(dims)), jnp.asarray(x0.reshape(dims)))
    assert np.allclose(np.asarray(got).ravel(), symgs_oracle(A, perm, r, x0),
                       rtol=0, atol=1e-13)


@pytest.mark.parametrize("levels", [2, 3])
def test_reference_cycle_is_the_cycle_in_matrices(levels):
    nx, ny, nz = 16, 8, 8
    hier = ref.hierarchy((nz, ny, nx), levels, "float64")
    r = np.random.default_rng(4).standard_normal(nx * ny * nz)
    got = ref.vcycle(hier, jnp.asarray(r.reshape(nz, ny, nx)))
    assert np.allclose(np.asarray(got).ravel(),
                       cycle_oracle((nx, ny, nz), levels, r), rtol=0, atol=1e-13)


def _solve(grid, levels, its, seed):
    data = ref.make({"grid": list(grid), "levels": levels, "iterations": its},
                    seed)
    hier = hg.build_hierarchy(*grid, levels=levels)
    x, iters = linalg.cg(hg.grid_operator(hier), jnp.asarray(data["b"]),
                         tol=0.0, maxiter=its, M=hg.make_vcycle(hier))
    return data, [{"x": np.asarray(x), "iters": int(iters), "index": 0}]


@pytest.mark.parametrize("seed", [7, 2147483659])
@pytest.mark.parametrize("grid,levels,its", SOLVES, ids=SOLVE_IDS)
def test_the_program_agrees_with_the_reference_under_the_limits(grid, levels, its, seed):
    data, answers = _solve(grid, levels, its, seed)
    checks = ref.check(data, answers, LIMITS, lambda *_: None)
    assert {c["name"] for c in checks} == set(LIMITS)
    assert all(c["ok"] for c in checks), checks
    # an answer that is the start, or off by a hundredth, is not
    for bad in (0.0, 1.01):
        wrong = [dict(answers[0], x=bad * answers[0]["x"])]
        assert not all(c["ok"] for c in ref.check(data, wrong, LIMITS,
                                                  lambda *_: None))


@pytest.mark.parametrize("grid,levels,its", SOLVES[:4] + SOLVES[6:],
                         ids=SOLVE_IDS[:4] + SOLVE_IDS[6:])
def test_the_control_in_bfloat16_fails_a_limit(grid, levels, its):
    data, answers = _solve(grid, levels, its, 11)
    control = ref.control_answers(data, answers)
    checks = ref.check(data, control, LIMITS, lambda *_: None)
    assert any(not c["ok"] for c in checks), checks
    assert next(c for c in checks if c["name"] == "iterations_off")["ok"]


def test_the_reference_solves_the_system_and_the_seed_reaches_b_alone():
    grid = [16, 16, 16]
    sizes = {"grid": grid, "levels": 3, "iterations": 12}
    data = ref.make(sizes, 5)
    assert data["b"].dtype == np.float32 and data["rows"] == 4096
    assert data["dims"] == (16, 16, 16) and data["grid"] == grid
    assert np.array_equal(data["b"], ref.make(sizes, 5)["b"])
    assert not np.array_equal(data["b"], ref.make(sizes, 6)["b"])
    # b = A (1 + (u - 1/2) / 2): the answer lies between 3/4 and 5/4
    x = ref.reference_cg(data["b"], data["dims"], 3, 12)
    assert 0.74 < x.min() and x.max() < 1.26
    A = hpcg_csr(*grid)
    assert np.linalg.norm(A @ x - data["b"]) < 1e-4 * np.linalg.norm(data["b"])
    assert ref.true_relres(x, data["b"], data["dims"]) < 1e-4
