"""The plain reference of the benchmark's nonsymmetric deployment
(``benchmark/operators/cfd_7pt.py``, which imports nothing of the program):
its generator against atmosmodd's own counts and scipy, and the program
(``linalg.gmres``) against the reference under the configuration's own
limits (PR 42).
"""

import json
import os

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as sla

import jax.numpy as jnp

import sparse_tpu
from sparse_tpu import linalg

from .utils.spd import operator_module

ref = operator_module("cfd_7pt")
CONFIG = json.load(open(os.path.join(
    os.path.dirname(__file__), "..", "benchmark", "configs",
    "atmosmodd-gmres30.json")))
LIMITS = CONFIG["limits"]
REHEARSE = CONFIG["rehearse"]["sizes"]
# box, restart, cycles: the configuration's rehearsal size and two other boxes
SOLVES = [(REHEARSE["box"], REHEARSE["restart"], REHEARSE["cycles"]),
          ([30, 20, 16], 30, 3), ([17, 13, 9], 10, 5)]
SOLVE_IDS = ["rehearse", "30x20x16-m30", "17x13x9-m10"]
SEEDS = [7, 2**31 + 11]  # the driver's seeds are past 32 signed bits


def _data(box, restart, cycles, seed):
    return ref.make({"box": box, "restart": restart, "cycles": cycles}, seed)


def _scipy(d, dtype=np.float64):
    n = d["rows"]
    return sp.csr_matrix((d["data"].astype(dtype), d["indices"], d["indptr"]),
                         shape=(n, n))


def test_the_counts_are_atmosmodds_by_arithmetic_alone():
    assert CONFIG["sizes"]["box"] == [148, 148, 58]
    assert ref.counts(CONFIG["sizes"]["box"]) == (1_270_432, 8_814_880)
    assert ref.counts([198, 198, 38]) == (1_489_752, 10_319_760)  # atmosmodl
    assert (CONFIG["sizes"]["restart"], CONFIG["sizes"]["cycles"]) == (30, 10)
    assert sorted(CONFIG["reduced"]) == ["dtype"]


@pytest.mark.parametrize("box", [[24, 24, 10], [7, 5, 3], [4, 9, 6], [2, 2, 2]],
                         ids=lambda b: "x".join(map(str, b)))
def test_the_generator_makes_the_seven_point_pattern(box):
    d = _data(box, 30, 1, 5)
    a, b, c = box
    n = a * b * c
    assert (d["rows"], d["nnz"]) == ref.counts(box) == (
        n, 7 * n - 2 * (a * b + b * c + c * a))
    S = _scipy(d)
    assert S.has_sorted_indices and S.nnz == d["nnz"]
    # the pattern is symmetric and the values are not
    P = S.copy()
    P.data[:] = 1.0
    assert (P != P.T).nnz == 0
    assert (S != S.T).nnz > 0
    offsets = np.unique((S.tocoo().col - S.tocoo().row))
    assert set(offsets) <= {-a * b, -a, -1, 0, 1, a, a * b}
    # an M-matrix: 6 on the diagonal, the others in [-1.5, -0.5], each pair
    # towards +/- an axis summing to -2, so rows sum to zero inside the box
    assert np.all(S.diagonal() == 6.0)
    off = S - sp.diags(S.diagonal())
    assert off.data.max() <= -0.5 and off.data.min() >= -1.5
    sums = np.asarray(S.sum(axis=1)).ravel()
    assert np.all(sums >= -1e-5)
    if min(box) > 2:
        inner = np.zeros((c, b, a), bool)
        inner[1:-1, 1:-1, 1:-1] = True
        assert np.allclose(sums[inner.ravel()], 0.0, atol=1e-5)
    # the seven fields are the matrix, plane for plane
    u = np.random.default_rng(1).standard_normal(n)
    got = np.asarray(ref.apply_box(  # float64: conftest.py turns x64 on
        jnp.asarray(d["fields"], jnp.float64),
        jnp.asarray(u.reshape(c, b, a)))).ravel()
    assert np.allclose(got, S @ u, rtol=1e-12, atol=1e-12)
    assert np.allclose(ref.apply_f64(d, u), S @ u, rtol=1e-12, atol=1e-12)


def test_the_seed_reaches_the_values_and_b_and_nothing_else():
    d1, d2 = _data([9, 8, 7], 30, 1, 1), _data([9, 8, 7], 30, 1, 2)
    assert np.array_equal(d1["indptr"], d2["indptr"])
    assert np.array_equal(d1["indices"], d2["indices"])
    assert not np.array_equal(d1["data"], d2["data"])
    assert not np.array_equal(d1["b"], d2["b"])
    d3 = _data([9, 8, 7], 30, 1, 1)
    assert np.array_equal(d1["data"], d3["data"]) and np.array_equal(d1["b"], d3["b"])
    assert 0.5 <= d1["b"].min() and d1["b"].max() <= 1.5
    assert d1["data"].dtype == d1["b"].dtype == np.float32


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("box,restart,cycles", SOLVES, ids=SOLVE_IDS)
def test_the_program_agrees_with_the_reference_under_the_limits(
        box, restart, cycles, seed):
    d = _data(box, restart, cycles, seed)
    n = d["rows"]
    A = sparse_tpu.csr_array((d["data"], d["indices"], d["indptr"]), shape=(n, n))
    x, iters = linalg.gmres(A, d["b"], restart=restart, maxiter=cycles, tol=1e-30)
    assert A._spmv_form(np.float32)[0] == "dia"
    answers = [{"x": np.asarray(x), "iters": iters, "index": 0}]
    checks = {c["name"]: c for c in ref.check(d, answers, LIMITS, lambda *_: None)}
    assert set(checks) == set(LIMITS)
    assert all(c["ok"] for c in checks.values()), checks
    assert checks["iterations_off"]["value"] == 0 and iters == restart * cycles
    # the bfloat16 control fails at least one of the limits
    ctl = ref.control_answers(d, answers)
    failed = [c["name"] for c in ref.check(d, ctl, LIMITS, lambda *_: None)
              if not c["ok"]]
    assert failed, "the control passed every limit"


@pytest.mark.parametrize("box,restart,cycles", [
    ([30, 20, 16], 30, 1), ([17, 13, 9], 10, 3), ([24, 24, 10], 30, 1)],
    ids=["30x20x16-m30", "17x13x9-m10", "24x24x10-m30"])
def test_the_reference_is_scipys_gmres(box, restart, cycles):
    """scipy's restarted GMRES in float64 on the same system, the same
    restart and cycle count from zero, short of float32's floor (where the
    residual's last bits are noise): the same residual to the float32
    reference's rounding, and the same iterate."""
    d = _data(box, restart, cycles, 3)
    x_ref = ref.reference_gmres(d)
    S, b = _scipy(d), d["b"].astype(np.float64)
    x_sp, _info = sla.gmres(S, b, restart=restart, maxiter=cycles, rtol=1e-30,
                            atol=0.0)
    rr_ref, rr_sp = ref.true_relres(d, x_ref), ref.true_relres(d, x_sp)
    assert rr_sp < 0.5  # the cycles made progress
    assert rr_ref == pytest.approx(rr_sp, rel=1e-3)
    assert np.linalg.norm(x_ref - x_sp) <= 1e-4 * np.linalg.norm(x_sp)
    assert ref.true_relres(d, x_ref) == pytest.approx(
        np.linalg.norm(b - S @ x_ref.astype(np.float64)) / np.linalg.norm(b))


def test_the_reference_stops_after_the_cycles_it_is_given():
    d = _data([12, 10, 8], 10, 4, 9)
    rr = [ref.true_relres(d, ref.reference_gmres(d, cycles=c)) for c in (1, 2, 4)]
    assert rr[0] > rr[1] > rr[2] > 0
    assert np.array_equal(ref.reference_gmres(d), ref.reference_gmres(d, cycles=4))
    assert np.all(ref.reference_gmres(d, cycles=0) == 0)
