"""The generator and the plain reference of the benchmark's XGC collision
deployment (``benchmark/operators/xgc_collision.py``, which imports nothing
of the program) against explicit scipy matrices and ``spsolve``, and the
program (``linalg.batched_bicgstab`` as the adaptor calls it) against the
reference at the rehearsal size under the configuration's own limits, with the
bfloat16 control outside them (PR 55).
"""

import json
import os

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

import jax.numpy as jnp

from sparse_tpu import linalg, precond
from sparse_tpu.batch import BatchedCSR, SparsityPattern

from .utils.spd import operator_module

gen = operator_module("xgc_collision")
CONFIG = json.load(open(os.path.join(
    os.path.dirname(__file__), "..", "benchmark", "configs",
    "xgc-collision-992.json")))
LIMITS = CONFIG["limits"]
SIZES = {**CONFIG["sizes"], **CONFIG["rehearse"]["sizes"]}
SEEDS = [7, 2147483659, 5500000123]


@pytest.fixture(scope="module")
def data():
    return gen.make(SIZES, SEEDS[0])


def _lane_matrix(d, lane) -> sp.csr_matrix:
    return sp.csr_matrix((np.asarray(d["values"][lane], dtype=np.float64),
                          d["indices"], d["indptr"]), shape=(d["rows"],) * 2)


def test_the_sizes_are_the_sources():
    s = CONFIG["sizes"]
    assert (s["velocity_grid"], s["rows"], s["nnz"], s["diags"], s["species"]) == (
        [32, 31], 992, 8554, 9, 2)
    assert gen.counts(s["velocity_grid"]) == (992, 8554)
    assert (s["tol_rel"], s["conv_test_iters"], s["dtype"]) == (1e-5, 1, "float32")
    assert s["systems"] in (32768, 16384) and SIZES["systems"] == 256
    assert gen.offsets_of(s["velocity_grid"]) == gen.OFFSETS
    assert set(CONFIG["reduced"]) == {"dtype"}
    assert gen.MARGIN == pytest.approx(
        1 - 2 * gen.DT_NU["electron"] * gen.COLLISIONALITY[1])


def test_the_pattern_is_the_nine_point_stencils(data):
    d = data
    assert (d["rows"], d["nnz"], d["diags"]) == (992, 8554, 9)
    assert d["values"].shape == (256, 8554) and d["values"].dtype == jnp.float32
    A = _lane_matrix(d, 1)
    assert A.nnz == 8554 and A.has_sorted_indices
    rows = np.repeat(np.arange(992), np.diff(d["indptr"]))
    assert sorted(set((d["indices"] - rows).tolist())) == list(gen.OFFSETS)
    # a row at the grid's corner holds 4 entries, at an edge 6, inside 9
    assert sorted(set(np.diff(d["indptr"]).tolist())) == [4, 6, 9]
    # symmetric in pattern, not in values
    P = sp.csr_matrix((np.ones(A.nnz), A.indices, A.indptr), shape=A.shape)
    assert (P != P.T).nnz == 0
    assert abs(A - A.T).max() > 1e-2 * abs(A).max()
    # every stored entry of an electron lane is a nonzero (an ion lane's
    # far corners underflow nowhere either)
    assert np.count_nonzero(np.asarray(d["values"][:2])) == 2 * 8554


def test_the_csr_values_are_the_generators_planes(data):
    d = data
    lanes = [0, 1, 255]
    made = np.asarray(gen.planes_of(d["grid"], d["params"], lanes))
    assert made.shape == (3, 9, 992)
    for k, lane in enumerate(lanes):
        planes = gen.lane_planes(d, lane)  # what the reference multiplies by
        # row layout: slot i of plane j holds A[i, i + o_j]
        D = sp.lil_matrix((992, 992))
        for j, o in enumerate(gen.OFFSETS):
            i = np.arange(max(0, -o), min(992, 992 - o))
            D[i, i + o] = planes[j, i]
        A = _lane_matrix(d, lane)
        assert abs(D.tocsr() - A).max() == 0
        # and a plane is zero where the neighbour is outside the grid
        assert np.count_nonzero(planes) == 8554
        # made again for three lanes alone, the planes are the stack's to an
        # ulp of the diagonal (XLA sums a cell's eight rates in another order)
        assert np.abs(made[k] - planes).max() <= 4e-6


@pytest.mark.parametrize("mesh_seed", SEEDS)
def test_every_matrix_is_strictly_dominant_conservative_and_an_m_matrix(mesh_seed):
    d = gen.make({**SIZES, "systems": 64, "mesh_seed": mesh_seed}, 3)
    margin = gen.dominance_margin(d, np.arange(64))
    # the stated margin, 1 - 2 dt nu at the largest collisionality
    assert margin.min() >= gen.MARGIN
    # ions barely collide in a step: their margin is near 1
    assert margin[0::2].min() > 0.9 and margin[1::2].max() < 0.7
    # another mesh is other matrices
    other = gen.make({**SIZES, "systems": 64, "mesh_seed": mesh_seed + 1}, 3)
    assert not np.array_equal(np.asarray(other["values"]), np.asarray(d["values"]))
    for lane in (0, 1, 62, 63):
        A = _lane_matrix(d, lane)
        off = A - sp.diags(A.diagonal())
        assert off.max() <= 0 and A.diagonal().min() >= 1
        # C's columns sum to zero: particles are conserved (float32 entries)
        assert np.allclose(np.asarray(A.sum(axis=0)).ravel(), 1.0, atol=2e-5)
        # ||A^-1||_inf <= 1 / margin
        inv = np.linalg.inv(A.toarray())
        assert np.abs(inv).sum(axis=1).max() <= 1 / margin[lane] + 1e-9


def test_two_species_and_a_smooth_vertex_profile(data):
    p = data["params"]
    assert np.array_equal(p["species"], np.arange(256) % 2)
    ratio = p["dt_nu"][1::2] / p["dt_nu"][0::2]
    assert np.allclose(ratio, gen.DT_NU["electron"] / gen.DT_NU["ion"], rtol=1e-6)
    lo, hi = gen.COLLISIONALITY
    assert lo * 0.999 <= p["collisionality"].min() and p["collisionality"].max() <= hi * 1.001
    # a vertex's two lanes share its draws; density spreads over about a decade
    for k in ("density", "theta", "drift", "collisionality"):
        assert np.array_equal(p[k][0::2], p[k][1::2])
    assert p["density"].max() / p["density"].min() > 5
    assert 0.85 <= p["theta"].min() and p["theta"].max() <= 1.2
    assert np.abs(p["drift"]).max() <= 0.5
    # the same seed draws the same run; another mesh_seed another mesh
    again, _order = gen.run_parameters(SIZES, SEEDS[0])
    assert all(np.array_equal(again[k], p[k]) for k in p)
    other = gen.lane_parameters(256, SIZES["mesh_seed"] + 1)
    assert not np.array_equal(np.sort(other["theta"]), np.sort(p["theta"]))


def test_a_runs_seed_draws_the_vertices_order_and_units_and_nothing_else():
    """``run_draw``: every run solves the mesh's systems, its vertices in
    another order and each in a density unit that is a power of two, both
    exact in floating point: the lanes' iteration counts, which set a call's
    length, are the mesh's whatever the seed."""
    a, b = gen.make(SIZES, SEEDS[0]), gen.make(SIZES, SEEDS[1])
    mesh = gen.lane_parameters(256, SIZES["mesh_seed"])
    assert not np.array_equal(a["order"], b["order"])
    for d in (a, b):
        order = d["order"]
        assert sorted(order.tolist()) == list(range(256))
        assert np.array_equal(order % 2, np.arange(256) % 2)  # species stay put
        assert np.array_equal(order[1::2], order[0::2] + 1)  # a vertex's pair
        unit = d["params"]["density"] / mesh["density"][order]
        assert set(np.log2(unit).tolist()) <= set(range(-3, 4))
        assert np.array_equal(unit[0::2], unit[1::2]) and len(set(unit.tolist())) > 3
        for k in ("dt_nu", "theta", "drift"):
            assert np.array_equal(d["params"][k], mesh[k][order])
    # the same matrices, lane for lane of the mesh, to the bit; b to its unit
    back_a, back_b = np.argsort(a["order"]), np.argsort(b["order"])
    assert np.array_equal(np.asarray(a["values"])[back_a],
                          np.asarray(b["values"])[back_b])
    ua = (a["params"]["density"] / mesh["density"][a["order"]])[back_a]
    ub = (b["params"]["density"] / mesh["density"][b["order"]])[back_b]
    assert np.array_equal(np.asarray(a["b"])[back_a] / ua[:, None],
                          np.asarray(b["b"])[back_b] / ub[:, None])
    # and so the program takes the same steps on every lane, and its
    # answers are the same to the unit
    (xa,), (xb,) = _program_answers(a), _program_answers(b)
    assert np.array_equal(xa["iters_lanes"][back_a], xb["iters_lanes"][back_b])
    assert np.array_equal(xa["x"][back_a] / ua[:, None], xb["x"][back_b] / ub[:, None])


def test_the_background_maxwellian_is_nearly_stationary(data):
    """``D grad f + F f = 0`` for the Maxwellian of the background's drift
    and temperature: the discrete operator moves it by the scheme's error
    alone, far less than it moves the old state."""
    d, lane = data, 1
    A = _lane_matrix(d, lane)
    p = {k: float(v[lane]) for k, v in d["params"].items()}
    h = gen.EXTENT / 32
    vx = (np.arange(32) + 0.5) * h - gen.EXTENT / 2
    vy = (np.arange(31) + 0.5) * h
    f = np.exp(-((vx[None, :] - p["drift"]) ** 2 + vy[:, None] ** 2)
               / (2 * p["theta"])).ravel()
    moved = np.linalg.norm(A @ f - f) / np.linalg.norm(f)
    b = np.asarray(d["b"][lane], dtype=np.float64)
    assert moved < 0.02 < np.linalg.norm(A @ b - b) / np.linalg.norm(b)


def test_the_old_state_and_the_sample(data):
    d = data
    b = np.asarray(d["b"])
    assert b.shape == (256, 992) and b.dtype == np.float32 and (b > 0).all()
    lanes = gen.sample_lanes(d)
    assert len(lanes) == 33 and lanes[-1] == 255
    species = d["species"][lanes]
    assert abs(int((species == 0).sum()) - int((species == 1).sum())) <= 1
    assert np.array_equal(lanes, gen.sample_lanes(d))


@pytest.mark.parametrize("lane", [0, 1, 128, 255])
def test_reference_against_spsolve(data, lane):
    d = data
    A = _lane_matrix(d, lane)
    b = np.asarray(d["b"][lane], dtype=np.float64)
    want = spla.spsolve(A.tocsc(), b)
    got = gen.reference_bicgstab(d, lane)
    assert got.dtype == np.float32
    # float32's floor: eps ||A|| ||A^-1||, a diagonal of up to 48
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 5e-6
    assert gen.true_relres(d, np.asarray(d["values"][lane]), got, b) < 6e-6
    # run on, it stays where it is: the steps are past the floor
    longer = gen.reference_bicgstab(d, lane, steps=2 * gen.REFERENCE_STEPS)
    assert np.linalg.norm(longer - got) / np.linalg.norm(got) < 5e-6


def _program_answers(d):
    """The adaptor's six calls."""
    n = d["rows"]
    pattern = SparsityPattern(d["indptr"], d["indices"], (n, n))
    op = BatchedCSR(pattern, d["values"]).todia()
    M = precond.make_factory(pattern, "jacobi")(d["values"], op.matvec)
    tol = d["tol_rel"] * jnp.linalg.norm(d["b"], axis=1)
    X, info = linalg.batched_bicgstab(
        op, d["b"], x0=d["b"], tol=tol, maxiter=d["maxiter"], M=M,
        conv_test_iters=d["conv_test_iters"])
    return [{"x": np.asarray(X), "iters": int(np.max(info.iters)),
             "iters_lanes": np.asarray(info.iters),
             "converged": np.asarray(info.converged), "index": 0}]


@pytest.mark.parametrize("seed", SEEDS)
def test_program_meets_the_limits_and_the_control_does_not(seed):
    d = gen.make(SIZES, seed)
    answers = _program_answers(d)
    checks = {c["name"]: c for c in gen.check(d, answers, LIMITS, lambda *_: None)}
    assert set(checks) == {"x_vs_reference", "relres_over_asked",
                           "lanes_unconverged", "mix_lost"}
    assert all(c["ok"] for c in checks.values()), checks
    assert checks["relres_over_asked"]["limit"] == 2.0
    # the two groups the configuration states, about 1 : 5 apart
    by = gen.species_counts(d, answers[0]["iters_lanes"])
    assert 3 <= by["ion"][0] and by["ion"][2] <= 8
    assert 12 <= by["electron"][0] and by["electron"][2] <= 40
    assert by["electron"][1] >= 3 * by["ion"][1]
    control = gen.control_answers(d, answers)
    failed = [c["name"] for c in gen.check(d, control, LIMITS, lambda *_: None)
              if not c["ok"]]
    assert "x_vs_reference" in failed


def test_the_exact_guarantees_catch_what_they_are_for(data):
    d = data
    (a,) = _program_answers(d)
    quiet = lambda *_: None  # noqa: E731
    stuck = dict(a, converged=np.where(np.arange(256) == 3, False, a["converged"]))
    names = {c["name"]: c["value"] for c in gen.check(d, [stuck], LIMITS, quiet)}
    assert names["lanes_unconverged"] == 1 and names["mix_lost"] == 0
    flat = dict(a, iters_lanes=np.full(256, 9))
    names = {c["name"]: c for c in gen.check(d, [flat], LIMITS, quiet)}
    assert names["mix_lost"]["value"] == 1 and not names["mix_lost"]["ok"]
    # an answer altered by a hundredth, or left at its start, is not correct
    for x in (a["x"] * 1.01, np.asarray(d["b"])):
        failed = [c["name"] for c in gen.check(d, [dict(a, x=x)], LIMITS, quiet)
                  if not c["ok"]]
        assert "x_vs_reference" in failed
