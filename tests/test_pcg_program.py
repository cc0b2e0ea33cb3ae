"""``linalg.cg`` over operators that declare what they hold: one compiled
whole-solve program (``jit_pcg``) with A's and M's arrays as arguments
(PR 40).

The clients: the grid-space multigrid hierarchy of
``sparse_tpu/models/gmg_grid.py`` (``grid_operator`` and ``make_vcycle``) and
a ``csr_array`` under ``precond.make_M``'s declared point-Jacobi. A second
solve of the same structure, whatever the values, traces nothing
(``cg.precond.traces``); the answer is the closure loop's
(``_cg_device_loop``), which an operator without operands still runs.
"""

import re

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import sparse_tpu
from sparse_tpu import linalg, precond, telemetry
from sparse_tpu.config import settings
from sparse_tpu.models import gmg_grid as gg
from sparse_tpu.telemetry import _metrics

TRACES = _metrics.counter("cg.precond.traces")

# grid side, levels, grid operator
HIERARCHIES = [(33, 2, "linear"), (33, 3, "linear"), (64, 2, "injection"),
               (64, 3, "linear"), (96, 3, "linear"), (96, 2, "injection")]
HIER_IDS = [f"n{n}-l{lv}-{op}" for n, lv, op in HIERARCHIES]


@pytest.fixture(autouse=True)
def _fresh_program():
    """These tests count traces of ``jit_pcg``; an earlier test of this
    process that solved the same structure would leave them none."""
    linalg._pcg_program.clear_cache()


@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield
    telemetry.configure(None)
    telemetry.reset()


def _rhs(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).random(n), jnp.float32)


def _grid(n, levels, gridop, omega=4.0 / 3.0):
    hier = gg.build_hierarchy(n, levels, gridop, omega=omega)
    return gg.grid_operator(hier), gg.make_vcycle(hier, gridop), _rhs(n * n)


def _csr(n=400, scale=1.0, seed=3):
    rng = np.random.default_rng(seed)
    S = sp.random(n, n, density=0.02, random_state=rng, dtype=np.float32)
    S = S + S.T  # symmetric, then strictly diagonally dominant: SPD
    S = (S + sp.diags(np.asarray(abs(S).sum(axis=1)).ravel() + 1.0 + rng.random(n))) * scale
    A = sparse_tpu.csr_array(S.tocsr().astype(np.float32))
    return A, precond.make_M(A, "jacobi"), _rhs(n, seed)


def _as_closures(A, M):
    """The same two products with nothing declared: the old path's input."""
    A = linalg.make_linear_operator(A)
    return (linalg.LinearOperator(A.shape, matvec=A.matvec, dtype=A.dtype),
            linalg.LinearOperator(M.shape, matvec=M.matvec, dtype=M.dtype))


# -- one program a structure ---------------------------------------------------
def _again_same(A, M, b, mk):
    return A, M, b, {}


def _again_other_b(A, M, b, mk):
    return A, M, 2.0 * b[::-1], {}


def _again_other_maxiter_tol(A, M, b, mk):
    return A, M, b, {"maxiter": 7, "tol": 1e-3}


def _again_x0(A, M, b, mk):
    return A, M, b, {"x0": 0.5 * b}


def _again_other_values(A, M, b, mk):
    A2, M2, _ = mk()  # new operator objects, other values in the operands
    return A2, M2, b, {}


AGAIN = [_again_same, _again_other_b, _again_other_maxiter_tol, _again_x0,
         _again_other_values]
AGAIN_IDS = [f.__name__[len("_again_"):] for f in AGAIN]


@pytest.mark.parametrize("again", AGAIN, ids=AGAIN_IDS)
@pytest.mark.parametrize("n,levels,gridop", HIERARCHIES[:4], ids=HIER_IDS[:4])
def test_a_later_solve_over_a_hierarchy_traces_nothing(n, levels, gridop, again):
    A, M, b = _grid(n, levels, gridop)
    t0 = TRACES.value
    linalg.cg(A, b, maxiter=12, M=M)
    assert TRACES.value == t0 + 1
    A2, M2, b2, kw = again(A, M, b, lambda: _grid(n, levels, gridop, omega=1.1))
    kw = {"maxiter": 12, **kw}
    x, iters = linalg.cg(A2, b2, M=M2, **kw)
    assert TRACES.value == t0 + 1
    assert 0 < iters <= kw["maxiter"] and np.all(np.isfinite(np.asarray(x)))


@pytest.mark.parametrize("again", AGAIN, ids=AGAIN_IDS)
def test_a_later_solve_over_a_matrix_under_jacobi_traces_nothing(again):
    A, M, b = _csr()
    t0 = TRACES.value
    linalg.cg(A, b, maxiter=12, M=M)
    assert TRACES.value == t0 + 1
    A2, M2, b2, kw = again(A, M, b, lambda: _csr(scale=2.0))
    x, iters = linalg.cg(A2, b2, M=M2, **{"maxiter": 12, **kw})
    assert TRACES.value == t0 + 1
    assert np.all(np.isfinite(np.asarray(x)))


@pytest.mark.parametrize("other", [(40, 3, "linear"), (36, 2, "linear"),
                                   (36, 2, "injection")],
                         ids=["other-levels", "other-grid", "other-gridop"])
def test_another_structure_is_one_more_program(other):
    A, M, b = _grid(36, 2, "linear")
    linalg.cg(A, b, maxiter=5, M=M)
    if other == (36, 2, "linear"):  # the same structure: the control
        A2, M2, b2 = _grid(*other)
        t0 = TRACES.value
        linalg.cg(A2, b2, maxiter=5, M=M2)
        assert TRACES.value == t0
        return
    A2, M2, b2 = _grid(*other)
    t0 = TRACES.value
    linalg.cg(A2, b2, maxiter=5, M=M2)
    assert TRACES.value == t0 + 1
    linalg.cg(A2, b2, maxiter=9, M=M2)
    linalg.cg(A, b, maxiter=9, M=M)  # the first one's program is still there
    assert TRACES.value == t0 + 1


# -- the closure loop's answer ---------------------------------------------------
@pytest.mark.parametrize("n,levels,gridop", HIERARCHIES, ids=HIER_IDS)
def test_the_program_gives_the_closure_loops_bits(n, levels, gridop):
    """From a zero start both run the same ops in the same order: the
    program's first residual, ``b - A 0``, is ``b`` to the bit."""
    A, M, b = _grid(n, levels, gridop)
    x, iters = linalg.cg(A, b, maxiter=30, M=M)
    t0 = TRACES.value
    Ac, Mc = _as_closures(A, M)
    xc, ic = linalg.cg(Ac, b, maxiter=30, M=Mc)
    assert TRACES.value == t0  # the closure loop is not the program
    assert iters == ic
    assert np.array_equal(np.asarray(x), np.asarray(xc))


@pytest.mark.parametrize("n,levels,gridop", HIERARCHIES[1:5], ids=HIER_IDS[1:5])
def test_from_a_start_the_program_agrees_with_the_closure_loop(n, levels, gridop):
    """With ``x0`` the start's residual is computed inside the program,
    where the compiler may fuse it with the first cycle, and outside it by
    the closure path: the same arithmetic, rounded apart by a few ulps that
    the iterations carry along, hence 1e-6 of the answer and not its bits."""
    A, M, b = _grid(n, levels, gridop)
    x0 = 0.25 * b
    x, iters = linalg.cg(A, b, x0=x0, maxiter=20, M=M)
    Ac, Mc = _as_closures(A, M)
    xc, ic = linalg.cg(Ac, b, x0=x0, maxiter=20, M=Mc)
    assert iters == ic
    scale = float(jnp.linalg.norm(xc))
    assert float(jnp.linalg.norm(x - xc)) <= 1e-6 * scale


@pytest.mark.parametrize("with_x0", [False, True], ids=["zero-start", "x0"])
def test_a_matrix_under_declared_jacobi_gives_the_closure_loops_answer(with_x0):
    A, M, b = _csr()
    kw = {"x0": 0.1 * b} if with_x0 else {}
    x, iters = linalg.cg(A, b, maxiter=25, M=M, **kw)
    _, Mc = _as_closures(A, M)
    t0 = TRACES.value
    xc, ic = linalg.cg(A, b, maxiter=25, M=Mc, **kw)  # a closure M: old path
    assert TRACES.value == t0
    assert iters == ic
    if with_x0:
        assert float(jnp.linalg.norm(x - xc)) <= 1e-6 * float(jnp.linalg.norm(xc))
    else:
        assert np.array_equal(np.asarray(x), np.asarray(xc))
    S = sp.csr_matrix((np.asarray(A.data), np.asarray(A.indices),
                       np.asarray(A.indptr)), shape=A.shape)
    assert np.linalg.norm(S @ np.asarray(x) - np.asarray(b)) < 1e-3 * np.linalg.norm(b)


@pytest.mark.parametrize("side", ["M", "A", "both"])
def test_a_closure_on_either_side_solves_through_the_old_path(side, tel):
    A, M, b = _grid(33, 2, "linear")
    Ac, Mc = _as_closures(A, M)
    t0 = TRACES.value
    x, iters = linalg.cg(Ac if side in ("A", "both") else A, b, maxiter=40,
                         M=Mc if side in ("M", "both") else M)
    assert TRACES.value == t0
    assert "cg.solve" not in [e["name"] for e in telemetry.events("span")]
    assert telemetry.events("solver.solve")[-1]["path"] == "device"
    assert float(jnp.linalg.norm(A.matvec(x) - b)) < 1e-3 * float(jnp.linalg.norm(b))


def test_an_unpreconditioned_declared_operator_runs_the_program_too(tel):
    A, _, b = _grid(33, 2, "linear")
    t0 = TRACES.value
    x, iters = linalg.cg(A, b, maxiter=15)
    assert TRACES.value == t0 + 1
    (ev,) = [e for e in telemetry.events("span") if e["name"] == "cg.solve"]
    assert (ev["path"], ev["precond"], ev["iters"]) == ("device", "none", iters)


def test_a_callback_still_runs_the_host_loop():
    A, M, b = _grid(33, 2, "linear")
    seen = []
    t0 = TRACES.value
    linalg.cg(A, b, maxiter=5, M=M, callback=lambda xk: seen.append(1))
    assert TRACES.value == t0 and len(seen) == 5


# -- what an operator declares -----------------------------------------------------
def test_a_declared_operator_is_a_linear_operator_with_its_product():
    hier = gg.build_hierarchy(33, 3)
    A, M = gg.grid_operator(hier), gg.make_vcycle(hier)
    r = _rhs(33 * 33)
    assert A.shape == M.shape == (33 * 33, 33 * 33)
    assert A.dtype == M.dtype == np.float32
    assert M.describe == {"precond": "gmg_grid", "levels": 3,
                          "fine_stencil_kernels": 0}
    assert A.describe == {"fine_stencil_kernels": 0}
    want = gg.stencil_apply(hier[0][0], r.reshape(33, 33)).reshape(-1)
    assert np.array_equal(np.asarray(A.matvec(r)), np.asarray(want))
    assert np.array_equal(np.asarray(M(r)), np.asarray(M.matvec(r)))
    assert np.array_equal(np.asarray(A @ r), np.asarray(want))
    # equal by value: what lets jit find the program of another hierarchy
    other = gg.make_vcycle(gg.build_hierarchy(33, 3, omega=1.0))
    assert other.apply == M.apply and hash(other.apply) == hash(M.apply)
    assert gg.make_vcycle(gg.build_hierarchy(33, 2)).apply != M.apply
    # the operands are the hierarchy's own arrays, nothing copied
    assert M.operands[1][1] is hier[1][1]


def test_a_plain_closure_operator_declares_nothing():
    op = linalg.LinearOperator((4, 4), matvec=lambda v: 2 * v)
    assert op.apply is None and op.operands is None and op.describe == {}
    assert linalg._declared(op, np.float32) is None
    with pytest.raises(NotImplementedError):
        linalg.LinearOperator((4, 4)).matvec(np.ones(4))


@pytest.mark.parametrize("kind,declared", [("jacobi", True), ("bjacobi", False),
                                           ("cheby", False)])
def test_make_M_declares_point_jacobi_alone(kind, declared):
    A, _, b = _csr(n=120)
    M = precond.make_M(A, kind)
    assert (M.apply is not None) == declared
    if declared:
        assert M.describe == {"precond": "jacobi"}
        want = np.asarray(b) / np.asarray(
            sp.csr_matrix((np.asarray(A.data), np.asarray(A.indices),
                           np.asarray(A.indptr)), shape=A.shape).diagonal())
        assert np.allclose(np.asarray(M.matvec(b)), want, rtol=1e-6)
    x, _ = linalg.cg(A, b, maxiter=60, M=M)
    assert float(jnp.linalg.norm(A @ x - b)) < 1e-3 * float(jnp.linalg.norm(b))


# -- spans, scopes, the compiled program ---------------------------------------------
@pytest.mark.parametrize("n,levels,gridop", HIERARCHIES[3:5], ids=HIER_IDS[3:5])
def test_one_cg_solve_span_a_call_and_one_build_span_a_hierarchy(
        n, levels, gridop, tel):
    hier = gg.build_hierarchy(n, levels, gridop)
    (build,) = [e for e in telemetry.events("span")
                if e["name"] == "gmg.build_hierarchy"]
    sizes = [n // 2 ** k for k in range(levels)]
    assert build["levels"] == levels and build["sizes"] == sizes
    assert len(build["rho"]) == levels and all(0 < r < 4 for r in build["rho"])
    held = sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(hier) if hasattr(a, "dtype"))
    assert build["bytes"] == held > 10 * 4 * sizes[1] ** 2
    assert telemetry.schema.validate(build) == []
    A, M, b = gg.grid_operator(hier), gg.make_vcycle(hier, gridop), _rhs(n * n)
    for k in range(2):
        n0 = len(telemetry.events("span"))
        _x, iters = linalg.cg(A, b, maxiter=10 + k, M=M)
        (ev,) = telemetry.events("span")[n0:]  # one event a call
        assert ev["name"] == "cg.solve"
        assert (ev["path"], ev["precond"], ev["levels"], ev["iters"]) == (
            "device", "gmg_grid", levels, iters)
        assert ev["fine_stencil_kernels"] == 0  # the CPU: stencil_apply
        assert 0 < ev["dispatch_s"] and 0 <= ev["fetch_s"]
        assert ev["dispatch_s"] + ev["fetch_s"] <= ev["dur_s"]
    assert telemetry.events("solver.solve")[-1]["path"] == "device"


def test_off_the_program_records_nothing():
    telemetry.reset()
    A, M, b = _grid(33, 2, "linear")
    linalg.cg(A, b, maxiter=5, M=M)
    assert telemetry.events() == []


def test_the_compiled_program_names_each_level_and_is_jits_own():
    A, M, b = _grid(64, 3, "linear")
    assert linalg._pcg_compiled(_as_closures(A, M)[0], b, M) is None
    linalg.cg(A, b, maxiter=5, M=M)
    t0 = TRACES.value
    text = linalg._pcg_compiled(A, b, M).as_text()
    assert TRACES.value == t0  # found again, not traced again
    for lvl in range(3):
        assert f"/gmg.l{lvl}/" in text
    assert "/gmg.l3/" not in text
    # the scopes do not nest: an op stands under its own level alone
    assert "gmg.l0/gmg.l1" not in text and "gmg.l1/gmg.l2" not in text


# -- the fine level's kernel inside the program (PR 44) --------------------------------
@pytest.fixture
def kernel_here(monkeypatch):
    """The fine level's kernel is taken where the hierarchy's scalars sit on
    one TPU; here the platform is this process's, and it runs interpreted."""
    monkeypatch.setattr(gg, "_KERNEL_PLATFORM", jax.default_backend())


@pytest.mark.parametrize("again", AGAIN, ids=AGAIN_IDS)
def test_a_later_solve_through_the_kernel_traces_nothing(again, kernel_here):
    A, M, b = _grid(128, 2, "linear")
    assert A.apply.fine_kernel and M.apply.fine_kernel
    t0 = TRACES.value
    linalg.cg(A, b, maxiter=6, M=M)
    assert TRACES.value == t0 + 1
    A2, M2, b2, kw = again(A, M, b, lambda: _grid(128, 2, "linear", omega=1.1))
    kw = {"maxiter": 6, **kw}
    x, iters = linalg.cg(A2, b2, M=M2, **kw)
    assert TRACES.value == t0 + 1
    assert 0 < iters <= kw["maxiter"] and np.all(np.isfinite(np.asarray(x)))


def test_the_kernel_is_part_of_the_programs_identity(kernel_here, monkeypatch):
    A, M, b = _grid(128, 2, "linear")
    linalg.cg(A, b, maxiter=3, M=M)
    monkeypatch.setattr(gg, "_KERNEL_PLATFORM", "tpu")  # no kernel here
    A0, M0, _ = _grid(128, 2, "linear")
    assert (A0.apply, M0.apply) != (A.apply, M.apply)
    assert A0.apply == gg._GridApply(128, A.apply.offsets)
    t0 = TRACES.value
    linalg.cg(A0, b, maxiter=3, M=M0)
    assert TRACES.value == t0 + 1


@pytest.mark.parametrize("n,levels", [(128, 2), (256, 3)])
def test_the_kernel_program_gives_the_closure_loops_answer(n, levels, kernel_here,
                                                           monkeypatch, tel):
    A, M, b = _grid(n, levels, "linear")
    x, iters = linalg.cg(A, b, maxiter=20, M=M)
    (ev,) = [e for e in telemetry.events("span") if e["name"] == "cg.solve"]
    assert (ev["path"], ev["precond"], ev["levels"]) == ("device", "gmg_grid", levels)
    assert ev["fine_stencil_kernels"] == 3  # A p, the residual, the post-smoothing
    # the closure loop over the same two products: the kernel's, op for op
    t0 = TRACES.value
    Ac, Mc = _as_closures(A, M)
    xc, ic = linalg.cg(Ac, b, maxiter=20, M=Mc)
    assert TRACES.value == t0 and iters == ic
    assert float(jnp.linalg.norm(x - xc)) <= 1e-6 * float(jnp.linalg.norm(xc))
    # and the program without the kernel: stencil_apply's sum, rounded apart
    monkeypatch.setattr(gg, "_KERNEL_PLATFORM", "tpu")
    A0, M0, _ = _grid(n, levels, "linear")
    x0, i0 = linalg.cg(A0, b, maxiter=20, M=M0)
    assert i0 == iters
    assert float(jnp.linalg.norm(x - x0)) <= 1e-4 * float(jnp.linalg.norm(x0))
    assert telemetry.events("span")[-1]["fine_stencil_kernels"] == 0


def test_the_kernels_stand_under_level_0s_scope_and_the_product_under_none(
        kernel_here):
    """What ``pcg_vcycle_pct`` and ``pcg_coarse_pct`` read: the cycle's two
    kernels carry ``gmg.l0`` in their ops' names, ``A p``'s carries no
    level's (here the kernels are interpreted, plain ops under the kernel's
    name; ``tests/test_chip_compile.py`` reads the custom calls)."""
    A, M, b = _grid(128, 3, "linear")
    text = linalg._pcg_compiled(A, b, M).as_text()
    names = set(re.findall(r'op_name="([^"]*grid_stencil5_\w+)', text))
    assert names and not [n for n in names if "gmg.l1" in n or "gmg.l2" in n]
    for form, scoped in (("residual", True), ("smooth", True), ("apply", False)):
        mine = [n for n in names if n.endswith("grid_stencil5_" + form)]
        assert mine and all(("/gmg.l0/" in n) == scoped for n in mine), (form, mine)
