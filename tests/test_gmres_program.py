"""``linalg.gmres`` over operators that declare what they hold: one compiled
whole-solve program (``jit_gmres``) with A's and M's arrays as arguments
(PR 42).

The clients: the 7-point nonsymmetric box of the benchmark's
``cfd_7pt`` generator (the pattern of SuiteSparse's atmosmodd at small
boxes) and a general ``csr_array`` under ``precond.make_M``'s declared
point-Jacobi. A second solve of the same structure, whatever the values,
traces nothing (``gmres.traces``) and makes one host fetch; the answer is
the cycle path's (``_make_gmres_cycle`` a call, one fetch a cycle), which a
closure on either side, a ``callback`` or an outer trace still runs.
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
from sparse_tpu.telemetry import _metrics
from .utils.spd import operator_module

TRACES = _metrics.counter("gmres.traces")
GEN = operator_module("cfd_7pt")

BOXES = [(6, 5, 4), (9, 8, 3), (12, 7, 5)]
CASES = [(box, restart) for box in BOXES for restart in (10, 30)]
CASE_IDS = [f"{'x'.join(map(str, b))}-m{m}" for b, m in CASES]


@pytest.fixture(autouse=True)
def _fresh_program():
    """These tests count traces of ``jit_gmres``; an earlier test of this
    process that solved the same structure would leave them none."""
    linalg._gmres_program.clear_cache()


@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield
    telemetry.configure(None)
    telemetry.reset()


def _box(box, seed=3, dtype=np.float32):
    """(A, b) on the box: the generator's CSR arrays; complex: the same
    pattern with an imaginary part on the diagonal."""
    d = GEN.make({"box": list(box), "restart": 30, "cycles": 1}, seed)
    n = d["rows"]
    data, b = d["data"].astype(dtype), d["b"].astype(dtype)
    if np.issubdtype(dtype, np.complexfloating):
        S = sp.csr_matrix((data, d["indices"], d["indptr"]), shape=(n, n))
        shift = np.random.default_rng(seed).uniform(-1, 1, n)
        S = (S + 0.3j * sp.diags(shift)).tocsr().astype(dtype)
        S.sort_indices()
        data, b = S.data, b * (1 + 0.5j)
    A = sparse_tpu.csr_array((data, d["indices"], d["indptr"]), shape=(n, n))
    return A, jnp.asarray(b)


def _general(n=300, scale=1.0, seed=5):
    """A nonsymmetric, diagonally dominant matrix that is not banded, its
    declared point-Jacobi, and b."""
    rng = np.random.default_rng(seed)
    S = sp.random(n, n, density=0.03, random_state=rng, dtype=np.float32)
    S = (S + sp.diags(np.asarray(abs(S).sum(axis=1)).ravel() + 1.0
                      + rng.random(n))) * scale
    A = sparse_tpu.csr_array(S.tocsr().astype(np.float32))
    b = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
    return A, precond.make_M(A, "jacobi"), b


def _as_closure(op):
    op = linalg.make_linear_operator(op)
    return linalg.LinearOperator(op.shape, matvec=op.matvec, dtype=op.dtype)


def _cycle_path(A, b, M=None, **kw):
    """The same solve through the old path: A rewrapped as a closure."""
    t0 = TRACES.value
    out = linalg.gmres(_as_closure(A), b, M=M, **kw)
    assert TRACES.value == t0  # the cycle path is not the program
    return out


# -- one program a structure ---------------------------------------------------
def _again_same(A, b, mk):
    return A, b, {}


def _again_other_b(A, b, mk):
    return A, 2.0 * b[::-1], {}


def _again_x0(A, b, mk):
    return A, b, {"x0": 0.5 * b}


def _again_other_tol_atol_maxiter(A, b, mk):
    return A, b, {"tol": 1e-3, "atol": 1e-4, "maxiter": 2}


def _again_other_values(A, b, mk):
    return mk()[0], b, {}  # another matrix object, other values


AGAIN = [_again_same, _again_other_b, _again_x0,
         _again_other_tol_atol_maxiter, _again_other_values]
AGAIN_IDS = [f.__name__[len("_again_"):] for f in AGAIN]


@pytest.mark.parametrize("again", AGAIN, ids=AGAIN_IDS)
@pytest.mark.parametrize("box,restart", CASES, ids=CASE_IDS)
def test_a_later_solve_on_the_box_traces_nothing(box, restart, again):
    A, b = _box(box)
    t0 = TRACES.value
    linalg.gmres(A, b, restart=restart, maxiter=3, tol=1e-30)
    assert TRACES.value == t0 + 1
    A2, b2, kw = again(A, b, lambda: _box(box, seed=11))
    x, iters = linalg.gmres(A2, b2, restart=restart, **{
        "maxiter": 3, "tol": 1e-30, **kw})
    assert TRACES.value == t0 + 1
    assert iters > 0 and np.all(np.isfinite(np.asarray(x)))


@pytest.mark.parametrize("again", AGAIN, ids=AGAIN_IDS)
def test_a_later_solve_of_a_general_matrix_under_jacobi_traces_nothing(again):
    A, M, b = _general()
    t0 = TRACES.value
    linalg.gmres(A, b, restart=10, maxiter=3, M=M)
    assert TRACES.value == t0 + 1
    if again is _again_other_values:
        A2, M2, _ = _general(scale=2.0)
        b2, kw = b, {}
    else:
        (A2, b2, kw), M2 = again(A, b, None), M
    x, _ = linalg.gmres(A2, b2, restart=10, M=M2, **{"maxiter": 3, **kw})
    assert TRACES.value == t0 + 1
    assert np.all(np.isfinite(np.asarray(x)))


@pytest.mark.parametrize("other", ["restart", "shape", "dtype", "same"])
def test_another_structure_is_one_more_program(other):
    A, b = _box((6, 5, 4))
    linalg.gmres(A, b, restart=10, maxiter=2, tol=1e-30)
    t0 = TRACES.value
    if other == "restart":
        linalg.gmres(A, b, restart=12, maxiter=2, tol=1e-30)
    elif other == "shape":
        A2, b2 = _box((7, 5, 4))
        linalg.gmres(A2, b2, restart=10, maxiter=2, tol=1e-30)
    elif other == "dtype":
        A2, b2 = _box((6, 5, 4), dtype=np.complex64)
        linalg.gmres(A2, b2, restart=10, maxiter=2, tol=1e-30)
    else:  # the control: the same structure again
        A2, b2 = _box((6, 5, 4), seed=8)
        linalg.gmres(A2, b2, restart=10, maxiter=2, tol=1e-30)
    assert TRACES.value == t0 + (other != "same")
    linalg.gmres(A, b, restart=10, maxiter=4)  # the first one's is still there
    assert TRACES.value == t0 + (other != "same")


# -- the cycle path's answer -----------------------------------------------------
@pytest.mark.parametrize("dtype", [np.float32, np.complex64],
                         ids=["float32", "complex64"])
@pytest.mark.parametrize("box,restart", CASES, ids=CASE_IDS)
def test_the_program_gives_the_cycle_paths_answer(box, restart, dtype):
    """Both run the one Arnoldi cycle (``_gmres_cycle``), the program inside
    an outer ``while_loop`` and the cycle path as a program of its own with
    the matrix as constants. On the CPU the two compile to the same
    arithmetic in the same order and the answers agree to the last bit; the
    test allows 1e-6 of the answer, because nothing makes a compiler fuse
    the cycle's residual the same way in both programs (``tests/
    test_pcg_program.py`` found a few ulps there), and holds ``iters``
    exactly."""
    A, b = _box(box, dtype=dtype)
    x, iters = linalg.gmres(A, b, restart=restart, maxiter=3, tol=1e-30)
    xc, ic = _cycle_path(A, b, restart=restart, maxiter=3, tol=1e-30)
    assert iters == ic == 3 * min(restart, b.shape[0])
    assert x.dtype == xc.dtype == dtype
    assert float(jnp.linalg.norm(x - xc)) <= 1e-6 * float(jnp.linalg.norm(xc))


@pytest.mark.parametrize("with_x0", [False, True], ids=["zero-start", "x0"])
def test_a_matrix_under_declared_jacobi_gives_the_cycle_paths_answer(with_x0):
    A, M, b = _general()
    kw = {"x0": 0.1 * b} if with_x0 else {}
    x, iters = linalg.gmres(A, b, restart=10, maxiter=4, M=M, tol=1e-30, **kw)
    t0 = TRACES.value
    xc, ic = linalg.gmres(A, b, restart=10, maxiter=4, M=_as_closure(M),
                          tol=1e-30, **kw)  # a closure M: the cycle path
    assert TRACES.value == t0
    assert iters == ic == 40
    assert float(jnp.linalg.norm(x - xc)) <= 1e-6 * float(jnp.linalg.norm(xc))
    assert float(jnp.linalg.norm(A @ x - b)) < 1e-4 * float(jnp.linalg.norm(b))


# -- how a solve ends ---------------------------------------------------------------
def _ends(A, b, **kw):
    """(x, iters, host syncs) of the program and of the cycle path."""
    out = []
    for solve in (linalg.gmres, _cycle_path):
        linalg.HOST_SYNCS = 0
        x, iters = solve(A, b, **kw)
        out.append((np.asarray(x), iters, linalg.HOST_SYNCS))
    return out


def test_converged_on_entry_ends_as_the_cycle_path_ends():
    A, b = _box((6, 5, 4))
    x_exact, _ = linalg.gmres(A, b, restart=30, maxiter=20, tol=1e-7)
    (x, iters, syncs), (xc, ic, _) = _ends(A, b, x0=x_exact, tol=1e-3,
                                           restart=10, maxiter=5)
    assert iters == ic == 0 and syncs == 1
    assert np.array_equal(x, np.asarray(x_exact)) and np.array_equal(x, xc)


def test_convergence_inside_a_cycle_ends_as_the_cycle_path_ends():
    A, b = _box((6, 5, 4))
    (x, iters, syncs), (xc, ic, sc) = _ends(A, b, restart=10, maxiter=50,
                                            tol=1e-4)
    assert iters == ic and 0 < iters < 500
    assert syncs == 1 and sc == -(-ic // 10) + 1  # one a cycle, one on entry
    assert np.linalg.norm(x - xc) <= 1e-6 * np.linalg.norm(xc)
    r = np.asarray(b) - np.asarray(A @ jnp.asarray(x))
    assert np.linalg.norm(r) <= 1.5e-4 * np.linalg.norm(np.asarray(b))


def test_maxiter_exhausted_ends_as_the_cycle_path_ends():
    A, b = _box((9, 8, 3))
    (x, iters, syncs), (xc, ic, sc) = _ends(A, b, restart=10, maxiter=4,
                                            tol=1e-30)
    assert iters == ic == 40 and (syncs, sc) == (1, 4)
    assert np.linalg.norm(x - xc) <= 1e-6 * np.linalg.norm(xc)


@pytest.mark.parametrize("maxiter", [1, 3])
def test_a_breakdown_ends_as_the_cycle_path_ends(maxiter):
    """A matrix whose Krylov space closes early: twice the identity. The
    first step's w is a multiple of v0, nothing is left of it, and the
    rotation's denominator is not zero (h00 = 2), so the step is a happy
    breakdown that the recurrence sees as convergence; a nilpotent shift
    from a start in its kernel's image breaks down with nothing to rotate."""
    n = 12
    Id = sparse_tpu.csr_array(sp.identity(n, dtype=np.float32, format="csr") * 2)
    b = jnp.asarray(np.arange(1.0, n + 1), jnp.float32)
    (x, iters, _), (xc, ic, _) = _ends(Id, b, restart=5, maxiter=maxiter,
                                       tol=1e-5)
    assert iters == ic == 1
    assert np.allclose(x, np.asarray(b) / 2) and np.array_equal(x, xc)
    # A e1 = 0 for the shift below: the first Hessenberg column is all zero,
    # the rotation has nothing to work on (denom 0): a true breakdown, which
    # both paths count as one step a cycle until the cycles are spent
    N = sparse_tpu.csr_array(sp.diags([np.ones(n - 1, np.float32)], [1],
                                      format="csr"))
    e1 = jnp.zeros(n, jnp.float32).at[0].set(1.0)
    (x, iters, _), (xc, ic, _) = _ends(N, e1, restart=5, maxiter=maxiter)
    assert iters == ic == maxiter
    assert np.array_equal(x, xc) and np.all(np.isfinite(x))


@pytest.mark.parametrize("box,restart", CASES[:3], ids=CASE_IDS[:3])
def test_one_host_sync_a_call(box, restart):
    A, b = _box(box)
    for k in range(3):
        linalg.HOST_SYNCS = 0
        linalg.gmres(A, (k + 1.0) * b, restart=restart, maxiter=2 + k, tol=1e-30)
        assert linalg.HOST_SYNCS == 1


# -- what keeps the old path ----------------------------------------------------------
@pytest.mark.parametrize("side", ["A", "M", "both", "callback"])
def test_the_old_path_still_solves(side, tel):
    """(A call under an outer trace: ``tests/test_compiled_solve.py``.)"""
    A, M, b = _general()
    seen = []
    kw = {"restart": 10, "maxiter": 30, "tol": 1e-6}
    t0 = TRACES.value
    if side == "callback":
        x, iters = linalg.gmres(A, b, M=M, callback=seen.append, **kw)
    else:
        x, iters = linalg.gmres(
            _as_closure(A) if side in ("A", "both") else A, b,
            M=_as_closure(M) if side in ("M", "both") else M, **kw)
    assert TRACES.value == t0
    (ev,) = [e for e in telemetry.events("span") if e["name"] == "gmres.solve"]
    assert ev["path"] == "cycle" and ev["iters"] == iters
    assert ev["fetches"] == ev["cycles"] + 1  # one a cycle, one on entry
    assert ev["precond"] == ("jacobi" if side in ("A", "callback") else "closure")
    assert len(seen) == (ev["cycles"] if side == "callback" else 0)
    assert float(jnp.linalg.norm(A @ x - b)) < 1e-4 * float(jnp.linalg.norm(b))


def test_an_operator_wrapped_for_fault_injection_keeps_the_old_path():
    from sparse_tpu.resilience import faults

    A, b = _box((6, 5, 4))
    t0 = TRACES.value
    faults.configure("nonfinite:matvec:p=0")
    try:
        x, _ = linalg.gmres(A, b, restart=10, maxiter=3)
    finally:
        faults.clear()
    assert TRACES.value == t0 and np.all(np.isfinite(np.asarray(x)))


# -- spans, events, the compiled program ------------------------------------------------
@pytest.mark.parametrize("box,restart", CASES[2:5], ids=CASE_IDS[2:5])
def test_one_gmres_solve_span_a_call_and_the_cycles_events(box, restart, tel):
    A, b = _box(box)
    for k in range(2):
        n0, i0 = len(telemetry.events("span")), len(telemetry.events("solver.iter"))
        _x, iters = linalg.gmres(A, b, restart=restart, maxiter=2 + k, tol=1e-30)
        (ev,) = [e for e in telemetry.events("span")[n0:]  # one a call; the
                 if e["name"] == "gmres.solve"]  # first builds the layout too
        assert (ev["path"], ev["restart"], ev["cycles"], ev["iters"],
                ev["fetches"], ev["precond"]) == (
            "device", restart, 2 + k, iters, 1, "none")
        assert 0 < ev["dispatch_s"] and 0 <= ev["fetch_s"]
        assert ev["dispatch_s"] + ev["fetch_s"] <= ev["dur_s"]
        assert telemetry.schema.validate(ev) == []
        # a `solver.iter` event a cycle, as the cycle path records them
        cyc = telemetry.events("solver.iter")[i0:]
        assert [(e["solver"], e["path"], e["iter"], e["inner"]) for e in cyc] == [
            ("gmres", "device", restart * (j + 1), restart) for j in range(2 + k)]
        assert all(e["resid"] > 0 for e in cyc)
        assert cyc[0]["resid"] > cyc[-1]["resid"]
    assert telemetry.events("solver.solve")[-1]["path"] == "device"
    # the cycle path's events of the same solve: the same but for rounding
    i0 = len(telemetry.events("solver.iter"))
    _cycle_path(A, b, restart=restart, maxiter=3, tol=1e-30)
    old = telemetry.events("solver.iter")[i0:]
    assert [(e["iter"], e["inner"]) for e in old] == [
        (e["iter"], e["inner"]) for e in cyc]
    assert np.allclose([e["resid"] for e in old], [e["resid"] for e in cyc],
                       rtol=1e-4)


def test_the_span_names_a_declared_preconditioner(tel):
    A, M, b = _general()
    linalg.gmres(A, b, restart=10, maxiter=2, M=M)
    (ev,) = [e for e in telemetry.events("span") if e["name"] == "gmres.solve"]
    assert (ev["path"], ev["precond"], ev["fetches"]) == ("device", "jacobi", 1)


def test_off_the_program_records_nothing_and_carries_no_tap():
    telemetry.reset()
    A, b = _box((6, 5, 4))
    linalg.gmres(A, b, restart=10, maxiter=2)
    assert telemetry.events() == []
    text = linalg._gmres_compiled(A, b, 10).as_text()
    assert "callback" not in text


def test_the_compiled_program_names_its_scopes_and_is_jits_own():
    A, b = _box((12, 7, 5))
    assert linalg._gmres_compiled(_as_closure(A), b, 30) is None
    linalg.gmres(A, b, restart=30, maxiter=2)
    t0 = TRACES.value
    text = linalg._gmres_compiled(A, b, 30).as_text()
    assert TRACES.value == t0  # found again, not traced again
    assert "jit_gmres" in text
    for scope in ("gmres.spmv", "gmres.orth", "gmres.small", "gmres.update"):
        assert f"/{scope}/" in text
    # the scopes do not nest: an op stands under its own scope alone
    assert not re.search(r"gmres\.\w+/[^\"]*gmres\.\w+/", text)


@pytest.mark.parametrize("m", [12, 20], ids=["three-stage-edges", "five-stage-edges"])
def test_the_arnoldi_basis_is_orthonormal(m):
    """The question the chip run of PR 42 answers at atmosmodd's size, here
    on the CPU: after a cycle ``V V^H`` is the identity to float32's
    rounding, and the residual the recurrence believes is the true one.
    ``m`` is far from converged, so that the residual is no rounding, and
    its steps cross the edges of the orthogonalisation's stages (every
    fourth row of the basis)."""
    A, b = _box((12, 7, 5))
    mv = linalg.make_linear_operator(A).matvec
    beta = jnp.linalg.norm(b)
    V, H, g, k, bd = linalg._gmres_arnoldi(mv, lambda v: v, b, beta,
                                           jnp.float32(1e-30), m)
    assert int(k) == m and not bool(bd)
    V64 = np.asarray(linalg._basis_flat(V, b.shape[0]), np.float64)
    assert np.abs(V64 @ V64.T - np.eye(m + 1)).max() < 5e-6
    y = np.linalg.solve(np.triu(np.asarray(H, np.float64)[:m, :m]),
                        np.asarray(g, np.float64)[:m])
    x64 = jnp.asarray(y @ V64[:m])  # x64 is on: conftest.py
    r = np.asarray(b, np.float64) - np.asarray(A @ x64, np.float64)
    assert abs(float(g[m])) == pytest.approx(np.linalg.norm(r), rel=1e-3)


# -- the orthogonalisation's stages (PR 43; blocks of 4 rows since PR 47) --------------------
@pytest.mark.parametrize("restart,block,his", [
    (1, 4, (2,)), (2, 4, (3,)), (3, 4, (4,)),  # one stage: the whole basis
    (4, 4, (4, 5)), (5, 4, (4, 6)), (7, 4, (4, 8)), (8, 4, (4, 8, 9)),
    (12, 4, (4, 8, 12, 13)), (30, 4, (*range(4, 29, 4), 31)),
    (31, 4, tuple(range(4, 33, 4))), (33, 8, (8, 16, 24, 32, 34)),
    (63, 8, tuple(range(8, 65, 8))), (64, 12, (12, 24, 36, 48, 60, 65)),
    (70, 12, (12, 24, 36, 48, 60, 71)),
    (200, 28, (*range(28, 197, 28), 201)),
])
def test_the_stages_follow_from_restart_alone(restart, block, his):
    assert linalg._orth_stages(restart) == (block, his)
    assert len(his) <= 8 and his[-1] == restart + 1
    for k in range(restart):  # the least stage that holds rows 0..k
        stage = k // block
        assert (his[stage - 1] if stage else 0) < k + 1 <= his[stage]
        assert his[stage] % 4 == 0 or his[stage] == restart + 1


def _masked_arnoldi(mv, r, beta, target, m):
    """The Arnoldi process as the tree had it before the stages, step by
    step on the host: the four contractions of a step run over ALL ``m + 1``
    rows of the basis under the mask of the rows the step holds; the Givens
    recurrences in numpy. ``(V, H, g, k, breakdown, |g[1:]|)``."""
    dt = np.dtype(r.dtype)
    n = r.shape[0]
    V = jnp.zeros((m + 1, n), dt).at[0].set(r / beta)
    H = np.zeros((m + 1, m), dt)
    cs, sn = np.zeros(m, dt), np.zeros(m, dt)
    g = np.zeros(m + 1, dt)
    g[0] = float(beta)
    k, history = 0, []
    while k < m:
        w = mv(V[k])
        mask = (jnp.arange(m + 1) <= k).astype(beta.dtype)
        hcol = (V.conj() @ w) * mask
        w = w - hcol @ V
        h2 = (V.conj() @ w) * mask
        w = w - h2 @ V
        hkk = jnp.sqrt(jnp.sum(jnp.real(w * jnp.conj(w))))
        if float(hkk) > 1e-30:
            V = V.at[k + 1].set(w / hkk)
        col = np.asarray(hcol + h2).copy()
        col[k + 1] = float(hkk)
        for i in range(k):
            col[i], col[i + 1] = (cs[i] * col[i] + sn[i] * col[i + 1],
                                  -np.conj(sn[i]) * col[i] + cs[i] * col[i + 1])
        a0, a1 = abs(col[k]), abs(col[k + 1])
        denom = np.hypot(a0, a1)
        if denom == 0:
            return V, H, g, k, True, history
        cs[k] = a0 / denom
        sn[k] = ((col[k] / a0 if a0 else 1.0) * np.conj(col[k + 1])
                 / (denom if a0 else a1))
        col[k], col[k + 1] = cs[k] * col[k] + sn[k] * col[k + 1], 0.0
        H[:, k] = col
        g[k + 1] = -np.conj(sn[k]) * g[k]
        g[k] = cs[k] * g[k]
        k += 1
        history.append(abs(g[k]))
        if abs(g[k]) < float(target):
            break
    return V, H, g, k, False, history


# restart: the step (no multiple of the block) at which the run that converges
# and the run that breaks down end, inside a stage
STAGED = {3: 2, 5: 3, 8: 6, 12: 9, 30: 19, 33: 27, 70: 37}


@pytest.mark.parametrize("ends", ["whole", "converges", "breaks-down"])
@pytest.mark.parametrize("dtype", [np.float32, np.complex64],
                         ids=["float32", "complex64"])
@pytest.mark.parametrize("restart", sorted(STAGED))
def test_the_staged_pass_is_the_whole_masked_pass(restart, dtype, ends):
    """One stage (3); two (5); a stage's edge (8); several (12, 30); past 32
    rows, the block widened to 8 (33) and to 12 (70). Rows past the step's are zero, so the
    stage's contractions drop terms that are zero and nothing else: the
    process agrees with the masked full-basis one to float32's rounding."""
    stop = STAGED[restart]
    if ends == "breaks-down":
        # a nilpotent shift from e_stop: the Krylov space closes after
        # `stop` steps with nothing left to rotate
        n = 96
        phase = (0.6 + 0.8j) if np.issubdtype(dtype, np.complexfloating) else 1.0
        A = sparse_tpu.csr_array(sp.diags(
            [np.full(n - 1, phase, dtype)], [1], format="csr"))
        b = jnp.zeros(n, dtype).at[stop].set(2.0)
    else:
        A, b = _box((12, 7, 5), dtype=dtype)
    mv = linalg.make_linear_operator(A).matvec
    beta = jnp.linalg.norm(b)
    target = jnp.asarray(1e-30, beta.dtype)
    if ends == "converges":
        history = _masked_arnoldi(mv, b, beta, target, restart)[5]
        target = jnp.asarray(np.sqrt(history[stop - 1] * history[stop - 2]),
                             beta.dtype)  # between two steps' residuals
    Vr, Hr, gr, kr, bdr, _ = _masked_arnoldi(mv, b, beta, target, restart)
    V, H, g, k, bd = linalg._gmres_arnoldi(mv, lambda v: v, b, beta, target,
                                           restart)
    assert V.dtype == dtype and V.shape[0] == restart + 1
    V = linalg._basis_flat(V, b.shape[0])
    assert (int(k), bool(bd)) == (kr, bdr)
    assert (kr, bdr) == {"whole": (restart, False), "converges": (stop, False),
                         "breaks-down": (stop, True)}[ends]
    k = kr
    # rows past the last one written are zero in both
    assert not np.asarray(V[k + 1 + (not bdr):]).any()
    assert np.abs(np.asarray(V) - np.asarray(Vr)).max() <= 5e-5
    assert np.abs(np.asarray(H)[:, :k] - Hr[:, :k]).max() <= 5e-5 * max(
        1.0, np.abs(Hr).max())
    assert np.abs(np.asarray(g)[:k + 1] - gr[:k + 1]).max() <= 5e-5 * float(beta)


# -- the basis a row to a tile (PR 47) -------------------------------------------------------
def _random_band(n, dtype, seed=7):
    """A nonsymmetric, diagonally dominant banded matrix of any size ``n``
    (the boxes' sizes are products; the layout's edges are 1024's), and b."""
    rng = np.random.default_rng(seed)
    offs = [-17, -3, -1, 0, 1, 5, 29]
    diags = [rng.uniform(-1, 1, n - abs(o)) for o in offs]
    diags[3] = 8.0 + rng.random(n)
    S = sp.diags(diags, offs, format="csr").astype(dtype)
    b = rng.uniform(0.5, 1.5, n).astype(dtype)
    if np.issubdtype(dtype, np.complexfloating):
        S = (S + 0.3j * sp.diags(rng.uniform(-1, 1, n))).tocsr().astype(dtype)
        b = b * (1 + 0.5j)
    return sparse_tpu.csr_array(S), jnp.asarray(b)


@pytest.mark.parametrize("dtype", [np.float32, np.complex64],
                         ids=["float32", "complex64"])
@pytest.mark.parametrize("n", [420, 1024, 1025, 2048 + 7])
def test_the_basis_is_a_row_to_a_tile_and_its_pad_stays_zero(n, dtype):
    """``[m + 1, R, 128]`` with ``R = 8 ceil(n / 1024)``: under, on and one
    past a whole number of ``(8, 128)`` tiles, and two tiles and a bit. After
    a cycle the pad of every row is exactly zero (nothing of the loop writes
    it), ``V V^H`` is the identity to float32's rounding, and basis,
    Hessenberg and right-hand side are the masked full-basis process's on
    ``[m + 1, n]``; the iterate of a solve is the cycle path's."""
    m = 12
    A, b = _random_band(n, dtype)
    mv = linalg.make_linear_operator(A).matvec
    beta = jnp.linalg.norm(b)
    target = jnp.asarray(1e-30, beta.dtype)
    V, H, g, k, bd = linalg._gmres_arnoldi(mv, lambda v: v, b, beta, target, m)
    rows = 8 * -(-n // 1024)
    assert V.shape == (m + 1, rows, 128) and V.dtype == dtype
    assert (int(k), bool(bd)) == (m, False)
    whole = np.asarray(V).reshape(m + 1, rows * 128)
    assert whole[:, n:].size == (m + 1) * (rows * 128 - n)
    assert not whole[:, n:].any()  # exactly zero
    flat = np.asarray(linalg._basis_flat(V, n))
    assert flat.shape == (m + 1, n) and np.array_equal(flat, whole[:, :n])
    assert np.array_equal(np.asarray(linalg._basis_flat(V[3], n)), flat[3])
    V64 = flat.astype(np.complex128)
    assert np.abs(V64 @ V64.conj().T - np.eye(m + 1)).max() < 5e-6
    Vr, Hr, gr, kr, bdr, _ = _masked_arnoldi(mv, b, beta, target, m)
    assert (kr, bdr) == (m, False)
    assert np.abs(flat - np.asarray(Vr)).max() <= 5e-5
    assert np.abs(np.asarray(H) - Hr).max() <= 5e-5 * max(1.0, np.abs(Hr).max())
    assert np.abs(np.asarray(g) - gr).max() <= 5e-5 * float(beta)
    # the iterate: x += V y over the padded rows, cut to n
    x, iters = linalg.gmres(A, b, restart=m, maxiter=2, tol=1e-30)
    xc, ic = _cycle_path(A, b, restart=m, maxiter=2, tol=1e-30)
    assert x.shape == (n,) and iters == ic == 2 * m
    assert float(jnp.linalg.norm(x - xc)) <= 1e-6 * float(jnp.linalg.norm(xc))
    y = np.linalg.solve(np.triu(np.asarray(H, np.complex128)[:m, :m]),
                        np.asarray(g, np.complex128)[:m])
    x1, _ = linalg.gmres(A, b, restart=m, maxiter=1, tol=1e-30)
    assert np.abs(np.asarray(x1) - y @ V64[:m]).max() <= 5e-5 * np.abs(y).max()


def test_the_basis_helpers_are_each_others_inverse():
    v = jnp.arange(1.0, 1031.0, dtype=jnp.float32)
    t = linalg._basis_tiles(v)
    assert t.shape == (16, 128) and not np.asarray(t).reshape(-1)[1030:].any()
    assert np.array_equal(np.asarray(linalg._basis_flat(t, 1030)), np.asarray(v))
    Vs = jnp.stack([t, 2 * t])
    h = jnp.asarray([0.5, -1.0], jnp.float32)
    assert np.allclose(np.asarray(linalg._basis_project(Vs, t)),
                       np.asarray(Vs).reshape(2, -1) @ np.asarray(t).reshape(-1))
    assert np.allclose(np.asarray(linalg._basis_combine(h, Vs)), -1.5 * np.asarray(t))


@pytest.mark.parametrize("path", ["program", "cycle-path"])
@pytest.mark.parametrize("restart,kw", [
    (30, {"maxiter": 2, "tol": 1e-30}),  # whole cycles: 570 / 30
    (10, {"maxiter": 50, "tol": 1e-4}),  # ends inside a cycle
    (3, {"maxiter": 3, "tol": 1e-30}),  # one stage: the whole basis
], ids=["m30-whole", "m10-ends-early", "m3-one-stage"])
def test_the_solve_span_counts_the_basis_rows_read(restart, kw, path, tel):
    A, b = _box((6, 5, 4))
    solve = linalg.gmres if path == "program" else _cycle_path
    _x, iters = solve(A, b, restart=restart, **kw)
    (ev,) = [e for e in telemetry.events("span") if e["name"] == "gmres.solve"]
    assert ev["path"] == ("device" if path == "program" else "cycle")
    rows = [min(4 * (k % restart // 4 + 1), restart + 1) for k in range(iters)]
    assert ev["orth_rows"] == pytest.approx(sum(rows) / iters, abs=1e-3)
    assert ev["basis_write_rows"] == 1  # a row to a tile (PR 47)
    if restart == 30:
        assert iters == 60 and ev["orth_rows"] == 17.0
    elif restart == 10:
        assert iters % restart and 6.0 < ev["orth_rows"] < 7.3
    else:
        assert ev["orth_rows"] == 4.0
    assert telemetry.schema.validate(ev) == []


@pytest.mark.parametrize("restart,staged", [(2, False), (3, False), (4, True),
                                            (30, True)])
def test_a_restart_of_one_stage_has_no_conditional(restart, staged):
    """``restart + 1`` rows within one block: the whole masked pass; past it
    one ``conditional`` chooses the stage."""
    A, b = _box((6, 5, 4))
    text = linalg._gmres_compiled(A, b, restart).as_text()
    assert len(re.findall(r" conditional\(", text)) == int(staged)


# -- the accumulated rotations as one matrix (PR 52) -----------------------------------------
def _rotations_reference(cols, beta):
    """The Givens QR of a Hessenberg given column by column (``cols[k]`` its
    ``k + 2`` entries), the rotations applied one after another in float64
    or complex128 numpy: nothing of the code under test. After each column
    ``(H's column, g, breakdown)``; a breakdown leaves ``g`` as it was and is
    the last step."""
    m = len(cols)
    wide = np.result_type(cols[0].dtype, np.float64)
    cs, sn = np.zeros(m), np.zeros(m, wide)
    g = np.zeros(m + 1, wide)
    g[0] = beta
    steps = []
    for k, entries in enumerate(cols):
        col = np.zeros(m + 1, wide)
        col[:k + 2] = entries
        for i in range(k):
            col[i], col[i + 1] = (cs[i] * col[i] + sn[i] * col[i + 1],
                                  -np.conj(sn[i]) * col[i] + cs[i] * col[i + 1])
        a0, a1 = abs(col[k]), abs(col[k + 1])
        denom = np.hypot(a0, a1)
        if denom == 0:
            steps.append((col, g.copy(), True))
            break
        cs[k] = a0 / denom
        sn[k] = ((col[k] / a0 if a0 else 1.0) * np.conj(col[k + 1])
                 / (denom if a0 else a1))
        col[k], col[k + 1] = cs[k] * col[k] + sn[k] * col[k + 1], 0.0
        g[k], g[k + 1] = cs[k] * g[k], -np.conj(sn[k]) * g[k]
        steps.append((col, g.copy(), False))
    return steps


def _hessenberg_columns(m, dtype, seed=11):
    rng = np.random.default_rng(seed)
    cols = [rng.uniform(-1, 1, k + 2) for k in range(m)]
    if np.issubdtype(dtype, np.complexfloating):
        cols = [c + 1j * rng.uniform(-1, 1, c.size) for c in cols]
    for c in cols:
        c[-1] = abs(c[-1]) + 0.1  # the entry under the diagonal: a norm
    return [c.astype(dtype) for c in cols]


def _givens_steps(cols, beta, target=0.0):
    """``linalg._givens_column`` over the columns, one compiled step with the
    index traced as the Arnoldi loop has it: after each step ``(H, Q, g,
    breakdown, conv)`` on the host."""
    m, dt = len(cols), cols[0].dtype
    step = jax.jit(linalg._givens_column)
    H, Q = jnp.zeros((m + 1, m), dt), jnp.eye(m + 1, dtype=dt)
    beta = jnp.asarray(beta, np.zeros((), dt).real.dtype)
    target = jnp.asarray(target, beta.dtype)
    out = []
    for k, entries in enumerate(cols):
        hcol = np.zeros(m + 1, dt)
        hcol[:k + 1] = entries[:k + 1]
        H, Q, bd, conv = step(jnp.asarray(hcol), jnp.asarray(entries[k + 1].real),
                              H, Q, beta, jnp.int32(k), target)
        out.append((np.asarray(H), np.asarray(Q),
                    np.asarray(linalg._givens_rhs(Q, beta)), bool(bd), bool(conv)))
    return out


@pytest.mark.parametrize("dtype", [np.float32, np.complex64],
                         ids=["float32", "complex64"])
@pytest.mark.parametrize("restart", [1, 3, 30, 200])
@pytest.mark.parametrize("zero", ["random", "zero-first", "zero-last"])
def test_the_rotations_as_one_matrix_are_the_rotations_one_by_one(
        restart, dtype, zero):
    """Every step of a cycle against the plain reference: the Hessenberg's
    column, the rotated right-hand side, ``Q`` unitary with the rows past the
    step the identity's still. ``zero``: at the cycle's first or last step
    the column's diagonal entry comes out of the accumulated rotations
    exactly zero (c = 0: the rotation swaps the two rows)."""
    m = restart
    cols = _hessenberg_columns(m, dtype)
    zero_at = {"random": None, "zero-first": 0, "zero-last": m - 1}[zero]
    if zero_at is not None:
        # the rotations are unitary: zero above the norm before them is zero
        # above it after them
        cols[zero_at][:-1] = 0
    beta = 1.7
    ref = _rotations_reference(cols, beta)
    got = _givens_steps(cols, beta)
    assert len(ref) == len(got) == m
    eps = float(np.finfo(np.zeros((), dtype).real.dtype).eps)
    scale = max(np.abs(c).max() for c in cols) * np.sqrt(m + 1)
    for k, ((rcol, rg, rbd), (H, Q, g, bd, conv)) in enumerate(zip(ref, got)):
        assert H.dtype == Q.dtype == g.dtype == dtype
        assert (bd, conv, rbd) == (False, False, False)
        tol = 8 * eps * np.sqrt(k + 2)
        assert np.abs(H[:, k] - rcol).max() <= tol * scale, k
        assert np.abs(g - rg).max() <= tol * beta, k
        assert not H[k + 1:, k].any() and not H[:, k + 1:].any()
        Q64 = Q.astype(np.complex128)
        assert np.abs(Q64 @ Q64.conj().T - np.eye(m + 1)).max() <= tol
        assert np.array_equal(Q[k + 2:], np.eye(m + 1, dtype=dtype)[k + 2:])
        if k == zero_at:
            assert H[k, k] == pytest.approx(abs(cols[k][-1])) and g[k] == 0


@pytest.mark.parametrize("dtype", [np.float32, np.complex64],
                         ids=["float32", "complex64"])
def test_a_breakdown_leaves_the_rotations_and_the_right_hand_side(dtype):
    """``denom == 0`` at the fourth step: the flag, ``Q`` and so ``g`` to the
    bit what the third step left; through the Arnoldi loop the step counter
    stays (a nilpotent shift: the Krylov space closes after three steps)."""
    m = 6
    cols = _hessenberg_columns(m, dtype)
    cols[3][:] = 0
    ref = _rotations_reference(cols, 2.0)
    got = _givens_steps(cols, 2.0)[:4]
    assert [s[2] for s in ref] == [False, False, False, True]
    (_H2, Q2, g2, bd2, _c2), (_H3, Q3, g3, bd3, _c3) = got[2], got[3]
    assert (bd2, bd3) == (False, True)
    assert np.array_equal(Q3, Q2) and np.array_equal(g3, g2)
    assert np.abs(g3 - ref[3][1]).max() <= 1e-6 * 2.0
    n, stop = 40, 3
    phase = (0.6 + 0.8j) if np.issubdtype(dtype, np.complexfloating) else 1.0
    A = sparse_tpu.csr_array(sp.diags([np.full(n - 1, phase, dtype)], [1],
                                      format="csr"))
    b = jnp.zeros(n, dtype).at[stop].set(2.0)
    beta = jnp.linalg.norm(b)
    _V, H, g, k, bd = linalg._gmres_arnoldi(
        linalg.make_linear_operator(A).matvec, lambda v: v, b, beta,
        jnp.asarray(1e-30, beta.dtype), m)
    assert (int(k), bool(bd)) == (stop, True)
    # the Hessenberg is the shift's: a one under each zero diagonal entry,
    # every rotation a swap; the fourth column is empty
    shift = [np.eye(j + 2, dtype=dtype)[j + 1] for j in range(stop)]
    shift += [np.zeros(j + 2, dtype) for j in range(stop, m)]
    ref = _rotations_reference(shift, 2.0)
    assert [s[2] for s in ref] == [False] * stop + [True]
    assert np.abs(np.asarray(g) - ref[stop][1]).max() <= 1e-6 * 2.0
    assert abs(ref[stop][1][stop]) == 2.0
    assert np.allclose(np.abs(np.asarray(H)[:stop, :stop]), np.eye(stop))


@pytest.mark.parametrize("dtype", [np.float32, np.complex64],
                         ids=["float32", "complex64"])
def test_the_recurrences_residual_under_the_target_is_conv(dtype):
    m, beta = 8, 3.0
    cols = _hessenberg_columns(m, dtype)
    history = [abs(g[k + 1]) for k, (_c, g, _b) in
               enumerate(_rotations_reference(cols, beta))]
    assert all(a > b for a, b in zip(history, history[1:]))
    target = np.sqrt(history[4] * history[5])  # between two steps'
    assert [s[4] for s in _givens_steps(cols, beta, target)] == \
        [False] * 5 + [True] * (m - 5)
