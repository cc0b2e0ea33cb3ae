"""The bucket program's matvec is chosen from the pattern (PR 28).

A pattern the banded rule (``dia.few_diagonals``) lays out as planes gets a
bucket program whose matvec is D shifted multiply-adds over row-layout
planes (``ops.dia_spmv.dia_planes_matvec``); any other pattern compiles the
SELL gather program it always did, text for text. Nothing sets the form:
these tests force the gather form by patching ``SparsityPattern.plane_pack``
in the test, never through an option of the program.
"""

from functools import partial

import numpy as np
import pytest
import scipy.sparse as sp

import jax

from sparse_tpu import fleet, plan_cache, telemetry
from sparse_tpu.batch import BatchedCSR, BatchedDIA, SolveSession, SparsityPattern
from sparse_tpu.batch import krylov, service
from sparse_tpu.config import settings
from sparse_tpu.dia import few_diagonals
from sparse_tpu.ops import dia_spmv as dia_ops
from sparse_tpu.ops import spmv as spmv_ops


@pytest.fixture(autouse=True)
def _scratch_sink(tmp_path):
    """Events of these tests go to a scratch sink, not the tracked one."""
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield
    telemetry.configure(None)
    telemetry.reset()


# ---------------------------------------------------------------------------
# patterns: each returns a scipy CSR with sorted indices; SPD unless said
# ---------------------------------------------------------------------------
def _finish(A):
    A = sp.csr_matrix(A)
    A.sort_indices()
    return A


def _dominant(A):
    """Strictly diagonally dominant, positive diagonal, on A's pattern."""
    A = sp.csr_matrix(A)
    A = A - sp.diags(A.diagonal())
    return _finish(A + sp.diags(np.asarray(abs(A).sum(axis=1)).ravel() + 1.0))


def grid5(g=7):
    T = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(g, g))
    I = sp.identity(g)
    return _dominant(sp.kron(I, T) + sp.kron(T, I))


def tridiag(n=40):
    return _dominant(sp.diags([-1.0, 0.0, -1.0], [-1, 0, 1], shape=(n, n)))


def band_unequal(n=37):
    """Offsets (-1, 0, 2, 5): overhang 1 to the left, 5 to the right, and the
    corner entries A[0, 2], A[n-6, n-1] and A[n-1, n-2] missing. Not symmetric."""
    A = sp.diags([-0.5, 0.0, -0.7, 0.3], [-1, 0, 2, 5], shape=(n, n)).tolil()
    A[0, 2] = A[n - 6, n - 1] = A[n - 1, n - 2] = 0.0
    A = sp.csr_matrix(A)
    A.eliminate_zeros()
    return _dominant(A)


def slot_run(n=48):
    """Tridiagonal plus diagonals at +-4 that have entries only in the first
    and the last third of their rows: a run of empty slots in the middle."""
    A = sp.diags([-1.0, 0.0, -1.0], [-1, 0, 1], shape=(n, n)).tolil()
    for i in list(range(0, n // 3)) + list(range(2 * n // 3, n - 4)):
        A[i, i + 4] = A[i + 4, i] = -0.5
    return _dominant(A)


def skewed(n=60, seed=3):
    """A general pattern: more diagonals than ``settings.dia_max_diags``."""
    rng = np.random.default_rng(seed)
    G = sp.random(n, n, density=0.08, random_state=rng, format="csr")
    return _dominant(G + G.T)


def sparse_band(n=96):
    """Banded (20 diagonals <= dia_max_diags) but mostly fill: each side
    diagonal holds four entries, so D * n > dia_max_fill * nnz."""
    A = sp.identity(n).tolil()
    for o in range(2, 21, 2):
        for i in (0, 7, 30, n - o - 1):
            A[i, i + o] = A[i + o, i] = -0.1
    return _dominant(A)


BANDED = {"grid5": grid5, "tridiag": tridiag, "band_unequal": band_unequal,
          "slot_run": slot_run}
GENERAL = {"skewed": skewed, "sparse_band": sparse_band}
SYMMETRIC = ("grid5", "tridiag", "slot_run")


def _lanes(base, B, dtype, seed):
    """B systems on base's pattern: the diagonal varied a lane, so each stays
    dominant (and symmetric where base is)."""
    rng = np.random.default_rng(seed)
    n = base.shape[0]
    mats = []
    for _ in range(B):
        A = _finish(base + sp.diags(rng.random(n)))
        mats.append(A.astype(dtype))
    rhs = rng.standard_normal((B, n)).astype(dtype)
    if np.dtype(dtype).kind == "c":
        rhs = rhs + 1j * rng.standard_normal((B, n)).astype(dtype)
    return mats, rhs


def _session_solve(monkeypatch, mats, rhs, tols, solver, planes, **kw):
    """(X, iters, the dispatches' matvec forms) through a fresh session;
    ``planes=False`` builds its programs with the selection forced off."""
    telemetry.reset()
    with monkeypatch.context() as m:
        m.setattr(settings, "telemetry", True)
        if not planes:
            m.setattr(SparsityPattern, "plane_pack", lambda self: None)
        ses = SolveSession(solver, batch_max=8, warm_start=False, **kw)
        tickets = [ses.submit(A, b, tol=t, maxiter=400)
                   for A, b, t in zip(mats, rhs, tols)]
        ses.flush()
        outs = [t.result() for t in tickets]
    forms = [e["matvec"] for e in telemetry.events("batch.dispatch")]
    telemetry.reset()
    return (np.stack([o[0] for o in outs]),
            np.asarray([o[1] for o in outs]), forms)


def _check_against_sell_and_dense(monkeypatch, mats, rhs, tols, solver,
                                  rtol, **kw):
    Xp, itp, forms_p = _session_solve(monkeypatch, mats, rhs, tols, solver,
                                      True, **kw)
    Xs, its, forms_s = _session_solve(monkeypatch, mats, rhs, tols, solver,
                                      False, **kw)
    assert forms_p and set(forms_p) == {"planes"}
    assert forms_s and set(forms_s) == {"sell"}
    # the same recurrences and test points: only the product's summation
    # order differs, so the lanes stop at the same iteration
    np.testing.assert_array_equal(itp, its)
    for i, (A, b, t) in enumerate(zip(mats, rhs, tols)):
        x_ref = np.linalg.solve(A.toarray(), b)
        scale = np.linalg.norm(x_ref)
        assert np.linalg.norm(Xp[i] - Xs[i]) <= rtol * scale
        assert np.linalg.norm(A @ Xp[i] - b) <= 2.0 * t + rtol * np.linalg.norm(b)
        assert np.linalg.norm(Xp[i] - x_ref) <= 50.0 * t + rtol * scale


CASES = [(p, s) for p in BANDED for s in ("cg", "bicgstab")
         if s == "bicgstab" or p in SYMMETRIC]


@pytest.mark.parametrize("B", [1, 3, 8])
@pytest.mark.parametrize("name, solver", CASES)
def test_plane_program_matches_sell_and_dense(monkeypatch, name, solver, B):
    """B = 3 pads to a bucket of 4 (one pad lane); tolerances mixed by lane."""
    mats, rhs = _lanes(BANDED[name](), B, np.float64, seed=B)
    tols = [1e-4 if i % 2 else 1e-9 for i in range(B)]
    _check_against_sell_and_dense(monkeypatch, mats, rhs, tols, solver, 1e-9)


@pytest.mark.parametrize("solver", ["cg", "bicgstab"])
@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
def test_plane_program_dtypes(monkeypatch, solver, dtype):
    base = grid5()
    if np.dtype(dtype).kind == "c":  # Hermitian: conjugate hops off the diagonal
        hop = sp.triu(base, 1) * (0.6 + 0.8j)
        base = _finish(hop + hop.conj().T + sp.diags(base.diagonal()))
    mats, rhs = _lanes(base, 3, dtype, seed=5)
    _check_against_sell_and_dense(monkeypatch, mats, rhs, [1e-3, 1e-4, 1e-3],
                                  solver, 2e-4)


@pytest.mark.parametrize("solver", ["cg", "bicgstab"])
def test_plane_program_with_jacobi(monkeypatch, solver):
    """The factory still gets the CSR value stack and the matvec."""
    mats, rhs = _lanes(grid5(), 3, np.float64, seed=6)
    _check_against_sell_and_dense(monkeypatch, mats, rhs, [1e-9, 1e-5, 1e-9],
                                  solver, 1e-9, precond="jacobi")


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(BANDED) + list(GENERAL))
def test_selection_agrees_with_few_diagonals(name):
    A = {**BANDED, **GENERAL}[name]()
    pattern = SparsityPattern.from_csr(A)
    coo = A.tocoo()
    D = len(np.unique(coo.col.astype(np.int64) - coo.row))
    rule = few_diagonals(D, A.shape[0], A.nnz)
    assert rule == (name in BANDED)
    pack = pattern.plane_pack()
    assert (pack is not None) == rule
    ses = SolveSession("cg", batch_max=2, warm_start=False)
    for solver in ("cg", "bicgstab"):
        run = ses._build_program(pattern, 2, np.dtype(np.float32),
                                 solver=solver)
        assert run.matvec == ("planes" if rule else "sell")
    if rule:
        assert pack.offsets == tuple(np.unique(coo.col - coo.row).tolist())
        assert pack.src.shape == (D, A.shape[0])
        assert int((np.asarray(pack.src) >= 0).sum()) == A.nnz


@pytest.mark.parametrize("solver, kw, strategy, form", [
    ("gmres", {}, "single", "sell"),
    ("cg", {"dtype_policy": "f32ir"}, "single", "sell"),
    ("bicgstab", {"dtype_policy": "bf16ir"}, "single", "sell"),
    ("cg", {"fleet": "row", "row_shard_min_n": 8}, "row", "sell"),
    ("cg", {}, "single", "planes"),
    # the batch-sharded exact program takes the single-device one's matvec
    # (their lanes are bit-identical: tests/test_precond.py, test_fleet.py)
    ("bicgstab", {"fleet": "batch", "fleet_min_b": 2}, "batch", "planes"),
    ("cg", {"fleet": "batch", "fleet_min_b": 2, "dtype_policy": "f32ir"},
     "batch", "sell"),
])
def test_which_programs_take_the_plane_form(monkeypatch, solver, kw, strategy,
                                            form):
    """What `batch.dispatch` reports for a banded pattern, by program: the
    form the launched program's builder tagged it with."""
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.reset()
    try:
        if "fleet" in kw:
            kw = dict(kw, fleet_mesh=fleet.fleet_mesh(2))
        ses = SolveSession(solver, batch_max=4, warm_start=False, **kw)
        mats, rhs = _lanes(grid5(), 1 if strategy == "row" else 4,
                           np.float64, seed=8)
        ses.solve_many(mats, rhs, tol=1e-6, maxiter=200)
        evs = telemetry.events("batch.dispatch")
        assert evs and {e["strategy"] for e in evs} == {strategy}
        assert {e["matvec"] for e in evs} == {form}
    finally:
        telemetry.reset()


def tridiag_dup(n=64):
    """A banded CSR that stores its diagonal twice (2 + 2), as an unsummed
    FEM or COO-to-CSR assembly leaves it: scipy allows it, ``toarray`` sums."""
    i = np.arange(n)
    rows = np.concatenate([i, i, i[1:], i[:-1]])
    cols = np.concatenate([i, i, i[:-1], i[1:]])
    data = np.concatenate([np.full(2 * n, 2.0), np.full(2 * n - 2, -1.0)])
    order = np.lexsort((cols, rows))
    A = sp.csr_matrix((data[order], cols[order],
                       np.concatenate([[0], np.cumsum(np.bincount(rows))])),
                      shape=(n, n))
    assert A.nnz == 4 * n - 2 and not A.has_canonical_format
    return A


@pytest.mark.parametrize("solver", ["cg", "bicgstab"])
def test_duplicate_entries_keep_the_sell_program(monkeypatch, solver):
    """Banded by the rule, but two stored entries share a plane slot: the
    pattern keeps the gather program, which sums what it gathers."""
    base = tridiag_dup()
    coo = base.tocoo()
    D = len(np.unique(coo.col.astype(np.int64) - coo.row))
    assert few_diagonals(D, base.shape[0], base.nnz)
    pattern = SparsityPattern.from_csr(base)
    assert pattern.plane_pack() is None
    rng = np.random.default_rng(9)
    mats = [sp.csr_matrix((base.data * c, base.indices, base.indptr),
                          shape=base.shape) for c in (1.0, 1.5, 2.5)]
    rhs = rng.standard_normal((3, 64))
    X, _, forms = _session_solve(monkeypatch, mats, rhs, [1e-9] * 3, solver,
                                 True)
    assert forms and set(forms) == {"sell"}
    for A, b, x in zip(mats, rhs, X):
        x_ref = np.linalg.solve(A.toarray(), b)
        assert np.linalg.norm(A.toarray() @ x - b) <= 1e-8 * np.linalg.norm(b)
        assert np.linalg.norm(x - x_ref) <= 1e-7 * np.linalg.norm(x_ref)
    ses = SolveSession(solver, batch_max=4, warm_start=False)
    pattern = ses.pattern_of(base)
    args = _program_args(pattern, 4, np.float32)
    run = ses._build_program(pattern, 4, np.dtype(np.float32))
    assert run.matvec == "sell"
    assert run.lower(*args).as_text() == _parent_program(
        ses, pattern, solver).lower(*args).as_text()


def test_dia_view_refuses_duplicate_entries():
    """A plane has one slot for an entry: the explicit view says so."""
    with pytest.raises(ValueError, match="duplicate"):
        BatchedCSR.from_stack([tridiag_dup(), tridiag_dup()]).todia()


def _program_args(pattern, B, dtype):
    n = pattern.shape[0]
    return (np.zeros((B, pattern.nnz), dtype), np.zeros((B, n), dtype),
            np.zeros((B, n), dtype), np.zeros((B,), np.float64), n * 10)


def _parent_program(ses, pattern, solver):
    """The bucket program as the parent commit built it for every pattern."""
    pack = pattern.sell_pack()
    idx_slabs, pos, zero_rows = pack.idx_slabs, pack.pos, pack.plan.zero_rows
    loop = krylov._cg_loop if solver == "cg" else krylov._bicgstab_loop
    cti = ses.conv_test_iters

    @partial(jax.jit, donate_argnums=service.donate_argnums())
    def run(values, rhs, x0, tols, maxiter):
        vals = pack.pack_values(values)

        def mv(X):
            with jax.named_scope("bucket.matvec"):
                return spmv_ops.csr_spmv_sell_batched(
                    idx_slabs, vals, pos, X, zero_rows
                )

        fmv = krylov._maybe_faulty_mv(mv)
        return loop(fmv, rhs, x0, tols, maxiter, cti, Mvec=None)

    return run


@pytest.mark.parametrize("solver", ["cg", "bicgstab"])
@pytest.mark.parametrize("name", list(GENERAL))
def test_general_pattern_compiles_the_parents_program(monkeypatch, name,
                                                      solver):
    """Not banded, or banded over ``dia_max_fill``: the program text is the
    parent's, and the one built with the selection forced off."""
    A = GENERAL[name]()
    ses = SolveSession(solver, batch_max=4, warm_start=False)
    pattern = ses.pattern_of(A)
    args = _program_args(pattern, 4, np.float32)
    text = ses._build_program(pattern, 4, np.dtype(np.float32)).lower(
        *args).as_text()
    assert text == _parent_program(ses, pattern, solver).lower(*args).as_text()
    with monkeypatch.context() as m:
        m.setattr(SparsityPattern, "plane_pack", lambda self: None)
        forced = ses._build_program(pattern, 4, np.dtype(np.float32))
    assert text == forced.lower(*args).as_text()
    assert "gather" in text


@pytest.mark.parametrize("name", ["grid5", "skewed"])
def test_program_is_named_run(name):
    """``bucket_cg_roofline`` finds the program in the trace as ``jit_run``."""
    A = {**BANDED, **GENERAL}[name]()
    ses = SolveSession("cg", batch_max=2, warm_start=False)
    pattern = ses.pattern_of(A)
    run = ses._build_program(pattern, 2, np.dtype(np.float32))
    text = run.lower(*_program_args(pattern, 2, np.float32)).as_text()
    assert "module @jit_run" in text
    # planes: the value stack's one gather a dispatch, none in the loop
    gathers = text.count('"stablehlo.gather"(')
    assert gathers == 1 if name == "grid5" else gathers > 1


def test_plane_program_needs_no_sell_pack(monkeypatch):
    """... and its matvec is the one shared plane product, traced once for
    the first residual and once in the loop's body."""
    calls = []
    shared = dia_ops.dia_planes_matvec

    def counted(planes, offsets, X):
        calls.append(offsets)
        return shared(planes, offsets, X)

    monkeypatch.setattr(dia_ops, "dia_planes_matvec", counted)
    ses = SolveSession("cg", batch_max=2, warm_start=False)
    pattern = ses.pattern_of(grid5())
    run = ses._build_program(pattern, 2, np.dtype(np.float32))
    run.lower(*_program_args(pattern, 2, np.float32))
    assert plan_cache.lookup(pattern, "sell.pattern") is None
    assert plan_cache.lookup(pattern, "planes.pattern") is not None
    assert calls == [pattern.plane_pack().offsets] * 2


def test_dispatch_events_report_the_form(monkeypatch):
    """One session over a banded and a general pattern: `planes` and `sell`."""
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.reset()
    try:
        ses = SolveSession("cg", batch_max=4, warm_start=False)
        for base in (grid5(), skewed()):
            mats, rhs = _lanes(base, 2, np.float64, seed=7)
            ses.solve_many(mats, rhs, tol=1e-8, maxiter=200)
        evs = telemetry.events("batch.dispatch")
        assert [e["matvec"] for e in evs] == ["planes", "sell"]
        assert all(telemetry.schema.validate(e) == [] for e in evs)
        assert evs[0]["program"] == evs[1]["program"]  # the key is unchanged
    finally:
        telemetry.reset()


# ---------------------------------------------------------------------------
# the one batched plane product
# ---------------------------------------------------------------------------
def _rect(m, n, offsets, seed):
    rng = np.random.default_rng(seed)
    A = sp.lil_matrix((m, n))
    for o in offsets:
        for i in range(m):
            if 0 <= i + o < n and rng.random() < 0.8:
                A[i, i + o] = rng.standard_normal()
    return _finish(A)


SHAPES = {**BANDED,
          "wide": lambda: _rect(6, 9, (-2, 0, 1, 5), 11),
          "tall": lambda: _rect(9, 6, (-6, -1, 0, 3), 12)}


@pytest.mark.parametrize("name", list(SHAPES))
def test_batched_dia_is_the_dense_product_through_the_shared_function(
        monkeypatch, name):
    base = SHAPES[name]()
    rng = np.random.default_rng(13)
    mats = [sp.csr_matrix((rng.standard_normal(base.nnz), base.indices,
                           base.indptr), shape=base.shape) for _ in range(3)]
    calls = []
    shared = dia_ops.dia_planes_matvec

    def counted(planes, offsets, X):
        calls.append(offsets)
        return shared(planes, offsets, X)

    monkeypatch.setattr(dia_ops, "dia_planes_matvec", counted)
    bd = BatchedCSR.from_stack(mats).todia()
    assert isinstance(bd, BatchedDIA)
    X = rng.standard_normal((3, base.shape[1]))
    Y = np.asarray(bd.matvec(X))
    assert calls == [bd.offsets]
    for i, A in enumerate(mats):
        np.testing.assert_allclose(Y[i], A.toarray() @ X[i], rtol=1e-12,
                                   atol=1e-12)
        # row-layout planes back to scipy's column-indexed DIA
        np.testing.assert_allclose(np.asarray(bd.lane(i).todense()),
                                   A.toarray(), rtol=1e-12)


def test_plane_product_without_diagonals_is_zero():
    Y = dia_ops.dia_planes_matvec(np.zeros((2, 0, 5), np.float32), (),
                                  np.ones((2, 7), np.float32))
    assert Y.shape == (2, 5) and Y.dtype == np.float32
    assert not np.asarray(Y).any()
