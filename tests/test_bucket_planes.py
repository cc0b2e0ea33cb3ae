"""The bucket program's matvec is chosen from the pattern (PR 28).

A pattern the banded rule (``dia.few_diagonals``) lays out as planes gets a
bucket program whose matvec is D shifted multiply-adds over row-layout
planes (``ops.dia_spmv.dia_planes_matvec``); any other pattern compiles the
SELL gather program. Nothing sets the form: these tests force the gather form
by patching ``SparsityPattern.plane_pack`` in the test, never through an
option of the program.

Since PR 36 the gather program without a preconditioner runs its loop in the
SELL pack's own row order (``batch.operator._PackOrder``): the ``pos`` gather
that closes a product in the caller's order leaves the loop, and the program
agrees with the parent's (``_parent_program``, kept here as the row-order
oracle) to rounding. With a preconditioner it is still the parent's, text for
text.
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
from sparse_tpu.kernels.sell_spmv import slab_rows
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
    # the GMRES bucket program asks the pattern as cg's and bicgstab's do
    ("gmres", {}, "single", "planes"),
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
    run = ses._build_program(pattern, 4, np.dtype(np.float64))
    assert run.matvec == "sell"
    values = [base.data * c for c in (1.0, 1.5, 2.5, 4.0)]
    _agrees_with_parent(run, _parent_program(ses, pattern, solver),
                        _lane_args(values, 64, np.float64, seed=10))
    _jacobi_program_is_the_parents(ses, pattern, solver)


def test_dia_view_refuses_duplicate_entries():
    """A plane has one slot for an entry: the explicit view says so."""
    with pytest.raises(ValueError, match="duplicate"):
        BatchedCSR.from_stack([tridiag_dup(), tridiag_dup()]).todia()


def _program_args(pattern, B, dtype):
    n = pattern.shape[0]
    return (np.zeros((B, pattern.nnz), dtype), np.zeros((B, n), dtype),
            np.zeros((B, n), dtype), np.zeros((B,), np.float64), n * 10)


def _parent_program(ses, pattern, solver, mfac=None):
    """The bucket program as the parent commit built it for every pattern:
    the loop in the caller's row order, every product closed by ``pos``."""
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
        Mvec = None if mfac is None else mfac(values, fmv)
        return loop(fmv, rhs, x0, tols, maxiter, cti, Mvec=Mvec)

    return run


def _lane_args(values, n, dtype, seed):
    """Arguments of a bucket program with work in them: the lanes' value
    rows, a start that is not zero, tolerances mixed by lane."""
    values = np.asarray(values, dtype)
    B = values.shape[0]
    rng = np.random.default_rng(seed)
    rhs = rng.standard_normal((B, n)).astype(dtype)
    x0 = (0.1 * rng.standard_normal((B, n))).astype(dtype)
    eps = 1e-4 if np.dtype(dtype) == np.float32 else 1e-10
    tols = np.asarray([eps * (10.0 if i % 2 else 1.0) for i in range(B)])
    return (values, rhs, x0, tols * np.linalg.norm(rhs, axis=1), n * 10)


def _agrees_with_parent(run, parent, args):
    """Answers, iteration counts, residual norms and the converged mask of
    the two programs: the same recurrences and test points; a dot product
    sums its rows in another order, which is rounding."""
    X, iters, resid2, conv = (np.asarray(o) for o in run(*args))
    Xp, itp, rp, cp = (np.asarray(o) for o in parent(*args))
    eps = np.finfo(X.dtype).eps
    assert conv.all() and cp.all()
    np.testing.assert_array_equal(iters, itp)
    assert iters.max() >= 5  # there was a loop to agree on
    scale = np.linalg.norm(Xp, axis=1)
    assert (np.linalg.norm(X - Xp, axis=1) <= 1e3 * eps * scale).all()
    # a residual norm is the sum of the same squares in another order
    # after the same number of steps
    tol2 = np.asarray(args[3]) ** 2
    assert (np.abs(resid2 - rp) <= 1e-2 * tol2 + 1e-3 * rp).all()


def _jacobi_program_is_the_parents(ses, pattern, solver):
    """A preconditioner is built and applied in the caller's row order, so
    the program that has one keeps the row-order loop, text for text."""
    args = _program_args(pattern, 4, np.float32)
    run = ses._build_program(pattern, 4, np.dtype(np.float32), solver=solver,
                             precond="jacobi")
    parent = _parent_program(ses, pattern, solver,
                             ses.precond.factory(pattern, "jacobi"))
    assert run.matvec == "sell"
    assert run.lower(*args).as_text() == parent.lower(*args).as_text()


@pytest.mark.parametrize("solver", ["cg", "bicgstab"])
@pytest.mark.parametrize("name", list(GENERAL))
def test_general_pattern_agrees_with_the_parents_program(monkeypatch, name,
                                                         solver):
    """Not banded, or banded over ``dia_max_fill``: the gather program, in
    the pack's row order. It agrees with the parent's to rounding, is the
    program built with the selection forced off, and with a preconditioner
    it is the parent's text."""
    A = GENERAL[name]()
    ses = SolveSession(solver, batch_max=4, warm_start=False)
    pattern = ses.pattern_of(A)
    run = ses._build_program(pattern, 4, np.dtype(np.float32))
    parent = _parent_program(ses, pattern, solver)
    for dtype, seed in ((np.float32, 21), (np.float64, 22)):
        mats, _ = _lanes(A, 4, dtype, seed)
        _agrees_with_parent(
            ses._build_program(pattern, 4, np.dtype(dtype)), parent,
            _lane_args([M.data for M in mats], A.shape[0], dtype, seed))
    args = _program_args(pattern, 4, np.float32)
    text = run.lower(*args).as_text()
    assert text != parent.lower(*args).as_text()
    with monkeypatch.context() as m:
        m.setattr(SparsityPattern, "plane_pack", lambda self: None)
        forced = ses._build_program(pattern, 4, np.dtype(np.float32))
    assert text == forced.lower(*args).as_text()
    assert "gather" in text
    _jacobi_program_is_the_parents(ses, pattern, solver)


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


# ---------------------------------------------------------------------------
# the gather program in the SELL pack's own row order (PR 36)
# ---------------------------------------------------------------------------
def empty_rows(n=70):
    """``skewed`` with every seventh row and column emptied: the pack's
    trailing block of all-empty rows (``zero_rows`` > 0). Not solvable."""
    A = skewed(n).tolil()
    for i in range(0, n, 7):
        A[i, :] = 0.0
        A[:, i] = 0.0
    A = sp.csr_matrix(A)
    A.eliminate_zeros()
    return _finish(A)


def fem_mesh(side):
    """The served general cell's pattern class at a small side."""
    from .utils.spd import fem_heat_data

    return _dominant(fem_heat_data(side, 3, clients=1)["pattern"])


ORDERED = {**GENERAL, "empty_rows": empty_rows,
           # 37 rows in slabs whose rows pad to multiples of ROW_ALIGN
           "pad_rows": lambda: skewed(37, seed=4),
           # 5184 rows: the slab of 1856 rows (832 past 1024: off the
           # gather's wide band, `slab_rows`) is stored with 2304, the
           # two of 1240 with 1280
           "slab_pad": lambda: fem_mesh(72),
           # 4096 rows: two slabs past 1024 rows moved to multiples of 256
           # in the band, their sum with the small slabs' (4440) is not
           # one: the space ends in 168 trailing pad rows
           "space_pad": lambda: fem_mesh(64)}


def _order_of(A):
    pattern = SparsityPattern.from_csr(A)
    pack = pattern.sell_pack()
    return pack, pack.own_order()


@pytest.mark.parametrize("name", list(ORDERED))
def test_packed_product_then_pos_is_the_row_order_product(name):
    """Bit for bit: the one slab loop, with and without its closing gather,
    and through the renumbered indices on vectors held in the pack's order."""
    A = ORDERED[name]()
    pack, order = _order_of(A)
    plan = pack.plan
    if name == "empty_rows":
        assert plan.zero_rows > 0
    stored = plan.zero_rows + sum(r for _k, r, _p in plan.slab_meta)
    if name == "pad_rows":
        assert plan.pad_rows > 0
    if name == "slab_pad":
        assert {(6, 1280, 40), (7, 2304, 448), (8, 1280, 40)} <= set(
            plan.slab_meta)
    if name == "space_pad":
        assert plan.pad_rows == 344 and order.pad_rows == 344 + 168
    # the space: the slabs and the all-empty rows, through the slabs' rule
    assert order.rows.shape[0] == slab_rows(stored) == stored + (
        order.pad_rows - plan.pad_rows)
    if stored > 1024:
        assert 8 <= order.rows.shape[0] % 1024 <= 768
        assert order.rows.shape[0] % 256 == 0
    rng = np.random.default_rng(31)
    values = rng.standard_normal((3, A.nnz)).astype(np.float32)
    X = rng.standard_normal((3, A.shape[0])).astype(np.float32)
    vals = pack.pack_values(values)
    want = np.asarray(spmv_ops.csr_spmv_sell_batched(
        pack.idx_slabs, vals, pack.pos, X, plan.zero_rows))
    packed = spmv_ops.csr_spmv_sell_batched(
        pack.idx_slabs, vals, None, X, plan.zero_rows)
    assert packed.shape == (3, stored)
    np.testing.assert_array_equal(np.asarray(packed[:, pack.pos]), want)
    mine = order.leave(order.product(vals, order.enter(X)))
    np.testing.assert_array_equal(np.asarray(mine), want)
    for i in range(3):  # ... and it is the product
        Ai = sp.csr_matrix((values[i], A.indices, A.indptr), shape=A.shape)
        np.testing.assert_allclose(want[i], Ai @ X[i], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", list(ORDERED))
def test_pad_rows_are_zero_and_stay_zero(name):
    """``enter`` zeroes the pad rows (the slabs', and the space's trailing
    ones) and a product leaves them zero, so R and P never carry anything
    there into a dot product."""
    A = ORDERED[name]()
    pack, order = _order_of(A)
    rows = np.asarray(order.rows)
    real = rows >= 0
    assert real.sum() == A.shape[0]
    assert (~real).sum() == order.pad_rows >= pack.plan.pad_rows
    np.testing.assert_array_equal(np.asarray(pack.pos)[rows[real]],
                                  np.nonzero(real)[0])
    rng = np.random.default_rng(32)
    mats, rhs = _lanes(A, 2, np.float64, seed=33)
    vals = pack.pack_values(np.stack([M.data for M in mats]))
    x0 = rng.standard_normal(rhs.shape)
    B, X = np.asarray(order.enter(rhs)), np.asarray(order.enter(x0))
    assert not B[:, ~real].any() and not X[:, ~real].any()
    np.testing.assert_array_equal(np.asarray(order.leave(B)), rhs)
    # three steps of the recurrence, as `krylov._cg_loop` writes them
    R = B - np.asarray(order.product(vals, X))
    P, rho = np.zeros_like(R), None
    for k in range(3):
        rho_new = (R * R).sum(axis=1)
        P = R if k == 0 else R + (rho_new / rho)[:, None] * P
        Q = np.asarray(order.product(vals, P))
        alpha = rho_new / (P * Q).sum(axis=1)
        R, rho = R - alpha[:, None] * Q, rho_new
        for V in (Q, R, P):
            assert not V[:, ~real].any()
    # whatever a pad row held, a product gives it zero
    junk = rng.standard_normal(B.shape)
    assert not np.asarray(order.product(vals, junk))[:, ~real].any()


def _gathers(jaxpr, inside_while=False, out=None):
    """Shapes (operand, result) of every gather of a jaxpr, by whether a
    ``while`` encloses it."""
    out = {True: [], False: []} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out[inside_while].append((eqn.invars[0].aval.shape,
                                      eqn.outvars[0].aval.shape))
        inner = inside_while or eqn.primitive.name == "while"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _gathers(sub, inner, out)
    return out


@pytest.mark.parametrize("solver", ["cg", "bicgstab"])
@pytest.mark.parametrize("name", ["skewed", "pad_rows"])
def test_no_row_permutation_in_the_loop(name, solver):
    """The while body gathers through the slabs' slots and nothing else: the
    parent's has one more gather a product, of the whole row count."""
    A = ORDERED[name]()
    ses = SolveSession(solver, batch_max=2, warm_start=False)
    pattern = ses.pattern_of(A)
    plan = pattern.sell_pack().plan
    slots = sum(k for k, _r, _p in plan.slab_meta)
    products = 1 if solver == "cg" else 2
    args = _program_args(pattern, 2, np.float32)
    m = A.shape[0]

    def body_gathers(run):
        return _gathers(jax.make_jaxpr(run)(*args).jaxpr)

    mine = body_gathers(ses._build_program(pattern, 2, np.dtype(np.float32)))
    rows_of_slabs = {r for _k, r, _p in plan.slab_meta}
    assert len(mine[True]) == products * slots
    assert {res[-1] for _op, res in mine[True]} <= rows_of_slabs
    parents = body_gathers(_parent_program(ses, pattern, solver))
    assert len(parents[True]) == products * (slots + 1)
    assert sum(res[-1] == m for _op, res in parents[True]) >= products
    # outside the loop: the value stack's gathers and the first residual's
    # slots in both; rhs and x0 in and X out here, one `pos` there
    assert len(mine[False]) == len(parents[False]) + 2


def _dispatch_events(monkeypatch, solver, base, **kw):
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.reset()
    ses = SolveSession(solver, batch_max=4, warm_start=False, **kw)
    mats, rhs = _lanes(base, 3, np.float64, seed=41)
    ses.solve_many(mats, rhs, tol=1e-8, maxiter=300)
    evs = telemetry.events("batch.dispatch")
    assert evs and all(telemetry.schema.validate(e) == [] for e in evs)
    return evs


@pytest.mark.parametrize("solver, products", [("cg", 1), ("bicgstab", 2)])
def test_dispatch_events_count_the_row_gathers(monkeypatch, solver, products):
    """`row_gathers`: rhs, x0 and X in the pack's order; the first
    residual's product and every product of the loop where a preconditioner
    keeps the caller's order; none for planes."""
    try:
        (ev,) = _dispatch_events(monkeypatch, solver, skewed())
        assert ev["matvec"] == "sell" and ev["iters_max"] >= 5
        assert ev["row_gathers"] == 3
        (ev,) = _dispatch_events(monkeypatch, solver, skewed(),
                                 precond="jacobi")
        assert ev["matvec"] == "sell"
        # 3 real lanes in a bucket of 4: the pad lane ends at the first test
        trips = max(ev["iters_max"], 25)
        assert ev["row_gathers"] == 1 + products * trips
        (ev,) = _dispatch_events(monkeypatch, solver, grid5())
        assert ev["matvec"] == "planes" and ev["row_gathers"] == 0
    finally:
        telemetry.reset()


@pytest.mark.parametrize("name", ["pad_rows", "slab_pad", "space_pad"])
def test_dispatch_events_count_the_pad_rows(monkeypatch, name):
    """`pad_rows`: the zero rows the program's vectors carry for the pack's
    sake: the slabs', and in the pack's order the space's trailing ones."""
    A = ORDERED[name]()
    try:
        (ev,) = _dispatch_events(monkeypatch, "cg", A)
        pack, order = _order_of(A)
        assert ev["matvec"] == "sell" and ev["row_gathers"] == 3
        assert ev["pad_rows"] == order.pad_rows > 0
        (ev,) = _dispatch_events(monkeypatch, "cg", A, precond="jacobi")
        assert ev["pad_rows"] == pack.plan.pad_rows  # the caller's order
        if name == "pad_rows":
            (ev,) = _dispatch_events(monkeypatch, "cg", grid5())
            assert ev["matvec"] == "planes" and "pad_rows" not in ev
            # the GMRES bucket program multiplies through the pattern's
            # pack too, in the caller's order
            evs = _dispatch_events(monkeypatch, "gmres", A)
            assert evs and all(e["matvec"] == "sell" and e["pad_rows"]
                               == pack.plan.pad_rows for e in evs)
    finally:
        telemetry.reset()


def test_programs_of_other_builders_report_no_row_gathers(monkeypatch):
    """GMRES (in the caller's row order, a left preconditioner's) and the
    refinement programs multiply through `pos`; their builders tag no count
    and the event leaves the field out."""
    try:
        for solver, kw in (("gmres", {}), ("cg", {"dtype_policy": "f32ir"})):
            evs = _dispatch_events(monkeypatch, solver, skewed(), **kw)
            assert all("row_gathers" not in e for e in evs)
    finally:
        telemetry.reset()


def test_a_pattern_that_is_not_square_has_no_order_of_its_own():
    A = _rect(9, 14, (-3, 0, 2, 6, 9), 51)
    pack = SparsityPattern.from_csr(A).sell_pack()
    assert pack.own_order() is None
    pack, order = _order_of(skewed())
    assert order is not None and pack.own_order() is order  # once a pack
    assert SparsityPattern.from_csr(grid5()).plane_pack().own_order() is None
