"""``linalg.batched_bicgstab`` as one compiled program (PR 55):
``jit_batched_bicgstab`` over operands that declare what they hold (a
``BatchedDIA``'s planes, the Jacobi factory's reciprocal diagonal), driven by
``linalg._run_compiled_solve``; everything else keeps the eager loop.

What is pinned, and why it is what it is. The program's body is
``krylov._bicgstab_loop`` as the eager call runs it, so on systems BiCGStab
solves steadily (the strictly dominant banded lanes of ``_lanes``, 56 rows)
the two give every lane the same iteration count and, on this XLA's CPU
backend, the same bits (0 ulps read on six seeds with and without Jacobi,
PR 55). Held here: the counts lane for lane, the answers to ``ULPS`` of a
lane's largest entry. Not the bits, because they are the compiler's: at the
benchmark's shapes (992 rows, nine planes) XLA fuses the start's residual
``b - A x0`` otherwise inside the one program than op by op before a loop
compiled on its own (read there: one ulp of the products' largest term,
2e-6 absolute), and the planes are arguments where they were constants. On
systems at float32's floor (the benchmark's electron lanes:
tests/test_xgc_reference.py) BiCGStab's recurrence amplifies that ulp and
lanes stop up to four steps apart either way; that is the method's, and
nothing is pinned there but the stopping rule.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from sparse_tpu import linalg, precond, telemetry
from sparse_tpu.batch import BatchedCSR, BatchedDIA, SparsityPattern, krylov
from sparse_tpu.batch.operator import make_batched_operator
from sparse_tpu.config import settings
from sparse_tpu.telemetry import _metrics

TRACES = _metrics.counter("batch.bicgstab.traces")
OFFSETS = (-7, -1, 0, 1, 7)
ULPS = 4  # of float32, against a lane's largest entry; 0 is what is read


def _lanes(B=6, n=56, dtype=np.float32, seed=0, complex_=False):
    """B nonsymmetric, strictly row-dominant systems on one banded pattern,
    the later lanes less dominant (more steps): ``(pattern, values [B, nnz],
    b [B, n])``."""
    rng = np.random.default_rng(seed)
    P = sp.diags([np.ones(n - abs(o)) for o in OFFSETS], OFFSETS,
                 format="csr")
    P.sort_indices()
    rows = np.repeat(np.arange(n), np.diff(P.indptr))
    diag = P.indices == rows
    values = np.empty((B, P.nnz), dtype=np.float64)
    for k in range(B):
        off = -rng.uniform(0.2, 1.0, size=P.nnz)
        A = sp.csr_matrix((np.where(diag, 0.0, off), P.indices, P.indptr))
        margin = 1.5 / (1 + 3 * k)
        values[k] = np.where(
            diag, np.repeat(-np.asarray(A.sum(axis=1)).ravel() + margin,
                            np.diff(P.indptr)), off)
    b = rng.standard_normal((B, n))
    if complex_:
        values = values * (1 + 0.2j)
        b = b + 1j * rng.standard_normal((B, n))
    pattern = SparsityPattern(P.indptr, P.indices, P.shape)
    return pattern, jnp.asarray(values.astype(dtype)), jnp.asarray(b.astype(dtype))


def _declared(pattern, values, jacobi=True):
    op = BatchedCSR(pattern, values).todia()
    M = precond.make_factory(pattern, "jacobi")(values, op.matvec) if jacobi else None
    return op, M


def _closures(op, M):
    """The same product and preconditioner as the parent's callers handed
    them: a callable and a closure, which declare nothing."""
    return op.matvec, (None if M is None else (lambda R: M(R)))


def _ulps(a, b):
    a, b = np.asarray(a), np.asarray(b)
    scale = np.abs(b).max(axis=-1, keepdims=True) if b.ndim > 1 else np.abs(b).max()
    return float(np.max(np.abs(a - b) / (scale * np.finfo(b.dtype).eps)))


@pytest.fixture
def live(tmp_path):
    was = settings.telemetry
    telemetry.reset()
    settings.telemetry = True
    telemetry.configure(str(tmp_path / "t.jsonl"))
    yield
    settings.telemetry = was
    telemetry.configure(None)
    telemetry.reset()


KW = dict(tol=1e-5, maxiter=60, conv_test_iters=1)


@pytest.mark.parametrize("jacobi", [True, False], ids=["jacobi", "no_precond"])
def test_compiled_call_matches_the_eager_loop(jacobi):
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values, jacobi)
    assert isinstance(op, BatchedDIA) and op.offsets == OFFSETS
    t0 = TRACES.value
    X, info = linalg.batched_bicgstab(op, b, M=M, **KW)
    assert TRACES.value == t0 + 1
    mv, Mc = _closures(op, M)
    Xe, infoe = linalg.batched_bicgstab(mv, b, M=Mc, **KW)
    assert TRACES.value == t0 + 1  # the closures ran the eager loop
    iters = np.asarray(info.iters)
    # lane for lane, and the lanes differ (the later ones are stiffer)
    assert np.array_equal(iters, np.asarray(infoe.iters))
    assert iters.min() < iters.max() < KW["maxiter"]
    assert np.asarray(info.converged).all() and np.asarray(infoe.converged).all()
    # nowhere near the 1e-5 of the stopping rule
    assert _ulps(X, Xe) <= ULPS
    assert np.asarray(info.resid2) == pytest.approx(np.asarray(infoe.resid2),
                                                    rel=1e-3)
    # the compiled call's counts are on the host: its one fetch brought them
    assert all(isinstance(a, np.ndarray)
               for a in (info.iters, info.resid2, info.converged))
    assert info.iters.dtype == np.int32 and info.converged.dtype == bool
    # and both answers solve the systems
    A = BatchedCSR(pattern, values)
    res = np.linalg.norm(np.asarray(A.matvec(X)) - np.asarray(b), axis=1)
    assert (res < 2 * KW["tol"]).all()


def test_new_values_and_a_new_b_trace_nothing():
    pattern, values, b = _lanes(seed=1)
    op, M = _declared(pattern, values)
    linalg.batched_bicgstab(op, b, M=M, **KW)
    t0 = TRACES.value
    _p, values2, b2 = _lanes(seed=2)
    op2, M2 = _declared(pattern, values2)
    X2, info2 = linalg.batched_bicgstab(op2, b, M=M2, **KW)
    # a third with another b, start, tolerance a lane and maxiter
    X3, info3 = linalg.batched_bicgstab(
        op2, b2, x0=b, tol=jnp.full((b.shape[0],), 1e-4, jnp.float32),
        maxiter=40, M=M2, conv_test_iters=1)
    assert TRACES.value == t0
    mv, Mc = _closures(op2, M2)
    Xe, infoe = linalg.batched_bicgstab(mv, b, M=Mc, **KW)
    assert np.array_equal(info2.iters, np.asarray(infoe.iters))
    assert _ulps(X2, Xe) <= ULPS
    assert np.asarray(info3.converged).all()
    # another structure is another program: the cadence is static
    linalg.batched_bicgstab(op2, b, M=M2, tol=1e-5, maxiter=60, conv_test_iters=5)
    assert TRACES.value == t0 + 1


def test_batch_of_one_reproduces_linalg_bicgstab():
    pattern, values, b = _lanes(B=1, seed=3)
    op, _M = _declared(pattern, values, jacobi=False)
    t0 = TRACES.value
    X, info = linalg.batched_bicgstab(op, b, tol=1e-5, maxiter=60,
                                      conv_test_iters=1)
    assert TRACES.value == t0 + 1
    A = BatchedCSR(pattern, values).lane(0)
    x, iters = linalg.bicgstab(A, b[0], tol=1e-5, maxiter=60, conv_test_iters=1)
    assert int(info.iters[0]) == int(iters)
    assert _ulps(X[0], x) <= ULPS


def test_a_frozen_lane_keeps_its_own_stopping_iterate():
    """A lane that stops early is frozen under its mask while the batch's
    last lane goes on: its answer is what the lane alone stops at."""
    pattern, values, b = _lanes(seed=4)
    op, M = _declared(pattern, values)
    X, info = linalg.batched_bicgstab(op, b, M=M, **KW)
    first = int(np.argmin(info.iters))
    assert info.iters[first] < info.iters.max()
    op1, M1 = _declared(pattern, values[first:first + 1])
    x, info1 = linalg.batched_bicgstab(op1, b[first:first + 1], M=M1, **KW)
    assert int(info1.iters[0]) == int(info.iters[first])
    assert _ulps(X[first], x[0]) <= ULPS
    assert info.resid2[first] == pytest.approx(float(info1.resid2[0]), rel=1e-3)


def _float64():
    pattern, values, b = _lanes(dtype=np.float64)
    op, M = _declared(pattern, values)
    return (op, b, M), (op.matvec, M)


def _complex64():
    pattern, values, b = _lanes(dtype=np.complex64, complex_=True)
    op, M = _declared(pattern, values)
    return (op, b, M), (op.matvec, M)


def _callable_a():
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values)
    return (op.matvec, b, M), (op.matvec, M)


def _closure_m():
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values)
    Mc = lambda R: M(R)  # noqa: E731
    return (op, b, Mc), (op.matvec, Mc)


def _dense_stack():
    pattern, values, b = _lanes()
    dense = np.stack([BatchedCSR(pattern, values).lane(i).toarray()
                      for i in range(values.shape[0])])
    op = make_batched_operator(dense)
    return (op, b, None), (op.matvec, None)


def _batched_csr():
    pattern, values, b = _lanes()
    op = BatchedCSR(pattern, values)
    M = precond.make_factory(pattern, "jacobi")(values, op.matvec)
    return (op, b, M), (op.matvec, M)


@pytest.mark.parametrize("case", [_float64, _complex64, _callable_a, _closure_m,
                                  _dense_stack, _batched_csr],
                         ids=lambda f: f.__name__.strip("_"))
def test_every_other_operand_takes_the_eager_loop(case, live):
    """The parent's path and the parent's answers, to the bit: the loop the
    call runs is the loop called directly."""
    (A, b, M), (mv, Mv) = case()
    t0 = TRACES.value
    X, info = linalg.batched_bicgstab(A, b, M=M, **KW)
    assert TRACES.value == t0
    names = [e["name"] for e in telemetry.events("span")]
    assert "batched_bicgstab.solve" not in names
    tol = jnp.broadcast_to(jnp.asarray(KW["tol"], jnp.real(b).dtype), b.shape[:1])
    Xp, iters, resid2, conv = krylov._bicgstab_loop(
        mv, b, jnp.zeros_like(b), tol, KW["maxiter"], 1, Mv)
    assert np.array_equal(np.asarray(X), np.asarray(Xp))
    assert np.array_equal(np.asarray(info.iters), np.asarray(iters))
    assert np.array_equal(np.asarray(info.resid2), np.asarray(resid2))
    assert np.asarray(info.converged).all()
    # the call still ends in its two events, the account's on the second
    (solve,) = telemetry.events("batch.solve")
    (call,) = telemetry.events("solver.solve")
    assert solve["solver"] == "bicgstab" and call["solver"] == "batched_bicgstab"
    assert call["iters"] == solve["iters_max"] and "call_ms" in call
    assert "dispatch_ms" not in call


def test_an_outer_trace_takes_the_eager_loop():
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values)
    t0 = TRACES.value
    X = jax.jit(lambda v: linalg.batched_bicgstab(op, v, M=M, **KW)[0])(b)
    assert TRACES.value == t0
    Xc, _info = linalg.batched_bicgstab(op, b, M=M, **KW)
    assert _ulps(X, Xc) <= ULPS


@pytest.mark.parametrize("telemetry_on", [False, True], ids=["off", "on"])
def test_one_fetch_a_call(telemetry_on, tmp_path, monkeypatch):
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values)
    linalg.batched_bicgstab(op, b, M=M, **KW)
    monkeypatch.setattr(settings, "telemetry", telemetry_on)
    telemetry.configure(str(tmp_path / "t.jsonl") if telemetry_on else None)
    fetched = []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda tree: fetched.append(tree) or get(tree))
    try:
        s0 = linalg.HOST_SYNCS
        _X, info = linalg.batched_bicgstab(op, b, M=M, **KW)
        assert linalg.HOST_SYNCS == s0 + 1
    finally:
        telemetry.configure(None)
        telemetry.reset()
    # the event's own device_get (telemetry on) is handed host arrays
    assert all(isinstance(a, np.ndarray) for tree in fetched for a in tree)
    assert len(fetched) == int(telemetry_on)
    assert info.iters.max() > 0


def test_the_eager_loops_event_makes_one_fetch(live, monkeypatch):
    """``krylov._solve_event``: the three per-lane arrays in one
    ``device_get``, where the parent made three ``np.asarray`` fetches."""
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values)
    mv, Mc = _closures(op, M)
    fetched = []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda tree: fetched.append(tree) or get(tree))
    linalg.batched_bicgstab(mv, b, M=Mc, **KW)
    linalg.batched_cg(mv, b, tol=1e-5, maxiter=3)
    assert [len(tree) for tree in fetched] == [3, 3]
    assert all(isinstance(a, jax.Array) for a in fetched[0])


def test_span_and_event_fields(live):
    pattern, values, b = _lanes(seed=5)
    op, M = _declared(pattern, values)
    X, info = linalg.batched_bicgstab(op, b, x0=b, M=M, **KW)
    (span,) = [e for e in telemetry.events("span")
               if e["name"] == "batched_bicgstab.solve"]
    B, n = b.shape
    iters = np.asarray(info.iters)
    want = {"path": "device", "B": B, "n": n, "diags": len(OFFSETS),
            "precond": "jacobi", "fetches": 1, "iters_max": int(iters.max()),
            "iters_sum": int(iters.sum()), "converged": B}
    assert {k: span[k] for k in want} == want
    frozen = 100.0 * (1.0 - iters.sum() / (B * iters.max()))
    assert span["frozen_lane_pct"] == pytest.approx(frozen, abs=1e-3) and frozen > 10
    assert span["dispatch_s"] >= 0 and span["fetch_s"] >= 0
    assert "lanes" not in span and "iters" not in span
    (solve,) = telemetry.events("batch.solve")
    assert solve["solver"] == "bicgstab" and (solve["B"], solve["n"]) == (B, n)
    for k in ("iters_max", "iters_sum", "frozen_lane_pct", "converged"):
        assert solve[k] == span[k]
    assert solve["iters_mean"] == pytest.approx(iters.mean())
    # the call's account closes onto the solver.solve event, the last one
    last = telemetry.events()[-1]
    assert last["kind"] == "solver.solve" and last["solver"] == "batched_bicgstab"
    assert (last["iters"], last["path"], last["n"]) == (int(iters.max()), "device", n)
    parts = [last[k] for k in ("prep_ms", "dispatch_ms", "wait_ms", "rest_ms")]
    assert last["call_ms"] == pytest.approx(sum(parts), abs=1e-6)
    assert last["dispatch_ms"] == pytest.approx(span["dispatch_s"] * 1e3, abs=1e-3)
    assert telemetry.summary()["spans"]["solver.call"]["n"] == 1
    # without a preconditioner the span says so
    linalg.batched_bicgstab(op, b, **KW)
    spans = [e for e in telemetry.events("span")
             if e["name"] == "batched_bicgstab.solve"]
    assert spans[-1]["precond"] == "none"


def test_the_value_stacks_repacks_are_spans(live):
    pattern, values, _b = _lanes()
    op, _M = _declared(pattern, values)
    packs = [e for e in telemetry.events("span")
             if e["name"] == "batch.values_pack"]
    assert [e["form"] for e in packs] == ["planes", "jacobi"]
    assert packs[0]["B"] == values.shape[0] and packs[0]["diags"] == len(OFFSETS)
    assert telemetry.summary()["spans"]["batch.values_pack"]["n"] == 2


def test_telemetry_off_writes_nothing():
    assert not settings.telemetry
    telemetry.reset()
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values)
    linalg.batched_bicgstab(op, b, M=M, **KW)
    assert telemetry.events() == []
    assert "solver.call" not in telemetry.summary().get("spans", {})


def test_the_operands_declare_what_they_hold():
    pattern, values, _b = _lanes()
    op, M = _declared(pattern, values)
    assert op.operands[0] is op.data and op.apply == _declared(pattern, values)[0].apply
    assert hash(op.apply) == hash(BatchedDIA(op.data, OFFSETS, pattern.shape).apply)
    assert np.array_equal(np.asarray(op.apply(op.operands, _b)),
                          np.asarray(op.matvec(_b)))
    assert M.apply is precond.jacobi._scale and M.describe == {"precond": "jacobi"}
    assert np.array_equal(np.asarray(M.apply(M.operands, _b)), np.asarray(M(_b)))
    # the one-lane wrapper declares the same function
    one = precond.make_M(BatchedCSR(pattern, values).lane(0), "jacobi")
    assert one.apply is M.apply
    assert BatchedCSR(pattern, values).apply is None


def test_the_compiled_programs_text_carries_the_scopes():
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values)
    linalg.batched_bicgstab(op, b, M=M, **KW)
    t0 = TRACES.value
    text = linalg._batched_bicgstab_compiled(op, b, M, 1).as_text()
    assert TRACES.value == t0  # jit's own executable: nothing is traced for it
    assert "jit_batched_bicgstab" in text
    for scope in ("/batch.spmv/", "/batch.precond/", "/bucket.dots/"):
        assert scope in text, scope
    mv, Mc = _closures(op, M)
    assert linalg._batched_bicgstab_compiled(mv, b, Mc, 1) is None
