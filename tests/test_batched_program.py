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

Since PR 56 the program steps a batch of 8,192 lanes or more down a ladder
of halving widths (``krylov._bicgstab_ladder``): the second half of this
file holds it against the one loop at the batch's width, which is what a
pair that does not say which operands hold lanes still runs, and holds the
one loop's programs against the parent's text (``_parent_loop``,
``_parent_lanes``: PR 55's two functions, verbatim).
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


# -- the ladder of widths (PR 56) ------------------------------------------------
LADDER_B = 4 * krylov._LADDER_FLOOR  # three stages: B, B / 2, the floor
LADDER_KW = dict(tol=1e-5, maxiter=40, conv_test_iters=1)


def _wide_lanes(B=LADDER_B, n=24, seed=0, alike=False):
    """``B`` lanes on one small banded pattern, made in bulk: easy and stiff
    ones interleaved (the even lanes stop after 6-9 steps, the odd ones
    after 12-20), every 37th odd lane so stiff that float32 BiCGStab is
    still going at ``maxiter``; ``alike`` makes every lane lane 0."""
    rng = np.random.default_rng(seed)
    offsets = (-5, -1, 0, 1, 5)
    P = sp.diags([np.ones(n - abs(o)) for o in offsets], offsets, format="csr")
    P.sort_indices()
    rows = np.repeat(np.arange(n), np.diff(P.indptr))
    diag = P.indices == rows
    values = -rng.uniform(0.2, 1.0, size=(B, P.nnz))
    values[:, diag] = 0.0
    sums = np.zeros((B, n))
    np.add.at(sums.T, rows, values.T)
    margin = np.where(np.arange(B) % 2 == 0, 1.5, 0.05)
    margin[1::74] = 1e-6
    values[:, diag] = margin[:, None] - sums
    b = rng.standard_normal((B, n))
    if alike:
        values[:], b[:] = values[0], b[0]
    pattern = SparsityPattern(P.indptr, P.indices, P.shape)
    return (pattern, jnp.asarray(values.astype(np.float32)),
            jnp.asarray(b.astype(np.float32)))


class _Mute:
    """An operator or a preconditioner that declares ``apply`` and
    ``operands`` as PR 55's did, and not which operands hold lanes."""

    def __init__(self, op):
        self.op, self.apply, self.operands = op, op.apply, op.operands
        self.describe = getattr(op, "describe", {})

    def __getattr__(self, name):
        if name == "lane_operands":
            raise AttributeError(name)
        return getattr(self.op, name)

    def __call__(self, R):
        return self.op(R)


def _ladder_steps(iters, maxiter, widths):
    """What the ladder steps for lanes of these counts, a stage: stage j
    runs until the lanes still active fit the next width."""
    active = lambda k: int(np.count_nonzero(iters > k))  # noqa: E731
    stepped, k = [], 0
    for j, w in enumerate(widths):
        fits = widths[j + 1] if j + 1 < len(widths) else 0
        k0 = k
        while k < maxiter and active(k) > fits:
            k += 1
        stepped.append(w * (k - k0))
    return stepped


@pytest.fixture
def untapped(live, monkeypatch):
    """Telemetry on without the CPU's per-step tap, as on the chip: a tapped
    call runs the one loop."""
    monkeypatch.setattr(linalg, "_iter_tapping", lambda: False)


def test_the_ladders_widths():
    floor = krylov._LADDER_FLOOR
    assert floor % 128 == 0
    assert krylov._ladder(8 * floor) == (8 * floor, 4 * floor, 2 * floor, floor)
    assert krylov._ladder(2 * floor) == (2 * floor, floor)
    # too narrow for a second stage: its half, in whole tiles, is under the floor
    assert krylov._ladder(2 * floor - 256) == (2 * floor - 256,)
    assert krylov._ladder(2 * floor - 255) == (2 * floor - 255, floor)
    assert krylov._ladder(64) == (64,) and krylov._ladder(1) == (1,)
    # a width that does not halve evenly rounds up to whole tiles of lanes
    assert krylov._ladder(4 * floor + 2) == (4 * floor + 2, 2 * floor + 128, floor + 128)
    assert all(w % 128 == 0 for w in krylov._ladder(5 * floor + 77)[1:])


def test_the_ladder_matches_the_one_loop(untapped):
    pattern, values, b = _wide_lanes()
    op, M = _declared(pattern, values)
    assert op.lane_operands == (True,) and M.lane_operands == (True,)
    widths = krylov._ladder(LADDER_B)
    assert len(widths) == 3
    s0 = linalg.HOST_SYNCS
    X, info = linalg.batched_bicgstab(op, b, x0=b, M=M, **LADDER_KW)
    assert linalg.HOST_SYNCS == s0 + 1  # the stages' trips ride in the one fetch
    X1, info1 = linalg.batched_bicgstab(_Mute(op), b, x0=b, M=_Mute(M), **LADDER_KW)
    iters = np.asarray(info.iters)
    # every lane's count, verdict and place
    assert np.array_equal(iters, info1.iters)
    assert np.array_equal(info.converged, info1.converged)
    easy, stiff = iters[0::2], iters[1::2]
    assert easy.max() < stiff.min() and stiff.max() == LADDER_KW["maxiter"]
    lost = ~np.asarray(info.converged)
    assert 0 < lost.sum() < 400 and (iters[lost] == LADDER_KW["maxiter"]).all()
    assert _ulps(X, X1) <= ULPS
    assert np.asarray(info.resid2) == pytest.approx(np.asarray(info1.resid2),
                                                    rel=1e-3)
    ladder, one = [e for e in telemetry.events("span")
                   if e["name"] == "batched_bicgstab.solve"]
    stepped = _ladder_steps(iters, LADDER_KW["maxiter"], widths)
    # three stages ran, and a compaction met an active count that is no
    # width's (slots past it were filled)
    assert all(stepped) and ladder["stages"] == 3
    k1 = stepped[0] // widths[0] + stepped[1] // widths[1]
    assert 0 < np.count_nonzero(iters > k1) < widths[2]
    assert ladder["lane_steps"] == sum(stepped)
    assert ladder["frozen_lane_pct"] == pytest.approx(
        100.0 * (1.0 - iters.sum() / sum(stepped)), abs=1e-3)
    assert one["stages"] == 1
    assert one["lane_steps"] == LADDER_B * iters.max()
    assert one["frozen_lane_pct"] == pytest.approx(
        100.0 * (1.0 - iters.sum() / (LADDER_B * iters.max())), abs=1e-3)
    assert ladder["frozen_lane_pct"] < one["frozen_lane_pct"] - 20
    for k in ("iters_max", "iters_sum", "converged", "fetches", "B"):
        assert ladder[k] == one[k]
    first, second = telemetry.events("batch.solve")
    for k in ("stages", "lane_steps", "frozen_lane_pct", "iters_sum"):
        assert first[k] == ladder[k] and second[k] == one[k]


def test_a_batch_of_like_lanes_runs_one_stage(untapped):
    pattern, values, b = _wide_lanes(alike=True)
    op, M = _declared(pattern, values)
    X, info = linalg.batched_bicgstab(op, b, M=M, **LADDER_KW)
    iters = np.asarray(info.iters)
    assert iters.min() == iters.max() > 0 and np.asarray(info.converged).all()
    (span,) = [e for e in telemetry.events("span")
               if e["name"] == "batched_bicgstab.solve"]
    assert span["stages"] == 1 and span["frozen_lane_pct"] == 0.0
    assert span["lane_steps"] == LADDER_B * iters.max() == span["iters_sum"]
    X1, info1 = linalg.batched_bicgstab(_Mute(op), b, M=_Mute(M), **LADDER_KW)
    assert np.array_equal(iters, info1.iters) and _ulps(X, X1) <= ULPS


def test_the_eager_loops_event_counts_one_stage(live):
    pattern, values, b = _lanes()
    op, M = _declared(pattern, values)
    mv, Mc = _closures(op, M)
    _X, info = linalg.batched_bicgstab(mv, b, M=Mc, **KW)
    (solve,) = telemetry.events("batch.solve")
    iters = np.asarray(info.iters)
    assert solve["stages"] == 1
    assert solve["lane_steps"] == b.shape[0] * iters.max()


def _parent_loop(matvec, b, X0, tol, maxiter, conv_test_iters,
                 Mvec=None, lane_reduce=None):
    """``krylov._bicgstab_loop`` of PR 55 (commit 802d6db), verbatim."""
    _bdot, _make_lanes_tap = krylov._bdot, krylov._make_lanes_tap
    tol2 = tol.astype(jnp.real(b).dtype) ** 2
    B = b.shape[0]
    cti = max(int(conv_test_iters), 1)
    any_active = jnp.any if lane_reduce is None else lane_reduce
    # sharded loops: no per-iteration host taps (see _cg_loop)
    tap = None if lane_reduce is not None else _make_lanes_tap("bicgstab")
    X = X0
    R = b - matvec(X)
    Rt = R
    Z = jnp.zeros_like(b)
    one = jnp.ones((B,), dtype=b.dtype)
    zero = jnp.zeros((B,), dtype=b.dtype)

    def body(st):
        X, R, P, V, rho, alpha, omega, active, iters, k = st
        rho_new = _bdot(Rt, R)
        beta = (rho_new / jnp.where(rho == 0, 1, rho)) * (
            alpha / jnp.where(omega == 0, 1, omega)
        )
        Pn = jnp.where(
            k == 0, R, R + beta[:, None] * (P - omega[:, None] * V)
        )
        Ph = Pn if Mvec is None else Mvec(Pn)
        Vn = matvec(Ph)
        rv = _bdot(Rt, Vn)
        alpha_n = rho_new / jnp.where(rv == 0, 1, rv)
        S = R - alpha_n[:, None] * Vn
        Sh = S if Mvec is None else Mvec(S)
        T = matvec(Sh)
        tt = _bdot(T, T)
        omega_n = _bdot(T, S) / jnp.where(tt == 0, 1, tt)
        am = active[:, None]
        X = jnp.where(
            am, X + alpha_n[:, None] * Ph + omega_n[:, None] * Sh, X
        )
        R = jnp.where(am, S - omega_n[:, None] * T, R)
        P = jnp.where(am, Pn, P)
        V = jnp.where(am, Vn, V)
        rho = jnp.where(active, rho_new, rho)
        alpha = jnp.where(active, alpha_n, alpha)
        omega = jnp.where(active, omega_n, omega)
        iters = iters + active.astype(jnp.int32)
        k = k + 1
        rn2 = jnp.real(_bdot(R, R))
        if tap is not None:
            jax.debug.callback(tap, k, rn2, tol2)
        tested = (k % cti == 0) | (k == maxiter - 1)
        active = active & ~(tested & (rn2 < tol2))
        return X, R, P, V, rho, alpha, omega, active, iters, k

    def cond(st):
        active, k = st[7], st[9]
        return (k < maxiter) & any_active(active)

    st = (X, R, Z, Z, zero, one, one,
          jnp.ones((B,), bool), jnp.zeros((B,), jnp.int32),
          jnp.zeros((), jnp.int32))
    out = jax.lax.while_loop(cond, body, st)
    X, R, active, iters = out[0], out[1], out[7], out[8]
    return X, iters, jnp.real(_bdot(R, R)), ~active


def _parent_lanes(a_operands, m_operands, b, x0, tol, maxiter, *, a_apply,
                  m_apply, conv_test_iters, tapped):
    """``krylov._bicgstab_lanes`` of PR 55, verbatim but for the counter."""

    def matvec(X):
        with jax.named_scope("batch.spmv"):
            return a_apply(a_operands, X)

    def precond(R):
        with jax.named_scope("batch.precond"):
            return m_apply(m_operands, R)

    X, iters, resid2, conv = _parent_loop(
        matvec, b, x0, tol, maxiter, conv_test_iters,
        None if m_apply is None else precond)
    return X, jnp.stack([
        iters, jax.lax.bitcast_convert_type(resid2, jnp.int32),
        conv.astype(jnp.int32)])


_parent_lanes.__name__ = _parent_lanes.__qualname__ = "batched_bicgstab"
_parent_program = jax.jit(
    _parent_lanes,
    static_argnames=("a_apply", "m_apply", "conv_test_iters", "tapped"))


def _narrow(monkeypatch):
    pattern, values, b = _lanes()
    return _declared(pattern, values), b


def _tapped(monkeypatch):
    pattern, values, b = _wide_lanes(B=2 * krylov._LADDER_FLOOR)
    monkeypatch.setattr(linalg, "_iter_tapping", lambda: True)
    return _declared(pattern, values), b


def _undeclared_m(monkeypatch):
    pattern, values, b = _wide_lanes(B=2 * krylov._LADDER_FLOOR)
    op, M = _declared(pattern, values)
    return (op, _Mute(M)), b


def _undeclared_a(monkeypatch):
    pattern, values, b = _wide_lanes(B=2 * krylov._LADDER_FLOOR)
    op, M = _declared(pattern, values)
    return (_Mute(op), None), b


@pytest.mark.parametrize("case", [_narrow, _tapped, _undeclared_m, _undeclared_a],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_one_loops_program_is_the_parents_text(case, monkeypatch):
    """A batch too narrow for a second stage, a tapped call and a pair that
    does not say which operands hold lanes: PR 55's program, text for text;
    the same pair, wide, untapped and declared, is another."""
    (A, M), b = case(monkeypatch)
    _mv, b, X0, tol, maxiter, _B, _n = krylov._prep(A, b, None, 1e-5, 40)
    args, static = krylov._lanes_call(A, M, b, X0, tol, maxiter, 1)
    assert len(krylov._stage_widths(
        b.shape[0], static["lane_operands"], static["tapped"])) == 1
    mine = krylov._bicgstab_program.lower(*args, **static).as_text()
    static.pop("lane_operands")
    assert mine == _parent_program.lower(*args, **static).as_text()
    assert "while" in mine and "gather" not in mine


def test_the_ladders_program_is_another_text(monkeypatch):
    (A, M), b = _tapped(monkeypatch)
    monkeypatch.setattr(linalg, "_iter_tapping", lambda: False)
    _mv, b, X0, tol, maxiter, _B, _n = krylov._prep(A, b, None, 1e-5, 40)
    args, static = krylov._lanes_call(A, M, b, X0, tol, maxiter, 1)
    assert static["lane_operands"] == ((True,), (True,))
    assert krylov._stage_widths(b.shape[0], static["lane_operands"], False) == (
        2 * krylov._LADDER_FLOOR, krylov._LADDER_FLOOR)
    text = krylov._bicgstab_program.lower(*args, **static).as_text(debug_info=True)
    # a loop a stage, the second with its compaction under one conditional
    assert text.count("stablehlo.while") == 2 and text.count("stablehlo.case") == 1
    assert "batch.compact" in text and "gather" in text and "scatter" in text
    B = b.shape[0]
    assert f"tensor<4x{B}xi32>" in text  # the trips ride with the lanes' counts


@pytest.mark.parametrize("jacobi", [False, True], ids=["no_precond", "jacobi"])
def test_the_sessions_bucket_traces_the_parents_loop(jacobi, monkeypatch):
    """``_bicgstab_loop`` took its start and its step apart for the ladder:
    the session's bucket program (64 lanes: under any floor) is the jaxpr it
    was."""
    from sparse_tpu.batch import SolveSession

    pattern, values, b = _lanes(B=64, dtype=np.float64)
    kw = dict(precond="jacobi") if jacobi else {}
    ses = SolveSession("bicgstab", batch_max=64, warm_start=False, **kw)
    args = (np.asarray(values), np.asarray(b), np.zeros_like(np.asarray(b)),
            np.full((64,), 1e-8), 100)

    def jaxpr():
        return str(jax.make_jaxpr(
            ses._build_program(pattern, 64, np.dtype(np.float64), **kw))(*args))

    mine = jaxpr()
    monkeypatch.setattr(krylov, "_bicgstab_loop", _parent_loop)
    assert mine == jaxpr()
    assert "while" in mine


def test_the_loop_is_the_parents_jaxpr_for_its_other_callers():
    """The eager call, the fleet's sharded loop (a ``lane_reduce``) and IR's
    inner sweeps (another dtype) call ``_bicgstab_loop`` directly."""
    pattern, values, b = _lanes(B=8)
    op, M = _declared(pattern, values)
    tol = jnp.full((8,), 1e-5, jnp.float32)

    def traced(loop, **kw):
        return str(jax.make_jaxpr(
            lambda b, x0, tol: loop(op.matvec, b, x0, tol, 40, 5, **kw))(b, b, tol))

    for kw in ({}, {"Mvec": M}, {"lane_reduce": lambda a: jnp.any(a) | False}):
        assert traced(krylov._bicgstab_loop, **kw) == traced(_parent_loop, **kw)


# -- the metric that reads the ladder's cost -------------------------------------
def test_the_compactions_share_resolves_and_reads_zero_without_the_scope():
    """``batched_compact_pct``: a data file over the benchmark's own
    ``op_scope_share``. On a trace of a program whose text carries the scope
    it is the scope's share of the program's device time; on one without it
    (the parent's program: one loop) it reads 0, and with no text nothing."""
    import importlib.util
    import json
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "layer_metrics", "batched_compact_pct.json")) as f:
        spec = json.load(f)
    assert spec["reducer"] == "op_scope_share"
    assert spec["params"] == {"program": "jit_batched_bicgstab",
                              "scope": "/batch\\.compact/"}
    with open(os.path.join(os.path.dirname(bench), "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert per_layer[-1] == {
        "name": "batched_compact_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "solver", "moves": "solve_s",
        "workloads": ["xgc_batched_bicgstab_1chip"]}
    sys.path.insert(0, bench)  # the reducer imports its neighbour `xplane`
    try:
        mod = importlib.util.spec_from_file_location(
            "_reducer", os.path.join(bench, "reducers", spec["reducer"] + ".py"))
        reducer = importlib.util.module_from_spec(mod)
        mod.loader.exec_module(reducer)
    finally:
        sys.path.remove(bench)

    def run(text):
        prog = "jit_batched_bicgstab(123)"
        ops = {(prog, "fusion.1", "fusion", "kLoop"): (10, 0.75),
               (prog, "fusion.2", "fusion", "kCustom"): (2, 0.125),
               (prog, "copy.3", "copy", ""): (2, 0.125),
               ("jit_convert_element_type(7)", "copy.3", "copy", ""): (2, 0.5)}
        trace = {"devices": {0: {"programs": {prog: (2, 1.0)}, "ops": ops}}}
        events = {} if text is None else {
            "program.hlo": [{"program": "jit_batched_bicgstab", "text": text}]}
        return {"trace": trace, "events": events}

    loop = '  %fusion.1 = f32[8]{0} fusion(), metadata={op_name="jit(batched_bicgstab)/while/body/bucket.dots/reduce_sum"}\n'
    ladder = loop + (
        '  %fusion.2 = f32[8]{0} fusion(), metadata={op_name="jit(batched_bicgstab)/cond/branch_1_fun/batch.compact/gather"}\n'
        '  %copy.3 = f32[8]{0} copy(), metadata={op_name="jit(batched_bicgstab)/cond/branch_1_fun/batch.compact/gather"}\n')
    assert reducer.read(run(ladder), spec["params"]) == pytest.approx(25.0)
    assert reducer.read(run(loop), spec["params"]) == 0.0
    assert reducer.read(run(None), spec["params"]) is None
    # the scope is on the text of the program the call runs
    pattern, values, b = _wide_lanes(B=2 * krylov._LADDER_FLOOR)
    op, M = _declared(pattern, values)
    text = linalg._batched_bicgstab_compiled(op, b, M, 1).as_text()
    names = reducer.op_names(text)
    assert any("/batch.compact/" in n for n in names.values())
    text = linalg._batched_bicgstab_compiled(_Mute(op), b, _Mute(M), 1).as_text()
    assert not any("batch.compact" in n for n in reducer.op_names(text).values())
