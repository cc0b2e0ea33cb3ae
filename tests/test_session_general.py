"""A pattern that is no stencil, served (PR 35): FEM heat steps on the
unstructured mesh of the benchmark's own generator
(``benchmark/operators/fem_heat_step.py``, through ``tests/utils/spd.py``)
through ``SolveSession("cg")``, as the cell ``fem_heat_served_closed`` sends
them: each client's own values on one pattern, tolerance relative to the
right-hand side, the last answer as the starting iterate.

Nothing sets the bucket program's product. The session takes it from the
pattern (``batch/operator.py`` ``pattern_matvec``): the SELL slabs' gathers
for this mesh (its loop in the SELL pack's own row order since PR 36: three
whole-vector row gathers a dispatch), planes for the 5-point grid beside it.
The one-time pattern pack is the span ``session.pattern_pack``, once a pattern.
"""

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

from sparse_tpu import plan_cache, telemetry
from sparse_tpu.batch import SolveSession
from sparse_tpu.config import settings

from .utils.spd import fem_heat_data, operator_module

SIDE = 24  # 576 rows, 3,842 entries, rows of 3 to 9


@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield
    telemetry.configure(None)
    telemetry.reset()


def _pack_spans():
    return [e for e in telemetry.events("span")
            if e["name"] == "session.pattern_pack"]


def _step(ses, pattern, d, state, batch_max):
    """Every client's next step: (tickets, right-hand sides). Whole buckets
    are flushed without waiting, as the benchmark's adaptor does it."""
    tickets, rhs = [], []
    for k in range(d["clients"]):
        b = np.float32(d["carry"]) * state[k] + d["source"][k]
        tol = d["rel_tol"] * float(np.linalg.norm(b.astype(np.float64)))
        tickets.append(ses.submit(d["values"][k], b, tol=tol, x0=state[k],
                                  pattern=pattern))
        rhs.append(b)
        if ses.pending >= batch_max:
            ses.flush(wait=False)
    ses.flush()
    return tickets, rhs


@pytest.mark.parametrize("batch_max", [4, 8])
@pytest.mark.parametrize("seed", [5, 2147483659])
def test_fem_heat_steps_through_the_session(tel, seed, batch_max):
    d = fem_heat_data(SIDE, seed, clients=8)
    op = operator_module("fem_heat_step")
    P, n = d["pattern"], d["rows"]
    assert len(np.unique(P.indices - np.repeat(np.arange(n), np.diff(P.indptr)))) > n // 2
    ses = SolveSession("cg", batch_max=batch_max, warm_start=False)
    pattern = ses.pattern_of(P)
    state = list(d["initial"])
    # the guarantee for x: kappa (Gershgorin, from the generated matrices)
    # times the residual's 2 x asked
    assert d["kappa_bound"] <= 19.0
    x_limit = d["kappa_bound"] * 2.0 * d["rel_tol"]
    for step in (1, 2):  # the second step starts from the first one's answer
        tickets, rhs = _step(ses, pattern, d, state, batch_max)
        answers = [np.asarray(t.result()[0]) for t in tickets]
        x_ref = op.reference_cg(d, d["coef"], np.stack(rhs))
        for k, (x, b) in enumerate(zip(answers, rhs)):
            assert x.dtype == np.float32
            A = sp.csr_matrix((d["values"][k].astype(np.float64), P.indices,
                               P.indptr), shape=P.shape)
            exact = spla.spsolve(A.tocsc(), b.astype(np.float64))
            scale = np.linalg.norm(exact)
            # the cell's two limits: the true residual within 2 x asked,
            # hence x within kappa x that of the solution
            assert op.true_relres(d, x, d["values"][k], b) <= 2.0 * d["rel_tol"]
            assert np.linalg.norm(x - exact) <= x_limit * scale
            # the plain reference runs to the float32 floor, a few 1e-7 of
            # the solution at this condition; the program's answer lies
            # within the same limit of it as of the solution
            assert np.linalg.norm(x_ref[k] - exact) <= 5e-6 * scale
            assert np.linalg.norm(x - x_ref[k]) <= x_limit * np.linalg.norm(x_ref[k])
        state = answers
        # one pack a pattern: the first dispatch's, none after it
        assert len(_pack_spans()) == 1
    dispatches = telemetry.events("batch.dispatch")
    assert len(dispatches) == 2 * d["clients"] // batch_max
    assert {e["matvec"] for e in dispatches} == {"sell"}
    assert {e["bucket"] for e in dispatches} == {batch_max}
    # every lane stops at the first convergence test, in both steps
    assert {e["iters_max"] for e in dispatches} == {25}
    # the loop runs in the pack's row order (PR 36): rhs, x0 and X are the
    # only whole-vector row gathers of a dispatch, not one a product
    assert all(e["row_gathers"] <= 3 for e in dispatches)
    assert plan_cache.lookup(pattern, "sell.pattern").form == "sell"
    assert not plan_cache.lookup(pattern, "planes.pattern")


def test_pattern_pack_span_carries_the_slabs_and_their_padding(tel):
    d = fem_heat_data(SIDE, 11, clients=4)
    ses = SolveSession("cg", batch_max=4, warm_start=False)
    pattern = ses.pattern_of(d["pattern"])
    state = list(d["initial"])
    for _ in range(2):
        tickets, _ = _step(ses, pattern, d, state, 4)
        state = [np.asarray(t.result()[0]) for t in tickets]
    (ev,) = _pack_spans()
    plan = plan_cache.lookup(pattern, "sell.pattern").plan
    assert ev["form"] == "sell" and ev["dur_s"] > 0
    assert (ev["rows"], ev["nnz"]) == (d["rows"], d["nnz"])
    assert ev["slabs"] == len(plan.slab_meta) >= 2
    assert ev["slots"] == sum(K * R for K, R, _ in plan.slab_meta)
    assert d["nnz"] <= ev["slots"] <= 2 * d["nnz"]  # what the gathers pay
    # the zero rows the slabs were given (PR 39), as the dispatches count them
    assert ev["pad_rows"] == plan.pad_rows
    order = plan_cache.lookup(pattern, "sell.pattern").own_order()
    assert {e["pad_rows"] for e in telemetry.events("batch.dispatch")} == {
        order.pad_rows}
    assert telemetry.summary()["spans"]["session.pattern_pack"]["n"] == 1
    # another session on the same pattern object packs nothing
    again = SolveSession("cg", batch_max=4, warm_start=False)
    tickets, _ = _step(again, again.pattern_of(pattern), d, state, 4)
    assert all(t.result()[0].shape == (d["rows"],) for t in tickets)
    assert len(_pack_spans()) == 1


PADDED_SIDE = 72  # 5184 rows; the 7-slot slab's 1856 rows are stored as 2304


def _one_step(d, batch_max):
    """(answers, rhs, dispatch events, pack) of every client's first step
    through a fresh session and a fresh pattern object."""
    telemetry.reset()
    ses = SolveSession("cg", batch_max=batch_max, warm_start=False)
    pattern = ses.pattern_of(sp.csr_matrix(d["pattern"]))
    tickets, rhs = _step(ses, pattern, d, list(d["initial"]), batch_max)
    answers = np.stack([np.asarray(t.result()[0]) for t in tickets])
    return (answers, np.stack(rhs), telemetry.events("batch.dispatch"),
            plan_cache.lookup(pattern, "sell.pattern"))


@pytest.mark.parametrize("batch_max", [8, 64])
def test_a_slab_moved_into_the_wide_band_steps_as_the_plain_pack(
        tel, monkeypatch, batch_max):
    """A pattern whose middle slabs get pad rows from `slab_rows` (PR 39:
    into the gather's wide band; PR 41: onto a multiple of 256 rows in it),
    through the session's `cg` program in the pack's own order: the
    answers are scipy's, every lane takes the iterations it takes on the
    pack rounded to ROW_ALIGN alone, and the pad rows of the loop's X are
    zero when it ends (those of r and p: tests/test_bucket_planes.py,
    `test_pad_rows_are_zero_and_stay_zero[slab_pad]`)."""
    from sparse_tpu.batch import krylov
    from sparse_tpu.kernels import sell_spmv

    d = fem_heat_data(PADDED_SIDE, 9, clients=batch_max)
    P = d["pattern"]
    answers, rhs, events, pack = _one_step(d, batch_max)
    with monkeypatch.context() as m:
        m.setattr(sell_spmv, "slab_rows",
                  lambda n: -(-n // sell_spmv.ROW_ALIGN) * sell_spmv.ROW_ALIGN)
        plain_answers, _, plain_events, plain_pack = _one_step(d, batch_max)
    assert (7, 1856, 0) in plain_pack.plan.slab_meta
    assert (7, 2304, 448) in pack.plan.slab_meta
    order = pack.own_order()
    assert (pack.plan.pad_rows, order.pad_rows) == (528, 704)
    assert order.rows.shape[0] == 5888  # 5 x 1024 + 768
    assert all(r % 256 == 0 for _k, r, _p in pack.plan.slab_meta if r > 1024)
    (ev,), (plain_ev,) = events, plain_events
    assert (ev["bucket"], ev["matvec"], ev["row_gathers"]) == (batch_max, "sell", 3)
    assert ev["pad_rows"] == order.pad_rows
    assert plain_ev["pad_rows"] == plain_pack.own_order().pad_rows < 200
    assert (ev["iters_max"], ev["iters_mean"]) == (
        plain_ev["iters_max"], plain_ev["iters_mean"]) == (25, 25.0)
    x_limit = d["kappa_bound"] * 2.0 * d["rel_tol"]
    for k in range(0, batch_max, max(batch_max // 8, 1)):
        A = sp.csr_matrix((d["values"][k].astype(np.float64), P.indices,
                           P.indptr), shape=P.shape)
        exact = spla.spsolve(A.tocsc(), rhs[k].astype(np.float64))
        assert np.linalg.norm(answers[k] - exact) <= x_limit * np.linalg.norm(exact)
    # float32 sums over a longer vector of the same numbers and zeros
    np.testing.assert_allclose(answers, plain_answers, rtol=0, atol=2e-5)
    # the loop itself, in the pack's order: nothing enters a pad row
    pad = np.asarray(order.rows) < 0
    assert pad.sum() == order.pad_rows
    vals = pack.pack_values(d["values"])
    tols = d["rel_tol"] * np.linalg.norm(rhs, axis=1)
    X, iters, _resid2, conv = krylov._cg_loop(
        lambda V: order.product(vals, V), order.enter(rhs),
        order.enter(np.stack(d["initial"])), tols.astype(np.float32), 200,
        25)  # the session's default `conv_test_iters`
    assert set(np.asarray(iters)) == {25}
    assert np.asarray(conv).all()
    assert not np.asarray(X)[:, pad].any()
    np.testing.assert_allclose(np.asarray(order.leave(X)), answers, rtol=0,
                               atol=2e-6)


def test_five_point_grid_beside_it_gets_planes(tel):
    g = 12
    T = sp.diags([-1.0, -1.0], [-1, 1], shape=(g, g))
    L = (sp.kron(sp.identity(g), T) + sp.kron(T, sp.identity(g))
         + 5.5 * sp.identity(g * g)).tocsr().astype(np.float32)
    L.sort_indices()
    rng = np.random.default_rng(3)
    ses = SolveSession("cg", batch_max=4, warm_start=False)
    pattern = ses.pattern_of(L)
    rhs = rng.standard_normal((4, g * g)).astype(np.float32)
    tickets = [ses.submit(L.data, b, tol=1e-5 * float(np.linalg.norm(b)),
                          pattern=pattern) for b in rhs]
    ses.flush()
    for t, b in zip(tickets, rhs):
        x = np.asarray(t.result()[0])
        assert np.linalg.norm(L @ x - b) <= 2e-5 * np.linalg.norm(b)
    assert {(e["matvec"], e["row_gathers"])
            for e in telemetry.events("batch.dispatch")} == {("planes", 0)}
    (ev,) = _pack_spans()
    assert ev["form"] == "planes" and ev["diagonals"] == 5
    assert (ev["rows"], ev["nnz"]) == (g * g, L.nnz)
    assert plan_cache.lookup(pattern, "sell.pattern") is None


def test_off_the_pack_is_no_span(monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", False)
    d = fem_heat_data(SIDE, 12, clients=2)
    ses = SolveSession("cg", batch_max=2, warm_start=False)
    pattern = ses.pattern_of(d["pattern"])
    tickets, _ = _step(ses, pattern, d, list(d["initial"]), 2)
    assert all(t.done or t.result() is not None for t in tickets)
    assert plan_cache.lookup(pattern, "sell.pattern").form == "sell"
    assert "session.pattern_pack" not in telemetry.summary().get("spans", {})


@pytest.mark.parametrize("seed", [7, 3200000103])
def test_spd_data_states_its_pattern_and_gives_the_matrix_it_gave(seed):
    """``tests/utils/spd.py`` passes ``pattern_seed=seed``: the generator then
    gives what it gave when it drew everything from the seed."""
    from .utils.spd import spd_data

    gen = operator_module()
    mine = spd_data(24, seed)
    bare = gen.make({"side": 24, "iterations": 50}, seed)  # the seed's pattern
    for key in ("indptr", "indices", "data", "b"):
        assert np.array_equal(mine[key], bare[key])
    other = gen.make({"side": 24, "iterations": 50, "pattern_seed": seed},
                     seed + 1)
    assert np.array_equal(mine["indices"], other["indices"])
    assert not np.array_equal(mine["data"], other["data"])
