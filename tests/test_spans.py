"""The span primitive and its sites (PR 25).

One primitive, ``telemetry.span``: a live span is a
``jax.profiler.TraceAnnotation`` (so it lies on the device trace's own
axis), a bounded aggregate, and an event that carries its recorded start.
Its sites: the session's seven phases of a dispatch, the fused CG's solve
with its per-chunk dispatch and ``rho`` fetch, and the library entry's
DIA plane build. With telemetry off every site gets the shared no-op.
"""

import glob
import os
import time

import numpy as np
import pytest
import scipy.sparse as sp

import jax

import sparse_tpu
from sparse_tpu import linalg, telemetry
from sparse_tpu.batch import SolveSession
from sparse_tpu.config import settings
from sparse_tpu.telemetry import _recorder
from sparse_tpu.telemetry._spans import _NULL, Span

SESSION_SPANS = (
    "session.pack", "session.upload", "session.plan", "session.call",
    "session.device_wait", "session.readback", "session.scatter",
)


@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield tmp_path / "records.jsonl"
    telemetry.configure(None)
    telemetry.reset()


@pytest.fixture
def off(monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", False)
    yield
    telemetry.reset()


def _tridiag(n):
    e = np.ones(n)
    return sp.diags([-e[:-1], 2.5 * e, -e[:-1]], [-1, 0, 1]).tocsr()


def _two_buckets(ses, n=300, lanes=4, buckets=2, seed=0):
    S = _tridiag(n)
    pat = ses.pattern_of(sparse_tpu.csr_array(S))
    rng = np.random.default_rng(seed)
    for _ in range(buckets):
        ts = [
            ses.submit(S.data * (1 + 0.1 * i), rng.standard_normal(n),
                       tol=1e-8, pattern=pat)
            for i in range(lanes)
        ]
        ses.flush()
        for t in ts:
            assert t.result()[0].shape == (n,)


def _pde(g=24):
    n = g * g
    a = np.full(n - 1, -1.0, np.float32)
    a[g - 1::g] = 0.0
    far = np.full(n - g, -1.0, np.float32)
    c = np.full(n, 4.0, np.float32)
    A = sparse_tpu.diags([far, a, c, a, far], [-g, -1, 0, 1, g],
                         shape=(n, n))
    return A, np.ones(n, np.float32)


# -- the primitive -----------------------------------------------------------
def test_one_clock_for_spans_tickets_and_deadlines(tel):
    assert telemetry.clock is time.monotonic
    before = telemetry.clock()
    with telemetry.span("t.clock") as sp_:
        pass
    ses = SolveSession("cg", warm_start=False)
    S = _tridiag(16)
    t = ses.submit(S, np.ones(16), tol=1e-8)
    after = telemetry.clock()
    assert before <= sp_.t0 <= sp_.t1 <= t.t_submit <= after
    ses.flush()
    assert before <= t.t_submit <= t.t_done


def test_span_holds_its_start_and_duration_after_exit(tel):
    with telemetry.span("t.held", n=3) as sp_:
        assert isinstance(sp_, Span)
        assert sp_.t0 is not None and sp_.dur_s is None and sp_.t1 is None
        time.sleep(0.01)
    assert sp_.dur_s >= 0.01
    assert sp_.t1 == pytest.approx(sp_.t0 + sp_.dur_s)
    assert _NULL.t0 is None and _NULL.dur_s is None and _NULL.t1 is None


def test_span_event_carries_its_recorded_start_on_the_tm_axis(tel):
    with telemetry.span("t.start", n=3) as sp_:
        time.sleep(0.01)
    (ev,) = telemetry.events("span")
    base = telemetry.session_info()["mono"]
    assert ev["t0"] == pytest.approx(sp_.t0 - base, abs=2e-6)
    assert ev["dur_s"] == pytest.approx(sp_.dur_s, abs=1e-8)
    # recorded at exit: the event's own stamp lies at or after the end
    assert ev["t0"] + ev["dur_s"] <= ev["tm"] + 2e-6
    assert ev["n"] == 3 and not telemetry.schema.validate(ev)


def test_trace_export_places_a_span_at_its_recorded_start(tel):
    with telemetry.span("t.outer"):
        time.sleep(0.005)
        # the sync at exit belongs to the span, the record after it does not
    (ev,) = telemetry.events("span")
    made = {"kind": "span", "ts": 50.0, "tm": 7.0, "name": "old", "dur_s": 1.0}
    tr = telemetry.to_chrome_trace([ev, made])["traceEvents"]
    (sl,) = [e for e in tr if e["ph"] == "X"]
    assert sl["name"] == "t.outer"
    assert sl["ts"] == pytest.approx(
        ev["ts"] * 1e6 - (ev["tm"] - ev["t0"]) * 1e6)
    assert sl["dur"] == pytest.approx(ev["dur_s"] * 1e6)
    # no recorded start, no inferred one: a mark like any other event
    (mark,) = [e for e in tr if e["ph"] == "i"]
    assert mark["name"] == "span" and mark["ts"] == 50.0 * 1e6


def test_span_aggregates_stay_bounded_with_exact_count_and_total(
        tel, monkeypatch):
    monkeypatch.setattr(settings, "telemetry_ring", 16)
    for i in range(100):
        telemetry.add_span("t.loop", 0.5 + i)
    n, total, mx, sample = _recorder.span_aggregates()["t.loop"]
    assert (n, total, mx) == (100, sum(0.5 + i for i in range(100)), 99.5)
    assert sample == [0.5 + i for i in range(84, 100)]
    assert len(_recorder._SPANS["t.loop"][3]) == 16
    s = telemetry.summary()["spans"]["t.loop"]
    assert s["n"] == 100 and s["total_s"] == pytest.approx(total)
    assert s["max_s"] == 99.5 and 84.5 <= s["p50_s"] <= 99.5


def test_span_events_are_not_stamped_with_the_ticket_scope(tel):
    with telemetry.ticket_scope("tk-x"):
        with telemetry.span("t.scoped"):
            telemetry.record("solver.iter", solver="cg", iter=1)
    (ev,) = telemetry.events("span")
    assert "tickets" not in ev
    assert telemetry.events("solver.iter")[0]["tickets"] == ["tk-x"]


# -- a span is a TraceAnnotation ---------------------------------------------
def _host_annotations(trace_dir, prefix):
    from jax.profiler import ProfileData

    (pb,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def test_session_spans_lie_on_the_profile_in_order(tel, tmp_path):
    ses = SolveSession("cg", inflight=1, warm_start=False)
    _two_buckets(ses, buckets=1, seed=1)  # compile outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _two_buckets(ses, buckets=2, seed=2)
    finally:
        jax.profiler.stop_trace()
    anns = _host_annotations(str(tmp_path / "trace"), "session.")
    # a bucket's four submits lie on the same line, before its seven
    assert [a[0] for a in anns] == (
        ["session.submit"] * 4 + list(SESSION_SPANS)) * 2
    for (_n0, s0, d0), (_n1, s1, _d1) in zip(anns, anns[1:]):
        assert s0 + d0 <= s1  # in order and not overlapping
    anns = [a for a in anns if a[0] != "session.submit"]  # emit=False
    # the same extents as the events hold, on the profile's own axis
    evs = [e for e in telemetry.events("span")
           if e["name"] in SESSION_SPANS][-14:]
    for (name, _s, d), ev in zip(anns, evs):
        assert name == ev["name"]
        assert d * 1e-9 >= ev["dur_s"]  # the annotation encloses the span
        assert d * 1e-9 == pytest.approx(ev["dur_s"], abs=2e-3)


# -- the session's sites -----------------------------------------------------
def test_seven_session_spans_sum_to_the_dispatch(tel):
    ses = SolveSession("cg", inflight=1, warm_start=False)
    _two_buckets(ses, n=20000, lanes=8, buckets=3)
    disp = {e["seq"]: e for e in telemetry.events("batch.dispatch")}
    assert sorted(disp) == [1, 2, 3]
    by_seq = {}
    for e in telemetry.events("span"):
        if e["name"] in SESSION_SPANS:  # not session.pattern_pack: once a pattern
            assert (e["bucket"], e["lanes"]) == (8, 8)
            by_seq.setdefault(e["seq"], {})[e["name"]] = e
    for seq, spans in by_seq.items():
        assert tuple(spans) == SESSION_SPANS  # recorded in this order
        # what holds on any machine, from the spans' own extents and the
        # event's stamp (how little lies outside the spans is
        # `unspanned_ms`'s to say, with a number): the seven do not
        # overlap, the first six end before `batch.dispatch` is recorded,
        # inside `session.scatter`, and so sum to less than `dispatch_ms`,
        # which runs from the launch's entry to that event
        seven = list(spans.values())
        for e0, e1 in zip(seven, seven[1:]):
            assert e0["t0"] + e0["dur_s"] <= e1["t0"] + 2e-6
        d, scatter = disp[seq], spans["session.scatter"]
        assert seven[5]["t0"] + seven[5]["dur_s"] <= d["tm"] + 2e-6
        assert scatter["t0"] - 2e-6 <= d["tm"]
        assert d["tm"] <= scatter["t0"] + scatter["dur_s"] + 2e-6
        assert 1e3 * sum(e["dur_s"] for e in seven[:6]) <= d["dispatch_ms"]
        # the dispatch's tag and the span's own extent, nothing computed
        # for the event's sake
        for e in spans.values():
            assert set(e) - {"kind", "ts", "tm", "pi", "pid", "name", "t0",
                             "dur_s"} == {"seq", "bucket", "lanes"}


def test_dispatch_and_phase_arithmetic_is_read_off_the_spans(tel):
    ses = SolveSession("cg", inflight=1, warm_start=False)
    S = _tridiag(5000)
    pat = ses.pattern_of(sparse_tpu.csr_array(S))
    ts = [ses.submit(S.data, np.ones(5000), tol=1e-8, pattern=pat)
          for _ in range(4)]
    ses.flush()
    (d,) = telemetry.events("batch.dispatch")
    sp_ = {e["name"]: e for e in telemetry.events("span")}
    call, wait = sp_["session.call"], sp_["session.device_wait"]
    plan = sp_["session.plan"]
    read = sp_["session.readback"]
    # solve_ms keeps its meaning: call start -> results ready
    assert d["solve_ms"] == pytest.approx(
        (wait["t0"] + wait["dur_s"] - call["t0"]) * 1e3, abs=0.01)
    ph = ts[0].phase_ms
    assert set(ph) == {"queue_ms", "pack_ms", "compile_ms", "solve_ms",
                       "readback_ms"}
    assert ph["solve_ms"] == pytest.approx(d["solve_ms"], abs=0.01)
    assert ph["readback_ms"] == pytest.approx(
        (read["t0"] + read["dur_s"] - wait["t0"] - wait["dur_s"]) * 1e3,
        abs=0.01)
    # queue and pack run from the submit to where session.plan begins
    base = telemetry.session_info()["mono"]
    assert ph["queue_ms"] + ph["pack_ms"] == pytest.approx(
        (plan["t0"] - (ts[0].t_submit - base)) * 1e3, abs=0.01)
    latency_ms = (ts[0].t_done - ts[0].t_submit) * 1e3
    assert sum(ph.values()) <= latency_ms * 1.05


def test_queue_ends_where_the_launch_begins(tel, monkeypatch):
    """``phase_ms``' boundaries are those from before the spans: the queue
    ends at ``_launch``'s entry, and ``pack_ms`` runs from there over the
    fleet and bucket decisions, the stack, the upload, and the policy
    decisions and the key, to where ``session.plan`` begins."""
    from sparse_tpu.batch import service

    ses = SolveSession("cg", inflight=1, warm_start=False)
    decide, snapshot = ses.fleet.decide, service.plan_cache.snapshot

    def slow_decide(*a, **kw):  # before session.pack
        time.sleep(0.02)
        return decide(*a, **kw)

    def slow_snapshot():  # between session.upload and session.plan
        time.sleep(0.02)
        return snapshot()

    monkeypatch.setattr(ses.fleet, "decide", slow_decide)
    monkeypatch.setattr(service.plan_cache, "snapshot", slow_snapshot)
    S = _tridiag(2000)
    pat = ses.pattern_of(sparse_tpu.csr_array(S))
    t = ses.submit(S.data, np.ones(2000), tol=1e-8, pattern=pat)
    ses.flush()
    (d,) = telemetry.events("batch.dispatch")
    sp_ = {e["name"]: e for e in telemetry.events("span")}
    pack, upload = sp_["session.pack"], sp_["session.upload"]
    ph = t.phase_ms
    submit = t.t_submit - telemetry.session_info()["mono"]
    in_spans_ms = (pack["dur_s"] + upload["dur_s"]) * 1e3
    assert ph["pack_ms"] >= 40.0 + in_spans_ms
    assert ph["queue_ms"] <= (pack["t0"] - submit) * 1e3 - 20.0
    assert d["queue_ms_max"] == pytest.approx(ph["queue_ms"], abs=0.01)
    assert ph["compile_ms"] <= sp_["session.plan"]["dur_s"] * 1e3


def test_sampled_dispatch_splits_at_the_call_spans_end(tel):
    ses = SolveSession("cg", inflight=1, warm_start=False, profile_every=1)
    _two_buckets(ses, buckets=1)
    (d,) = telemetry.events("batch.dispatch")
    call = next(e for e in telemetry.events("span")
                if e["name"] == "session.call")
    assert d["host_ms"] == pytest.approx(call["dur_s"] * 1e3, abs=0.01)
    assert d["host_ms"] + d["device_ms"] == pytest.approx(
        d["solve_ms"], abs=0.01)


# -- the solver's and the library entry's sites ------------------------------
def test_fused_cg_solve_is_one_span_event_with_its_chunks(tel, monkeypatch):
    monkeypatch.setattr(settings, "fused_cg", "force")
    A, b = _pde()
    A = A.tocsr()
    linalg.cg(A, b, maxiter=60)  # builds the layout, compiles
    n0 = len(telemetry.events("span"))
    agg0 = telemetry.summary()["spans"]
    _x, iters = linalg.cg(A, b, maxiter=60)
    evs = telemetry.events("span")[n0:]
    assert [e["name"] for e in evs] == ["cg.solve"]  # one event a call
    (ev,) = evs
    assert set(ev) >= {"path", "chunks", "iters", "packs", "dispatch_s", "fetch_s"}
    assert ev["path"] == "fused" and ev["iters"] == int(iters)
    first = next(e for e in telemetry.events("span") if e["name"] == "cg.solve")
    assert (first["packs"], ev["packs"]) == (1, 0)  # one pack an operator
    assert ev["chunks"] == len(telemetry.events("solver.iter")) // 2
    assert ev["chunks"] >= 2
    assert 0 < ev["dispatch_s"] and 0 < ev["fetch_s"]
    assert ev["dispatch_s"] + ev["fetch_s"] <= ev["dur_s"]
    # the inner spans are annotations and aggregates, not events
    agg = telemetry.summary()["spans"]
    for name in ("cg.chunk", "cg.rho_fetch"):
        assert agg[name]["n"] - agg0[name]["n"] == ev["chunks"]
    assert agg["cg.chunk"]["total_s"] - agg0["cg.chunk"]["total_s"] == \
        pytest.approx(ev["dispatch_s"], abs=1e-5)


def test_layout_span_fires_once_an_operator(tel, monkeypatch):
    monkeypatch.setattr(settings, "fused_cg", "force")
    D, b = _pde()
    A = D.tocsr()
    assert telemetry.events("span") == []
    linalg.cg(A, b, maxiter=30)
    linalg.cg(A, b, maxiter=30)  # cached: no second build
    A @ b
    evs = [e for e in telemetry.events("span")
           if e["name"] == "layout.dia_build"]
    assert len(evs) == 1
    assert telemetry.summary()["spans"]["layout.dia_build"]["n"] == 1
    # a matrix that is not banded: the detection is the span
    R = sparse_tpu.csr_array(sp.random(200, 200, density=0.2, random_state=0,
                                       format="csr"))
    R @ np.ones(200)
    R @ np.ones(200)
    assert telemetry.summary()["spans"]["layout.dia_build"]["n"] == 2


def _general(side=24, seed=5):
    from .utils.spd import spd_data

    d = spd_data(side, seed)
    n = d["rows"]
    A = sparse_tpu.csr_array((d["data"], d["indices"], d["indptr"]), shape=(n, n))
    return A, d["b"]


def test_general_cg_solve_is_one_span_event_with_its_fields(tel):
    """A matrix that is not banded: the compiled general program's solve is
    a ``cg.solve`` span too, with the fused path's fields."""
    A, b = _general()
    traces = telemetry._metrics.counter("cg.general.traces")
    linalg.cg(A, b, maxiter=60)  # builds the layout, compiles
    n0 = len(telemetry.events("span"))
    agg0 = telemetry.summary()["spans"]
    traced = traces.value
    _x, iters = linalg.cg(A, b, maxiter=60)
    (ev,) = telemetry.events("span")[n0:]  # one event a call
    assert ev["name"] == "cg.solve"
    assert (ev["path"], ev["layout"], ev["iters"]) == ("device", "ell", int(iters))
    assert 0 < ev["dispatch_s"] and 0 < ev["fetch_s"]
    assert ev["dispatch_s"] + ev["fetch_s"] <= ev["dur_s"]
    first = next(e for e in telemetry.events("span") if e["name"] == "cg.solve")
    # the first call's dispatch holds the trace and the compile, the second
    # one's neither: read off the program's trace counter, not off two host
    # timings (under six workers the second read longer than the first, and
    # a worker that ran these shapes before this test traces nothing at all)
    assert first["dispatch_s"] > 0 and traces.value == traced
    # the inner spans are annotations and aggregates, not events
    agg = telemetry.summary()["spans"]
    for name in ("cg.dispatch", "cg.iters_fetch"):
        assert agg[name]["n"] - agg0[name]["n"] == 1
    assert telemetry.events("solver.solve")[-1]["path"] == "device"


def test_general_layout_spans_fire_once_an_operator(tel, monkeypatch):
    A, b = _general()
    assert telemetry.events("span") == []
    linalg.cg(A, b, maxiter=30)
    linalg.cg(A, b, maxiter=30)  # cached: no second build
    A @ b
    names = [e["name"] for e in telemetry.events("span")]
    # the banded rule's decision lies inside the span that would build planes
    assert [n for n in names if n.startswith("layout.")] == [
        "layout.detect", "layout.dia_build", "layout.ell_build"]
    agg = telemetry.summary()["spans"]
    assert agg["layout.detect"]["total_s"] <= agg["layout.dia_build"]["total_s"]
    # a skewed row profile packs SELL slabs instead
    from .test_sell_spmv import powerlaw_csr

    R = sparse_tpu.csr_array(powerlaw_csr(100, seed=8).astype(np.float32))
    R @ np.ones(100, np.float32)
    R @ np.ones(100, np.float32)
    agg = telemetry.summary()["spans"]
    assert agg["layout.sell_build"]["n"] == 1 and agg["layout.ell_build"]["n"] == 1
    assert agg["layout.detect"]["n"] == 2


# -- the mesh: shard_csr's build and dist_cg's solve (PR 27) -------------------
def test_mesh_spans_carry_their_fields(tel):
    from sparse_tpu.parallel import dist_cg, get_mesh, shard_csr

    D0, b = _pde()
    Dd = shard_csr(D0.tocsr(), mesh=get_mesh(4))
    for _ in range(2):
        dist_cg(Dd, b, tol=0.0, maxiter=12)
    spans = [e for e in telemetry.events("span")]
    (build,) = [e for e in spans if e["name"] == "dist.shard_csr"]
    assert {k: build[k] for k in ("layout", "mode", "S", "rows", "nnz")} == {
        "layout": "dia", "mode": "halo", "S": 4, "rows": b.shape[0],
        "nnz": int(D0.tocsr().nnz)}
    assert (build["R"], build["HL"], build["HR"]) == (Dd.R, Dd.HL, Dd.HR)
    solves = [e for e in spans if e["name"] == "dist.cg.solve"]
    assert len(solves) == 2
    for e in solves:
        assert e["iters"] == 12 and e["layout"] == "dia" and e["S"] == 4
        assert 0 <= e["dispatch_s"] and 0 <= e["wait_s"]
        assert e["dispatch_s"] + e["wait_s"] <= e["dur_s"]
    # the first call's dispatch holds the trace and the compile
    assert solves[0]["dispatch_s"] > solves[1]["dispatch_s"]
    agg = telemetry.summary()["spans"]
    assert agg["dist.cg.dispatch"]["n"] == agg["dist.cg.wait"]["n"] == 2
    assert not [e for e in spans
                if e["name"] in ("dist.cg.dispatch", "dist.cg.wait")]


def test_off_the_mesh_records_no_span(off):
    from sparse_tpu.parallel import dist_cg, get_mesh, shard_csr

    D0, b = _pde()
    Dd = shard_csr(D0.tocsr(), mesh=get_mesh(4))
    _, iters, _ = dist_cg(Dd, b, tol=0.0, maxiter=12)
    assert iters == 12
    assert telemetry.events() == [] and telemetry.summary()["spans"] == {}


# -- telemetry off -----------------------------------------------------------
def test_off_every_site_gets_the_null_span_and_nothing_is_recorded(
        off, monkeypatch):
    monkeypatch.setattr(settings, "fused_cg", "force")
    got = []
    real = telemetry.span

    def spy(name, *a, **kw):
        s = real(name, *a, **kw)
        got.append((name, s))
        return s

    monkeypatch.setattr(telemetry, "span", spy)
    ses = SolveSession("cg", warm_start=False)
    _two_buckets(ses)
    D, b = _pde()
    A = D.tocsr()
    linalg.cg(A, b, maxiter=30)
    G, bg = _general()
    linalg.cg(G, bg, maxiter=30)
    assert {n for n, _ in got} >= set(SESSION_SPANS) | {
        "cg.solve", "cg.chunk", "cg.rho_fetch", "layout.dia_build",
        "cg.dispatch", "cg.iters_fetch", "layout.detect", "layout.ell_build"}
    assert all(s is _NULL for _, s in got)
    assert telemetry.events() == []
    assert telemetry.summary()["spans"] == {}
    assert _recorder._SPANS == {}


def test_off_the_session_still_accounts_its_solve_time(off):
    seen = []
    ses = SolveSession("cg", warm_start=False)
    real = ses._fleet_account

    def account(plan, solver, dt, nb, bkt, iters, solve_s, **kw):
        seen.append(solve_s)
        return real(plan, solver, dt, nb, bkt, iters, solve_s, **kw)

    ses._fleet_account = account
    _two_buckets(ses)
    assert len(seen) == 2 and all(0 < s < 60 for s in seen)


# -- the period's account (PR 37) --------------------------------------------
PERIOD_FIELDS = ("period_ms", "inside_ms", "caller_ms", "submit_ms",
                 "submits", "spanned_ms", "unspanned_ms")
PARTS = ("caller_ms", "submit_ms", "spanned_ms", "unspanned_ms")


def _steady(ses, buckets=4, lanes=4, n=300, between=None, wait=True,
            sent=None):
    """A caller's loop: ``lanes`` submits, a flush, and (``wait``) the
    results, ``buckets`` times; ``between(k)`` runs outside the session
    after bucket ``k``. Returns the ``batch.dispatch`` events by ``seq``;
    ``sent`` takes each bucket's tickets."""
    S = _tridiag(n)
    pat = ses.pattern_of(sparse_tpu.csr_array(S))
    rng = np.random.default_rng(3)
    for k in range(buckets):
        ts = [ses.submit(S.data * (1 + 0.1 * i), rng.standard_normal(n),
                         tol=1e-8, pattern=pat) for i in range(lanes)]
        if sent is not None:
            sent.append(ts)
        if ses.auto_flush is None:
            ses.flush(wait=wait)
        if wait:
            for t in ts:
                t.result()
        if between is not None:
            between(k)
    ses.drain()
    return {e["seq"]: e for e in telemetry.events("batch.dispatch")}


@pytest.mark.parametrize("inflight", [1, 2])
@pytest.mark.parametrize("how", ["flush_and_wait", "streaming", "auto_flush"])
def test_a_period_is_its_four_parts_on_every_dispatch_but_the_first(
        tel, inflight, how):
    ses = SolveSession("cg", inflight=inflight, warm_start=False,
                       auto_flush=4 if how == "auto_flush" else None)
    sent = []
    disp = _steady(ses, buckets=5, wait=how == "flush_and_wait", sent=sent)
    assert sorted(disp) == [1, 2, 3, 4, 5]
    # a session's first dispatch has no interval before it: no field at all
    assert not set(PERIOD_FIELDS) & set(disp[1])
    for seq in (2, 3, 4, 5):
        e = disp[seq]
        assert set(PERIOD_FIELDS) <= set(e) and not telemetry.schema.validate(e)
        assert e["period_ms"] == pytest.approx(
            sum(e[k] for k in PARTS), abs=0.01)
        assert e["inside_ms"] == pytest.approx(
            e["period_ms"] - e["caller_ms"], abs=0.01)
        assert e["submits"] == e["batch"] == 4  # the bucket's lanes
        # re-entrancy counts once (auto_flush nests a flush in a submit):
        # no part is negative, and the intake is not the nested flush
        assert all(e[k] >= 0 for k in PARTS)
        assert e["submit_ms"] < e["spanned_ms"]
    # the period is the spacing of the launches' entries, where the
    # tickets' queue ends: the first lane's submit plus the longest wait
    t0 = {seq: min(t.t_submit for t in sent[seq - 1]) * 1e3
          + disp[seq]["queue_ms_max"] for seq in disp}
    for seq in (2, 3, 4, 5):
        assert disp[seq]["period_ms"] == pytest.approx(
            t0[seq] - t0[seq - 1], abs=0.01)


@pytest.mark.parametrize("inflight", [1, 2])
def test_a_callers_pause_between_buckets_is_caller_ms(tel, inflight):
    ses = SolveSession("cg", inflight=inflight, warm_start=False)
    disp = _steady(ses, buckets=5,
                   between=lambda k: time.sleep(0.05) if k == 2 else None)
    slow, others = disp[4], [disp[s] for s in (2, 3, 5)]
    assert slow["caller_ms"] >= 50.0
    assert slow["period_ms"] >= slow["caller_ms"]
    for e in others:
        assert e["caller_ms"] < 50.0
    # and in nothing else: the other three parts read what they read
    # beside it, tens of milliseconds below the pause
    for k in ("submit_ms", "unspanned_ms"):
        assert slow[k] < 25.0
    assert slow["inside_ms"] <= slow["period_ms"] - 50.0 + 0.01


@pytest.mark.parametrize("inflight", [1, 2])
def test_time_between_the_spans_is_unspanned_ms(tel, monkeypatch, inflight):
    ses = SolveSession("cg", inflight=inflight, warm_start=False)
    decide, calls = ses.fleet.decide, []

    def slow_decide(*a, **kw):  # in `_launch`, before session.pack: no span
        calls.append(1)
        if len(calls) == 3:
            time.sleep(0.05)
        return decide(*a, **kw)

    monkeypatch.setattr(ses.fleet, "decide", slow_decide)
    disp = _steady(ses, buckets=5)
    # launch 3's decision lies in the period that launch 4 ends
    slow = disp[4]
    assert slow["unspanned_ms"] >= 50.0
    assert slow["caller_ms"] < 25.0 and slow["submit_ms"] < 25.0
    for s in (2, 3, 5):
        assert disp[s]["unspanned_ms"] < 50.0


def test_a_blocking_admission_is_not_submit_ms(tel):
    """``submit`` at ``max_queue_depth`` drives the pipeline itself: what it
    dispatches and retires there is spans and the rest, like a nested
    flush, and ``submit_ms`` stays the intake. Here every fourth submit
    finds three tickets out: it launches them (a launch inside
    ``session.submit``: that period has three submits) and retires them,
    and is then flushed alone."""
    ses = SolveSession("cg", inflight=2, warm_start=False,
                       max_queue_depth=3, admission="block")
    disp = _steady(ses, buckets=3, wait=False)
    assert len(telemetry.events("batch.admission")) == 3
    assert [disp[s]["batch"] for s in sorted(disp)] == [3, 1] * 3
    for seq in sorted(disp)[1:]:
        e = disp[seq]
        assert e["period_ms"] == pytest.approx(
            sum(e[k] for k in PARTS), abs=0.01)
        assert e["submits"] == e["batch"]
        assert 0 <= e["submit_ms"] < e["spanned_ms"]
        # the fourth submit's few microseconds before its launch are the
        # period's before it, and counted in the one after
        assert e["unspanned_ms"] > -5.0


@pytest.mark.parametrize("inflight", [1, 2])
def test_off_the_session_keeps_no_account_and_reads_no_clock_for_it(
        off, monkeypatch, inflight):
    """Telemetry off: the entry points read the clock where the parent
    did (a ticket's ``t_submit`` and ``t_done``; a launch's entry and the
    four instants the retire's arithmetic needs, for which a live span
    would have stood) and nowhere else, and nothing is summed."""
    reads = []
    monkeypatch.setattr(telemetry, "clock",
                        lambda: reads.append(1) or time.monotonic())
    ses = SolveSession("cg", inflight=inflight, warm_start=False)
    S = _tridiag(300)
    pat = ses.pattern_of(sparse_tpu.csr_array(S))

    def count(call):
        n = len(reads)
        out = call()
        return len(reads) - n, out

    for bucket in range(3):
        tickets = []
        for _ in range(4):
            n, t = count(lambda: ses.submit(S.data, np.ones(300), tol=1e-8,
                                            pattern=pat))
            assert n == 1  # the ticket's t_submit
            tickets.append(t)
        # the launch's entry, session.plan's and session.call's starts (the
        # first one's build: two more), the results' arrival, the
        # readback's end, four tickets' t_done: the parent's count
        assert count(ses.flush)[0] == 3 + (2 if bucket == 0 else 0) + 2 + 4
        assert count(ses.poll)[0] == 0
        assert count(lambda: tickets[0].result())[0] == 0
        assert count(lambda: tickets[1].ready)[0] == 0
        assert count(ses.drain)[0] == 0
    acct = ses._period
    assert (acct.depth, acct.entered, acct.last_t0, acct.submits) == (
        0, None, None, 0)
    assert acct.inside == acct.submit == acct.spanned == 0.0
    assert telemetry.events() == []


# -- the call's account (PR 51) ------------------------------------------------
# A public solver that ends in a `solver.solve` event partitions its own call
# from inside (linalg._CallAccount): call_ms = prep_ms + dispatch_ms + wait_ms
# + rest_ms, and caller_ms since the thread's previous call closed.
CALL_PARTS = ("prep_ms", "dispatch_ms", "wait_ms", "rest_ms")
CALL_FIELDS = ("call_ms", *CALL_PARTS, "caller_ms")
WHOLE = {"call_ms", *CALL_PARTS}


def _account(ev):
    return {k: ev[k] for k in CALL_FIELDS if k in ev}


def _closure(A):
    return linalg.LinearOperator(A.shape, matvec=A.dot, dtype=A.dtype)


def _call_fused(monkeypatch):
    monkeypatch.setattr(settings, "fused_cg", "force")
    A, b = _pde()
    A = A.tocsr()
    return lambda: linalg.cg(A, b, maxiter=60)


def _call_general(monkeypatch):
    A, b = _general()
    return lambda: linalg.cg(A, b, maxiter=40)


def _call_declared(monkeypatch):
    from sparse_tpu import precond

    A, b = _general()
    M = precond.make_M(A, "jacobi")
    return lambda: linalg.cg(A, b, maxiter=40, M=M)


def _call_gmres(monkeypatch):
    A, b = _general()
    return lambda: linalg.gmres(A, b, restart=8, maxiter=3)


def _call_gmres_cycle(monkeypatch):
    A, b = _general()
    op = _closure(A)
    return lambda: linalg.gmres(op, b, restart=8, maxiter=3)


def _call_bicgstab(monkeypatch):
    A, b = _general()
    return lambda: linalg.bicgstab(A, b, maxiter=20)


def _call_dist_cg(monkeypatch):
    from sparse_tpu.parallel import dist_cg, get_mesh, shard_csr

    D0, b = _pde()
    Dd = shard_csr(D0.tocsr(), mesh=get_mesh(4))
    return lambda: dist_cg(Dd, b, tol=0.0, maxiter=12)


# path: (the call, the `.solve` span and its wait field, the fields it has)
CALL_PATHS = {
    "cg_fused": (_call_fused, ("cg.solve", "fetch_s"), WHOLE),
    "cg_general": (_call_general, ("cg.solve", "fetch_s"), WHOLE),
    "cg_declared_M": (_call_declared, ("cg.solve", "fetch_s"), WHOLE),
    "gmres_device": (_call_gmres, ("gmres.solve", "fetch_s"), WHOLE),
    # the cycle path's span sums no dispatch and no fetch
    "gmres_cycle": (_call_gmres_cycle, ("gmres.solve", None),
                    {"call_ms", "prep_ms", "rest_ms"}),
    "bicgstab": (_call_bicgstab, None, {"call_ms"}),  # no `.solve` span
    "dist_cg": (_call_dist_cg, ("dist.cg.solve", "wait_s"), WHOLE),
}


@pytest.mark.parametrize("path", list(CALL_PATHS))
def test_a_call_is_its_four_parts_on_its_solve_event(tel, monkeypatch, path):
    make, span_of, has = CALL_PATHS[path]
    call = make(monkeypatch)
    call()  # layout, trace, compile
    n0 = len(telemetry.events())
    agg0 = telemetry.summary()["spans"]["solver.call"]["n"]
    call()
    evs = telemetry.events()[n0:]
    ev = evs[-1]  # the event is the last thing a call does
    assert ev["kind"] == "solver.solve"
    assert [e["kind"] for e in evs].count("solver.solve") == 1
    acct = _account(ev)
    assert set(acct) == has | {"caller_ms"}
    assert all(v >= 0 for v in acct.values())
    assert acct["call_ms"] > 0
    if "rest_ms" in acct:
        assert acct["call_ms"] == pytest.approx(
            sum(acct.get(k, 0.0) for k in CALL_PARTS), abs=1e-6)
    # `solver.call` is an annotation and an aggregate, no event
    assert telemetry.summary()["spans"]["solver.call"]["n"] == agg0 + 1
    assert not [e for e in evs if e.get("name") == "solver.call"]
    if span_of is None:
        return
    name, wait = span_of
    (solve,) = [e for e in evs if e["kind"] == "span" and e["name"] == name]
    # prep ends where the `.solve` span starts; the span lies inside the call
    assert solve["t0"] + solve["dur_s"] <= ev["tm"] + 1e-3
    assert acct["prep_ms"] + solve["dur_s"] * 1e3 <= acct["call_ms"] + 2e-3
    if wait is not None:
        assert acct["dispatch_ms"] == pytest.approx(
            solve["dispatch_s"] * 1e3, abs=1e-3)
        assert acct["wait_ms"] == pytest.approx(solve[wait] * 1e3, abs=1e-3)
    else:
        assert "dispatch_s" not in solve


def test_caller_ms_is_the_gap_since_the_threads_last_close(tel):
    import threading

    A, b = _general()
    linalg.cg(A, b, maxiter=30)  # the main thread has called before
    got = []

    def client():
        n0 = len(telemetry.events("solver.solve"))
        linalg.cg(A, b, maxiter=30)
        time.sleep(0.05)  # the caller's own time between two calls
        linalg.cg(A, b, maxiter=30)
        got.extend(telemetry.events("solver.solve")[n0:])

    t = threading.Thread(target=client)
    t.start()
    t.join()
    first, second = got
    assert "caller_ms" not in first and "call_ms" in first  # a thread's first
    assert second["caller_ms"] >= 50.0  # the pause is the caller's
    # call + caller is the spacing of the two closes, event to event (an
    # event's `tm` is read just after its account's close)
    spacing = (second["tm"] - first["tm"]) * 1e3
    assert second["call_ms"] + second["caller_ms"] == pytest.approx(
        spacing, abs=25.0)


def test_two_threads_keep_two_accounts(tel):
    import threading

    sides = (20, 24)
    systems = {s * s: _general(side=s) for s in sides}
    for A, b in systems.values():
        linalg.cg(A, b, maxiter=30)  # compiled before the threads start
    n0 = len(telemetry.events("solver.solve"))
    gate = threading.Barrier(2)

    def client(A, b):
        gate.wait()
        for _ in range(3):
            linalg.cg(A, b, maxiter=30)

    threads = [threading.Thread(target=client, args=ab)
               for ab in systems.values()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = telemetry.events("solver.solve")[n0:]
    assert len(evs) == 6
    for n in systems:
        mine = [e for e in evs if e["n"] == n]
        assert ["caller_ms" in e for e in mine] == [False, True, True]
        for e in mine:
            a = _account(e)
            assert WHOLE <= set(a)
            assert a["call_ms"] == pytest.approx(
                sum(a[k] for k in CALL_PARTS), abs=1e-6)
        # a thread's calls follow one another: the other thread's are not
        # in its gaps
        for e0, e1 in zip(mine, mine[1:]):
            assert e1["call_ms"] + e1["caller_ms"] == pytest.approx(
                (e1["tm"] - e0["tm"]) * 1e3, abs=25.0)


def test_a_solver_inside_a_solver_leaves_the_outer_account_whole(tel):
    A, b = _general()
    inner_calls = []

    def apply(r):
        inner_calls.append(1)
        return linalg.cg(A, r, maxiter=3)[0]  # a public solver, nested

    M = linalg.LinearOperator(A.shape, matvec=apply, dtype=A.dtype)
    linalg.gmres(_closure(A), b, restart=4, maxiter=2, M=M)
    n0 = len(telemetry.events())
    inner_calls.clear()
    linalg.gmres(_closure(A), b, restart=4, maxiter=2, M=M)
    evs = telemetry.events()[n0:]
    solves = [e for e in evs if e["kind"] == "solver.solve"]
    inner, outer = solves[:-1], solves[-1]
    assert evs[-1] is outer and outer["solver"] == "gmres"
    assert inner and all(e["solver"] == "cg" for e in inner)
    assert all(_account(e) == {} for e in inner)  # the inner events are bare
    # the outer's account is of its own `gmres.solve` span, not of the
    # nested `cg.solve` spans that closed inside it
    acct = _account(outer)
    assert set(acct) == {"call_ms", "prep_ms", "rest_ms", "caller_ms"}
    (span,) = [e for e in evs if e.get("name") == "gmres.solve"]
    assert acct["rest_ms"] >= span["dur_s"] * 1e3 - 2e-3
    assert acct["call_ms"] == pytest.approx(
        acct["prep_ms"] + acct["rest_ms"], abs=1e-6)
    assert getattr(linalg._CALLS, "account").depth == 0


def test_a_call_that_raises_leaves_the_next_one_no_caller_ms(tel):
    A, b = _general()
    linalg.cg(A, b, maxiter=30)
    with pytest.raises(AssertionError):
        linalg.cg(A, b, maxiter=30, atol=1.0)
    acct = linalg._CALLS.account
    assert (acct.depth, acct.call, acct.closed) == (0, None, None)
    linalg.cg(A, b, maxiter=30)
    assert "caller_ms" not in telemetry.events("solver.solve")[-1]
    linalg.cg(A, b, maxiter=30)
    assert "caller_ms" in telemetry.events("solver.solve")[-1]


def test_under_jit_the_account_is_inert(tel):
    A, b = _general()
    linalg.cg(A, b, maxiter=30)
    acct = linalg._CALLS.account
    closed = acct.closed
    seen = []

    @jax.jit
    def traced(v):
        with linalg._solver_call() as scope:
            seen.append(scope)
            linalg._call_solved(telemetry.span("cg.solve"), 1.0, 1.0)
        return v + 1

    traced(np.ones(4))
    assert seen == [_NULL]
    assert (acct.depth, acct.call, acct.closed) == (0, None, closed)


def test_off_a_solve_keeps_no_account_and_reads_no_clock(off, monkeypatch):
    """Telemetry off: a library solve reads ``telemetry.clock`` as often as
    it did before the account (never), gets the shared no-op for its call's
    scope and makes no account."""
    from sparse_tpu.parallel import dist_cg, get_mesh, shard_csr

    reads = []
    counting = lambda: reads.append(1) or time.monotonic()  # noqa: E731
    monkeypatch.setattr(telemetry, "clock", counting)
    monkeypatch.setattr(_recorder, "clock", counting)
    monkeypatch.setattr(linalg, "_CALLS", type(linalg._CALLS)())
    assert linalg._solver_call() is _NULL
    A, b = _general()
    D0, bd = _pde()
    Dd = shard_csr(D0.tocsr(), mesh=get_mesh(4))
    linalg.cg(A, b, maxiter=30)
    linalg.gmres(A, b, restart=8, maxiter=2)
    linalg.gmres(_closure(A), b, restart=8, maxiter=2)
    linalg.bicgstab(A, b, maxiter=10)
    dist_cg(Dd, bd, tol=0.0, maxiter=12)
    assert reads == []
    assert getattr(linalg._CALLS, "account", None) is None
    assert telemetry.events() == []
