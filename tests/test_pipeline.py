"""Streaming dispatch (ISSUE 13): the SolveSession pipeline.

Pins the pipeline contract pillars: (a) `SPARSE_TPU_INFLIGHT=1`
reproduces the classic synchronous path bit-identically (numeric AND
jaxpr parity — the window changes host scheduling, never programs);
(b) the deferred-readback future API (`ready` / `result(timeout=)` /
`poll()` / `drain()`) resolves interleaved patterns in any await order;
(c) per-ticket deadlines are re-checked at readback — a lane gone stale
in flight keeps its result instead of spending a requeue past its
deadline, while a lane expired before dispatch still fails; (d)
admission control blocks or rejects at `max_queue_depth` with
`batch.admission` evidence; (e) the async `_prebuild` warm replay races
a first `submit` to a zero-serving-build window; (f) the
`batch.queue_depth` gauge decrements per ticket at finalize — no drift
through failures, deadlines or requeues (`queue_depth_drift == 0`); (g) the lane stacks of a bucket are the same
four arguments, bit for bit and aval for aval, whether they were stacked
on the host (small lanes) or uploaded lane by lane and assembled on the
device (lanes of `bucket.DEVICE_STACK_LANE_BYTES` or more, ISSUE 26).
"""

import contextlib
import tracemalloc

import numpy as np
import pytest
import scipy.sparse as sp

import jax

from sparse_tpu import plan_cache, telemetry
from sparse_tpu.batch import (
    AdmissionError,
    SolveSession,
    TicketDeadlineError,
    TicketTimeoutError,
    bucket_batch,
    pad_lanes,
    stage_lanes,
)
from sparse_tpu.batch import bucket as bucketing
from sparse_tpu.batch.service import _InFlight
from sparse_tpu.config import settings
from sparse_tpu.resilience import faults
from sparse_tpu.telemetry import _metrics


@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield tmp_path / "records.jsonl"
    telemetry.configure(None)
    telemetry.reset()


def _tridiag(n, seed=0):
    rng = np.random.default_rng(seed)
    e = np.ones(n)
    A = sp.diags([-e[:-1], 3.0 * e, -e[:-1]], [-1, 0, 1], format="csr")
    A = A.copy()
    A.setdiag(3.0 + rng.random(n))
    A.sort_indices()
    return A


def _systems(B=6, n=48, seed=7):
    rng = np.random.default_rng(seed)
    mats = [_tridiag(n, seed=s) for s in range(B)]
    rhs = rng.standard_normal((B, n))
    return mats, rhs


# ---------------------------------------------------------------------------
# (a) parity: the window changes scheduling, never results or programs
# ---------------------------------------------------------------------------
def test_inflight1_numeric_parity_with_pipelined():
    mats, rhs = _systems()
    s_sync = SolveSession("cg", inflight=1, warm_start=False)
    X0, it0, r0 = s_sync.solve_many(mats, rhs, tol=1e-10)

    s_pipe = SolveSession("cg", inflight=3, warm_start=False)
    tickets = [
        s_pipe.submit(A, b, tol=1e-10) for A, b in zip(mats, rhs)
    ]
    s_pipe.flush(wait=False)
    outs = [t.result() for t in tickets]
    X1 = np.stack([o[0] for o in outs])
    it1 = np.asarray([o[1] for o in outs])
    r1 = np.asarray([o[2] for o in outs])
    # bit-identical, not merely close: same program, same inputs
    assert np.array_equal(X0, X1)
    assert np.array_equal(it0, it1)
    assert np.array_equal(r0, r1)


def test_inflight_never_enters_program_jaxpr_or_keys():
    mats, _ = _systems(B=2)
    s1 = SolveSession("cg", inflight=1, warm_start=False)
    s2 = SolveSession("cg", inflight=4, warm_start=False)
    pat1 = s1.pattern_of(mats[0])
    pat2 = s2.pattern_of(mats[0])
    B, dt = 2, np.dtype(np.float64)
    j1 = jax.make_jaxpr(s1._build_program(pat1, B, dt))(
        np.zeros((B, pat1.nnz)), np.zeros((B, 48)), np.zeros((B, 48)),
        np.zeros(B), 10,
    )
    j2 = jax.make_jaxpr(s2._build_program(pat2, B, dt))(
        np.zeros((B, pat2.nnz)), np.zeros((B, 48)), np.zeros((B, 48)),
        np.zeros(B), 10,
    )
    assert str(j1) == str(j2)


def test_stage_lanes_matches_pad_lanes():
    rng = np.random.default_rng(3)
    values = rng.standard_normal((3, 10))
    rhs = rng.standard_normal((3, 5))
    tols = np.array([1e-8, 1e-6, 1e-4])
    ref = pad_lanes(values, rhs, tols, 4)
    dev = stage_lanes(values, rhs, tols, 4)
    assert ref[4] == dev[4] == 3
    for a, b in zip(ref[:4], dev[:4]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# (b) deferred readback: future API, interleaved patterns, poll/drain
# ---------------------------------------------------------------------------
def test_deferred_readback_interleaved_patterns_any_order():
    n = 40
    mats_a = [_tridiag(n, seed=s) for s in range(3)]
    mats_b = [_tridiag(n + 8, seed=10 + s) for s in range(3)]
    rng = np.random.default_rng(11)
    ses = SolveSession("cg", inflight=4, batch_max=2, warm_start=False)
    tickets = []
    oracle = []
    for A in [mats_a[0], mats_b[0], mats_a[1], mats_b[1], mats_a[2],
              mats_b[2]]:
        b = rng.standard_normal(A.shape[0])
        tickets.append(ses.submit(A, b, tol=1e-10))
        oracle.append((A, b))
    ses.flush(wait=False)
    # await in reverse order: retirement is FIFO underneath, the
    # future API hides it
    for t, (A, b) in reversed(list(zip(tickets, oracle))):
        x, _iters, _r2 = t.result()
        assert np.linalg.norm(A @ x - b) < 1e-8
    assert ses.session_stats()["tickets"]["queue_depth_drift"] == 0


def test_ready_flag_and_poll_and_drain_counts():
    mats, rhs = _systems(B=4)
    ses = SolveSession("cg", inflight=8, batch_max=2, warm_start=False)
    ts = [ses.submit(A, b, tol=1e-10) for A, b in zip(mats, rhs)]
    assert not any(t.ready for t in ts)  # still queued
    dispatched = ses.flush(wait=False)
    assert dispatched == 2
    retired = ses.poll() + ses.drain()
    assert retired <= 2
    assert all(t.ready for t in ts)
    assert all(t.done for t in ts)
    st = ses.session_stats()
    assert st["pipeline"]["depth"] == 0
    assert st["tickets"]["queue_depth_drift"] == 0


def test_result_timeout_leaves_ticket_pending(monkeypatch):
    mats, rhs = _systems(B=1)
    ses = SolveSession("cg", inflight=2, warm_start=False)
    t = ses.submit(mats[0], rhs[0], tol=1e-12)
    # deterministic timeout: pretend the device never finishes
    monkeypatch.setattr(_InFlight, "is_ready", lambda self: False)
    with pytest.raises(TicketTimeoutError):
        t.result(timeout=0.01)
    assert not t.done  # a timeout never loses work
    monkeypatch.undo()
    x, _iters, _r2 = t.result()
    assert np.linalg.norm(mats[0] @ x - rhs[0]) < 1e-8


# ---------------------------------------------------------------------------
# (c) deadlines: still fail at dispatch; re-checked at readback
# ---------------------------------------------------------------------------
def test_deadline_expired_before_dispatch_still_fails():
    mats, rhs = _systems(B=1)
    ses = SolveSession("cg", inflight=2, warm_start=False)
    t = ses.submit(mats[0], rhs[0], tol=1e-10, deadline_s=0.0)
    ses.flush(wait=False)
    with pytest.raises(TicketDeadlineError):
        t.result()
    assert ses.session_stats()["tickets"]["queue_depth_drift"] == 0


def test_deadline_at_readback_skips_requeue(tel):
    mats, rhs = _systems(B=2)
    before = _metrics.counter("batch.stale_requeues").value
    ses = SolveSession("cg", inflight=4, requeue=True, warm_start=False)
    # maxiter=1 cannot converge -> the lanes would requeue. Hold the
    # bucket in flight (is_ready False keeps poll() from retiring it),
    # then lapse the deadlines WHILE in flight: readback must keep the
    # unconverged results instead of spending a fallback solve
    ts = [
        ses.submit(A, b, tol=1e-14, maxiter=1, deadline_s=60.0)
        for A, b in zip(mats, rhs)
    ]
    orig_ready = _InFlight.is_ready
    _InFlight.is_ready = lambda self: False
    try:
        ses.flush(wait=False)
        assert ses.session_stats()["pipeline"]["depth"] == 1
        for t in ts:
            t.deadline_s = 1e-9  # in-flight wait outlived the budget
    finally:
        _InFlight.is_ready = orig_ready
    ses.drain()
    for t in ts:
        assert t.done and not t.converged
        assert not t.requeued
    assert _metrics.counter("batch.stale_requeues").value >= before + 2
    evs = [
        e for e in telemetry.events()
        if e["kind"] == "batch.deadline" and e.get("stage") == "readback"
    ]
    assert evs and evs[0]["lanes"] == 2
    assert ses.session_stats()["tickets"]["queue_depth_drift"] == 0


def test_unexpired_unconverged_lane_still_requeues():
    mats, rhs = _systems(B=1)
    ses = SolveSession("cg", inflight=4, requeue=True, warm_start=False)
    t = ses.submit(mats[0], rhs[0], tol=1e-10, maxiter=1)
    ses.flush(wait=False)
    x, _iters, _r2 = t.result()
    assert t.requeued  # no deadline -> the fallback ran
    assert np.linalg.norm(mats[0] @ x - rhs[0]) < 1e-6


def test_result_follows_a_requeue_still_in_flight(monkeypatch):
    """The first bucket is not ready when ``flush(wait=False)`` polls, so
    ``result()`` retires it, which requeues the lane into a fallback
    bucket that stays in the window: ``result()`` goes on to that one."""
    mats, rhs = _systems(B=1)
    ses = SolveSession("cg", inflight=4, requeue=True, warm_start=False)
    t = ses.submit(mats[0], rhs[0], tol=1e-10, maxiter=1)
    with monkeypatch.context() as m:
        m.setattr(_InFlight, "is_ready", lambda self: False)
        ses.flush(wait=False)
    assert t.t_done is None and len(ses._inflight) == 1
    x, _iters, _r2 = t.result()
    assert t.requeued and t.converged and t.solver == "gmres"
    assert np.linalg.norm(mats[0] @ x - rhs[0]) < 1e-6
    assert not ses._inflight


# ---------------------------------------------------------------------------
# (d) admission control
# ---------------------------------------------------------------------------
def test_admission_reject_mode(tel):
    mats, rhs = _systems(B=3)
    ses = SolveSession("cg", inflight=2, max_queue_depth=2,
                       admission="reject", warm_start=False)
    ses.submit(mats[0], rhs[0], tol=1e-10)
    ses.submit(mats[1], rhs[1], tol=1e-10)
    with pytest.raises(AdmissionError):
        ses.submit(mats[2], rhs[2], tol=1e-10)
    evs = [e for e in telemetry.events() if e["kind"] == "batch.admission"]
    assert evs and evs[0]["mode"] == "reject" and evs[0]["depth"] == 2
    ses.drain()
    # rejected work never entered: the admitted two still solve
    assert ses.session_stats()["tickets"]["done"] == 2
    assert ses.session_stats()["tickets"]["queue_depth_drift"] == 0


def test_admission_block_mode_drives_pipeline(tel):
    mats, rhs = _systems(B=6)
    ses = SolveSession("cg", inflight=2, max_queue_depth=3,
                       admission="block", warm_start=False)
    ts = [ses.submit(A, b, tol=1e-10) for A, b in zip(mats, rhs)]
    assert ses._unfinalized < 3 + 1  # backpressure held the line
    ses.drain()
    assert all(t.done for t in ts)
    evs = [e for e in telemetry.events() if e["kind"] == "batch.admission"]
    assert evs and all(e["mode"] == "block" for e in evs)
    assert "waited_ms" in evs[0]
    assert ses.session_stats()["tickets"]["queue_depth_drift"] == 0


# ---------------------------------------------------------------------------
# (e) async warm replay races the first submit
# ---------------------------------------------------------------------------
def test_async_prebuild_races_first_submit(tmp_path, monkeypatch):
    monkeypatch.setattr(settings, "vault", str(tmp_path / "vault"))
    mats, rhs = _systems(B=4)
    seed_ses = SolveSession("cg", warm_start=False)
    X0, _, _ = seed_ses.solve_many(mats, rhs, tol=1e-10)
    plan_cache.clear()  # "the process died"
    ses = SolveSession("cg", inflight=2, warm_start=True)  # async replay
    # submit IMMEDIATELY — the race the pipeline must win: dispatch
    # waits for the replay's program instead of rebuilding it
    ts = [ses.submit(A, b, tol=1e-10) for A, b in zip(mats, rhs)]
    ses.flush(wait=False)
    X1 = np.stack([t.result()[0] for t in ts])
    assert ses.warm_replayed >= 1
    assert ses.session_stats()["pipeline"]["serving_builds"] == 0
    np.testing.assert_allclose(X0, X1, atol=1e-12)


def test_warm_async_false_replays_synchronously(tmp_path, monkeypatch):
    monkeypatch.setattr(settings, "vault", str(tmp_path / "vault"))
    mats, rhs = _systems(B=4)
    SolveSession("cg", warm_start=False).solve_many(mats, rhs, tol=1e-10)
    plan_cache.clear()
    ses = SolveSession("cg", warm_start=True, warm_async=False)
    assert ses._warm is None  # no thread; replay already done
    assert ses.warm_replayed >= 1


# ---------------------------------------------------------------------------
# (f) queue-depth gauge accounting
# ---------------------------------------------------------------------------
def test_queue_depth_gauge_no_drift_on_bucket_failure():
    mats, rhs = _systems(B=4)
    g = _metrics.gauge("batch.queue_depth")
    base = g.value
    ses = SolveSession("cg", inflight=1, dispatch_attempts=1,
                       warm_start=False)
    ts = [ses.submit(A, b, tol=1e-10) for A, b in zip(mats, rhs)]
    assert g.value == base + 4
    faults.configure("drop:dispatch:p=1")  # every dispatch drops
    try:
        ses.flush()
    finally:
        faults.clear()
    assert all(t.failed for t in ts)
    # per-ticket decrement at finalize: failures fully drain the gauge
    assert g.value == base
    assert ses.session_stats()["tickets"]["queue_depth_drift"] == 0


def test_queue_depth_gauge_no_drift_through_requeue_and_deadline():
    mats, rhs = _systems(B=3)
    g = _metrics.gauge("batch.queue_depth")
    base = g.value
    ses = SolveSession("cg", inflight=2, warm_start=False)
    ses.submit(mats[0], rhs[0], tol=1e-10)              # clean
    ses.submit(mats[1], rhs[1], tol=1e-10, maxiter=1)   # will requeue
    t3 = ses.submit(mats[2], rhs[2], tol=1e-10, deadline_s=0.0)  # expires
    ses.flush()
    assert t3.failed
    assert g.value == base
    assert ses.session_stats()["tickets"]["queue_depth_drift"] == 0


def test_inflight_event_and_gauge(tel):
    mats, rhs = _systems(B=4)
    ses = SolveSession("cg", inflight=8, batch_max=2, warm_start=False)
    for A, b in zip(mats, rhs):
        ses.submit(A, b, tol=1e-10)
    ses.flush(wait=False)
    ses.drain()
    evs = [e for e in telemetry.events() if e["kind"] == "batch.inflight"]
    assert len(evs) == 2  # one per dispatched bucket
    assert all(e["capacity"] == 8 for e in evs)
    assert max(e["depth"] for e in evs) >= 1
    assert _metrics.gauge("batch.inflight").value == 0  # drained


# ---------------------------------------------------------------------------
# loadgen rides the future API
# ---------------------------------------------------------------------------
def test_loadgen_closed_loop_records_inflight_depth():
    from sparse_tpu import loadgen

    mats, rhs = _systems(B=4)
    ses = SolveSession("cg", inflight=4, batch_max=4, warm_start=False)
    trace = loadgen.ArrivalTrace.parse("closed:requests=12,concurrency=4")
    # keep buckets "unready" so opportunistic poll() can't retire them
    # before the await point — the depth the runner records is then the
    # genuinely outstanding window, deterministic on any machine
    orig_ready = _InFlight.is_ready
    _InFlight.is_ready = lambda self: False
    try:
        rep = loadgen.run_load(ses, trace, list(zip(mats, rhs)),
                               tol=1e-10)
    finally:
        _InFlight.is_ready = orig_ready
    assert rep.completed == 12
    assert rep.inflight_depth  # recorded
    assert rep.inflight_depth["max"] >= 4  # concurrency honestly held
    assert rep.inflight_depth["pipelined"] is True
    assert rep.as_dict()["inflight_depth"] == rep.inflight_depth


def test_bucket_batch_unchanged_by_pipeline():
    # the pipeline must not perturb bucketing: same pow2 quantization
    assert bucket_batch(5, policy="pow2", batch_max=64) == 8
    assert bucket_batch(5, policy="exact", batch_max=64) == 5


# ---------------------------------------------------------------------------
# (g) staging: host stack or device assemble, the same four arguments
# ---------------------------------------------------------------------------
_FORMS = {"device": 0, "host": 1 << 62}  # DEVICE_STACK_LANE_BYTES of each


@pytest.fixture(params=sorted(_FORMS))
def form(request, monkeypatch):
    monkeypatch.setattr(
        bucketing, "DEVICE_STACK_LANE_BYTES", _FORMS[request.param]
    )
    return request.param


def _x0_of(kind, rng, nb, n):
    if kind == "none":
        return None
    x0 = list(rng.standard_normal((nb, n)))
    if kind == "some":
        x0[1] = None
    return x0


@pytest.mark.parametrize(
    "nb,bkt,x0_kind,lanes_as",
    [
        (4, 4, "all", "list"),       # a full bucket
        (3, 4, "all", "list"),       # a pad lane: lane 0 again, zero rhs/x0
        (3, 8, "some", "list"),      # a real lane without x0
        (3, 4, "none", "list"),      # no lane brings one
        (3, 4, "all", "f32_lane"),   # one lane of another dtype
        (3, 4, "none", "rows_2d"),   # lanes as rows of one 2-D array
    ],
)
def test_stage_lanes_equals_pad_lanes_either_form(
    form, nb, bkt, x0_kind, lanes_as
):
    rng = np.random.default_rng(5)
    n, nnz, dt = 5, 10, np.dtype(np.float64)
    values = rng.standard_normal((nb, nnz))
    rhs = rng.standard_normal((nb, n))
    tols = np.array([1e-8, 1e-6, 1e-4, 1e-2][:nb])
    x0 = _x0_of(x0_kind, rng, nb, n)
    if lanes_as != "rows_2d":
        values, rhs = list(values), list(rhs)
    if lanes_as == "f32_lane":
        # what _launch does with a lane that is not of the group's dtype
        values[1] = values[1].astype(np.float32).astype(dt, copy=False)
    # the parent's form: stacks of copies, zeros for an absent x0
    ref = pad_lanes(
        np.stack([np.asarray(v).astype(dt) for v in values]),
        np.stack([np.asarray(b).astype(dt) for b in rhs]), tols, bkt,
        x0=None if x0 is None else np.stack(
            [np.zeros(n, dt) if x is None else x for x in x0]
        ),
    )
    dev = stage_lanes(values, rhs, tols, bkt, x0=x0)
    assert ref[4] == dev[4] == nb
    for want, got in zip(ref[:4], dev[:4]):
        put = jax.device_put(want)  # what the parent handed the program
        assert isinstance(got, jax.Array)
        assert got.aval == put.aval and not got.aval.weak_type
        assert got.sharding == put.sharding
        assert got.committed == put.committed is False
        assert np.array_equal(np.asarray(got), want)
    if bkt > nb:
        assert np.array_equal(np.asarray(dev[0])[nb], np.asarray(values[0]))
        assert not np.asarray(dev[1])[nb:].any()
        assert not np.asarray(dev[3])[nb:].any()
        assert (np.asarray(dev[2])[nb:] == 1e30).all()


def _serve(solver, inflight, maxiter=None, x0=False, B=5):
    mats, rhs = _systems(B=B)
    ses = SolveSession(solver, inflight=inflight, batch_max=4,
                       requeue=True, warm_start=False)
    ts = [
        ses.submit(A, b, tol=1e-10, maxiter=maxiter,
                   x0=(0.1 * b if x0 and i != 2 else None))
        for i, (A, b) in enumerate(zip(mats, rhs))
    ]
    ses.flush(wait=False)
    outs = [t.result() for t in ts]
    return ts, outs


@pytest.mark.parametrize("inflight", [1, 2])
@pytest.mark.parametrize("solver", ["cg", "bicgstab", "gmres"])
def test_session_answers_identical_under_either_staging(
    monkeypatch, solver, inflight
):
    runs = {}
    for name, lane_bytes in _FORMS.items():
        monkeypatch.setattr(bucketing, "DEVICE_STACK_LANE_BYTES", lane_bytes)
        runs[name] = _serve(solver, inflight, x0=True)
    for (td, od), (th, oh) in zip(zip(*runs["device"]), zip(*runs["host"])):
        assert np.array_equal(od[0], oh[0])  # bit for bit
        assert od[1] == oh[1] and od[2] == oh[2]
        assert sorted(td.phase_ms) == sorted(th.phase_ms)
        assert td.converged and th.converged


def test_requeue_rereads_host_arrays_under_either_staging(monkeypatch):
    """Every lane stops at one iteration and requeues: the fallback
    buckets are staged from the requests' host arrays again, under either
    form."""
    runs = {}
    for name, lane_bytes in _FORMS.items():
        monkeypatch.setattr(bucketing, "DEVICE_STACK_LANE_BYTES", lane_bytes)
        runs[name] = _serve("cg", 2, maxiter=1)
    for (td, od), (th, oh) in zip(zip(*runs["device"]), zip(*runs["host"])):
        assert td.requeued == th.requeued
        assert np.array_equal(od[0], oh[0]) and od[1] == oh[1]
    assert all(t.requeued and t.solver == "gmres" and t.converged
               for t in runs["device"][0])


def test_mixed_dtype_lane_through_a_session(form):
    """A float32 lane among float64 ones whose right-hand sides are all
    float64 joins their group: its values are the one copy the pack makes."""
    mats, rhs = _systems(B=3)
    ses = SolveSession("cg", warm_start=False)
    pat = ses.pattern_of(mats[0])
    vals = [A.data for A in mats]
    vals[1] = vals[1].astype(np.float32)
    ts = [ses.submit(v, b, tol=1e-6, pattern=pat)
          for v, b in zip(vals, rhs)]
    assert ses.flush() == 1  # one group, one bucket
    for v, b, t in zip(vals, rhs, ts):
        A = sp.csr_matrix((v.astype(np.float64), mats[0].indices,
                           mats[0].indptr), shape=mats[0].shape)
        assert np.linalg.norm(A @ t.result()[0] - b) < 1e-5


def _big_systems(B=4, n=60000):
    """Lanes over the shipped constant: 180k float64 values and a
    right-hand side, 1.9 MB a lane."""
    mats, rhs = _systems(B=B, n=n)
    assert mats[0].data.nbytes + rhs[0].nbytes >= (
        bucketing.DEVICE_STACK_LANE_BYTES
    )
    return mats, rhs


def test_launch_allocates_no_lane_sized_host_array():
    mats, rhs = _big_systems()
    ses = SolveSession("cg", inflight=2, warm_start=False)
    pat = ses.pattern_of(mats[0])
    vals = [A.data for A in mats]
    ses.solve_many(mats, rhs, tol=1e-8)  # builds the program, then drains
    ts = [ses.submit(v, b, tol=1e-8, x0=b, pattern=pat)
          for v, b in zip(vals, rhs)]
    tracemalloc.start()
    try:
        ses.flush(wait=False)  # the launch alone: the window holds it
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert len(ses._inflight) == 1
    # numpy reports its buffers to tracemalloc: a stack of the four lanes
    # (the parent made three) would show as four lanes at least
    assert peak < vals[0].nbytes + rhs[0].nbytes
    ses.drain()
    assert all(t.converged for t in ts)


@pytest.mark.parametrize("size", ["large", "small"])
def test_lanes_staged_counter_and_dispatch_event(tel, size):
    where = {"large": "device", "small": "host"}[size]
    mats, rhs = _big_systems(B=3) if size == "large" else _systems(B=3)
    counters = {
        w: _metrics.counter("batch.lanes_staged", where=w)
        for w in ("device", "host")
    }
    base = {w: c.value for w, c in counters.items()}
    SolveSession("cg", warm_start=False).solve_many(mats, rhs, tol=1e-8)
    other = "host" if where == "device" else "device"
    assert counters[where].value == base[where] + 3  # real lanes, not 4
    assert counters[other].value == base[other]
    evs = [e for e in telemetry.events() if e["kind"] == "batch.dispatch"]
    assert [e["staged"] for e in evs] == [where]


@contextlib.contextmanager
def _compiles():
    """The programs compiled inside the block, as the benchmark counts
    them (``compiles_in_window``)."""
    from jax import monitoring

    seen = []

    def on_duration(name, *_a, **_k):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(name)

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(on_duration)


def test_assemble_built_once_second_step_compiles_nothing(monkeypatch):
    monkeypatch.setattr(bucketing, "DEVICE_STACK_LANE_BYTES", 0)
    mats, rhs = _systems(B=3)
    ses = SolveSession("cg", inflight=2, warm_start=False)
    X, _, _ = ses.solve_many(mats, rhs, tol=1e-10)  # step 1: builds
    traces = bucketing._assemble._cache_size()
    snap = plan_cache.snapshot()
    with _compiles() as compiled:
        # step 2: x0 carried over, other right-hand sides, the same bucket
        ts = [ses.submit(A, b + 1.0, tol=1e-10, x0=x)
              for A, b, x in zip(mats, rhs, X)]
        ses.flush()
    assert all(t.converged for t in ts)
    assert bucketing._assemble._cache_size() == traces
    assert plan_cache.delta(snap)["misses"] == 0
    assert compiled == []


def test_program_key_and_avals_same_under_either_staging(monkeypatch, tel):
    mats, rhs = _systems(B=3)
    seen = {}
    real = bucketing.stage_lanes

    def spy(*a, **k):
        out = real(*a, **k)
        seen.setdefault(name, []).append(
            [(x.aval, x.sharding, x.committed) for x in out[:4]]
        )
        return out

    monkeypatch.setattr(bucketing, "stage_lanes", spy)
    for name, lane_bytes in _FORMS.items():
        monkeypatch.setattr(bucketing, "DEVICE_STACK_LANE_BYTES", lane_bytes)
        SolveSession("cg", warm_start=False).solve_many(mats, rhs, tol=1e-10)
    assert seen["device"] == seen["host"] and len(seen["host"]) == 1
    keys = [e["program"] for e in telemetry.events()
            if e["kind"] == "batch.dispatch"]
    assert len(keys) == 2 and keys[0] == keys[1]


def test_warm_replayed_program_takes_device_assembled_arguments(monkeypatch):
    """The replay compiles a bucket program ahead of time against zero
    stacks; an executable compiled so refuses other avals, so the first
    real dispatch, assembled on the device, must bring the same ones."""
    monkeypatch.setattr(bucketing, "DEVICE_STACK_LANE_BYTES", 0)
    mats, rhs = _systems(B=3)
    plan_cache.clear()
    ses = SolveSession("cg", warm_start=False)
    pat = ses.pattern_of(mats[0])
    ses._prebuild(pat, "cg", 4, np.dtype(np.float64))
    degraded = _metrics.counter("batch.degraded").value
    snap = plan_cache.snapshot()
    X, _, _ = ses.solve_many(mats, rhs, tol=1e-10)
    assert plan_cache.delta(snap)["misses"] == 0
    assert ses.session_stats()["pipeline"]["serving_builds"] == 0
    assert _metrics.counter("batch.degraded").value == degraded
    for A, b, x in zip(mats, rhs, X):
        assert np.linalg.norm(A @ x - b) < 1e-8
