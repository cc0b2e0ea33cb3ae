"""Fleet serving tier (ISSUE 10): mesh-sharded SolveSession.

The load-bearing contracts:

* **Parity** — batch-sharded dispatches produce the SAME per-lane
  iterates as the single-device programs (machine eps; lanes never
  exchange data, only the all-converged exit crosses the mesh), for all
  three solvers.
* **mesh=1 ≡ classic** — a one-device mesh selects the single-device
  strategy and builds a jaxpr-identical program under the same
  plan-cache key (fleet can never perturb the non-fleet path).
* **Compile economics** — exactly one plan-cache miss per
  (bucket, mesh); a second mesh is a second program.
* **Mesh-keyed warm restart** — manifest entries carry the mesh
  fingerprint; a same-topology restart replays to a zero-miss serving
  window, a different topology (or fleet off) cold-starts cleanly.
* **Resilience** — an injected dispatch drop on a sharded bucket rides
  the ordinary retry/requeue machinery to recovery.

Runs on the conftest-forced 8-device virtual CPU mesh
(``--xla_force_host_platform_device_count=8``).
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax

import sparse_tpu
from sparse_tpu import fleet, linalg, plan_cache, telemetry, vault
from sparse_tpu.batch import SolveSession
from sparse_tpu.batch import bucket as bucketing
from sparse_tpu.batch.operator import SparsityPattern, pattern_matvec
from sparse_tpu.config import settings
from sparse_tpu.parallel.mesh import mesh_fingerprint
from sparse_tpu.resilience import faults

SOLVERS = ("cg", "bicgstab", "gmres")


@pytest.fixture(autouse=True)
def _clean_state(tmp_path):
    """Scratch telemetry sink, no faults, vault off, cold plan cache."""
    faults.clear()
    old_vault = settings.vault
    old_tel = settings.telemetry
    settings.vault = ""
    telemetry.configure(str(tmp_path / "records.jsonl"))
    telemetry.reset()
    plan_cache.clear()
    yield
    faults.clear()
    settings.vault = old_vault
    settings.telemetry = old_tel
    telemetry.configure(None)
    telemetry.reset()
    plan_cache.clear()


def _traffic(B=32, n=96, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    e = np.ones(n)
    mats = []
    for _ in range(B):
        A = sp.diags(
            [-e[:-1], 3.0 * e, -e[:-1]], [-1, 0, 1], format="csr"
        ).astype(dtype)
        A.setdiag((3.0 + rng.random(n)).astype(dtype))
        A.sort_indices()
        mats.append(A.tocsr())
    rhs = rng.standard_normal((B, n)).astype(dtype)
    return mats, rhs


def _mesh(S):
    return fleet.fleet_mesh(S)


# ---------------------------------------------------------------------------
# parity: sharded ≡ single-device at machine eps
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("solver", SOLVERS)
def test_sharded_parity_machine_eps(solver):
    mats, rhs = _traffic(B=32)
    s0 = SolveSession(solver, batch_max=32, fleet=False)
    X0, it0, r0 = s0.solve_many(mats, rhs, tol=1e-10)
    s1 = SolveSession(
        solver, batch_max=32, fleet="auto", fleet_mesh=_mesh(8),
        fleet_min_b=4,
    )
    X1, it1, r1 = s1.solve_many(mats, rhs, tol=1e-10)
    assert np.max(np.abs(X1 - X0)) < 1e-13
    assert np.array_equal(it0, it1)
    assert np.max(np.abs(r1 - r0)) < 1e-20
    # the solve really converged (not a trivially-equal failure)
    for A, x, b in zip(mats, X1, rhs):
        assert np.linalg.norm(A @ x - b) < 1e-8


def test_sharded_parity_f32():
    mats, rhs = _traffic(B=16, dtype=np.float32)
    s0 = SolveSession("cg", batch_max=16, fleet=False)
    X0, _, _ = s0.solve_many(mats, rhs, tol=1e-5)
    s1 = SolveSession(
        "cg", batch_max=16, fleet="auto", fleet_mesh=_mesh(8),
        fleet_min_b=4,
    )
    X1, _, _ = s1.solve_many(mats, rhs, tol=1e-5)
    assert np.max(np.abs(X1 - X0)) < 1e-6


# ---------------------------------------------------------------------------
# mesh=1 ≡ the classic single-device path
# ---------------------------------------------------------------------------
def test_mesh1_selects_single_and_jaxpr_identical():
    mats, _ = _traffic(B=1, n=64)
    pat = SparsityPattern.from_csr(mats[0])
    pol = fleet.FleetPolicy("auto", mesh=_mesh(1), min_b=2)
    assert not pol.enabled
    plan = pol.decide(pat, 8, "cg")
    assert plan.strategy == "single"
    assert plan.key_suffix == ""

    s0 = SolveSession("cg", fleet=False)
    s1 = SolveSession("cg", fleet="auto", fleet_mesh=_mesh(1), fleet_min_b=2)
    B, n = 8, pat.shape[0]
    args = (
        np.zeros((B, pat.nnz)), np.zeros((B, n)), np.zeros((B, n)),
        np.zeros(B), 100,
    )
    j0 = jax.make_jaxpr(s0._build_program(pat, B, np.dtype(np.float64)))(
        *args
    )
    j1 = jax.make_jaxpr(
        s1._build_program(pat, B, np.dtype(np.float64), plan=plan)
    )(*args)
    assert str(j0) == str(j1)


def test_fleet_off_env_default_is_single():
    ses = SolveSession("cg")
    assert not ses.fleet.enabled
    st = ses.session_stats()
    assert st["mesh"] == {"enabled": False, "devices": 1}


# ---------------------------------------------------------------------------
# compile economics: one miss per (bucket, mesh)
# ---------------------------------------------------------------------------
def test_one_plan_cache_miss_per_bucket_and_mesh():
    mats, rhs = _traffic(B=16)
    pat = SparsityPattern.from_csr(mats[0])
    pattern_matvec(pat)  # warm the pattern pack (of its form) outside the window
    vals = [np.asarray(A.data) for A in mats]

    def serve(ses):
        tickets = [
            ses.submit(v, b, tol=1e-10, pattern=pat)
            for v, b in zip(vals, rhs)
        ]
        ses.flush()
        return [t.result() for t in tickets]

    s8 = SolveSession(
        "cg", batch_max=16, fleet="auto", fleet_mesh=_mesh(8), fleet_min_b=4
    )
    snap = plan_cache.snapshot()
    serve(s8)
    d1 = plan_cache.delta(snap)
    assert d1["misses"] == 1  # exactly the bucket program
    snap = plan_cache.snapshot()
    serve(s8)
    assert plan_cache.delta(snap)["misses"] == 0  # warm re-dispatch

    # a DIFFERENT mesh is a different program: one more miss, once
    s4 = SolveSession(
        "cg", batch_max=16, fleet="auto", fleet_mesh=_mesh(4), fleet_min_b=4
    )
    snap = plan_cache.snapshot()
    serve(s4)
    assert plan_cache.delta(snap)["misses"] == 1
    snap = plan_cache.snapshot()
    serve(s4)
    assert plan_cache.delta(snap)["misses"] == 0


# ---------------------------------------------------------------------------
# bucketing: mesh-multiple rounding + pad accounting (satellite bugfix)
# ---------------------------------------------------------------------------
def test_bucket_batch_mesh_multiple():
    assert bucketing.bucket_batch(5, "pow2", 64, multiple_of=8) == 8
    assert bucketing.bucket_batch(9, "pow2", 64, multiple_of=8) == 16
    assert bucketing.bucket_batch(5, "exact", 64, multiple_of=8) == 8
    assert bucketing.bucket_batch(12, "exact", 64, multiple_of=8) == 16
    # a cap below the mesh size rounds UP (never an unshardable bucket)
    assert bucketing.bucket_batch(3, "pow2", 4, multiple_of=8) == 8
    # no constraint = unchanged classic behavior
    assert bucketing.bucket_batch(5, "pow2", 64) == 8
    assert bucketing.bucket_batch(5, "exact", 64) == 5


def test_mesh_pad_lanes_instant_converge_and_occupancy():
    mats, rhs = _traffic(B=5)  # pow2 would say 8; mesh multiple keeps 8
    settings.telemetry = True
    ses = SolveSession(
        "cg", batch_max=64, fleet="auto", fleet_mesh=_mesh(8),
        fleet_min_b=4, conv_test_iters=5,
    )
    X, iters, _ = ses.solve_many(mats, rhs, tol=1e-10)
    assert X.shape == (5, rhs.shape[1])
    ev = [e for e in telemetry.events() if e["kind"] == "batch.dispatch"][-1]
    assert ev["bucket"] == 8 and ev["batch"] == 5 and ev["pad_waste"] == 3
    fd = [e for e in telemetry.events() if e["kind"] == "fleet.dispatch"][-1]
    # pad lanes are excluded from the device occupancy surface
    assert fd["device_lanes"] == [1, 1, 1, 1, 1, 0, 0, 0]
    occ = ses.session_stats()["device_occupancy"]
    assert occ == [1.0, 1.0, 1.0, 1.0, 1.0, 0.0, 0.0, 0.0]
    # pad lanes froze at the first conv test, never at maxiter
    shards = [e for e in telemetry.events() if e["kind"] == "fleet.shard"]
    assert len(shards) >= 8
    for A, x, b in zip(mats, X, rhs):
        assert np.linalg.norm(A @ x - b) < 1e-8


def test_session_stats_mesh_dimension():
    ses = SolveSession(
        "cg", fleet="auto", fleet_mesh=_mesh(8), fleet_min_b=4
    )
    st = ses.session_stats()
    assert st["mesh"]["devices"] == 8
    assert st["mesh"]["fingerprint"] == mesh_fingerprint(_mesh(8))
    assert st["device_occupancy"] == []  # nothing dispatched yet
    assert "device_occupancy" in st and "mesh" in st


# ---------------------------------------------------------------------------
# comm accounting: measured psum bytes reconcile with the model
# ---------------------------------------------------------------------------
def test_sharded_comm_measured_within_tolerance():
    mats, rhs = _traffic(B=16)
    settings.telemetry = True
    ses = SolveSession(
        "cg", batch_max=16, fleet="auto", fleet_mesh=_mesh(8),
        fleet_min_b=4, conv_test_iters=5,
    )
    ses.solve_many(mats, rhs, tol=1e-10)
    evs = [
        e for e in telemetry.events()
        if e["kind"] == "comm.measured" and e.get("site") == "fleet.batch"
    ]
    assert evs, "sharded dispatch emitted no comm.measured event"
    ev = evs[-1]
    assert ev["S"] == 8 and ev["exact"]
    assert abs(ev["divergence_pct"]) <= 10.0


# ---------------------------------------------------------------------------
# warm restart: mesh fingerprint gates replay
# ---------------------------------------------------------------------------
def test_warm_restart_matching_vs_mismatched_mesh(tmp_path):
    settings.vault = str(tmp_path / "vault")
    mats, rhs = _traffic(B=16)
    s1 = SolveSession(
        "cg", batch_max=16, fleet="auto", fleet_mesh=_mesh(8), fleet_min_b=4
    )
    s1.solve_many(mats, rhs, tol=1e-10)
    ents = vault.manifest_entries()
    assert [e.get("mesh") for e in ents] == [mesh_fingerprint(_mesh(8))]
    assert ents[0].get("strategy") == "batch"

    # same topology: replay -> zero-miss serving window
    plan_cache.clear()
    s2 = SolveSession(
        "cg", batch_max=16, fleet="auto", fleet_mesh=_mesh(8),
        fleet_min_b=4, warm_start=True,
    )
    assert s2.warm_replayed == 1
    snap = plan_cache.snapshot()
    X2, _, _ = s2.solve_many(mats, rhs, tol=1e-10)
    assert plan_cache.delta(snap)["misses"] == 0

    # different topology: entry skipped, clean cold start
    plan_cache.clear()
    s3 = SolveSession(
        "cg", batch_max=16, fleet="auto", fleet_mesh=_mesh(4),
        fleet_min_b=4, warm_start=True,
    )
    assert s3.warm_replayed == 0
    X3, _, _ = s3.solve_many(mats, rhs, tol=1e-10)
    assert np.max(np.abs(X3 - X2)) < 1e-13

    # fleet off entirely: mesh-keyed entry also skipped
    plan_cache.clear()
    s4 = SolveSession("cg", batch_max=16, fleet=False, warm_start=True)
    assert s4.warm_replayed == 0


# ---------------------------------------------------------------------------
# resilience: injected dispatch drop on a sharded bucket
# ---------------------------------------------------------------------------
def test_injected_dispatch_drop_recovers():
    mats, rhs = _traffic(B=16)
    settings.telemetry = True
    ses = SolveSession(
        "cg", batch_max=16, fleet="auto", fleet_mesh=_mesh(8),
        fleet_min_b=4, dispatch_attempts=2,
    )
    faults.configure("drop:dispatch:p=1,n=1")
    try:
        X, iters, r2 = ses.solve_many(mats, rhs, tol=1e-10)
    finally:
        faults.clear()
    for A, x, b in zip(mats, X, rhs):
        assert np.linalg.norm(A @ x - b) < 1e-8
    kinds = {e["kind"] for e in telemetry.events()}
    assert "fault.injected" in kinds
    assert "fleet.dispatch" in kinds  # the retry still sharded


# ---------------------------------------------------------------------------
# row-sharded strategy: oversized single systems
# ---------------------------------------------------------------------------
def test_row_sharded_submission_parity():
    n = 1024
    e = np.ones(n)
    A = sp.diags([-e[:-1], 3.0 * e, -e[:-1]], [-1, 0, 1], format="csr")
    rng = np.random.default_rng(3)
    A.setdiag(3.0 + rng.random(n))
    A.sort_indices()
    A = A.tocsr()
    b = rng.standard_normal(n)
    settings.telemetry = True
    ses = SolveSession(
        "cg", fleet="auto", fleet_mesh=_mesh(8), row_shard_min_n=512
    )
    t = ses.submit(A, b, tol=1e-9)
    x, iters, resid2 = t.result()
    assert t.converged and t.solver == "cg"
    assert np.linalg.norm(A @ x - b) < 1e-8
    x0, _ = linalg.cg(sparse_tpu.csr_array(A), b, tol=1e-9, maxiter=n * 10)
    assert np.max(np.abs(x - np.asarray(x0))) < 1e-10
    fd = [e for e in telemetry.events() if e["kind"] == "fleet.dispatch"]
    assert fd and fd[-1]["strategy"] == "row" and fd[-1]["S"] == 8
    # a row-sharded system spans every device
    assert ses.session_stats()["device_occupancy"] == [1.0] * 8


def test_row_threshold_not_met_stays_single():
    n = 64
    e = np.ones(n)
    A = sp.diags([-e[:-1], 3.0 * e, -e[:-1]], [-1, 0, 1], format="csr").tocsr()
    b = np.ones(n)
    settings.telemetry = True
    ses = SolveSession(
        "cg", fleet="auto", fleet_mesh=_mesh(8), row_shard_min_n=4096
    )
    t = ses.submit(A, b, tol=1e-9)
    x, _, _ = t.result()
    assert np.linalg.norm(A @ x - b) < 1e-8
    assert not [
        e for e in telemetry.events() if e["kind"] == "fleet.dispatch"
    ]


# ---------------------------------------------------------------------------
# policy plumbing
# ---------------------------------------------------------------------------
def test_policy_modes_and_resolve():
    assert fleet.FleetPolicy("").mode == ""
    assert fleet.FleetPolicy("off").mode == ""
    for sp_ in ("1", "on", "true", "auto"):
        assert fleet.FleetPolicy(sp_, mesh=_mesh(2)).mode == "auto"
    assert fleet.FleetPolicy("batch", mesh=_mesh(2)).mode == "batch"
    with pytest.raises(ValueError):
        fleet.FleetPolicy("bogus", mesh=_mesh(2))
    pol = fleet.FleetPolicy.resolve(True, mesh=_mesh(8), min_b=3)
    assert pol.enabled and pol.min_b == 3
    assert fleet.FleetPolicy.resolve(pol) is pol
    assert not fleet.FleetPolicy.resolve(False).enabled


def test_policy_mode_restriction():
    mats, _ = _traffic(B=1, n=64)
    pat = SparsityPattern.from_csr(mats[0])
    row_only = fleet.FleetPolicy("row", mesh=_mesh(8), min_b=2, row_min_n=32)
    assert row_only.decide(pat, 16, "cg").strategy == "single"
    assert row_only.decide(pat, 1, "cg").strategy == "row"
    assert row_only.bucket_multiple() == 1
    batch_only = fleet.FleetPolicy(
        "batch", mesh=_mesh(8), min_b=2, row_min_n=32
    )
    assert batch_only.decide(pat, 16, "cg").strategy == "batch"
    assert batch_only.decide(pat, 1, "cg").strategy == "single"
    assert batch_only.bucket_multiple() == 8
    # row never triggers for non-cg primaries (dist only carries cg)
    auto = fleet.FleetPolicy("auto", mesh=_mesh(8), min_b=2, row_min_n=32)
    assert auto.decide(pat, 1, "gmres").strategy == "single"


def test_device_lane_counts():
    assert fleet.device_lane_counts(5, 8, 8) == [1, 1, 1, 1, 1, 0, 0, 0]
    assert fleet.device_lane_counts(32, 32, 8) == [4] * 8
    assert fleet.device_lane_counts(9, 16, 4) == [4, 4, 1, 0]
    assert fleet.device_lane_counts(1, 1, 1) == [1]


def test_mesh_fingerprint_stability():
    fp8 = mesh_fingerprint(_mesh(8))
    assert fp8 == mesh_fingerprint(_mesh(8))
    assert fp8 != mesh_fingerprint(_mesh(4))
    assert fp8 == "cpu:8:lanes"
