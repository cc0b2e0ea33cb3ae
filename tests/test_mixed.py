"""Mixed precision as the fast path (ISSUE 15): policy, IR solver, keys.

The load-bearing contracts:

* **Accuracy** — the ``ir`` solver (reduced-precision inner Krylov
  sweeps under the f64 iterative-refinement outer loop) reaches the
  f64 answer at its absolute tolerance; ``scripts/f64_oracle.py``'s
  per-size table is pinned HERE (the oracle-fixture satellite), not
  just pasted into a notes file. The divergence safeguard returns
  the best iterate, reported unconverged, when refinement cannot
  contract.
* **Kernels** — the SELL/DIA formulations accept a storage dtype
  distinct from the accumulation dtype (``acc_dtype``): bf16/f32
  value planes, wide products/reductions; ``None`` stays
  byte-identical. The fused Pallas CG's recurrence scalars carry the
  same split.
* **Policy/keys** — SPARSE_TPU_DTYPE / per-session / per-ticket
  resolution, ``.P<policy>``-suffixed program keys with 'exact'
  byte-identical to the historic keys and numerics, vault manifest
  round-trip at zero serving misses, and the promote_dtype rung
  (anomalous reduced buckets escalate to 'exact' through the requeue
  machinery, ahead of solver escalation).
* **Frozen lanes** — converged lanes stay bit-stable under the IR
  outer loop while neighbors keep refining.

Runs on the conftest-forced 8-device virtual CPU mesh.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import sparse_tpu
from sparse_tpu import linalg, mixed, plan_cache, telemetry, vault
from sparse_tpu.batch import SolveSession, SparsityPattern
from sparse_tpu.batch.krylov import batched_ir
from sparse_tpu.batch.operator import BatchedCSR
from sparse_tpu.config import settings
from sparse_tpu.resilience import faults
from sparse_tpu.telemetry import _cost, _metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state(tmp_path):
    faults.clear()
    old_vault = settings.vault
    old_tel = settings.telemetry
    old_policy = settings.dtype_policy
    settings.vault = ""
    telemetry.configure(str(tmp_path / "records.jsonl"))
    telemetry.reset()
    plan_cache.clear()
    yield
    faults.clear()
    settings.vault = old_vault
    settings.telemetry = old_tel
    settings.dtype_policy = old_policy
    telemetry.configure(None)


def _tridiag(n=64, seed=0, diag=3.0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    e = np.ones(n)
    A = sp.diags([-e[:-1], diag * e, -e[:-1]], [-1, 0, 1], format="csr")
    A = A.copy()
    A.setdiag(diag + rng.random(n))
    A = A.tocsr().astype(dtype)
    A.sort_indices()
    return A


def _pattern(A):
    return SparsityPattern(A.indptr, A.indices, A.shape)


# ---------------------------------------------------------------------------
# policy resolution and key suffixes
# ---------------------------------------------------------------------------
def test_canonical_policy_spellings():
    for s in ("", "off", "none", "exact", None, "0", "false"):
        assert mixed.canonical_policy(s) == "exact"
    assert mixed.canonical_policy("f32ir") == "f32ir"
    assert mixed.canonical_policy("BF16IR") == "bf16ir"
    assert mixed.canonical_policy("auto") == "auto"
    with pytest.raises(ValueError):
        mixed.canonical_policy("auto", allow_auto=False)
    with pytest.raises(ValueError):
        mixed.canonical_policy("f16")


def test_key_suffix_backcompat():
    assert mixed.key_suffix("exact") == ""
    assert mixed.key_suffix(None) == ""
    assert mixed.key_suffix("f32ir") == ".Pf32ir"
    assert mixed.key_suffix("bf16ir") == ".Pbf16ir"


def test_inner_dtypes_split():
    s, c = mixed.inner_dtypes("f32ir")
    assert s == np.float32 and c == np.float32
    s, c = mixed.inner_dtypes("bf16ir")
    assert s == jnp.bfloat16 and c == np.float32
    assert mixed.outer_dtype() == np.float64


def test_policy_auto_and_env():
    A = _tridiag(16)
    pat = _pattern(A)
    pol = mixed.DtypePolicy("auto")
    assert pol.decide(pat, "cg", 4, np.float64) == "f32ir"
    assert pol.decide(pat, "bicgstab", 4, np.float64) == "f32ir"
    # gmres has no fused IR loop; f32 requests stay exact under auto
    assert pol.decide(pat, "gmres", 4, np.float64) == "exact"
    assert pol.decide(pat, "cg", 4, np.float32) == "exact"
    settings.dtype_policy = "f32ir"
    try:
        pol2 = mixed.DtypePolicy()
        assert pol2.mode == "f32ir"
        assert pol2.decide(pat, "cg", 4, np.float64,
                           override="exact") == "exact"
    finally:
        settings.dtype_policy = ""
    with pytest.raises(ValueError):
        mixed.DtypePolicy("bogus")


def test_policy_degrades_complex_and_gmres():
    A = _tridiag(16)
    pat = _pattern(A)
    pol = mixed.DtypePolicy("f32ir")
    assert pol.decide(pat, "cg", 4, np.complex128) == "exact"
    assert pol.decide(pat, "gmres", 4, np.float64) == "exact"
    assert pol.decide(pat, "cg", 4, np.float64) == "f32ir"


def test_promote_pins_group_and_counts():
    A = _tridiag(16)
    pat = _pattern(A)
    pol = mixed.DtypePolicy("f32ir")
    assert pol.decide(pat, "cg", 4, np.float64) == "f32ir"
    before = float(
        _metrics.counter("mixed.promotions", reason="unit").value
    )
    pol.promote(pat, "cg", 4, np.float64, reason="unit")
    assert pol.decide(pat, "cg", 4, np.float64) == "exact"
    # other buckets of the same pattern are untouched
    assert pol.decide(pat, "cg", 8, np.float64) == "f32ir"
    after = float(
        _metrics.counter("mixed.promotions", reason="unit").value
    )
    assert after - before == 1
    assert pol.describe()["promoted_groups"] == 1


def test_ir_knobs_scale_with_n():
    pol = mixed.DtypePolicy("f32ir")
    small = pol.ir_knobs("f32ir", 64, 25)
    big = pol.ir_knobs("f32ir", 100_000, 25)
    assert small["inner_iters"] >= 200
    assert big["inner_iters"] == 4000  # capped
    assert big["max_outer"] >= 1 and big["eta"] > 0


# ---------------------------------------------------------------------------
# the ir solver: accuracy, parity, safeguards
# ---------------------------------------------------------------------------
def test_ir_matches_exact_cg():
    A = _tridiag(96, seed=1)
    b = np.random.default_rng(2).standard_normal(96)
    x64, _ = linalg.cg(sparse_tpu.csr_array(A), b, tol=1e-10, maxiter=4000)
    x, info = mixed.ir_solve(A, b, tol=1e-10, policy="f32ir")
    assert np.asarray(info.converged).all()
    assert np.linalg.norm(A @ np.asarray(x) - b) <= 1e-10
    assert np.allclose(np.asarray(x), np.asarray(x64), atol=1e-9)


def test_ir_f32_request_reaches_beyond_f32():
    """The point of the outer f64 loop: an f32-stored operator still
    solves to an absolute residual plain f32 CG cannot reach."""
    A = _tridiag(96, seed=3, dtype=np.float32)
    b = np.random.default_rng(4).standard_normal(96).astype(np.float32)
    x, info = mixed.ir_solve(A, b, tol=1e-11, policy="f32ir")
    assert np.asarray(info.converged).all()
    r = A.astype(np.float64) @ np.asarray(x, dtype=np.float64) - b.astype(
        np.float64
    )
    assert np.linalg.norm(r) <= 1e-11


def test_ir_bf16_storage_converges_well_conditioned():
    A = _tridiag(64, seed=5)
    b = np.random.default_rng(6).standard_normal(64)
    x, info = mixed.ir_solve(A, b, tol=1e-9, policy="bf16ir")
    assert np.asarray(info.converged).all()
    assert np.linalg.norm(A @ np.asarray(x) - b) <= 1e-9
    assert info.outer >= 2  # bf16 storage genuinely needs refinement


def test_batched_ir_lanes_and_outer_counter():
    A = _tridiag(48, seed=7)
    pat = _pattern(A)
    B = 3
    vals = np.stack([A.data * (1.0 + 0.01 * i) for i in range(B)])
    op = BatchedCSR(pat, vals)
    rhs = np.random.default_rng(8).standard_normal((B, 48))
    before = float(_metrics.counter("mixed.ir_outer_iters").value)
    X, info = batched_ir(op, rhs, tol=1e-9)
    after = float(_metrics.counter("mixed.ir_outer_iters").value)
    assert np.asarray(info.converged).all()
    assert after > before
    for i in range(B):
        Ai = sp.csr_matrix((vals[i], A.indices, A.indptr), shape=A.shape)
        assert np.linalg.norm(Ai @ np.asarray(X[i]) - rhs[i]) <= 1e-9


def test_linalg_ir_entry_point():
    A = _tridiag(48, seed=9)
    b = np.ones(48)
    x, iters = linalg.ir(sparse_tpu.csr_array(A), b, tol=1e-9)
    assert isinstance(iters, int) and iters > 0
    assert np.linalg.norm(A @ np.asarray(x) - b) <= 1e-9
    assert "ir" in linalg.__all__ and "batched_ir" in linalg.__all__


def test_ir_rejects_complex_and_exact():
    A = _tridiag(16).astype(np.complex128)
    with pytest.raises(ValueError):
        mixed.ir_solve(A, np.ones(16, complex), policy="f32ir")
    with pytest.raises(ValueError):
        mixed.ir_solve(_tridiag(16), np.ones(16), policy="exact")


def test_ir_divergence_safeguard_returns_best():
    """A deliberately WRONG low-precision operator (2x the true one)
    cannot contract — the safeguard must freeze at the best iterate,
    finite and unconverged, instead of diverging."""
    from sparse_tpu.ops.spmv import csr_spmv_segment
    from sparse_tpu.utils import asjnp

    A = _tridiag(32, seed=10)
    indptr, indices = asjnp(A.indptr), asjnp(A.indices)

    def mk(vals):
        def mv(X):
            return jax.vmap(
                lambda v: csr_spmv_segment(indptr, indices, vals, v, 32)
            )(X)

        return mv

    mvw = mk(asjnp(A.data))
    mvl = mk(jnp.asarray(2.0 * A.data, dtype=jnp.float32))  # WRONG operator

    b = np.random.default_rng(11).standard_normal(32)
    x, info = mixed.ir_solve((mvw, mvl), b, tol=1e-12, policy="f32ir",
                             max_outer=10)
    r = np.linalg.norm(A @ np.asarray(x) - b)
    assert np.isfinite(r)
    assert not np.asarray(info.converged).all()
    # best iterate beats the trivial x=0 start (one half-step correction)
    assert r < np.linalg.norm(b)


def test_frozen_lane_bit_stability_under_ir():
    """Lane 0 (loose tol) freezes while lane 1 refines; its bits must
    not depend on how long lane 1 keeps the outer loop alive."""
    A = _tridiag(40, seed=12)
    op = BatchedCSR(_pattern(A), np.stack([A.data, A.data]))
    rng = np.random.default_rng(13)
    b0 = rng.standard_normal(40)
    b1 = rng.standard_normal(40)
    b1_alt = rng.standard_normal(40)
    tols = np.asarray([1e-3, 1e-12])
    X_a, _ = batched_ir(op, np.stack([b0, b1]), tol=tols)
    X_b, _ = batched_ir(op, np.stack([b0, b1_alt]), tol=tols)
    assert np.array_equal(np.asarray(X_a[0]), np.asarray(X_b[0]))


# ---------------------------------------------------------------------------
# the f64_oracle fixture (satellite: the table pinned in CI)
# ---------------------------------------------------------------------------
def test_f64_oracle_table_pinned():
    spec = importlib.util.spec_from_file_location(
        "f64_oracle", os.path.join(REPO, "scripts", "f64_oracle.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    row = mod.run(24)  # small grid: the same columns, seconds not minutes
    # plain f32 plateaus orders of magnitude above f64...
    assert row["rel_resid_f32"] > 100 * row["rel_resid_f64"]
    # ...while the IR solver matches the f64 target it was driven to
    assert row["f32ir_converged"]
    assert row["rel_resid_f32ir"] <= max(row["rel_resid_f64"] * 1.01, 1e-12)
    assert row["bf16ir_converged"]
    assert row["rel_resid_bf16ir"] <= max(row["rel_resid_f64"] * 1.01, 1e-12)
    assert row["f32ir_outer"] >= 1 and row["f32ir_inner_iters"] > 0


# ---------------------------------------------------------------------------
# kernel storage/accumulation splits
# ---------------------------------------------------------------------------
def test_sell_spmv_acc_dtype_widening():
    from sparse_tpu.ops import spmv as spmv_ops

    A = _tridiag(64, seed=14)
    pat = _pattern(A)
    pack = pat.sell_pack()
    x = np.random.default_rng(15).standard_normal(64)
    y64 = A @ x
    vals_bf = pack.pack_values(
        jnp.asarray(A.data, dtype=jnp.float32)[None].astype(jnp.bfloat16)
    )
    y = spmv_ops.csr_spmv_sell_batched(
        pack.idx_slabs, vals_bf, pack.pos,
        jnp.asarray(x, dtype=jnp.float32)[None], pack.plan.zero_rows,
        acc_dtype=jnp.float32,
    )
    assert y.dtype == jnp.float32
    rel = np.abs(np.asarray(y[0]) - y64).max() / np.abs(y64).max()
    assert rel < 2e-2  # bf16 storage error, not accumulation error


def test_segment_spmv_acc_dtype():
    from sparse_tpu.ops.spmv import csr_spmv_segment
    from sparse_tpu.utils import asjnp

    A = _tridiag(48, seed=16)
    x = np.random.default_rng(17).standard_normal(48)
    vals_bf = jnp.asarray(A.data, dtype=jnp.float32).astype(jnp.bfloat16)
    y = csr_spmv_segment(
        asjnp(A.indptr), asjnp(A.indices), vals_bf,
        jnp.asarray(x, dtype=jnp.float32), 48, acc_dtype=jnp.float32,
    )
    assert y.dtype == jnp.float32
    rel = np.abs(np.asarray(y) - A @ x).max() / np.abs(A @ x).max()
    assert rel < 2e-2
    # default path unchanged: no acc_dtype => result_type behavior
    y64 = csr_spmv_segment(
        asjnp(A.indptr), asjnp(A.indices), asjnp(A.data), asjnp(x), 48
    )
    assert y64.dtype == jnp.float64


def test_dia_spmv_acc_dtype():
    from sparse_tpu.ops.dia_spmv import dia_spmv_xla

    n = 32
    e = np.ones(n)
    data = np.stack([-e, 3.0 * e, -e])
    offsets = (-1, 0, 1)
    x = np.random.default_rng(18).standard_normal(n)
    y64 = np.asarray(dia_spmv_xla(jnp.asarray(data), offsets,
                                  jnp.asarray(x), (n, n)))
    y = dia_spmv_xla(
        jnp.asarray(data, dtype=jnp.float32).astype(jnp.bfloat16), offsets,
        jnp.asarray(x, dtype=jnp.float32), (n, n),
        acc_dtype=jnp.float32,
    )
    assert y.dtype == jnp.float32
    assert np.abs(np.asarray(y) - y64).max() / np.abs(y64).max() < 2e-2


def test_cg_dia_fused_acc_dtype_noop_is_identical():
    """acc_dtype=None vs acc_dtype=<the vector dtype> must be the SAME
    program numerically (the no-op convert contract)."""
    from sparse_tpu.kernels.cg_dia import cg_dia_fused

    n = 64
    e = np.ones(n)
    data = jnp.asarray(np.stack([-e, 3.0 * e, -e]))
    b = jnp.asarray(np.random.default_rng(19).standard_normal(n))
    x1, r1, rho1 = cg_dia_fused(data, (-1, 0, 1), b, None, n, iters=20,
                                interpret=True)
    x2, r2, rho2 = cg_dia_fused(data, (-1, 0, 1), b, None, n, iters=20,
                                interpret=True, acc_dtype=jnp.float64)
    assert np.array_equal(np.asarray(x1), np.asarray(x2))
    assert float(rho1) == float(rho2)


def test_cg_dia_fused_wide_scalars_for_f32():
    """f32 vectors with f64 recurrence scalars: the dot partials carry
    f64 and the iterates stay close to the all-f64 run."""
    from sparse_tpu.kernels.cg_dia import cg_dia_fused

    n = 64
    e = np.ones(n)
    data64 = jnp.asarray(np.stack([-e, 3.0 * e, -e]))
    b64 = jnp.asarray(np.random.default_rng(20).standard_normal(n))
    x64, _, _ = cg_dia_fused(data64, (-1, 0, 1), b64, None, n, iters=30,
                             interpret=True)
    x32, _, rho32 = cg_dia_fused(
        data64.astype(jnp.float32), (-1, 0, 1), b64.astype(jnp.float32),
        None, n, iters=30, interpret=True, acc_dtype=jnp.float64,
    )
    assert rho32.dtype == jnp.float64
    assert np.abs(np.asarray(x32) - np.asarray(x64)).max() < 1e-4


# ---------------------------------------------------------------------------
# serving integration: keys, invariance, promote rung, vault
# ---------------------------------------------------------------------------
def test_session_program_keys_and_per_ticket_override():
    A = _tridiag(32, seed=21)
    b = np.ones(32)
    _cost.reset()
    ses = SolveSession("cg", warm_start=False, dtype_policy="f32ir")
    t1 = ses.submit(A, b, tol=1e-9, maxiter=2000)
    t2 = ses.submit(A, b, tol=1e-9, maxiter=2000, dtype_policy="exact")
    ses.flush()
    for t in (t1, t2):
        x, _i, r2 = t.result()
        assert np.sqrt(r2) <= 1e-9 * 1.01
    keys = set(_cost.programs())
    assert "batch.cg.B1.<f8.Pf32ir" in keys
    assert "batch.cg.B1.<f8" in keys  # the exact override: historic key


def test_exact_policy_is_bit_identical_to_default():
    A = _tridiag(32, seed=22)
    b = np.random.default_rng(23).standard_normal(32)
    _cost.reset()
    ses_d = SolveSession("cg", warm_start=False)
    td = ses_d.submit(A, b, tol=1e-9, maxiter=2000)
    ses_d.flush()
    ses_e = SolveSession("cg", warm_start=False, dtype_policy="exact")
    te = ses_e.submit(A, b, tol=1e-9, maxiter=2000)
    ses_e.flush()
    xd, id_, rd = td.result()
    xe, ie, re_ = te.result()
    assert np.array_equal(np.asarray(xd), np.asarray(xe))
    assert id_ == ie and rd == re_
    # one shared historic key — no .P suffix anywhere
    assert set(_cost.programs()) == {"batch.cg.B1.<f8"}


def test_ir_bucket_program_solves_and_counts_outer():
    A = _tridiag(48, seed=24)
    mats = [A.copy() for _ in range(4)]
    for i, m in enumerate(mats):
        m.setdiag(m.diagonal() + 0.01 * i)
    rhs = np.random.default_rng(25).standard_normal((4, 48))
    before = float(_metrics.counter("mixed.ir_outer_iters").value)
    ses = SolveSession("cg", warm_start=False, dtype_policy="f32ir")
    X, iters, r2 = ses.solve_many(mats, rhs, tol=1e-9, maxiter=4000)
    after = float(_metrics.counter("mixed.ir_outer_iters").value)
    assert after > before
    for i, m in enumerate(mats):
        assert np.linalg.norm(m @ X[i] - rhs[i]) <= 1e-9 * 1.5


def test_promote_dtype_rung_end_to_end():
    """Injected corruption in the inner f32 sweep: the promote rung
    requeues at exact (same solver), the ticket converges, and the
    group is pinned so later dispatches are exact."""
    A = _tridiag(64, seed=26)
    b = np.random.default_rng(27).standard_normal(64)
    settings.telemetry = True
    faults.configure("nonfinite:matvec:p=1,n=6,seed=3")

    def promos():
        # the divergence safeguard reports a NaN-corrupted lane as
        # unconverged-with-finite-best-residual, so either reason is a
        # correct classification of the injected anomaly
        return sum(
            float(_metrics.counter("mixed.promotions", reason=r).value)
            for r in ("nonfinite", "unconverged")
        )

    before = promos()
    try:
        ses = SolveSession("cg", warm_start=False, dtype_policy="f32ir")
        t = ses.submit(A, b, tol=1e-9, maxiter=4000)
        ses.flush()
        x, _i, _r = t.result()
    finally:
        faults.clear()
    assert t.converged and t.promoted
    assert np.linalg.norm(A @ np.asarray(x) - b) <= 1e-9 * 1.5
    assert promos() - before == 1
    kinds = [e.get("kind") for e in telemetry.events()]
    assert "mixed.promote" in kinds
    actions = [e.get("action") for e in telemetry.events()
               if e.get("kind") == "batch.requeue"]
    assert "promote_dtype" in actions
    # the group is pinned: the next dispatch resolves exact
    pat = ses.pattern_of(A)
    assert ses.dtype_policy.decide(pat, "cg", 1, np.float64) == "exact"


def test_ticket_event_carries_dtype_policy_label():
    A = _tridiag(32, seed=28)
    settings.telemetry = True
    ses = SolveSession("cg", warm_start=False, dtype_policy="f32ir")
    t = ses.submit(A, np.ones(32), tol=1e-9, maxiter=2000)
    ses.flush()
    t.result()
    ev = [e for e in telemetry.events() if e.get("kind") == "batch.ticket"]
    assert ev and ev[-1]["dtype_policy"] == "f32ir"
    assert ev[-1]["promoted"] is False
    # exact tickets keep the historic event shape (no dtype_policy key)
    telemetry.reset()
    ses2 = SolveSession("cg", warm_start=False)
    t2 = ses2.submit(A, np.ones(32), tol=1e-9, maxiter=2000)
    ses2.flush()
    t2.result()
    ev2 = [e for e in telemetry.events() if e.get("kind") == "batch.ticket"]
    assert ev2 and "dtype_policy" not in ev2[-1]


def test_vault_manifest_precision_keyed_warm_restart(tmp_path):
    A = _tridiag(48, seed=29)
    b = np.random.default_rng(30).standard_normal(48)
    settings.vault = str(tmp_path / "vault")
    ses = SolveSession("cg", warm_start=False, dtype_policy="f32ir")
    t = ses.submit(A, b, tol=1e-9, maxiter=4000)
    ses.flush()
    t.result()
    entries = vault.manifest_entries()
    assert any(e.get("dtype_policy") == "f32ir" for e in entries)
    plan_cache.clear()
    ses2 = SolveSession("cg", warm_start=True, warm_async=False,
                        dtype_policy="f32ir")
    assert ses2.warm_replayed >= 1
    snap = plan_cache.snapshot()
    t2 = ses2.submit(A, b, tol=1e-9, maxiter=4000)
    ses2.flush()
    x2, _i, _r = t2.result()
    assert plan_cache.delta(snap)["misses"] == 0
    assert np.linalg.norm(A @ np.asarray(x2) - b) <= 1e-9 * 1.5


def test_session_stats_dtype_policy_block():
    ses = SolveSession("cg", warm_start=False, dtype_policy="f32ir")
    blk = ses.session_stats()["dtype_policy"]
    assert blk["mode"] == "f32ir" and blk["enabled"]
    ses2 = SolveSession("cg", warm_start=False)
    assert ses2.session_stats()["dtype_policy"]["mode"] == "exact"


def test_schema_kind_registered_and_validates():
    from sparse_tpu.telemetry import _schema

    assert "mixed.promote" in _schema.KINDS
    ev = {"kind": "mixed.promote", "ts": 1.0, "reason": "nonfinite",
          "lanes": 2}
    assert _schema.validate(ev) == []
    assert _schema.validate({"kind": "mixed.promote", "ts": 1.0})
