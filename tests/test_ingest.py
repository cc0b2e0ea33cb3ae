"""sparse_tpu.ingest — streaming matrix ingestion data plane (ISSUE 18).

Pins the subsystem's contract pillars:

* **sort parity** — the mesh-sharded samplesort COO->CSR
  (:func:`ingest_coo_to_csr`) matches the scipy host oracle bit-for-bit
  on indices and to fp tolerance on summed duplicate values, in f32 and
  f64, on both the single-device fast path and the distributed path;
* **fingerprinting** — :func:`structure_key` is permutation/value
  invariant, equals ``SparsityPattern.fingerprint[2]`` exactly, and the
  dedup path is observable: a structural re-arrival reports
  ``dedup=True`` and its first solve costs ZERO new plan-cache misses
  (the PR's acceptance criterion);
* **balance()** — nnz-balanced row bounds beat uniform row splits on a
  skewed profile and are always a valid monotone partition;
* **background onboarding** — `SolveSession.ingest` returns a
  future-style ticket, an onboard racing the first solve of the same
  structure converges on ONE canonical pattern object, and the
  admission bound rejects/blocks at ``max_depth``;
* **streaming IO** — :func:`sparse_tpu.io.read_coo_host` (chunked
  :func:`stream_coo`) matches ``scipy.io.mmread`` on every testdata
  file plus symmetric-expansion and pattern-only bodies, at chunk sizes
  that force multi-chunk parses;
* **telemetry** — the four ``ingest.*`` event kinds are registered in
  the schema and every event a live run emits validates against it;
* **loadgen** — the ``ingest`` trace clause round-trips through
  parse/describe, and ``build_report`` rolls onboarding latency
  percentiles separately from the solve latencies.
"""

import json

import numpy as np
import pytest
import scipy.io as sci_io
import scipy.sparse as sp

import sparse_tpu as sparse
from sparse_tpu import plan_cache, telemetry
from sparse_tpu.batch import SolveSession
from sparse_tpu.config import settings
from sparse_tpu.ingest import (
    FingerprintIndex,
    IngestAdmissionError,
    Onboarder,
    balance,
    balance_stats,
    ingest_coo_to_csr,
    structure_key,
)
from sparse_tpu.ingest.fingerprint import canonicalize_coo
from sparse_tpu.loadgen import ArrivalTrace, build_report

from .utils.common import test_mtx_files


@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield tmp_path / "records.jsonl"
    telemetry.configure(None)
    telemetry.reset()


def _random_coo(n=40, k=160, seed=0, dtype=np.float64, dups=True):
    """Unsorted COO with duplicate coordinates (when ``dups``)."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, size=k)
    cols = rng.integers(0, n, size=k)
    if dups:  # force at least a few exact duplicates
        rows[: k // 8] = rows[k // 2 : k // 2 + k // 8]
        cols[: k // 8] = cols[k // 2 : k // 2 + k // 8]
    vals = rng.standard_normal(k).astype(dtype)
    return rows, cols, vals, (n, n)


def _spd_coo(n=24, seed=0):
    """Diagonally-dominant symmetric COO (CG-solvable)."""
    rng = np.random.default_rng(seed)
    k = 2 * n
    r = rng.integers(0, n, size=k)
    c = rng.integers(0, n, size=k)
    v = 0.1 * rng.standard_normal(k)
    d = np.arange(n)
    rows = np.concatenate([d, r, c])
    cols = np.concatenate([d, c, r])
    vals = np.concatenate([np.full(n, float(n)), v, v])
    return rows, cols, vals, (n, n)


# ---------------------------------------------------------------------------
# samplesort COO -> CSR parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("num_shards", [1, 4])
def test_sort_parity_vs_host_oracle(dtype, num_shards):
    rows, cols, vals, shape = _random_coo(seed=3, dtype=dtype)
    got = ingest_coo_to_csr(rows, cols, vals, shape, num_shards=num_shards)
    ref = sp.coo_matrix((vals, (rows, cols)), shape=shape).tocsr()
    ref.sum_duplicates()
    ref.sort_indices()
    assert got.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(got.indptr), ref.indptr)
    np.testing.assert_array_equal(np.asarray(got.indices), ref.indices)
    tol = 1e-5 if dtype == np.float32 else 1e-12
    np.testing.assert_allclose(np.asarray(got.data), ref.data, atol=tol)


def test_sort_empty_and_validation():
    got = ingest_coo_to_csr(
        np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros(0), (5, 7)
    )
    assert got.shape == (5, 7) and got.nnz == 0
    with pytest.raises(ValueError):
        ingest_coo_to_csr(np.array([0, 1]), np.array([0]), np.array([1.0]),
                          (2, 2))


# ---------------------------------------------------------------------------
# fingerprinting + dedup
# ---------------------------------------------------------------------------


def test_structure_key_permutation_and_value_invariant():
    rows, cols, vals, shape = _random_coo(seed=5)
    k1 = structure_key(rows, cols, shape)
    perm = np.random.default_rng(0).permutation(rows.shape[0])
    k2 = structure_key(rows[perm], cols[perm], shape)
    assert k1 == k2  # order never matters
    # values never matter — and the key matches the live pattern's
    csr = ingest_coo_to_csr(rows, cols, vals, shape)
    from sparse_tpu.batch.operator import SparsityPattern

    pat = SparsityPattern.from_csr(csr)
    assert pat.fingerprint[2] == k1
    # different structure -> different key
    k3 = structure_key(rows, (cols + 1) % shape[1], shape)
    assert k3 != k1


def test_canonicalize_dedups_by_sum():
    rows = np.array([1, 0, 1, 1])
    cols = np.array([2, 0, 2, 0])
    vals = np.array([1.5, 2.0, 2.5, -1.0])
    crows, ccols, cvals = canonicalize_coo(rows, cols, vals, (3, 3))
    np.testing.assert_array_equal(crows, [0, 1, 1])
    np.testing.assert_array_equal(ccols, [0, 0, 2])
    np.testing.assert_allclose(cvals, [2.0, -1.0, 4.0])
    with pytest.raises(ValueError):
        canonicalize_coo(np.array([3]), np.array([0]), None, (3, 3))


def test_fingerprint_index_note_and_lookup():
    idx = FingerprintIndex(autoload=False)
    assert idx.lookup("abc") is None
    idx.note("abc", "p123")
    assert idx.lookup("abc") == "p123"
    assert len(idx) == 1
    assert idx.snapshot() == {"abc": "p123"}


# ---------------------------------------------------------------------------
# balance(): nnz-balanced row resharding
# ---------------------------------------------------------------------------


def test_balance_beats_uniform_on_skew():
    # front-loaded profile: first rows hold almost all the nnz
    counts = np.zeros(64, np.int64)
    counts[:8] = 120
    counts[8:] = 2
    indptr = np.concatenate([[0], np.cumsum(counts)])
    bounds = balance(indptr, 8)
    assert bounds[0] == 0 and bounds[-1] == 64
    assert np.all(np.diff(bounds) >= 0)
    st = balance_stats(indptr, 8)
    assert st["balanced_imbalance"] < st["uniform_imbalance"]
    # uniform row splits put 8x the ideal nnz on shard 0; balanced
    # bounds stay within one heavy row of the ideal
    assert st["uniform_imbalance"] > 7.0
    assert st["balanced_imbalance"] < 2.0


def test_balance_uniform_profile_is_even():
    indptr = np.arange(0, 33 * 4, 4)  # 32 rows x 4 nnz
    bounds = balance(indptr, 4)
    np.testing.assert_array_equal(bounds, [0, 8, 16, 24, 32])


# ---------------------------------------------------------------------------
# background onboarding through SolveSession.ingest
# ---------------------------------------------------------------------------


def test_ingest_cold_then_dedup_zero_plan_misses():
    src = _spd_coo(n=24, seed=11)
    sess = SolveSession(solver="cg")
    try:
        out = sess.ingest(src, wait=True, timeout=180.0).result()
        assert out["state"] == "ready" and out["dedup"] is False
        pat = out["pattern"]
        assert pat.fingerprint in sess._patterns
        assert "ingest" in sess.session_stats()

        # structural re-arrival (same pattern, new values): dedup hit,
        # and its first solve costs zero new plan-cache compiles
        rows, cols, vals, shape = src
        src2 = (rows, cols, vals * 1.5, shape)
        snap = plan_cache.snapshot()
        out2 = sess.ingest(src2, wait=True, timeout=60.0).result()
        assert out2["dedup"] is True
        assert out2["pattern"] is pat  # the SAME canonical object
        b = np.ones(shape[0])
        tk = sess.submit(out2["csr"], b, tol=1e-9)
        sess.drain()
        x = np.asarray(tk.result()[0])
        A = sp.csr_matrix(
            (np.asarray(out2["csr"].data), np.asarray(out2["csr"].indices),
             np.asarray(out2["csr"].indptr)), shape=shape,
        )
        np.testing.assert_allclose(A @ x, b, atol=1e-6)
        assert plan_cache.delta(snap)["misses"] == 0
    finally:
        sess._onboarder.close()


def test_onboard_races_first_solve_converges():
    rows, cols, vals, shape = _spd_coo(n=20, seed=13)
    A = sp.csr_matrix(
        sp.coo_matrix((vals, (rows, cols)), shape=shape)
    )
    A.sum_duplicates()
    A.sort_indices()
    sess = SolveSession(solver="cg")
    try:
        t = sess.ingest((rows, cols, vals, shape))  # background
        b = np.ones(shape[0])
        tk = sess.submit(sparse.csr_array(A), b, tol=1e-9)
        sess.flush()
        x = np.asarray(tk.result()[0])
        np.testing.assert_allclose(A @ x, b, atol=1e-6)
        assert t.wait(timeout=180.0)
        out = t.result()
        # both sides raced _patterns.setdefault: ONE canonical pattern
        fp = out["pattern"].fingerprint
        assert sess._patterns[fp] is out["pattern"]
        assert sum(1 for k in sess._patterns if k == fp) == 1
    finally:
        sess._onboarder.close()


class _Blocker:
    """tocoo() blocks until released — pins the worker mid-item."""

    def __init__(self):
        import threading

        self.release = threading.Event()

    def tocoo(self):
        self.release.wait(30.0)
        c = sp.coo_matrix(np.eye(3))
        return c


def test_admission_bound_rejects_at_depth():
    import time

    sess = SolveSession(solver="cg")
    onb = Onboarder(sess, max_depth=1, admission="reject", retries=0)
    try:
        blocker = _Blocker()
        t1 = onb.submit(blocker)
        deadline = time.monotonic() + 10.0
        while onb.stats()["active"] != 1:  # worker picked up the blocker
            assert time.monotonic() < deadline
            time.sleep(0.005)
        t2 = onb.submit(_spd_coo(n=6, seed=1))  # fills the queue
        with pytest.raises(IngestAdmissionError):
            onb.submit(_spd_coo(n=7, seed=2))
        assert onb.stats()["queued"] == 1
        blocker.release.set()
        assert t1.wait(timeout=180.0) and t2.wait(timeout=180.0)
        assert t1.state == "ready" and t2.state == "ready"
    finally:
        onb.close()
        if sess._onboarder is not None:
            sess._onboarder.close()


def test_failed_arrival_retries_then_raises():
    sess = SolveSession(solver="cg")
    onb = Onboarder(sess, retries=1)
    try:
        t = onb.submit(object())  # not ingestable
        assert t.wait(timeout=30.0)
        assert t.state == "failed"
        with pytest.raises(Exception, match="failed after 2 attempts"):
            t.result()
        assert onb.stats()["failed"] == 1
        assert onb.stats()["retries"] == 1
    finally:
        onb.close()
        if sess._onboarder is not None:
            sess._onboarder.close()


# ---------------------------------------------------------------------------
# streaming MatrixMarket IO vs the scipy oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("filename", test_mtx_files)
@pytest.mark.parametrize("chunk_nnz", [3, 1 << 20])
def test_read_coo_host_parity(filename, chunk_nnz):
    rows, cols, vals, shape = sparse.io.read_coo_host(
        filename, chunk_nnz=chunk_nnz
    )
    ref = sci_io.mmread(filename)
    got = sp.coo_matrix((vals, (rows, cols)), shape=shape)
    assert got.shape == ref.shape
    assert np.allclose(got.toarray(), ref.toarray())


def test_stream_coo_symmetric_and_pattern(tmp_path):
    p1 = tmp_path / "sym.mtx"
    p1.write_text(
        "%%MatrixMarket matrix coordinate real symmetric\n"
        "% comment line\n"
        "3 3 4\n1 1 2.0\n2 1 -1.0\n3 2 0.5\n3 3 4.0\n"
    )
    rows, cols, vals, shape = sparse.io.read_coo_host(str(p1), chunk_nnz=2)
    got = sp.coo_matrix((vals, (rows, cols)), shape=shape).toarray()
    ref = sci_io.mmread(str(p1)).toarray()
    assert np.allclose(got, ref)

    p2 = tmp_path / "pat.mtx"
    p2.write_text(
        "%%MatrixMarket matrix coordinate pattern general\n"
        "2 4 3\n1 2\n2 1\n2 4\n"
    )
    rows, cols, vals, shape = sparse.io.read_coo_host(str(p2), chunk_nnz=2)
    got = sp.coo_matrix((vals, (rows, cols)), shape=shape).toarray()
    ref = sci_io.mmread(str(p2)).toarray()
    assert np.allclose(got, ref)


def test_stream_coo_rejects_bad_bodies(tmp_path):
    p = tmp_path / "short.mtx"
    p.write_text(
        "%%MatrixMarket matrix coordinate real general\n"
        "2 2 3\n1 1 1.0\n2 2 2.0\n"
    )
    with pytest.raises(ValueError, match="expected 3"):
        list(sparse.io.stream_coo(str(p)))
    p2 = tmp_path / "arr.mtx"
    p2.write_text("%%MatrixMarket matrix array real general\n1 1\n1.0\n")
    with pytest.raises(ValueError, match="coordinate"):
        list(sparse.io.stream_coo(str(p2)))
    # read_coo_host falls back to the dense decoder for array files
    rows, cols, vals, shape = sparse.io.read_coo_host(str(p2))
    assert shape == (1, 1) and vals[0] == 1.0


def test_ingest_from_mtx_path(tmp_path, tel):
    rows, cols, vals, shape = _spd_coo(n=10, seed=3)
    A = sp.coo_matrix((vals, (rows, cols)), shape=shape)
    A.sum_duplicates()
    path = tmp_path / "arrival.mtx"
    sci_io.mmwrite(str(path), A)
    sess = SolveSession(solver="cg")
    try:
        out = sess.ingest(str(path), wait=True, timeout=180.0).result()
        assert out["state"] == "ready"
        got = sp.csr_matrix(
            (np.asarray(out["csr"].data), np.asarray(out["csr"].indices),
             np.asarray(out["csr"].indptr)), shape=shape,
        )
        assert np.allclose(got.toarray(), A.toarray())
    finally:
        sess._onboarder.close()
    # every emitted ingest.* event validates against the schema
    from sparse_tpu.telemetry import _schema

    for kind in ("ingest.arrive", "ingest.sort", "ingest.dedup",
                 "ingest.onboard"):
        assert kind in _schema.KINDS
    telemetry.flush()
    events = [json.loads(ln) for ln in tel.read_text().splitlines()]
    ingest_events = [e for e in events if e["kind"].startswith("ingest.")]
    kinds = {e["kind"] for e in ingest_events}
    assert {"ingest.arrive", "ingest.sort", "ingest.dedup",
            "ingest.onboard"} <= kinds
    for e in ingest_events:
        _schema.validate(e)


# ---------------------------------------------------------------------------
# loadgen: the ingest arrival clause + onboard report rollup
# ---------------------------------------------------------------------------


def test_trace_ingest_clause_roundtrip():
    spec = "poisson:rate=8,duration=1,seed=2;ingest:rate=3,duration=1,seed=5,size=32"
    tr = ArrivalTrace.parse(spec)
    kinds = [a.kind for a in tr.arrivals]
    assert "ingest" in kinds and "solve" in kinds
    for a in tr.arrivals:
        if a.kind == "ingest":
            assert a.size == 32 and a.tenant == "ingest"
    # describe() -> parse() is a fixed point
    again = ArrivalTrace.parse(tr.describe())
    assert again.describe() == tr.describe()
    assert [(a.t, a.kind, a.size) for a in again.arrivals] == [
        (a.t, a.kind, a.size) for a in tr.arrivals
    ]
    with pytest.raises(Exception):
        ArrivalTrace.parse("ingest:rate=1,duration=1,size=1")  # size < 2


def test_build_report_onboard_rollup():
    tr = ArrivalTrace.parse(
        "poisson:rate=10,duration=1,seed=0;ingest:rate=2,duration=1,seed=1"
    )
    n_solve = sum(1 for a in tr.arrivals if a.kind == "solve")
    outcomes = [("", 0.010, True, False)] * n_solve
    onboard = [(250.0, True, False), (40.0, True, True),
               (None, False, False)]
    rep = build_report(tr, outcomes, wall_s=1.0, slo_ms=100.0,
                       onboard=onboard, onboard_rejected=1)
    assert rep.onboard["arrivals"] == 4
    assert rep.onboard["completed"] == 2
    assert rep.onboard["failed"] == 2
    assert rep.onboard["dedup_hits"] == 1
    assert rep.onboard["latency_ms"]["max"] == 250.0
    assert rep.onboard["latency_ms"]["p50"] in (40.0, 250.0)
    # onboarding never leaks into the solve rollup
    assert rep.completed == n_solve
    assert rep.slo_misses == 0
    # offered counts solve arrivals only
    assert rep.offered_rps == round(n_solve / 1.0, 3)
    assert "ingest" not in rep.tenants
    d = rep.as_dict()
    assert d["onboard"]["latency_ms"]["p95"] == 250.0
    # no ingest clause -> empty rollup
    tr2 = ArrivalTrace.parse("poisson:rate=5,duration=1,seed=0")
    rep2 = build_report(tr2, [("", 0.01, True, False)], wall_s=1.0)
    assert rep2.onboard == {}
