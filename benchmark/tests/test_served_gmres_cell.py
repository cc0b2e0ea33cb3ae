"""The cell ``nonsym_served_gmres_closed`` on the CPU: what its configuration
states against the generator, the plain reference against a direct solve, the
adaptor's guarantees (a tree whose GMRES bucket is driven from the host is
refused before the ramp), a rehearsal sound and with the bucket program's
answer broken or stale underneath, and the cell's new metric files on
hand-made runs. (test_benchmark.py runs the rehearsal, the control and the
broken tickets for every cell of BENCHMARK.json in processes of their own.)"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402

CELL = "nonsym_served_gmres_closed"
NEW = {"served_gmres_roofline": ("device_trace", "kernels", "%"),
       "served_gmres_orth_pct": ("device_trace", "kernels", "%"),
       "served_gmres_spmv_pct": ("device_trace", "kernels", "%"),
       "served_gmres_fetches_per_bucket": ("program_counter", "session",
                                           "count"),
       "served_gmres_frozen_lane_pct": ("program_counter", "session", "%")}
APPENDED = ("dispatch_solve_ms", "ticket_queue_ms_p95", "pad_lane_pct",
            "operator_build_s")
op = manifest.load_module("operators", "cfd_step")
base = manifest.load_module("operators", "cfd_7pt")


def small():
    return manifest.cell(CELL, rehearse=True)["config"]


def test_the_cell_resolves_to_its_files_and_metrics():
    res = manifest.cell(CELL)
    wl, cfg = res["workload"], res["config"]
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "atmos-step-gmres30", "closed_64", 1)
    assert (cfg["operator"], cfg["system"]) == ("cfd_step",
                                                "solve_session_gmres")
    assert cfg["session"] == {"solver": "gmres", "restart": 30,
                              "batch_max": 32}
    t = res["traffic"]
    assert (t["loop"], t["clients"], t["trace_seconds"], t["rehearse"]) == (
        "closed", 64, 9.0, {"clients": 8})
    # two buckets in rotation, as closed_128 is over 64 lanes
    assert t["clients"] == 2 * cfg["session"]["batch_max"] == cfg["sizes"]["clients"]
    assert [m["name"] for m in res["end_to_end"]] == [
        "setup_s", "solves_per_s", "ticket_p95_ms"]
    assert {m["name"] for m in res["per_layer"]} == {*APPENDED, *NEW}
    for name, (source, layer, unit) in NEW.items():
        m = next(m for m in res["per_layer"] if m["name"] == name)
        assert (m["source"], m["layer"], m["unit"], m["moves"],
                m["workloads"]) == (source, layer, unit, "solves_per_s", [CELL])
    # appended, never put in the middle; the lists the other tests pin are
    # left as they were
    bm = manifest.benchmark()
    assert bm["workloads"][-1]["name"] == CELL
    assert bm["configs"][-1]["name"] == cfg["name"]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL


def test_the_configuration_states_atmosmodds_box_and_what_a_bucket_holds():
    res = manifest.cell(CELL)
    cfg = res["config"]
    lib = manifest.cell("nonsym_gmres_1chip")["config"]
    assert cfg["sizes"]["box"] == lib["sizes"]["box"] == [148, 148, 58]
    assert base.counts(cfg["sizes"]["box"]) == (1_270_432, 8_814_880)
    assert cfg["sizes"]["restart"] == cfg["session"]["restart"] == 30
    assert list(cfg["reduced"]) == ["dtype", "check_sample"]
    listed = next(c for c in manifest.benchmark()["configs"]
                  if c["name"] == cfg["name"])
    assert (listed["source"], listed["reduced"]) == (
        cfg["source"], ["dtype", "check_sample"])
    assert "1,270,432" in cfg["source"] and "1.27M" in res["workload"]["why"]
    for key in ("shift", "batch_max", "rel_tol", "values", "source_term"):
        assert cfg["assumed"][key]
    assert cfg["limits"]["relres_over_asked"] == 2.0
    assert cfg["sizes"]["rel_tol"] == 1e-5 and cfg["sizes"]["shift"] == 0.125
    # the issue's 16, cut by its own rule (a cold traced run inside 85 s)
    assert cfg["sizes"]["check_sample"] == 12
    assert cfg["limits_why"] and len(cfg["guarantees"]) >= 6


def test_one_pattern_each_members_own_wind_and_a_source_that_moves():
    sizes = small()["sizes"]
    a, b = op.make(sizes, 1), op.make(sizes, 2147483659)
    P = a["pattern"]
    n, nnz = base.counts(sizes["box"])
    assert (a["rows"], a["nnz"], P.shape) == (n, nnz, (n, n))
    assert P.has_sorted_indices
    assert np.array_equal(P.indptr, b["pattern"].indptr)
    assert np.array_equal(P.indices, b["pattern"].indices)
    assert a["values"].shape == (sizes["clients"], nnz)
    assert a["values"].dtype == a["initial"].dtype == np.float32
    assert not np.array_equal(a["values"], b["values"])
    assert not np.array_equal(a["values"][0], a["values"][1])
    # a member's matrix: cfd_7pt's for the member's seed, s on the diagonal
    for k in (0, sizes["clients"] - 1):
        one = base.make({"box": sizes["box"], "restart": 30, "cycles": 1},
                        op.member_seed(1, k))
        A = sp.csr_matrix((a["values"][k], P.indices, P.indptr), shape=(n, n))
        B = sp.csr_matrix((one["data"], one["indices"], one["indptr"]),
                          shape=(n, n))
        D = (A - B).tocsr()
        D.eliminate_zeros()
        assert D.nnz == n and np.allclose(D.diagonal(), sizes["shift"])
        # not symmetric, and every row diagonally dominant by the shift
        assert abs(A - A.T).max() > 0.1
        rows = np.asarray(abs(A).sum(axis=1)).ravel() - 2 * A.diagonal()
        assert np.all(rows <= -sizes["shift"] + 1e-5)
    assert a["carry"] == sizes["shift"]
    same = op.make(sizes, 1)
    assert all(np.array_equal(a[k], same[k]) for k in ("values", "initial"))
    # the source is another draw at every read, the same in every run
    s0, s1 = a["source"][0], a["source"][0]
    assert not np.array_equal(s0, s1) and np.array_equal(s0, same["source"][0])
    assert abs(float(np.dot(s0, s1))) < 0.2 * float(np.dot(s0, s0))
    assert sorted(s0) == sorted(s1) and len(a["source"]) == sizes["clients"]


def test_reference_converges_to_the_direct_solution_and_residuals_are_true():
    d = op.make(small()["sizes"], 5)
    P = d["pattern"]
    for k in (0, 3):
        b = np.float32(d["carry"]) * d["initial"][k] + d["source"][k]
        x = op.reference_gmres(d, k, b)
        assert x.shape == b.shape and x.dtype == np.float32
        A = sp.csr_matrix((d["values"][k].astype(np.float64), P.indices,
                           P.indptr), shape=P.shape)
        exact = spla.spsolve(A.tocsc(), b.astype(np.float64))
        assert np.linalg.norm(x - exact) <= 5e-6 * np.linalg.norm(exact)
        assert np.allclose(op.apply_f64(d, d["values"][k], exact), A @ exact,
                           rtol=1e-12)
        assert op.true_relres(d, exact, d["values"][k], b) < 1e-12
        # the control, in bfloat16, is two digits from it and more
        low = op.reference_gmres(d, k, b, dtype="bfloat16")
        assert np.linalg.norm(low - exact) >= 3e-3 * np.linalg.norm(exact)
    assert op.true_relres(d, np.zeros(d["rows"]), d["values"][0], b) == (
        pytest.approx(1.0))


def test_the_stated_kappa_is_of_the_order_of_the_rehearsal_matrices():
    """``kappa`` is an estimate, not a bound: on the rehearsal box the
    members' 2-norm condition numbers lie under it."""
    cfg = small()
    d = op.make(cfg["sizes"], 3)
    P = d["pattern"]
    A = sp.csr_matrix((d["values"][0].astype(np.float64), P.indices,
                       P.indptr), shape=P.shape)
    top = spla.svds(A, k=1, which="LM", return_singular_vectors=False)[0]
    low = 1.0 / spla.svds(spla.LinearOperator(
        A.shape, matvec=spla.factorized(A.tocsc()),
        rmatvec=spla.factorized(A.T.tocsc())), k=1, which="LM",
        return_singular_vectors=False)[0]
    s = cfg["sizes"]["shift"]
    assert s < low and top < s + 12.0
    assert 10.0 < top / low < 100.0


def rehearse(seed, seconds=0.3, trace=0):
    """One rehearsal of the cell in this process: (run record, checks by
    name)."""
    import run as harness

    seen = {}
    ns = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                            trace=trace, rehearse=True)
    code, line = harness.run_cell(ns, on_result=seen.update)
    assert code == 1 and line["correct"] is False  # for want of a chip
    return seen, {c["name"]: c for c in seen["checks"]}


def test_a_rehearsal_is_correct_and_the_control_is_not():
    run, checks = rehearse(2147483693)
    assert run["checks_ok"], checks
    assert {"session_matvec_not_planes", "answers_past_two_cycles",
            "gmres_traces_in_window", "x_vs_reference", "relres_over_asked",
            "failed", "compiles_in_window", "batch_requeues"} <= set(checks)
    assert run["result"]["failed"] == 0 and run["result"]["answers"]
    # two cycles in every answer: past the restart, inside the second
    assert all(30 < a["iters"] <= 60 for a in run["result"]["answers"])
    assert run["shape"] == {"rows": 5760, "nnz": 38208, "lanes": 4,
                            "diagonals": 7, "restart": 30}
    cfg = run["cell"]["config"]
    d = op.make(cfg["sizes"], 2147483693)
    answers = run["result"]["answers"][-12:]
    sound = {c["name"]: c for c in op.check(d, answers, cfg["limits"],
                                            lambda *_: None)}
    ctl = {c["name"]: c for c in op.check(d, op.control_answers(d, answers),
                                          cfg["limits"], lambda *_: None)}
    assert all(c["ok"] for c in sound.values()), sound
    assert not ctl["x_vs_reference"]["ok"] and not ctl["relres_over_asked"]["ok"]


@pytest.mark.parametrize("how", ["answer", "stale"])
def test_a_rehearsal_with_the_bucket_program_broken_underneath_is_not_correct(
        monkeypatch, how):
    """The break is under the session, in the loop the bucket program
    compiles: its answer scaled by 1 + 1e-2, or its start handed back."""
    from sparse_tpu.batch import krylov

    loop = krylov._gmres_loop

    def broken(matvec, b, X0, *args, **kw):
        X, *rest = loop(matvec, b, X0, *args, **kw)
        return (X0 if how == "stale" else X * (1.0 + 1e-2), *rest)

    monkeypatch.setattr(krylov, "_gmres_loop", broken)
    run, checks = rehearse(2147483659)
    assert not run["checks_ok"]
    assert not checks["x_vs_reference"]["ok"]
    assert not checks["relres_over_asked"]["ok"]
    assert checks["session_matvec_not_planes"]["ok"]  # the path is the same


def test_the_adaptor_raises_on_a_host_driven_gmres_bucket(monkeypatch):
    import run as harness
    from sparse_tpu.batch import krylov, service

    system = manifest.load_module("systems", "solve_session_gmres")
    assert system.System.__mro__[1].__module__ == "bench_systems_solve_session"
    cfg = small()
    data = op.make(cfg["sizes"], 3)

    def closure_builder(self, pattern, bkt, dt, precond="none"):
        # the tree before PR 49: a closure over the public function
        def run(values, rhs, x0, tols, maxiter):
            raise AssertionError("the ramp was reached")
        return run

    ctx = harness.Context(False)
    try:
        sut = system.System(cfg, data, ctx)  # this tree's program passes
        assert sut.shape["diagonals"] == 7
        sut.close()
        monkeypatch.setattr(service.SolveSession, "_build_gmres_program",
                            closure_builder)
        with pytest.raises(RuntimeError, match="driven from the host"):
            system.System(cfg, data, ctx)
        monkeypatch.undo()
        # a compiled program whose product is the gathers is refused too
        from sparse_tpu.batch.operator import SparsityPattern

        monkeypatch.setattr(SparsityPattern, "plane_pack", lambda self: None)
        with pytest.raises(RuntimeError, match="plane product"):
            system.System(cfg, data, ctx)
    finally:
        ctx.close()
    assert krylov._gmres_loop  # what the builder compiles


def test_the_adaptor_holds_the_window_to_planes_one_fetch_and_two_cycles_of_steps():
    import run as harness

    system = manifest.load_module("systems", "solve_session_gmres")
    ctx = harness.Context(False)
    ok = {"matvec": "planes", "fetches": 1, "cycles_max": 2, "iters_max": 45}
    try:
        sut = object.__new__(system.System)
        sut.ctx = ctx
        sut._cached_program = lambda: None  # no text: the shares read nothing
        sut.shape = {"restart": 30}
        # a third pass of a step is inside the guarantee, 61 steps are not
        for evs in ([], [ok, dict(ok, matvec="sell")],
                    [ok, dict(ok, fetches=3)], [ok, dict(ok, iters_max=61)],
                    [ok, {"matvec": "planes"}],
                    [ok, dict(ok, cycles_max=3, iters_max=50)] * 2):
            sut.check_events({"batch.dispatch": evs} if evs else {})
    finally:
        ctx.close()
    by_name = {}
    for c in ctx.checks:
        by_name.setdefault(c["name"], []).append(c["value"])
    assert by_name == {
        "window_matvec_not_planes": [1.0, 1.0, 0.0, 0.0, 0.0, 0.0],
        "window_fetches_not_one": [1.0, 0.0, 1.0, 0.0, 1.0, 0.0],
        "window_past_two_cycles": [1.0, 0.0, 0.0, 1.0, 1.0, 0.0]}


def test_a_traced_rehearsal_carries_the_buckets_own_fields():
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings

    was = settings.telemetry
    try:
        run, checks = rehearse(11, seconds=0.2, trace=1)
    finally:
        settings.telemetry = was
        telemetry.configure(None)
        telemetry.reset()
    assert run["checks_ok"], checks
    for name in ("window_matvec_not_planes", "window_fetches_not_one",
                 "window_past_two_cycles"):
        assert checks[name]["value"] == 0.0
    sent = run["events"]["batch.dispatch"]
    assert sent and {e["matvec"] for e in sent} == {"planes"}
    assert all(e["restart"] == 30 and e["cycles_max"] in (2, 3)
               and 30 < e["iters_max"] <= 60 and e["fetches"] == 1
               and 0.0 <= e["frozen_lane_pct"] < 50.0
               and e["iters_sum"] <= e["batch"] * e["iters_max"]
               for e in sent)
    (hlo,) = run["events"]["program.hlo"]
    assert hlo["program"] == "jit_bucket_gmres"
    assert "bucket.gmres.orth" in hlo["text"]
    for name in ("served_gmres_fetches_per_bucket",
                 "served_gmres_frozen_lane_pct"):
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(run, params) is not None


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_resolves_and_reads_nothing_from_an_empty_run(name):
    spec = manifest.load_json("layer_metrics", name + ".json")
    assert spec["doc"]
    read, params = manifest.metric_reader("layer_metrics", name)
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    assert read(empty, params) is None
    # a tree without the program: events and a trace of another program
    dev = {"programs": {"jit_run": [10, 8.0]},
           "ops": {("jit_run", "fusion.1", "fusion", "kLoop"): [10, 8.0]}}
    other = {"trace": {"devices": {0: dev}},
             "events": {"batch.dispatch": [{"iters_max": 25,
                                            "matvec": "planes"}]},
             "shape": {"rows": 1, "diagonals": 7, "restart": 30, "lanes": 32},
             "peaks": {"hbm_bytes_per_s": 819e9}, "spans": {},
             "result": {"completions": []}}
    assert read(other, params) is None


def test_the_new_metrics_read_hand_made_runs():
    n, lanes = 1_270_432, 32
    ops = {("jit_bucket_gmres", "fusion.1", "fusion", "kLoop"): [90, 3.0],
           ("jit_bucket_gmres", "fusion.2", "fusion", "kLoop"): [90, 1.0],
           ("jit_bucket_gmres", "copy.3", "copy", ""): [90, 0.5]}
    dev = {"programs": {"jit_bucket_gmres": [2, 5.0]}, "ops": ops}
    text = ('  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(bucket_gmres)/while/body/while/body/'
            'bucket.gmres.orth/mul"}\n'
            '  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(bucket_gmres)/while/body/while/body/'
            'bucket.gmres.spmv/add"}\n'
            '  %copy.3 = f32[4]{0} copy(f32[4]{0} %p)\n')
    sent = [{"iters_max": 44, "fetches": 1, "frozen_lane_pct": 2.5},
            {"iters_max": 46, "fetches": 1, "frozen_lane_pct": 3.5},
            {"iters_max": 45, "fetches": 1, "frozen_lane_pct": 3.0}]
    run = {"trace": {"devices": {0: dev}},
           "events": {"batch.dispatch": sent,
                      "program.hlo": [{"program": "jit_bucket_gmres",
                                       "text": text}]},
           "shape": {"rows": n, "nnz": 8_814_880, "lanes": lanes,
                     "diagonals": 7, "restart": 30},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    got = {}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        got[name] = read(run, params)
    # 42.4 n values a step and lane (bytes/gmres_dia.py), 2 runs of 45 steps
    per_step = lanes * 1272 * n * 4 / 30
    assert got["served_gmres_roofline"] == pytest.approx(
        100 * per_step * 90 / 5.0 / 819e9)
    assert got["served_gmres_roofline"] < 100
    assert got["served_gmres_orth_pct"] == pytest.approx(60.0)
    assert got["served_gmres_spmv_pct"] == pytest.approx(20.0)
    assert got["served_gmres_fetches_per_bucket"] == 1
    assert got["served_gmres_frozen_lane_pct"] == 3.0
    bytes_of = manifest.load_module("bytes", "bucket_gmres")
    one = manifest.load_module("bytes", "gmres_dia")
    assert bytes_of.bytes_per_iteration(n, 7, 30, lanes) == pytest.approx(
        lanes * one.bytes_per_iteration(n, 7, 30, 10))
    assert bytes_of.bytes_per_iteration(n, 7, 30, 1) == pytest.approx(
        42.4 * n * 4)
