"""The cell ``fem_heat_served_closed`` on the CPU: what its configuration
states against the generator, the plain reference against a direct solve, the
adaptor's guarantee, a rehearsal and the control in-process, and the cell's
new metric files on hand-made runs. (test_benchmark.py runs the rehearsal, the
control and the broken timed path for every cell of BENCHMARK.json in
processes of their own.)"""

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

CELL = "fem_heat_served_closed"
NEW = {"bucket_gather_pct": ("device_trace", "kernels", "solves_per_s"),
       "pattern_pack_s": ("program_span", "session", "setup_s"),
       "served_gather_upload_ms": ("program_span", "session", "solves_per_s"),
       "served_gather_host_share_pct": ("program_span", "session",
                                        "solves_per_s"),
       "served_gather_pack_ms": ("program_span", "session", "solves_per_s"),
       "served_gather_readback_ms": ("program_span", "session",
                                     "ticket_p95_ms")}
op = manifest.load_module("operators", "fem_heat_step")
stiffness = manifest.load_module("operators", "spd_unstructured")


def small_sizes():
    return manifest.cell(CELL, rehearse=True)["config"]["sizes"]


def test_the_cell_resolves_to_its_files_and_metrics():
    res = manifest.cell(CELL)
    wl, cfg = res["workload"], res["config"]
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "fem-heat-1m2", "closed_128", 1)
    assert (cfg["operator"], cfg["system"]) == (
        "fem_heat_step", "solve_session_general")
    assert cfg["session"] == {"solver": "cg"}
    assert res["traffic"] == manifest.cell("heat_served_closed")["traffic"]
    assert [m["name"] for m in res["end_to_end"]] == [
        "setup_s", "solves_per_s", "ticket_p95_ms"]
    assert {m["name"] for m in res["per_layer"]} == {
        "dispatch_solve_ms", "ticket_queue_ms_p95", "pad_lane_pct",
        "bucket_cg_roofline", "operator_build_s", *NEW}
    for name, (source, layer, moves) in NEW.items():
        m = next(m for m in res["per_layer"] if m["name"] == name)
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            source, layer, moves, [CELL])


def test_the_configuration_states_the_pattern_seed_of_spd_thermal_1m2():
    cfg = manifest.cell(CELL)["config"]
    other = manifest.cell("spd_general_1chip")["config"]
    # the generator and the pattern_seed of the library cell; the rows are cut
    # to what a run's 90 s allow, and that is the one thing reduced
    assert cfg["sizes"]["pattern_seed"] == other["sizes"]["pattern_seed"] == 3200000103
    assert "pattern_seed" not in cfg["rehearse"]["sizes"]  # the same at every size
    s = cfg["sizes"]["side"]
    assert list(cfg["reduced"]) == ["side"] and s <= other["sizes"]["side"]
    # thermal2's class: 6.99 entries a row at every side; three quarters of
    # its rows, and the deployment's lanes and clients whole
    assert s * s == 921_600 >= 0.75 * 1_228_045
    assert 7 * s * s - 8 * s + 2 == 6_443_522
    # the entry in BENCHMARK.json carries the file's source and cut, and the
    # source and the cell's why state the rows that are run
    res = manifest.cell(CELL)
    listed = manifest.load_json(os.pardir, "BENCHMARK.json")
    entry = next(c for c in listed["configs"] if c["name"] == cfg["name"])
    assert (entry["source"], entry["reduced"]) == (cfg["source"], ["side"])
    rows = f"{s * s:,}"
    assert rows in cfg["source"] and rows in res["workload"]["why"]
    assert cfg["sizes"]["clients"] == 128 and cfg["sizes"]["check_sample"] >= 16
    lo, hi = cfg["sizes"]["coefficient_range"]
    # Gershgorin with K_ii < 12 (eight edges of weight under 1.5)
    assert cfg["limits"]["kappa_bound"] == pytest.approx((hi + 24.0) / lo)
    assert cfg["limits"]["x_vs_reference"] == pytest.approx(
        cfg["limits"]["kappa_bound"] * 2.0 * cfg["sizes"]["rel_tol"])
    assert cfg["limits"]["relres_over_asked"] == 2.0


def test_one_pattern_and_each_seeds_own_values():
    sizes = small_sizes()
    a, b = op.make(sizes, 1), op.make(sizes, 2147483659)
    P = a["pattern"]
    assert P.has_sorted_indices and a["rows"] == sizes["side"] ** 2
    assert np.array_equal(P.indptr, b["pattern"].indptr)
    assert np.array_equal(P.indices, b["pattern"].indices)
    assert not np.array_equal(a["values"], b["values"])
    assert not np.array_equal(a["coef"], b["coef"])
    # the pattern and the stiffness values are spd_unstructured's own
    K = stiffness.make({"side": sizes["side"], "iterations": 0,
                        "pattern_seed": sizes["pattern_seed"]}, 1)
    assert np.array_equal(P.indices, K["indices"])
    assert np.array_equal(P.data, K["data"])
    assert a["values"].shape == (sizes["clients"], a["nnz"])
    assert a["values"].dtype == a["coef"].dtype == np.float32
    # every client's values: K's with its coefficients on the diagonal
    n = a["rows"]
    for k in (0, sizes["clients"] - 1):
        A = sp.csr_matrix((a["values"][k], P.indices, P.indptr), shape=(n, n))
        D = A - sp.csr_matrix((K["data"], P.indices, P.indptr), shape=(n, n))
        D.eliminate_zeros()
        assert np.allclose(D.diagonal(), a["coef"][k], rtol=1e-6)
        assert D.nnz == n
    assert a["carry"] == sizes["coefficient_range"][0]
    same = op.make(sizes, 1)
    assert all(np.array_equal(a[k], same[k])
               for k in ("values", "coef", "initial", "source"))


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_stated_kappa_holds_for_the_generated_matrices(seed):
    cfg = manifest.cell(CELL, rehearse=True)["config"]
    d = op.make(cfg["sizes"], seed)
    assert d["kappa_bound"] <= cfg["limits"]["kappa_bound"]
    P = d["pattern"]
    worst = 0.0
    for k in range(d["clients"]):
        A = sp.csr_matrix((d["values"][k].astype(np.float64), P.indices,
                           P.indptr), shape=P.shape).toarray()
        ev = np.linalg.eigvalsh(A)
        assert ev[0] >= d["coef"][k].min() * (1 - 1e-6)
        worst = max(worst, ev[-1] / ev[0])
    assert 1.0 < worst <= d["kappa_bound"]


def test_reference_converges_to_the_direct_solution_and_residuals_are_true():
    d = op.make(small_sizes(), 5)
    P = d["pattern"]
    b = np.float32(d["carry"]) * d["initial"] + d["source"]
    x = op.reference_cg(d, d["coef"], b)
    assert x.shape == b.shape and x.dtype == np.float32
    for k in range(d["clients"]):
        A = sp.csr_matrix((d["values"][k].astype(np.float64), P.indices,
                           P.indptr), shape=P.shape)
        exact = spla.spsolve(A.tocsc(), b[k].astype(np.float64))
        assert np.linalg.norm(x[k] - exact) <= 2e-6 * np.linalg.norm(exact)
        assert np.allclose(op.apply_f64(d, d["values"][k], exact),
                           A @ exact, rtol=1e-12)
        assert op.true_relres(d, exact, d["values"][k], b[k]) < 1e-12
    assert op.true_relres(d, np.zeros(d["rows"]), d["values"][0], b[0]) == (
        pytest.approx(1.0))


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
    assert {"session_matvec_not_sell", "answers_past_first_test",
            "x_vs_reference", "relres_over_asked", "kappa_bound", "failed",
            "compiles_in_window"} <= set(checks)
    assert run["result"]["failed"] == 0 and run["result"]["answers"]
    assert {a["iters"] for a in run["result"]["answers"]} == {25}
    cfg = run["cell"]["config"]
    d = op.make(cfg["sizes"], 2147483693)
    answers = run["result"]["answers"][-12:]
    sound = {c["name"]: c for c in op.check(d, answers, cfg["limits"],
                                            lambda *_: None)}
    ctl = {c["name"]: c for c in op.check(d, op.control_answers(d, answers),
                                          cfg["limits"], lambda *_: None)}
    assert all(c["ok"] for c in sound.values()), sound
    assert not ctl["x_vs_reference"]["ok"] and not ctl["relres_over_asked"]["ok"]
    assert ctl["kappa_bound"]["ok"]  # the matrices are the same


def test_a_traced_rehearsal_holds_every_dispatch_to_the_gather_form():
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
    assert checks["window_matvec_not_sell"]["value"] == 0.0
    assert checks["window_dispatch_past_first_test"]["value"] == 0.0
    dispatches = run["events"]["batch.dispatch"]
    assert dispatches and {e["matvec"] for e in dispatches} == {"sell"}
    assert {e["iters_max"] for e in dispatches} == {25}


def test_the_adaptor_refuses_planes_a_second_block_and_an_empty_window():
    import run as harness

    system = manifest.load_module("systems", "solve_session_general")
    assert system.System.__mro__[1].__module__ == "bench_systems_solve_session"
    ctx = harness.Context(False)
    sell, planes = ({"matvec": m, "iters_max": 25} for m in ("sell", "planes"))
    try:
        sut = object.__new__(system.System)
        sut.ctx, sut.block = ctx, 25
        sut.check_events({})
        sut.check_events({"batch.dispatch": [sell, planes]})
        sut.check_events({"batch.dispatch": [sell, dict(sell, iters_max=50)]})
        sut.check_events({"batch.dispatch": [sell] * 3})
    finally:
        ctx.close()
    by_name = {}
    for c in ctx.checks:
        by_name.setdefault(c["name"], []).append(c["value"])
    assert by_name == {"window_matvec_not_sell": [1.0, 1.0, 0.0, 0.0],
                       "window_dispatch_past_first_test": [1.0, 0.0, 1.0, 0.0]}


def test_an_answer_past_the_first_test_is_not_correct():
    import run as harness

    system = manifest.load_module("systems", "solve_session_general")
    cfg = manifest.cell(CELL, rehearse=True)["config"]
    for iters, value in (({25}, 0.0), ({25, 50}, 1.0), (set(), 1.0)):
        ctx = harness.Context(False)
        try:
            sut = system.System(cfg, op.make(cfg["sizes"], 3), ctx)
            assert sut.block == 25  # the session's default, nothing set
            sut.iters = iters
            sut.close()
        finally:
            ctx.close()
        checks = {c["name"]: c["value"] for c in ctx.checks}
        assert checks["answers_past_first_test"] == value


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_resolves_and_reads_nothing_from_an_empty_run(name):
    from sparse_tpu import telemetry

    telemetry.reset()
    spec = manifest.load_json("layer_metrics", name + ".json")
    assert spec["doc"]
    read, params = manifest.metric_reader("layer_metrics", name)
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    assert read(empty, params) is None


def test_the_new_metrics_read_hand_made_runs():
    per_layer = {m["name"] for m in manifest.cell(CELL)["per_layer"]}
    assert set(NEW) <= per_layer
    # a bucket: 25 iterations, the product's gathers and the value stack's
    ops = {("jit_run", f"fusion.{k}", "fusion", "kCustom"): [250, 0.8]
           for k in range(7)}
    ops[("jit_run", "multiply_reduce_fusion.6", "fusion", "kLoop")] = [250, 1.4]
    dev = {"programs": {"jit_run": [10, 8.0]}, "ops": ops}
    run = {"trace": {"devices": {0: dev}},
           "events": {"batch.dispatch": [{"iters_max": 25}] * 10},
           "shape": {"rows": 640_000, "nnz": 4_473_602, "lanes": 64},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    read, params = manifest.metric_reader("layer_metrics", "bucket_gather_pct")
    assert read(run, params) == pytest.approx(100 * 7 * 0.8 / 8.0)
    read, params = manifest.metric_reader("layer_metrics", "bucket_cg_roofline")
    per_it = 64 * 4_473_602 * 4 + 4_473_602 * 4 + 6 * 64 * 640_000 * 4
    assert read(run, params) == pytest.approx(
        100 * per_it * 250 / 8.0 / 819e9)
    # the session's spans, through the metrics of this cell's own
    names = ("pack", "upload", "plan", "call", "device_wait", "readback",
             "scatter")
    durs = {"pack": 0.004, "upload": 0.2, "readback": 0.1, "device_wait": 0.896}
    spans = [{"kind": "span", "name": f"session.{n}", "seq": 4,
              "dur_s": durs.get(n, 0.0)} for n in names]
    run = {"events": {"span": spans}}
    read, params = manifest.metric_reader("layer_metrics",
                                          "served_gather_upload_ms")
    assert read(run, params) == pytest.approx(200.0)
    for short, ms in (("pack", 4.0), ("readback", 100.0)):
        read, params = manifest.metric_reader("layer_metrics",
                                              f"served_gather_{short}_ms")
        assert read(run, params) == pytest.approx(ms)
        assert params == manifest.load_json(
            "layer_metrics", f"session_{short}_ms.json")["params"]
    read, params = manifest.metric_reader("layer_metrics",
                                          "served_gather_host_share_pct")
    assert read(run, params) == pytest.approx(100 * 0.304 / 1.2)
    assert params == manifest.load_json(
        "layer_metrics", "session_host_share_pct.json")["params"]


def test_pattern_pack_s_reads_the_programs_aggregate():
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings

    read, params = manifest.metric_reader("layer_metrics", "pattern_pack_s")
    was = settings.telemetry
    settings.telemetry = True
    try:
        telemetry.reset()
        assert read({}, params) is None
        telemetry.add_span("session.pattern_pack", 3.5)
        telemetry.add_span("session.upload", 0.25)
        assert read({}, params) == pytest.approx(3.5)
    finally:
        telemetry.reset()
        settings.telemetry = was
