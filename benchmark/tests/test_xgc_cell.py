"""The cell ``xgc_batched_bicgstab_1chip`` on the CPU: that it resolves to its
files and metrics, the bytes function against the figure in its docstring,
the cell's metric files on hand-made runs and on an empty one, the adaptor's
refusal of a program without the compiled batched solve and its guarantees,
a rehearsal that is ``correct`` but for the chip, and a rehearsal with
``linalg.batched_bicgstab`` broken underneath that is not. (The rehearsal and
the control run for every cell of BENCHMARK.json in test_benchmark.py;
``broken_run.py`` there breaks ``linalg.cg`` and the session's tickets and
does not reach the batched solve, so this cell's broken timed path is driven
here, as test_gmres_cell.py does for GMRES. The generator and the plain
reference are tied to scipy in tests/test_xgc_reference.py.)"""

from __future__ import annotations

import argparse
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402

CELL = "xgc_batched_bicgstab_1chip"
NEW = ("batched_bicgstab_roofline", "batched_frozen_lane_pct",
       "batched_spmv_pct", "batched_dots_pct", "batched_bicgstab_dispatch_ms",
       "batched_fetches_per_solve", "batched_pack_s")
SHARED = ("iters_per_s", "operator_build_s", "solve_call_ms",
          "solve_call_max_ms", "solve_prep_ms", "solve_wait_ms",
          "solve_rest_ms", "solve_caller_ms")
gen = manifest.load_module("operators", "xgc_collision")
SMALL = {"velocity_grid": [32, 31], "rows": 992, "nnz": 8554, "systems": 16,
         "mesh_seed": 55, "tol_rel": 1e-5, "maxiter": 200, "conv_test_iters": 1,
         "check_sample": 4}


def test_the_cell_resolves_to_its_files():
    res = manifest.cell(CELL)
    cfg, wl = res["config"], res["workload"]
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "xgc-collision-992", "back_to_back_brief_trace", 1)
    assert (cfg["operator"], cfg["system"]) == (
        "xgc_collision", "library_batched_bicgstab")
    sizes = cfg["sizes"]
    # the source's shapes, never cut
    assert {k: sizes[k] for k in ("velocity_grid", "rows", "nnz", "diags",
                                  "species")} == {
        "velocity_grid": [32, 31], "rows": 992, "nnz": 8554, "diags": 9,
        "species": 2}
    assert sizes["systems"] in (32768, 16384)
    assert (sizes["tol_rel"], sizes["maxiter"], sizes["conv_test_iters"],
            sizes["check_sample"], sizes["dtype"]) == (1e-5, 200, 1, 32, "float32")
    assert set(cfg["reduced"]) == {"dtype"} and all(cfg["reduced"].values())
    assert isinstance(sizes["mesh_seed"], int)
    assert {"systems", "coefficients", "x0", "iteration_counts",
            "mesh_seed"} <= set(cfg["assumed"])
    assert set(cfg["limits"]) == {"relres_over_asked", "x_vs_reference"}
    assert cfg["limits"]["relres_over_asked"] == 2
    assert cfg["limits"]["x_vs_reference"] < 1e-2  # broken_run.py's hundredth
    assert {m["name"] for m in res["end_to_end"]} >= {"solve_s", "setup_s"}
    per_layer = {m["name"]: m for m in res["per_layer"]}
    assert set(per_layer) >= set(NEW) | set(SHARED)
    assert all(CELL in per_layer[n]["workloads"] for n in per_layer)
    assert all(per_layer[n]["workloads"] == [CELL] for n in NEW)
    assert per_layer["batched_pack_s"]["moves"] == "setup_s"
    assert all(per_layer[n]["moves"] == "solve_s" for n in NEW[:-1])
    assert per_layer["batched_bicgstab_roofline"]["unit"] == "%"
    for name in per_layer:
        manifest.metric_reader("layer_metrics", name)
    small = manifest.cell(CELL, rehearse=True)["config"]["sizes"]
    assert small["systems"] == 256 and small["rows"] == sizes["rows"]


def test_bytes_function_counts_the_figure_in_its_docstring():
    mod = manifest.load_module("bytes", "batched_bicgstab_dia")
    b = mod.bytes_per_iteration
    assert b(992, 9) == (2 * 9 + 15) * 992 * 4 == 130_944
    assert "130,944 B" in mod.__doc__
    assert b(992, 9, itemsize=2) * 2 == b(992, 9)
    assert b(10, 0, itemsize=1) == 150  # no matrix: the vectors alone
    # 573 thousand lane-steps a call at 819 GB/s: the floor of a call
    assert 573_440 * b(992, 9) / 819e9 == pytest.approx(0.0917, rel=1e-2)


def test_every_new_metric_reads_nothing_from_an_empty_run():
    from sparse_tpu import telemetry

    telemetry.reset()  # span_total reads the process's own aggregate
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(empty, params) is None, name
    # a trace without the program and another solver's span (the parent
    # never gets this far in the cell: its adaptor refuses; its batch.solve
    # events have no lane fields, and the frozen share reads none of them)
    dev = {"programs": {"jit_pcg": [3, 1.0]}, "ops": {
        ("jit_pcg", "fusion.1", "fusion", "kLoop"): [3, 1.0]}}
    run = {"trace": {"devices": {0: dev}},
           "shape": {"rows": 992, "diags": 9, "nnz": 8554, "systems": 256},
           "events": {"span": [{"name": "cg.solve", "dispatch_s": 0.001}],
                      "program.hlo": [{"program": "jit_batched_bicgstab",
                                       "text": ""}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(run, params) is None, name
    run["events"]["batch.solve"] = [{"solver": "bicgstab", "B": 256,
                                     "iters_max": 30, "iters_mean": 14.0}]
    read, params = manifest.metric_reader("layer_metrics", "batched_frozen_lane_pct")
    assert read(run, params) is None


HLO = """HloModule jit_batched_bicgstab
%body (t: (f32[256,992])) -> (f32[256,992]) {
  %fusion.1 = f32[256,992]{0,1} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(batched_bicgstab)/while/body/batch.spmv/add"}
  %fusion.2 = f32[256,992]{0,1} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(batched_bicgstab)/while/body/batch.spmv/mul"}
  %multiply_reduce_fusion.3 = f32[256]{0} fusion(%fusion.1), kind=kLoop, calls=%fc, metadata={op_name="jit(batched_bicgstab)/while/body/bucket.dots/reduce_sum"}
  %fusion.4 = f32[256,992]{0,1} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(batched_bicgstab)/while/body/batch.precond/mul"}
  %fusion.5 = f32[256,992]{0,1} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(batched_bicgstab)/while/body/select_n"}
  %copy.6 = f32[256,992]{0,1} copy(%fusion.5)
  ROOT %fusion.7 = f32[256,992]{0,1} fusion(%x), kind=kLoop, calls=%fc, metadata={op_name="jit(batched_bicgstab)/batch.spmv/sub"}
}
"""


def test_shares_roofline_and_counts_read_a_hand_made_run():
    """Two whole runs of ``jit_batched_bicgstab`` over 256 lanes, 30 steps
    each, 3,712 lane-steps of answers a call, a second of device time."""
    secs = {"fusion.1": 0.30, "fusion.2": 0.10, "multiply_reduce_fusion.3": 0.25,
            "fusion.4": 0.05, "fusion.5": 0.20, "copy.6": 0.04, "fusion.7": 0.02}
    ops = {("jit_batched_bicgstab", k, "fusion", "kLoop"): [60, v]
           for k, v in secs.items()}
    ops[("jit_other", "fusion.1", "fusion", "kLoop")] = [1, 9.0]
    dev = {"programs": {"jit_batched_bicgstab": [2, 1.0], "jit_other": [1, 9.0]},
           "ops": ops}
    solve = {"kind": "batch.solve", "solver": "bicgstab", "B": 256, "n": 992,
             "iters_max": 30, "iters_sum": 3712, "iters_mean": 14.5,
             "frozen_lane_pct": 51.667, "converged": 256}
    span = {"kind": "span", "name": "batched_bicgstab.solve", "path": "device",
            "B": 256, "fetches": 1, "dur_s": 0.5}
    run = {"trace": {"devices": {0: dev}},
           "shape": {"rows": 992, "diags": 9, "nnz": 8554, "systems": 256},
           "events": {"batch.solve": [solve, dict(solve, frozen_lane_pct=50.0),
                                      dict(solve, frozen_lane_pct=54.0)],
                      "span": [dict(span, dispatch_s=d)
                               for d in (0.0004, 0.0005, 0.0009)]
                      + [{"name": "cg.solve", "dispatch_s": 0.5}],
                      "program.hlo": [{"program": "jit_batched_bicgstab",
                                       "text": HLO}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}

    def value(name):
        read, params = manifest.metric_reader("layer_metrics", name)
        return read(run, params)

    assert value("batched_spmv_pct") == pytest.approx(100 * (0.30 + 0.10 + 0.02))
    assert value("batched_dots_pct") == pytest.approx(100 * 0.25)
    assert value("batched_frozen_lane_pct") == pytest.approx(51.667)
    assert value("batched_bicgstab_dispatch_ms") == pytest.approx(0.5)
    assert value("batched_fetches_per_solve") == 1
    # the answers' lane-steps, not the loop's 256 x 30
    assert value("batched_bicgstab_roofline") == pytest.approx(
        100 * 130_944 * 3712 * 2 / 1.0 / 819e9)
    # a program that stopped stepping converged lanes would run as long as
    # its answers need and read up to the whole: never past 100
    assert 100 * 130_944 * 3712 * 2 / 819e9 < 100 * 1.0
    spec = manifest.load_json("layer_metrics", "batched_pack_s.json")
    assert (spec["reducer"], spec["params"]) == (
        "span_total", {"name": "batch.values_pack"})
    # without the text the shares read nothing; the roofline does not need it
    del run["events"]["program.hlo"]
    assert value("batched_spmv_pct") is None
    assert value("batched_bicgstab_roofline") is not None


def test_the_pack_metric_totals_the_programs_two_spans():
    from sparse_tpu import precond, telemetry
    from sparse_tpu.batch import BatchedCSR, SparsityPattern
    from sparse_tpu.config import settings

    d = gen.make(SMALL, 3)
    was = settings.telemetry
    try:
        telemetry.reset()
        settings.telemetry = True
        pattern = SparsityPattern(d["indptr"], d["indices"], (992, 992))
        op = BatchedCSR(pattern, d["values"]).todia()
        precond.make_factory(pattern, "jacobi")(d["values"], op.matvec)
        read, params = manifest.metric_reader("layer_metrics", "batched_pack_s")
        total = read({}, params)
        spans = [e for e in telemetry.events("span")
                 if e["name"] == "batch.values_pack"]
    finally:
        settings.telemetry = was
        telemetry.reset()
    assert [e["form"] for e in spans] == ["planes", "jacobi"]
    assert total == pytest.approx(sum(e["dur_s"] for e in spans), abs=1e-6)


def test_the_adaptor_refuses_a_program_without_the_compiled_solve(monkeypatch):
    from sparse_tpu import linalg  # noqa: F401 - registers the counter
    from sparse_tpu.telemetry import _metrics

    system = manifest.load_module("systems", "library_batched_bicgstab")
    monkeypatch.setattr(_metrics, "family", lambda name: [])
    with pytest.raises(RuntimeError, match="batch.bicgstab.traces"):
        system.System({}, {}, None)


def test_the_adaptor_holds_the_guarantees_and_hands_over_spans_and_text():
    import run as harness
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings

    system = manifest.load_module("systems", "library_batched_bicgstab")
    d = gen.make(SMALL, 4)
    cfg = {"sizes": SMALL, "operator": "xgc_collision"}
    was = settings.telemetry
    ctx = harness.Context(True)
    try:
        telemetry.reset()
        ctx.events_on()
        sut = system.System(cfg, d, ctx)
        assert sut.shape == {"rows": 992, "diags": 9, "nnz": 8554, "systems": 16}
        sut.warm()
        n0 = len(telemetry.events("span"))
        out = sut.call()
        answer = sut.answer(out)
        window = telemetry.events("span")[n0:]
        events = {"span": list(window)}
        sut.check_events(events)
        sut.check_events({"span": [dict(window[0], fetches=3)]})
        sut.close()
    finally:
        settings.telemetry = was
        telemetry.configure(None)
        telemetry.reset()
        ctx.close()
    assert answer["x"].shape == (16, 992) and answer["iters_lanes"].shape == (16,)
    assert out["iters"] == answer["iters_lanes"].max() and answer["converged"].all()
    assert events["span"] == window
    assert [e["name"] for e in window] == ["batched_bicgstab.solve"]
    assert window[0]["frozen_lane_pct"] == pytest.approx(100 * (
        1 - answer["iters_lanes"].sum() / (16 * out["iters"])), abs=1e-3)
    names = [e["name"] for e in events["setup.span"]]
    # two value stacks, each repacked into planes and a diagonal; three solves
    assert names.count("batch.values_pack") == 4
    assert names.count("batched_bicgstab.solve") == 3
    (hlo,) = events["program.hlo"]
    assert hlo["program"] == "jit_batched_bicgstab"
    assert "/batch.spmv/" in hlo["text"] and "/bucket.dots/" in hlo["text"]
    checks = {c["name"]: c for c in ctx.checks}
    assert set(checks) == {
        "solver_path_not_device", "warm_call_not_jit_batched_bicgstab",
        "second_values_traced", "window_solve_not_jit_batched_bicgstab",
        "batch_bicgstab_traces_in_window"}
    # all held, but for the second window's span of three fetches
    assert [c["ok"] for c in ctx.checks] == [True, True, True, True, False, True]
    assert {"operator_build", "first_call", "warm_call",
            "second_values"} <= set(ctx.spans)


def _rehearse(seconds=0.5):
    import run as harness

    seen = {}
    ns = argparse.Namespace(workload=CELL, seed=2147483659, seconds=seconds,
                            trace=0, rehearse=True)
    code, line = harness.run_cell(ns, on_result=seen.update)
    return code, line, seen


def test_a_rehearsal_is_correct_but_for_the_chip():
    code, line, seen = _rehearse()
    assert code == 1 and line["correct"] is False  # for want of a chip
    assert seen["checks_ok"], [c for c in seen["checks"] if not c["ok"]]
    names = {c["name"] for c in seen["checks"]}
    assert {"x_vs_reference", "relres_over_asked", "lanes_unconverged",
            "mix_lost", "second_values_traced", "compiles_in_window",
            "batch_bicgstab_traces_in_window"} <= names
    assert all(c["iters"] >= 10 for c in seen["result"]["completions"])


@pytest.mark.parametrize("how", ["answer", "stale"])
def test_a_rehearsal_with_the_solve_broken_underneath_is_not_correct(how, monkeypatch):
    """``tests/broken_run.py``'s two breaks, on the solver this cell calls:
    every answer scaled by 1 + 1e-2, or the start returned unchanged."""
    from sparse_tpu import linalg

    solve = linalg.batched_bicgstab

    def broken(A, b, **k):
        X, info = solve(A, b, **k)
        return (k["x0"] if how == "stale" else X * (1.0 + 1e-2)), info

    monkeypatch.setattr(linalg, "batched_bicgstab", broken)
    code, line, seen = _rehearse()
    assert code == 1 and line["correct"] is False
    failed = [c["name"] for c in seen["checks"] if not c["ok"]]
    assert not seen["checks_ok"] and "x_vs_reference" in failed
