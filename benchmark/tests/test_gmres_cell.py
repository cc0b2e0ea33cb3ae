"""The cell ``nonsym_gmres_1chip`` on the CPU: that it resolves to its files,
the bytes function, the cell's metric files on hand-made runs and on an empty
one, the adaptor's refusal and what it hands a traced run, and a rehearsal
with ``linalg.gmres`` broken underneath. (The rehearsal and the control run
for every cell of BENCHMARK.json in test_benchmark.py; ``broken_run.py``
there breaks ``linalg.cg`` and the session's tickets and does not reach
GMRES, so this cell's broken timed path is driven here. The plain reference
is tied to scipy's GMRES in tests/test_gmres_reference.py.)"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402

CELL = "nonsym_gmres_1chip"
NEW = ("gmres_dispatch_ms", "gmres_fetches_per_solve", "gmres_roofline",
       "gmres_orth_pct", "gmres_spmv_pct", "gmres_small_pct")
gen = manifest.load_module("operators", "cfd_7pt")


def test_the_cell_resolves_to_its_files():
    res = manifest.cell(CELL)
    cfg, wl = res["config"], res["workload"]
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "atmosmodd-gmres30", "back_to_back_brief_trace", 1)
    assert (cfg["operator"], cfg["system"]) == ("cfd_7pt", "library_gmres")
    sizes = cfg["sizes"]
    # the box and the restart are never cut; the cycles only by the 80 s rule
    assert sizes["box"] == [148, 148, 58] and sizes["restart"] == 30
    assert 4 <= sizes["cycles"] <= 10
    assert gen.counts(sizes["box"]) == (1_270_432, 8_814_880)
    source = {"cycles": 10, "dtype": "float64"}
    cut = {k for k, v in source.items() if sizes[k] != v}
    assert cut <= set(cfg["reduced"]) and all(cfg["reduced"].values())
    assert set(cfg["limits"]) == {"x_vs_reference", "relres_gap", "iterations_off"}
    assert {m["name"] for m in res["end_to_end"]} >= {"solve_s", "setup_s"}
    per_layer = {m["name"]: m for m in res["per_layer"]}
    assert set(per_layer) >= set(NEW) | {"iters_per_s", "operator_build_s"}
    assert all(CELL in per_layer[n]["workloads"] for n in per_layer)
    assert all(per_layer[n]["workloads"] == [CELL] for n in NEW)
    assert all(per_layer[n]["moves"] == "solve_s" for n in NEW)
    assert per_layer["operator_build_s"]["moves"] == "setup_s"
    for name in per_layer:
        manifest.metric_reader("layer_metrics", name)
    small = manifest.cell(CELL, rehearse=True)["config"]["sizes"]
    assert int(np.prod(small["box"])) <= 10_000
    assert small["restart"] == sizes["restart"] and small["cycles"] >= 4


def test_bytes_function_counts_the_floor_of_a_call():
    mod = manifest.load_module("bytes", "gmres_dia")
    n = 1_270_432
    # a cycle at D = 7, m = 30: 30 (7 + 3 + 31) + (30 + 7 + 5) = 1272 n values
    assert mod.bytes_per_call(n, 7, 30, 10) == 10 * 1272 * n * 4
    assert mod.bytes_per_call(n, 7, 30, 10) == pytest.approx(64.6e9, rel=2e-3)
    assert mod.bytes_per_call(n, 7, 30, 1) * 10 == mod.bytes_per_call(n, 7, 30, 10)
    # one triangular pass: steps j = 1..m read j vectors twice
    assert mod.bytes_per_call(1, 0, 3, 1, itemsize=1) == 3 * (0 + 3 + 4) + (3 + 0 + 5)
    assert mod.bytes_per_iteration(n, 7, 30, 10) * 300 == pytest.approx(
        mod.bytes_per_call(n, 7, 30, 10))
    assert mod.bytes_per_call(10, 7, 30, 10, itemsize=2) * 2 == mod.bytes_per_call(
        10, 7, 30, 10)


def test_every_new_metric_reads_nothing_from_an_empty_run():
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(empty, params) is None, name
    # a trace without the program, and the spans of another solver, as the
    # parent's would be: nothing too
    dev = {"programs": {"jit_pcg": [3, 1.0]}, "ops": {
        ("jit_pcg", "fusion.1", "fusion", "kLoop"): [3, 1.0]}}
    run = {"trace": {"devices": {0: dev}},
           "shape": {"rows": 96, "diagonals": 7, "restart": 30, "cycles": 10},
           "events": {"solver.solve": [{"iters": 300}],
                      "span": [{"name": "cg.solve", "dispatch_s": 0.001}],
                      "program.hlo": [{"program": "jit_gmres", "text": ""}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(run, params) is None, name


HLO = """HloModule jit_gmres
%fused_computation.7 (p: f32[96]) -> f32[96] {
  ROOT %mul.30 = f32[96]{0} multiply(%p, %p), metadata={op_name="jit(gmres)/while/body/while/body/gmres.orth/mul"}
}
%body (t: (f32[31,96])) -> (f32[31,96]) {
  %fusion.1 = f32[96]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(gmres)/while/body/while/body/gmres.spmv/jit(dia_spmv_xla)/add" stack_frame_id=4}
  %multiply_reduce_fusion.2 = f32[31]{0} fusion(%fusion.1), kind=kLoop, calls=%fc, metadata={op_name="jit(gmres)/while/body/while/body/gmres.orth/dot_general"}
  %mul.3 = f32[96]{0} fusion(%multiply_reduce_fusion.2), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(gmres)/while/body/while/body/gmres.orth/mul"}
  %sqrt.4 = f32[]{:T(128)} sqrt(%s), metadata={op_name="jit(gmres)/while/body/while/body/gmres.small/sqrt"}
  %mul.5 = f32[]{:T(128)} multiply(%c, %d), metadata={op_name="jit(gmres)/while/body/while/body/gmres.small/while/body/closed_call/mul"}
  %dynamic-update-slice.6 = f32[31,96]{1,0} dynamic-update-slice(%V, %w, %k), metadata={op_name="jit(gmres)/while/body/while/body/gmres.update/scatter"}
  %broadcast_select_fusion.7 = f32[1,96]{1,0} fusion(%w), kind=kLoop, calls=%fc
  ROOT %add.8 = f32[96]{0} fusion(%x), kind=kLoop, calls=%fc, metadata={op_name="jit(gmres)/while/body/gmres.update/add"}
}
"""


def test_scope_shares_and_roofline_read_a_hand_made_run():
    """Three whole runs of ``jit_gmres`` of 300 steps: a scope's share is
    the self time of the ops whose ``op_name`` in the program's text stands
    under it; a fusion the compiler made has no ``op_name`` and counts to no
    scope."""
    red = manifest.load_module("reducers", "op_scope_share")
    names = red.op_names(HLO)
    assert names["multiply_reduce_fusion.2"].endswith("gmres.orth/dot_general")
    assert names["broadcast_select_fusion.7"] == ""
    secs = {"fusion.1": 0.20, "multiply_reduce_fusion.2": 0.30, "mul.3": 0.10,
            "sqrt.4": 0.02, "mul.5": 0.13, "dynamic-update-slice.6": 0.05,
            "broadcast_select_fusion.7": 0.04, "add.8": 0.01, "while": 0.05}
    ops = {("jit_gmres", k, "fusion", "kLoop"): [900, v] for k, v in secs.items()}
    ops[("jit_other", "mul.5", "fusion", "kLoop")] = [1, 9.0]
    dev = {"programs": {"jit_gmres": [3, 1.0], "jit_other": [1, 9.0]}, "ops": ops}
    shape = {"rows": 96, "diagonals": 7, "restart": 30, "cycles": 10}
    run = {"trace": {"devices": {0: dev}}, "shape": shape,
           "events": {"solver.solve": [{"iters": 300}] * 4,
                      "program.hlo": [{"program": "jit_gmres", "text": HLO}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    want = {"gmres_orth_pct": 0.30 + 0.10, "gmres_spmv_pct": 0.20,
            "gmres_small_pct": 0.02 + 0.13}
    for name, share in want.items():
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(run, params) == pytest.approx(100 * share / 1.0), name
    assert red.scope_seconds(run["trace"], names, "jit_gmres", "nothing") == (
        0.0, pytest.approx(0.04 + 0.05), pytest.approx(sum(secs.values())))
    read, params = manifest.metric_reader("layer_metrics", "gmres_roofline")
    per_call = manifest.load_module("bytes", "gmres_dia").bytes_per_call(
        96, 7, 30, 10)
    assert read(run, params) == pytest.approx(100 * per_call * 3 / 1.0 / 819e9)
    # without the text the shares read nothing; the roofline does not need it
    del run["events"]["program.hlo"]
    read_o, params_o = manifest.metric_reader("layer_metrics", "gmres_orth_pct")
    assert read_o(run, params_o) is None and read(run, params) is not None


def test_span_metrics_read_hand_made_events():
    spans = [{"kind": "span", "name": "gmres.solve", "path": "device",
              "restart": 30, "cycles": 10, "iters": 300, "fetches": f,
              "dur_s": 0.4, "dispatch_s": d}
             for d, f in ((0.0011, 1), (0.0012, 1), (0.0016, 1))]
    spans.append({"kind": "span", "name": "cg.solve", "dur_s": 7.0,
                  "dispatch_s": 0.5})
    run = {"events": {"span": spans}}
    read, params = manifest.metric_reader("layer_metrics", "gmres_dispatch_ms")
    assert read(run, params) == pytest.approx(1.2)
    read, params = manifest.metric_reader("layer_metrics", "gmres_fetches_per_solve")
    assert read(run, params) == 1
    # the cycle path: one fetch a cycle, which the metric shows
    for e in spans[:3]:
        e.update(path="cycle", fetches=10)
    assert read(run, params) == 10


def test_the_adaptor_refuses_a_program_without_the_compiled_gmres(monkeypatch):
    from sparse_tpu import linalg  # noqa: F401 - registers the counter
    from sparse_tpu.telemetry import _metrics

    system = manifest.load_module("systems", "library_gmres")
    monkeypatch.setattr(_metrics, "family", lambda name: [])
    with pytest.raises(RuntimeError, match="gmres.traces"):
        system.System({}, {}, None)


def test_the_adaptor_holds_the_guarantees_and_hands_over_spans_and_text():
    import run as harness
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings

    system = manifest.load_module("systems", "library_gmres")
    d = gen.make({"box": [10, 9, 8], "restart": 12, "cycles": 3}, 4)
    was = settings.telemetry
    ctx = harness.Context(True)
    try:
        telemetry.reset()
        ctx.events_on()
        sut = system.System({}, d, ctx)
        sut.warm()
        assert sut.shape == {"rows": 720, "diagonals": 7, "restart": 12,
                             "cycles": 3}
        n0 = len(telemetry.events("span"))
        out = sut.call()
        window = telemetry.events("span")[n0:]
        events = {"span": list(window)}
        sut.check_events(events)
        sut.check_events({"span": [dict(window[0], path="cycle", fetches=3)]})
        sut.close()
    finally:
        settings.telemetry = was
        telemetry.configure(None)
        telemetry.reset()
        ctx.close()
    assert out["iters"] == 36 and np.asarray(out["x"]).shape == (720,)
    assert events["span"] == window and [e["name"] for e in window] == ["gmres.solve"]
    names = [e["name"] for e in events["setup.span"]]
    assert names.count("gmres.solve") == 2 and names.count("layout.dia_build") == 1
    (hlo,) = events["program.hlo"]
    assert hlo["program"] == "jit_gmres" and "/gmres.orth/" in hlo["text"]
    checks = {c["name"]: c for c in ctx.checks}
    assert set(checks) == {
        "solver_path_not_device", "warm_call_not_jit_gmres", "layout_not_dia",
        "window_solve_not_jit_gmres", "gmres_traces_in_window"}
    # all held, but for the second window's span of the cycle path
    assert [c["ok"] for c in ctx.checks] == [True, True, True, True, False, True]
    assert "operator_build" in ctx.spans and "warm_call" in ctx.spans


@pytest.mark.parametrize("how", ["answer", "stale"])
def test_a_rehearsal_with_gmres_broken_underneath_is_not_correct(how, monkeypatch):
    """``tests/broken_run.py``'s two breaks, on the solver this cell calls:
    every answer scaled by 1 + 1e-2, or the start returned unchanged."""
    import run as harness
    from sparse_tpu import linalg

    gmres = linalg.gmres

    def broken(*a, **k):
        x, it = gmres(*a, **k)
        return (x * 0 if how == "stale" else x * (1.0 + 1e-2)), it

    monkeypatch.setattr(linalg, "gmres", broken)
    seen = {}
    ns = argparse.Namespace(workload=CELL, seed=2147483659, seconds=0.5,
                            trace=0, rehearse=True)
    code, line = harness.run_cell(ns, on_result=seen.update)
    assert code == 1 and line["correct"] is False
    failed = [c["name"] for c in seen["checks"] if not c["ok"]]
    assert not seen["checks_ok"] and "x_vs_reference" in failed
