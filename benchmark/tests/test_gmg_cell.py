"""The cell ``gmg_pcg_1chip`` on the CPU: that it resolves to its files, the
bytes function, the cell's metric files on hand-made runs and on an empty
one, the adaptor's refusal and what it hands a traced run. (The rehearsal,
the control and the broken timed path run for every cell of BENCHMARK.json in
test_benchmark.py; the plain reference is tied to scipy's explicit products
in tests/test_gmg_reference.py.)"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402

CELL = "gmg_pcg_1chip"
NEW = ("gmg_build_s", "pcg_dispatch_ms", "pcg_roofline", "pcg_vcycle_pct",
       "pcg_coarse_pct")
gen = manifest.load_module("operators", "gmg_poisson")


def test_the_cell_resolves_to_its_files():
    res = manifest.cell(CELL)
    cfg, wl = res["config"], res["workload"]
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "gmg-poisson-4500", "back_to_back_brief_trace", 1)
    assert (cfg["operator"], cfg["system"]) == ("gmg_poisson", "library_gmg_pcg")
    sizes = cfg["sizes"]
    assert sizes["levels"] == 3 and sizes["gridop"] == "linear"
    assert sizes["grid"] % 4 == 0 and 3200 <= sizes["grid"] <= 4500
    assert sizes["iterations"] % 25 == 0 and 50 <= sizes["iterations"] <= 200
    # every cut from the source's run line is listed with its reason
    source = {"grid": 4500, "iterations": 200, "dtype": "float64"}
    cut = {k for k, v in source.items() if sizes[k] != v}
    assert cut <= set(cfg["reduced"]) and all(cfg["reduced"].values())
    assert {m["name"] for m in res["end_to_end"]} >= {"solve_s", "setup_s"}
    per_layer = {m["name"]: m for m in res["per_layer"]}
    assert set(per_layer) >= set(NEW) | {"iters_per_s"}
    assert all(per_layer[n]["workloads"] == [CELL] for n in NEW)
    assert CELL in per_layer["iters_per_s"]["workloads"]
    assert per_layer["gmg_build_s"]["moves"] == "setup_s"
    assert all(per_layer[n]["moves"] == "solve_s" for n in NEW[1:])
    for name in per_layer:
        manifest.metric_reader("layer_metrics", name)
    small = manifest.cell(CELL, rehearse=True)["config"]["sizes"]
    assert small["grid"] <= 256 and small["levels"] == sizes["levels"]


def test_bytes_function_counts_the_floor_of_an_iteration():
    b = manifest.load_module("bytes", "pcg_gmg").bytes_per_iteration
    n = 4500 * 4500
    # CG's 8 N, the fine level's 5 N, level 1's 30 N/4, level 2's 5 N/16
    assert b(4500, 3) == 4 * (8 * n + 5 * n + 30 * 2250 ** 2 + 5 * 1125 ** 2)
    assert b(4500, 3) == pytest.approx(20.8125 * 4 * n)
    assert b(4500, 1) == 4 * 9 * n  # no hierarchy: z = w r, w a scalar
    assert b(4500, 2) == 4 * (13 * n + 5 * 2250 ** 2)
    assert b(33, 3) == 4 * (13 * 33 ** 2 + 30 * 16 ** 2 + 5 * 8 ** 2)  # halves round down
    assert b(4480, 3, itemsize=2) * 2 == b(4480, 3)


def test_every_new_metric_reads_nothing_from_an_empty_run():
    from sparse_tpu import telemetry

    telemetry.reset()  # span_total reads the process's own aggregate
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(empty, params) is None, name
    # a trace without the program, as the parent's would be: nothing too
    dev = {"programs": {"jit_cg_general": [3, 1.0]}, "ops": {
        ("jit_cg_general", "fusion.1", "fusion", "kLoop"): [3, 1.0]}}
    run = {"trace": {"devices": {0: dev}}, "shape": {"grid": 96, "levels": 3},
           "events": {"solver.solve": [{"iters": 25}],
                      "program.hlo": [{"program": "jit_pcg", "text": ""}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in ("pcg_roofline", "pcg_vcycle_pct", "pcg_coarse_pct"):
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(run, params) is None, name


HLO = """HloModule jit_pcg
%fused_computation.7 (p: f32[96,96]) -> f32[96,96] {
  ROOT %mul.30 = f32[96,96]{1,0} multiply(%p, %p), metadata={op_name="jit(pcg)/while/body/gmg.l1/mul"}
}
%body (t: (f32[9216])) -> (f32[9216]) {
  %fusion.1 = f32[96,96]{1,0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/gmg.l0/sub" stack_frame_id=4}
  %fusion.2 = f32[48,48]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/gmg.l0/jit(restrict_grid)/mul"}
  %mul.3 = f32[48,48]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(pcg)/while/body/gmg.l1/mul"}
  %fusion.4 = f32[24,24]{1,0} fusion(%mul.3), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/gmg.l2/mul"}
  %multiply_add_fusion.5 = f32[96,96]{1,0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/jit(stencil_apply)/add"}
  %copy.6 = f32[96,96]{0,1} copy(%fusion.1)
  ROOT %fusion.8 = f32[9216]{0} fusion(%x), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/add"}
}
"""


def test_scope_shares_and_roofline_read_a_hand_made_run():
    """Three whole runs of ``jit_pcg`` of 25 iterations: a level's share is
    the self time of the ops whose ``op_name`` in the program's text stands
    under the level's scope; ``A p`` (``stencil_apply`` without a scope) and
    the flat ops are CG's own; a copy the compiler made has no ``op_name``."""
    red = manifest.load_module("reducers", "op_scope_share")
    names = red.op_names(HLO)
    assert names["fusion.2"].endswith("gmg.l0/jit(restrict_grid)/mul")
    assert names["copy.6"] == "" and names["mul.3"].endswith("gmg.l1/mul")
    secs = {"fusion.1": 0.30, "fusion.2": 0.10, "mul.3": 0.08, "fusion.4": 0.02,
            "multiply_add_fusion.5": 0.15, "copy.6": 0.05, "fusion.8": 0.25,
            "while": 0.01}
    ops = {("jit_pcg", k, "fusion", "kLoop"): [75, v] for k, v in secs.items()}
    ops[("jit_other", "fusion.4", "fusion", "kLoop")] = [1, 9.0]
    dev = {"programs": {"jit_pcg": [3, 1.0], "jit_other": [1, 9.0]}, "ops": ops}
    run = {"trace": {"devices": {0: dev}}, "shape": {"rows": 9216, "grid": 96,
                                                     "levels": 3},
           "events": {"solver.solve": [{"iters": 25}] * 4,
                      "program.hlo": [{"program": "jit_pcg", "text": HLO}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    read, params = manifest.metric_reader("layer_metrics", "pcg_vcycle_pct")
    assert read(run, params) == pytest.approx(100 * (0.30 + 0.10 + 0.08 + 0.02) / 1.0)
    read, params = manifest.metric_reader("layer_metrics", "pcg_coarse_pct")
    assert read(run, params) == pytest.approx(100 * (0.08 + 0.02) / 1.0)
    assert red.scope_seconds(run["trace"], names, "jit_pcg", "nothing") == (
        0.0, pytest.approx(0.05 + 0.01), pytest.approx(sum(secs.values())))
    read, params = manifest.metric_reader("layer_metrics", "pcg_roofline")
    per_it = manifest.load_module("bytes", "pcg_gmg").bytes_per_iteration(96, 3)
    assert read(run, params) == pytest.approx(100 * per_it * 75 / 1.0 / 819e9)
    # without the text the shares read nothing; the roofline does not need it
    del run["events"]["program.hlo"]
    read_v, params_v = manifest.metric_reader("layer_metrics", "pcg_vcycle_pct")
    assert read_v(run, params_v) is None and read(run, params) is not None


def test_span_metrics_read_hand_made_events():
    read, params = manifest.metric_reader("layer_metrics", "pcg_dispatch_ms")
    spans = [{"kind": "span", "name": "cg.solve", "path": "device",
              "precond": "gmg_grid", "levels": 3, "dur_s": 1.2, "dispatch_s": d}
             for d in (0.0011, 0.0012, 0.0016)]
    spans.append({"kind": "span", "name": "gmg.build_hierarchy", "dur_s": 7.0})
    assert read({"events": {"span": spans}}, params) == pytest.approx(1.2)
    spec = manifest.load_json("layer_metrics", "gmg_build_s.json")
    assert spec["reducer"] == "span_total"
    assert spec["params"] == {"name": "gmg.build_hierarchy"}


def test_the_adaptor_refuses_a_program_without_the_compiled_pcg(monkeypatch):
    from sparse_tpu import linalg  # noqa: F401 - registers the counter
    from sparse_tpu.telemetry import _metrics

    system = manifest.load_module("systems", "library_gmg_pcg")
    monkeypatch.setattr(_metrics, "family", lambda name: [])
    with pytest.raises(RuntimeError, match="cg.precond.traces"):
        system.System({}, {}, None)


def test_the_adaptor_holds_the_guarantees_and_hands_over_spans_and_text():
    import run as harness
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings

    system = manifest.load_module("systems", "library_gmg_pcg")
    d = gen.make({"grid": 48, "levels": 3, "iterations": 10,
                  "gridop": "linear"}, 4)
    was = settings.telemetry
    ctx = harness.Context(True)
    try:
        telemetry.reset()
        ctx.events_on()
        sut = system.System({}, d, ctx)
        assert sut.shape == {"rows": 48 * 48, "grid": 48, "levels": 3}
        sut.warm()
        n0 = len(telemetry.events("span"))
        out = sut.call()
        window = telemetry.events("span")[n0:]
        events = {"span": list(window)}
        sut.check_events(events)
        sut.check_events({"span": [dict(window[0], precond="jacobi")]})
        sut.close()
    finally:
        settings.telemetry = was
        telemetry.configure(None)
        telemetry.reset()
        ctx.close()
    assert out["iters"] == 10 and np.asarray(out["x"]).shape == (48 * 48,)
    assert events["span"] == window and [e["name"] for e in window] == ["cg.solve"]
    names = [e["name"] for e in events["setup.span"]]
    assert names.count("gmg.build_hierarchy") == 1 and names.count("cg.solve") == 2
    (hlo,) = events["program.hlo"]
    assert hlo["program"] == "jit_pcg" and "/gmg.l2/" in hlo["text"]
    checks = {c["name"]: c for c in ctx.checks}
    assert set(checks) == {
        "solver_path_not_device", "warm_call_not_jit_pcg_over_gmg_grid",
        "window_solve_not_jit_pcg_over_gmg_grid", "cg_precond_traces_in_window"}
    # all held, but for the second window's span of another preconditioner
    assert [c["ok"] for c in ctx.checks] == [True, True, True, False, True]
    assert "operator_build" in ctx.spans and "warm_call" in ctx.spans
