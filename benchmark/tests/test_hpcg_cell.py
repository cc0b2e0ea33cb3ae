"""The cell ``hpcg_pcg_1chip`` on the CPU: that it resolves to its files, the
bytes function against the figure in its docstring, the cell's metric files
on hand-made runs and on an empty one, the adaptor's refusal of a program
without the model and its guarantees (27 stored planes a level among them).
(The rehearsal, the control and the broken timed path run for every cell of
BENCHMARK.json in test_benchmark.py, this one included; the plain reference
is tied to scipy's explicit matrices in tests/test_hpcg_reference.py.)"""

from __future__ import annotations

import builtins
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402

CELL = "hpcg_pcg_1chip"
NEW = ("hpcg_build_s", "hpcg_roofline", "hpcg_symgs_pct", "hpcg_spmv_pct",
       "hpcg_coarse_pct", "hpcg_colour_updates", "hpcg_colour_roofline")
SHARED = ("iters_per_s", "operator_build_s", "solve_call_ms",
          "solve_call_max_ms", "solve_prep_ms", "solve_wait_ms",
          "solve_rest_ms", "solve_caller_ms")
gen = manifest.load_module("operators", "hpcg_27pt")


def test_the_cell_resolves_to_its_files():
    res = manifest.cell(CELL)
    cfg, wl = res["config"], res["workload"]
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "hpcg-27pt-256", "back_to_back_brief_trace", 1)
    assert (cfg["operator"], cfg["system"]) == ("hpcg_27pt", "library_hpcg_pcg")
    sizes = cfg["sizes"]
    # the source's widths: the local grid's plane shape, four levels, and
    # never fewer than a set's 50 iterations
    assert sizes["grid"][:2] == [256, 256] and sizes["grid"][2] in (128, 256)
    assert sizes["levels"] == 4 and sizes["iterations"] >= 50
    assert sizes["dtype"] == "float32" and "dtype" in cfg["reduced"]
    source = {"grid": [256, 256, 256], "dtype": "float64"}
    cut = {k for k, v in source.items() if sizes[k] != v}
    assert cut <= set(cfg["reduced"]) and all(cfg["reduced"].values())
    assert {"iterations", "sweep_order", "b", "tol"} <= set(cfg["assumed"])
    assert {m["name"] for m in res["end_to_end"]} >= {"solve_s", "setup_s"}
    per_layer = {m["name"]: m for m in res["per_layer"]}
    assert set(per_layer) == set(NEW) | set(SHARED)
    assert all(per_layer[n]["workloads"] == [CELL] for n in NEW)
    assert all(per_layer[n]["workloads"][-1] == CELL for n in SHARED)
    assert per_layer["hpcg_build_s"]["moves"] == "setup_s"
    assert all(per_layer[n]["moves"] == "solve_s" for n in NEW[1:])
    for name in per_layer:
        manifest.metric_reader("layer_metrics", name)
    small = manifest.cell(CELL, rehearse=True)["config"]["sizes"]
    assert max(small["grid"]) <= 64 and small["levels"] == sizes["levels"]


def test_bytes_function_counts_the_figure_in_its_docstring():
    mod = manifest.load_module("bytes", "pcg_hpcg")
    b = mod.bytes_per_iteration
    at_256 = b([256, 256, 256], 4)
    assert at_256 == 11_924_013_056 and "11,924,013,056 B" in mod.__doc__
    assert at_256 == 4 * ((986 + 296) * 2_097_152 + 986 * 262_144
                          + 986 * 32_768 + 408 * 4_096)
    assert "2,981,003,264 values" in mod.__doc__ and at_256 // 4 == 2_981_003_264
    assert at_256 / 819e9 == pytest.approx(14.56e-3, rel=1e-3)
    # the kernel's own share of it: all but CG's recurrence and a block a
    # level for the prolongation
    kernel = manifest.load_module("bytes", "hpcg_colour")
    k = kernel.bytes_per_iteration
    assert k([256, 256, 256], 4) == 11_377_573_888
    assert "11,377,573,888 B" in kernel.__doc__
    assert at_256 - k([256, 256, 256], 4) == 4 * (
        (296 - 232 + 1) * 2_097_152 + 262_144 + 32_768)
    # one level: a step from zero and CG's own; a flat grid; half the bytes
    assert b([16, 16, 16], 1) == 4 * (408 + 296) * 512
    assert b([32, 16, 8], 2) == 4 * ((986 + 296) * 512 + 408 * 64)
    assert b([256, 256, 128], 4) * 2 == at_256
    assert b([256, 256, 256], 4, itemsize=2) * 2 == at_256


def test_every_new_metric_reads_nothing_from_an_empty_run():
    from sparse_tpu import telemetry

    telemetry.reset()  # span_total reads the process's own aggregate
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(empty, params) is None, name
    # a trace without the program and spans without the field, as the
    # parent's would be: nothing too
    dev = {"programs": {"jit_cg_general": [3, 1.0]}, "ops": {
        ("jit_cg_general", "fusion.1", "fusion", "kLoop"): [3, 1.0]}}
    run = {"trace": {"devices": {0: dev}},
           "shape": {"grid": [32, 32, 32], "levels": 4},
           "events": {"solver.solve": [{"iters": 63}],
                      "span": [{"name": "cg.solve", "precond": "gmg_grid"}],
                      "program.hlo": [{"program": "jit_pcg", "text": ""}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in NEW[1:]:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(run, params) is None, name


HLO = """HloModule jit_pcg
%body (t: (f32[32768])) -> (f32[32768]) {
  %fusion.1 = f32[16,16,16]{2,1,0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/hpcg.l0/hpcg.l0.symgs/div"}
  %fusion.2 = f32[16,16,16]{2,1,0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/hpcg.l0/hpcg.l0.spmv/sub"}
  %fusion.3 = f32[8,8,8,8]{3,2,1,0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/hpcg.l0/hpcg.l0.transfer/transpose"}
  %fusion.4 = f32[8,8,8]{2,1,0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/hpcg.l1/hpcg.l1.symgs/div"}
  %fusion.5 = f32[8,8,8]{2,1,0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/hpcg.l1/hpcg.l1.spmv/sub"}
  %fusion.6 = f32[2,2,2]{2,1,0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/hpcg.l3/hpcg.l3.symgs/div"}
  %fusion.7 = f32[8,16,16,16]{3,2,1,0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/hpcg.spmv/add"}
  %copy.8 = f32[16,16,16]{1,2,0} copy(%fusion.1)
  ROOT %fusion.9 = f32[32768]{0} fusion(%x), kind=kLoop, calls=%fc, metadata={op_name="jit(pcg)/while/body/add"}
}
"""


def test_scope_shares_roofline_and_count_read_a_hand_made_run():
    """Two whole runs of ``jit_pcg`` of 63 iterations, a second and a half of
    device time in all."""
    secs = {"fusion.1": 0.50, "fusion.2": 0.06, "fusion.3": 0.04,
            "fusion.4": 0.10, "fusion.5": 0.02, "fusion.6": 0.03,
            "fusion.7": 0.30, "copy.8": 0.05, "fusion.9": 0.20}
    ops = {("jit_pcg", k, "fusion", "kLoop"): [126, v] for k, v in secs.items()}
    ops[("jit_other", "fusion.4", "fusion", "kLoop")] = [1, 9.0]
    dev = {"programs": {"jit_pcg": [2, 1.5], "jit_other": [1, 9.0]}, "ops": ops}
    solve = {"kind": "span", "name": "cg.solve", "path": "device",
             "precond": "hpcg_mg", "levels": 4, "colours": 8,
             "colour_updates": 105}
    run = {"trace": {"devices": {0: dev}},
           "shape": {"rows": 32768, "grid": [32, 32, 32], "levels": 4},
           "events": {"solver.solve": [{"iters": 63}] * 3,
                      "span": [solve, dict(solve), {"name": "other"}],
                      "program.hlo": [{"program": "jit_pcg", "text": HLO}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}

    def value(name):
        read, params = manifest.metric_reader("layer_metrics", name)
        return read(run, params)

    assert value("hpcg_symgs_pct") == pytest.approx(100 * (0.50 + 0.10 + 0.03) / 1.5)
    assert value("hpcg_spmv_pct") == pytest.approx(100 * (0.06 + 0.02 + 0.30) / 1.5)
    assert value("hpcg_coarse_pct") == pytest.approx(100 * (0.10 + 0.02 + 0.03) / 1.5)
    per_it = manifest.load_module("bytes", "pcg_hpcg").bytes_per_iteration(
        [32, 32, 32], 4)
    assert value("hpcg_roofline") == pytest.approx(
        100 * per_it * 126 / 1.5 / 819e9)
    assert value("hpcg_colour_updates") == 105
    # the kernel's roofline takes the program's custom calls alone
    ops[("jit_pcg", "custom-call.1", "custom-call", "tpu_custom_call")] = [218, 0.8]
    ops[("jit_pcg", "custom-call.2", "custom-call", "tpu_custom_call")] = [109, 0.4]
    per_it = manifest.load_module("bytes", "hpcg_colour").bytes_per_iteration(
        [32, 32, 32], 4)
    assert value("hpcg_colour_roofline") == pytest.approx(
        100 * per_it * 3 / 1.2 / 819e9)
    spec = manifest.load_json("layer_metrics", "hpcg_build_s.json")
    assert spec["reducer"] == "span_total"
    assert spec["params"] == {"name": "hpcg.build_hierarchy"}


def test_the_adaptor_refuses_a_program_without_the_model(monkeypatch):
    system = manifest.load_module("systems", "library_hpcg_pcg")
    real = builtins.__import__

    def without_the_model(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "sparse_tpu.models" and "hpcg_grid" in (fromlist or ()):
            raise ImportError("cannot import name 'hpcg_grid'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.delitem(sys.modules, "sparse_tpu.models.hpcg_grid", raising=False)
    monkeypatch.setattr(builtins, "__import__", without_the_model)
    with pytest.raises(RuntimeError, match="no sparse_tpu.models.hpcg_grid"):
        system.System({}, {}, None)


def test_the_adaptor_holds_the_guarantees_and_hands_over_spans_and_text(monkeypatch):
    import run as harness
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings
    from sparse_tpu.models import hpcg_grid

    system = manifest.load_module("systems", "library_hpcg_pcg")
    d = gen.make({"grid": [16, 16, 16], "levels": 3, "iterations": 9}, 4)
    was = settings.telemetry
    ctx = harness.Context(True)
    try:
        telemetry.reset()
        ctx.events_on()
        sut = system.System({}, d, ctx)
        assert sut.shape == {"rows": 4096, "grid": [16, 16, 16], "levels": 3}
        sut.warm()
        n0 = len(telemetry.events("span"))
        out = sut.call()
        window = telemetry.events("span")[n0:]
        events = {"span": list(window)}
        sut.check_events(events)
        sut.check_events({"span": [dict(window[0], precond="gmg_grid")]})
        sut.close()
        # a hierarchy that keeps fewer coefficients than 27 a row is a
        # matrix-free product, whatever it computes
        real = hpcg_grid.build_hierarchy
        monkeypatch.setattr(
            hpcg_grid, "build_hierarchy",
            lambda *a, **k: [p[:, :14] for p in real(*a, **k)])
        monkeypatch.setattr(hpcg_grid, "grid_operator", lambda hier: type(
            "A", (), {"operands": hier[0]})())
        monkeypatch.setattr(hpcg_grid, "make_vcycle", lambda hier: type(
            "M", (), {"operands": tuple(hier)})())
        thin = harness.Context(False)
        system.System({}, d, thin)
        thin.close()
    finally:
        settings.telemetry = was
        telemetry.configure(None)
        telemetry.reset()
        ctx.close()
    assert out["iters"] == 9 and np.asarray(out["x"]).shape == (4096,)
    assert events["span"] == window and [e["name"] for e in window] == ["cg.solve"]
    assert window[0]["colour_updates"] == 15 * 5
    names = [e["name"] for e in events["setup.span"]]
    assert names.count("hpcg.build_hierarchy") == 1 and names.count("cg.solve") == 2
    (hlo,) = events["program.hlo"]
    assert hlo["program"] == "jit_pcg" and "hpcg" in hlo["text"]
    checks = {c["name"]: c for c in ctx.checks}
    assert set(checks) == {
        "levels_not_27_stored_planes", "solver_path_not_device",
        "warm_call_not_jit_pcg_over_hpcg_mg",
        "window_solve_not_jit_pcg_over_hpcg_mg", "cg_precond_traces_in_window"}
    # all held, but for the second window's span of another preconditioner
    assert [c["ok"] for c in ctx.checks] == [True, True, True, True, False, True]
    assert "operator_build" in ctx.spans and "warm_call" in ctx.spans
    (thin_check,) = thin.checks
    assert thin_check["name"] == "levels_not_27_stored_planes"
    assert not thin_check["ok"]
