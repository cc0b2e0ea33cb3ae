"""The cell ``gmg_pcg_4chip`` on the CPU with four host devices: that it
resolves to its files, the bytes of one chip's share, the cell's metric files
on hand-made runs and on an empty one, what the adaptor reads off a compiled
program's text, the guarantees it holds over a laid-out hierarchy and over
one left on a single device, and a rehearsal with ``linalg.cg`` broken
underneath. (The rehearsal, the control and the broken timed path run for
every cell of BENCHMARK.json in test_benchmark.py too; the plain reference is
tied to scipy's explicit products in tests/test_gmg_reference.py; the program
over a mesh against that reference in tests/test_gmg_mesh.py.)"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402

CELL = "gmg_pcg_4chip"
TWINS = {"gmg_mesh_build_s": "gmg_build_s",
         "gmg_mesh_dispatch_ms": "pcg_dispatch_ms",
         "gmg_mesh_vcycle_pct": "pcg_vcycle_pct",
         "gmg_mesh_coarse_pct": "pcg_coarse_pct"}
NEW = ("gmg_mesh_collective_pct", "gmg_mesh_permutes_per_iter",
       "gmg_mesh_roofline", "gmg_mesh_fine_kernel_pct", "gmg_mesh_layout_s",
       *TWINS)


def run_code(code: str, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    prelude = (f"import sys\nsys.path[:0] = [{HERE!r}, {ROOT!r}]\n"
               "import json, manifest\n")
    return subprocess.run([sys.executable, "-c", prelude + code], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def last_json(p):
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_resolves_to_its_files():
    res = manifest.cell(CELL)
    cfg, wl = res["config"], res["workload"]
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "gmg-poisson-weak4", "back_to_back_brief_trace", 4)
    assert (cfg["operator"], cfg["system"], cfg["chips"]) == (
        "gmg_poisson", "library_gmg_pcg_mesh", 4)
    sizes = cfg["sizes"]
    assert sizes["levels"] == 3 and sizes["gridop"] == "linear"
    # the issue's sides: multiples of 512, so that every level's rows a
    # shard are a multiple of 8 and the side one of 128
    assert sizes["grid"] in (5120, 6400, 7680, 8960)
    assert sizes["iterations"] % 25 == 0 and 50 <= sizes["iterations"] <= 200
    source = {"grid": 9000, "iterations": 200, "dtype": "float64"}
    cut = {k for k, v in source.items() if sizes[k] != v}
    assert cut <= set(cfg["reduced"]) and all(cfg["reduced"].values())
    entry = next(c for c in manifest.benchmark()["configs"]
                 if c["name"] == wl["config"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert set(cfg["limits"]) == {"x_vs_reference", "relres_gap", "iterations_off"}
    assert {m["name"] for m in res["end_to_end"]} >= {"solve_s", "setup_s"}
    per_layer = {m["name"]: m for m in res["per_layer"]}
    assert set(per_layer) == set(NEW) | {
        "iters_per_s", "operator_build_s", "pcg_fine_stencil_kernels"}
    assert all(per_layer[n]["workloads"] == [CELL] for n in NEW)
    for name in per_layer:
        manifest.metric_reader("layer_metrics", name)
    # a twin is its one-chip metric under a name of its own: same reducer,
    # same parameters, same layer, same end-to-end metric
    all_metrics = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    for twin, of in TWINS.items():
        mine = manifest.load_json("layer_metrics", twin + ".json")
        theirs = manifest.load_json("layer_metrics", of + ".json")
        assert (mine["reducer"], mine["params"]) == (
            theirs["reducer"], theirs["params"]), twin
        assert all(all_metrics[twin][k] == all_metrics[of][k]
                   for k in ("unit", "better", "source", "layer", "moves")), twin
    assert per_layer["gmg_mesh_layout_s"]["moves"] == "setup_s"
    # no share of a kernel's roofline: it would read past 105 % (the doc)
    assert [n for n in NEW if n.endswith("_roofline")] == ["gmg_mesh_roofline"]
    small = manifest.cell(CELL, rehearse=True)["config"]["sizes"]
    assert small["grid"] == 192 and small["levels"] == sizes["levels"]
    assert all((small["grid"] >> k) % 4 == 0 for k in range(small["levels"]))


def test_bytes_are_one_chips_share():
    whole = manifest.load_module("bytes", "pcg_gmg").bytes_per_iteration
    share = manifest.load_module("bytes", "pcg_gmg_mesh").bytes_per_iteration
    assert share(5120, 3, 4) * 4 == whole(5120, 3)
    assert share(5120, 3, 1) == whole(5120, 3)
    assert share(192, 3, 4, itemsize=2) * 2 == share(192, 3, 4)


def test_every_new_metric_reads_nothing_from_an_empty_run():
    from sparse_tpu import telemetry

    telemetry.reset()  # span_total reads the process's own aggregate
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(empty, params) is None, name
    # a trace without the program and spans without the new field, as a
    # tree without the row-block forms would leave them: nothing either,
    # but for the dispatch, which every compiled solve's span carries
    dev = {"programs": {"jit_dist_cg_dia": [3, 1.0]}, "ops": {
        ("jit_dist_cg_dia", "collective-permute.1", "collective-permute", ""):
        [3, 1.0]}}
    run = {"trace": {"devices": {0: dev}},
           "shape": {"grid": 192, "levels": 3, "chips": 4}, "spans": {},
           "events": {"solver.solve": [{"iters": 25}],
                      "span": [{"name": "cg.solve", "dispatch_s": 0.001}],
                      "program.hlo": [{"program": "jit_pcg", "text": ""}]},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in NEW:
        read, params = manifest.metric_reader("layer_metrics", name)
        if name != "gmg_mesh_dispatch_ms":
            assert read(run, params) is None, name


def test_the_mesh_metrics_read_a_hand_made_run():
    """Three whole runs of ``jit_pcg`` of 25 iterations on the first chip:
    the collectives' own ops, the custom calls, and one chip's share of the
    bytes against one chip's peak."""
    ops = {("jit_pcg", "collective-permute-start.3", "collective-permute-start",
            ""): [75, 0.02],
           ("jit_pcg", "collective-permute-done.3", "collective-permute-done",
            ""): [75, 0.01],
           ("jit_pcg", "all-reduce.1", "all-reduce", ""): [150, 0.03],
           ("jit_pcg", "all-gather.2", "all-gather", ""): [75, 0.04],
           ("jit_pcg", "grid_stencil5_apply.3", "custom-call",
            "tpu_custom_call"): [75, 0.12],
           ("jit_pcg", "grid_stencil5_smooth.1", "custom-call",
            "tpu_custom_call"): [75, 0.08],
           ("jit_pcg", "fusion.80", "fusion", "kLoop"): [75, 0.70],
           ("jit_other", "all-reduce.9", "all-reduce", ""): [1, 9.0]}
    dev = {"programs": {"jit_pcg": [3, 1.0], "jit_other": [1, 9.0]}, "ops": ops}
    spans = [{"name": "cg.solve", "halo_exchanges": 14, "dispatch_s": d}
             for d in (0.0019, 0.0020, 0.0024)] + [{"name": "gmg.build_hierarchy"}]
    run = {"trace": {"devices": {0: dev, 1: {"programs": {}, "ops": {}}}},
           "shape": {"rows": 192 * 192, "grid": 192, "levels": 3, "chips": 4},
           "spans": {"operator_build": 5.0, "mesh_layout": 0.25},
           "events": {"solver.solve": [{"iters": 25}] * 4, "span": spans},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    want = {"gmg_mesh_collective_pct": 100 * (0.02 + 0.01 + 0.03 + 0.04),
            "gmg_mesh_fine_kernel_pct": 100 * (0.12 + 0.08),
            "gmg_mesh_permutes_per_iter": 14, "gmg_mesh_layout_s": 0.25,
            "gmg_mesh_dispatch_ms": 2.0}
    for name, value in want.items():
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(run, params) == pytest.approx(value), name
    read, params = manifest.metric_reader("layer_metrics", "gmg_mesh_roofline")
    per_it = manifest.load_module("bytes", "pcg_gmg").bytes_per_iteration(192, 3)
    assert read(run, params) == pytest.approx(
        100 * (per_it // 4) * 75 / 1.0 / 819e9)


TEXT = """HloModule jit_pcg
%body {
  %collective-permute-start.1 = (f32[1,192]{1,0}, f32[1,192]{1,0}) collective-permute-start(%a), metadata={op_name="jit(pcg)/while/body/gmg.l0/shard_map/ppermute"}
  %collective-permute-done.1 = f32[1,192]{1,0} collective-permute-done(%collective-permute-start.1), metadata={op_name="jit(pcg)/while/body/gmg.l0/shard_map/ppermute"}
  %collective-permute.2 = f32[1,96]{1,0} collective-permute(%b), metadata={op_name="jit(pcg)/while/body/gmg.l1/shard_map/ppermute"}
  %all-reduce.3 = f32[]{:T(128)} all-reduce(%c), metadata={op_name="jit(pcg)/while/body/reduce_sum"}
  %all-gather.4 = s32[48,1]{1,0} all-gather(%d), metadata={op_name="jit(pcg)/while/body/gmg.l1/gather"}
}
ENTRY %main {
  %collective-permute.5 = f32[1,192]{1,0} collective-permute(%e), metadata={op_name="jit(pcg)/shard_map/ppermute"}
  %all-gather-start.6 = (f32[48,192]{1,0}, f32[192,192]{1,0}) all-gather-start(%f), metadata={op_name="jit(pcg)/gmg.l0/reshape"}
  %all-to-all.7 = f32[192,192]{1,0} all-to-all(%g), metadata={op_name="jit(pcg)/while/body/transpose"}
}
"""


def test_the_adaptor_reads_the_loops_collectives_and_the_gathers():
    system = manifest.load_module("systems", "library_gmg_pcg_mesh")
    loop, gathered = system.collectives(TEXT)
    # an asynchronous pair once; the permute before the loop is not the loop's
    assert loop == {"collective-permute": 2, "all-reduce": 1, "all-gather": 1,
                    "all-to-all": 1}
    # the index vector passes; a float32 grid gathered or exchanged does not
    assert len(gathered) == 2 and "all-gather-start.6" in gathered[0]
    assert "all-to-all.7" in gathered[1]
    assert system.collectives("") == ({}, [])


def test_the_adaptor_refuses_a_program_without_the_compiled_pcg(monkeypatch):
    from sparse_tpu import linalg  # noqa: F401 - registers the counter
    from sparse_tpu.telemetry import _metrics

    system = manifest.load_module("systems", "library_gmg_pcg_mesh")
    monkeypatch.setattr(_metrics, "family", lambda name: [])
    with pytest.raises(RuntimeError, match="cg.precond.traces"):
        system.System({"chips": 4}, {}, None)


ADAPTOR = """
import numpy as np
import run as harness
from sparse_tpu import telemetry
from sparse_tpu.models import gmg_grid

if {on_one_device}:
    # a lay-out that leaves everything where the build put it
    gmg_grid.shard_hierarchy_grid = lambda h, mesh, **k: (
        h, __import__("jax").sharding.NamedSharding(
            mesh, __import__("jax").sharding.PartitionSpec()))
gen = manifest.load_module("operators", "gmg_poisson")
system = manifest.load_module("systems", "library_gmg_pcg_mesh")
d = gen.make({{"grid": 128, "levels": 3, "iterations": 10, "gridop": "linear"}}, 4)
ctx = harness.Context(True)
telemetry.reset()
ctx.events_on()
sut = system.System({{"chips": 4}}, d, ctx)
sut.warm()
n0 = len(telemetry.events("span"))
out = sut.call()
window = telemetry.events("span")[n0:]
events = {{"span": list(window)}}
sut.check_events(events)
ans = sut.answer(out)
sut.close()
print(json.dumps({{
    "shape": sut.shape, "iters": ans["iters"], "x": list(ans["x"].shape),
    "devices": len(out["x"].sharding.device_set),
    "window": [e["name"] for e in window],
    "window_fields": {{k: window[0].get(k) for k in ("devices", "halo_exchanges")}},
    "setup": [e["name"] for e in events["setup.span"]],
    "hlo": [events["program.hlo"][0]["program"],
            "/gmg.l1/" in events["program.hlo"][0]["text"]],
    "checks": [[c["name"], c["ok"]] for c in ctx.checks],
    "spans": sorted(ctx.spans)}}))
"""


def test_the_adaptor_holds_the_guarantees_and_hands_over_spans_and_text():
    got = last_json(run_code(ADAPTOR.format(on_one_device=False)))
    assert got["shape"] == {"rows": 128 * 128, "grid": 128, "levels": 3, "chips": 4}
    assert got["iters"] == 10 and got["x"] == [128 * 128] and got["devices"] == 4
    assert got["window"] == ["cg.solve"]
    assert got["window_fields"] == {"devices": 4, "halo_exchanges": 14}
    assert got["setup"].count("gmg.build_hierarchy") == 1
    assert got["hlo"] == ["jit_pcg", True]
    assert got["checks"] == [[name, True] for name in (
        "solver_path_not_device", "warm_call_not_jit_pcg_over_gmg_grid",
        "iterate_not_in_row_blocks_on_every_chip",
        "planes_not_in_row_blocks_on_every_chip",
        "program_gathers_a_grid_or_a_vector",
        "loop_permutes_not_the_declared_halo_exchanges",
        "window_solve_not_jit_pcg_over_gmg_grid", "cg_precond_traces_in_window")]
    assert {"operator_build", "mesh_layout", "first_call", "warm_call"} <= set(
        got["spans"])


def test_a_hierarchy_left_on_one_device_breaks_the_mesh_guarantees():
    got = last_json(run_code(ADAPTOR.format(on_one_device=True)))
    checks = dict(map(tuple, got["checks"]))
    assert got["iters"] == 10  # it solves all the same, and says so
    assert not checks["iterate_not_in_row_blocks_on_every_chip"]
    assert not checks["planes_not_in_row_blocks_on_every_chip"]
    # nothing in row blocks exchanges nothing and declares nothing: no count
    assert "loop_permutes_not_the_declared_halo_exchanges" not in checks
    assert checks["program_gathers_a_grid_or_a_vector"]
    assert checks["warm_call_not_jit_pcg_over_gmg_grid"]


@pytest.mark.parametrize("how", ["answer", "stale"])
def test_a_rehearsal_with_cg_broken_underneath_is_not_correct(how):
    """``tests/broken_run.py``'s two breaks of ``linalg.cg``, which this
    cell calls: every answer scaled by 1 + 1e-2, or the start returned
    unchanged; and the same rehearsal unbroken holds every check."""
    p = run_code(f"""
sys.argv = ["broken_run.py", "--workload", {CELL!r}, "--break", {how!r}]
sys.path.insert(0, {os.path.join(HERE, 'tests')!r})
import broken_run
broken_run.main()
""")
    got = last_json(p)
    assert got["exit"] == 1 and got["correct"] is False
    assert not got["checks_ok"] and "x_vs_reference" in got["failed_checks"]


def test_the_rehearsal_is_correct_but_for_the_chip():
    p = run_code(f"""
sys.argv = ["broken_run.py", "--workload", {CELL!r}]
sys.path.insert(0, {os.path.join(HERE, 'tests')!r})
import broken_run
broken_run.main()
""")
    got = last_json(p)
    # a rehearsal never says correct: it has no chip; every check holds
    assert got == {"exit": 1, "correct": False, "checks_ok": True,
                   "failed_checks": []}
