"""Self-check of the benchmark, on the CPU, at the configurations' rehearsal
sizes (python3 -m pytest benchmark/tests -q, or python3 benchmark/selfcheck.py).

Not under the repo's tests/: the tier-1 count is untouched. What it holds:
the manifest's names, files and cross-references; the trace reducer against a
recorded trace; that a run without a chip is refused and a rehearsal ends
``correct: false``; that the control (the reference in bfloat16 in the
program's place) comes out as not correct against each configuration's
limits; and that a run with the timed path broken underneath comes out as
not correct."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import manifest  # noqa: E402
import stats  # noqa: E402
import xplane  # noqa: E402

BM = manifest.benchmark()
CELLS = [w["name"] for w in BM["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module", autouse=True)
def compile_cache_of_its_own():
    """The rehearsals get a compile cache directory of their own, so that the
    repo's ``.jax_cache`` stays what the program's own runs made it."""
    d = tempfile.mkdtemp(prefix="bench-selfcheck-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    yield
    del os.environ["JAX_COMPILATION_CACHE_DIR"]
    shutil.rmtree(d, ignore_errors=True)


def run_py(script, *args, chips=1, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    return subprocess.run([sys.executable, os.path.join(HERE, script), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=timeout)


def chips_of(cell):
    return next(w["chips"] for w in BM["workloads"] if w["name"] == cell)


def last_json(out):
    return json.loads(out.strip().splitlines()[-1])


def test_manifest_names_units_and_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51 and isinstance(BM["run_seconds"], int)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in BM[k]]
        assert len(ns) == len(set(ns))
    ms = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(ms) == len(set(ms))
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BM["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(len(BM["workloads"]) // 2, 1)
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in BM["workloads"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_every_name_resolves_to_files():
    for c in BM["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for kind in ("operator", "system"):
            manifest.load_module(kind + "s", cfg[kind])
        assert all(k in cfg for k in ("sizes", "limits", "guarantees"))
    for cell in CELLS:
        res = manifest.cell(cell)
        assert res["config"]["chips"] == res["workload"]["chips"]
        manifest.load_module("loops", res["traffic"]["loop"])
        for group, key in (("end_to_end", "end_to_end"),
                           ("layer_metrics", "per_layer")):
            assert res[key], f"{cell} reports no {key} metric"
            for m in res[key]:
                manifest.metric_reader(group, m["name"])
        assert any(m["name"] == "setup_s" for m in res["end_to_end"])
        assert len(res["end_to_end"]) >= 2


def test_every_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in e2e[m["moves"]].get("workloads", CELLS), (m, cell)
    for kind in manifest.load_json("peaks.json").values():
        assert kind["hbm_bytes_per_s"] > 0 and kind["source"]


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 0.95) == 95
    assert stats.percentile([3.0, 1.0, 2.0], 0.5) == 2.0
    assert stats.percentile([5.0], 0.95) == 5.0
    assert stats.percentile(list(range(1, 241)), 0.95) == 228


def test_trace_reducer_reproduces_the_recorded_trace():
    """Two ``linalg.cg(maxiter=60)`` calls at 1024^2 on the v5e (PR 24): four
    chunk programs a call (25, 25, 9, 1 iterations), two Pallas kernels an
    iteration. Numbers read by hand from the trace's XLA Modules line."""
    r = xplane.reduce(os.path.join(HERE, "testdata",
                                   "cg_1024_two_calls.xplane.pb"))
    runs, secs = xplane.program_seconds(r, "jit_cg_dia_fused")
    assert runs == 8
    assert secs == pytest.approx(7.302402e-3, rel=1e-6)
    assert r["busy_s"] == pytest.approx(secs, rel=1e-9)  # programs never overlap
    assert r["window_s"] == pytest.approx(20.907893e-3, rel=1e-6)
    n, ksecs = xplane.op_seconds(r, "jit_cg_dia_fused", "tpu_custom_call")
    assert n == 2 * 60 * 2
    assert ksecs == pytest.approx(4.540777e-3, rel=1e-5)
    assert ksecs < secs
    b = xplane.breakdown(r)
    assert b["device_ops"][0][0].startswith("jit_cg_dia_fused/closed_call")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=0.02)


def test_op_label_and_self_times():
    name, opcode, note = xplane.op_label(
        '%closed_call.22 = (f32[8]{0}, f32[1,1]{1,0}) custom-call(f32[8]{0} '
        '%a), custom_call_target="tpu_custom_call"')
    assert (name, opcode, note) == ("closed_call.22", "custom-call",
                                    "tpu_custom_call")
    assert xplane.op_label("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), "
                           "kind=kLoop, calls=%c")[1:] == ("fusion", "kLoop")
    evs = [("while", 0.0, 100.0), ("a", 10.0, 30.0), ("b", 50.0, 40.0),
           ("c", 120.0, 5.0)]
    assert dict((n, s) for n, _, s in xplane.self_times(evs)) == {
        "while": 30.0, "a": 30.0, "b": 40.0, "c": 5.0}
    assert xplane.merged([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]


@pytest.mark.parametrize("cell", CELLS)
def test_without_a_chip_the_run_is_refused(cell):
    p = run_py("run.py", "--workload", cell, "--seed", "1", "--seconds", "1",
               "--trace", "0", chips=chips_of(cell))
    assert p.returncode == 2
    assert '"correct"' not in p.stdout and "refused" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_not_correct_with_every_check_ok(cell):
    p = run_py("tests/broken_run.py", "--workload", cell, chips=chips_of(cell))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = last_json(p.stdout)
    assert out["checks_ok"] and out["failed_checks"] == []
    assert out["correct"] is False and out["exit"] == 1  # for want of a chip
    assert '"metrics": {}' in p.stdout or "metrics" not in p.stdout


@pytest.mark.parametrize("how", ["answer", "stale"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, how):
    p = run_py("tests/broken_run.py", "--workload", cell, "--break", how,
               chips=chips_of(cell))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = last_json(p.stdout)
    assert not out["checks_ok"] and "x_vs_reference" in out["failed_checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell):
    """The reference in bfloat16, put in the program's place, fails at least
    one of the configuration's limits on every seed, and the program passes
    all of them, at the rehearsal size."""
    p = run_py("tools/read_limits.py", "--workload", cell, "--rehearse",
               "--seeds", "2147483693", "11", "12", "--seconds", "0.3",
               chips=chips_of(cell))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    limits = manifest.cell(cell)["config"]["limits"]
    rows = [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith('{"seed"')]
    assert len(rows) == 3
    for row in rows:
        assert all(row["sound"][k] <= limits[k] for k in limits), row
        assert any(row["control"][k] > limits[k] for k in limits), row
