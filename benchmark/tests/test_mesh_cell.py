"""The four-chip cell pde_cg_4chip (PR 27), on the CPU with four host
devices: the adaptor's refusal of any layout but 'dia' over 'halo', the timed
path broken underneath ``dist_cg``, the collective-share reducer on a
hand-made reduced trace, the loop that traces a few calls, and the cell's
metrics through the manifest.
Beside test_benchmark.py, whose parametrised cases run the cell's rehearsal,
its refusal without a chip and its bfloat16 control; like it outside the
repo's tier 1."""

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

CELL = "pde_cg_4chip"
PROGRAM = "jit_dist_cg_dia"


def run_code(code: str, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


PRELUDE = f"""
import sys
sys.path[:0] = [{HERE!r}, {ROOT!r}, {os.path.join(HERE, 'tests')!r}]
import sparse_tpu.parallel as par
from sparse_tpu.parallel import dist
"""


def test_the_adaptor_refuses_a_layout_that_is_not_dia():
    """As on a commit whose shard_csr has no banded layout: the run ends at
    the adaptor's check, before the first solve and without a result line."""
    p = run_code(PRELUDE + f"""
real = dist.shard_csr
par.shard_csr = lambda A, **k: real(A, layout="ell", **k)
import run
sys.exit(run.main(["--workload", {CELL!r}, "--seed", "5", "--seconds", "1",
                   "--trace", "0", "--rehearse"]))
""")
    assert p.returncode != 0  # an uncaught error: fails cleanly, and soon
    assert "guarantees layout 'dia' over mode 'halo'" in p.stderr
    assert "shard_csr gave 'ell' over 'halo'" in p.stderr
    assert '"correct"' not in p.stdout and "first_call" not in p.stdout


@pytest.mark.parametrize("factor", [1.0 + 1e-2, 0.0], ids=["answer", "stale"])
def test_broken_dist_cg_is_not_correct(factor):
    """broken_run.py breaks ``linalg.cg`` and the session's tickets; this
    cell's timed path is ``dist_cg``, broken here in the same two ways."""
    p = run_code(PRELUDE + f"""
real = dist.dist_cg
def broken(*a, **k):
    x, it, conv = real(*a, **k)
    return x * {factor!r}, it, conv
par.dist_cg = dist.dist_cg = broken
import broken_run
sys.argv = ["broken_run.py", "--workload", {CELL!r}]
sys.exit(broken_run.main())
""")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert not out["checks_ok"] and "x_vs_reference" in out["failed_checks"]


def reduced_trace():
    """One device, two whole runs of the program (0.2 s) and another program;
    ops keyed as xplane.reduce keys them: (program, result, opcode, note)."""
    ops = {
        (PROGRAM, "fusion.1", "fusion", "kLoop"): [600, 0.120],
        (PROGRAM, "collective-permute-start", "collective-permute-start", ""): [600, 0.010],
        (PROGRAM, "collective-permute-done.1", "collective-permute-done", ""): [600, 0.006],
        (PROGRAM, "all-reduce.7", "all-reduce", ""): [1200, 0.024],
        (PROGRAM, "while.19", "while", ""): [2, 0.002],
        ("jit_other", "all-reduce.1", "all-reduce", ""): [5, 0.5],
    }
    dev = {"programs": {PROGRAM: [2, 0.2], "jit_other": [5, 0.7]}, "ops": ops}
    return {"devices": {0: dev, 1: {"programs": {}, "ops": {}}}}


def test_collective_share_counts_the_programs_own_collectives():
    read, params = manifest.metric_reader("layer_metrics", "mesh_collective_pct")
    assert params["program"] == PROGRAM
    run = {"trace": reduced_trace()}
    assert read(run, params) == pytest.approx(100.0 * 0.040 / 0.2)
    assert read({"trace": None}, params) is None
    gone = {"devices": {0: {"programs": {"jit_other": [5, 0.7]}, "ops": {}}}}
    assert read({"trace": gone}, params) is None  # as on the parent commit


def test_iteration_time_of_the_mesh_program():
    """Device time of the program's whole runs over the iterations they
    made; nothing where the program or its events are missing."""
    read, params = manifest.metric_reader("layer_metrics", "dist_cg_iter_us")
    assert params["program"] == PROGRAM
    run = {"trace": reduced_trace(),
           "events": {"comm.cg": [{"iters": 300}, {"iters": 300}]}}
    assert read(run, params) == pytest.approx(1e6 * 0.2 / 600)
    assert read({"trace": None, "events": run["events"]}, params) is None
    assert read({"trace": reduced_trace(), "events": {}}, params) is None
    gone = {"devices": {0: {"programs": {"jit_other": [5, 0.7]}, "ops": {}}}}
    assert read({"trace": gone, "events": run["events"]}, params) is None


def test_operator_build_reads_the_adaptors_span():
    read, params = manifest.metric_reader("layer_metrics", "operator_build_s")
    assert read({"spans": {"operator_build": 2.5, "shard_build": 3.0}},
                params) == 2.5
    assert read({"spans": {}}, params) is None


class FakeContext:
    """The harness's Context as a loop sees it, on a clock of its own."""

    def __init__(self, warm_call):
        self.now = 100.0
        self.spans = {"warm_call": warm_call}
        self.ticks, self.log = [], []
        self.trace_state = "armed"

    def clock(self):
        return self.now

    def open_window(self, t):
        return t

    def tick(self, elapsed, seconds, traced):
        self.ticks.append((elapsed, seconds, traced))
        if self.trace_state == "armed" and elapsed >= seconds - traced:
            self.trace_state = "on"
            self.now += 0.25  # the profiler's start
        elif self.trace_state == "on" and elapsed >= seconds:
            self.trace_state = "done"  # as run.py: stopped inside the window
            self.log.append("stopped by tick")

    def annotate(self, name):
        import contextlib

        return contextlib.nullcontext()

    def stop_trace(self):
        self.log.append("stop_trace")

    def say(self, msg):
        self.log.append(msg)


def test_the_brief_trace_loop_traces_a_few_calls_and_stops_before_answers():
    """The traced part is the traffic file's calls and one to spare at the
    warm call's length, plus the two edges xplane.reduce leaves out of the
    per-program sums; the profiler's start lengthens the window by what it
    took and is in no call's latency; the profiler is stopped before the
    first answer is converted; first, last and a seeded answer are kept."""
    import xplane

    loop = manifest.load_module("loops", "caller_brief_trace")
    traffic = manifest.load_json("traffic", "back_to_back_brief_trace.json")
    assert traffic["loop"] == "caller_brief_trace"
    ctx = FakeContext(warm_call=0.03125)  # 1/32: sums exactly

    class Sut:
        def call(self):
            ctx.now += 0.03125
            return {"x": None, "iters": 300}

        def answer(self, out):
            ctx.log.append("answer")
            return {"x": None, "iters": out["iters"]}

    res = loop.run(Sut(), traffic, seed=5, seconds=1.0, ctx=ctx)
    traced = {t for _, _, t in ctx.ticks}
    edges = 2 * xplane.EDGE_NS * 1e-9
    assert len(traced) == 1 and traced.pop() == pytest.approx(
        (traffic["trace_calls"] + 1) * 0.03125 + edges)
    assert ctx.log[0] == "stop_trace" and ctx.log.count("answer") == 3
    assert "stopped by tick" not in ctx.log  # traced to the window's end
    assert max(e for e, _, _ in ctx.ticks) < 1.0  # the start is not elapsed
    assert res["attempted"] == len(res["completions"]) == 32
    assert [a["index"] for a in res["answers"]][0::2] == [0, 31]
    assert res["failed"] == 0 and res["window_s"] == pytest.approx(1.25)
    assert {c["t_done"] - c["t_submit"] for c in res["completions"]} == {0.03125}
    ctx = long = FakeContext(warm_call=1.0)
    loop.run(Sut(), traffic, seed=5, seconds=1.0, ctx=long)
    assert {t for _, _, t in long.ticks} == {0.5}  # at most half the window


def test_dispatch_metric_reads_the_solve_spans_field():
    read, params = manifest.metric_reader("layer_metrics", "dist_cg_dispatch_ms")
    spans = [{"kind": "span", "name": "dist.cg.solve", "dur_s": 0.1,
              "dispatch_s": d, "wait_s": 0.09, "iters": 300}
             for d in (0.002, 0.004, 0.003)]
    spans.append({"kind": "span", "name": "cg.solve", "dur_s": 1.0,
                  "dispatch_s": 9.0})
    assert read({"events": {"span": spans}}, params) == pytest.approx(3.0)
    assert read({"events": {}}, params) is None


def test_the_mesh_metrics_are_the_cells_alone():
    res = manifest.cell(CELL)
    names = {m["name"] for m in res["per_layer"]}
    assert names == {"iters_per_s", "dist_build_s", "dist_cg_dispatch_ms",
                     "dist_cg_iter_us", "mesh_collective_pct",
                     "operator_build_s"}
    assert {m["name"] for m in res["end_to_end"]} == {"setup_s", "solve_s"}
    assert manifest.metric_reader("layer_metrics", "dist_build_s")[1] == {
        "name": "dist.shard_csr"}
    for other in ("pde_cg_1chip", "heat_served_closed"):
        assert not names & {m["name"] for m in
                            manifest.cell(other)["per_layer"]} - {"iters_per_s"}
