"""The per-layer metrics that read the program's spans (PR 25): each reducer
on a hand-made run record, and the six metric files through the manifest.
Beside test_benchmark.py, and like it outside the repo's tier 1."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402

SESSION = ("pack", "upload", "plan", "call", "device_wait", "readback",
           "scatter")


def span(name, dur_s, **fields):
    return {"kind": "span", "name": name, "dur_s": dur_s, **fields}


def bucket(seq, **durs):
    return [span(f"session.{n}", durs.get(n, 0.0), seq=seq) for n in SESSION]


def run_of(spans):
    return {"events": {"span": spans} if spans else {}}


def reader(name):
    return manifest.load_module("reducers", name).read


def test_span_median_takes_its_name_and_field():
    read = reader("span_median")
    run = run_of([span("session.pack", 2.0), span("session.pack", 4.0),
                  span("session.pack", 3.0), span("session.upload", 9.0),
                  span("cg.solve", 0.4, dispatch_s=0.004),
                  span("cg.solve", 0.4, dispatch_s=0.006),
                  span("cg.solve", 0.4)])  # no such field: left out
    assert read(run, {"name": "session.pack", "scale": 1000.0}) == 3000.0
    assert read(run, {"name": "session.upload"}) == 9.0
    assert read(run, {"name": "cg.solve", "field": "dispatch_s",
                      "scale": 1000.0}) == pytest.approx(5.0)


def test_span_median_without_such_a_span_reads_nothing():
    read = reader("span_median")
    assert read(run_of([]), {"name": "session.pack"}) is None
    assert read(run_of([span("session.upload", 1.0)]),
                {"name": "session.pack"}) is None
    assert read(run_of([span("cg.solve", 1.0)]),
                {"name": "cg.solve", "field": "dispatch_s"}) is None


def test_span_host_share_over_whole_dispatches():
    read = reader("span_host_share")
    params = manifest.load_json("layer_metrics", "session_host_share_pct.json")["params"]
    # the host works 3 s a bucket and waits 1 s for the device: 75 %
    spans = bucket(7, pack=2.0, upload=0.5, readback=0.5, device_wait=1.0)
    spans += bucket(8, pack=2.0, upload=0.5, readback=0.5, device_wait=1.0)
    assert read(run_of(spans), params) == pytest.approx(75.0)
    # a dispatch cut by the window's edge (its launch lies before it) is
    # left out, whatever it would add
    spans += [span("session.device_wait", 50.0, seq=6),
              span("session.readback", 0.5, seq=6),
              span("session.scatter", 0.0, seq=6)]
    assert read(run_of(spans), params) == pytest.approx(75.0)
    # the host never waits: it sets the pace
    assert read(run_of(bucket(1, pack=3.0)), params) == pytest.approx(100.0)


def test_span_host_share_without_spans_reads_nothing():
    read = reader("span_host_share")
    params = manifest.load_json("layer_metrics", "session_host_share_pct.json")["params"]
    assert read(run_of([]), params) is None
    assert read(run_of([span("session.pack", 1.0, seq=1)]), params) is None


def test_span_total_reads_the_programs_aggregate():
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings

    read = reader("span_total")
    was = settings.telemetry
    settings.telemetry = True
    try:
        telemetry.reset()
        assert read({}, {"name": "layout.dia_build"}) is None
        telemetry.add_span("layout.dia_build", 20.0)
        telemetry.add_span("layout.dia_build", 0.5)
        telemetry.add_span("cg.solve", 4.0)
        assert read({}, {"name": "layout.dia_build"}) == pytest.approx(20.5)
    finally:
        telemetry.reset()
        settings.telemetry = was


NEW = {
    "session_pack_ms": ("heat_served_closed", "solves_per_s"),
    "session_upload_ms": ("heat_served_closed", "solves_per_s"),
    "session_readback_ms": ("heat_served_closed", "ticket_p95_ms"),
    "session_host_share_pct": ("heat_served_closed", "solves_per_s"),
    "dia_build_s": ("pde_cg_1chip", "setup_s"),
    "cg_dispatch_ms_per_solve": ("pde_cg_1chip", "solve_s"),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_resolves_and_reads_nothing_from_an_empty_run(name):
    cell, moves = NEW[name]
    entry = next(m for m in manifest.benchmark()["per_layer"]
                 if m["name"] == name)
    assert entry["source"] == "program_span"
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert name in [m["name"] for m in manifest.cell(cell)["per_layer"]]
    read, params = manifest.metric_reader("layer_metrics", name)
    if name != "dia_build_s":  # that one reads the live aggregate, above
        assert read(run_of([]), params) is None


def test_the_session_metrics_read_one_hand_made_bucket():
    run = run_of(bucket(3, pack=2.5, upload=0.3, readback=0.2,
                        device_wait=0.1, call=0.05))
    got = {}
    for name in ("session_pack_ms", "session_upload_ms",
                 "session_readback_ms", "session_host_share_pct"):
        read, params = manifest.metric_reader("layer_metrics", name)
        got[name] = read(run, params)
    assert got["session_pack_ms"] == pytest.approx(2500.0)
    assert got["session_upload_ms"] == pytest.approx(300.0)
    assert got["session_readback_ms"] == pytest.approx(200.0)
    assert got["session_host_share_pct"] == pytest.approx(
        100.0 * 3.05 / 3.15)
