"""The per-layer metrics that read the session's account of its period (PR
37) and the bucket program's memory gauge: the two new reducers on hand-made
records, and the six metric files through the manifest. Beside
test_span_metrics.py, and like it outside the repo's tier 1."""

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

CELLS = ["heat_served_closed", "fem_heat_served_closed"]
PERIOD = {  # metric -> (field of batch.dispatch, reducer, moves)
    "session_period_ms": ("period_ms", "event_field_median", "solves_per_s"),
    "session_caller_ms": ("caller_ms", "event_field_median", "solves_per_s"),
    "session_submit_ms": ("submit_ms", "event_field_median", "solves_per_s"),
    "session_unspanned_ms": ("unspanned_ms", "event_field_median",
                             "solves_per_s"),
    "session_period_max_ms": ("period_ms", "event_field_max",
                              "ticket_p95_ms"),
}


def dispatch(seq, period=None, **parts):
    e = {"kind": "batch.dispatch", "seq": seq, "solver": "cg", "batch": 64,
         "bucket": 64, "solve_ms": 900.0}
    if period is not None:
        e.update(period_ms=period, submits=64, **parts)
    return e


def canned():
    """A window of five dispatches: the session's first (no interval), three
    clean periods and one that a stall held, in the callers."""
    return {"events": {"batch.dispatch": [
        dispatch(1),
        dispatch(2, 630.0, caller_ms=365.0, submit_ms=1.0, spanned_ms=262.0,
                 unspanned_ms=2.0),
        dispatch(3, 628.0, caller_ms=362.0, submit_ms=0.8, spanned_ms=263.6,
                 unspanned_ms=1.6),
        dispatch(4, 3360.0, caller_ms=3094.0, submit_ms=1.2, spanned_ms=262.4,
                 unspanned_ms=2.4),
        dispatch(5, 632.0, caller_ms=366.0, submit_ms=0.9, spanned_ms=263.1,
                 unspanned_ms=2.0),
    ]}}


@pytest.mark.parametrize("name", sorted(PERIOD))
def test_period_metric_resolves_to_its_file_and_cells(name):
    field, reducer, moves = PERIOD[name]
    entry = next(m for m in manifest.benchmark()["per_layer"]
                 if m["name"] == name)
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"], entry["workloads"]) == (
        "ms", "lower", "program_span", "session", moves, CELLS)
    spec = manifest.load_json("layer_metrics", name + ".json")
    assert spec["reducer"] == reducer
    assert spec["params"] == {"kind": "batch.dispatch", "field": field}
    for cell in CELLS:
        assert name in [m["name"] for m in manifest.cell(cell)["per_layer"]]


def test_period_metrics_read_a_canned_window():
    run, got = canned(), {}
    for name in PERIOD:
        read, params = manifest.metric_reader("layer_metrics", name)
        got[name] = read(run, params)
    # medians over the four dispatches that carry the account, the first
    # left out; the stall moves the largest and not the medians
    assert got["session_period_ms"] == pytest.approx(631.0)
    assert got["session_caller_ms"] == pytest.approx(365.5)
    assert got["session_submit_ms"] == pytest.approx(0.95)
    assert got["session_unspanned_ms"] == pytest.approx(2.0)
    assert got["session_period_max_ms"] == 3360.0


def test_a_program_without_the_account_reads_nothing():
    """The parent's events: every ``batch.dispatch`` there, none with a
    period. Nothing to read, and nothing raised."""
    run = {"events": {"batch.dispatch": [dispatch(s) for s in (1, 2, 3)]}}
    for name in PERIOD:
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(run, params) is None
        assert read({"events": {}}, params) is None


def test_event_field_max_is_the_medians_twin():
    read = manifest.load_module("reducers", "event_field_max").read
    run = {"events": {"k": [{"f": 2.0}, {"f": 7.5}, {"g": 99.0}, {"f": 3.0}]}}
    assert read(run, {"kind": "k", "field": "f"}) == 7.5
    assert read(run, {"kind": "k", "field": "h"}) is None
    assert read(run, {"kind": "other", "field": "f"}) is None


def test_bucket_program_hbm_reads_the_largest_programs_gauge():
    from sparse_tpu import telemetry

    entry = next(m for m in manifest.benchmark()["per_layer"]
                 if m["name"] == "bucket_program_hbm_gb")
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"], entry["workloads"]) == (
        "GB", "lower", "program_counter", "kernels", "solves_per_s", CELLS)
    read, params = manifest.metric_reader("layer_metrics",
                                          "bucket_program_hbm_gb")
    gauge = telemetry.metrics.gauge
    name = "plan_cache.program_peak_bytes"
    telemetry.metrics.remove(name)
    try:
        assert read({}, params) is None  # no analysis: nothing, not a zero
        gauge(name, program="batch.gmres.B1.<f8")  # registered, never set
        assert read({}, params) is None
        gauge(name, program="batch.cg.B64.<f4").set(11.06e9)
        gauge(name, program="batch.cg.B8.<f4").set(1.4e9)
        assert read({}, params) == pytest.approx(11.06)
    finally:
        telemetry.metrics.remove(name)
