"""The cell ``spd_general_1chip`` on the CPU: the generator against the
numbers the configuration states, the plain reference against a dense
solve, the bytes function, and the cell's metric files on hand-made runs.
(The rehearsal, the control and the broken timed path run for every cell of
BENCHMARK.json in test_benchmark.py.)"""

from __future__ import annotations

import hashlib
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402

CELL = "spd_general_1chip"
gen = manifest.load_module("operators", "spd_unstructured")


def dense(d):
    n = d["rows"]
    A = np.zeros((n, n))
    rows = gen.coo_rows(d)
    A[rows, d["indices"]] = d["data"]
    return A


def digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


# what the generator of PR 31 to PR 33, which drew everything from the seed,
# gave at side 48 (the rehearse size), taken from it before it was edited
BEFORE = {
    3200000103: {"indptr": "18c4e528faa78e61", "indices": "29a5881b91b51255",
                 "data": "29f7a7e336653532", "b": "e850ea8a671a75a0"},
    2147483659: {"indptr": "0aeaf659a3ec83ef", "indices": "1ce2045c96d6d8e2",
                 "data": "3d0153057697577f", "b": "ceda1609c30ec13e"},
    7: {"indptr": "65c6ff801ea8b555", "indices": "1d03dbf77f486503",
        "data": "d14d37356a40f03f", "b": "a99f27620a46a340"},
}


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_generator_gives_the_stated_class(seed):
    s = 24
    d = gen.make({"side": s, "iterations": 50, "pattern_seed": 11}, seed)
    n = s * s
    assert d["rows"] == n and d["nnz"] == 7 * s * s - 8 * s + 2
    assert d["indptr"][-1] == d["nnz"] == d["indices"].shape[0]
    lengths = np.diff(d["indptr"])
    assert 3 <= lengths.min() and lengths.max() <= 9
    A = dense(d)
    assert np.array_equal(A, A.T)
    assert np.linalg.eigvalsh(A)[0] > 0  # positive definite
    # diagonally dominant, strictly on the ring's rows: a weighted Laplacian
    # plus the eliminated ring
    off = np.abs(A).sum(axis=1) - np.abs(np.diag(A))
    assert np.all(np.diag(A) >= off * (1 - 1e-6)) and np.any(np.diag(A) > off * 1.01)
    # sorted columns in a row, no entry stored twice
    rows = gen.coo_rows(d).astype(np.int64)
    assert np.all(np.diff(rows * n + d["indices"]) > 0)
    # not banded: the diagonals are as many as a random order gives
    assert len(np.unique(d["indices"] - rows)) > n // 2
    # the same seed gives the same data
    again = gen.make({"side": s, "iterations": 50, "pattern_seed": 11}, seed)
    assert all(np.array_equal(d[k], again[k])
               for k in ("indptr", "indices", "data", "b"))


def test_the_seed_draws_the_values_and_the_pattern_seed_the_pattern():
    sizes = {"side": 24, "iterations": 50, "pattern_seed": 11}
    d, other = gen.make(sizes, 1), gen.make(sizes, 2)
    # another seed: the same pattern, other values and another b
    assert np.array_equal(d["indptr"], other["indptr"])
    assert np.array_equal(d["indices"], other["indices"])
    assert not np.array_equal(d["data"], other["data"])
    assert not np.array_equal(d["b"], other["b"])
    # another pattern_seed: another pattern, and with the same seed the same b
    moved = gen.make({**sizes, "pattern_seed": 12}, 1)
    assert not np.array_equal(d["indices"], moved["indices"])
    assert np.array_equal(d["b"], moved["b"])
    # ... and the same edge weights, on other edges
    assert np.array_equal(np.sort(d["data"][d["data"] < 0]),
                          np.sort(moved["data"][moved["data"] < 0]))


def test_the_configurations_pattern_is_the_one_the_generator_gave_before():
    """P's pattern at the rehearse size, entry for entry, whatever the seed;
    with ``pattern_seed`` equal to the seed every array, bit for bit."""
    cfg = manifest.cell(CELL)["config"]
    small = manifest.cell(CELL, rehearse=True)["config"]["sizes"]
    P = cfg["sizes"]["pattern_seed"]
    assert small["pattern_seed"] == P and small["side"] == 48
    assert cfg["rehearse"]["sizes"]["pattern_seed"] == P
    other, same = gen.make(small, 5), gen.make(small, P)
    for d in (other, same):
        assert digest(d["indptr"]) == BEFORE[P]["indptr"]
        assert digest(d["indices"]) == BEFORE[P]["indices"]
    assert digest(same["data"]) == BEFORE[P]["data"]
    assert digest(same["b"]) == BEFORE[P]["b"]
    assert digest(other["data"]) != BEFORE[P]["data"]


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_pattern_seed_equal_to_the_seed_is_the_generator_before(seed):
    d = gen.make({"side": 48, "iterations": 50, "pattern_seed": seed}, seed)
    assert {k: digest(d[k]) for k in BEFORE[seed]} == BEFORE[seed]
    assert (d["indptr"].dtype, d["indices"].dtype, d["data"].dtype,
            d["b"].dtype) == (np.int32, np.int32, np.float32, np.float32)
    # tests/utils/spd.py gives no pattern_seed yet and gets the same matrix
    # (PERF.md section 7: it should pass pattern_seed=seed, and a missing
    # key become an error)
    bare = gen.make({"side": 48, "iterations": 50}, seed)
    assert {k: digest(bare[k]) for k in BEFORE[seed]} == BEFORE[seed]


def test_every_configuration_of_this_operator_states_its_pattern():
    cfg_dir = os.path.join(HERE, "configs")
    mine = []
    for name in sorted(os.listdir(cfg_dir)):
        cfg = manifest.load_json("configs", name)
        if cfg.get("operator") == "spd_unstructured":
            mine.append(name)
            assert isinstance(cfg["sizes"].get("pattern_seed"), int), name
            small = cfg.get("rehearse", {}).get("sizes")
            assert small is None or isinstance(small.get("pattern_seed"), int), name
    assert "spd-thermal-1m2.json" in mine


def test_the_size_the_configuration_states():
    cfg = manifest.cell(CELL)["config"]
    s = cfg["sizes"]["side"]
    assert s * s == 1_227_664 and 7 * s * s - 8 * s + 2 == 8_584_786
    assert sorted(cfg["reduced"]) == ["dtype", "iterations"]
    assert cfg["sizes"]["iterations"] % 25 == 0 and cfg["sizes"]["iterations"] <= 300


def test_reference_converges_to_the_dense_solution_and_residuals_are_true():
    d = gen.make({"side": 12, "iterations": 50}, 5)
    A = dense(d)
    x = gen.reference_cg(d, 60)  # past convergence a textbook CG divides 0 by 0
    x_true = np.linalg.solve(A, d["b"].astype(np.float64))
    assert np.linalg.norm(x - x_true) / np.linalg.norm(x_true) < 1e-3
    assert np.allclose(gen.apply_f64(d, x), A @ x.astype(np.float64))
    assert gen.true_relres(d, x_true) < 1e-12
    assert gen.true_relres(d, np.zeros(d["rows"])) == pytest.approx(1.0)


def test_bytes_function_counts_entries_once_and_seven_vector_passes():
    b = manifest.load_module("bytes", "cg_ell").bytes_per_iteration
    assert b(1_227_664, 8_584_786) == 8 * 8_584_786 + 28 * 1_227_664 == 103_052_880
    assert b(10, 0) == 280


def test_the_cells_metric_files_resolve_and_read_hand_made_runs():
    per_layer = {m["name"]: m for m in manifest.cell(CELL)["per_layer"]}
    assert set(per_layer) == {
        "iters_per_s", "operator_build_s", "general_cg_dispatch_ms",
        "ell_build_s", "layout_detect_s", "general_cg_roofline",
        "general_cg_gather_pct", "well_steps", "reorder_s",
        "general_cg_kernel_pct"}
    assert all(m["workloads"] == [CELL] for name, m in per_layer.items()
               if name.startswith(("general_", "ell_", "layout_detect",
                                   "well_", "reorder_")))
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    for name in ("general_cg_roofline", "general_cg_gather_pct",
                 "general_cg_kernel_pct", "well_steps",
                 "general_cg_dispatch_ms", "operator_build_s"):
        read, params = manifest.metric_reader("layer_metrics", name)
        assert read(empty, params) is None
    # the general path's dispatch: the `cg.solve` span's field, as the fused
    # path's `cg_dispatch_ms_per_solve` reads it in its own cell
    read, params = manifest.metric_reader("layer_metrics", "general_cg_dispatch_ms")
    spans = [{"kind": "span", "name": "cg.solve", "path": "device",
              "dur_s": 4.7, "dispatch_s": d} for d in (0.0008, 0.0009, 0.0010)]
    assert read({"events": {"span": spans}}, params) == pytest.approx(0.9)
    assert per_layer["general_cg_dispatch_ms"]["moves"] == "solve_s"
    # the layout's step count: a field of the set-up span `layout.reorder`,
    # which the adaptor keeps aside and files under a kind of its own
    read, params = manifest.metric_reader("layer_metrics", "well_steps")
    setup = [{"kind": "span", "name": "layout.detect", "dur_s": 0.5},
             {"kind": "span", "name": "layout.reorder", "dur_s": 2.0,
              "offered": True, "steps": 14932, "window_chunks_max": 19},
             {"kind": "span", "name": "cg.solve", "dur_s": 0.1}]
    assert read({"events": {"setup.span": setup, "span": setup[2:]}}, params) == 14932
    assert read({"events": {"setup.span": setup[:1]}}, params) is None
    assert read({"events": {"span": setup}}, params) is None
    assert per_layer["well_steps"]["moves"] == "solve_s"
    assert per_layer["reorder_s"]["moves"] == "setup_s"
    assert manifest.load_json("layer_metrics", "reorder_s.json")["params"] == {
        "name": "layout.reorder"}
    # a hand-made reduced trace: ten whole runs of the program, 50 iterations
    # a call, the product's nine gather fusions 80 % of its device time, or
    # (below) the kernel's custom call and the two permutations
    ops = {("jit_cg_general", f"fusion.{k}", "fusion", "kCustom"): [500, 0.4]
           for k in range(9)}
    ops[("jit_cg_general", "multiply_reduce_fusion.7", "fusion", "kLoop")] = [500, 0.9]
    dev = {"programs": {"jit_cg_general": [10, 4.5]}, "ops": ops}
    run = {"trace": {"devices": {0: dev}},
           "events": {"solver.solve": [{"iters": 50}] * 11},
           "shape": {"rows": 1_227_664, "nnz": 8_584_786},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    read, params = manifest.metric_reader("layer_metrics", "general_cg_roofline")
    assert read(run, params) == pytest.approx(
        100 * 103_052_880 * 500 / 4.5 / 819e9)
    read, params = manifest.metric_reader("layer_metrics", "general_cg_gather_pct")
    assert read(run, params) == pytest.approx(100 * 9 * 0.4 / 4.5)
    read_k, params_k = manifest.metric_reader("layer_metrics", "general_cg_kernel_pct")
    assert read_k(run, params_k) == 0.0  # no custom call in that program
    dev["ops"] = {
        ("jit_cg_general", "well_spmv.6", "custom-call", "tpu_custom_call"): [500, 3.6],
        ("jit_cg_general", "fusion.1", "fusion", "kCustom"): [10, 0.5],
        ("jit_cg_general", "fusion", "fusion", "kCustom"): [10, 0.3],
        ("jit_cg_general", "multiply_reduce_fusion.7", "fusion", "kLoop"): [500, 0.1]}
    assert read_k(run, params_k) == pytest.approx(100 * 3.6 / 4.5)
    assert read(run, params) == pytest.approx(100 * 0.8 / 4.5)


def test_the_adaptor_refuses_a_program_without_the_compiled_general_cg(monkeypatch):
    from sparse_tpu import linalg  # noqa: F401 - registers the counter
    from sparse_tpu.telemetry import _metrics

    system = manifest.load_module("systems", "library_csr_cg")
    monkeypatch.setattr(_metrics, "family", lambda name: [])
    with pytest.raises(RuntimeError, match="cg.general.traces"):
        system.System({}, {}, None)


def test_the_adaptor_files_the_set_ups_spans_under_a_kind_of_their_own():
    """`well_steps` reads a field of a set-up span: the adaptor keeps the
    spans the recorder holds after its warm-up and hands them to a traced
    run's events as `setup.span`, beside the window's own, untouched."""
    import run as harness
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings

    system = manifest.load_module("systems", "library_csr_cg")
    d = gen.make({"side": 12, "iterations": 5, "pattern_seed": 3}, 4)
    was = settings.telemetry
    ctx = harness.Context(True)
    try:
        telemetry.reset()
        ctx.events_on()
        sut = system.System({}, d, ctx)
        sut.warm()
        window = [{"kind": "span", "name": "cg.solve", "path": "device"}]
        events = {"span": list(window)}
        sut.check_events(events)
        sut.close()
    finally:
        settings.telemetry = was
        telemetry.configure(None)
        telemetry.reset()
        ctx.close()
    assert events["span"] == window
    names = [e["name"] for e in events["setup.span"]]
    assert "layout.detect" in names and names.count("cg.solve") == 2
    assert all(c["ok"] for c in ctx.checks), ctx.checks
