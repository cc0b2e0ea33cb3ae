"""The cell ``spd_general_1chip`` on the CPU: the generator against the
numbers the configuration states, the plain reference against a dense
solve, the bytes function, and the cell's metric files on hand-made runs.
(The rehearsal, the control and the broken timed path run for every cell of
BENCHMARK.json in test_benchmark.py.)"""

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

CELL = "spd_general_1chip"
gen = manifest.load_module("operators", "spd_unstructured")


def dense(d):
    n = d["rows"]
    A = np.zeros((n, n))
    rows = gen.coo_rows(d)
    A[rows, d["indices"]] = d["data"]
    return A


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_generator_gives_the_stated_class(seed):
    s = 24
    d = gen.make({"side": s, "iterations": 50}, seed)
    n = s * s
    assert d["rows"] == n and d["nnz"] == 7 * s * s - 8 * s + 2
    assert d["indptr"][-1] == d["nnz"] == d["indices"].shape[0]
    lengths = np.diff(d["indptr"])
    assert 3 <= lengths.min() and lengths.max() <= 9
    A = dense(d)
    assert np.array_equal(A, A.T)
    assert np.linalg.eigvalsh(A)[0] > 0  # positive definite
    # sorted columns in a row, no entry stored twice
    rows = gen.coo_rows(d).astype(np.int64)
    assert np.all(np.diff(rows * n + d["indices"]) > 0)
    # not banded: the diagonals are as many as a random order gives
    assert len(np.unique(d["indices"] - rows)) > n // 2
    # the same seed gives the same data, another seed another matrix
    again = gen.make({"side": s, "iterations": 50}, seed)
    other = gen.make({"side": s, "iterations": 50}, seed + 1)
    assert all(np.array_equal(d[k], again[k]) for k in ("data", "indices", "b"))
    assert not np.array_equal(d["indices"], other["indices"])


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
        "general_cg_gather_pct"}
    assert all(m["workloads"] == [CELL] for name, m in per_layer.items()
               if name.startswith(("general_", "ell_", "layout_detect")))
    empty = {"trace": None, "events": {}, "spans": {},
             "result": {"completions": []}}
    for name in ("general_cg_roofline", "general_cg_gather_pct",
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
    # a hand-made reduced trace: ten whole runs of the program, 50 iterations
    # a call, the product's nine gather fusions 80 % of its device time
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


def test_the_adaptor_refuses_a_program_without_the_compiled_general_cg(monkeypatch):
    from sparse_tpu import linalg  # noqa: F401 - registers the counter
    from sparse_tpu.telemetry import _metrics

    system = manifest.load_module("systems", "library_csr_cg")
    monkeypatch.setattr(_metrics, "family", lambda name: [])
    with pytest.raises(RuntimeError, match="cg.general.traces"):
        system.System({}, {}, None)
