"""Example scripts as system tests (SURVEY §4: the reference's test runner
executes ``examples/`` alongside the integration suite).

Each example runs as a subprocess on the virtual CPU mesh with tiny sizes —
the exact command a user runs, not an import of its internals. The parent
conftest pins JAX_PLATFORMS=cpu, which the subprocesses inherit.
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420, devices=8):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, f"{script} rc={proc.returncode}\n{proc.stderr[-2000:]}"
    return proc.stdout


def test_pde_example():
    out = _run("pde.py", "-nx", "32", "-ny", "32", "-max_iter", "60")
    m = re.search(r"Iterations: (\d+)\s+residual: ([0-9.e+-]+)", out)
    assert m, out
    assert float(m.group(2)) < 1e-2


def test_gmg_example():
    # default dispatch = the structured-grid pipeline (models/gmg_grid.py)
    out = _run("gmg.py", "-n", "16", "-levels", "2", "-maxiter", "40")
    m = re.search(r"Iterations: (\d+)\s+residual: ([0-9.e+-]+)", out)
    assert m, out
    assert float(m.group(2)) < 1e-5


def test_hpcg_example():
    # HPCG's problem through models/hpcg_grid.py: b = A 1, so the answer is 1
    out = _run("hpcg.py", "-nx", "16", "-ny", "16", "-nz", "16", "-levels", "3",
               "-maxiter", "20", "-sets", "1")
    m = re.search(r"Iterations: (\d+)\s+residual: ([0-9.e+-]+)", out)
    assert m and int(m.group(1)) == 20, out
    assert float(m.group(2)) < 1e-10
    assert float(re.search(r"Error: ([0-9.e+-]+)", out).group(1)) < 1e-9
    assert re.search(r"GFLOP/s \(HPCG's count\): [0-9.]+", out)


def test_xgc_collision_example():
    # thousands of small same-pattern systems in one call (here 64): every
    # lane converges, in two groups of iteration counts
    out = _run("xgc_collision.py", "--precision", "f32", "-systems", "64",
               "-seed", "7", "-calls", "2")
    assert re.search(r"Systems: 64  converged: 64", out), out
    m = re.search(r"ions \((\d+), ([0-9.]+), (\d+)\)  electrons "
                  r"\((\d+), ([0-9.]+), (\d+)\)", out)
    assert m and 3 * float(m.group(2)) <= float(m.group(5)), out
    assert float(re.search(r"Largest relative residual: ([0-9.e+-]+)",
                           out).group(1)) < 2e-5
    assert float(re.search(r"Frozen lane-steps: ([0-9.]+) %", out).group(1)) > 30


def test_gmg_example_generic_path():
    # --no-grid keeps the generic sparse-matrix hierarchy (GMG class,
    # SpGEMM Galerkin products) exercised end-to-end
    out = _run("gmg.py", "-n", "16", "-levels", "2", "-maxiter", "40", "--no-grid")
    m = re.search(r"Iterations: (\d+)\s+residual: ([0-9.e+-]+)", out)
    assert m, out
    assert float(m.group(2)) < 1e-5


def test_spectral_norm_example():
    out = _run("spectral_norm.py")
    # dense vs sparse estimates printed and equal to a few digits
    nums = re.findall(r"([0-9]+\.[0-9]+)", out)
    assert len(nums) >= 2, out
    assert abs(float(nums[0]) - float(nums[1])) < 1e-2 * max(float(nums[0]), 1.0)


def test_quantum_evolution_example():
    out = _run("quantum_evolution.py", "-nodes", "8", "-t", "0.2")
    m = re.search(r"norm drift: ([0-9.e+-]+)", out)
    assert m, out
    assert float(m.group(1)) < 1e-3


def test_dot_microbenchmark_example():
    out = _run("dot_microbenchmark.py", "-n", "200", "-i", "3")
    assert re.search(r"Iterations / sec: [0-9.]+", out), out


def test_spgemm_microbenchmark_example():
    out = _run("spgemm_microbenchmark.py", "-n", "200", "-i", "2")
    assert re.search(r"Iterations / sec: [0-9.]+", out), out


def test_weak_scaling_example():
    out = _run("weak_scaling.py", "-n", "24", "-shards", "1,2", "-iters", "4")
    m = re.search(r'\{"weak_scaling":', out)
    assert m, out


def test_pyamg_adapter_example():
    pytest.importorskip("pyamg")
    _run("pyamg_sparse_tpu_test.py")


def test_gmg_dist_example():
    """Distributed GMG, generic machinery (--no-grid): Galerkin products
    via mesh SpGEMM, DistCSR V-cycle CG on the 8-device mesh."""
    out = _run("gmg.py", "-n", "32", "-levels", "3", "-maxiter", "60", "-dist",
               "--no-grid")
    m = re.search(r"Iterations: (\d+)\s+residual: ([0-9.e+-]+)", out)
    assert m, out
    assert float(m.group(2)) < 1e-6


def test_heat_implicit_example():
    out = _run("heat_implicit.py", "-n", "12", "-t", "0.2", "-explicit",
               devices=1)
    m = re.search(r"BDF:\s+status=0", out)
    assert m, out
    m = re.search(r"measured ([0-9.e+-]+) vs exp\(-lam1\*t\) ([0-9.e+-]+)",
                  out)
    assert m, out
    a, b = float(m.group(1)), float(m.group(2))
    assert abs(a - b) <= 0.02 * max(abs(b), 1e-3)  # relative
    m = re.search(r"stiffness ratio nfev: ([0-9.]+)x", out)
    assert m and float(m.group(1)) > 1.5, out


def test_gmg_stencil_transfer_operators_match_matrices():
    """The TPU-first conv forms of R (stride-2 conv) and P = R.T
    (input-dilated conv) must be exactly the linear maps of the
    assembled matrices, on even and odd grids, for both gridops."""
    import importlib.util
    import sys as _sys

    import jax.numpy as jnp
    import numpy as np

    here = os.path.join(os.path.dirname(os.path.dirname(__file__)), "examples")
    _sys.path.insert(0, here)
    old_argv = _sys.argv
    _sys.argv = ["gmg.py", "-n", "8", "--precision", "f32"]
    try:
        spec = importlib.util.spec_from_file_location(
            "gmg_stencil_mod", os.path.join(here, "gmg.py")
        )
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
    finally:
        _sys.argv = old_argv
        _sys.path.remove(here)
    rng = np.random.default_rng(0)
    for fine_n in (8, 9, 13):
        dim = fine_n * fine_n
        for gridop, op in (
            ("injection", m.injection_operator), ("linear", m.linear_operator)
        ):
            R, cdim = op(dim)
            cn = int(np.sqrt(cdim))
            r = rng.standard_normal(dim).astype(np.float32)
            xc = rng.standard_normal(cdim).astype(np.float32)
            np.testing.assert_allclose(
                np.asarray(m._restrict_stencil(jnp.asarray(r), fine_n, cn, gridop)),
                np.asarray(R @ r), atol=1e-5,
            )
            np.testing.assert_allclose(
                np.asarray(m._prolong_stencil(jnp.asarray(xc), fine_n, cn, gridop)),
                np.asarray(R.T.tocsr() @ xc), atol=1e-5,
            )


def test_amg_example_single_device():
    # single-device AMG path: device-MIS aggregation hierarchy + the
    # best-of-2 timed solve block
    out = _run("amg.py", "-n", "32", "-maxiter", "60")
    m = re.search(r"Iterations: (\d+)\s+residual: ([0-9.e+-]+)", out)
    assert m, out
    assert float(m.group(2)) < 1e-6


def test_gmg_dist_grid_example():
    """Distributed GMG, grid pipeline: the -dist default — row-sharded
    stencil hierarchy, XLA-inserted halo collectives."""
    out = _run("gmg.py", "-n", "32", "-levels", "3", "-maxiter", "60", "-dist")
    m = re.search(r"Iterations: (\d+)\s+residual: ([0-9.e+-]+)", out)
    assert m, out
    assert float(m.group(2)) < 1e-6
