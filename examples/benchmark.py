"""Shared benchmark harness for the examples.

Reference analog: ``examples/benchmark.py`` — Timer protocol (LegateTimer uses
time futures so timing doesn't synchronize, benchmark.py:18-31), per-phase
machine scoping (benchmark.py:92-117), and the ``--package legate|cupy|scipy``
switch (benchmark.py:120-140).

TPU translation:
  * the future-based timer becomes a fetch-fence timer: ``stop(fence=arr)``
    pulls one scalar from the last result, which orders the host clock after
    all device work (jax dispatch is async);
  * machine phase scoping becomes ``jax.default_device`` scoping: build
    phases can run on CPU while solve phases run on the TPU chip;
  * ``--package sparse_tpu|scipy`` keeps the scipy oracle runnable from every
    example for comparison runs.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# allow running the examples straight from the repo checkout
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _repo_root not in sys.path:
    sys.path.insert(0, _repo_root)


class Timer:
    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, fence=None) -> float:
        """Milliseconds since start(). ``fence`` orders the clock after device
        work by fetching one scalar from the given array."""
        if fence is not None:
            _fetch_scalar(fence)
        return (time.perf_counter() - self._t0) * 1000.0


def _fetch_scalar(arr):
    import numpy as np

    a = arr
    while getattr(a, "ndim", 0) > 0:
        a = a[tuple(0 for _ in range(a.ndim))]
    return float(np.real(np.asarray(a)))


def parse_common_args(extra=None):
    """Returns (args, timer, np_like, sparse, linalg, use_tpu_package)."""
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument(
        "--package", default="sparse_tpu", choices=["sparse_tpu", "scipy"]
    )
    parser.add_argument(
        "--precision", default="f64", choices=["f32", "f64"],
        help="f64 enables x64 (emulated on TPU); f32 is TPU-native",
    )
    parser.add_argument("--build-on-cpu", action="store_true",
                        help="run construction phases on the host CPU device")
    args, _ = parser.parse_known_args()

    if args.package == "sparse_tpu":
        import jax

        if args.precision == "f64":
            jax.config.update("jax_enable_x64", True)
        from sparse_tpu.utils import enable_compilation_cache

        enable_compilation_cache()  # reruns skip recompiles
        import numpy as np

        import sparse_tpu as sparse
        from sparse_tpu import linalg

        return args, Timer(), np, sparse, linalg, True
    else:
        import numpy as np
        import scipy.sparse as sparse
        import scipy.sparse.linalg as linalg

        return args, Timer(), np, sparse, linalg, False


def get_phase_procs(use_tpu: bool):
    """(build_scope, solve_scope) context managers — the machine-scoping
    analog (benchmark.py:92-117). On TPU: device placement scopes."""
    import contextlib

    if not use_tpu:
        return contextlib.nullcontext(), contextlib.nullcontext()
    import jax

    # jax.devices() lists only the DEFAULT platform — on a TPU the CPU
    # backend never appears there, which silently routes the whole build
    # phase through the accelerator op by op. Ask for the cpu backend
    # explicitly; it coexists with the accelerator client.
    try:
        cpus = jax.devices("cpu")
    except RuntimeError:
        cpus = None
    accel = jax.devices()[0]
    build = jax.default_device(cpus[0]) if cpus and accel.platform != "cpu" else contextlib.nullcontext()
    solve = jax.default_device(accel)
    return build, solve


def solve_timed_best_of_2(solve, timer):
    """Shared estimator block for the single-device benchmark examples:
    one warm-up solve outside the clock (the reference's CUDA tasks are
    prebuilt), two timed solves, and BOTH estimators disclosed — min-of-2
    approximates machine capability under host-clock noise, mean-of-2 is the comparable-estimator number
    (the reference baselines are means over dedicated-node runs).

    ``solve`` is a zero-arg callable returning (x, iters) with identical
    arguments each call, so the timed calls reuse the compiled while_loop.
    Prints the disclosure lines ("Iterations / sec (mean)") and returns (x, iters, min_ms).
    """
    _ = solve()
    timer.start()
    x, iters = solve()
    first_ms = timer.stop(fence=x)
    timer.start()
    x, iters = solve()
    second_ms = timer.stop(fence=x)
    mean_ms = (first_ms + second_ms) / 2.0
    min_ms = min(first_ms, second_ms)
    print(f"Timing: 2 timed solves, min {min_ms:.1f} ms / mean {mean_ms:.1f} ms")
    print(f"Iterations / sec (mean): {iters / (mean_ms / 1000.0):.3f}")
    return x, iters, min_ms


def solve_dist_cg_timed(A0d, cycle, b, timer, tol, maxiter, conv_test_iters=5):
    """Shared -dist solve block for the multigrid examples: compile the
    distributed preconditioned CG outside the timing, fence on a host
    scalar read, and fetch the full solution only after the clock stops.
    Returns (x, iters, total_ms)."""
    import jax.numpy as jnp

    from sparse_tpu.parallel.dist import make_dist_cg

    solver = make_dist_cg(
        A0d, tol=tol, maxiter=maxiter, M=cycle, conv_test_iters=conv_test_iters
    )
    bp = A0d.pad_out_vector(b)
    x0p = jnp.zeros_like(bp)
    solver(bp, x0p)[0].block_until_ready()  # compile outside timing
    timer.start()
    xp, iters, _ = solver(bp, x0p)
    iters = int(iters)  # completion fence (host scalar read)
    total_ms = timer.stop(fence=xp)
    x = A0d.unpad_vector(xp)  # full-vector fetch outside the timing
    return x, iters, total_ms


def galerkin_spgemm(X, Y, dist: bool):
    """Sparse @ sparse for hierarchy setup, routed through the
    mesh-distributed row-gather SpGEMM (parallel.spgemm.dist_spgemm;
    reference csr.py:1390-1490) when ``dist`` — shared by the -dist modes
    of the multigrid examples."""
    if dist:
        from sparse_tpu.parallel import dist_spgemm

        return dist_spgemm(X.tocsr(), Y.tocsr())
    return X @ Y
