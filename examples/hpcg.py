"""HPCG's problem: multigrid-preconditioned CG on a stored 27-point operator.

Reference analog: the HPCG benchmark 3.1 (hpcg-benchmark.org; Heroux,
Dongarra, Luszczek, SAND2013-8752): 26 on the diagonal and -1 to each
neighbour of an nx x ny x nz grid, b = A 1, x0 = 0, CG preconditioned by one
V-cycle over four levels with a symmetric Gauss-Seidel smoother, a fixed
count of iterations that never stops early, rated by its own operation count.

TPU-first redesign (``sparse_tpu/models/hpcg_grid.py``): the matrix is 27
stored coefficient planes a level, the unknowns are ordered by colour (the
parity of (z, y, x)) so that a Gauss-Seidel sweep is eight whole-block
updates, and ``linalg.cg(A, b, M=M)`` crosses into that order once a solve
inside its one compiled program. The sweep order is not the reference's
lexicographic one, so a set runs HPCG's optimised count of iterations: as
many as reach the reference's residual at 50 (``benchmark/tools/
hpcg_opt_iters.py``: 64 covers every size read).

Run:  python examples/hpcg.py -nx 32 -ny 32 -nz 32 -levels 4 -maxiter 64
"""

import argparse

from benchmark import parse_common_args

parser = argparse.ArgumentParser()
parser.add_argument("-nx", type=int, default=32)
parser.add_argument("-ny", type=int, default=32)
parser.add_argument("-nz", type=int, default=32)
parser.add_argument("-levels", type=int, default=4)
parser.add_argument("-maxiter", type=int, default=64)
parser.add_argument("-sets", type=int, default=2, help="timed sets of maxiter iterations")
args, _ = parser.parse_known_args()
common, timer, np, sparse, linalg, use_tpu = parse_common_args()
if not use_tpu:
    raise SystemExit("examples/hpcg.py runs the sparse_tpu package only")

import jax.numpy as jnp  # noqa: E402

from sparse_tpu.models import hpcg_grid  # noqa: E402


def hpcg_flops(nx, ny, nz, levels, iters):
    """Operations of ``iters`` iterations by HPCG's own count
    (``ReportResults``): three dot products and three vector updates of 2 n,
    a product of 2 nnz, and the cycle: on every level above the coarsest two
    symmetric steps of 4 nnz, a residual of 2 nnz and a restriction of 2 n;
    on the coarsest one step."""
    def nnz(k):
        return (3 * (nx >> k) - 2) * (3 * (ny >> k) - 2) * (3 * (nz >> k) - 2)

    def rows(k):
        return (nx >> k) * (ny >> k) * (nz >> k)

    per_iter = 12.0 * rows(0) + 2.0 * nnz(0) + 4.0 * nnz(levels - 1)
    for k in range(levels - 1):
        per_iter += 10.0 * nnz(k) + 2.0 * rows(k)
    return per_iter * iters


dtype = jnp.float32 if common.precision == "f32" else jnp.float64
timer.start()
hier = hpcg_grid.build_hierarchy(args.nx, args.ny, args.nz, levels=args.levels,
                                 dtype=dtype)
A = hpcg_grid.grid_operator(hier)
M = hpcg_grid.make_vcycle(hier)
n = args.nx * args.ny * args.nz
b = A @ jnp.ones(n, dtype)  # lexicographic, as HPCG numbers its unknowns
print(f"Hierarchy build time: {timer.stop(fence=b):.1f} ms "
      f"({sum(int(p.size) * p.dtype.itemsize for p in hier) / 1e9:.3f} GB of planes)")

# the first set compiles the program; the timed sets find it again
x, iters = linalg.cg(A, b, tol=0.0, maxiter=args.maxiter, M=M)
timer.start()
for _ in range(args.sets):
    x, iters = linalg.cg(A, b, tol=0.0, maxiter=args.maxiter, M=M)
total_ms = timer.stop(fence=x)
resid = float(jnp.linalg.norm(b - A @ x) / jnp.linalg.norm(b))
print(f"Iterations: {iters}  residual: {resid:.3e}")
print(f"Error: {float(jnp.max(jnp.abs(x - 1))):.3e}")
print(f"Solve time: {total_ms / args.sets:.1f} ms a set")
print(f"Iterations / sec: {args.sets * iters / (total_ms / 1000.0):.3f}")
print("GFLOP/s (HPCG's count): "
      f"{hpcg_flops(args.nx, args.ny, args.nz, args.levels, args.sets * iters) / (total_ms * 1e6):.3f}")
