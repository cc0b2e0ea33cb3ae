"""XGC's collision step: thousands of small same-pattern nonsymmetric
systems in one call.

Reference analog: Ginkgo's batched BiCGStab with a scalar Jacobi
preconditioner inside the gyrokinetic code XGC (Kashi et al., IPDPS 2022):
at every mesh vertex and for every species one backward-Euler step of the
Fokker-Planck-Landau collision operator is a linear system on a 32 x 31
velocity grid, 992 unknowns, a 9-point stencil, 8,554 stored entries; every
system has the same pattern and its own values, ion systems take a handful
of steps and electron systems several times more, and all of them go to the
solver in ONE call.

TPU-first redesign: the value stack ``[systems, 8554]`` (CSR order, as an
assembly hands it over) is repacked once into nine planes a lane
(``BatchedCSR.todia``), Jacobi is the reciprocal of the diagonal plane
(``precond.make_factory``), and ``linalg.batched_bicgstab`` over the two is
one compiled program, ``jit_batched_bicgstab``, whose arguments are the planes,
the diagonal, b, the start and the lanes' tolerances: the second call (the
next Picard iteration: new values on the same pattern) traces and compiles
nothing. A lane that has converged is frozen under its mask; a call of 7,937
systems or more stops stepping the ones that are done (the program compacts
its active lanes down a ladder of halving widths: docs/batching.md). The
matrices here are made by the benchmark's generator
(``benchmark/operators/xgc_collision.py``: A = I - dt C, C a finite-volume
discretisation of div (D grad f + F f), strictly diagonally dominant), which
stands in for XGC's own assembly.

Run:  python examples/xgc_collision.py --precision f32 -systems 1024 -seed 7
"""

import argparse
import importlib.util
import os

from benchmark import parse_common_args

parser = argparse.ArgumentParser()
parser.add_argument("-systems", type=int, default=1024,
                    help="lanes: two species a mesh vertex, interleaved")
parser.add_argument("-seed", type=int, default=7,
                    help="the order the vertices come in and their units")
parser.add_argument("-mesh_seed", type=int, default=55,
                    help="the mesh: every vertex's density, temperature, drift")
parser.add_argument("-tol", type=float, default=1e-5,
                    help="relative residual a lane, against its own ||b||")
parser.add_argument("-maxiter", type=int, default=200)
parser.add_argument("-calls", type=int, default=3)
args, _ = parser.parse_known_args()
common, timer, np, sparse, linalg, use_tpu = parse_common_args()
if not use_tpu:
    raise SystemExit("examples/xgc_collision.py runs the sparse_tpu package only")
if common.precision != "f32":
    print("note: float64 lanes run the eager loop, compiled at every call; "
          "--precision f32 runs the one compiled program")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from sparse_tpu import precond  # noqa: E402
from sparse_tpu.batch import BatchedCSR, SparsityPattern  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "xgc_collision_assembly", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "benchmark",
        "operators", "xgc_collision.py"))
assembly = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(assembly)

sizes = {"velocity_grid": [32, 31], "rows": 992, "nnz": 8554,
         "systems": args.systems, "mesh_seed": args.mesh_seed,
         "tol_rel": args.tol, "maxiter": args.maxiter,
         "conv_test_iters": 1, "check_sample": 8}
timer.start()
data = assembly.make(sizes, args.seed)
dtype = jnp.float32 if common.precision == "f32" else jnp.float64
values, b = data["values"].astype(dtype), data["b"].astype(dtype)
print(f"Assembly time: {timer.stop(fence=values):.1f} ms "
      f"({values.size * values.dtype.itemsize / 1e9:.3f} GB of values)")

# the six calls: pattern, operator, preconditioner, tolerances, solve, fence
timer.start()
n = data["rows"]
pattern = SparsityPattern(data["indptr"], data["indices"], (n, n))
op = BatchedCSR(pattern, values).todia()
M = precond.make_factory(pattern, "jacobi")(values, op.matvec)
tol = args.tol * jnp.linalg.norm(b, axis=1)
print(f"Operator build time: {timer.stop(fence=op.data):.1f} ms")

# the first call compiles the program; the timed calls find it again
X, info = linalg.batched_bicgstab(op, b, x0=b, tol=tol, maxiter=args.maxiter,
                                  M=M, conv_test_iters=1)
timer.start()
for _ in range(args.calls):
    X, info = linalg.batched_bicgstab(op, b, x0=b, tol=tol,
                                      maxiter=args.maxiter, M=M,
                                      conv_test_iters=1)
    jax.block_until_ready(X)
total_ms = timer.stop(fence=X)
iters = np.asarray(info.iters)
resid = np.asarray(jnp.linalg.norm(b - op.matvec(X), axis=1)
                   / jnp.linalg.norm(b, axis=1))
by = assembly.species_counts(data, iters)
print(f"Systems: {args.systems}  converged: {int(np.sum(np.asarray(info.converged)))}")
print(f"Iterations (min, median, max): ions {by['ion']}  electrons {by['electron']}")
print(f"Largest relative residual: {resid.max():.3e}")
print("Frozen lane-steps: "
      f"{100 * (1 - iters.sum() / (iters.size * iters.max())):.1f} %")
print(f"Solve time: {total_ms / args.calls:.1f} ms a call")
print(f"Systems / sec: {args.calls * args.systems / (total_ms / 1000.0):.1f}")
