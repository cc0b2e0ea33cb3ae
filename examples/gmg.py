"""Geometric multigrid (V-cycle) preconditioned CG on the 2-D Poisson problem.

Reference analog: ``examples/gmg.py`` (541 LoC; the BASELINE.md "GMG" row —
4500^2/GPU, 37.2 iters/s @1 V100). Same algorithm: weighted-Jacobi smoothing,
Galerkin coarse operators A_c = R A P via SpGEMM, V-cycle used as the CG
preconditioner.

TPU-first redesigns vs the reference:
  * restriction operators are assembled **vectorized** (9-point stencil masks
    over the whole coarse grid at once) instead of the reference's Python
    loop over coarse points (gmg.py:303-380);
  * the weighted-Jacobi omega uses the pyamg formula omega/rho(D^-1 A);
  * machine-subset scoping for coarse levels (gmg.py:196-224) maps to the
    planned subset-mesh execution; single-chip here.

Run:  python examples/gmg.py -n 128 -levels 4 -maxiter 200
"""

import argparse

import numpy as np

from benchmark import get_phase_procs, parse_common_args

parser = argparse.ArgumentParser()
parser.add_argument("-n", type=int, default=128)
parser.add_argument("-levels", type=int, default=3)
parser.add_argument("-maxiter", type=int, default=200)
parser.add_argument("-tol", type=float, default=1e-8)
parser.add_argument("-gridop", default="linear", choices=["injection", "linear"])
parser.add_argument("-verbose", action="store_true")
parser.add_argument(
    "-dist",
    action="store_true",
    help="build Galerkin coarse operators with mesh-distributed SpGEMM and "
    "solve with a distributed V-cycle-preconditioned CG over the mesh",
)
parser.add_argument(
    "--no-grid",
    action="store_true",
    help="disable the structured-grid stencil pipeline (models/gmg_grid.py) "
    "and use the generic sparse-matrix hierarchy on TPU too",
)
args, _ = parser.parse_known_args()
common, timer, _np, sparse, linalg, use_tpu = parse_common_args()


def _spgemm(X, Y):
    """Galerkin sparse @ sparse (mesh-distributed under -dist; shared
    switch in benchmark.galerkin_spgemm)."""
    from benchmark import galerkin_spgemm

    return galerkin_spgemm(X, Y, args.dist and use_tpu)


def poisson2D(N):
    """5-point Poisson on an N x N grid via the DIA->CSC->T->CSR path."""
    first = np.full(N - 1, -1.0)
    diag_a = np.full(N * N - 1, -1.0)
    diag_a[N - 1 :: N] = 0.0
    diag_g = -1.0 * np.ones(N * (N - 1))
    diag_c = 4.0 * np.ones(N * N)
    diagonals = [diag_g, diag_a, diag_c, diag_a, diag_g]
    offsets = [-N, -1, 0, 1, N]
    return sparse.diags(diagonals, offsets, dtype=np.float64).tocsc().T


def injection_operator(fine_dim):
    """R picking every second fine point (gmg.py:287) — vectorized."""
    fine_n = int(np.sqrt(fine_dim))
    coarse_n = fine_n // 2
    coarse_dim = coarse_n * coarse_n
    ij = np.arange(coarse_dim, dtype=np.int64)
    ci, cj = ij // coarse_n, ij % coarse_n
    cols = 2 * ci * fine_n + 2 * cj
    indptr = np.arange(coarse_dim + 1, dtype=np.int64)
    R = sparse.csr_matrix(
        (np.ones(coarse_dim), cols, indptr), shape=(coarse_dim, fine_dim)
    )
    return R, coarse_dim


def linear_operator(fine_dim):
    """Full-weighting 9-point restriction (gmg.py:303) — vectorized assembly:
    for each of the 9 stencil offsets, one masked COO slab over the whole
    coarse grid; duplicates/order resolved by the sort-based COO->CSR."""
    fine_n = int(np.sqrt(fine_dim))
    coarse_n = fine_n // 2
    coarse_dim = coarse_n * coarse_n
    ij = np.arange(coarse_dim, dtype=np.int64)
    ci, cj = ij // coarse_n, ij % coarse_n
    rows_l, cols_l, vals_l = [], [], []
    weights = {(-1, -1): 1, (-1, 0): 2, (-1, 1): 1,
               (0, -1): 2, (0, 0): 4, (0, 1): 2,
               (1, -1): 1, (1, 0): 2, (1, 1): 1}
    for (di, dj), w in weights.items():
        fi = 2 * ci + di
        fj = 2 * cj + dj
        ok = (fi >= 0) & (fi < fine_n) & (fj >= 0) & (fj < fine_n)
        rows_l.append(ij[ok])
        cols_l.append((fi * fine_n + fj)[ok])
        vals_l.append(np.full(int(ok.sum()), w / 16.0))
    rows = np.concatenate(rows_l)
    cols = np.concatenate(cols_l)
    vals = np.concatenate(vals_l)
    if use_tpu:
        R = sparse.coo_array((vals, (rows, cols)), shape=(coarse_dim, fine_dim)).tocsr()
    else:
        R = sparse.coo_matrix((vals, (rows, cols)), shape=(coarse_dim, fine_dim)).tocsr()
    return R, coarse_dim


def max_eigenvalue(matvec, n, iters=15, seed=0):
    """Power iteration + Rayleigh quotient (gmg.py:134) on a matvec
    closure — lets callers estimate rho(D^-1 A) without materializing
    the scaled matrix (a full SpGEMM+sort per level in the old form)."""
    rng = np.random.default_rng(seed)
    x1 = rng.random(n)
    for _ in range(iters):
        x1 = np.asarray(matvec(x1))
        x1 = x1 / np.linalg.norm(x1)
    return float(np.dot(x1, np.asarray(matvec(x1))))


class WeightedJacobi:
    def __init__(self, omega=4.0 / 3.0):
        self.level_params = []
        self._init_omega = omega

    def init_level_params(self, A, level):
        D_inv = 1.0 / np.asarray(A.diagonal())
        # pyamg-style: omega / rho(D^-1 A); the scaled operator is applied
        # as matvec closures (row scale after SpMV) — no materialized
        # D^-1 A product, no per-level SpGEMM sort
        Di = self._as_backend(D_inv, D_inv)
        Ac = A.tocsr()
        spectral_radius = max_eigenvalue(
            lambda x: Di * (Ac @ x), A.shape[1]
        )
        omega = self._init_omega / spectral_radius
        self.level_params.append((omega, D_inv))
        assert len(self.level_params) - 1 == level

    def pre(self, A, r, x, level):
        omega, D_inv = self.level_params[level]
        return omega * r * self._as_backend(D_inv, r)

    def post(self, A, r, x, level):
        omega, D_inv = self.level_params[level]
        return x + omega * (r - A @ x) * self._as_backend(D_inv, r)

    def coarse(self, A, r, x, level):
        return self.pre(A, r, x, level)

    @staticmethod
    def _as_backend(D_inv, like):
        # keep the smoother traceable: jnp arrays stay jnp (the whole V-cycle
        # then fuses into CG's while_loop); scipy path stays numpy
        if use_tpu:
            import jax.numpy as jnp

            return jnp.asarray(D_inv)
        return D_inv


def _restrict_stencil(r, fine_n, coarse_n, gridop):
    """Apply the restriction R as a separable strided stencil on the 2-D
    grid — TPU-first: three strided slices + weighted add per axis (pure
    VPU elementwise, exact f32) instead of a rectangular gather SpMV.
    A 1-channel XLA conv was tried first: 15x slower on v5e (MXU-shaped
    op at channel count 1) and bf16-rounded. Exactly the linear map of
    injection_operator/linear_operator (oracle-tested)."""
    import jax.numpy as jnp

    cn = coarse_n
    X = r.reshape(fine_n, fine_n)
    if gridop == "injection":
        return X[0 : 2 * cn : 2, 0 : 2 * cn : 2].reshape(-1)

    def r1(Y):  # [1,2,1]/4 at stride 2 along axis 0 of a 1-padded array
        return (
            Y[0 : 2 * cn : 2, :] + 2.0 * Y[1 : 2 * cn + 1 : 2, :]
            + Y[2 : 2 * cn + 2 : 2, :]
        ) * jnp.asarray(0.25, Y.dtype)

    Xp = jnp.pad(X, 1)
    return r1(r1(Xp).T).T.reshape(-1)


def _prolong_stencil(xc, fine_n, coarse_n, gridop):
    """Apply P = R.T as the transposed separable stencil: strided
    scatter-adds of the coarse values onto the fine grid."""
    import jax.numpy as jnp

    cn = coarse_n
    Z = xc.reshape(cn, cn)
    if gridop == "injection":
        out = jnp.zeros((fine_n, fine_n), dtype=Z.dtype)
        return out.at[0 : 2 * cn : 2, 0 : 2 * cn : 2].set(Z).reshape(-1)

    def p1(Y):  # transpose of r1 along axis 0: coarse rows -> fine rows
        half = jnp.asarray(0.5, Y.dtype)
        quarter = jnp.asarray(0.25, Y.dtype)
        out = jnp.zeros((fine_n, Y.shape[1]), Y.dtype)
        out = out.at[0 : 2 * cn : 2, :].add(half * Y)          # f = 2c
        out = out.at[1 : 2 * cn + 1 : 2, :].add(quarter * Y)   # f = 2c+1
        out = out.at[1 : 2 * cn - 2 : 2, :].add(quarter * Y[1:, :])  # f = 2c-1
        return out

    return p1(p1(Z).T).T.reshape(-1)


class GMG:
    """V-cycle preconditioner (gmg.py:148)."""

    def __init__(self, A, shape, levels, gridop):
        self.A = A
        self.shape = shape
        self.N = int(np.prod(shape))
        self.levels = levels
        self.gridop = gridop
        self.restriction_op = {
            "injection": injection_operator,
            "linear": linear_operator,
        }[gridop]
        self.smoother = WeightedJacobi()
        self.grid_dims = []  # per level: (fine_n, coarse_n)
        self.operators = self.compute_operators(A)

    def compute_operators(self, A):
        operators = []
        dim = self.N
        self.smoother.init_level_params(A, 0)
        for level in range(self.levels - 1):
            fine_n = int(np.sqrt(dim))
            R, dim = self.restriction_op(dim)
            self.grid_dims.append((fine_n, int(np.sqrt(dim))))
            P = R.T.tocsr()
            A = _spgemm(_spgemm(R, A), P).tocsr()  # Galerkin: two SpGEMMs
            self.smoother.init_level_params(A, level + 1)
            operators.append((R, A, P))
        return operators

    def cycle(self, r):
        # fully traceable (sparse ops + elementwise): under the sparse_tpu
        # package the entire V-cycle inlines into CG's compiled while_loop
        return self._cycle(self.A, r, 0)

    def _cycle(self, A, r, level):
        if level == self.levels - 1:
            return self.smoother.coarse(A, r, None, level=level)
        R, coarse_A, P = self.operators[level]
        x = self.smoother.pre(A, r, None, level=level)
        fine_r = r - A @ x
        if use_tpu:
            # stencil (conv) form of R/P: the rectangular transfer
            # operators are the one part of the cycle with no banded
            # (DIA) fast path, and the gather SpMV is the V-cycle's
            # bottleneck on TPU — the conv form is exact and XLA-native
            fn, cn = self.grid_dims[level]
            coarse_r = _restrict_stencil(fine_r, fn, cn, self.gridop)
            coarse_x = self._cycle(coarse_A, coarse_r, level + 1)
            x_corrected = x + _prolong_stencil(coarse_x, fn, cn, self.gridop)
        else:
            coarse_r = R @ fine_r
            coarse_x = self._cycle(coarse_A, coarse_r, level + 1)
            x_corrected = x + P @ coarse_x
        return self.smoother.post(A, r, x_corrected, level=level)

    def linear_operator(self):
        if use_tpu:
            return linalg.LinearOperator(
                self.A.shape, dtype=np.float64, matvec=lambda r: self.cycle(r)
            )
        import scipy.sparse.linalg as sla

        return sla.LinearOperator(
            self.A.shape, dtype=np.float64, matvec=lambda r: self.cycle(r)
        )


def build_dist_cycle(mg, mesh, replicate_below: int = 2048):
    """Mesh-sharded weighted-Jacobi V-cycle over the geometric hierarchy
    (shared machinery: ``sparse_tpu.parallel.multigrid``). The coarsest
    level applies the smoother, as in GMG._cycle — no dense solve.

    Levels at or below ``replicate_below`` rows run as a dense REPLICATED
    tail (one gather in, one scatter out, zero per-level collectives) —
    the fix for the reference's coarse-level weak-scaling collapse
    (SURVEY §6: 4% efficiency at 192 GPUs).
    """
    from sparse_tpu.parallel.multigrid import (
        make_dist_vcycle,
        make_replicated_tail,
        shard_hierarchy,
        tail_crossover,
    )

    As = [mg.A] + [op[1] for op in mg.operators]
    RPs = [(op[0], op[2]) for op in mg.operators]
    L = len(As)
    # no bottom_always: a smoother bottom never NEEDS replication, so a
    # hierarchy whose coarsest level is still large stays fully sharded
    # (densifying it would be an O(n^2) replicated allocation)
    c = tail_crossover([A.shape[0] for A in As], replicate_below)

    def pad_w(i, Ad):
        omega, D_inv = mg.smoother.level_params[i]
        # pad slots get omega*1.0 — inert (padded inputs are exactly zero)
        return float(omega) * (
            Ad.pad_out_vector(np.asarray(D_inv) - 1.0) + 1.0
        )

    if c >= L:  # fully sharded, smoother bottom
        ops, _ = shard_hierarchy(As, RPs, mesh)
        weights = [pad_w(i, ops[i][0]) for i in range(L)]
        return ops[0][0], make_dist_vcycle(
            ops, weights, coarse_apply=lambda rp: weights[-1] * rp
        )

    ops, spl_list = shard_hierarchy(As[: c + 1], RPs[:c], mesh)
    weights = [pad_w(i, ops[i][0]) for i in range(c)]
    weights.append(None)  # level c enters the replicated tail

    def host_w(i):
        omega, D_inv = mg.smoother.level_params[i]
        return float(omega) * np.asarray(D_inv)

    coarse_apply = make_replicated_tail(
        As[c:],
        RPs[c:],
        [host_w(i) for i in range(c, L - 1)],
        spl_list[-1],
        ops[-1][0].R,
        bottom="smooth",
        bottom_weight=host_w(L - 1),
    )
    return ops[0][0], make_dist_vcycle(ops, weights, coarse_apply)


def main_grid():
    """Structured-grid pipeline (sparse_tpu/models/gmg_grid.py): stencil
    hierarchy via comb-probed Galerkin products, grid-space V-cycle, the
    whole PCG one compiled program (jit_pcg) that the next solve reuses.
    Numerically the same hierarchy as
    the generic path (oracle-pinned in tests/test_gmg_grid.py); replaces
    its two dominant costs — host COO sorts + eager power iteration in
    init (~52 s at n=4000 measured r3) and CSR/gather ops in the cycle."""
    import jax
    import jax.numpy as jnp

    from sparse_tpu.models import gmg_grid as gg

    N = args.n
    dtype = jnp.float64 if common.precision == "f64" else jnp.float32
    build, solve = get_phase_procs(use_tpu)
    timer.start()
    with build:
        rng = np.random.default_rng(0)
        b = jnp.asarray(rng.random(N * N), dtype=dtype)
    print(f"Data creation time: {timer.stop():.1f} ms")

    timer.start()
    with build:
        hier = gg.build_hierarchy(N, args.levels, args.gridop, dtype=dtype)
    print(f"GMG init time: {timer.stop():.1f} ms")

    with solve:
        if args.dist:
            # every level's planes and the vectors in row blocks over the
            # mesh; the SAME vcycle/cg code below then runs each stencil
            # apply and transfer on a shard's own rows and one row from each
            # neighbour (gmg_grid's row-block forms: two exchanges an apply,
            # one a transfer, the fine level's kernel a shard; CG's dot
            # products are the partitioner's psums). Held to the one-device
            # solve and the plain reference in tests/test_gmg_mesh.py; on
            # four v5e chips: PERF.md section 5 (the builder's readings, PR 46)
            from sparse_tpu.parallel.mesh import get_mesh

            hier, vec_sharding = gg.shard_hierarchy_grid(hier, get_mesh())
            b = jax.device_put(b, vec_sharding)
        else:
            # commit the stencil planes (built CPU-side) to the
            # accelerator: jit ARGUMENTS that stay host-resident would
            # re-cross the device link every call (kernels/cg_dia.py
            # residency note). Arrays only — the per-level grid size n is
            # a PYTHON int feeding static_argnums and must not become a
            # jax Array.
            from sparse_tpu.utils import commit_to_exec_device

            hier = [
                (
                    dict(
                        zip(st.keys(), commit_to_exec_device(tuple(st.values())))
                    ),
                    commit_to_exec_device((w,))[0],
                    n,
                )
                for (st, w, n) in hier
            ]
            b = commit_to_exec_device((b,))[0]
        # both operators declare what they hold (the planes and weights),
        # so linalg.cg runs ONE compiled program, jit_pcg, with the
        # hierarchy as its arguments: the second solve compiles nothing
        A_op = gg.grid_operator(hier)
        M = gg.make_vcycle(hier, args.gridop)
        mv = A_op.matvec

        from benchmark import solve_timed_best_of_2

        x, iters, total_ms = solve_timed_best_of_2(
            lambda: linalg.cg(A_op, b, tol=args.tol, maxiter=args.maxiter, M=M),
            timer,
        )

    resid = float(np.linalg.norm(np.asarray(mv(x)) - np.asarray(b)))
    print(f"Iterations: {iters}  residual: {resid:.3e}")
    print(f"Solve time: {total_ms:.1f} ms")
    print(f"Iterations / sec: {iters / (total_ms / 1000.0):.3f}")


def main():
    N = args.n
    build, solve = get_phase_procs(use_tpu)
    timer.start()
    with build:
        A = poisson2D(N).tocsr()
        rng = np.random.default_rng(0)
        b = rng.random(N * N)
    print(f"Data creation time: {timer.stop():.1f} ms")

    timer.start()
    with build:
        mg = GMG(A=A, shape=(N, N), levels=args.levels, gridop=args.gridop)
        M = mg.linear_operator()
    print(f"GMG init time: {timer.stop():.1f} ms")

    callback = None
    if args.verbose:
        def callback(x):
            print(f"Residual: {np.linalg.norm(b - np.asarray(A @ x)):.3e}")

    with solve:
        if use_tpu and args.dist:
            from benchmark import solve_dist_cg_timed
            from sparse_tpu.parallel.mesh import get_mesh

            A0d, cycle = build_dist_cycle(mg, get_mesh())
            x, iters, total_ms = solve_dist_cg_timed(
                A0d, cycle, b, timer, tol=args.tol, maxiter=args.maxiter
            )
            resid = float(np.linalg.norm(np.asarray(A @ x) - b))
            print(f"Iterations: {iters}  residual: {resid:.3e}")
            print(f"Solve time: {total_ms:.1f} ms")
            print(f"Iterations / sec: {iters / (total_ms / 1000.0):.3f}")
            return
        _ = float(np.linalg.norm(np.asarray(A @ np.zeros(A.shape[1]))))  # warm up
        if use_tpu and callback is None:
            import os as _os

            if _os.environ.get("SPARSE_TPU_SPMV_MODE") is None:
                # banded level operators: Mosaic DIA kernel beats the XLA
                # shift-add form (+17% measured on v5e at n=1000); safe —
                # cached_prepared_spmv falls back off-TPU
                from sparse_tpu.config import settings

                settings.spmv_mode = "pallas"
            from benchmark import solve_timed_best_of_2

            x, iters, total_ms = solve_timed_best_of_2(
                lambda: linalg.cg(A, b, tol=args.tol, maxiter=args.maxiter, M=M),
                timer,
            )
        else:
            timer.start()
            if use_tpu:
                x, iters = linalg.cg(
                    A, b, tol=args.tol, maxiter=args.maxiter, M=M,
                    callback=callback,
                )
            else:
                it = [0]

                def count(xk):
                    it[0] += 1

                x, _ = linalg.cg(
                    A, b, rtol=args.tol, maxiter=args.maxiter, M=M,
                    callback=count,
                )
                iters = it[0]
            total_ms = timer.stop(fence=x)

    resid = float(np.linalg.norm(np.asarray(A @ x) - b))
    print(f"Iterations: {iters}  residual: {resid:.3e}")
    print(f"Solve time: {total_ms:.1f} ms")
    print(f"Iterations / sec: {iters / (total_ms / 1000.0):.3f}")


if __name__ == "__main__":
    # grid pipeline is the default on the sparse_tpu package (single-
    # device AND -dist, where it distributes via sharding annotations);
    # --no-grid keeps the generic sparse-matrix machinery exercised,
    # including the explicit DistCSR/replicated-tail -dist path.
    if use_tpu and not args.no_grid:
        main_grid()
    else:
        main()
