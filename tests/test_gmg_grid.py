"""Structured-grid GMG (sparse_tpu/models/gmg_grid.py) oracle tests.

Every grid-space op is pinned EXACTLY (f64 atol 1e-12) to the explicit
sparse-matrix formulation it replaces — the restriction/prolongation
matrices and Galerkin SpGEMM products of examples/gmg.py — so the stencil
pipeline is provably the same linear algebra, just without general sparse
formats. Reference analog: examples/gmg.py:287-381 (gmg.py:303-380 in the
reference repo).
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from sparse_tpu.models import gmg_grid as gg


def poisson_sp(N):
    diag_a = np.full(N * N - 1, -1.0)
    diag_a[N - 1 :: N] = 0.0
    diag_g = -np.ones(N * (N - 1))
    diag_c = 4.0 * np.ones(N * N)
    return sp.diags(
        [diag_g, diag_a, diag_c, diag_a, diag_g], [-N, -1, 0, 1, N]
    ).tocsr()


def R_mat(fine_n, gridop):
    """Explicit restriction matrix (examples/gmg.py:injection_operator /
    linear_operator, scipy form)."""
    coarse_n = fine_n // 2
    coarse_dim = coarse_n * coarse_n
    fine_dim = fine_n * fine_n
    ij = np.arange(coarse_dim)
    ci, cj = ij // coarse_n, ij % coarse_n
    if gridop == "injection":
        cols = 2 * ci * fine_n + 2 * cj
        return sp.csr_matrix(
            (np.ones(coarse_dim), cols, np.arange(coarse_dim + 1)),
            shape=(coarse_dim, fine_dim),
        )
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
    return sp.coo_matrix(
        (np.concatenate(vals_l), (np.concatenate(rows_l), np.concatenate(cols_l))),
        shape=(coarse_dim, fine_dim),
    ).tocsr()


def stencil_to_dense(stc, cn):
    out = np.zeros((cn * cn, cn * cn))
    for (di, dj), C in stc.items():
        C = np.broadcast_to(np.asarray(C), (cn, cn))  # scalar or plane form
        for i in range(cn):
            for j in range(cn):
                ii, jj = i + di, j + dj
                if 0 <= ii < cn and 0 <= jj < cn:
                    out[i * cn + j, ii * cn + jj] += C[i, j]
    return out


@pytest.mark.parametrize("n", [8, 9, 13])
@pytest.mark.parametrize("gridop", ["linear", "injection"])
def test_grid_ops_match_matrices(n, gridop):
    cn = n // 2
    A = poisson_sp(n)
    R = R_mat(n, gridop)
    P = R.T.tocsr()
    st = gg.poisson_stencil(n, jnp.float64)
    x = np.random.default_rng(1).random((n, n))
    z = np.random.default_rng(2).random((cn, cn))

    np.testing.assert_allclose(
        np.asarray(gg.stencil_apply(st, jnp.asarray(x))),
        (A @ x.reshape(-1)).reshape(n, n), atol=1e-12,
    )
    np.testing.assert_allclose(
        np.asarray(gg.restrict_grid(jnp.asarray(x), cn, gridop)),
        (R @ x.reshape(-1)).reshape(cn, cn), atol=1e-12,
    )
    np.testing.assert_allclose(
        np.asarray(gg.prolong_grid(jnp.asarray(z), n, cn, gridop)),
        (P @ z.reshape(-1)).reshape(n, n), atol=1e-12,
    )
    stc = gg.galerkin_stencil(st, n, cn, gridop)
    np.testing.assert_allclose(
        stencil_to_dense(stc, cn), (R @ A @ P).toarray(), atol=1e-12
    )


def test_galerkin_recursion_matches_spgemm_chain():
    """Three coarsening steps: the probed stencils equal the R A P chain."""
    n = 33
    A = poisson_sp(n)
    st = gg.poisson_stencil(n, jnp.float64)
    for _ in range(3):
        cn = n // 2
        R = R_mat(n, "linear")
        Ac = (R @ A @ R.T).tocsr()
        st = gg.galerkin_stencil(st, n, cn, "linear")
        np.testing.assert_allclose(
            stencil_to_dense(st, cn), Ac.toarray(), atol=1e-12
        )
        A, n = Ac, cn


def test_omega_matches_host_power_iteration():
    """The jitted fori_loop rho equals the examples/gmg.py host loop
    (same seed, same iteration count, same Rayleigh quotient)."""
    n = 16
    A = poisson_sp(n)
    D_inv = 1.0 / A.diagonal()
    rng = np.random.default_rng(0)
    x1 = rng.random(n * n)
    for _ in range(15):
        x1 = D_inv * (A @ x1)
        x1 = x1 / np.linalg.norm(x1)
    rho_host = float(np.dot(x1, D_inv * (A @ x1)))

    st = gg.poisson_stencil(n, jnp.float64)
    rho_grid = gg._rho(st, 1.0 / st[(0, 0)], n, seed=0, iters=15)
    np.testing.assert_allclose(rho_grid, rho_host, rtol=1e-10)


def test_vcycle_equals_matrix_form():
    """One V-cycle output == the same recursion done with explicit
    scipy matrices and the same smoother weights."""
    n, levels, gridop = 13, 3, "linear"
    hier = gg.build_hierarchy(n, levels, gridop, dtype=jnp.float64)

    mats = []
    A = poisson_sp(n)
    fn = n
    for lvl in range(levels):
        w = np.asarray(hier[lvl][1]).reshape(-1)  # omega * D^-1, flat
        mats.append((A, w, fn))
        if lvl < levels - 1:
            R = R_mat(fn, gridop)
            A = (R @ A @ R.T).tocsr()
            fn = fn // 2

    def cycle_ref(r, lvl):
        A, w, fn = mats[lvl]
        if lvl == levels - 1:
            return w * r
        x = w * r
        fine_r = r - A @ x
        R = R_mat(fn, gridop)
        coarse_x = cycle_ref(R @ fine_r, lvl + 1)
        x = x + R.T @ coarse_x
        return x + w * (r - A @ x)

    r = np.random.default_rng(3).random(n * n)
    got = np.asarray(jax.jit(gg.make_vcycle(hier, gridop))(jnp.asarray(r)))
    np.testing.assert_allclose(got, cycle_ref(r, 0), atol=1e-10)


def test_pcg_with_grid_vcycle_converges():
    """linalg.cg + the grid V-cycle preconditioner solves the Poisson
    problem in far fewer iterations than plain CG (the GMG benchmark
    composition, examples/gmg.py:main)."""
    from sparse_tpu import linalg

    n = 64
    hier = gg.build_hierarchy(n, 4, "linear", dtype=jnp.float64)
    vc = gg.make_vcycle(hier, "linear")
    st = hier[0][0]

    A_op = linalg.LinearOperator(
        (n * n, n * n), dtype=np.float64,
        matvec=lambda v: gg.stencil_apply(st, v.reshape(n, n)).reshape(-1),
    )
    M = linalg.LinearOperator((n * n, n * n), dtype=np.float64, matvec=vc)
    b = np.random.default_rng(0).random(n * n)
    x, iters = linalg.cg(A_op, b, tol=1e-8, maxiter=300, M=M)
    A = poisson_sp(n)
    assert np.linalg.norm(A @ np.asarray(x) - b) < 1e-6
    _, iters_plain = linalg.cg(A_op, b, tol=1e-8, maxiter=2000)
    assert iters < iters_plain / 3, (iters, iters_plain)


def test_sharded_grid_hierarchy_matches_single_device():
    """GSPMD-distributed form (VERDICT: distributed is first-class): the
    SAME vcycle/cg code over a row-sharded hierarchy must produce the
    single-device iterates — XLA inserts the stencil halo collectives
    from the sharding annotations alone."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparse_tpu import linalg
    from sparse_tpu.parallel.mesh import get_mesh

    n = 64
    mesh = get_mesh(8)
    hier = gg.build_hierarchy(n, 3, "linear", dtype=jnp.float64)
    vc = gg.make_vcycle(hier, "linear")
    r = np.random.default_rng(7).random(n * n)
    want = np.asarray(jax.jit(vc)(jnp.asarray(r)))

    hs, vec_sharding = gg.shard_hierarchy_grid(hier, mesh, replicate_below=1024)
    vc_s = jax.jit(gg.make_vcycle(hs, "linear"))
    rs = jax.device_put(jnp.asarray(r), vec_sharding)
    assert vec_sharding.spec == P("shards"), vec_sharding
    got = vc_s(rs)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-11)
    # the compiled program must be genuinely distributed: some
    # collective moves the stencil halos / transfer rows
    txt = vc_s.lower(rs).compile().as_text()
    assert ("collective-permute" in txt) or ("all-gather" in txt), (
        "no collective in the sharded V-cycle program"
    )

    # end-to-end: the full PCG over the sharded hierarchy converges to
    # the same answer as the single-device run
    st_s = hs[0][0]
    mv = jax.jit(
        lambda v: gg.stencil_apply(st_s, v.reshape(n, n)).reshape(-1)
    )
    A_op = linalg.LinearOperator((n * n, n * n), dtype=np.float64, matvec=mv)
    M = linalg.LinearOperator(
        (n * n, n * n), dtype=np.float64, matvec=gg.make_vcycle(hs, "linear")
    )
    b = np.random.default_rng(8).random(n * n)
    bs = jax.device_put(jnp.asarray(b), vec_sharding)
    x, iters = linalg.cg(A_op, bs, tol=1e-9, maxiter=200, M=M)
    A = poisson_sp(n)
    assert np.linalg.norm(A @ np.asarray(x) - b) < 1e-6
    assert iters < 60


def test_sharded_grid_hierarchy_odd_sizes_replicate():
    """Non-divisible levels must REPLICATE, not crash: n=33 hierarchy on
    8 devices (33 % 8 != 0 at every level) runs end to end."""
    from jax.sharding import PartitionSpec as P

    from sparse_tpu.parallel.mesh import get_mesh

    mesh = get_mesh(8)
    hier = gg.build_hierarchy(33, 3, "linear", dtype=jnp.float64)
    hs, vec_sharding = gg.shard_hierarchy_grid(hier, mesh)
    assert vec_sharding.spec == P(), "unshardable level 0 must replicate"
    r = np.random.default_rng(9).random(33 * 33)
    rs = jax.device_put(jnp.asarray(r), vec_sharding)
    got = jax.jit(gg.make_vcycle(hs, "linear"))(rs)
    want = jax.jit(gg.make_vcycle(hier, "linear"))(jnp.asarray(r))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-11)


# -- the fine level's on-tile form (kernels/grid_stencil.py, PR 44) -----------
FIVE = {(0, 0): 4.25, (-1, 0): -1.5, (1, 0): -0.75, (0, -1): -1.25, (0, 1): -0.5}
USES = {
    "apply": lambda A, w, x, r: A(x),
    "residual": lambda A, w, x, r: x - A(w * x),
    "smooth": lambda A, w, x, r: x + w * (r - A(x)),
}


def five_point_sp(N, coef):
    """The 5-point stencil ``coef`` ({(di, dj): value}) as a scipy matrix on
    the flat grid, couplings across the grid's edge dropped."""
    i, j = np.divmod(np.arange(N * N), N)
    rows, cols, vals = [], [], []
    for (di, dj), c in coef.items():
        ok = (i + di >= 0) & (i + di < N) & (j + dj >= 0) & (j + dj < N)
        rows.append(np.arange(N * N)[ok])
        cols.append(((i + di) * N + j + dj)[ok])
        vals.append(np.full(int(ok.sum()), float(c)))
    return sp.coo_matrix((np.concatenate(vals), (np.concatenate(rows),
                                                 np.concatenate(cols))),
                         shape=(N * N, N * N)).tocsr()


def _edge_heavy(n, seed):
    """A grid whose boundary rows and columns are its largest entries: the
    edge mask and the halo rows are where the on-tile form can go wrong."""
    g = np.random.default_rng(seed).standard_normal((n, n))
    for edge in (g[0], g[-1], g[:, 0], g[:, -1]):
        edge *= 50.0
    return g.astype(np.float32)


@pytest.mark.parametrize("coef", [None, FIVE], ids=["poisson", "five-distinct"])
@pytest.mark.parametrize("n", [128, 256, 384])
@pytest.mark.parametrize("use", list(USES))
def test_on_tile_stencil_equals_stencil_apply_and_scipy(use, n, coef):
    """Each of the fine level's three uses through the kernel (interpreted
    on the CPU), at one, two and three row blocks: a first, a middle and a
    last block, halo rows from both neighbours. Distinct coefficients tell
    the four neighbours apart, which Poisson's -1s do not."""
    st = gg.poisson_stencil(n) if coef is None else {
        d: jnp.asarray(c, jnp.float32) for d, c in coef.items()}
    S = poisson_sp(n) if coef is None else five_point_sp(n, coef)
    w = jnp.asarray(0.3, jnp.float32)
    x, r = _edge_heavy(n, 1), _edge_heavy(n, 2)
    got = gg._fine_stencil(use, tuple(st), tuple(st.values()),
                           None if use == "apply" else w,
                           jnp.asarray(x), jnp.asarray(r) if use == "smooth" else None)
    assert got.dtype == jnp.float32 and got.shape == (n, n)
    by_xla = USES[use](lambda v: gg.stencil_apply(st, v), w, jnp.asarray(x),
                       jnp.asarray(r))
    by_scipy = USES[use](
        lambda v: (S @ v.astype(np.float64).reshape(-1)).reshape(n, n),
        0.3, x, r)
    scale = np.abs(by_scipy).max()
    assert np.abs(np.asarray(got) - np.asarray(by_xla)).max() <= 2e-6 * scale
    assert np.abs(np.asarray(got) - by_scipy).max() <= 2e-6 * scale
    # row 0 and the last row, column 0 and the last column, by themselves
    for edge in (np.s_[0], np.s_[-1], np.s_[:, 0], np.s_[:, -1]):
        assert np.abs(np.asarray(got)[edge] - by_scipy[edge]).max() <= 2e-6 * scale


def _shard8(hier):
    from sparse_tpu.parallel.mesh import get_mesh

    return gg.shard_hierarchy_grid(hier, get_mesh(8), replicate_below=1024)[0]


WHO_KEEPS_STENCIL_APPLY = {
    # a side off a multiple of 128
    "side-130": lambda: gg.build_hierarchy(130, 2),
    # nine coefficient planes: a coarse level's operator as the fine one
    "planes": lambda: gg.build_hierarchy(
        128, 2, planes=gg.build_hierarchy(256, 2)[1][0]),
    # float64 scalars
    "float64": lambda: gg.build_hierarchy(128, 2, dtype=jnp.float64),
    # float64 laid over a mesh, three rows a shard: no kernel (what a mesh
    # changes, the row-block forms, is tests/test_gmg_mesh.py's)
    "sharded-float64": lambda: _shard8(gg.build_hierarchy(24, 2, dtype=jnp.float64)),
}


@pytest.mark.parametrize("who", list(WHO_KEEPS_STENCIL_APPLY))
def test_who_does_not_fit_the_kernel_keeps_stencil_apply_and_its_bits(
        who, monkeypatch):
    """With the kernel's platform set to this process's (the CPU: a
    hierarchy of float32 scalars at a side of 128 on one device would take
    the kernel, interpreted), these keep the operators they had: equal
    ``apply`` objects, so the same programs, and the same bits."""
    hier = WHO_KEEPS_STENCIL_APPLY[who]()
    n = hier[0][2]
    A0, M0 = gg.grid_operator(hier), gg.make_vcycle(hier)
    monkeypatch.setattr(gg, "_KERNEL_PLATFORM", "cpu")
    A, M = gg.grid_operator(hier), gg.make_vcycle(hier)
    assert A.apply == A0.apply == gg._GridApply(n, tuple(hier[0][0]),
                                                rows=A0.apply.rows)
    assert M.apply == M0.apply and not M.apply.fine_kernel
    assert (A.apply.rows is not None) == who.startswith("sharded")
    assert A.describe["fine_stencil_kernels"] == 0 and A.describe == A0.describe
    assert M.describe["fine_stencil_kernels"] == 0 and M.describe == M0.describe
    assert (M.describe["precond"], M.describe["levels"]) == ("gmg_grid", 2)
    r = jnp.asarray(np.random.default_rng(5).random(n * n), hier[0][1].dtype)
    assert np.array_equal(np.asarray(A.matvec(r)), np.asarray(A0.matvec(r)))
    assert np.array_equal(np.asarray(M.matvec(r)), np.asarray(M0.matvec(r)))
    want = gg.stencil_apply(hier[0][0], r.reshape(n, n)).reshape(-1)
    assert np.array_equal(np.asarray(A.matvec(r)), np.asarray(want))
    # the control: the plain hierarchy at this side does take the kernel
    plain = gg.build_hierarchy(128, 2)
    assert gg.grid_operator(plain).apply.fine_kernel
    assert gg.make_vcycle(plain).describe["fine_stencil_kernels"] == 2


def test_the_kernels_cycle_is_the_matrix_cycle(monkeypatch):
    """One V-cycle with level 0's residual and post-smoothing through the
    kernel against the same cycle through ``stencil_apply``."""
    monkeypatch.setattr(gg, "_KERNEL_PLATFORM", "cpu")
    hier = gg.build_hierarchy(256, 3)
    M = gg.make_vcycle(hier)
    assert M.apply.fine_kernel
    r = jnp.asarray(_edge_heavy(256, 4).reshape(-1))
    want = gg._Cycle(M.apply.static, "linear")(M.operands, r)
    got = M.matvec(r)
    assert float(jnp.abs(got - want).max()) <= 2e-6 * float(jnp.abs(want).max())
    # a one-level "hierarchy" applies no stencil: nothing for the kernel
    assert not gg.make_vcycle(hier[:1]).apply.fine_kernel
