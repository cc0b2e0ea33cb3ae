"""Regression tests for review findings (solver edge cases, layout caps)."""

import numpy as np
import pytest
import scipy.sparse as sp

import sparse_tpu
from sparse_tpu import linalg

from .utils.sample import sample_csr


def spd(n, seed=0):
    a = sample_csr(n, n, density=0.3, seed=seed)
    s = (a + a.T).toarray() + n * np.eye(n)
    return s


def test_lsqr_damp_identity():
    # min ||x - b||^2 + ||x||^2 has solution b/2
    A = sparse_tpu.identity(5)
    b = np.arange(1.0, 6.0)
    x, *_ = linalg.lsqr(A, b, damp=1.0)
    np.testing.assert_allclose(np.asarray(x), b / 2, rtol=1e-6)


def test_lsqr_damp_matches_scipy():
    s = sample_csr(20, 12, density=0.4, seed=5)
    b = np.random.default_rng(0).standard_normal(20)
    x_ref = sp.linalg.lsqr(s, b, damp=0.7, atol=1e-12, btol=1e-12)[0]
    x, *_ = linalg.lsqr(sparse_tpu.csr_array(s), b, damp=0.7, atol=1e-12, btol=1e-12)
    np.testing.assert_allclose(np.asarray(x), x_ref, rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("solver", [linalg.cg, linalg.bicg, linalg.bicgstab, linalg.cgs])
def test_zero_rhs_returns_zeros(solver):
    A = sparse_tpu.csr_array(spd(8))
    x, _ = solver(A, np.zeros(8), maxiter=100)
    assert np.all(np.isfinite(np.asarray(x)))
    np.testing.assert_allclose(np.asarray(x), 0.0)


def test_gmres_zero_rhs():
    A = sparse_tpu.csr_array(spd(8))
    x, iters = linalg.gmres(A, np.zeros(8))
    np.testing.assert_allclose(np.asarray(x), 0.0)
    assert np.all(np.isfinite(np.asarray(x)))


def test_gmres_complex():
    rng = np.random.default_rng(3)
    n = 12
    d = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    d = d + n * np.eye(n)  # well conditioned
    d[np.abs(d) < 0.8] = 0
    d += n * np.eye(n)
    A = sparse_tpu.csr_array(d)
    xtrue = rng.standard_normal(n) + 1j * rng.standard_normal(n)
    b = d @ xtrue
    x, _ = linalg.gmres(A, b, tol=1e-10, restart=n, maxiter=50)
    np.testing.assert_allclose(np.asarray(x), xtrue, rtol=1e-6, atol=1e-8)


def test_linear_operator_transpose_of_sparse():
    s = sample_csr(9, 7, density=0.4, seed=2)
    op = linalg.aslinearoperator(sparse_tpu.csr_array(s))
    x = np.random.default_rng(1).standard_normal(9)
    np.testing.assert_allclose(np.asarray(op.T.matvec(x)), s.T @ x, rtol=1e-12)


def test_linear_operator_transpose_complex():
    s = sample_csr(6, 5, density=0.5, seed=2, dtype=np.complex128)
    op = linalg.aslinearoperator(sparse_tpu.csr_array(s))
    x = np.random.default_rng(1).standard_normal(6)
    np.testing.assert_allclose(
        np.asarray(op.T.matvec(x)), s.T.toarray() @ x, rtol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(op.H.matvec(x)), s.conj().T.toarray() @ x, rtol=1e-12
    )


def test_wide_ell_spmv_fori_path():
    # force the ELL path on a matrix wider than ELL_UNROLL_MAX
    from sparse_tpu.config import settings
    from sparse_tpu.ops.spmv import ELL_UNROLL_MAX

    n = ELL_UNROLL_MAX + 17
    d = np.random.default_rng(0).standard_normal((8, n))
    A = sparse_tpu.csr_array(d)
    old = settings.spmv_mode
    settings.spmv_mode = "ell"
    try:
        x = np.random.default_rng(1).standard_normal(n)
        np.testing.assert_allclose(np.asarray(A @ x), d @ x, rtol=1e-10)
        B = np.random.default_rng(2).standard_normal((n, 4))
        np.testing.assert_allclose(np.asarray(A @ B), d @ B, rtol=1e-10)
    finally:
        settings.spmv_mode = old


def test_random_large_path_covers_high_rows():
    A = sparse_tpu.random(10000, 10000, density=1e-5, random_state=0)
    assert A.nnz == 1000
    # the fixed sampler must reach the top of the index space
    assert np.asarray(A.row).max() > 5000


def test_wide_dim_requires_x64_message():
    # fused m*n keys are gone everywhere (pair sorts); only a single
    # DIMENSION beyond int32 still needs x64 (kron of huge factors)
    import jax

    from sparse_tpu.ops.coords import require_x64_index

    assert not require_x64_index(60000)
    if jax.config.jax_enable_x64:
        assert require_x64_index(2**31 + 1)
    else:
        with pytest.raises(ValueError, match="x64"):
            require_x64_index(2**31 + 1)


# ---------------------------------------------------------------------------
# Big-shape (m*n > 2**31) paths must work WITHOUT x64: every single-device
# sort/dedup works on (row, col) pairs (ops.coords.lexsort_rc), so only a
# single dimension overflowing int32 ever requires int64 indices. This is
# what lets examples/gmg.py build 4500^2-grid hierarchies in pure int32.
# ---------------------------------------------------------------------------

BIG = 60_000  # BIG*BIG = 3.6e9 > 2**31


def _big_coo(seed=0, nnz=200):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, BIG, nnz)
    cols = rng.integers(0, BIG, nnz)
    vals = rng.random(nnz)
    return rows, cols, vals


def test_big_shape_coo_tocsr_matches_scipy():
    rows, cols, vals = _big_coo()
    ours = sparse_tpu.coo_array((vals, (rows, cols)), shape=(BIG, BIG)).tocsr()
    ref = sp.coo_matrix((vals, (rows, cols)), shape=(BIG, BIG)).tocsr()
    got = ours.tocoo()
    want = ref.tocoo()
    want.sum_duplicates()
    np.testing.assert_array_equal(np.asarray(got.row), want.row)
    np.testing.assert_array_equal(np.asarray(got.col), want.col)
    np.testing.assert_allclose(np.asarray(got.data), want.data, rtol=1e-12)


def test_big_shape_transpose_roundtrip():
    rows, cols, vals = _big_coo(seed=1)
    A = sparse_tpu.coo_array((vals, (rows, cols)), shape=(BIG, BIG)).tocsr()
    At = A.T.tocsr()  # CSR -> (zero-copy CSC) -> sort-based CSR
    ref = sp.coo_matrix((vals, (rows, cols)), shape=(BIG, BIG)).tocsr().T.tocsr()
    got = At.tocoo()
    want = ref.tocoo()
    want.sum_duplicates()
    np.testing.assert_array_equal(np.asarray(got.row), want.row)
    np.testing.assert_array_equal(np.asarray(got.col), want.col)
    np.testing.assert_allclose(np.asarray(got.data), want.data, rtol=1e-12)


def test_big_shape_add_and_mult_match_scipy():
    ra, ca, va = _big_coo(seed=2)
    rb, cb, vb = _big_coo(seed=3)
    # force some structural overlap so mult has nonempty intersection
    rb[:50], cb[:50] = ra[:50], ca[:50]
    A = sparse_tpu.coo_array((va, (ra, ca)), shape=(BIG, BIG)).tocsr()
    B = sparse_tpu.coo_array((vb, (rb, cb)), shape=(BIG, BIG)).tocsr()
    As = sp.coo_matrix((va, (ra, ca)), shape=(BIG, BIG)).tocsr()
    Bs = sp.coo_matrix((vb, (rb, cb)), shape=(BIG, BIG)).tocsr()
    for got, want in (((A + B), (As + Bs)), ((A * B), (As.multiply(Bs)))):
        g = got.tocoo()
        w = sp.coo_matrix(want)
        w.sum_duplicates()
        np.testing.assert_array_equal(np.asarray(g.row), w.row)
        np.testing.assert_array_equal(np.asarray(g.col), w.col)
        np.testing.assert_allclose(np.asarray(g.data), w.data, rtol=1e-12)


def test_big_shape_spgemm_matches_scipy():
    ra, ca, va = _big_coo(seed=4)
    rb, cb, vb = _big_coo(seed=5)
    rb[:100] = ca[:100]  # make A's columns hit B's rows
    A = sparse_tpu.coo_array((va, (ra, ca)), shape=(BIG, BIG)).tocsr()
    B = sparse_tpu.coo_array((vb, (rb, cb)), shape=(BIG, BIG)).tocsr()
    C = (A @ B).tocoo()
    Cs = (
        sp.coo_matrix((va, (ra, ca)), shape=(BIG, BIG)).tocsr()
        @ sp.coo_matrix((vb, (rb, cb)), shape=(BIG, BIG)).tocsr()
    ).tocoo()
    Cs.sum_duplicates()
    np.testing.assert_array_equal(np.asarray(C.row), Cs.row)
    np.testing.assert_array_equal(np.asarray(C.col), Cs.col)
    np.testing.assert_allclose(np.asarray(C.data), Cs.data, rtol=1e-10)


def test_big_shape_diags_spmv():
    # diags at a >2**31-key shape, then SpMV — the gmg.py WeightedJacobi path
    d = np.arange(BIG, dtype=np.float64) + 1.0
    D = sparse_tpu.diags([d], [0], shape=(BIG, BIG), format="csr")
    x = np.ones(BIG)
    y = np.asarray(D @ x)
    np.testing.assert_allclose(y, d, rtol=1e-12)


def test_big_shape_kron_small_factors():
    # kron whose OUTPUT shape crosses 2**31 keys but whose dims fit int32
    A = sp.random(300, 300, density=0.001, random_state=6, format="coo")
    B = sp.random(200, 200, density=0.001, random_state=7, format="coo")
    got = sparse_tpu.kron(
        sparse_tpu.coo_array((A.data, (A.row, A.col)), shape=A.shape),
        sparse_tpu.coo_array((B.data, (B.row, B.col)), shape=B.shape),
        format="csr",
    ).tocoo()
    want = sp.kron(A, B, format="csr").tocoo()
    want.sum_duplicates()
    np.testing.assert_array_equal(np.asarray(got.row), want.row)
    np.testing.assert_array_equal(np.asarray(got.col), want.col)
    np.testing.assert_allclose(np.asarray(got.data), want.data, rtol=1e-12)


def test_segment_searchsorted_pow2_segments():
    # regression: the binary-search trip count was one short for power-of-
    # two data lengths, returning lo below the true lower bound (dropped
    # intersection entries in A.multiply(B) with 2^k-nnz operands)
    import jax.numpy as jnp

    from sparse_tpu.ops.coords import segment_searchsorted

    rng = np.random.default_rng(0)
    for nb in [1, 2, 4, 8, 16, 32, 3, 7, 33]:
        vals = np.sort(rng.integers(0, 50, nb))
        starts = rng.integers(0, nb + 1, 64)
        ends = np.array([rng.integers(s, nb + 1) for s in starts])
        qs = rng.integers(-1, 51, 64)
        want = np.array(
            [s + np.searchsorted(vals[s:e], q) for s, e, q in zip(starts, ends, qs)]
        )
        got = np.asarray(
            segment_searchsorted(
                jnp.asarray(vals), jnp.asarray(starts), jnp.asarray(ends), jnp.asarray(qs)
            )
        )
        np.testing.assert_array_equal(got, want)


def test_mult_two_nnz_single_row():
    # the exact power-of-two scenario from the off-by-one: 1x2 operands
    A = sparse_tpu.coo_array(
        (np.array([1.0, 2.0]), (np.array([0, 0]), np.array([0, 1]))), shape=(1, 2)
    ).tocsr()
    B = sparse_tpu.coo_array(
        (np.array([3.0, 4.0]), (np.array([0, 0]), np.array([0, 1]))), shape=(1, 2)
    ).tocsr()
    got = np.asarray((A * B).todense())
    np.testing.assert_allclose(got, np.array([[3.0, 8.0]]))


def test_big_shape_paths_without_x64_subprocess():
    """The no-x64 contract the suite itself cannot test (conftest enables
    x64 globally): big-shape conversion + distributed conversion must work
    with jax_enable_x64 = False — int32 pair sorts end to end."""
    import os
    import subprocess
    import sys

    script = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
import jax
jax.config.update("jax_platforms", "cpu")
assert not jax.config.jax_enable_x64
import numpy as np, scipy.sparse as sp
import sparse_tpu
from sparse_tpu.parallel.sort import coo_to_csr_distributed

BIG = 60_000
rng = np.random.default_rng(0)
nnz = 200
rows = rng.integers(0, BIG, nnz)
cols = rng.integers(0, BIG, nnz)
rows[:30] = rows[30:60]; cols[:30] = cols[30:60]  # duplicates
vals = rng.integers(1, 100, nnz).astype(np.float32)  # f32-exact values

want = sp.coo_matrix((vals, (rows, cols)), shape=(BIG, BIG)).tocsr()
want.sum_duplicates()
w = want.tocoo()

for A in (
    sparse_tpu.coo_array((vals, (rows, cols)), shape=(BIG, BIG)).tocsr(),
    coo_to_csr_distributed(rows, cols, vals, (BIG, BIG), 8),
):
    got = A.tocoo()
    np.testing.assert_array_equal(np.asarray(got.row), w.row)
    np.testing.assert_array_equal(np.asarray(got.col), w.col)
    np.testing.assert_allclose(np.asarray(got.data), w.data)
print("NO_X64_OK")
"""
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NO_X64_OK" in proc.stdout


def test_layout_detection_inside_trace_falls_back_not_raises():
    """A csr first applied INSIDE a jit trace (multigrid transfer
    operators) must not host-sync in _maybe_dia/_maybe_ell — the
    resulting TracerArrayConversionError silently demoted CG to its
    host loop. The guard skips detection without
    poisoning the cache, so a later eager call still detects."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import scipy.sparse as sp

    import sparse_tpu as sparse

    S = sp.random(64, 64, 0.1, random_state=np.random.default_rng(0), format="csr")
    S.setdiag(3.0)
    A = sparse.csr_array(S)
    x = jnp.ones(64, dtype=jnp.float32)
    y = jax.jit(lambda v: A @ v)(x)  # must trace cleanly, no fallback
    np.testing.assert_allclose(np.asarray(y), S @ np.ones(64), rtol=1e-5)
    assert A._dia is False or A._dia is None  # cache not poisoned by the trace
    A @ np.ones(64)  # eager use afterwards still allowed to detect+cache


def test_cg_with_traceable_preconditioner_stays_on_device_loop(monkeypatch):
    """Preconditioned CG whose M is first seen inside the loop must run
    the compiled device loop (the eager warm call primes layout
    caches), not the host fallback."""
    import numpy as np
    import scipy.sparse as sp

    import sparse_tpu as sparse
    from sparse_tpu import linalg

    rng = np.random.default_rng(1)
    n = 128
    S = sp.diags([np.full(n - 1, -1.0), np.full(n, 2.0), np.full(n - 1, -1.0)],
                 [-1, 0, 1]).tocsr()
    A = sparse.csr_array(S)
    Mmat = sparse.csr_array(sp.diags([1.0 / S.diagonal()], [0]).tocsr())
    M = linalg.LinearOperator((n, n), matvec=lambda r: Mmat @ r, dtype=np.float64)
    b = rng.standard_normal(n)
    called = {"host": 0}
    orig = linalg._cg_host_loop
    monkeypatch.setattr(
        linalg, "_cg_host_loop",
        lambda *a, **k: called.__setitem__("host", called["host"] + 1) or orig(*a, **k),
    )
    x, iters = linalg.cg(A, b, tol=1e-6, maxiter=200, M=M)
    assert called["host"] == 0, "preconditioned CG fell back to the host loop"
    resid = np.linalg.norm(np.asarray(A @ x) - b)
    assert resid < 1e-4


def test_host_scope_and_commit_helpers():
    """host_scope keeps eager analysis on the CPU backend; on a CPU
    target commit_to_exec_device is an identity (no copies)."""
    import jax
    import jax.numpy as jnp

    from sparse_tpu.utils import commit_to_exec_device, host_scope, in_trace

    with host_scope():
        a = jnp.arange(8) * 2
    assert next(iter(a.sharding.device_set)).platform == "cpu"
    arrs = (jnp.arange(4), jnp.ones(3))
    out = commit_to_exec_device(arrs)
    assert out[0] is arrs[0] and out[1] is arrs[1]  # cpu target: no-op
    assert not in_trace()
    flags = []
    jax.jit(lambda x: (flags.append(in_trace()), x)[1])(1.0)
    assert flags == [True]
