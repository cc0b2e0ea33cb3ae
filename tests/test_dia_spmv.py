"""DIA SpMV kernels (XLA + Pallas-interpret) and the CSR banded fast path."""

import numpy as np
import pytest
import scipy.sparse as sp

import sparse_tpu
from sparse_tpu.config import settings
from sparse_tpu.ops.dia_spmv import dia_spmv_xla

CASES = [
    (50, 50, [-5, -1, 0, 1, 5]),
    (40, 60, [-3, 0, 2, 10]),
    (60, 40, [-7, 0, 1]),
    (7, 7, [0]),
    (300, 300, [-17, -1, 0, 1, 17]),
]


@pytest.mark.parametrize("m,n,offs", CASES)
def test_dia_spmv_xla(m, n, offs):
    rng = np.random.default_rng(m + n)
    data = rng.standard_normal((len(offs), n))
    s = sp.dia_matrix((data, offs), shape=(m, n))
    x = rng.standard_normal(n)
    got = np.asarray(dia_spmv_xla(data, tuple(offs), x, (m, n)))
    np.testing.assert_allclose(got, s @ x, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("m,n,offs", CASES)
def test_dia_spmv_packed_interpret(m, n, offs):
    from sparse_tpu.kernels.dia_spmv import dia_spmv_pallas_v2

    rng = np.random.default_rng(m * 3 + n)
    data = rng.standard_normal((len(offs), n)).astype(np.float32)
    s = sp.dia_matrix((data, offs), shape=(m, n))
    x = rng.standard_normal(n).astype(np.float32)
    got = np.asarray(
        dia_spmv_pallas_v2(data, tuple(offs), x, (m, n), tile=1024, interpret=True)
    )
    np.testing.assert_allclose(got, s @ x, rtol=1e-5, atol=1e-5)


def test_dia_packed_multi_tile_interpret():
    from sparse_tpu.kernels.dia_spmv import dia_spmv_pallas_v2

    m = 2500  # three 1024-tiles with a ragged tail
    offs = (-70, -1, 0, 1, 70)
    rng = np.random.default_rng(7)
    data = rng.standard_normal((len(offs), m)).astype(np.float32)
    s = sp.dia_matrix((data, offs), shape=(m, m))
    x = rng.standard_normal(m).astype(np.float32)
    got = np.asarray(
        dia_spmv_pallas_v2(data, offs, x, (m, m), tile=1024, interpret=True)
    )
    np.testing.assert_allclose(got, s @ x, rtol=1e-4, atol=1e-4)


def test_dia_packed_wide_matrix_interpret():
    # n >> m_pad + B: packing must truncate, not let update-slice clamp
    from sparse_tpu.kernels.dia_spmv import dia_spmv_pallas_v2

    m, n, offs = 100, 2000, (0, 5)
    rng = np.random.default_rng(11)
    data = rng.standard_normal((2, n)).astype(np.float32)
    s = sp.dia_matrix((data, offs), shape=(m, n))
    x = rng.standard_normal(n).astype(np.float32)
    got = np.asarray(
        dia_spmv_pallas_v2(data, offs, x, (m, n), tile=1024, interpret=True)
    )
    np.testing.assert_allclose(got, s @ x, rtol=1e-5, atol=1e-5)


def test_dia_array_dot_uses_dia_path():
    offs = [-2, 0, 3]
    data = np.random.default_rng(0).standard_normal((3, 30))
    s = sp.dia_matrix((data, offs), shape=(30, 30))
    A = sparse_tpu.dia_array((data, offs), shape=(30, 30))
    x = np.random.default_rng(1).standard_normal(30)
    np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-12)


def test_csr_banded_autodetect():
    s = sp.diags([1.0, -2.0, 1.0], [-1, 0, 1], shape=(64, 64), format="csr")
    A = sparse_tpu.csr_array(s)
    assert A._maybe_dia() is not None  # detected as banded
    x = np.random.default_rng(2).standard_normal(64)
    np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-12)


def test_csr_unbanded_rejects_dia():
    from .utils.sample import sample_csr

    s = sample_csr(80, 80, density=0.3, seed=1)
    A = sparse_tpu.csr_array(s)
    assert A._maybe_dia() is None  # ~everything is a distinct diagonal
    x = np.random.default_rng(3).standard_normal(80)
    np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-10)


def test_with_data_invalidates_dia_cache():
    s = sp.diags([1.0, -2.0, 1.0], [-1, 0, 1], shape=(32, 32), format="csr")
    A = sparse_tpu.csr_array(s)
    _ = A._maybe_dia()
    B = A * 2.0
    x = np.random.default_rng(4).standard_normal(32)
    np.testing.assert_allclose(np.asarray(B @ x), 2.0 * (s @ x), rtol=1e-12)


def test_dia_transpose_nonsquare_dot():
    # transpose leaves wider data planes; must fall back to CSR, not crash
    A = sparse_tpu.dia_array((np.ones((1, 60)), [0]), shape=(40, 60))
    At = A.T
    got = np.asarray(At @ np.ones(40))
    want = sp.dia_matrix((np.ones((1, 60)), [0]), shape=(40, 60)).T @ np.ones(40)
    np.testing.assert_allclose(got, want)


def test_spmv_mode_ell_overrides_dia():
    s = sp.diags([1.0, -2.0, 1.0], [-1, 0, 1], shape=(32, 32), format="csr")
    A = sparse_tpu.csr_array(s)
    x = np.random.default_rng(5).standard_normal(32)
    old = settings.spmv_mode
    try:
        settings.spmv_mode = "ell"
        np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-12)
        settings.spmv_mode = "segment"
        np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-12)
        settings.spmv_mode = "auto"
        np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-12)
    finally:
        settings.spmv_mode = old


def test_csr_duplicate_entries_dia_path_sums():
    # non-canonical CSR with duplicate (i, j): banded fast path must sum
    indptr = np.array([0, 2, 3])
    indices = np.array([0, 0, 1])
    data = np.array([1.0, 2.0, 5.0])
    A = sparse_tpu.csr_array.from_parts(data, indices, indptr, (2, 2))
    assert A._maybe_dia() is not None
    got = np.asarray(A @ np.array([1.0, 1.0]))
    np.testing.assert_allclose(got, [3.0, 5.0])


def test_spmv_mode_pallas_prepared_cache():
    """spmv_mode='pallas' routes through the cached PreparedDia operator
    (interpret mode off-TPU) for both dia_array and banded csr_array."""
    offs = [-2, 0, 3]
    rng = np.random.default_rng(21)
    data = rng.standard_normal((3, 40)).astype(np.float32)
    s = sp.dia_matrix((data, offs), shape=(40, 40))
    x = rng.standard_normal(40).astype(np.float32)
    old = settings.spmv_mode
    try:
        settings.spmv_mode = "pallas"
        A = sparse_tpu.dia_array((data, offs), shape=(40, 40))
        np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-4, atol=1e-5)
        # PreparedDia now lives in the library-wide plan cache (weak-ref
        # keyed under the legacy attr name), not as an object attribute
        from sparse_tpu import plan_cache

        assert plan_cache.lookup(A, "_prepared") is not None
        np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-4, atol=1e-5)
        C = sparse_tpu.csr_array(s.tocsr())
        np.testing.assert_allclose(np.asarray(C @ x), s @ x, rtol=1e-4, atol=1e-5)
        assert plan_cache.lookup(C, "_dia_prepared") is not None
        # mutation produces a fresh object -> fresh cache
        C2 = C * 2.0
        np.testing.assert_allclose(np.asarray(C2 @ x), 2 * (s @ x), rtol=1e-4, atol=1e-5)
    finally:
        settings.spmv_mode = old


def test_spmv_chain_matches_repeated_apply():
    """_spmv_chain (the autotuner/bench timing primitive) must be an HONEST
    dependency chain: k compiled iterations == k explicit SpMV+update steps."""
    import jax.numpy as jnp

    from sparse_tpu.kernels.dia_spmv import (
        _spmv_chain, dia_pack, dia_pad_x, dia_plan, dia_spmv_packed,
    )

    offs = (-2, 0, 1)
    m = 40
    rng = np.random.default_rng(3)
    data = (0.1 * rng.standard_normal((3, m))).astype(np.float32)
    plan = dia_plan(offs, (m, m), tile=1024)
    pf = dia_pack(jnp.asarray(data), plan)
    xp0 = dia_pad_x(jnp.asarray(rng.standard_normal(m).astype(np.float32)), plan)
    got = np.asarray(_spmv_chain(pf, xp0, plan, 3, interpret=True))

    xp = xp0
    import jax

    for _ in range(3):
        y = dia_spmv_packed(pf, xp, plan, interpret=True)
        xp = jax.lax.dynamic_update_slice(xp, y.astype(xp.dtype), (plan.B,))
    np.testing.assert_allclose(got, np.asarray(xp), rtol=1e-5, atol=1e-6)


def test_autotune_off_tpu_returns_default_without_caching():
    from sparse_tpu.kernels import dia_spmv as K

    data = np.ones((3, 64), dtype=np.float32)
    K._TILE_CACHE.clear()
    tile, band = K.autotune_dia_tile(data, (-1, 0, 1), (64, 64))
    assert tile == 65536 and band == {}  # no probing off-TPU
    # the GATE result must not be memoized as if a probe ran (ADVICE r5):
    # flipping pallas_autotune on later in the session — or moving to a
    # TPU backend — must still probe this geometry
    assert ((-1, 0, 1), (64, 64), "float32") not in K._TILE_CACHE
    # PreparedDia with tile=None resolves through the same default off-TPU
    p = K.PreparedDia(data, (-1, 0, 1), (64, 64))
    assert p.plan.TM >= 1024


def test_autotune_probe_failure_returns_default_without_crash(monkeypatch):
    """On a backend where the chain/kernel cannot run, every candidate
    drops out of the race and the default tile comes back — no exception
    escapes (the contract of the one-attempt design)."""
    from sparse_tpu.kernels import dia_spmv as K

    K._TILE_CACHE.clear()
    # the retirement flag is process-global by design; isolate it so this
    # deliberately-failing probe can't leak host-clock-only behavior into
    # later tests
    monkeypatch.setattr(K, "_CHAIN_RETIRED", [False])
    monkeypatch.setattr(K.jax, "default_backend", lambda: "tpu")
    data = np.ones((3, 4096), dtype=np.float32)
    tile, band = K.autotune_dia_tile(
        data, (-1, 0, 1), (4096, 4096), chain=2, reps=1, budget_s=5
    )
    assert isinstance(tile, int) and tile in (16384, 32768, 65536, 131072)
    assert ((-1, 0, 1), (4096, 4096), "float32") in K._TILE_CACHE
