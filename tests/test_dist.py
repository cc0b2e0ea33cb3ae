"""Distributed-layer tests on the virtual 8-device CPU mesh.

Mirrors the reference's strategy (SURVEY §4): the same scipy-oracle
correctness checks, run under multi-shard resource shapes so the full
partitioning/halo/collective machinery is exercised (the CI-configs analog of
.github/workflows/ci.yml:73-80).
"""

import importlib.util
import os

import numpy as np
import pytest
import scipy.sparse as sp

import sparse_tpu
from sparse_tpu.parallel.dist import dist_cg, make_dist_cg, shard_csr
from sparse_tpu.parallel.mesh import get_mesh
from sparse_tpu.telemetry import _metrics

from .utils.sample import sample_csr


def laplacian_1d(n, dtype=np.float64):
    return sp.diags(
        [-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n), format="csr"
    ).astype(dtype)


def laplacian_2d(n, dtype=np.float64):
    l1 = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    eye = sp.identity(n)
    return (sp.kron(l1, eye) + sp.kron(eye, l1)).tocsr().astype(dtype)


MESH_SIZES = [1, 2, 3, 8]


@pytest.mark.parametrize("num_shards", MESH_SIZES)
@pytest.mark.parametrize("balanced", [False, True])
def test_dist_spmv_banded(num_shards, balanced):
    s = laplacian_1d(101)
    A = sparse_tpu.csr_array(s)
    mesh = get_mesh(num_shards)
    D = shard_csr(A, mesh=mesh, balanced=balanced)
    assert D.mode == "halo"
    x = np.random.default_rng(0).standard_normal(101)
    np.testing.assert_allclose(D.dot(x), s @ x, rtol=1e-12)


@pytest.mark.parametrize("num_shards", [2, 8])
@pytest.mark.parametrize("layout", ["ell", "csr"])
def test_dist_spmv_random(num_shards, layout):
    s = sample_csr(73, 61, density=0.15, seed=3, dtype=np.float64)
    A = sparse_tpu.csr_array(s)
    D = shard_csr(A, mesh=get_mesh(num_shards), layout=layout)
    x = np.random.default_rng(1).standard_normal(61)
    np.testing.assert_allclose(D.dot(x), s @ x, rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("num_shards", [2, 8])
def test_dist_spmv_gather_fallback(num_shards):
    # a dense-ish matrix whose windows span everything -> all_gather mode
    rng = np.random.default_rng(7)
    d = rng.standard_normal((40, 40))
    d[np.abs(d) < 0.5] = 0.0
    s = sp.csr_matrix(d)
    A = sparse_tpu.csr_array(s)
    D = shard_csr(A, mesh=get_mesh(num_shards), halo_max_ratio=0.25)
    assert D.mode == "gather"
    x = rng.standard_normal(40)
    np.testing.assert_allclose(D.dot(x), s @ x, rtol=1e-10, atol=1e-12)


def test_dist_spmv_more_shards_than_rows():
    # the "more shards than rows" edge the reference defends (coo.py:283-290)
    s = laplacian_1d(5)
    A = sparse_tpu.csr_array(s)
    D = shard_csr(A, mesh=get_mesh(8))
    x = np.arange(5.0)
    np.testing.assert_allclose(D.dot(x), s @ x, rtol=1e-12)


@pytest.mark.parametrize("num_shards", [1, 8])
def test_dist_cg_poisson(num_shards):
    s = laplacian_2d(12)  # 144x144, SPD
    A = sparse_tpu.csr_array(s)
    D = shard_csr(A, mesh=get_mesh(num_shards))
    rng = np.random.default_rng(0)
    xtrue = rng.standard_normal(s.shape[0])
    b = s @ xtrue
    xp, iters, converged = dist_cg(D, b, tol=1e-8, maxiter=2000)
    x = D.unpad_vector(xp)
    np.testing.assert_allclose(x, xtrue, rtol=1e-6, atol=1e-7)
    assert iters < 2000
    assert converged


def test_dist_matches_single_chip():
    s = laplacian_2d(8)
    A = sparse_tpu.csr_array(s)
    D = shard_csr(A, mesh=get_mesh(8))
    x = np.random.default_rng(4).standard_normal(s.shape[0])
    np.testing.assert_allclose(D.dot(x), np.asarray(A @ x), rtol=1e-12)


def test_precise_windows_asymmetric_halo(monkeypatch):
    """settings.precise_windows keeps left/right halos separate: an upper
    bidiagonal matrix needs no left halo (LEGATE_SPARSE_PRECISE_IMAGES
    analog, partition.py:152-160)."""
    import scipy.sparse as sp

    from sparse_tpu.config import settings

    n = 64
    s = sp.diags([np.full(n, 2.0), np.full(n - 1, -1.0)], [0, 1], format="csr")
    x = np.random.default_rng(3).standard_normal(n)
    monkeypatch.setattr(settings, "precise_windows", True)
    D = shard_csr(sparse_tpu.csr_array(s), mesh=get_mesh(8), balanced=False)
    assert D.HL == 0 and D.HR >= 1
    np.testing.assert_allclose(D.dot(x), s @ x, rtol=1e-12)
    monkeypatch.setattr(settings, "precise_windows", False)
    D2 = shard_csr(sparse_tpu.csr_array(s), mesh=get_mesh(8), balanced=False)
    assert D2.HL == D2.HR
    np.testing.assert_allclose(D2.dot(x), s @ x, rtol=1e-12)


def test_force_serial_sort(monkeypatch):
    """settings.force_serial pins the distributed sort to one shard
    (reference coo.py:242)."""
    from sparse_tpu.config import settings
    from sparse_tpu.parallel.sort import dist_sort_host

    monkeypatch.setattr(settings, "force_serial", True)
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 50, size=101)
    payload = rng.standard_normal(101)
    sk, (spay,) = dist_sort_host(keys, (payload,))
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(sk, keys[order])
    np.testing.assert_allclose(spay, payload[order])


# ---------------------------------------------------------------------------
# the banded ('dia') layout: what shard_csr(layout="auto") takes for a banded
# operator, and the compiled CG kept on the layout
# ---------------------------------------------------------------------------
def _pde_5pt():
    """The benchmark's PDE operator and plain reference (it imports nothing
    of the program), by file path: benchmark/ is no package."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "operators", "pde_5pt.py")
    spec = importlib.util.spec_from_file_location("bench_pde_5pt", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pde_system(grid, seed, iterations=40, dtype=np.float64):
    pde = _pde_5pt()
    d = pde.make({"grid": grid, "iterations": iterations}, seed)
    N = d["rows"]
    s = sp.diags(d["diagonals"], d["offsets"], shape=(N, N)).tocsr()
    return pde, d, s.astype(dtype)


def _blocks_equal(D1, D2):
    b1, b2 = D1._blocks(), D2._blocks()
    return len(b1) == len(b2) and all(
        a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(b1, b2))


@pytest.mark.parametrize("num_shards", [1, 2, 4, 8])
@pytest.mark.parametrize("balanced", [False, True])
def test_dist_dia_matches_ell_csr_scipy(num_shards, balanced):
    """auto takes 'dia' over 'halo' for the PDE operator on every mesh size,
    with even (equal) and uneven (nnz-balanced) row blocks, and its SpMV,
    SpMM and dense x sparse product equal the other layouts' and scipy's."""
    _, _, s = _pde_system(24, seed=5)
    A = sparse_tpu.csr_array(s)
    mesh = get_mesh(num_shards)
    D = shard_csr(A, mesh=mesh, balanced=balanced)
    assert (D.layout, D.mode) == ("dia", "halo")
    assert D.dia_offsets == (-24, -1, 0, 1, 24)
    assert D.HL == D.HR == (24 if num_shards > 1 else 0)
    uneven = len(set(np.diff(D.row_splits))) > 1
    assert uneven == (balanced and num_shards > 2)
    rng = np.random.default_rng(2)
    x = rng.standard_normal(s.shape[0])
    B = rng.standard_normal((s.shape[0], 3))
    L = rng.standard_normal((2, s.shape[0]))
    np.testing.assert_allclose(D.dot(x), s @ x, rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(D.dot(B), s @ B, rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(D.rdot(L), L @ s, rtol=1e-12, atol=1e-9)
    for other in ("ell", "csr"):
        Do = shard_csr(A, mesh=mesh, balanced=balanced, layout=other)
        assert Do.layout == other
        np.testing.assert_array_equal(Do.row_splits, D.row_splits)
        np.testing.assert_allclose(D.dot(x), Do.dot(x), rtol=1e-12, atol=1e-9)
        np.testing.assert_allclose(D.dot(B), Do.dot(B), rtol=1e-12, atol=1e-9)


def _wide_band(n=64, reach=20):
    return sp.diags([np.full(n - reach, -1.0), np.full(n, 4.0),
                     np.full(n - reach, -1.0)], [-reach, 0, reach],
                    format="csr")


@pytest.mark.parametrize("case", ["general", "skewed", "rectangular",
                                  "wide_band", "more_shards_than_rows"])
def test_auto_layout_of_what_is_not_banded_is_the_parents(case):
    """Whatever the 'dia' layout cannot hold lays out as before it existed:
    the same choice between 'ell' and 'csr', the same halo, the same blocks
    bit for bit (the explicit layouts never enter the banded code)."""
    num_shards = 8
    if case == "general":
        s = sample_csr(73, 73, density=0.15, seed=3, dtype=np.float64)
        expect = "ell"
    elif case == "skewed":  # one dense row: ELL would pad every row to it
        s = sample_csr(80, 80, density=0.03, seed=4, dtype=np.float64).tolil()
        s[7, :] = 1.0
        s = s.tocsr()
        expect = "csr"
    elif case == "rectangular":  # banded, but rows and columns split apart
        s = sp.diags([np.ones(60), np.ones(60)], [0, 1], shape=(60, 61),
                     format="csr")
        expect = "ell"
    elif case == "wide_band":  # the band passes a neighbour's 8 rows
        s = _wide_band()
        expect = "ell"
    else:
        s = laplacian_1d(5)
        expect = "ell"
    A = sparse_tpu.csr_array(s)
    mesh = get_mesh(num_shards)
    D = shard_csr(A, mesh=mesh)
    assert D.layout == expect and D.dia_planes is None
    De = shard_csr(A, mesh=mesh, layout=expect)
    assert (D.mode, D.R, D.C, D.HL, D.HR) == (De.mode, De.R, De.C, De.HL, De.HR)
    assert _blocks_equal(D, De)
    x = np.random.default_rng(1).standard_normal(s.shape[1])
    np.testing.assert_allclose(D.dot(x), s @ x, rtol=1e-10, atol=1e-12)
    with pytest.raises(ValueError, match="layout='dia' cannot hold"):
        shard_csr(A, mesh=mesh, layout="dia")


def test_dist_cg_dia_ties_the_shards_to_the_whole():
    """Four shards, the PDE operator at grid 96, 40 iterations from zero:
    the sharded iterate, one-device linalg.cg and the plain reference
    (textbook CG on grid slices, benchmark/operators/pde_5pt.py) agree.
    Tolerances: dist_cg and linalg.cg are float64 here and differ only in
    the order of their sums (four partial dot products and a psum against
    one sum), which 40 CG iterations on this operator amplify: read 2.1e-14;
    1e-11 is 500 times that and five digits below what a float32 step
    anywhere would show. The reference computes in float32, so the gap to
    it is its own rounding: read 8.2e-7, limit 2e-5 (the cell's limit on
    the chip is 1e-3 after 300 iterations)."""
    import jax.numpy as jnp

    pde, d, s = _pde_system(96, seed=11)
    b = d["b"].astype(np.float64)
    D = shard_csr(sparse_tpu.csr_array(s), mesh=get_mesh(4))
    assert (D.layout, D.mode) == ("dia", "halo")
    assert len(set(np.diff(D.row_splits))) > 1  # nnz-balanced: uneven blocks
    xp, iters, converged = dist_cg(D, b, tol=0.0, maxiter=40)
    assert iters == 40 and not converged
    assert len(xp.sharding.device_set) == 4
    x = D.unpad_vector(xp)
    x_one, it_one = sparse_tpu.linalg.cg(
        sparse_tpu.csr_array(s), jnp.asarray(b), tol=0.0, maxiter=40)
    assert int(it_one) == 40
    scale = np.linalg.norm(x)
    assert np.linalg.norm(x - np.asarray(x_one)) / scale < 1e-11
    x_ref = pde.reference_cg(d["b"], 96, 40)
    assert np.linalg.norm(x - x_ref) / scale < 2e-5


def test_dist_cg_keeps_its_program_on_the_layout():
    """The second dist_cg call on one DistCSR neither traces nor compiles,
    whatever its tolerances; make_dist_cg shares the kept program; another
    maxiter is another program. The program is named after the layout."""
    import jax.numpy as jnp

    _, d, s = _pde_system(16, seed=3)
    b = d["b"].astype(np.float64)
    D = shard_csr(sparse_tpu.csr_array(s), mesh=get_mesh(4))
    traces = _metrics.counter("dist.cg.traces")
    t0 = traces.value
    x1, it1, _ = dist_cg(D, b, tol=0.0, maxiter=30)
    assert traces.value == t0 + 1
    x2, it2, _ = dist_cg(D, b, tol=0.0, maxiter=30)
    x3, it3, conv3 = dist_cg(D, b, tol=1e-2, atol=1e-30, maxiter=30)
    run = make_dist_cg(D, tol=0.0, maxiter=30)
    bp = D.pad_out_vector(b)
    x4, it4, _ = run(bp, jnp.zeros_like(bp))
    assert traces.value == t0 + 1
    assert it1 == it2 == int(it4) == 30
    assert conv3 and it3 < 30  # the traced tolerance is honoured
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x4))
    (fn, _m), = D._cg_fns.values()
    assert fn.__name__ == "dist_cg_dia"
    dist_cg(D, b, tol=0.0, maxiter=31)
    assert traces.value == t0 + 2 and len(D._cg_fns) == 2


@pytest.mark.parametrize("layout", ["dia", "ell"])
def test_dist_cg_leaves_the_layout_to_refcounting(layout):
    """The program dist_cg keeps on the DistCSR captures the compiled
    product, not the DistCSR: the layout (and its device planes) is freed
    when its last reference goes, with the cyclic collector off, after a
    solve as after a product alone."""
    import gc
    import weakref

    _, d, s = _pde_system(16, seed=5)
    b = d["b"].astype(np.float64)
    gc.collect()
    gc.disable()
    try:
        D = shard_csr(sparse_tpu.csr_array(s), mesh=get_mesh(4), layout=layout)
        dist_cg(D, b, tol=0.0, maxiter=5)
        gone = weakref.ref(D)
        del D
        assert gone() is None
    finally:
        gc.enable()
