"""Fused two-pass CG (kernels/cg_dia.py) vs the plain step-loop oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparse_tpu.kernels.cg_dia import cg_dia_fused
from sparse_tpu.models.poisson import (
    cg_dia,
    laplacian_2d_dia,
    make_cg_step_dia,
)


def _five_point(n, fmt="dia"):
    import sparse_tpu

    a = np.full(n * n - 1, -1.0, np.float32)
    a[n - 1 :: n] = 0.0
    g = np.full(n * (n - 1), -1.0, np.float32)
    c = np.full(n * n, 4.0, np.float32)
    A = sparse_tpu.diags([g, a, c, a, g], [-n, -1, 0, 1, n], dtype=np.float32)
    return A.tocsr() if fmt == "csr" else A


@pytest.mark.parametrize("n,iters", [(16, 50), (40, 30)])
def test_cg_fused_matches_step_loop(n, iters):
    N = n * n
    planes, offsets = laplacian_2d_dia(n)
    b = jax.random.normal(jax.random.PRNGKey(0), (N,), dtype=jnp.float32)
    x0 = jnp.zeros((N,), jnp.float32)

    step = make_cg_step_dia(offsets, n, use_pallas=False)
    state = (planes, x0, b, jnp.zeros((N,), jnp.float32), jnp.zeros((), jnp.float32))
    x_ref = np.asarray(cg_dia(step, *state, iters=iters)[0])

    x_f, r_f, rho = cg_dia_fused(
        planes, offsets, b, x0, N, iters=iters, interpret=True
    )
    assert np.allclose(np.asarray(x_f), x_ref, atol=1e-4)
    assert float(rho) >= 0.0


def test_cg_fused_nonzero_x0():
    n = 16
    N = n * n
    planes, offsets = laplacian_2d_dia(n)
    key = jax.random.PRNGKey(1)
    b = jax.random.normal(key, (N,), dtype=jnp.float32)
    x0 = jax.random.normal(jax.random.PRNGKey(2), (N,), dtype=jnp.float32)

    step = make_cg_step_dia(offsets, n, use_pallas=False)
    from sparse_tpu.ops.dia_spmv import dia_spmv_xla

    r0 = b - dia_spmv_xla(planes, offsets, x0, (N, N))
    state = (planes, x0, r0, jnp.zeros((N,), jnp.float32), jnp.zeros((), jnp.float32))
    x_ref = np.asarray(cg_dia(step, *state, iters=40)[0])

    x_f = cg_dia_fused(planes, offsets, b, x0, N, iters=40, interpret=True)[0]
    assert np.allclose(np.asarray(x_f), x_ref, atol=1e-4)


def test_cg_fused_junk_dia_tail_slots():
    """scipy-ignored out-of-band DIA slots must not leak into the solve.

    Dense-random planes are a legal sp.dia_matrix input whose slots for
    nonexistent rows hold junk; the packing must mask them or padded rows
    of q contaminate r/rho (regression: residual was ~1e5 before the
    row-mask in dia_pack).
    """
    import scipy.sparse as sp

    m, offsets = 600, (-1, 0, 1)
    rng = np.random.default_rng(3)
    off = rng.uniform(0.5, 1.0, m).astype(np.float32)  # A[j+1, j] = off[j]
    data = np.zeros((3, m), dtype=np.float32)
    data[0, :] = off                      # o=-1: data[0][j] = A[j+1, j]
    data[1, :] = 4.0
    data[2, 1:] = off[:-1]                # o=+1: data[2][j] = A[j-1, j] (symmetric)
    data[0, m - 1] = 1e6                  # scipy-ignored slots: junk
    data[2, 0] = -1e6
    A = sp.dia_matrix((data, offsets), shape=(m, m)).tocsr()
    b = rng.standard_normal(m).astype(np.float32)

    x = np.asarray(
        cg_dia_fused(jnp.asarray(data), offsets, jnp.asarray(b), None, m,
                     iters=80, tile=1024, interpret=True)[0]
    )
    assert np.linalg.norm(A @ x - b) < 1e-2


def test_cg_fused_multi_tile():
    """G > 1 exercises the double-buffered plane/window DMA machinery."""
    import scipy.sparse as sp

    m = 2500  # three 1024-tiles
    offsets = (-50, -1, 0, 1, 50)
    rng = np.random.default_rng(5)
    A = sp.diags(
        [np.full(m - 50, -1.0), np.full(m - 1, -1.0), np.full(m, 4.2),
         np.full(m - 1, -1.0), np.full(m - 50, -1.0)],
        offsets, shape=(m, m), format="dia",
    )
    data = A.data.astype(np.float32)
    b = rng.standard_normal(m).astype(np.float32)
    x = np.asarray(
        cg_dia_fused(jnp.asarray(data), offsets, jnp.asarray(b), None, m,
                     iters=120, tile=1024, interpret=True)[0]
    )
    assert np.linalg.norm(A.tocsr() @ x - b) < 1e-2


def test_cg_fused_bf16_planes_exact():
    """bf16 plane streaming with exactly-representable stencil values
    reproduces the f32 result bit-for-bit at the solver level.

    Geometry matters: TM must be a 2048 multiple or the alignment guard
    silently falls back to f32 and the test stops testing anything —
    n=48 (N=2304 -> TM=2048 at tile=2048) keeps the bf16 path live; the
    planes dtype reaching the kernel is asserted via the packing helper.
    """
    from sparse_tpu.kernels.dia_spmv import plane_stream_dtype

    n = 48
    N = n * n
    planes, offsets = laplacian_2d_dia(n)
    assert bool(jnp.all(planes == planes.astype(jnp.bfloat16).astype(planes.dtype)))
    # the guard must RESOLVE to bf16 for this geometry (TM=2048)
    assert plane_stream_dtype(jnp.bfloat16, jnp.float32, 2048) == jnp.dtype(jnp.bfloat16)
    b = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (N,), jnp.float32))
    x32 = cg_dia_fused(planes, offsets, jnp.asarray(b), None, N,
                       iters=100, tile=2048, interpret=True)[0]
    xbf = cg_dia_fused(planes, offsets, jnp.asarray(b), None, N,
                       iters=100, tile=2048, plane_dtype=jnp.bfloat16,
                       interpret=True)[0]
    np.testing.assert_allclose(np.asarray(x32), np.asarray(xbf), rtol=0, atol=0)


def test_plane_stream_dtype_alignment_guard():
    from sparse_tpu.kernels.dia_spmv import plane_stream_dtype

    f32 = jnp.dtype(jnp.float32)
    assert plane_stream_dtype(None, jnp.float32, 1024) == f32
    assert plane_stream_dtype(jnp.bfloat16, jnp.float32, 1024) == f32  # odd-1024
    assert plane_stream_dtype(jnp.bfloat16, jnp.float32, 4096) == jnp.dtype(jnp.bfloat16)


def test_linalg_cg_fused_fast_path_matches_loop():
    """linalg.cg's fused fast path (forced into interpret mode off-TPU)
    must produce the same solution and iteration count as the plain
    device loop — identical iterates, same absolute-||r|| stopping rule."""
    from sparse_tpu import linalg
    from sparse_tpu.config import settings

    n = 24
    A = _five_point(n)
    b = np.random.default_rng(0).random(n * n).astype(np.float32)

    old = settings.fused_cg
    try:
        settings.fused_cg = False
        x_loop, it_loop = linalg.cg(A, b, tol=1e-4, maxiter=400)
        settings.fused_cg = "force"
        x_fused, it_fused = linalg.cg(A, b, tol=1e-4, maxiter=400)
    finally:
        settings.fused_cg = old
    assert it_fused == it_loop
    np.testing.assert_allclose(
        np.asarray(x_fused), np.asarray(x_loop), rtol=2e-4, atol=2e-4
    )
    # and the answer actually solves the system
    resid = np.linalg.norm(np.asarray(A @ x_fused) - b)
    assert resid < 1e-3


def test_linalg_cg_fused_respects_x0_and_maxiter():
    from sparse_tpu import linalg
    from sparse_tpu.config import settings

    n = 16
    A = _five_point(n)
    rng = np.random.default_rng(1)
    xtrue = rng.random(n * n).astype(np.float32)
    b = np.asarray(A @ xtrue)
    old = settings.fused_cg
    try:
        settings.fused_cg = "force"
        # warm start very close to the solution: should converge immediately
        x, iters = linalg.cg(
            A, b, x0=xtrue + 1e-6, tol=1e-3, maxiter=400, conv_test_iters=5
        )
        assert iters <= 5
        # maxiter cap respected
        x2, iters2 = linalg.cg(A, b, tol=1e-30, maxiter=7)
        assert iters2 == 7
    finally:
        settings.fused_cg = old


# ---------------------------------------------------------------------------
# PR 30: the planes are packed once an operator, a solve's chunks thread the
# padded state alone. Same kernels, same values, same recurrence: the
# iterates are the ones the tree before it gave, bit for bit.
# ---------------------------------------------------------------------------
def _rhs(n, seed):
    return np.random.default_rng(seed).random(n * n).astype(np.float32)


def _sha(x):
    import hashlib

    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


@pytest.fixture
def forced(monkeypatch):
    """The fused path off a TPU (interpret mode), at the smallest tile
    ``_try_fused_cg`` takes, so that 130^2 rows are two tiles."""
    from sparse_tpu.config import settings

    monkeypatch.setattr(settings, "fused_cg", "force")
    monkeypatch.setattr(settings, "fused_cg_tile", 16384)
    return settings


@pytest.fixture
def events(monkeypatch):
    from sparse_tpu import telemetry
    from sparse_tpu.config import settings

    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    yield lambda: [e for e in telemetry.events("span") if e["name"] == "cg.solve"]
    telemetry.reset()


# What the parent tree (afd4ab3, per-chunk pack inside the loop program) gave
# on this sandbox's CPU: iterations, float.hex(rho), sha256(x)[:16] of
# `_try_fused_cg`, and sha256(x) of the plain device loop on the same system.
# The last is code this change does not touch: where it reads otherwise, the
# CPU compiles reductions in another order and the recorded bits say nothing.
_PARENT = {
    "one-tile-maxiter": (24, 0, 0.0, 50, 50, "0x1.29af340000000p-19",
                         "7b3c09d0b35fc803", "b87a6d6f1039b1c4"),
    "one-tile-tol": (24, 1, 1e-3, 400, 75, "0x1.9ac9840000000p-45",
                     "d72006b5e2162a4c", "b943dac639617a69"),
    "two-tiles-maxiter": (130, 2, 0.0, 50, 50, "0x1.0601760000000p+15",
                          "80b88df3a8dae3b0", "48a229d879923f02"),
    "two-tiles-tol": (130, 3, 3e-2, 400, 225, "0x1.11fe380000000p-12",
                      "85ff748608095500", "07afca8af6812e9f"),
}


@pytest.mark.parametrize("case", sorted(_PARENT))
def test_chunked_solve_is_one_long_run_bit_for_bit(case, forced):
    """25 + 24 + 1 (maxiter 50) and a tolerance exit at a chunk's end: the
    chunked solve's x, iteration count and rho are those of ONE
    ``cg_dia_fused`` call of as many iterations."""
    from sparse_tpu import linalg

    n, seed, tol, maxiter, iters_p = _PARENT[case][:5]
    A, b = _five_point(n), jnp.asarray(_rhs(n, seed))
    x, iters, rho, info = linalg._try_fused_cg(A, b, None, tol, maxiter, 25)
    assert iters == iters_p and info == (0 if tol else maxiter)
    x1, _r1, rho1 = cg_dia_fused(
        A.data, tuple(int(o) for o in A.offsets), b, None, n * n,
        iters=iters, tile=16384, interpret=True,
    )
    assert np.array_equal(np.asarray(x), np.asarray(x1))
    assert float(rho1) == rho


@pytest.mark.parametrize("case", sorted(_PARENT))
def test_chunked_solve_gives_the_parents_bits(case, forced):
    from sparse_tpu import linalg

    n, seed, tol, maxiter, iters_p, rho_p, x_p, loop_x_p = _PARENT[case]
    A, b = _five_point(n), _rhs(n, seed)
    forced.fused_cg = False
    x_loop, _ = linalg.cg(A, b, tol=tol, maxiter=maxiter)
    if _sha(x_loop) != loop_x_p:
        pytest.skip("this CPU is not the one the parent's bits were read on")
    forced.fused_cg = "force"
    x, iters, rho, _info = linalg._try_fused_cg(
        A, jnp.asarray(b), None, tol, maxiter, 25
    )
    assert (iters, float(rho).hex(), _sha(x)) == (iters_p, rho_p, x_p)


@pytest.mark.parametrize("fmt", ["csr", "dia"])
def test_an_operator_packs_once(fmt, forced, events):
    from sparse_tpu import linalg

    A, b = _five_point(24, fmt), _rhs(24, 4)
    x1, it1 = linalg.cg(A, b, tol=1e-4, maxiter=400)
    src = A._cg_pack[0]
    assert src is (A._dia[0] if fmt == "csr" else A.data)
    A @ b  # a product between two solves leaves the planes who they are
    x2, it2 = linalg.cg(A, b, tol=1e-4, maxiter=400)
    assert [e["packs"] for e in events()] == [1, 0]
    assert A._cg_pack[0] is src
    assert it1 == it2 and np.array_equal(np.asarray(x1), np.asarray(x2))


def test_an_equal_operator_packs_again(forced, events):
    from sparse_tpu import linalg

    b = _rhs(24, 5)
    for _ in range(2):  # equal values, another object: nothing is shared
        linalg.cg(_five_point(24), b, tol=1e-4, maxiter=400)
    assert [e["packs"] for e in events()] == [1, 1]


@pytest.mark.parametrize("change", ["data", "tile"])
def test_what_the_pack_depends_on_packs_again(change, forced, events):
    """A ``dia_array`` whose ``.data`` is replaced solves the NEW system;
    another tile is another plan."""
    from sparse_tpu import linalg

    A, b = _five_point(24), _rhs(24, 6)
    linalg.cg(A, b, tol=1e-4, maxiter=400)
    if change == "data":
        A.data = A.data * 2.0
    else:
        forced.fused_cg_tile = 32768
    x, _ = linalg.cg(A, b, tol=1e-4, maxiter=400)
    assert [e["packs"] for e in events()] == [1, 1]
    assert np.linalg.norm(np.asarray(A @ x) - b) < 1e-3
    if change == "data":
        half = linalg.cg(_five_point(24), b, tol=2e-4, maxiter=400)[0]
        np.testing.assert_allclose(np.asarray(x), np.asarray(half) / 2, atol=1e-4)


@pytest.mark.parametrize("maxiter", [37, 60])
@pytest.mark.parametrize("warm", [False, True])
def test_x0_and_ragged_maxiter_agree_with_the_device_loop(warm, maxiter, forced):
    """``x0`` given, ``maxiter`` not a multiple of the chunk: the iteration
    count and the iterate of ``_cg_device_loop``."""
    from sparse_tpu import linalg

    n = 24
    A, b = _five_point(n), _rhs(n, 7)
    x0 = _rhs(n, 8) if warm else None
    x_f, it_f = linalg.cg(A, b, x0=x0, tol=1e-12, maxiter=maxiter)
    forced.fused_cg = False
    x_l, it_l = linalg.cg(A, b, x0=x0, tol=1e-12, maxiter=maxiter)
    assert it_f == it_l == maxiter
    np.testing.assert_allclose(np.asarray(x_f), np.asarray(x_l), rtol=2e-4, atol=2e-4)
