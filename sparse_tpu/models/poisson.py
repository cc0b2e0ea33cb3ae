"""5-point Poisson/Laplacian workload — the PDE benchmark's compute core.

Reference analog: ``examples/pde.py`` builds the 2-D 5-point Laplacian with
``sparse.diags`` and solves it with ``linalg.cg`` (the BASELINE.md "PDE"
row: 6000^2 unknowns/GPU, 300 CG iterations). TPU-first redesign: the matrix
is *generated on device* directly in the padded-row (ELL) layout with pure
jnp ops — a 36M-row operator materializes in HBM in milliseconds with no host
round-trip — and the CG loop is one compiled ``lax.fori_loop``/``while_loop``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnums=(0,), static_argnames=("dtype",))
def laplacian_2d_ell(n: int, dtype=jnp.float32):
    """The n*n-point 2-D 5-point Laplacian as ELL planes ([N, 5] idx/val).

    Stencil per grid point (i, j): 4 on the diagonal, -1 to each in-grid
    neighbor. Out-of-grid slots point at column 0 with value 0.
    """
    N = n * n
    ids = jnp.arange(N, dtype=jnp.int32)
    i = ids // n
    j = ids % n
    # neighbor columns: W, S, center, N, E (sorted by column id)
    cols = jnp.stack([ids - n, ids - 1, ids, ids + 1, ids + n], axis=1)
    valid = jnp.stack(
        [i > 0, j > 0, jnp.ones_like(ids, dtype=bool), j < n - 1, i < n - 1],
        axis=1,
    )
    vals = jnp.where(
        valid,
        jnp.where(jnp.arange(5) == 2, jnp.asarray(4.0, dtype), jnp.asarray(-1.0, dtype)),
        jnp.asarray(0.0, dtype),
    )
    cols = jnp.where(valid, cols, 0).astype(jnp.int32)
    return cols, vals


def laplacian_2d_csr(n: int, dtype=np.float64):
    """Small-scale CSR construction via the library's own diags/kron path."""
    import sparse_tpu as st

    l1 = st.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n), dtype=dtype)
    eye = st.identity(n, dtype=dtype)
    return (st.kron(l1, eye) + st.kron(eye, l1)).tocsr()


def laplacian_2d_csr_host(n: int, dtype=np.float64):
    """Large-scale CSR construction fully on host (pure numpy assembly).

    Million-row layout-construction inputs (shard_csr timing, dryrun) need
    the matrix itself built in O(nnz) host time with no device round-trips;
    this assembles the 5-point stencil rows directly in CSR order.
    """
    import sparse_tpu as st

    N = n * n
    ids = np.arange(N, dtype=np.int64)
    i, j = ids // n, ids % n
    # per-row neighbor columns in sorted order: W(-n), S(-1), C, N(+1), E(+n)
    cols = np.stack([ids - n, ids - 1, ids, ids + 1, ids + n], axis=1)
    valid = np.stack(
        [i > 0, j > 0, np.ones(N, dtype=bool), j < n - 1, i < n - 1], axis=1
    )
    vals = np.where(np.arange(5) == 2, 4.0, -1.0).astype(dtype)
    vals = np.broadcast_to(vals, (N, 5))[valid]
    indices = cols[valid].astype(np.int64)
    indptr = np.zeros(N + 1, dtype=np.int64)
    np.cumsum(valid.sum(axis=1), out=indptr[1:])
    return st.csr_array.from_parts(vals, indices, indptr, (N, N))


from ..ops.spmv import csr_spmv_ell as _spmv_ell


def laplacian_2d_dia(n: int, dtype=jnp.float32):
    """The n*n 2-D 5-point Laplacian as DIA planes ([5, N] data).

    scipy DIA convention: data[k, j] = A[j - o_k, j], so the mask for
    offset o is "row j - o is a grid neighbor of column j". The diagonal
    layout makes SpMV zero-gather (ops.dia_spmv) — the flagship bench
    formulation. Returns (planes, offsets) with offsets a static tuple.
    """
    return _laplacian_2d_dia_planes(n, dtype=dtype), (-n, -1, 0, 1, n)


@partial(jax.jit, static_argnums=(0,), static_argnames=("dtype",))
def _laplacian_2d_dia_planes(n: int, dtype=jnp.float32):
    N = n * n
    j = jnp.arange(N, dtype=jnp.int32)
    col_in_row = j % n
    neg = jnp.asarray(-1.0, dtype)
    zero = jnp.asarray(0.0, dtype)
    planes = jnp.stack(
        [
            jnp.where(j + n < N, neg, zero),  # o=-n: vertical edge (j+n, j)
            jnp.where(col_in_row < n - 1, neg, zero),  # o=-1: edge (j+1, j)
            jnp.full((N,), 4.0, dtype),  # o=0
            jnp.where(col_in_row > 0, neg, zero),  # o=+1: edge (j-1, j)
            jnp.where(j - n >= 0, neg, zero),  # o=+n: edge (j-n, j)
        ]
    )
    return planes


def cg_step_ell(ell_idx, ell_val, x, r, p, rho):
    """One CG iteration on an ELL matrix — the flagship jittable step.

    The AXPBY fusion of the reference (linalg.py:479-496) is implicit: under
    jit XLA fuses every elementwise update into the SpMV epilogue.
    """
    rho_new = jnp.vdot(r, r)
    beta = rho_new / jnp.where(rho == 0, 1, rho)
    p = jnp.where(rho == 0, r, r + beta * p)
    q = _spmv_ell(ell_idx, ell_val, p)
    alpha = rho_new / jnp.vdot(p, q)
    x = x + alpha * p
    r = r - alpha * q
    return x, r, p, rho_new


def poisson_cg_state(n: int, dtype=jnp.float32, seed: int = 0):
    """Build (ell_idx, ell_val, x0, r0, p0, rho0) for an n*n Poisson solve."""
    ell_idx, ell_val = laplacian_2d_ell(n, dtype=dtype)
    N = n * n
    key = jax.random.PRNGKey(seed)
    xtrue = jax.random.normal(key, (N,), dtype=dtype)
    b = _spmv_ell(ell_idx, ell_val, xtrue)
    x0 = jnp.zeros((N,), dtype=dtype)
    r0 = b  # r = b - A @ 0
    p0 = jnp.zeros((N,), dtype=dtype)
    rho0 = jnp.zeros((), dtype=dtype)
    return ell_idx, ell_val, x0, r0, p0, rho0


@partial(jax.jit, static_argnames=("iters",))
def cg_ell(ell_idx, ell_val, x, r, p, rho, iters: int = 300):
    """Fixed-iteration CG (throughput mode, like `pde.py -throughput`)."""

    def body(_, state):
        return cg_step_ell(ell_idx, ell_val, *state)

    return jax.lax.fori_loop(0, iters, body, (x, r, p, rho))


# ---------------------------------------------------------------------------
# DIA (zero-gather) flagship variant — see ops.dia_spmv
# ---------------------------------------------------------------------------
def make_cg_step_dia(offsets: tuple, n: int, use_pallas: bool | None = None):
    """One CG iteration with the diagonal-layout SpMV; offsets are static
    structure, closed over so the returned fn is jittable on arrays alone.

    On TPU the SpMV is the packed Pallas VMEM-windowed kernel (not measured
    against the XLA formulation on the current chip); elsewhere the XLA
    zero-gather path. The step takes scipy-layout planes, so it packs them
    on every call, and the chip's compiler keeps that pack inside the body
    of ``cg_dia``'s ``fori_loop`` (compiled for a described v5e, PR 29): a
    solver that iterates packs once (``PreparedDia``; ``linalg.cg``'s
    fused kernel does).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from ..kernels.dia_spmv import dia_spmv_pallas_v2 as _spmv_dia
    else:
        from ..ops.dia_spmv import dia_spmv_xla as _spmv_dia

    N = n * n

    def cg_step_dia(planes, x, r, p, rho):
        rho_new = jnp.vdot(r, r)
        beta = rho_new / jnp.where(rho == 0, 1, rho)
        p = jnp.where(rho == 0, r, r + beta * p)
        q = _spmv_dia(planes, offsets, p, (N, N))
        alpha = rho_new / jnp.vdot(p, q)
        return x + alpha * p, r - alpha * q, p, rho_new

    return cg_step_dia


def poisson_cg_state_dia(n: int, dtype=jnp.float32, seed: int = 0):
    """(planes, x0, r0, p0, rho0) + the step fn for an n*n Poisson solve."""
    from ..ops.dia_spmv import dia_spmv_xla

    planes, offsets = laplacian_2d_dia(n, dtype=dtype)
    N = n * n
    key = jax.random.PRNGKey(seed)
    xtrue = jax.random.normal(key, (N,), dtype=dtype)
    b = dia_spmv_xla(planes, offsets, xtrue, (N, N))
    x0 = jnp.zeros((N,), dtype=dtype)
    state = (planes, x0, b, jnp.zeros((N,), dtype=dtype), jnp.zeros((), dtype=dtype))
    return state, make_cg_step_dia(offsets, n)


_cg_dia_compiled = {}


def cg_dia(step_fn, planes, x, r, p, rho, iters: int = 300):
    """Fixed-iteration DIA-CG, one compiled loop.

    The jitted runner is cached per step_fn so repeated calls (benchmark
    timing loops) hit the compilation cache instead of retracing."""
    run = _cg_dia_compiled.get(step_fn)
    if run is None:

        @partial(jax.jit, static_argnames=("iters",))
        def run(planes, x, r, p, rho, iters):
            def body(_, state):
                return step_fn(planes, *state)

            return jax.lax.fori_loop(0, iters, body, (x, r, p, rho))

        _cg_dia_compiled[step_fn] = run
    return run(planes, x, r, p, rho, iters=iters)
