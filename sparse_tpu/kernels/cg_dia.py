"""Fused CG iteration on a DIA matrix — two Pallas kernels per iteration.

The plain CG loop issues ~7 separate elementwise/reduction XLA kernels plus
an SpMV per iteration; each streams full-length vectors through HBM. Here
one iteration is exactly two fused passes:

  * kernel A: p_new = r + beta*p computed IN the SpMV's halo window
    (redundant halo recompute instead of a barrier), q = A p_new from
    row-indexed diagonal planes, and the partial dot <p_new, q> — one
    window read of r and p, one streamed read of the planes, one write of
    p_new and q, one scalar.
  * kernel B: x += alpha*p, r -= alpha*q and the partial dot <r, r> (the
    next iteration's rho) — tile-local streams, no halos.

Layout: vectors live PADDED at [L] = [(G+2)*TM] with one all-zero block on
each side; the halo B (band rounded to the 1024-element HBM tiling) fits
inside that block for any tile size TM >= B, so out-block index maps shift
by exactly one block while window DMA starts (gg*TM - B) stay 1024-aligned.
Row-indexed planes (data_row[k, i] = coefficient of diagonal k at ROW i)
make the plane stream halo-free.

Residency: the row-indexed planes depend on the operator alone, so they are
packed by a program of their own (:func:`cg_dia_pack`) and kept by whoever
keeps the operator; the padded state is made once a solve
(:func:`cg_dia_start`) and threaded through the loop program
(:func:`cg_dia_chunk`), which holds nothing but the iterations. Nothing is
re-packed, padded or un-padded between the chunks of a solve.

Reference analog: the fused AXPBY task family (linalg.py:479-496) taken to
its limit — the reference fuses two vector ops per launch; the TPU version
fuses the entire iteration into two memory passes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _plan(m: int, offsets: tuple, tile: int = 16384):
    """Tile TM and halo B (both multiples of the 1024-element HBM tiling).

    B covers the band; TM is as large as ``tile`` allows (fewer grid steps
    -> less per-step overhead, smaller window/tile overlap) but at least B
    so the one-block [L] padding contains the halo window.
    """
    band = max(max((abs(int(o)) for o in offsets), default=0), 1)
    B = _round_up(band, 1024)
    TM = max(B, min(_round_up(tile, 1024), _round_up(m, 1024)))
    G = (m + TM - 1) // TM
    return TM, B, G


def _row_planes(data, offsets: tuple, TM: int, B: int, G: int, m: int):
    """Column-indexed scipy DIA planes -> flat row-indexed [D * m_pad].

    Flat 1-D packing (not [Dp, m_pad]) so kernel A fetches exactly D
    aligned [TM] plane slices per tile by manual DMA — no ceil8(D) zero
    planes and no halo on the plane stream. Delegates to
    :func:`..dia_spmv.dia_pack` (single source for the packing identity).
    ``m`` is the true row count — the junk-row mask bound — which may be
    smaller than the plane width (scipy accepts over-wide DIA data)."""
    from .dia_spmv import DiaPlan, dia_pack

    return dia_pack(data, DiaPlan(offsets, m, data.shape[1], TM, B, G))


def _resolve_plane_dtype(plane_dtype, dt, TM: int = 2048):
    """Stream dtype for the packed planes (bf16 halves matrix traffic;
    callers opt in only when values are exactly representable); alignment
    policy shared with the SpMV kernels (dia_spmv.plane_stream_dtype)."""
    from .dia_spmv import plane_stream_dtype

    return plane_stream_dtype(plane_dtype, dt, TM)


def _pad_vec(v, TM: int, G: int):
    """[m] -> [L] padded with one zero block each side (+ tail zeros)."""
    m = v.shape[0]
    L = (G + 2) * TM
    out = jnp.zeros((L,), dtype=v.dtype)
    return jax.lax.dynamic_update_slice(out, v, (TM,))


def _unpad_vec(vp, m: int, TM: int):
    return jax.lax.dynamic_slice(vp, (TM,), (m,))


def _kernel_a(offsets: tuple, TM: int, B: int, win: int, D: int, m_pad: int):
    """p_new (windowed), q, and the <p, q> partial.

    r/p windows AND the D flat row-indexed plane slices are all manual
    double-buffered DMAs (sem slots: 0=r, 1=p, 2..2+D-1=planes). Planes
    land in D separate 1-D (TM,) VMEM buffers per slot — Mosaic rejects
    DMA into one row of a 2-D (8,128)-tiled scratch."""

    def kernel(beta_ref, r_hbm, p_hbm, planes_hbm, pnew_ref, q_ref, pq_ref,
               *scr):
        rwinA, rwinB, pwinA, pwinB = scr[:4]
        dwinA, dwinB = scr[4 : 4 + D], scr[4 + D : 4 + 2 * D]
        semA, semB = scr[4 + 2 * D :]
        gg = pl.program_id(0)
        Gp2 = pl.num_programs(0)

        @pl.when(gg == 0)
        def _():
            pq_ref[0, 0] = jnp.zeros((), pq_ref.dtype)

        def copies(rwin, pwin, dwin, sem, g2):
            # g2*TM - B is divisible by the 1024-element HBM tiling (TM and
            # B both are), but Mosaic's prover can't see through the
            # subtraction — assert it explicitly or the compile fails.
            start = pl.multiple_of(g2 * TM - B, 1024)
            yield pltpu.make_async_copy(
                r_hbm.at[pl.ds(start, win)], rwin, sem.at[0]
            )
            yield pltpu.make_async_copy(
                p_hbm.at[pl.ds(start, win)], pwin, sem.at[1]
            )
            for k in range(D):
                yield pltpu.make_async_copy(
                    planes_hbm.at[
                        pl.ds(pl.multiple_of(k * m_pad + (g2 - 1) * TM, TM), TM)
                    ],
                    dwin[k],
                    sem.at[2 + k],
                )

        def issue(rwin, pwin, dwin, sem, g2):
            for c in copies(rwin, pwin, dwin, sem, g2):
                c.start()

        def wait(rwin, pwin, dwin, sem, g2):
            for c in copies(rwin, pwin, dwin, sem, g2):
                c.wait()

        def interior(rwin, pwin, dwin, sem, rwin_n, pwin_n, dwin_n, sem_n):
            # windows address padded coords [gg*TM - B, (gg+1)*TM + B);
            # the first interior tile (gg == 1) starts at TM - B >= 0
            @pl.when(gg == 1)
            def _():
                issue(rwin, pwin, dwin, sem, gg)

            @pl.when(gg + 1 < Gp2 - 1)
            def _():
                issue(rwin_n, pwin_n, dwin_n, sem_n, gg + 1)

            wait(rwin, pwin, dwin, sem, gg)
            beta = beta_ref[0, 0]
            pw = rwin[:] + beta * pwin[:]
            acc = jnp.zeros((TM,), dtype=q_ref.dtype)
            for k, o in enumerate(offsets):
                lo = B + int(o)
                acc = acc + dwin[k][:].astype(acc.dtype) * pw[lo : lo + TM]
            mid = pw[B : B + TM]
            pnew_ref[:] = mid
            q_ref[:] = acc
            # the <p, q> partial reduces at pq_ref's dtype — with the
            # acc_dtype split (ISSUE 15) the recurrence scalars stay
            # wide even when the vector planes are narrow; a no-op
            # convert when the dtypes match
            pq_ref[0, 0] += jnp.sum(
                mid.astype(pq_ref.dtype) * acc.astype(pq_ref.dtype)
            )

        def halo():
            pnew_ref[:] = jnp.zeros((TM,), pnew_ref.dtype)
            q_ref[:] = jnp.zeros((TM,), q_ref.dtype)

        is_halo = (gg == 0) | (gg == Gp2 - 1)

        @pl.when(~is_halo & (gg % 2 == 1))
        def _():
            interior(rwinA, pwinA, dwinA, semA, rwinB, pwinB, dwinB, semB)

        @pl.when(~is_halo & (gg % 2 == 0))
        def _():
            interior(rwinB, pwinB, dwinB, semB, rwinA, pwinA, dwinA, semA)

        @pl.when(is_halo)
        def _():
            halo()

    return kernel


def _kernel_b():
    """x += alpha p, r -= alpha q, <r_new, r_new> partial."""

    def kernel(alpha_ref, x_ref, p_ref, r_ref, q_ref, xo_ref, ro_ref, rr_ref):
        gg = pl.program_id(0)

        @pl.when(gg == 0)
        def _():
            rr_ref[0, 0] = jnp.zeros((), rr_ref.dtype)

        alpha = alpha_ref[0, 0]
        r_new = r_ref[:] - alpha * q_ref[:]
        xo_ref[:] = x_ref[:] + alpha * p_ref[:]
        ro_ref[:] = r_new
        # <r, r> reduces at rr_ref's dtype (the acc_dtype split)
        rr = r_new.astype(rr_ref.dtype)
        rr_ref[0, 0] += jnp.sum(rr * rr)

    return kernel


@partial(jax.jit, static_argnames=("offsets", "m", "tile", "plane_dtype"))
def cg_dia_pack(data, offsets: tuple, m: int, tile: int, plane_dtype):
    """The operator half of :func:`cg_dia_fused`: scipy-layout planes ->
    the kernels' flat row-indexed ``[D * m_pad]`` stream at ``plane_dtype``
    (already resolved: :func:`_resolve_plane_dtype`). Depends on the planes
    and the plan ``tile`` gives, on nothing of a solve: pack once an
    operator and hand the result to every :func:`cg_dia_chunk`. The scope
    names this work in each op's ``op_name``."""
    TM, B, G = _plan(m, offsets, tile=tile)
    with jax.named_scope("cg_dia.repack"):
        return _row_planes(data.astype(plane_dtype), offsets, TM, B, G, m)


@partial(jax.jit, static_argnames=("offsets", "m", "tile", "acc_dtype"))
def cg_dia_start(data, offsets: tuple, b, x0, m: int, tile: int, acc_dtype=None):
    """The padded CG state ``(xp, rp, pp, rho_prev, rho)`` a solve starts
    from: r0 = b - A x0 (r0 = b and no product when ``x0`` is None), padded
    once; rho at ``acc_dtype`` (None: the vectors' dtype)."""
    dt = jnp.result_type(data.dtype, b.dtype)
    adt = jnp.dtype(acc_dtype) if acc_dtype is not None else dt
    TM, _, G = _plan(m, offsets, tile=tile)
    if x0 is None:
        xp = jnp.zeros(((G + 2) * TM,), dt)
        rp0 = _pad_vec(b.astype(dt), TM, G)  # r = b - A @ 0
    else:
        from ..ops.dia_spmv import dia_spmv_xla

        xp = _pad_vec(x0.astype(dt), TM, G)
        r0 = b.astype(dt) - dia_spmv_xla(
            data.astype(dt), offsets, x0.astype(dt), (m, m)
        )
        rp0 = _pad_vec(r0, TM, G)
    rho0 = jnp.vdot(rp0, rp0).real.astype(adt)
    return xp, rp0, jnp.zeros_like(rp0), jnp.zeros((), adt), rho0


def _chunk(planes_row, state, offsets: tuple, m: int, iters: int, tile: int,
           interpret: bool):
    xp, rp, _, _, rho = state
    dt, adt, pdt = xp.dtype, rho.dtype, planes_row.dtype
    TM, B, G = _plan(m, offsets, tile=tile)
    win = TM + 2 * B
    m_pad = G * TM
    L = (G + 2) * TM
    D = len(offsets)

    kA = pl.pallas_call(
        _kernel_a(offsets, TM, B, win, D, m_pad),
        name="cg_dia_a",
        grid=(G + 2,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((TM,), lambda gg: (gg,), memory_space=pltpu.VMEM),
            pl.BlockSpec((TM,), lambda gg: (gg,), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda gg: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L,), dt),
            jax.ShapeDtypeStruct((L,), dt),
            jax.ShapeDtypeStruct((1, 1), adt),
        ],
        scratch_shapes=(
            [
                pltpu.VMEM((win,), dt),
                pltpu.VMEM((win,), dt),
                pltpu.VMEM((win,), dt),
                pltpu.VMEM((win,), dt),
            ]
            + [pltpu.VMEM((TM,), pdt)] * (2 * D)
            + [
                pltpu.SemaphoreType.DMA((2 + D,)),
                pltpu.SemaphoreType.DMA((2 + D,)),
            ]
        ),
        interpret=interpret,
    )

    kB = pl.pallas_call(
        _kernel_b(),
        name="cg_dia_b",
        grid=(G + 2,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((TM,), lambda gg: (gg,), memory_space=pltpu.VMEM),
            pl.BlockSpec((TM,), lambda gg: (gg,), memory_space=pltpu.VMEM),
            pl.BlockSpec((TM,), lambda gg: (gg,), memory_space=pltpu.VMEM),
            pl.BlockSpec((TM,), lambda gg: (gg,), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((TM,), lambda gg: (gg,), memory_space=pltpu.VMEM),
            pl.BlockSpec((TM,), lambda gg: (gg,), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda gg: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L,), dt),
            jax.ShapeDtypeStruct((L,), dt),
            jax.ShapeDtypeStruct((1, 1), adt),
        ],
        interpret=interpret,
    )

    def body(_, state):
        xp, rp, pp, rho_prev, rho = state
        # the scalar recurrence runs at adt (the acc_dtype split); only
        # the SMEM kernel inputs cast down to the vector dtype
        beta = jnp.where(rho_prev == 0, 0.0, rho / jnp.where(rho_prev == 0, 1, rho_prev)).astype(adt)
        pnew, q, pq = kA(beta.astype(dt).reshape(1, 1), rp, pp, planes_row)
        alpha = rho / jnp.where(pq[0, 0] == 0, 1, pq[0, 0])
        xp2, rp2, rr = kB(alpha.reshape(1, 1).astype(dt), xp, pnew, rp, q)
        return xp2, rp2, pnew, rho, rr[0, 0]

    # Two iterations a trip: a `while` keeps each carried array in ONE
    # buffer, and a kernel cannot write x', r' or p' into the buffer it reads
    # x, r or p from, so a one-iteration trip copies all three vectors to
    # make room (three [L] copies an iteration: a quarter of the device time
    # at 3200^2). Over two iterations the state ping-pongs between two sets
    # of buffers and the trip ends where it began. Same ops, same order.
    return jax.lax.fori_loop(0, iters, body, state, unroll=2)


# The loop program. A profile and the benchmark's roofline reader find it
# by the jitted function's name, which has to stay `cg_dia_fused` (with the
# kernels' `cg_dia_a`/`cg_dia_b`). Two compilations of one function: the
# chip's takes the threaded state's buffers for its outputs; the CPU has no
# donation (interpret mode, the tests) and jax would warn at every call.
_chunk.__name__ = _chunk.__qualname__ = "cg_dia_fused"
_CHUNK_STATICS = ("offsets", "m", "iters", "tile", "interpret")
_chunk_donating = jax.jit(
    _chunk, static_argnames=_CHUNK_STATICS, donate_argnames=("state",)
)
_chunk_interpreted = jax.jit(_chunk, static_argnames=_CHUNK_STATICS)


def cg_dia_chunk(planes_row, state, offsets: tuple, m: int, iters: int,
                 tile: int = 16384, interpret: bool = False):
    """``iters`` CG iterations from ``state`` to the next state: nothing but
    the two kernels and the scalar recurrence. ``planes_row`` from
    :func:`cg_dia_pack`, the first ``state`` from :func:`cg_dia_start`, both
    for the same ``tile``; the dtypes ride the arrays. On the chip
    (``interpret=False``) the state handed in is DONATED: its buffers become
    the next state's, and the caller may not read it again. ``state[-1]`` is
    rho = ||r||^2 and :func:`cg_dia_x` un-pads the iterate."""
    run = _chunk_interpreted if interpret else _chunk_donating
    return run(planes_row, state, offsets=offsets, m=m, iters=iters,
               tile=tile, interpret=interpret)


def cg_dia_x(state, offsets: tuple, m: int, tile: int = 16384):
    """The iterate ``x`` ``[m]`` of a padded CG state."""
    TM, _, _ = _plan(m, offsets, tile=tile)
    return _unpad_vec(state[0], m, TM)


def cg_dia_fused(
    data, offsets: tuple, b, x0, m: int, iters: int = 300, tile: int = 16384,
    plane_dtype=None, interpret: bool = False, state=None,
    return_state: bool = False, acc_dtype=None,
):
    """``iters`` fixed CG iterations on the DIA matrix (throughput mode),
    as one call: :func:`cg_dia_pack`, :func:`cg_dia_start` and one
    :func:`cg_dia_chunk`. A caller that solves more than once with an
    operator, or in chunks, keeps the pack and threads the state itself
    (``linalg.cg``'s fused fast path).

    Returns (x, r, rho) with rho = ||r||^2. Matches ``cg_step_dia``'s
    recurrence exactly (same beta/alpha guards) — two fused passes per
    iteration instead of an SpMV plus a train of elementwise kernels.
    ``x0=None`` starts from zero and skips the setup SpMV (r0 = b).

    ``state``/``return_state`` thread the FULL padded CG state
    (xp, rp, pp, rho_prev, rho) across calls — identical iterates to one
    long run, no CG restart between chunks. A ``state`` handed in is
    consumed on the chip (:func:`cg_dia_chunk`).

    ``acc_dtype`` is the recurrence-scalar split (ISSUE 15): the
    <p, q> / <r, r> dot partials reduce — and rho/beta/alpha carry —
    at ``acc_dtype`` while vectors stream at ``dt`` (and planes at
    ``plane_dtype``). ``None`` = historic single-dtype behavior,
    byte-identical; callers threading ``state`` must keep the same
    ``acc_dtype`` across chunks (the rho entries carry it).
    """
    dt = jnp.result_type(data.dtype, b.dtype)
    TM, _, _ = _plan(m, offsets, tile=tile)
    planes_row = cg_dia_pack(
        data, offsets, m, tile, _resolve_plane_dtype(plane_dtype, dt, TM)
    )
    if state is None:
        state = cg_dia_start(data, offsets, b, x0, m, tile, acc_dtype)
    state = cg_dia_chunk(planes_row, state, offsets, m, iters, tile, interpret)
    out = cg_dia_x(state, offsets, m, tile), _unpad_vec(state[1], m, TM), state[4]
    return (*out, state) if return_state else out
