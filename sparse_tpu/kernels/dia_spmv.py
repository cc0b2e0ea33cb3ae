"""Pallas TPU kernel: DIA SpMV with explicit VMEM windowing.

The XLA formulation (``ops.dia_spmv``) already avoids gathers; this kernel
additionally controls the memory schedule: the packed planes and x stay in
HBM, each grid step DMAs the D ``[TM]`` plane rows and the ``[TM + 2B]`` x
window its row tile needs into VMEM, and the diagonal contributions —
**including the data*x multiply** — are computed in VMEM as
statically-shifted slices on the VPU. Per element that is one plane load +
one (windowed) x load + one y store — no full-size intermediate product
array ever exists in HBM.

Reference analog: the cuSPARSE-backed CSR SpMV task
(``src/sparse/array/csr/spmv.cu:42-116``) with the shifted-pointer trick;
here the "shifted pointer" is a static slice offset into the VMEM window.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


# ---------------------------------------------------------------------------
# Prepared layout: row-indexed planes, packed once, reused per SpMV.
#
# Plane k's coefficient for row i is pr[k, i] = data[k, i + o_k], so each
# grid step needs exactly [D, TM] plane elements (no halo, no pad planes)
# fetched as D aligned 1-D DMAs from the flattened [D * m_pad] buffer. Only
# the x window keeps the 2B halo. Per-element traffic is 1 plane load +
# ~1 x load + 1 y store — the bandwidth floor for DIA SpMV. (Padding the
# scipy-layout, column-indexed planes on every call instead costs an extra
# read+write of the whole matrix per SpMV and a 2B halo on each of
# ceil8(D) planes.)
#
# Mosaic DMA alignment: 1-D HBM memrefs carry a (1024,) tiling, so the row
# tile TM rounds to 1024 and the halo B to 512 (``dia_plan``) — then the
# window win = TM + 2B, every window start g*TM, and each plane's base
# k*m_pad in the flat buffer are all multiples of 1024.
# ---------------------------------------------------------------------------


def plane_stream_dtype(requested, default, TM: int):
    """Resolve the plane stream dtype against the DMA alignment rule:
    2-byte elements need 2048-element-aligned starts, so an odd-1024 TM
    forces the default (4-byte) stream. Single source for every caller
    (PreparedDia, dia_spmv_packed, the fused CG kernels)."""
    if requested is None:
        return jnp.dtype(default)
    rdt = jnp.dtype(requested)
    if rdt.itemsize == 2 and TM % 2048:
        return jnp.dtype(default)
    return rdt


class DiaPlan:
    """Static geometry of a prepared DIA operator (hashable => jit-static)."""

    __slots__ = ("offsets", "m", "n", "TM", "B", "G", "D")

    def __init__(self, offsets, m, n, TM, B, G):
        self.offsets = tuple(int(o) for o in offsets)
        self.m, self.n, self.TM, self.B, self.G = m, n, TM, B, G
        self.D = len(self.offsets)

    def _key(self):
        return (self.offsets, self.m, self.n, self.TM, self.B, self.G)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, DiaPlan) and self._key() == other._key()


def dia_plan(offsets, shape, tile: int = 65536) -> DiaPlan:
    m, n = shape
    B = _round_up(max(max((abs(int(o)) for o in offsets), default=0), 1), 512)
    TM = min(_round_up(tile, 1024), _round_up(max(m, 1024), 1024))
    G = (m + TM - 1) // TM
    return DiaPlan(offsets, m, n, TM, B, G)


@partial(jax.jit, static_argnames=("plan",))
def dia_pack(data, plan: DiaPlan):
    """scipy-layout [D, n] planes -> flat row-indexed [D * m_pad] buffer.

    Columns beyond m_pad + B - 1 can never be touched (row i reads column
    i + o <= m_pad - 1 + B), so wide matrices are truncated to that bound —
    without it, dynamic_update_slice would CLAMP the start when the operand
    overruns the buffer and silently shift every coefficient.
    """
    m_pad = plan.G * plan.TM
    B = plan.B
    ncap = min(plan.n, m_pad + B)
    buf = jnp.zeros((plan.D, m_pad + 2 * B), dtype=data.dtype)
    buf = jax.lax.dynamic_update_slice(buf, data[:, :ncap], (0, B))
    # Row mask: scipy ignores DIA slots whose row j - o falls outside the
    # matrix, but the arrays may hold junk there. Those slots land in
    # pr rows i >= m; zeroing them keeps padded rows exactly zero — vital
    # for cg_dia_fused, where nonzero padded q would leak into r and rho.
    valid = jnp.arange(m_pad) < plan.m
    rows = [
        jnp.where(valid, jax.lax.dynamic_slice(buf[k], (B + o,), (m_pad,)), 0)
        for k, o in enumerate(plan.offsets)
    ]
    return jnp.concatenate(rows)  # [D * m_pad]


@partial(jax.jit, static_argnames=("plan",))
def dia_pad_x(x, plan: DiaPlan):
    """[n] -> [m_pad + 2B] with x at offset B (zeros elsewhere).

    Same wide-matrix truncation as :func:`dia_pack`: entries past
    m_pad + B - 1 are unreachable by any in-band diagonal.
    """
    m_pad = plan.G * plan.TM
    ncap = min(x.shape[0], m_pad + plan.B)
    out = jnp.zeros((m_pad + 2 * plan.B,), dtype=x.dtype)
    return jax.lax.dynamic_update_slice(out, x[:ncap], (plan.B,))


@partial(jax.jit, static_argnames=("plan", "interpret", "acc_dtype"))
def dia_spmv_packed(planes_flat, x_padded, plan: DiaPlan, interpret: bool = False,
                    acc_dtype=None):
    """y = A @ x from the prepared layout; returns the [m_pad] padded y.

    ``planes_flat`` from :func:`dia_pack`, ``x_padded`` from
    :func:`dia_pad_x` — keep both resident across calls (solvers keep their
    vectors in padded coordinates and never repack).

    The plane stream already supports reduced-width storage
    (:func:`plane_stream_dtype` — bf16 planes halve matrix traffic and
    widen at the accumulate); ``acc_dtype`` additionally pins the
    accumulator/output dtype ABOVE the natural result type (ISSUE 15:
    bf16 planes + bf16 x still reduce in f32). ``None`` = historic
    result-type behavior, byte-identical.
    """
    TM, B, G, D = plan.TM, plan.B, plan.G, plan.D
    win = TM + 2 * B
    m_pad = G * TM
    out_dt = acc_dtype or jnp.result_type(planes_flat.dtype, x_padded.dtype)
    # direct callers may hand us 2-byte planes with a misaligned TM; the
    # pack-time guard in PreparedDia avoids this per-call cast on hot paths
    safe_dt = plane_stream_dtype(planes_flat.dtype, out_dt, TM)
    if safe_dt != planes_flat.dtype:
        planes_flat = planes_flat.astype(safe_dt)

    # Each plane gets its OWN 1-D (TM,) VMEM buffer: Mosaic rejects DMA into
    # a single row of a 2-D (8,128)-tiled scratch ("slice along dim 0 must
    # be aligned to tiling (8)"), while 1-D destinations are unrestricted —
    # and D separate buffers keep the stream at exactly D planes (no ceil8
    # padding traffic, the point of the packed layout).
    def kernel(planes_hbm, x_hbm, y_ref, *scr):
        dwinsA, dwinsB = scr[:D], scr[D : 2 * D]
        xwinA, xwinB, semA, semB = scr[2 * D :]
        g = pl.program_id(0)
        G_ = pl.num_programs(0)

        def copies(dwins, xwin, sem, gg):
            for k in range(D):
                yield pltpu.make_async_copy(
                    planes_hbm.at[pl.ds(k * m_pad + gg * TM, TM)],
                    dwins[k],
                    sem.at[k],
                )
            yield pltpu.make_async_copy(
                x_hbm.at[pl.ds(gg * TM, win)], xwin, sem.at[D]
            )

        def issue(dwins, xwin, sem, gg):
            for c in copies(dwins, xwin, sem, gg):
                c.start()

        def wait(dwins, xwin, sem, gg):
            for c in copies(dwins, xwin, sem, gg):
                c.wait()

        def step(dwins, xwin, sem, dwins_n, xwin_n, sem_n):
            @pl.when(g == 0)
            def _():
                issue(dwins, xwin, sem, g)

            @pl.when(g + 1 < G_)
            def _():
                issue(dwins_n, xwin_n, sem_n, g + 1)

            wait(dwins, xwin, sem, g)
            acc = jnp.zeros((TM,), dtype=y_ref.dtype)
            for k, o in enumerate(plan.offsets):
                lo = B + o
                acc = acc + dwins[k][:].astype(acc.dtype) * xwin[lo : lo + TM]
            y_ref[:] = acc

        @pl.when(g % 2 == 0)
        def _():
            step(dwinsA, xwinA, semA, dwinsB, xwinB, semB)

        @pl.when(g % 2 == 1)
        def _():
            step(dwinsB, xwinB, semB, dwinsA, xwinA, semA)

    return pl.pallas_call(
        kernel,
        name="dia_spmv_packed",
        grid=(G,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((TM,), lambda g: (g,), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m_pad,), out_dt),
        scratch_shapes=[pltpu.VMEM((TM,), planes_flat.dtype)] * (2 * D)
        + [
            pltpu.VMEM((win,), x_padded.dtype),
            pltpu.VMEM((win,), x_padded.dtype),
            pltpu.SemaphoreType.DMA((D + 1,)),
            pltpu.SemaphoreType.DMA((D + 1,)),
        ],
        interpret=interpret,
    )(planes_flat, x_padded)


# ---------------------------------------------------------------------------
# The layout ``dia``'s own product on the chip (``csr.form_matvec``): the
# same packed planes and the same window arithmetic, but x and y are the
# caller's own vectors padded to whole 1024-element tiles, ``[n_tiles]``:
# no copy of x with a halo at both ends and no ``[m_pad]`` result to cut.
# A window that overhangs x (the first step's left halo, the last steps'
# right one) is fetched as far as x goes and the rest of it zeroed in VMEM;
# the packed planes are zero wherever a diagonal leaves the matrix, so the
# zeros only keep 0 x junk from being NaN. The halo is rounded to 1024 (every
# window start in x is then a whole tile) and is at most one row tile, so the
# only steps with an overhang are the first and the last two.
# ---------------------------------------------------------------------------

DIA_TILE = 1024  # Mosaic's tiling of a 1-D float32 array in HBM


def dia_rows_plan(offsets, n: int, vmem_bytes: int, tile: int = 65536):
    """The :class:`DiaPlan` of :func:`dia_spmv_rows` for a square matrix of
    ``n`` rows, or None where no row tile fits: the kernel holds ``2 D TM``
    plane elements, two x windows of ``TM + 2 B`` and Pallas two blocks of y,
    4 bytes each, and that has to stay under ``vmem_bytes`` with ``TM`` at
    least the halo ``B``. The tile is the largest under ``tile`` that fits,
    then evened out over the steps (the planes are stored to whole row
    tiles: 1,270,432 rows take 20 steps of 64,512, not of 65,536)."""
    D = len(offsets)
    B = _round_up(max(max((abs(int(o)) for o in offsets), default=0), 1), DIA_TILE)
    fit = (vmem_bytes // 4 - 4 * B) // (2 * D + 4) // DIA_TILE * DIA_TILE
    cap = min(_round_up(tile, DIA_TILE), fit)
    if n < 1 or cap < B:
        return None
    tiles = -(-n // DIA_TILE)
    G = -(-tiles * DIA_TILE // cap)
    return DiaPlan(offsets, n, n, DIA_TILE * -(-tiles // G), B, G)


@jax.tree_util.register_pytree_node_class
class DiaRows:
    """What the layout ``dia`` multiplies through on the chip: the flat
    row-indexed plane stream of :func:`dia_pack` (a jax array, a jit
    argument) and its static :class:`DiaPlan` (part of a program's key)."""

    __slots__ = ("planes", "plan")

    def __init__(self, planes, plan: DiaPlan):
        self.planes, self.plan = planes, plan

    def tree_flatten(self):
        return (self.planes,), self.plan

    @classmethod
    def tree_unflatten(cls, plan, children):
        return cls(children[0], plan)

    @property
    def n_tiles(self) -> int:
        """x's and y's length: the rows in whole 1024-element tiles."""
        return _round_up(self.plan.m, DIA_TILE)

    def matvec(self, x, interpret: bool = False):
        """``A @ x`` for ``x [n]``: x padded to whole tiles, the result cut
        to the rows (no-ops where ``n`` is whole tiles)."""
        n = self.plan.m
        xt = jnp.pad(x, (0, self.n_tiles - n))
        return dia_spmv_rows(self.planes, xt, self.plan, interpret=interpret)[:n]


@partial(jax.jit, static_argnames=("plan", "interpret"))
def dia_spmv_rows(planes_flat, x_tiles, plan: DiaPlan, interpret: bool = False):
    """y = A @ x from the packed planes of a plan of :func:`dia_rows_plan`;
    ``x_tiles`` and the result are ``[n_tiles]``, the rows padded to whole
    1024-element tiles (x's pad anything finite, y's exactly zero)."""
    TM, B, G, D = plan.TM, plan.B, plan.G, plan.D
    win = TM + 2 * B
    m_pad = G * TM
    n_tiles = _round_up(plan.m, DIA_TILE)
    assert x_tiles.shape == (n_tiles,) and B % DIA_TILE == 0 and B <= TM, plan._key()

    def xspan(g: int):
        """Step g's part of x: (where its window starts in x, the offset of
        what x holds of it inside the window, its length)."""
        lo, hi = max(g * TM - B, 0), min(g * TM + TM + B, n_tiles)
        return lo, lo - (g * TM - B), hi - lo

    # the steps whose window overhangs x, each with static bounds of its own
    edge = {g: xspan(g) for g in sorted({0, *range(max(G - 2, 0), G)})
            if xspan(g)[1:] != (0, win)}

    def kernel(planes_hbm, x_hbm, y_ref, *scr):
        dwinsA, dwinsB = scr[:D], scr[D : 2 * D]
        xwinA, xwinB, semA, semB = scr[2 * D :]
        g = pl.program_id(0)
        G_ = pl.num_programs(0)

        def on_x_copy(xwin, sem, gg, do):
            """``do`` on the copy of step gg's part of x into ``xwin``."""
            inside = None
            for s, (lo, off, size) in edge.items():
                @pl.when(gg == s)
                def _(lo=lo, off=off, size=size):
                    do(pltpu.make_async_copy(
                        x_hbm.at[pl.ds(lo, size)], xwin.at[pl.ds(off, size)],
                        sem.at[D]))

                inside = gg != s if inside is None else inside & (gg != s)
            if len(edge) < G:
                def whole():
                    do(pltpu.make_async_copy(
                        x_hbm.at[pl.ds(gg * TM - B, win)], xwin, sem.at[D]))

                whole() if inside is None else pl.when(inside)(whole)

        def on_copies(dwins, xwin, sem, gg, do):
            for k in range(D):
                do(pltpu.make_async_copy(
                    planes_hbm.at[pl.ds(k * m_pad + gg * TM, TM)],
                    dwins[k], sem.at[k]))
            on_x_copy(xwin, sem, gg, do)

        def step(dwins, xwin, sem, dwins_n, xwin_n, sem_n):
            @pl.when(g == 0)
            def _():
                on_copies(dwins, xwin, sem, g, lambda c: c.start())

            @pl.when(g + 1 < G_)
            def _():
                on_copies(dwins_n, xwin_n, sem_n, g + 1, lambda c: c.start())

            # the overhang of an edge step's window: no copy writes it
            for s, (_lo, off, size) in edge.items():
                @pl.when(g == s)
                def _(off=off, size=size):
                    if off:
                        xwin[0:off] = jnp.zeros((off,), xwin.dtype)
                    if off + size < win:
                        xwin[off + size : win] = jnp.zeros(
                            (win - off - size,), xwin.dtype)

            on_copies(dwins, xwin, sem, g, lambda c: c.wait())
            acc = jnp.zeros((TM,), dtype=y_ref.dtype)
            for k, o in enumerate(plan.offsets):
                lo = B + o
                acc = acc + dwins[k][:] * xwin[lo : lo + TM]
            y_ref[:] = acc

        @pl.when(g % 2 == 0)
        def _():
            step(dwinsA, xwinA, semA, dwinsB, xwinB, semB)

        @pl.when(g % 2 == 1)
        def _():
            step(dwinsB, xwinB, semB, dwinsA, xwinA, semA)

    return pl.pallas_call(
        kernel,
        name="dia_spmv_rows",
        grid=(G,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((TM,), lambda g: (g,), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_tiles,), x_tiles.dtype),
        scratch_shapes=[pltpu.VMEM((TM,), planes_flat.dtype)] * (2 * D)
        + [
            pltpu.VMEM((win,), x_tiles.dtype),
            pltpu.VMEM((win,), x_tiles.dtype),
            pltpu.SemaphoreType.DMA((D + 1,)),
            pltpu.SemaphoreType.DMA((D + 1,)),
        ],
        interpret=interpret,
    )(planes_flat, x_tiles)


def dia_spmv_pallas_v2(data, offsets, x, shape, tile=65536, interpret=None):
    """One-shot wrapper over the prepared path (packs per call — for tests
    and drop-in use; hot loops should pack once via PreparedDia)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    plan = dia_plan(tuple(offsets), tuple(shape), tile=tile)
    y = dia_spmv_packed(
        dia_pack(data, plan), dia_pad_x(x, plan), plan, interpret=interpret
    )
    return y[: plan.m]


@partial(jax.jit, static_argnames=("plan", "iters", "interpret"))
def _spmv_chain(planes_flat, x_padded, plan: DiaPlan, iters: int,
                interpret: bool = False):
    """``iters`` dependent SpMVs compiled as ONE dispatch (y feeds the next
    x window), for wall-clock timing that per-dispatch host latency
    cannot contaminate — the best-of-chain measurement discipline
    behind the autotuner."""

    def body(_, xp):
        y = dia_spmv_packed(planes_flat, xp, plan, interpret=interpret)
        return jax.lax.dynamic_update_slice(xp, y.astype(xp.dtype), (plan.B,))

    return jax.lax.fori_loop(0, iters, body, x_padded)


_TILE_CACHE: dict = {}
# Process-wide retirement of the compiled fori_loop chain clock: a clock
# that failed once would fail (and cost a compile) for every candidate —
# so after the FIRST failure anywhere (any geometry, any call) the
# compiled clock is never attempted again this process (same one-time-latch pattern as the
# resilience.failover registry, but autotune-local).
_CHAIN_RETIRED = [False]


@partial(jax.jit, static_argnames=("plan",))
def _chain_step(planes_flat, x_padded, plan: DiaPlan):
    """One SpMV + x-window update as a single COMPILED step — the host-
    chained clock dispatches K of these (data dependence serializes on
    device) with no eager ops on the accelerator between steps."""
    y = dia_spmv_packed(planes_flat, x_padded, plan)
    return jax.lax.dynamic_update_slice(
        x_padded, y.astype(x_padded.dtype), (plan.B,)
    )


def autotune_dia_tile(
    data,
    offsets,
    shape,
    candidates=(65536, 131072),
    chain: int = 16,
    reps: int = 3,
    budget_s: float = 30.0,
):
    """Pick the fastest row-tile for this geometry on the CURRENT backend.

    Times a ``chain``-long compiled SpMV chain per candidate (best of
    ``reps``) and memoizes the winner per (offsets, shape, dtype) for the
    session — the runtime analog of the reference's one-time partition
    analysis, sized so the probe costs ~1 s of device time once compiles
    are cached. Returns ``(best_tile, {tile: seconds_per_spmv})``.
    Off-TPU (interpret mode) timings are meaningless: returns the default
    without probing.

    Cold-compile guard: each candidate costs a fresh Mosaic compile
    (seconds), so the default candidate list is
    just the two tiles that have ever won a session sweep, the first
    candidate is the always-safe 65536 default, and probing stops once
    ``budget_s`` of wall clock is spent — best-so-far wins, later
    sessions with a warm compile cache probe the full list.
    """
    import time

    from .. import telemetry
    from ..config import settings

    offsets = tuple(int(o) for o in offsets)
    shape = tuple(int(s) for s in shape)
    key = (offsets, shape, str(np.dtype(data.dtype)))
    if key in _TILE_CACHE:
        telemetry.count("autotune.cache_hit")
        return _TILE_CACHE[key]
    # the off-switch (SPARSE_TPU_PALLAS_AUTOTUNE=0) gates EVERY probe
    # path, direct calls included — it exists so an operator can
    # forbid the extra cold Mosaic compiles.
    # The gate result is NOT memoized (ADVICE r5): caching it under the
    # geometry key would make a later same-session flip of the setting
    # (or a backend change) return the gate default as if a probe ran.
    if not settings.pallas_autotune or jax.default_backend() != "tpu":
        reason = (
            "autotune-disabled" if not settings.pallas_autotune
            else "backend-not-tpu"
        )
        telemetry.record(
            "autotune.result", tile=65536, probed=False, reason=reason,
            shape=list(shape), diags=len(offsets),
            dtype=str(np.dtype(data.dtype)),
        )
        return (65536, {})

    # Two clocks, never mixed in one race. Preferred: the compiled
    # fori_loop chain (one dispatch per timing); it gets exactly ONE
    # lifetime attempt process-wide (_CHAIN_RETIRED); any
    # failure retires it and the race RESTARTS on the host-chained clock:
    # K jitted single steps (data dependence serializes on device, no
    # eager accelerator ops), fenced by a host scalar fetch (see
    # bench._time_kernel). The fence cost is a constant per timing shared
    # by every candidate, so the RANKING is unaffected; band values in a
    # host-clock race carry ~1/chain of one round-trip each.
    def run_compiled(pf, xp, plan):
        try:
            t0 = time.perf_counter()
            out = _spmv_chain(pf, xp, plan, chain)
            float(jnp.asarray(out)[-1])  # host-scalar fence
            return (time.perf_counter() - t0) / chain
        except Exception:  # pragma: no cover - backend-dependent
            _CHAIN_RETIRED[0] = True
            return None

    def run_host(pf, xp, plan):
        t0 = time.perf_counter()
        x_cur = xp
        for _ in range(chain):
            x_cur = _chain_step(pf, x_cur, plan)
        float(jnp.asarray(x_cur)[-1])  # host-scalar fence
        return (time.perf_counter() - t0) / chain

    def time_candidate(pf, xp, plan):
        # per-PLAN warm run outside the clock: both clocks' jits are keyed
        # on the static plan, so every candidate's first call compiles
        # — that must never land in a timed rep. Only the ACTIVE clock is warmed (finding: a spare
        # compile per candidate can eat the whole probe budget). Returns
        # (best_secs, used_compiled_clock).
        if not _CHAIN_RETIRED[0]:
            run_compiled(pf, xp, plan)  # warm; may retire the clock
        if _CHAIN_RETIRED[0]:
            float(jnp.asarray(_chain_step(pf, xp, plan))[-1])  # warm host
        best = float("inf")
        used_compiled = False
        for _ in range(reps):
            s = run_compiled(pf, xp, plan) if not _CHAIN_RETIRED[0] else None
            if s is None:
                s = run_host(pf, xp, plan)
            else:
                used_compiled = True
            best = min(best, s)
        return best, used_compiled

    timings: dict[int, float] = {}
    for _race in range(2):
        t_begin = time.perf_counter()  # each race gets the full budget
        retired_at_start = _CHAIN_RETIRED[0]
        timings = {}
        any_compiled = False
        for tile in candidates:
            if timings and time.perf_counter() - t_begin > budget_s:
                break  # out of probe budget: best-so-far wins
            plan = dia_plan(offsets, shape, tile=tile)
            if plan.G == 1 and timings:
                continue  # a single-grid-step plan is tile-size invariant
            try:
                pf = dia_pack(data, plan)
                xp = dia_pad_x(
                    jnp.ones(
                        (shape[1],),
                        dtype=jnp.result_type(data.dtype, jnp.float32),
                    ),
                    plan,
                )
                timings[tile], used = time_candidate(pf, xp, plan)
                any_compiled = any_compiled or used
            except Exception:  # pragma: no cover - backend-dependent
                continue  # an unlowerable candidate drops out of the race
        if _CHAIN_RETIRED[0] == retired_at_start or not any_compiled:
            # no mid-race clock flip — or the flip happened before any
            # compiled timing landed, so everything recorded is already
            # pure host-clock: keep it, no re-race (extra device probes
            # cost compiles)
            break
        # the compiled clock died mid-race WITH compiled timings on the
        # board: cross-clock offsets differ by ~a host round-trip, so
        # discard and re-race everything on the host clock (retirement is
        # process-wide, so this happens at most once)
    if not timings:
        result = (65536, {})
    else:
        result = (min(timings, key=timings.get), timings)
    _TILE_CACHE[key] = result
    return result


class PreparedDia:
    """A DIA operator packed once into the kernel-native layout.

    Holds the flat row-indexed plane buffer on device; each call pads x
    into window coordinates, runs :func:`dia_spmv_packed`, and trims the
    result. Format classes cache one of these per matrix so solver loops
    never repack (the reference likewise keeps its CSR stores resident
    across task launches rather than re-materializing per SpMV).

    ``tile=None`` autotunes on real TPUs when ``settings.pallas_autotune``
    is on (one ~1 s chained probe per geometry per session) and otherwise
    uses the 65536 default.
    """

    __slots__ = ("plan", "planes")

    def __init__(self, data, offsets, shape, tile: int | None = None):
        if tile is None:
            # autotune_dia_tile itself gates on settings.pallas_autotune
            # and the backend; off / off-TPU it returns the 65536 default
            tile, _ = autotune_dia_tile(data, offsets, shape)
        self.plan = dia_plan(tuple(int(o) for o in offsets), tuple(shape), tile=tile)
        sdt = plane_stream_dtype(data.dtype, jnp.float32, self.plan.TM)
        if sdt != jnp.dtype(data.dtype):
            data = data.astype(sdt)  # misaligned TM: stream at f32
        self.planes = dia_pack(data, self.plan)
        from .. import telemetry

        telemetry.count("kernel.dia_pack")

    @classmethod
    def from_parts(cls, plan: DiaPlan, planes) -> "PreparedDia":
        """Reassemble from an already-packed plane buffer — the vault
        codec's constructor. The stored :class:`DiaPlan` carries the
        session that wrote it's autotuned row tile, so a disk hit also
        skips the autotune probe."""
        prep = object.__new__(cls)
        prep.plan = plan
        prep.planes = planes
        return prep

    def __call__(self, x, interpret=None):
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        from .. import telemetry

        # dispatch counter (counts trace entries once when called under
        # jit — kernel dispatch counts, not device executions)
        telemetry.count("kernel.dia_spmv_packed")
        y = dia_spmv_packed(
            self.planes, dia_pad_x(x, self.plan), self.plan, interpret=interpret
        )
        return y[: self.plan.m]


#: failover-registry kernel name (resilience/failover.py)
DIA_KERNEL = "dia_spmv"


def _vault_codecs():
    from ..vault import _codecs

    return _codecs


def cached_prepared_spmv(obj, attr: str, data, offsets, shape, x):
    """Shared band-gated PreparedDia dispatch for the format classes.

    Returns ``None`` when the band exceeds ``settings.pallas_max_band``
    (caller falls back to the XLA formulation); otherwise obtains a
    :class:`PreparedDia` for ``obj`` from the library-wide
    ``sparse_tpu.plan_cache`` (weak-ref keyed under ``attr``) and applies
    it. Fresh objects from ``_with_data``/constructors are new cache keys,
    so mutation invalidates the plan for free.

    Failure handling lives in the shared failover registry
    (``sparse_tpu.resilience.failover``): it classifies with the strict
    lowering-unavailability vocabulary (on a real TPU nothing but an
    injected failure is benign, a Mosaic compile regression stays LOUD;
    off-TPU any lowering-availability wording qualifies), honors
    ``SPARSE_TPU_STRICT_PALLAS``, emits the consistent
    ``kernel.failover`` event, and latches per matrix object — a latch
    :func:`~sparse_tpu.resilience.failover.probe` can clear again when
    the backend heals.
    """
    from .. import plan_cache
    from ..config import settings
    from ..resilience import failover

    band = max((abs(int(o)) for o in offsets), default=0)
    if band > settings.pallas_max_band:
        return None
    if failover.failed(DIA_KERNEL, obj):
        return None
    prepared = plan_cache.get(
        obj, attr, lambda: PreparedDia(data, offsets, shape),
        # persistent tier (sparse_tpu.vault): the packed plane buffer +
        # autotuned tile persist across processes, content-keyed on the
        # exact planes/offsets/shape (dtype rides the array hash)
        vault_kind="prepared_dia",
        vault_key=lambda: _vault_codecs().prepared_dia_key(
            data, offsets, shape
        ),
    )
    try:
        # forced-failure injection point, then the real kernel attempt
        failover.maybe_inject(DIA_KERNEL)
        return prepared(x)
    except (ValueError, NotImplementedError) as e:
        failover.handle(DIA_KERNEL, obj, e)
        return None
