"""Masked batched Krylov solvers: CG, BiCGStab, GMRES over lane stacks.

One compiled loop drives B independent systems; each lane carries its own
convergence mask, iteration count and residual. Converged lanes FREEZE —
every carried array updates through ``jnp.where(active, new, old)`` so a
finished lane's iterate is bit-stable while its neighbors keep working —
and the ``lax.while_loop`` exits as soon as the mask is all-true (or the
global step count hits ``maxiter``). Convergence is tested at the same
points as the unbatched solvers in :mod:`sparse_tpu.linalg` (every
``conv_test_iters`` steps and at ``maxiter - 1``, absolute ``||r|| <
tol``), so a batch of one reproduces the unbatched solve exactly — the
parity contract ``tests/test_batch.py`` pins.

Inputs pass through :func:`sparse_tpu.utils.asjnp`, i.e. complex host
data bound for transfer-restricted backends rides the stacked-real shim
(two real planes recombined in a compiled program) — c64 batches work
through the public API on such backends the same way unbatched solves do.

The loop cores (``_cg_loop``/``_bicgstab_loop``/``_gmres_loop``) are pure jnp and
jit-safe: :class:`~sparse_tpu.batch.service.SolveSession` closes them
over a pattern's packed matvec inside ONE jitted program per batch
bucket, which is where the compile-amortization of microbatching comes
from (one trace+compile serves every same-bucket dispatch).

Which public calls compile once: :func:`batched_bicgstab` over an operator
and a preconditioner that declare what they hold (a
:class:`~sparse_tpu.batch.operator.BatchedDIA`, the Jacobi factory's
``Mvec``), in float32, runs ``jit_batched_bicgstab``, whose arguments are
the planes, the reciprocal diagonal, ``b``, the start, the lanes' ``tol``
and ``maxiter``, so that a later call of the same shapes, whatever the
values, traces and compiles nothing. That program stops stepping lanes
that are done (PR 56): a batch wide enough runs down a static ladder of
halving widths (:func:`_bicgstab_ladder`), the lanes still active gathered
into the next width between two stages and every lane's answer scattered
back to its place, so that a lane waits frozen for the lanes of its stage
and not of the batch (docs/batching.md). Every other call
(:func:`batched_cg`, :func:`batched_gmres`, a callable, a dense stack, a
``BatchedCSR``, a closure ``M``, float64, complex) runs its loop eagerly
with the operator's arrays closed over, and so traces, lowers and compiles
it again at every call.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import linalg as _linalg
from .. import telemetry
from ..resilience import faults as _faults
from ..telemetry import _metrics
from ..utils import asjnp, in_trace
from .operator import BatchedOperator, as_batched_matvec


def _maybe_faulty_mv(mv):
    """Install the fault-injection wrapper on a batched matvec when a
    matvec clause is active (resilience.faults) — absent otherwise, so
    clean traces are byte-identical."""
    if _faults.ACTIVE and _faults.targets("matvec") and not getattr(
        mv, "_fault_wrapped", False
    ):
        return _faults.wrap_batched_matvec(mv)
    return mv


@dataclass
class BatchedSolveInfo:
    """Per-lane outcome of a batched solve.

    ``iters``/``resid2``/``converged`` are ``(B,)`` arrays: iteration
    count at freeze (== the unbatched solver's ``iters`` for that lane),
    final squared residual norm, and whether the lane met its tolerance
    (as opposed to hitting ``maxiter``).
    """

    iters: object
    resid2: object
    converged: object

    @property
    def batch(self) -> int:
        return int(np.asarray(self.iters).shape[0])


def _bdot(a, b):
    """Per-lane inner product with the first argument conjugated — the
    batched form of ``linalg._vdot`` (scipy's ``np.vdot`` choice).
    The scope names the reduction's ops in a device trace (``op_name``)."""
    with jax.named_scope("bucket.dots"):
        return jnp.sum(jnp.conj(a) * b, axis=-1)


def _prep(A, b, x0, tol, maxiter):
    """Shared entry glue: resolve the matvec, promote dtypes, shape the
    per-lane tolerance. Returns (matvec, b, X0, tol(B,), maxiter, B, n)."""
    mv = _maybe_faulty_mv(as_batched_matvec(A))
    b = asjnp(b)
    if b.ndim == 1:
        b = b[None, :]
    if b.ndim != 2:
        raise ValueError(f"rhs must be (B, n); got {b.shape}")
    if isinstance(A, BatchedOperator):
        if A.batch != b.shape[0]:
            raise ValueError(
                f"operator batch {A.batch} != rhs batch {b.shape[0]}"
            )
        b = b.astype(jnp.result_type(b.dtype, A.dtype))
    B, n = b.shape
    if maxiter is None:
        maxiter = n * 10
    X0 = jnp.zeros_like(b) if x0 is None else asjnp(x0).astype(b.dtype)
    if X0.ndim == 1:
        X0 = X0[None, :]
    rdt = jnp.zeros((), b.dtype).real.dtype
    tol = jnp.broadcast_to(jnp.asarray(tol, dtype=rdt), (B,))
    return mv, b, X0, tol, int(maxiter), B, n


def _lane_fields(iters, converged, stepped=None) -> dict:
    """What a solve's span and its ``batch.solve`` event say of the lanes'
    fetched counts. ``stepped`` holds, a stage, the lane-steps the program
    stepped there (the stage's width x its trips: the compiled
    ``batched_bicgstab``'s ladder, :func:`_bicgstab_ladder`); None is one
    loop at the batch's width, ``B iters_max``. ``stages`` counts the
    stages that ran a step, ``lane_steps`` sums them, and
    ``frozen_lane_pct`` is the share of the lane-steps the program stepped
    that a lane which had already stopped spent frozen under its mask,
    waiting for a lane of its stage: 100 (1 - iters_sum / lane_steps)."""
    B = int(iters.shape[0])
    iters_max, iters_sum = int(iters.max(initial=0)), int(iters.sum())
    if stepped is None:
        stepped = np.asarray([B * iters_max])
    lane_steps = int(stepped.sum())
    return {
        "iters_max": iters_max, "iters_sum": iters_sum,
        "iters_mean": iters_sum / B if B else 0.0,
        "stages": int(np.count_nonzero(stepped)), "lane_steps": lane_steps,
        "frozen_lane_pct": round(
            100.0 * (1.0 - iters_sum / lane_steps), 3
        ) if lane_steps else 0.0,
        "converged": int(np.count_nonzero(converged)),
    }


def _solve_event(solver: str, info: BatchedSolveInfo, n: int, stepped=None):
    """One ``batch.solve`` event per completed batched solve; returns the
    lanes' largest count (None with telemetry off). The lanes' counts come
    to the host in ONE fetch, and only with telemetry on (documented sync
    cost); a compiled solve's are on the host already, with what its stages
    ``stepped`` (:func:`_lane_fields`)."""
    if not telemetry.enabled():
        return None
    iters, resid2, converged = (np.asarray(a) for a in jax.device_get(
        (info.iters, info.resid2, info.converged)))
    fields = _lane_fields(iters, converged, stepped)
    telemetry.record(
        "batch.solve", solver=solver, B=int(iters.shape[0]), n=int(n),
        **fields,
    )
    # final per-lane health sweep (NaN lanes flag even when the per-iter
    # taps were off, e.g. on TPU backends)
    telemetry.health.end_batch(solver, iters, resid2, converged)
    return fields["iters_max"]


def _make_lanes_tap(solver: str):
    """Per-iteration (iter, per-lane ||r||^2, per-lane tol^2) tap for the
    masked compiled loops, or None when off — the batched analog of
    ``linalg._make_iter_tap``, with the same CPU-backend-only discipline
    (a host callback per iteration stalls the device loop it observes).
    Feeds the health monitor's per-lane detectors; converged
    (frozen) lanes are masked by their tolerance inside ``observe_lanes``
    so a finished lane's bit-stable residual never reads as stagnation."""
    if not _linalg._iter_tapping():
        return None

    def tap(k, rn2, tol2):
        telemetry.health.observe_lanes(
            solver, int(k), np.asarray(rn2), np.asarray(tol2)
        )

    return tap


# ---------------------------------------------------------------------------
# CG
# ---------------------------------------------------------------------------
def _cg_loop(matvec, b, X0, tol, maxiter, conv_test_iters, Mvec=None,
             lane_reduce=None):
    """Masked batched CG core (pure jnp, jit-safe).

    Same recurrences and test points as ``linalg._cg_device_loop``; every
    carry masks on the per-lane ``active`` flag. Returns
    ``(X, iters, resid2, converged)``.

    ``lane_reduce`` generalizes the all-converged exit for mesh-sharded
    lane stacks (``sparse_tpu.fleet``): the while condition's
    "any lane still active" test runs through it instead of the local
    ``jnp.any``, so a shard_map body passes a psum-over-the-batch-axis
    reduction and every shard exits the SAME global iteration — frozen
    (converged) lanes stay bit-stable while any shard anywhere still
    works. ``None`` (the default) traces byte-identically to the
    single-device loop.
    """
    tol2 = tol.astype(jnp.real(b).dtype) ** 2
    B = b.shape[0]
    cti = max(int(conv_test_iters), 1)
    any_active = jnp.any if lane_reduce is None else lane_reduce
    # mesh-sharded loops never tap per-iteration: a host callback from a
    # shard_map body would report LOCAL lane indices (misattributed) and
    # serialize the shards through the host; the end_batch health sweep
    # still covers fleet solves
    tap = None if lane_reduce is not None else _make_lanes_tap("cg")
    X = X0
    R = b - matvec(X)
    P = jnp.zeros_like(b)
    rho = jnp.zeros((B,), dtype=b.dtype)
    active0 = jnp.ones((B,), dtype=bool)
    iters0 = jnp.zeros((B,), dtype=jnp.int32)

    def body(st):
        X, R, P, rho, active, iters, k = st
        Z = R if Mvec is None else Mvec(R)
        rho_new = _bdot(R, Z)
        beta = rho_new / jnp.where(rho == 0, 1, rho)
        Pn = jnp.where(k == 0, Z, Z + beta[:, None] * P)
        Q = matvec(Pn)
        pq = _bdot(Pn, Q)
        alpha = rho_new / jnp.where(pq == 0, 1, pq)  # 0/0 guard: b=0/exact x0
        am = active[:, None]
        with jax.named_scope("bucket.axpy"):
            X = jnp.where(am, X + alpha[:, None] * Pn, X)
            R = jnp.where(am, R - alpha[:, None] * Q, R)
            P = jnp.where(am, Pn, P)
        rho = jnp.where(active, rho_new, rho)
        iters = iters + active.astype(jnp.int32)
        k = k + 1
        rn2 = jnp.real(_bdot(R, R))
        if tap is not None:
            jax.debug.callback(tap, k, rn2, tol2)
        tested = (k % cti == 0) | (k == maxiter - 1)
        active = active & ~(tested & (rn2 < tol2))
        return X, R, P, rho, active, iters, k

    def cond(st):
        active, k = st[4], st[6]
        return (k < maxiter) & any_active(active)

    st = (X, R, P, rho, active0, iters0, jnp.zeros((), jnp.int32))
    X, R, _P, _rho, active, iters, _k = jax.lax.while_loop(cond, body, st)
    return X, iters, jnp.real(_bdot(R, R)), ~active


def batched_cg(A, b, x0=None, tol=1e-08, maxiter=None, M=None,
               conv_test_iters=25):
    """Batched conjugate gradient over a lane stack.

    ``A`` is a :class:`~sparse_tpu.batch.operator.BatchedOperator`, a
    ``(B, n) -> (B, n)`` callable, or anything
    :func:`~sparse_tpu.batch.operator.make_batched_operator` accepts;
    ``b`` is ``(B, n)`` (``tol`` broadcasts per-lane). Returns
    ``(X, BatchedSolveInfo)``. Batch-of-1 matches :func:`sparse_tpu.
    linalg.cg` (same recurrences and conv-test points).

    ``conv_test_iters``: the residual is tested every that many steps and
    at ``maxiter - 1``. The default of 25 is the unbatched solvers' (and
    what the session's buckets rely on); it is wrong for solves shorter
    than 25 steps, which then all run 25: pass 1 for those.
    """
    mv, b, X0, tol, maxiter, _B, n = _prep(A, b, x0, tol, maxiter)
    Mvec = None if M is None else as_batched_matvec(M)
    X, iters, resid2, conv = _cg_loop(
        mv, b, X0, tol, maxiter, conv_test_iters, Mvec
    )
    info = BatchedSolveInfo(iters, resid2, conv)
    _solve_event("cg", info, n)
    return X, info


# ---------------------------------------------------------------------------
# BiCGStab
# ---------------------------------------------------------------------------
def _bicgstab_start(matvec, b, X0):
    """The masked BiCGStab's state before its first step, ``(X, R, P, V, rho,
    alpha, omega, active, iters, k)``: every lane active, ``R = b - A X0``
    (the shadow residual is this ``R``), one step counter ``k`` for the
    batch."""
    B = b.shape[0]
    R = b - matvec(X0)
    Z = jnp.zeros_like(b)
    one = jnp.ones((B,), dtype=b.dtype)
    zero = jnp.zeros((B,), dtype=b.dtype)
    return (X0, R, Z, Z, zero, one, one,
            jnp.ones((B,), bool), jnp.zeros((B,), jnp.int32),
            jnp.zeros((), jnp.int32))


def _bicgstab_step(matvec, Mvec, Rt, tol2, maxiter, cti, tap):
    """One masked step over the state of :func:`_bicgstab_start`, as a
    ``while_loop`` body: ``Rt`` the lanes' shadow residuals and ``tol2``
    their squared tolerances, at the width of the state the body is
    given."""

    def body(st):
        X, R, P, V, rho, alpha, omega, active, iters, k = st
        rho_new = _bdot(Rt, R)
        beta = (rho_new / jnp.where(rho == 0, 1, rho)) * (
            alpha / jnp.where(omega == 0, 1, omega)
        )
        Pn = jnp.where(
            k == 0, R, R + beta[:, None] * (P - omega[:, None] * V)
        )
        Ph = Pn if Mvec is None else Mvec(Pn)
        Vn = matvec(Ph)
        rv = _bdot(Rt, Vn)
        alpha_n = rho_new / jnp.where(rv == 0, 1, rv)
        S = R - alpha_n[:, None] * Vn
        Sh = S if Mvec is None else Mvec(S)
        T = matvec(Sh)
        tt = _bdot(T, T)
        omega_n = _bdot(T, S) / jnp.where(tt == 0, 1, tt)
        am = active[:, None]
        X = jnp.where(
            am, X + alpha_n[:, None] * Ph + omega_n[:, None] * Sh, X
        )
        R = jnp.where(am, S - omega_n[:, None] * T, R)
        P = jnp.where(am, Pn, P)
        V = jnp.where(am, Vn, V)
        rho = jnp.where(active, rho_new, rho)
        alpha = jnp.where(active, alpha_n, alpha)
        omega = jnp.where(active, omega_n, omega)
        iters = iters + active.astype(jnp.int32)
        k = k + 1
        rn2 = jnp.real(_bdot(R, R))
        if tap is not None:
            jax.debug.callback(tap, k, rn2, tol2)
        tested = (k % cti == 0) | (k == maxiter - 1)
        active = active & ~(tested & (rn2 < tol2))
        return X, R, P, V, rho, alpha, omega, active, iters, k

    return body


def _bicgstab_loop(matvec, b, X0, tol, maxiter, conv_test_iters,
                   Mvec=None, lane_reduce=None):
    """Masked batched BiCGStab core — the recurrences of
    ``linalg.bicgstab`` with per-lane scalars and frozen converged lanes.
    ``lane_reduce`` is the sharded all-converged exit hook (see
    :func:`_cg_loop`). ``Mvec`` right-preconditions the search
    directions (``p_hat = M p``, ``s_hat = M s``) — ``None`` (the
    default) traces byte-identically to the unpreconditioned loop. One
    ``while_loop`` at the batch's width over :func:`_bicgstab_start` and
    :func:`_bicgstab_step`, which the compiled call's ladder of widths
    (:func:`_bicgstab_ladder`) runs too."""
    tol2 = tol.astype(jnp.real(b).dtype) ** 2
    cti = max(int(conv_test_iters), 1)
    any_active = jnp.any if lane_reduce is None else lane_reduce
    # sharded loops: no per-iteration host taps (see _cg_loop)
    tap = None if lane_reduce is not None else _make_lanes_tap("bicgstab")
    st = _bicgstab_start(matvec, b, X0)
    body = _bicgstab_step(matvec, Mvec, st[1], tol2, maxiter, cti, tap)

    def cond(st):
        active, k = st[7], st[9]
        return (k < maxiter) & any_active(active)

    return _bicgstab_answers(jax.lax.while_loop(cond, body, st))


def _bicgstab_answers(st):
    """``(X, iters, resid2, converged)`` of a state's lanes."""
    X, R, active, iters = st[0], st[1], st[7], st[8]
    return X, iters, jnp.real(_bdot(R, R)), ~active


# -- the compiled call's ladder of widths ------------------------------------
# The narrowest stage, in lanes: below it a step is no longer bound by the
# bytes of its arrays and another stage only adds to the program's text
# (PERF.md, PR 56: read on the chip at 4096 and 1024). Widths are whole
# tiles of 128 lanes.
_LADDER_FLOOR = 4096
_LANE_TILE = 128


def _ladder(B: int) -> tuple:
    """The static widths of the stages of a compiled solve of ``B`` lanes:
    ``B``, then halves rounded up to whole tiles while they stay at or over
    the floor. ``(B,)`` for a batch too narrow for a second stage."""
    widths = [int(B)]
    while True:
        half = -(-widths[-1] // (2 * _LANE_TILE)) * _LANE_TILE
        if half < _LADDER_FLOOR or half >= widths[-1]:
            return tuple(widths)
        widths.append(half)


def _stage_widths(B: int, lane_operands, tapped: bool) -> tuple:
    """The widths ``jit_batched_bicgstab`` steps ``B`` lanes at, from what
    is static in it: one stage at ``B`` unless both operators say which of
    their operands hold lanes and the loop is not tapped (the CPU's
    per-step tap reports the full width)."""
    return (int(B),) if lane_operands is None or tapped else _ladder(B)


def _compact(st, Rt, tol2, place, held, narrow, width, batch):
    """A stage's active lanes moved to the front of ``width`` slots, in the
    order they had: every array with a lane axis (the state's vectors and
    lane scalars, the shadow residuals, the tolerances, the operators'
    lane-holding operands through ``narrow``) and ``place``, the lanes'
    places in the caller's ``batch`` lanes. The slots past the active count
    hold the stage's last lane, inactive, at places past the batch's end,
    where the scatter of :func:`_bicgstab_ladder` drops them: they are never
    counted, never written back and never hold a loop open."""
    X, R, P, V, rho, alpha, omega, active, iters, k = st
    (sel,) = jnp.nonzero(active, size=width, fill_value=active.shape[0] - 1)
    slots = jnp.arange(width, dtype=place.dtype)
    live = slots < jnp.count_nonzero(active)

    def take(a):
        return a.at[sel].get(mode="promise_in_bounds", indices_are_sorted=True)

    st = (*(take(a) for a in (X, R, P, V, rho, alpha, omega)),
          live, take(iters), k)
    # unique and sorted, the dropped ones too
    place = jnp.where(live, take(place), batch + slots)
    return st, take(Rt), take(tol2), place, narrow(held, take)


def _bicgstab_ladder(products, held, narrow, b, X0, tol, maxiter, cti,
                     widths):
    """The masked BiCGStab of :func:`_bicgstab_loop` down a static ladder
    of ``widths`` (:func:`_ladder`), so that lanes which are done stop
    being stepped. Stage j runs :func:`_bicgstab_step` on its ``widths[j]``
    lanes until its active lanes FIT the next width (the last one until
    none is active), then the active ones are compacted into the next
    stage (:func:`_compact`, scope ``batch.compact``) and, at that stage's
    end, their ``X``, ``iters``, ``resid2`` and ``converged`` scattered
    back to their places in the full-width results (same scope). The step
    counter ``k`` runs on through the stages, so the test cadence and
    ``maxiter - 1`` fall where they fall in the one loop, and a lane
    freezes bit-stable as there: every lane takes the steps it takes in
    :func:`_bicgstab_loop`. A stage and its compaction sit under a
    ``lax.cond`` on "a lane is still active", so a batch whose lanes stop
    together runs stage 0 alone.

    ``products(held)`` gives ``(matvec, Mvec)`` over the operators'
    operands ``held``; ``narrow(held, take)`` gives ``held`` with ``take``
    mapped over the operands that hold lanes. Returns ``(X, iters, resid2,
    converged, trips)``, ``trips [len(widths)]`` each stage's steps."""
    B, last = b.shape[0], len(widths) - 1
    tol2 = tol.astype(jnp.real(b).dtype) ** 2

    def stage(j, st, Rt, tol2, held):
        fits = widths[j + 1] if j < last else 0
        body = _bicgstab_step(*products(held), Rt, tol2, maxiter, cti, None)

        def cond(st):
            return (st[9] < maxiter) & (jnp.count_nonzero(st[7]) > fits)

        return jax.lax.while_loop(cond, body, st)

    def rest(j, st, Rt, tol2, held, place, out):
        """Stages ``j + 1`` on, from stage ``j``'s last state: the results
        and those stages' trips."""
        if j == last:
            return (*out, jnp.zeros((0,), jnp.int32))

        def go():
            with jax.named_scope("batch.compact"):
                st1, Rt1, tol21, place1, held1 = _compact(
                    st, Rt, tol2, place, held, narrow, widths[j + 1], B)
            st1 = stage(j + 1, st1, Rt1, tol21, held1)
            mine = _bicgstab_answers(st1)
            with jax.named_scope("batch.compact"):
                out1 = tuple(
                    full.at[place1].set(a, mode="drop",
                                        indices_are_sorted=True,
                                        unique_indices=True)
                    for full, a in zip(out, mine))
            *out2, trips = rest(j + 1, st1, Rt1, tol21, held1, place1, out1)
            return (*out2, jnp.concatenate([(st1[9] - st[9])[None], trips]))

        return jax.lax.cond(
            (st[9] < maxiter) & jnp.any(st[7]), go,
            lambda: (*out, jnp.zeros((last - j,), jnp.int32)))

    st = _bicgstab_start(products(held)[0], b, X0)
    Rt = st[1]
    st = stage(0, st, Rt, tol2, held)
    *out, trips = rest(0, st, Rt, tol2, held, jnp.arange(B, dtype=jnp.int32),
                       _bicgstab_answers(st))
    return (*out, jnp.concatenate([st[9][None], trips]))


def batched_ir(A, b, x0=None, tol=1e-08, maxiter=None, M=None,
               conv_test_iters=25, policy="f32ir", **kwargs):
    """Batched mixed-precision iterative refinement (ISSUE 15): inner
    reduced-precision CG sweeps under an f64 residual-and-correct outer
    loop, per-lane freeze masks at both levels — the first-class ``ir``
    solver of :mod:`sparse_tpu.mixed`. Same lane contract as
    :func:`batched_cg` (absolute per-lane ``||r|| < tol``, evaluated in
    f64); the returned info additionally carries ``info.outer``."""
    from ..mixed import ir_solve

    return ir_solve(A, b, x0=x0, tol=tol, maxiter=maxiter, M=M,
                    conv_test_iters=conv_test_iters, policy=policy,
                    **kwargs)


_BICGSTAB_TRACES = _metrics.counter(
    "batch.bicgstab.traces",
    help="traces of the compiled batched BiCGStab over declared operators "
    "(krylov._bicgstab_lanes, the program jit_batched_bicgstab): one per "
    "program built, none for a call that reuses one",
)


def _bicgstab_lanes(a_operands, m_operands, b, x0, tol, maxiter, *, a_apply,
                    m_apply, conv_test_iters, tapped, lane_operands=None):
    """Whole-solve masked BiCGStab over declared batched operators: A's
    operands, M's operands, ``b``, the start, the lanes' ``tol`` and
    ``maxiter`` all arguments, only structure static (the two ``apply``
    functions, the operands' shapes and which of them hold lanes, the test
    cadence, whether the loop is tapped), so nothing an operator holds is a
    constant of the program. The step is :func:`_bicgstab_loop`'s as the
    eager call runs it; the scopes ``batch.spmv`` and ``batch.precond``
    name the products and the preconditioner's applies in a device trace, as
    ``bucket.dots`` names the reductions.

    Where both operators say which operands hold lanes (``lane_operands``:
    A's flags and M's), the loop is not tapped and the batch is wide enough
    (:func:`_stage_widths`), the solve runs down the ladder of
    :func:`_bicgstab_ladder`, ``batch.compact`` the scope of its gathers
    and scatters; else it is the one loop at the batch's width.

    Returns ``(X, counts)``, ``counts [3, B]`` the lanes' ``iters``,
    ``resid2`` and ``converged`` as ONE int32 array for the solve's one
    fetch, the float32 residuals by their bits (:func:`_lane_counts` reads
    them back); the ladder adds a fourth row, whose first entries are its
    stages' trips. Integers, because a TPU flushes float32 denormals to
    zero, which is what a small count's bits are: the other way round every
    count came back 0 on the chip (PR 55)."""
    _BICGSTAB_TRACES.inc()

    def products(held):
        a_held, m_held = held

        def matvec(X):
            with jax.named_scope("batch.spmv"):
                return a_apply(a_held, X)

        def precond(R):
            with jax.named_scope("batch.precond"):
                return m_apply(m_held, R)

        return matvec, None if m_apply is None else precond

    def narrow(held, take):
        return tuple(
            tuple(jax.tree.map(take, op) if lanes else op
                  for op, lanes in zip(ops, flags))
            for ops, flags in zip(held, lane_operands))

    B = b.shape[0]
    widths = _stage_widths(B, lane_operands, tapped)
    held = (a_operands, m_operands)
    if len(widths) == 1:
        matvec, precond = products(held)
        X, iters, resid2, conv = _bicgstab_loop(
            matvec, b, x0, tol, maxiter, conv_test_iters, precond)
        rows = []
    else:
        X, iters, resid2, conv, trips = _bicgstab_ladder(
            products, held, narrow, b, x0, tol, maxiter, conv_test_iters,
            widths)
        rows = [jnp.pad(trips, (0, B - len(widths)))]
    return X, jnp.stack([
        iters, jax.lax.bitcast_convert_type(resid2, jnp.int32),
        conv.astype(jnp.int32), *rows])


_bicgstab_lanes.__name__ = _bicgstab_lanes.__qualname__ = "batched_bicgstab"
_bicgstab_program = jax.jit(
    _bicgstab_lanes,
    static_argnames=("a_apply", "m_apply", "conv_test_iters", "tapped",
                     "lane_operands"),
)


def _lane_counts(counts, static):
    """The compiled solve's one fetch and what its span says of it; the
    lanes' arrays and what each stage stepped (width x trips) ride along
    as ``lanes`` for the caller (``linalg._run_compiled_solve`` takes them
    off the fields)."""
    syncs0 = _linalg.HOST_SYNCS
    iters, resid2, converged, *trips = _linalg._sync_fetch(counts)
    converged = converged.astype(bool)
    stepped = None
    if trips:
        widths = _stage_widths(iters.shape[0], static["lane_operands"],
                               static["tapped"])
        stepped = np.asarray(widths, np.int64) * trips[0][:len(widths)]
    return {**_lane_fields(iters, converged, stepped),
            "fetches": _linalg.HOST_SYNCS - syncs0,
            "lanes": (iters, resid2.view(np.float32), converged, stepped)}


_BICGSTAB = _linalg._CompiledSolve(
    _bicgstab_program,
    ("batched_bicgstab.solve", "batched_bicgstab.dispatch",
     "batched_bicgstab.fetch"),
    _lane_counts)


def _lanes_call(A, M, b, X0, tol, maxiter, conv_test_iters):
    """``(args, static)`` with which ``jit_batched_bicgstab`` runs this
    solve, or None where the eager loop takes it: the rule
    ``linalg._declared_pair`` states for the unbatched solves (both sides
    declare what they hold, no outer trace is open, no fault clause wraps
    the product), for float32 lanes, which the packed counts are made for."""
    if (in_trace() or b.dtype != jnp.float32
            or getattr(A, "apply", None) is None
            or not (M is None or getattr(M, "apply", None) is not None)
            or (_faults.ACTIVE and _faults.targets("matvec"))):
        return None
    m_apply, m_operands = (None, ()) if M is None else (M.apply, M.operands)
    return ((A.operands, m_operands, b, X0, tol, _linalg._i32(maxiter)),
            dict(a_apply=A.apply, m_apply=m_apply,
                 conv_test_iters=max(int(conv_test_iters), 1),
                 tapped=_linalg._iter_tapping(),
                 lane_operands=_lane_operands(A, M)))


def _lane_operands(A, M):
    """Which operands of a declared pair hold lanes, ``(A's flags, M's
    flags)`` with a flag an operand (``lane_operands`` beside ``apply`` and
    ``operands``: True for an operand whose arrays have the lanes as their
    leading axis, which the ladder's compaction gathers; False for one the
    lanes share), or None where a side does not say: the solve is then one
    loop at the batch's width."""
    flags = []
    for op in (A, M):
        said = () if op is None else getattr(op, "lane_operands", None)
        if said is None or len(said) != len(getattr(op, "operands", ())):
            return None
        flags.append(tuple(bool(x) for x in said))
    return tuple(flags)


def _lanes_fields(A, M, b) -> dict:
    """What the compiled solve's span says of its operands."""
    B, n = b.shape
    fields = {"B": int(B), "n": int(n),
              "precond": "none" if M is None else getattr(
                  M, "describe", {}).get("precond", "declared")}
    offsets = getattr(A.apply, "offsets", None)
    return fields if offsets is None else {**fields, "diags": len(offsets)}


def batched_bicgstab(A, b, x0=None, tol=1e-08, maxiter=None, M=None,
                     conv_test_iters=25):
    """Batched BiCGStab; see :func:`batched_cg` for the lane contract and
    for ``conv_test_iters``' default. ``M`` right-preconditions (applied to
    the search directions), so the residual recurrence — and the stopping
    rule — stay those of the unpreconditioned solver.

    Over an operator and a preconditioner that declare what they hold
    (``BatchedDIA``; the Jacobi factory's ``Mvec``, or none), in float32,
    the call is ONE compiled program, ``jit_batched_bicgstab``
    (:func:`_bicgstab_lanes`), found again by the two ``apply`` functions
    and the shapes: other values, another ``b``, ``x0``, ``tol`` or
    ``maxiter`` trace and compile nothing. One dispatch and one fetch a
    call; ``info``'s arrays are then on the host. Where both sides say
    which of their operands hold lanes (``lane_operands``: both of those
    do) and the batch is wide enough, the program compacts its active
    lanes down a ladder of halving widths (:func:`_bicgstab_ladder`): every
    lane's count and place are the one loop's. Anything else runs the
    loop eagerly, compiled anew at every call, and ``info`` stays on the
    device."""
    with _linalg._solver_call():
        mv, b, X0, tol, maxiter, _B, n = _prep(A, b, x0, tol, maxiter)
        call = _lanes_call(A, M, b, X0, tol, maxiter, conv_test_iters)
        if call is not None:
            X, (*lanes, stepped) = _linalg._run_compiled_solve(
                _BICGSTAB, call, _lanes_fields(A, M, b))
        else:
            Mvec = None if M is None else as_batched_matvec(M)
            X, *lanes = _bicgstab_loop(
                mv, b, X0, tol, maxiter, conv_test_iters, Mvec
            )
            stepped = None
        info = BatchedSolveInfo(*lanes)
        iters_max = _solve_event("bicgstab", info, n, stepped)
        _linalg._solve_event("batched_bicgstab", n, iters_max, "device")
    return X, info


def _bicgstab_compiled(A, b, M=None, conv_test_iters=25):
    """The executable ``batched_bicgstab(A, b, M=M)`` runs (its scopes:
    ``batch.spmv``, ``batch.precond``, ``bucket.dots``), or None where that
    call takes the eager loop (``linalg._compiled_call``)."""
    _mv, b, X0, tol, maxiter, _B, _n = _prep(A, b, None, 1e-8, 1)
    return _linalg._compiled_call(
        _BICGSTAB, _lanes_call(A, M, b, X0, tol, maxiter, conv_test_iters))


# ---------------------------------------------------------------------------
# GMRES — the library's Arnoldi cycle with a lane axis in front, restarts
# inside the loop
# ---------------------------------------------------------------------------
def _gmres_arnoldi_lanes(mv, Mv, R, beta, target, restart: int,
                         orth_blocks=None):
    """The Arnoldi process of ``linalg._gmres_arnoldi`` for B lanes at once,
    from the lanes' (preconditioned) residuals ``R [B, n]`` of norms ``beta
    [B]``: the same step (``linalg._orth_against``, ``_givens_column``), the
    same basis layout a row to a tile with the lane axis in front (``[B,
    restart + 1, R, 128]``; ``linalg._basis_*``), and ONE step counter ``j``
    for the bucket. A lane that converges, breaks down or starts at its
    target is ``done``: its Hessenberg, the matrix ``Q`` of its accumulated
    rotations (so its right-hand side, ``beta Q[:, 0]``) and its column
    count ``kk`` freeze under the mask while ``j`` finishes the bucket's
    last lane (the loop ends at ``restart`` or when every lane is done).
    The stage ``V[:, :his[j // block]]`` of ``linalg._orth_stages``
    is chosen by ``j``, one ``lax.switch`` index for the bucket: a ``vmap``
    of the library's loop would turn its carry into a select over the whole
    basis and run every stage. One basis row is written a step and lane, in
    place and unmasked: a done lane's later rows take no part in its answer
    (their coefficients ``y`` are zero past ``kk``), and they stay finite
    (normalised vectors, or zero after a breakdown). ``orth_blocks`` are the
    stages' blocks of the orthogonalisation's kernel (``linalg._orth_blocks``,
    given by who builds the program), None for four contractions.

    ``(V, H, g, kk, breakdown)``: per lane what the library's gives."""
    from ..linalg import (_basis_flat, _basis_tiles, _givens_column,
                          _givens_rhs, _orth_stage_steps, _orth_stages)

    dt = R.dtype
    B, n = R.shape
    with jax.named_scope("bucket.gmres.update"):
        start_ok = beta > target
        beta_safe = jnp.where(start_ok, beta, 1.0)
        v0 = _basis_tiles(R / beta_safe[:, None].astype(dt))
        V = jnp.zeros((B, restart + 1, *v0.shape[1:]), dtype=dt)
        V = V.at[:, 0].set(v0)
    H = jnp.zeros((B, restart + 1, restart), dtype=dt)
    Q = jnp.broadcast_to(jnp.eye(restart + 1, dtype=dt),
                         (B, restart + 1, restart + 1))

    block, _his = _orth_stages(restart)
    stages = _orth_stage_steps(restart, orth_blocks)
    # the step's scalars are a lane's own; the column index is the bucket's
    givens = jax.vmap(_givens_column, in_axes=(0, 0, 0, 0, 0, None, 0))

    def cond(st):
        done, j = st[5], st[6]
        return (j < restart) & jnp.any(~done)

    def body(st):
        V, H, Q, kk, bd, done, j = st
        with jax.named_scope("bucket.gmres.spmv"):
            vj = jax.lax.dynamic_index_in_dim(V, j, 1, keepdims=False)
            w = _basis_tiles(Mv(mv(_basis_flat(vj, n))))
        with jax.named_scope("bucket.gmres.orth"):
            hcol, w, ww = jax.lax.switch(j // block, stages, V, w, j)
        with jax.named_scope("bucket.gmres.small"):
            hkk = jnp.sqrt(ww)
            grew = hkk > 1e-30
        with jax.named_scope("bucket.gmres.update"):
            # j + 1 <= restart: a plain in-place write of each lane's one
            # row, the division inside its fusion
            scale = jnp.where(grew, hkk, 1.0)[:, None, None]
            V = jax.lax.dynamic_update_index_in_dim(
                V, jnp.where(grew[:, None, None], w / scale, 0.0
                             ).astype(dt)[:, None], j + 1, 1)
        with jax.named_scope("bucket.gmres.small"):
            Hn, Qn, breakdown, conv = givens(
                hcol, hkk, H, Q, beta, j, target)
            upd = ~done
            H = jnp.where(upd[:, None, None], Hn, H)
            Q = jnp.where(upd[:, None, None], Qn, Q)
            kk = kk + (upd & ~breakdown).astype(jnp.int32)
            bd = bd | (upd & breakdown)
            done = done | (upd & (breakdown | conv))
        return V, H, Q, kk, bd, done, j + 1

    st = (V, H, Q, jnp.zeros((B,), jnp.int32),
          jnp.zeros((B,), bool), ~start_ok, jnp.int32(0))
    V, H, Q, kk, bd, _done, _j = jax.lax.while_loop(cond, body, st)
    with jax.named_scope("bucket.gmres.small"):
        g = jax.vmap(_givens_rhs)(Q, beta)
    return V, H, g, kk, bd


def _gmres_cycle_lanes(mv, Mv, X, b, target, restart: int, orth_blocks=None):
    """One restart cycle of ``linalg._gmres_cycle`` for B lanes: the lanes'
    residuals, the Arnoldi process from them (:func:`_gmres_arnoldi_lanes`),
    each lane's small triangular solve, ``X += y V``. ``(X', kk, beta,
    breakdown)``, each but ``X'`` a ``(B,)`` vector: the steps that gave a
    column, the entry residual norm, whether a step broke down; ``kk == 0``
    with no breakdown is a lane at its target on entry, whose ``X`` comes
    back as it went in. The scopes ``bucket.gmres.spmv``, ``.orth``,
    ``.small`` and ``.update`` name the work as the library's ``gmres.*``
    name its own."""
    from ..linalg import _basis_combine, _basis_flat, _hessenberg_solve

    with jax.named_scope("bucket.gmres.spmv"):
        AX = mv(X)
    with jax.named_scope("bucket.gmres.update"):
        R = b - AX
    with jax.named_scope("bucket.gmres.spmv"):
        R = Mv(R)
    with jax.named_scope("bucket.gmres.update"):
        beta = jnp.linalg.norm(R, axis=-1)
    V, H, g, kk, bd = _gmres_arnoldi_lanes(mv, Mv, R, beta, target, restart,
                                           orth_blocks)
    with jax.named_scope("bucket.gmres.small"):
        y = jax.vmap(_hessenberg_solve)(H, g, kk)
    with jax.named_scope("bucket.gmres.update"):
        X = X + _basis_flat(_basis_combine(y, V[:, :restart]), X.shape[-1])
    return X, kk, beta, bd


def _gmres_loop(matvec, b, X0, target, cycles, restart: int, Mvec=None,
                orth_blocks=None):
    """Masked batched restarted GMRES, the whole solve (pure jnp, jit-safe):
    a ``lax.while_loop`` over the restart cycles of
    :func:`_gmres_cycle_lanes`, as ``linalg._gmres`` has them over its own.
    ``target [B]`` are the lanes' absolute residual targets and ``cycles``
    bounds the passes (a traced or a Python integer). A lane is finished
    when a cycle finds its (preconditioned) residual at its target ON ENTRY,
    the library's rule: a lane whose recurrence says converged mid-cycle is
    checked against its true residual by the next pass, and goes on if that
    disagrees. A finished lane freezes: its ``X`` passes through every later
    cycle unchanged (its ``y`` is zero), its counts stop. The loop ends when
    every lane is finished or the passes are spent. ``orth_blocks`` goes to
    :func:`_gmres_arnoldi_lanes`.

    Returns ``(X, iters, resid2, converged, cycles_run)``: per lane the
    Arnoldi steps counted as the library counts them (a breakdown's stage
    included), the square of the last entry residual norm read while the
    lane was not finished, whether it finished; and the passes in which some
    lane made a step."""
    Mv = (lambda r: r) if Mvec is None else Mvec
    B = b.shape[0]
    tap = _make_lanes_tap("gmres")

    def cond(st):
        done, c = st[3], st[4]
        return (c < cycles) & jnp.any(~done)

    def body(st):
        X, iters, beta_last, done, c, worked = st
        X, kk, beta, bd = _gmres_cycle_lanes(matvec, Mv, X, b, target,
                                             restart, orth_blocks)
        if tap is not None:
            # cycle granularity: the entry residuals, squared to the health
            # monitor's resid2 convention
            jax.debug.callback(tap, c + 1, beta * beta, target * target)
        steps = jnp.where(done, 0, kk + bd.astype(jnp.int32))
        beta_last = jnp.where(done, beta_last, beta)
        done = done | ((kk == 0) & ~bd)
        worked = worked + jnp.any(steps > 0).astype(jnp.int32)
        return X, iters + steps, beta_last, done, c + 1, worked

    zero = jnp.zeros((), jnp.int32)
    st = (X0, jnp.zeros((B,), jnp.int32), jnp.zeros_like(target),
          jnp.zeros((B,), bool), zero, zero)
    X, iters, beta_last, done, _c, worked = jax.lax.while_loop(cond, body, st)
    return X, iters, beta_last * beta_last, done, worked


def batched_gmres(A, b, x0=None, tol=1e-08, restart=None, maxiter=None,
                  M=None, atol=None):
    """Batched restarted GMRES: the whole solve one masked loop
    (:func:`_gmres_loop`: the restarts inside it, no host round trip a
    cycle), per-lane masks at both granularities (a lane freezes mid-cycle
    when its recurrence converges or breaks down, and for good once a cycle
    finds it at its target).

    Same stopping rule as :func:`sparse_tpu.linalg.gmres`: relative
    ``tol * ||b||`` floored by ``atol``, per lane; ``maxiter`` counts
    restart cycles. Returns ``(X, BatchedSolveInfo)``; ``info.iters``
    counts inner iterations (breakdown stages included) exactly like the
    unbatched driver.
    """
    mv = _maybe_faulty_mv(as_batched_matvec(A))
    b = asjnp(b)
    if b.ndim == 1:
        b = b[None, :]
    dt = b.dtype
    if isinstance(A, BatchedOperator):
        dt = jnp.result_type(dt, A.dtype)
    if x0 is not None:
        x0 = asjnp(x0)
        if x0.ndim == 1:
            x0 = x0[None, :]
        dt = jnp.result_type(dt, x0.dtype)
    b = b.astype(dt)
    B, n = b.shape
    if restart is None:
        restart = min(20, n)
    restart = min(int(restart), n)
    if maxiter is None:
        maxiter = max(n // restart, 1) * 10
    X = jnp.zeros_like(b) if x0 is None else x0.astype(dt)
    rdt = jnp.zeros((), dt).real.dtype
    bnorm = jnp.linalg.norm(b, axis=-1)
    tol_l = jnp.broadcast_to(jnp.asarray(tol, rdt), (B,))
    target = jnp.maximum(tol_l * bnorm, atol if atol is not None else 0.0)
    target = jnp.maximum(target, 1e-30).astype(rdt)

    Mvec = None if M is None else as_batched_matvec(M)
    X, iters, resid2, done, _cycles = _gmres_loop(
        mv, b, X, target, int(maxiter), restart, Mvec)
    info = BatchedSolveInfo(iters, resid2, done)
    _solve_event("gmres", info, n)
    return X, info
