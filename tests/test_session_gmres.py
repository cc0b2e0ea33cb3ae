"""The session's GMRES bucket program (PR 49): ``SolveSession("gmres")``
builds ONE compiled program a (pattern, bucket, dtype, restart), the whole
solve on the library's Arnoldi cycle with a lane axis in front
(``batch/krylov.py`` ``_gmres_loop``), and its answers are the plain
reference's (``benchmark/operators/cfd_step.py``, which imports nothing of
the program) on seeded values, tight enough that contractions in bfloat16
fail the same comparison.
"""

import time

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import sparse_tpu
from sparse_tpu import linalg, telemetry
from sparse_tpu.batch import SolveSession, krylov
from sparse_tpu.config import settings
from sparse_tpu.telemetry import _metrics

from .utils.spd import operator_module

ref = operator_module("cfd_step")
BOX = (9, 7, 6)
SHIFT, REL_TOL, RESTART = 0.125, 1e-5, 12
# the comparison: x within X_LIMIT of the converged reference, the true
# relative residual within twice what was asked, every lane converged
X_LIMIT = 5e-4
TRACES = _metrics.counter("batch.gmres.traces")


def _ensemble(members, seed=7, box=BOX):
    return ref.make({"box": box, "clients": members, "shift": SHIFT,
                     "restart": RESTART, "rel_tol": REL_TOL,
                     "check_sample": members}, seed)


def _shuffled(d, seed=3):
    """The same systems under one random symmetric permutation: a pattern
    that is no band. ``(pattern, values [members, nnz], perm)`` with
    ``A_p = A[perm][:, perm]``."""
    n = d["rows"]
    perm = np.random.default_rng(seed).permutation(n)
    P = d["pattern"]
    first = sp.csr_matrix((np.arange(1, P.nnz + 1, dtype=np.float64),
                           P.indices, P.indptr), shape=(n, n))
    moved = first[perm][:, perm].tocsr()
    moved.sort_indices()
    src = moved.data.astype(np.int64) - 1  # where each entry came from
    pat = sp.csr_matrix((np.ones(P.nnz, np.float32), moved.indices,
                         moved.indptr), shape=(n, n))
    return pat, d["values"][:, src], perm


def _solve(ses, pattern, values, rhs, x0=None, tol=None, maxiter=None):
    pat = ses.pattern_of(pattern)
    tickets = [
        ses.submit(values[k], rhs[k], x0=None if x0 is None else x0[k],
                   tol=(REL_TOL * float(np.linalg.norm(rhs[k]))
                        if tol is None else tol[k]),
                   maxiter=maxiter, pattern=pat)
        for k in range(len(rhs))]
    ses.flush()
    return tickets


def _compare(d, tickets, rhs, unshuffle=None):
    """Worst (x_vs_reference, relres / asked) of the tickets' answers against
    the plain reference and the float64 residual of the benchmark's own
    operator; ``unshuffle`` is the permutation the systems were solved
    under."""
    worst_x = worst_r = 0.0
    for k, t in enumerate(tickets):
        x = np.asarray(t.result()[0])
        b = rhs[k]
        if unshuffle is not None:
            inv = np.empty_like(unshuffle)
            inv[unshuffle] = np.arange(len(unshuffle))
            x, b = x[inv], b[inv]
        if not np.all(np.isfinite(x)):
            return np.inf, np.inf
        nums = ref.compare(d, x, ref.reference_gmres(d, k, b),
                           d["values"][k], b)
        worst_x = max(worst_x, nums["x_vs_reference"])
        worst_r = max(worst_r, nums["relres"] / REL_TOL)
    return worst_x, worst_r


def _rhs(d, members):
    return [np.float32(d["carry"]) * d["initial"][k] + d["source"][k]
            for k in range(members)]


@pytest.mark.parametrize("members", [1, 3, 8])
@pytest.mark.parametrize("form", ["planes", "sell"])
def test_the_bucket_program_gives_the_plain_references_answers(
        monkeypatch, form, members):
    """Over the 3-D nonsymmetric box the product is ``planes``, over the same
    systems shuffled it is ``sell``; both agree with the reference."""
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.reset()
    try:
        d = _ensemble(members)
        rhs = _rhs(d, members)
        pattern, values, perm = d["pattern"], d["values"], None
        if form == "sell":
            pattern, values, perm = _shuffled(d)
            rhs = [b[perm] for b in rhs]
        ses = SolveSession("gmres", restart=RESTART, batch_max=8,
                           warm_start=False)
        tickets = _solve(ses, pattern, values, rhs)
        assert all(t.converged for t in tickets)
        worst_x, worst_r = _compare(d, tickets, rhs, perm)
        assert worst_x <= X_LIMIT and worst_r <= 2.0, (worst_x, worst_r)
        (ev,) = telemetry.events("batch.dispatch")
        assert ev["matvec"] == form and ev["batch"] == members
        assert ev["restart"] == RESTART and ev["fetches"] == 1
        its = [int(t.result()[1]) for t in tickets]
        assert ev["iters_max"] == max(its) and ev["iters_sum"] == sum(its)
        assert ev["cycles_max"] == -(-max(its) // RESTART) >= 2
        assert ev["frozen_lane_pct"] == pytest.approx(
            100 * (1 - sum(its) / (members * max(its))), abs=1e-3)
        rows = 8 * -(-d["rows"] // 1024)
        assert ev["basis_gb"] == pytest.approx(
            ev["bucket"] * (RESTART + 1) * rows * 128 * 4 / 1e9, rel=1e-3)
        assert telemetry.schema.validate(ev) == []
    finally:
        telemetry.reset()


def _bfloat16(fn):
    def low(*args):
        return fn(*(a.astype(jnp.bfloat16) for a in args)).astype(jnp.float32)
    return low


def test_contractions_in_bfloat16_fail_the_same_comparison(monkeypatch):
    """The comparison's point: the program's answers pass it, and the same
    program with its two contractions against the basis rounded to bfloat16
    (the nearest precision below) does not. The second orthogonalisation
    pass and the true residual at every restart absorb most of such a
    rounding (each cycle then gains three digits and no more, as an
    iterative refinement would), so what fails is the budget: the sound
    program is done in one cycle of 30 and the pass that finds it so, the
    rounded one is not."""
    restart = 30
    d = _ensemble(3, seed=11)
    rhs = _rhs(d, 3)

    def readings(passes):
        ses = SolveSession("gmres", restart=restart, batch_max=4,
                           warm_start=False, requeue=False)
        # a new pattern object: a program of its own, traced now
        pattern = sp.csr_matrix(d["pattern"])
        tickets = _solve(ses, pattern, d["values"], rhs,
                         maxiter=passes * restart)
        steps = max(int(t.result()[1]) for t in tickets)
        return (*_compare(d, tickets, rhs),
                all(t.converged for t in tickets), steps)

    x, r, conv, steps = readings(2)
    assert x <= X_LIMIT and r <= 2.0 and conv and steps <= restart
    monkeypatch.setattr(linalg, "_basis_project",
                        _bfloat16(linalg._basis_project))
    monkeypatch.setattr(linalg, "_basis_combine",
                        _bfloat16(linalg._basis_combine))
    x, r, conv, steps = readings(2)
    assert not conv and steps > restart, (x, r, conv, steps)


def test_a_frozen_lane_keeps_what_it_had_and_a_pad_lane_changes_nothing():
    """Three lanes that converge at different steps (and a pad lane) in one
    bucket of four: each lane's answer, ``iters`` and ``resid2`` are those of
    the lane solved alone."""
    d = _ensemble(3, seed=5)
    rhs = _rhs(d, 3)
    # the second lane asks for less, the third starts from its answer
    tols = [REL_TOL * float(np.linalg.norm(b)) for b in rhs]
    tols[1] *= 1e3
    easy = SolveSession("gmres", restart=RESTART, batch_max=1,
                        warm_start=False)
    x0 = [np.zeros_like(b) for b in rhs]
    x0[2] = np.asarray(_solve(easy, d["pattern"], d["values"][2:],
                              rhs[2:], tol=tols[2:])[0].result()[0])
    together = SolveSession("gmres", restart=RESTART, batch_max=4,
                            warm_start=False)
    lanes = _solve(together, d["pattern"], d["values"], rhs, x0=x0, tol=tols)
    its = [int(t.result()[1]) for t in lanes]
    assert its[2] == 0 < its[1] < its[0] and its[0] > RESTART
    for k, t in enumerate(lanes):
        alone = SolveSession("gmres", restart=RESTART, batch_max=1,
                             warm_start=False)
        (one,) = _solve(alone, d["pattern"], d["values"][k:k + 1],
                        rhs[k:k + 1], x0=x0[k:k + 1], tol=tols[k:k + 1])
        xa, ia, ra = one.result()
        xt, it, rt = t.result()
        assert int(it) == int(ia) and t.converged and one.converged
        np.testing.assert_allclose(xt, xa, rtol=2e-6, atol=2e-6)
        # a residual at float32's floor: its own last bits are noise
        np.testing.assert_allclose(rt, ra, rtol=5e-2, atol=1e-12)


@pytest.mark.parametrize("members", [1, 3])
def test_three_cycles_inside_the_program_are_three_host_driven_cycles(members):
    """The restarts inside the program: a solve of exactly three cycles
    equals the library's cycle path (one compiled cycle, driven from the
    host with one fetch a cycle) lane by lane, to rounding."""
    d = _ensemble(members, seed=13)
    rhs = _rhs(d, members)
    ses = SolveSession("gmres", restart=RESTART, batch_max=4,
                       warm_start=False, requeue=False)
    tickets = _solve(ses, d["pattern"], d["values"], rhs,
                     tol=[1e-30] * members, maxiter=3 * RESTART)
    P = d["pattern"]
    for k, t in enumerate(tickets):
        x, iters, _r2 = t.result()
        A = sparse_tpu.csr_array(sp.csr_matrix(
            (d["values"][k], P.indices, P.indptr), shape=P.shape))
        closure = linalg.LinearOperator(A.shape, matvec=A.dot, dtype=A.dtype)
        syncs = linalg.HOST_SYNCS
        xh, ih = linalg.gmres(closure, jnp.asarray(rhs[k]), restart=RESTART,
                              maxiter=3, tol=0.0, atol=1e-30)
        assert linalg.HOST_SYNCS - syncs == 3  # one fetch a cycle
        assert int(iters) == ih == 3 * RESTART
        np.testing.assert_allclose(x, np.asarray(xh), rtol=2e-5, atol=2e-6)


def test_maxiter_counts_inner_steps_rounded_up_to_whole_cycles():
    d = _ensemble(1, seed=17)
    rhs = _rhs(d, 1)
    for maxiter, steps in ((1, RESTART), (RESTART, RESTART),
                           (RESTART + 1, 2 * RESTART)):
        ses = SolveSession("gmres", restart=RESTART, batch_max=1,
                           warm_start=False, requeue=False)
        (t,) = _solve(ses, sp.csr_matrix(d["pattern"]), d["values"], rhs,
                      tol=[1e-30], maxiter=maxiter)
        assert int(t.result()[1]) == steps and not t.converged


def _dispatch_is_asynchronous() -> bool:
    """Whether a jitted call on this backend returns before its work is
    done, read off a call that takes a tenth of a second."""
    step = jax.jit(lambda x: jax.lax.fori_loop(
        0, 60, lambda _, y: y @ y / jnp.linalg.norm(y), x))
    x = jnp.ones((500, 500), jnp.float32)
    step(x).block_until_ready()
    t0 = time.perf_counter()
    y = step(x)
    t_call = time.perf_counter() - t0
    y.block_until_ready()
    return t_call < 0.5 * (time.perf_counter() - t0)


def test_two_dispatches_trace_once_and_return_before_the_solve_is_done():
    """A second dispatch of one (pattern, bucket) neither traces nor
    compiles, and on a backend that runs asynchronously the dispatch returns
    while the solve runs: ``inflight`` 2 overlaps the next bucket's pack
    with it."""
    box = (40, 40, 30)
    d = _ensemble(2, seed=19, box=box)
    rhs = _rhs(d, 2)
    ses = SolveSession("gmres", restart=30, batch_max=2, inflight=2,
                       warm_start=False, requeue=False)
    pat = ses.pattern_of(d["pattern"])
    traces0 = TRACES.value

    def dispatch():
        tickets = [ses.submit(d["values"][k], rhs[k], tol=1e-30,
                              maxiter=150, pattern=pat) for k in range(2)]
        t0 = time.perf_counter()
        ses.flush(wait=False)
        t_call = time.perf_counter() - t0
        ready = ses._inflight[0].is_ready()
        tickets[0].result()
        return t_call, time.perf_counter() - t0, ready

    dispatch()
    assert TRACES.value == traces0 + 1
    misses = sparse_tpu.plan_cache.snapshot()
    t_call, t_done, ready = dispatch()
    assert TRACES.value == traces0 + 1  # the program was found again
    assert sparse_tpu.plan_cache.delta(misses)["misses"] == 0
    if not _dispatch_is_asynchronous():
        pytest.skip("this backend runs a dispatch to its end")
    assert not ready and t_call < 0.5 * t_done, (t_call, t_done)


def test_the_public_function_runs_the_same_loop_and_no_jit_of_its_own():
    """``krylov.batched_gmres`` is the loop of the bucket program: the same
    answers as the session's, and the full-basis ``einsum`` cycle with its
    per-call ``jax.jit`` is gone."""
    assert not hasattr(krylov, "_make_batched_gmres_cycle")
    d = _ensemble(3, seed=23)
    rhs = np.stack(_rhs(d, 3))
    from sparse_tpu.batch import BatchedCSR, SparsityPattern

    pat = SparsityPattern.from_csr(d["pattern"])
    X, info = krylov.batched_gmres(BatchedCSR(pat, d["values"]), rhs,
                                   tol=REL_TOL, restart=RESTART)
    ses = SolveSession("gmres", restart=RESTART, batch_max=4,
                       warm_start=False)
    tickets = _solve(ses, d["pattern"], d["values"], list(rhs))
    for k, t in enumerate(tickets):
        x, iters, r2 = t.result()
        assert int(np.asarray(info.iters)[k]) == int(iters)
        np.testing.assert_allclose(np.asarray(X)[k], x, rtol=2e-5, atol=2e-6)
    assert bool(np.all(np.asarray(info.converged)))


def test_a_done_lane_keeps_its_hessenberg_rotations_and_rhs_to_the_bit(
        monkeypatch):
    """A lane whose recurrence reaches its target at the fifth step, beside a
    lane that ends with it and beside one that runs the cycle out: the same
    program on the same shapes, so what the first lane holds may differ by
    the steps ``j`` made after it was done and by nothing else. ``H``, ``g``,
    ``kk`` and (returned in ``g``'s place by a patched ``_givens_rhs``) the
    accumulated rotations ``Q`` are the same bits."""
    n, m, stop = 200, 12, 5
    rng = np.random.default_rng(31)
    A = jnp.asarray((rng.uniform(-1, 1, (n, n)) / np.sqrt(n)
                     + 2.0 * np.eye(n)).astype(np.float32))
    r = jnp.asarray(rng.uniform(0.5, 1.5, (2, n)).astype(np.float32))
    mv = lambda X: jnp.sum(A[None] * X[:, None, :], axis=-1)  # noqa: E731
    ident = lambda X: X  # noqa: E731

    def lanes(R, target):
        beta = jnp.linalg.norm(R, axis=-1)
        return krylov._gmres_arnoldi_lanes(
            mv, ident, R, beta, jnp.asarray(target, jnp.float32), m)

    # the first lane's residuals by step: g[k + 1] as each step leaves it
    whole = lanes(r[:1], [0.0])
    assert int(whole[3][0]) == m
    y = np.abs(np.asarray(whole[2][0], np.float64))
    history = np.sqrt(np.cumsum((y ** 2)[::-1])[::-1])[1:]  # |g[k+1]| then
    assert all(a > b for a, b in zip(history, history[1:]))
    target = float(np.sqrt(history[stop - 1] * history[stop - 2]))

    for g_is_q in (False, True):
        with monkeypatch.context() as mp:
            if g_is_q:
                mp.setattr(linalg, "_givens_rhs", lambda Q, beta: Q)
            pair = lanes(jnp.stack([r[0], r[0]]), [target, target])
            mixed = lanes(r, [target, 0.0])
        assert [int(k) for k in pair[3]] == [stop, stop]
        assert [int(k) for k in mixed[3]] == [stop, m]  # j ran on
        assert not np.asarray(mixed[4]).any()
        for got, kept in zip(mixed[1:3], pair[1:3]):  # H; g or Q
            assert got.shape[1] == m + 1
            assert np.array_equal(np.asarray(got[0]), np.asarray(kept[0]))
            assert np.asarray(got[0]).any()
        if g_is_q:
            Q = np.asarray(mixed[2])
            assert Q.shape == (2, m + 1, m + 1)
            assert np.array_equal(Q[0, stop + 1:], np.eye(m + 1)[stop + 1:])
            assert not np.array_equal(Q[1, stop + 1:], np.eye(m + 1)[stop + 1:])
