#!/usr/bin/env python3
"""One smoke run of sparse_tpu's main path on the chip it was written for.

    python chip_smoke.py             # one TPU chip: library, general, served
    python chip_smoke.py --chips 4   # four chips: shard_csr + dist_cg only
    JAX_PLATFORMS=cpu SPARSE_TPU_FUSED_CG=force python chip_smoke.py --small
                                     # CPU rehearsal; always ends "ok": false

One process, through the entry points a user calls (``sparse.diags``,
``A @ x``, ``linalg.cg``, ``SolveSession``, ``shard_csr``/``dist_cg``), at the
upstream suite's published PDE size (BASELINE.md: 5-point Laplacian, 6000^2
unknowns, 300 CG iterations). Every result is checked against a plain
reference that shares no code with the package: ``scipy.sparse`` in f64 on
the host. Data is made from ``--seed``.

This is a smoke run, not a benchmark: the seconds it prints are single
readings around ``block_until_ready`` and say only that the program ran.

The last line of stdout is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``. ``ok`` is true only
when the platform is ``tpu``, the device count is what was asked for and
every check passed; the exit code is 0 only then.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla

F32_EPS = float(np.finfo(np.float32).eps)

# Default sizes. SERVED_GRID/SERVED_BUCKET/DIST_GRID are the shapes
# tests/test_chip_compile.py compiles for the described chip (5.50 GiB
# planned for the served bucket program = a third of the 16 GB HBM; 2.00 GiB
# per device for dist_cg).
PDE_N = 6000  # published: 6000^2 unknowns on one chip
PDE_ITERS = 300  # published: 300 CG iterations
PDE_N_SMALLER = 1500  # the grid scipy can afford the same 300 iterations on
# scipy.sparse.linalg.cg in f64 on the host, b = ones, exactly 300 iterations
# (scipy_cg_fixed below), true relative residual |b - A x| / |b|. Taken once
# in the sandbox (PR 22, 367 s of one core at 6000^2): too slow to repeat in
# every smoke run, so the full-size bound is this constant; the smaller grid
# runs scipy live. CG's residual norm is not monotone: for b = ones on the
# Dirichlet Laplacian it climbs for the first few hundred iterations, so the
# reference value, not "< 1", is the bound.
PDE_REF_RELRES = {(6000, 300): 41.745549850099906}
GENERAL_ROWS = 1 << 20
GENERAL_K = 8  # random entries per row before symmetrizing: ~16 nnz/row + diag
SERVED_GRID = 2048  # n = 4,194,304 unknowns per lane
SERVED_BUCKET = 16
DIST_GRID = 4096  # per-chip grid side; four chips hold (2*DIST_GRID)^2


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def say(msg: str) -> None:
    print(msg, flush=True)


class clock:
    """Host seconds around a block; the body must end in block_until_ready
    (or a host fetch) for the reading to cover device work."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0


def timed_twice(jax, fn):
    """Run ``fn`` twice, blocking on its first output each time: (result,
    first-call seconds = compile + run, second-call seconds)."""
    secs = []
    for _ in range(2):
        with clock() as t:
            out = fn()
            jax.block_until_ready(out[0])
        secs.append(t.s)
    return out, secs[0], secs[1]


def where(x) -> str:
    """Device set of a jax array, short."""
    return ",".join(sorted(f"{d.platform}:{d.id}" for d in x.devices()))


# ---------------------------------------------------------------------------
# references (scipy, f64, host) — no sparse_tpu code below this line
# ---------------------------------------------------------------------------
def pde_diagonals(n: int):
    """The examples/pde.py operator (d2_mat_dirichlet_2d) on an (n+2)^2 grid:
    n^2 unknowns, 5 diagonals, Dirichlet boundary. Returns the five
    diagonals and offsets as float32 host arrays."""
    nx = ny = n + 2
    dx = 1.0 / (nx - 1)
    dy = 1.0 / (ny - 1)
    a, g = 1.0 / dx**2, 1.0 / dy**2
    c = -2.0 * a - 2.0 * g
    N = n * n
    diag_a = np.full(N - 1, a, dtype=np.float32)
    diag_a[n - 1 :: n] = 0.0
    diag_g = np.full(N - n, g, dtype=np.float32)
    diag_c = np.full(N, c, dtype=np.float32)
    return [diag_g, diag_a, diag_c, diag_a, diag_g], [-n, -1, 0, 1, n]


def scipy_pde(n: int) -> sp.csr_matrix:
    diags, offs = pde_diagonals(n)
    return sp.diags(
        [d.astype(np.float64) for d in diags], offs, shape=(n * n, n * n)
    ).tocsr()


def scipy_cg_fixed(A, b, iters: int):
    """scipy's CG for exactly ``iters`` iterations (tolerances 0)."""
    x, _ = spla.cg(A, b, rtol=0.0, atol=0.0, maxiter=iters)
    return x


def relres(A_ref, x, b) -> float:
    x = np.asarray(x, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b - A_ref @ x) / np.linalg.norm(b))


def spmv_rounding_error(A_ref, x, y) -> float:
    """max_i |y_i - (A x)_i| / (|A| |x|)_i in units of f32 eps."""
    x64 = np.asarray(x, dtype=np.float64)
    y_ref = A_ref @ x64
    scale = abs(A_ref) @ np.abs(x64)
    scale[scale == 0] = 1.0
    return float(np.max(np.abs(np.asarray(y, np.float64) - y_ref) / scale) / F32_EPS)


def random_spd(rows: int, k: int, rng) -> sp.csr_matrix:
    """Seeded unstructured SPD matrix: ``k`` uniformly random columns per
    row, symmetrized, made strictly diagonally dominant. ~2k+1 nnz/row,
    Poisson-spread degrees, no band structure."""
    r = np.repeat(np.arange(rows, dtype=np.int64), k)
    c = rng.integers(0, rows, size=rows * k, dtype=np.int64)
    v = rng.uniform(-1.0, 1.0, size=rows * k)
    B = sp.csr_matrix((v, (r, c)), shape=(rows, rows))
    S = (B + B.T).tocsr()
    S.setdiag(0.0)
    S.eliminate_zeros()
    d = np.asarray(abs(S).sum(axis=1)).ravel() + 1.0
    A = (S + sp.diags(d)).tocsr()
    A.sort_indices()
    return A


def five_point_pattern(g: int) -> sp.csr_matrix:
    T = sp.diags([-1.0, -1.0], [-1, 1], shape=(g, g))
    I = sp.identity(g)
    A = (sp.kron(I, T) + sp.kron(T, I) + 4.0 * sp.identity(g * g)).tocsr()
    A.sort_indices()
    return A


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_complex_roundtrip(jax):
    say("== complex64 host->device->host round trip")
    z = np.array([1 + 2j, -3.5 + 0.25j, 0 - 1j], dtype=np.complex64)
    zd = jax.device_put(z, jax.devices()[0])
    back = np.asarray(zd)
    twice = np.asarray(zd * 2)
    say(f"  sent {z.tolist()} on {where(zd)}; got {back.tolist()}; "
        f"2*z computed on device {twice.tolist()}")
    check(np.array_equal(back, z) and np.array_equal(twice, 2 * z),
          "complex64 moves to the device and back unchanged")


def phase_library(args, jax, sparse, linalg, telemetry, failovers):
    n = args.pde_n
    N = n * n
    say(f"== library: examples/pde.py operator, {n}^2 = {N} unknowns, f32, "
        f"{args.pde_iters} CG iterations")
    rng = np.random.default_rng(args.seed)
    diags, offs = pde_diagonals(n)
    with clock() as t:
        A = sparse.diags(diags, offs, shape=(N, N)).tocsr()
        jax.block_until_ready(A.data)
    say(f"  built diags->CSR in {t.s:.2f} s: {A}, data on {where(A.data)}")
    with clock() as t:
        A_ref = scipy_pde(n)
    say(f"  scipy reference operator (f64, host) in {t.s:.2f} s")

    # placement of the one-time layout build (utils.host_scope) and of the
    # commit to the execution device (utils.commit_to_exec_device)
    with clock() as t:
        dia = A._maybe_dia()
    check(dia is not None, "CSR detected as banded (DIA planes built)")
    say(f"  DIA planes {tuple(dia[0].shape)} offsets {dia[1]} built on "
        f"{where(dia[0])} in {t.s:.2f} s")

    x = rng.standard_normal(N).astype(np.float32)
    with clock() as t:
        y = jax.block_until_ready(A @ x)
    say(f"  first A @ x (compile + run) {t.s:.2f} s; planes now on "
        f"{where(A._dia[0])}, y on {where(y)}")
    with clock() as t:
        y = jax.block_until_ready(A @ x)
    say(f"  second A @ x {t.s * 1e3:.2f} ms")
    err = spmv_rounding_error(A_ref, x, y)
    say(f"  A @ x vs scipy f64: max error {err:.2f} eps_f32 of (|A||x|)_i")
    check(err <= 8.0, "A @ x agrees with scipy to f32 rounding (<= 8 eps)")

    b = np.ones(N, dtype=np.float32)  # pde.py -throughput right-hand side
    telemetry.reset()
    f0 = failovers()
    (xs, iters), cold, warm = timed_twice(
        jax, lambda: linalg.cg(A, b, maxiter=args.pde_iters))
    say(f"  linalg.cg first call {cold:.2f} s (compile + run), second call "
        f"{warm:.2f} s; iterations {iters}; x on {where(xs)}")
    check(iters == args.pde_iters, f"ran the {args.pde_iters} iterations")
    evs = telemetry.events("solver.iter")
    paths = sorted({e.get("path") for e in evs})
    say(f"  solver.iter events: {len(evs)}, paths {paths}")
    check(paths == ["fused"], "the solve took the fused Pallas kernel")
    check(failovers() == f0 == 0, "kernel.failovers reads 0")
    rho = float(evs[-1]["resid2"])
    xh = np.asarray(xs)
    check(bool(np.all(np.isfinite(xh))), "iterate is finite")
    rr = relres(A_ref, xh, b)
    rec = float(np.sqrt(rho) / np.linalg.norm(b.astype(np.float64)))
    say(f"  true relative residual (scipy operator, f64) {rr:.6e}; "
        f"kernel's recursive residual {rec:.6e}")
    ref = PDE_REF_RELRES.get((n, args.pde_iters))
    if ref is None:
        ref = relres(A_ref, scipy_cg_fixed(A_ref, b.astype(np.float64),
                                           args.pde_iters), b)
        say(f"  scipy cg (f64, live) relres after {iters} iterations {ref:.6e}")
    else:
        say(f"  scipy cg (f64, recorded constant) relres after {iters} "
            f"iterations {ref:.6e}")
    check(rr <= 1.01 * ref,
          "true relative residual within 1% of scipy's f64 CG at the same "
          "iteration count")
    check(abs(rr - rec) <= 0.05 * rr + 1e-6,
          "recursive residual within 5% of the true one (no f32 drift)")

    # the same call against scipy's CG at a grid the host can afford
    n2 = args.pde_n_smaller
    d2, o2 = pde_diagonals(n2)
    A2 = sparse.diags(d2, o2, shape=(n2 * n2, n2 * n2)).tocsr()
    A2_ref = scipy_pde(n2)
    b2 = np.ones(n2 * n2, dtype=np.float32)
    with clock() as t:
        x2, it2 = linalg.cg(A2, b2, maxiter=args.pde_iters)
        jax.block_until_ready(x2)
    with clock() as ts:
        x2_ref = scipy_cg_fixed(A2_ref, b2.astype(np.float64), args.pde_iters)
    r_dev, r_ref = relres(A2_ref, x2, b2), relres(A2_ref, x2_ref, b2)
    say(f"  smaller grid {n2}^2: linalg.cg {it2} iterations in {t.s:.2f} s, "
        f"relres {r_dev:.6e}; scipy cg (f64) same iterations in {ts.s:.2f} s, "
        f"relres {r_ref:.6e}")
    check(0.5 <= r_dev / r_ref <= 2.0,
          "residuals of linalg.cg and scipy cg agree within a factor 2")
    check(failovers() == 0, "kernel.failovers still 0")


def phase_general(args, jax, sparse, linalg, telemetry, failovers):
    rows, k = args.general_rows, GENERAL_K
    say(f"== general sparsity: seeded unstructured SPD, {rows} rows, "
        f"{k} random columns/row symmetrized")
    rng = np.random.default_rng(args.seed + 1)
    with clock() as t:
        A_ref = random_spd(rows, k, rng)
    deg = np.diff(A_ref.indptr)
    say(f"  scipy build {t.s:.2f} s: nnz {A_ref.nnz}, nnz/row mean "
        f"{deg.mean():.2f} max {deg.max()} min {deg.min()}")
    with clock() as t:
        A = sparse.csr_array(A_ref.astype(np.float32))
        jax.block_until_ready(A.data)
    say(f"  csr_array from scipy in {t.s:.2f} s; data on {where(A.data)}")
    check(A._maybe_dia() is None, "not detected as banded")

    telemetry.reset()
    # the one-time layout build (host_scope), before the commit to the chip
    with clock() as t:
        A.prepare()
    say(f"  layouts built in {t.s:.2f} s")
    x = rng.standard_normal(rows).astype(np.float32)
    with clock() as t:
        y = jax.block_until_ready(A @ x)
    say(f"  first A @ x (commit + compile + run) {t.s:.2f} s")
    with clock() as t:
        y = jax.block_until_ready(A @ x)
    say(f"  second A @ x {t.s * 1e3:.2f} ms")
    from sparse_tpu import plan_cache

    prep = plan_cache.lookup(A, "sell")
    if prep is not None:
        it, vt = prep.slabs[0]
        say(f"  path: SELL ({len(prep.slabs)} slabs, pad ratio "
            f"{prep.plan.pad_ratio:.2f}); slab planes on {where(vt)}")
    elif A._ell is not None:
        say(f"  path: ELL width {A._ell[0].shape[1]}; planes on "
            f"{where(A._ell[1])}")
    else:
        say("  path: segment")
    check(prep is not None or A._ell is not None,
          "took a prepared (ELL or SELL) layout, not the segment path")
    err = spmv_rounding_error(A_ref.astype(np.float32).astype(np.float64), x, y)
    say(f"  A @ x vs scipy f64: max error {err:.2f} eps_f32 of (|A||x|)_i")
    check(err <= 4.0 * np.log2(deg.max()) + 8.0,
          "A @ x agrees with scipy to f32 rounding")

    b = rng.standard_normal(rows).astype(np.float32)
    iters = args.general_iters
    (xs, it), cold, warm = timed_twice(
        jax, lambda: linalg.cg(A, b, tol=0.0, maxiter=iters))
    say(f"  linalg.cg first call {cold:.2f} s, second {warm:.2f} s; "
        f"iterations {it}")
    x_ref = scipy_cg_fixed(A_ref, b.astype(np.float64), iters)
    r_dev, r_ref = relres(A_ref, xs, b), relres(A_ref, x_ref, b)
    say(f"  relres after {iters} iterations: linalg.cg {r_dev:.3e}, scipy cg "
        f"(f64) {r_ref:.3e}")
    check(it == iters, f"ran the {iters} iterations")
    check(r_dev <= max(10.0 * r_ref, 2e-6),
          "residual within 10x of scipy's, or at f32 rounding")
    xe = float(np.linalg.norm(np.asarray(xs, np.float64) - x_ref)
               / np.linalg.norm(x_ref))
    say(f"  |x - x_scipy| / |x_scipy| = {xe:.3e}")
    check(xe <= 1e-4, "solution agrees with scipy's to 1e-4")
    check(failovers() == 0, "kernel.failovers reads 0")


def phase_served(args, jax, telemetry, failovers):
    from sparse_tpu.batch import service
    from sparse_tpu.telemetry import _metrics

    g, bmax = args.served_grid, args.served_bucket
    n = g * g
    say(f"== served: SolveSession('cg'), 5-point pattern on a {g}^2 grid "
        f"(n = {n}), batch_max {bmax}")
    rng = np.random.default_rng(args.seed + 2)
    P_ref = five_point_pattern(g)
    diag_pos = np.flatnonzero(
        P_ref.indices == np.repeat(np.arange(n), np.diff(P_ref.indptr))
    )
    base = P_ref.data.astype(np.float32)

    def operator(vals):
        return sp.csr_matrix(
            (vals.astype(np.float64), P_ref.indices, P_ref.indptr), shape=(n, n)
        )

    def request():
        """(I*sigma + L) x = b with a per-row reaction term: the implicit
        step of a heat equation with its own coefficients per request."""
        vals = base.copy()
        vals[diag_pos] += rng.uniform(0.5, 1.5, size=n).astype(np.float32)
        b = rng.standard_normal(n).astype(np.float32)
        return vals, b

    requeues = _metrics.counter("batch.requeues")
    q0 = requeues.value
    telemetry.reset()
    ses = service.SolveSession("cg", batch_max=bmax, warm_start=False)
    pattern = ses.pattern_of(P_ref)
    say(f"  pattern nnz {pattern.nnz}; bucket of {bmax}: value stack "
        f"{bmax * pattern.nnz * 4 / 2**20:.0f} MiB, each (B, n) vector "
        f"{bmax * n * 4 / 2**20:.0f} MiB; compile rehearsal planned 5.50 GiB "
        f"for g=2048 B=16")
    rel_tol = 1e-5
    flushes = args.served_flushes  # e.g. [24, 12] -> buckets 16+8, then 16
    tickets = []
    for fi, count in enumerate(flushes):
        with clock() as t:
            batch = []
            for _ in range(count):
                vals, b = request()
                tol = rel_tol * float(np.linalg.norm(b))
                batch.append((ses.submit(vals, b, tol=tol, pattern=pattern),
                              vals, b, tol))
        with clock() as tf:
            retired = ses.flush(wait=True)
        say(f"  flush {fi}: {count} submits in {t.s:.2f} s, flush {tf.s:.2f} s,"
            f" {retired} buckets retired")
        tickets += batch
    stats = ses.session_stats()
    say("  session_stats: " + json.dumps(stats, default=str, sort_keys=True))
    check(all(t.done or t.failed for t, *_ in tickets),
          "every ticket is terminal")
    check(all(t.done for t, *_ in tickets), "no ticket failed")
    worst = 0.0
    its = []
    for t, vals, b, tol in tickets:
        x, iters, _resid2 = t.result()
        worst = max(worst, relres(operator(vals), x, b))
        its.append(int(iters))
    say(f"  {len(tickets)} tickets: iterations min {min(its)} max {max(its)}; "
        f"worst true relres vs scipy operator {worst:.3e} (asked {rel_tol:g})")
    check(worst <= 2.0 * rel_tol, "every solution meets 2x its tolerance "
          "against the scipy operator")
    # and the answers themselves, against scipy's CG, on a few tickets
    for t, vals, b, tol in (tickets[0], tickets[len(tickets) // 2], tickets[-1]):
        x_ref, info = spla.cg(operator(vals), b.astype(np.float64),
                              rtol=1e-10, atol=0.0)
        xe = float(np.linalg.norm(np.asarray(t.result()[0], np.float64) - x_ref)
                   / np.linalg.norm(x_ref))
        say(f"  ticket {t.id}: |x - x_scipy| / |x_scipy| = {xe:.3e}")
        check(info == 0 and xe <= 1e-4, "solution agrees with scipy cg to 1e-4")
    disp = telemetry.events("batch.dispatch")
    for e in disp:
        say(f"  dispatch: bucket {e['bucket']} lanes {e['batch']} pad "
            f"{e['pad_waste']} compile_ms {e['compile_ms']} solve_ms "
            f"{e['solve_ms']} iters_max {e['iters_max']} program {e['program']}")
    buckets = sorted({int(e["bucket"]) for e in disp})
    check(len(disp) >= 3 and len(buckets) >= 2,
          f"at least three dispatches over two bucket sizes {buckets}")
    check(requeues.value == q0, "zero requeues")
    check(failovers() == 0, "kernel.failovers reads 0")


def fleet_bucket(args, mesh):
    """One batch-sharded fleet bucket across the mesh, per-device occupancy
    printed (runs first under --fleet: it is cheap, dist_cg is not)."""
    from sparse_tpu.batch import service

    gs = min(args.served_grid, 512)
    ns = gs * gs
    say(f"  fleet: SolveSession('cg', fleet='batch'), {gs}^2 grid, one "
        "bucket of 16")
    rng = np.random.default_rng(args.seed + 3)
    P_ref = five_point_pattern(gs)
    ses = service.SolveSession("cg", batch_max=16, warm_start=False,
                               fleet="batch", fleet_mesh=mesh)
    pattern = ses.pattern_of(P_ref)
    ts = []
    for _ in range(16):
        vals = (P_ref.data + (P_ref.data == 4.0)).astype(np.float32)
        b = rng.standard_normal(ns).astype(np.float32)
        ts.append((ses.submit(vals, b, tol=1e-5 * float(np.linalg.norm(b)),
                              pattern=pattern), b))
    ses.flush(wait=True)
    st = ses.session_stats()
    say(f"  fleet device_occupancy {st.get('device_occupancy')} mesh "
        f"{st.get('mesh')}")
    A_f = P_ref + sp.identity(ns)
    worst = max(relres(A_f, t.result()[0], b) for t, b in ts)
    say(f"  fleet worst relres {worst:.3e}")
    check(worst <= 2e-5, "fleet bucket solutions meet their tolerance")


def phase_four_chips(args, jax, sparse, linalg, failovers):
    from sparse_tpu.parallel import comm_stats, dist_cg, get_mesh, shard_csr

    g = args.dist_grid
    n = 2 * g  # weak scaling (BASELINE.md): grid side grows as sqrt(chips)
    N = n * n
    iters = args.dist_iters
    say(f"== four chips: shard_csr + dist_cg, {n}^2 = {N} unknowns "
        f"({g}^2 per chip), {iters} iterations")
    if g < PDE_N and not args.small:
        say(f"  CUT: {g}^2 unknowns per chip instead of the published "
            f"{PDE_N}^2, and {iters} iterations instead of {PDE_ITERS}: the "
            "compile rehearsal says the weak-scaled dist_cg program fits "
            "(4.3 GiB per device), but the CSR is built on one device and "
            "the one-device comparison solves the whole system there, and "
            "the host-side builds (DIA planes, shard_csr) are minutes at "
            "four times the chip price")
    devs = jax.devices()
    mesh = get_mesh(4)
    if args.fleet:
        fleet_bucket(args, mesh)
    diags, offs = pde_diagonals(n)
    with clock() as t:
        A = sparse.diags(diags, offs, shape=(N, N)).tocsr()
        jax.block_until_ready(A.data)
    say(f"  built diags->CSR in {t.s:.2f} s: {A}")
    with clock() as t:
        D = shard_csr(A, mesh=mesh)
    cs = comm_stats(D)
    say(f"  shard_csr in {t.s:.2f} s: layout {D.layout}, R {D.R}, halo "
        f"{D.HL}/{D.HR}; comm_stats {json.dumps(cs)}")
    check(cs["mode"] == "halo", "comm_stats mode is 'halo'")
    b = np.ones(N, dtype=np.float32)
    bp = D.pad_out_vector(b)
    blocks = [(f"block {i}", arr) for i, arr in enumerate(D._blocks())]
    for name, arr in (*blocks, ("b", bp)):
        ds = arr.sharding.device_set
        per = sorted(
            (s.device.id, tuple(s.data.shape)) for s in arr.addressable_shards
        )
        say(f"  {name}: {len(ds)} devices, shards {per}")
        check(len(ds) == 4 and len({d for d, _ in per}) == 4,
              f"{name} is on four distinct devices")
    (xp, it, _conv), cold, warm = timed_twice(
        jax, lambda: dist_cg(D, bp, tol=0.0, maxiter=iters))
    say(f"  dist_cg first call {cold:.2f} s (traces and compiles), second "
        f"{warm:.2f} s (the program kept on the layout); iterations {it}; "
        f"x on {len(xp.sharding.device_set)} devices")
    check(it == iters, f"ran the {iters} iterations")
    check(len(xp.sharding.device_set) == 4, "the iterate is on four devices")
    x_dist = D.unpad_vector(xp)

    # the same solve on one device of the same process: the library path
    with clock() as t:
        with jax.default_device(devs[0]):
            x1, it1 = linalg.cg(A, b, tol=0.0, maxiter=iters)
            jax.block_until_ready(x1)
    say(f"  linalg.cg on {where(x1)} {t.s:.2f} s (compile + run); "
        f"iterations {it1}")
    x1 = np.asarray(x1, np.float64)
    diff = float(np.linalg.norm(x_dist - x1) / np.linalg.norm(x1))
    say(f"  |x_dist - x_one| / |x_one| = {diff:.3e}")
    check(bool(np.all(np.isfinite(x_dist))), "distributed iterate is finite")
    check(diff <= 1e-3, "distributed and single-device iterates agree to 1e-3")
    A_ref = scipy_pde(n)
    r_d, r_1 = relres(A_ref, x_dist, b), relres(A_ref, x1, b)
    say(f"  true relres (scipy operator): dist {r_d:.6e}, one device {r_1:.6e}")
    check(0.5 <= r_d / r_1 <= 2.0, "residuals agree within a factor 2")
    check(failovers() == 0, "kernel.failovers reads 0")

# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--small", action="store_true",
                    help="shrunk sizes for the CPU rehearsal")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet", action="store_true",
                    help="with --chips 4: also one batch-sharded fleet bucket")
    args = ap.parse_args()
    if args.small:
        args.pde_n, args.pde_n_smaller, args.pde_iters = 64, 48, 40
        args.general_rows, args.general_iters = 4096, 30
        args.served_grid, args.served_bucket = 32, 4
        args.served_flushes = [6, 3]
        args.dist_grid, args.dist_iters = 48, 50
    else:
        args.pde_n, args.pde_n_smaller, args.pde_iters = (
            PDE_N, PDE_N_SMALLER, PDE_ITERS)
        args.general_rows, args.general_iters = GENERAL_ROWS, 30
        args.served_grid, args.served_bucket = SERVED_GRID, SERVED_BUCKET
        args.served_flushes = [24, 12]
        args.dist_grid, args.dist_iters = DIST_GRID, 50

    t_start = time.perf_counter()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"jax {jax.__version__}; backend {jax.default_backend()}; devices "
        f"{device}; reached in {time.perf_counter() - t_start:.1f} s")

    def finish(ok: bool) -> int:
        say(f"total {time.perf_counter() - t_start:.1f} s")
        print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
        return 0 if ok else 1

    on_chip = dev.platform == "tpu" and device["count"] == args.chips
    if not on_chip:
        say(f"NOT ON THE CHIP: need platform 'tpu' with {args.chips} "
            f"device(s), have {device}")
        if not args.small:
            return finish(False)
        say("rehearsal at --small sizes follows; the run still ends ok=false")

    try:
        cpus = jax.local_devices(backend="cpu")
    except RuntimeError:
        cpus = []
    say(f"host backend for one-time layout builds (utils.host_scope): "
        f"{cpus[:1] or 'none: builds would run op by op on the chip'}")
    say(f"JAX_COMPILATION_CACHE_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')}")

    import sparse_tpu as sparse
    from sparse_tpu import linalg, native, telemetry
    from sparse_tpu.config import settings
    from sparse_tpu.telemetry import _metrics
    from sparse_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    say("native library: " + ("loaded (built on this machine)"
                              if native.lib() is not None
                              else "not loaded; numpy fallback in use"))
    say(f"settings: spmv_mode={settings.spmv_mode} fused_cg={settings.fused_cg} "
        f"fused_cg_tile={settings.fused_cg_tile}")

    # telemetry on for the run; its JSONL goes beside the other run outputs,
    # never into the repo's committed results/
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    settings.telemetry = True
    telemetry.configure(os.path.join(out_dir, "chip_smoke_telemetry.jsonl"))
    fo = _metrics.counter("kernel.failovers")

    def failovers() -> int:
        return int(fo.value)

    try:
        if args.chips == 4:
            phase_four_chips(args, jax, sparse, linalg, failovers)
        else:
            phase_complex_roundtrip(jax)
            phase_library(args, jax, sparse, linalg, telemetry, failovers)
            phase_general(args, jax, sparse, linalg, telemetry, failovers)
            phase_served(args, jax, telemetry, failovers)
        try:
            ms = dev.memory_stats() or {}
            say(f"device memory: peak {ms.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                f" GiB of {ms.get('bytes_limit', 0) / 2**30:.2f} GiB")
        except Exception as e:  # noqa: BLE001 - a print, not a check
            say(f"device memory: not reported ({e})")
    except BaseException:
        traceback.print_exc(file=sys.stdout)
        finish(False)
        raise
    return finish(on_chip)


if __name__ == "__main__":
    sys.exit(main())
