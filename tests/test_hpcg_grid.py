"""``sparse_tpu/models/hpcg_grid.py`` against scipy (PR 53): the generator
against a CSR matrix built from HPCG's rule, the colour-major order's round
trip, the product, the coloured sweep against scipy's two triangular solves
on the colour-permuted matrix (the exact oracle), the transfers against their
explicit matrices, the V-cycle against the same cycle in matrices, HPCG's own
symmetry test, and what ``linalg.cg(A, b, M=M)`` does with the pair: one
trace for many solves, the caller's vectors lexicographic, the spans' fields
and the scopes in the compiled text."""

import re

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spl

import jax
import jax.numpy as jnp

from sparse_tpu import linalg, telemetry
from sparse_tpu.config import settings
from sparse_tpu.models import hpcg_grid as hg
from sparse_tpu.telemetry import _metrics

TRACES = _metrics.counter("cg.precond.traces")
UPDATES = _metrics.counter("hpcg.symgs.colour_updates")


def hpcg_csr(nx, ny, nz):
    """HPCG's ``GenerateProblem_ref`` in scipy: 26 on the diagonal, -1 to
    every neighbour inside the grid; rows numbered x fastest."""
    n = nx * ny * nz
    idx = np.arange(n).reshape(nz, ny, nx)
    rows, cols, vals = [], [], []
    for dz, dy, dx in hg.OFFSETS:
        here = idx[max(0, -dz):nz - max(0, dz), max(0, -dy):ny - max(0, dy),
                   max(0, -dx):nx - max(0, dx)].ravel()
        rows.append(here)
        cols.append(here + (dz * ny + dy) * nx + dx)
        vals.append(np.full(here.size, 26.0 if (dz, dy, dx) == (0, 0, 0) else -1.0))
    return sp.csr_matrix((np.concatenate(vals),
                          (np.concatenate(rows), np.concatenate(cols))),
                         shape=(n, n))


FORWARD = (7, 3, 5, 6, 1, 2, 4, 0)  # the configuration's sweep order


def colour_perm(nx, ny, nz, order=range(8)):
    """perm[new] = old: the unknowns colour by colour in ``order``, from the
    definition (colour = 4 (z % 2) + 2 (y % 2) + x % 2, lexicographic within
    a colour), not from the program. The default is the order of storage;
    ``FORWARD`` the order of a sweep."""
    z, y, x = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                          indexing="ij")
    colour = (4 * (z % 2) + 2 * (y % 2) + x % 2).ravel()
    return np.argsort(np.argsort(order)[colour], kind="stable")


def injection(nx, ny, nz):
    """R of ``ComputeRestriction_ref``: coarse point (k, j, i) takes fine
    point (2k, 2j, 2i); the prolongation adds R^T."""
    cz, cy, cx = nz // 2, ny // 2, nx // 2
    k, j, i = np.meshgrid(np.arange(cz), np.arange(cy), np.arange(cx),
                          indexing="ij")
    fine = ((2 * k * ny + 2 * j) * nx + 2 * i).ravel()
    return sp.csr_matrix((np.ones(fine.size), (np.arange(fine.size), fine)),
                         shape=(cz * cy * cx, nx * ny * nz))


def symgs_oracle(A, perm, r, x):
    """One symmetric step in the coloured order by scipy: forward ``(D + L)
    x = r - U x``, backward ``(D + U) x = r - L x`` of ``P A P^T``."""
    PAP = A[perm][:, perm].tocsr()
    rp, xp = r[perm], x[perm]
    xp = spl.spsolve_triangular(sp.tril(PAP, 0).tocsr(),
                                rp - sp.triu(PAP, 1) @ xp, lower=True)
    xp = spl.spsolve_triangular(sp.triu(PAP, 0).tocsr(),
                                rp - sp.tril(PAP, -1) @ xp, lower=False)
    out = np.empty_like(xp)
    out[perm] = xp
    return out


def cycle_oracle(dims_xyz, levels, r, lvl=0):
    nx, ny, nz = (d >> lvl for d in dims_xyz)
    A, perm = hpcg_csr(nx, ny, nz), colour_perm(nx, ny, nz, FORWARD)
    x = symgs_oracle(A, perm, r, np.zeros_like(r))
    if lvl == levels - 1:
        return x
    R = injection(nx, ny, nz)
    x = x + R.T @ cycle_oracle(dims_xyz, levels, R @ (r - A @ x), lvl + 1)
    return symgs_oracle(A, perm, r, x)


GRIDS = [(8, 8, 8), (16, 16, 16), (16, 8, 4)]


@pytest.mark.parametrize("nx,ny,nz", GRIDS)
def test_the_generator_is_hpcgs_rule(nx, ny, nz):
    A = hpcg_csr(nx, ny, nz)
    # A 1 is 26 less the neighbours a row has: 19 at a corner, 0 inside
    ones = (A @ np.ones(A.shape[0])).reshape(nz, ny, nx)
    assert ones[0, 0, 0] == 19 and (ones[1:-1, 1:-1, 1:-1] == 0).all()
    assert A.nnz == (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)
    (planes,) = hg.build_hierarchy(nx, ny, nz, levels=1, dtype=jnp.float64)
    assert planes.shape == (8, 27, nz // 2, ny // 2, nx // 2)
    p = np.asarray(planes)
    assert set(np.unique(p)) <= {-1.0, 0.0, 26.0} and (p[:, hg.CENTRE] == 26).all()
    assert np.count_nonzero(p) == A.nnz
    # every stored coefficient is the matrix's entry: row by colour and
    # half-grid index, column by the offset
    perm = colour_perm(nx, ny, nz)
    dense = A.toarray()
    for d, (dz, dy, dx) in enumerate(hg.OFFSETS):
        col = perm + (dz * ny + dy) * nx + dx
        z, y, x = np.unravel_index(perm, (nz, ny, nx))
        inside = ((0 <= z + dz) & (z + dz < nz) & (0 <= y + dy) & (y + dy < ny)
                  & (0 <= x + dx) & (x + dx < nx))
        want = np.where(inside, dense[perm, np.where(inside, col, 0)], 0.0)
        assert np.array_equal(p[:, d].ravel(), want), (dz, dy, dx)


@pytest.mark.parametrize("nx,ny,nz", GRIDS)
def test_the_colour_major_order_round_trips(nx, ny, nz):
    space = hg.ColourMajor((nz, ny, nx))
    v = jnp.arange(nx * ny * nz)
    inside = np.asarray(space.enter(v))
    assert np.array_equal(inside, colour_perm(nx, ny, nz))
    assert np.array_equal(np.asarray(space.leave(space.enter(v))), np.asarray(v))
    assert space.blocks(space.enter(v)).shape == (8, nz // 2, ny // 2, nx // 2)
    assert space == hg.ColourMajor((nz, ny, nx)) != hg.ColourMajor((nx, ny, nz + 2))


@pytest.mark.parametrize("nx,ny,nz", GRIDS)
def test_the_product_is_scipys(nx, ny, nz):
    A = hpcg_csr(nx, ny, nz)
    hier = hg.build_hierarchy(nx, ny, nz, levels=1, dtype=jnp.float64)
    op = hg.grid_operator(hier)
    v = np.random.default_rng(3).standard_normal(A.shape[0])
    assert op.shape == A.shape
    assert np.allclose(np.asarray(op @ v), A @ v, rtol=0, atol=1e-12)
    # inside the space the same product, on the permuted vector
    perm = colour_perm(nx, ny, nz)
    inside = op.apply.within()(op.operands, jnp.asarray(v[perm]))
    assert np.allclose(np.asarray(inside), (A @ v)[perm], rtol=0, atol=1e-12)


@pytest.mark.parametrize("start", ["zero", "given"])
@pytest.mark.parametrize("nx,ny,nz", GRIDS)
def test_the_coloured_sweep_is_two_triangular_solves(nx, ny, nz, start):
    A, perm = hpcg_csr(nx, ny, nz), colour_perm(nx, ny, nz, FORWARD)
    assert hg.FORWARD == FORWARD
    (planes,) = hg.build_hierarchy(nx, ny, nz, levels=1, dtype=jnp.float64)
    rng = np.random.default_rng(5)
    r = rng.standard_normal(A.shape[0])
    x0 = np.zeros_like(r) if start == "zero" else rng.standard_normal(r.size)
    space = hg.ColourMajor((nz, ny, nx))
    blocks = (None if start == "zero"
              else space.blocks(space.enter(jnp.asarray(x0))))
    before = UPDATES.value
    out = hg._symgs(planes, space.blocks(space.enter(jnp.asarray(r))), blocks)
    assert UPDATES.value - before == 15
    got = np.asarray(space.leave(out))
    assert np.allclose(got, symgs_oracle(A, perm, r, x0), rtol=0, atol=1e-13)


def test_the_backward_sweeps_repeat_of_a_colour_writes_the_same_bits(monkeypatch):
    """The step as the specification words it (eight updates forward, eight
    backward) and the program's fifteen give the same bits, in float32 as in
    float64, from zero and from a start."""
    for dtype in (jnp.float32, jnp.float64):
        (planes,) = hg.build_hierarchy(16, 8, 8, levels=1, dtype=dtype)
        space = hg.ColourMajor((8, 8, 16))
        rng = np.random.default_rng(9)
        r = space.blocks(jnp.asarray(rng.standard_normal(1024), dtype))
        x = space.blocks(jnp.asarray(rng.standard_normal(1024), dtype))
        for start in (None, x):
            mine = hg._symgs(planes, r, start)
            with monkeypatch.context() as m:
                m.setattr(hg, "_SWEEP", FORWARD + FORWARD[::-1])
                full = hg._symgs(planes, r, start)
            assert np.array_equal(np.asarray(mine), np.asarray(full))


@pytest.mark.parametrize("nx,ny,nz", GRIDS)
def test_the_transfers_are_their_explicit_matrices(nx, ny, nz):
    """Injection is the level's block 0 re-coloured for the next level;
    prolongation adds the next level's vector, back in grid order, to
    block 0."""
    R = injection(nx, ny, nz)
    fine = hg.ColourMajor((nz, ny, nx))
    coarse = hg.ColourMajor((nz // 2, ny // 2, nx // 2))
    rng = np.random.default_rng(2)
    v = rng.standard_normal(nx * ny * nz)
    block0 = fine.blocks(fine.enter(jnp.asarray(v)))[0]
    assert np.array_equal(np.asarray(block0).ravel(), R @ v)
    if min(coarse.dims) >= 2:
        vc = rng.standard_normal(R.shape[0])
        back = coarse.leave(coarse.enter(jnp.asarray(vc)))
        added = fine.blocks(fine.enter(jnp.asarray(v))).at[0].add(
            back.reshape(coarse.dims))
        assert np.allclose(np.asarray(fine.leave(added)), v + R.T @ vc,
                           rtol=0, atol=1e-15)


@pytest.mark.parametrize("nx,ny,nz,levels", [
    (16, 16, 16, 2), (16, 16, 16, 3), (16, 16, 16, 4), (32, 16, 16, 3)])
def test_the_vcycle_is_the_cycle_in_matrices(nx, ny, nz, levels):
    hier = hg.build_hierarchy(nx, ny, nz, levels=levels, dtype=jnp.float64)
    M = hg.make_vcycle(hier)
    r = np.random.default_rng(4).standard_normal(nx * ny * nz)
    want = cycle_oracle((nx, ny, nz), levels, r)
    assert np.allclose(np.asarray(M @ r), want, rtol=0, atol=1e-13)
    assert np.allclose(np.asarray(M(r)), want, rtol=0, atol=1e-13)
    assert M.describe == {"precond": "hpcg_mg", "levels": levels, "colours": 8,
                          "smoother": "symgs",
                          "colour_updates": 15 * (2 * levels - 1)}


def test_every_level_reaches_the_answer():
    """The coarse correction lands on the even points, block 0, and the sweep
    visits block 0 last: a sweep that began there would overwrite the
    correction unread (an update reads no point of its own colour) and leave
    every coarser level dead code. Each level's planes move the answer, and
    the compiled program keeps them all as arguments."""
    hier = hg.build_hierarchy(16, 16, 16, levels=3, dtype=jnp.float64)
    r = np.random.default_rng(4).standard_normal(4096)
    base = np.asarray(hg.make_vcycle(hier) @ r)
    for lvl in range(3):
        other = list(hier)
        other[lvl] = hier[lvl] * 1.5
        moved = np.asarray(hg.make_vcycle(other) @ r)
        assert np.abs(moved - base).max() > 1e-6, lvl
    assert hg.FORWARD[-1] == 0 and sorted(hg.FORWARD) == list(range(8))
    M = hg.make_vcycle(hier)
    text = jax.jit(M.apply).lower(M.operands, jnp.asarray(r)).as_text()
    main = text[text.index("@main("):text.index("{", text.index("@main("))]
    assert all(f"tensor<8x27x{s}x{s}x{s}xf64>" in main for s in (8, 4, 2)), main


def test_a_and_m_pass_hpcgs_symmetry_test():
    """``TestSymmetry``: <u, A v> = <A u, v> and <u, M v> = <M u, v> to
    rounding, u and v random."""
    hier = hg.build_hierarchy(16, 16, 16, levels=3, dtype=jnp.float64)
    A, M = hg.grid_operator(hier), hg.make_vcycle(hier)
    rng = np.random.default_rng(8)
    u, v = rng.standard_normal((2, 4096))
    for op in (A, M):
        left, right = float(u @ np.asarray(op @ v)), float(np.asarray(op @ u) @ v)
        assert abs(left - right) <= 1e-12 * (abs(left) + np.linalg.norm(u)
                                             * np.linalg.norm(v))


def test_sides_the_levels_do_not_divide_are_refused():
    for nx, ny, nz, levels in [(24, 16, 16, 4), (16, 16, 20, 3), (16, 16, 16, 5),
                               (16, 16, 16, 0)]:
        with pytest.raises(ValueError, match=r"multiple of 2\*\*levels"):
            hg.build_hierarchy(nx, ny, nz, levels=levels)
    hier = hg.build_hierarchy(16, 16, 16, levels=2)
    with pytest.raises(ValueError, match="half the side"):
        hg.make_vcycle([hier[0], hier[0]])


def test_cg_solves_hpcgs_system_in_the_callers_order():
    """b = A 1 lexicographic, x = 1 back lexicographic; preconditioned by the
    cycle, by nothing (the identity commutes with the order) and from a
    start."""
    nx, ny, nz = 32, 16, 16
    A = hpcg_csr(nx, ny, nz)
    hier = hg.build_hierarchy(nx, ny, nz, levels=3, dtype=jnp.float64)
    op, M = hg.grid_operator(hier), hg.make_vcycle(hier)
    x_true = 1.0 + 0.1 * np.arange(A.shape[0]) / A.shape[0]  # not symmetric
    b = A @ x_true
    x, iters = linalg.cg(op, b, tol=1e-9, maxiter=60, M=M)
    assert iters <= 50 and np.allclose(np.asarray(x), x_true, rtol=0, atol=1e-9)
    plain, n_plain = linalg.cg(op, b, tol=1e-9, maxiter=400)
    assert n_plain > iters and np.allclose(np.asarray(plain), x_true, atol=1e-8)
    # a start in the caller's order: the exact answer stays where it is
    again, _ = linalg.cg(op, b, x0=x_true, tol=1e-9, maxiter=25, M=M)
    assert np.allclose(np.asarray(again), x_true, rtol=0, atol=1e-12)
    # the same iterates as scipy's CG preconditioned by the oracle's cycle
    Mo = spl.LinearOperator(A.shape, matvec=lambda r: cycle_oracle(
        (nx, ny, nz), 3, np.asarray(r, dtype=np.float64)))
    xs, _ = spl.cg(A, b, rtol=0, atol=0, maxiter=6, M=Mo)
    x6, six = linalg.cg(op, b, tol=0.0, maxiter=6, M=M)
    assert six == 6 and np.allclose(np.asarray(x6), xs, rtol=0, atol=1e-10)


def test_one_trace_for_many_solves_and_the_spans_fields():
    hier = hg.build_hierarchy(16, 16, 16, levels=3)
    other = hg.build_hierarchy(16, 16, 16, levels=3)
    rng = np.random.default_rng(1)
    was = settings.telemetry
    settings.telemetry = True
    try:
        telemetry.reset()
        before = TRACES.value
        for h in (hier, other):
            A, M = hg.grid_operator(h), hg.make_vcycle(h)
            for _ in range(2):
                b = jnp.asarray(rng.random(4096), jnp.float32)
                x, iters = linalg.cg(A, b, tol=0.0, maxiter=7, M=M)
                assert iters == 7 and x.shape == (4096,)
        assert TRACES.value - before == 1
        solves = [e for e in telemetry.events("span") if e["name"] == "cg.solve"]
        assert len(solves) == 4
        for e in solves:
            assert (e["path"], e["precond"], e["levels"], e["colours"],
                    e["smoother"], e["colour_updates"], e["iters"]) == (
                "device", "hpcg_mg", 3, 8, "symgs", 75, 7)
        telemetry.reset()
        hg.build_hierarchy(32, 16, 16, levels=2)
        (built,) = [e for e in telemetry.events("span")
                    if e["name"] == "hpcg.build_hierarchy"]
        assert (built["levels"], built["colours"]) == (2, 8)
        assert built["sizes"] == [[32, 16, 16], [16, 8, 8]]
        assert built["bytes"] == 27 * 4 * (32 * 16 * 16 + 16 * 8 * 8)
    finally:
        settings.telemetry = was
        telemetry.reset()


def test_the_compiled_text_holds_the_scopes_and_no_reordering_in_the_loop():
    levels = 3
    # (a grid no other test of this file solves on: the trace is this test's)
    hier = hg.build_hierarchy(32, 16, 8, levels=levels)
    A, M = hg.grid_operator(hier), hg.make_vcycle(hier)
    b = jnp.ones(4096, jnp.float32)
    before = UPDATES.value
    args, static = linalg._declared_call(A, M, b, jnp.zeros_like(b), 0.0, 5,
                                         conv_test_iters=25)
    lowered = linalg._pcg_program.lower(*args, **static)
    assert UPDATES.value - before == 15 * (2 * levels - 1)
    # every op's scopes, as the tracer names them (XLA's CPU fusions keep one
    # op's name each; tests/test_chip_compile.py reads the chip's program)
    text = lowered.as_text(debug_info=True)
    for lvl in range(levels):
        assert f"/hpcg.l{lvl}/hpcg.l{lvl}.symgs/" in text
    for lvl in range(levels - 1):
        assert f"/hpcg.l{lvl}/hpcg.l{lvl}.spmv/" in text
        assert f"/hpcg.l{lvl}/hpcg.l{lvl}.transfer/" in text
    assert f"hpcg.l{levels - 1}.spmv" not in text and "/hpcg.spmv/" in text
    assert "hpcg.l0/hpcg.l1/" not in text and f"hpcg.l{levels}" not in text
    assert "symgs/hpcg" not in text and "spmv/hpcg" not in text
    # the loop of the traced program: no gather, no scatter, and the only
    # transposes are the re-colourings of the restricted residuals and of
    # the coarse corrections (with a product by a 0/1 matrix each for the
    # lane axis): never a vector of the fine level
    stable = lowered.as_text()
    loop = stable[stable.index("stablehlo.while"):]
    loop = loop[:loop.index("\n    }", loop.index(" do {"))]
    assert "gather" not in loop and "scatter" not in loop
    fine = 32 * 16 * 8
    # (a transpose of two axes is the lane matrix's own, a side squared)
    moved = r"stablehlo\.transpose.*-> tensor<((?:\d+x){3,})f32>"

    def sizes(text):
        return [int(np.prod([int(d) for d in dims.rstrip("x").split("x")]))
                for dims in re.findall(moved, text)]

    assert sorted(sizes(loop)) == sorted(
        2 * [fine // 8 ** k for k in range(1, levels)])
    # and outside it the solve's own crossings: b's, the start's and x's
    assert sizes(stable.replace(loop, "")) == [fine] * 3


# -- kernels/hpcg_colour.py, interpreted on the CPU ----------------------------
from sparse_tpu.kernels import hpcg_colour  # noqa: E402

KERNEL_GRIDS = [(16, 16, 16), (32, 16, 8), (8, 4, 4)]


def test_the_kernels_terms_are_the_27_offsets_of_every_colour():
    assert len(hpcg_colour.TERMS) == 27 and hpcg_colour.TERMS[0] == ((0, 0, 0),) * 2
    for c in range(8):
        seen = {}
        for m, u in hpcg_colour.TERMS:
            d = int(hpcg_colour.plane_index(c, m, u))
            src, shift = hg._source(c, hg.OFFSETS[d])
            # the term reads block c ^ m, moved along u's axes towards 2 p - 1
            assert src == c ^ (4 * m[0] + 2 * m[1] + m[2])
            par = ((c >> 2) & 1, (c >> 1) & 1, c & 1)
            assert shift == tuple((2 * p - 1) * moved for p, moved in zip(par, u))
            seen[d] = (m, u)
        assert sorted(seen) == list(range(27))
    row = hpcg_colour.colour_params(5, 0b00100110)
    assert row.shape == (hpcg_colour.PARAMS,) and row[0] == 5
    assert list(row[28:36]) == [5 ^ m for m in range(8)]
    assert list(row[36:44]) == [0b00100110 >> (5 ^ m) & 1 for m in range(8)]
    assert row[44] == 0 and hpcg_colour.colour_params(5, residual=True)[44] == 1
    assert hpcg_colour.slices_a_step(128, 128, 128) == 4
    assert hpcg_colour.slices_a_step(64, 64, 64) == 8
    assert hpcg_colour.slices_a_step(16, 16, 16) == 16
    assert hpcg_colour.slices_a_step(6, 4, 4) == 2


@pytest.mark.parametrize("nx,ny,nz", KERNEL_GRIDS)
def test_the_kernel_is_the_row_sum_of_every_colour(nx, ny, nz):
    (planes,) = hg.build_hierarchy(nx, ny, nz, levels=1)
    space = hg.ColourMajor((nz, ny, nx))
    rng = np.random.default_rng(12)
    x = space.blocks(jnp.asarray(rng.standard_normal(nx * ny * nz), jnp.float32))
    r = space.blocks(jnp.asarray(rng.standard_normal(nx * ny * nz), jnp.float32))
    got = hg._rows(planes, x, None, hg._params(range(8)), "product")
    want = jnp.stack([hg._row_sum(planes, x, c, True) for c in range(8)])
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-4)
    got = hg._rows(planes, x, r, hg._params([0, 5], residual=True), "update")
    assert np.allclose(np.asarray(got), np.asarray(jnp.stack(
        [r[c] - hg._row_sum(planes, x, c, True) for c in (0, 5)])), rtol=0, atol=2e-4)
    for c in (0, 3, 6, 7):
        got = hg._rows(planes, x, r, hg._params([c], [255 & ~(1 << c)]), "update")[0]
        want = (r[c] - hg._row_sum(planes, x, c, False)) / planes[c, hg.CENTRE]
        assert np.allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5)
    # a block that is not live holds zeros, and its terms stay at step 0's
    # slices of it: the same bits as reading it all
    live = 0b10101000
    zeros = x.at[jnp.asarray([0, 1, 2, 4, 6])].set(0.0)
    got = hg._rows(planes, zeros, r, hg._params([2], [live]), "update")
    want = hg._rows(planes, zeros, r, hg._params([2], [255 & ~4]), "update")
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_kernel_reads_what_is_stored_across_its_steps():
    """Planes that are not HPCG's (the kernel knows no entry, the diagonal it
    divides by included) on a half-grid of six slices, which it makes two a
    step: the slice beyond a step's own comes from the next step's, and a
    dead block's terms stay at step 0 while the live ones move on."""
    nx, ny, nz = 8, 4, 12
    assert hpcg_colour.slices_a_step(6, 2, 4) == 2
    rng = np.random.default_rng(21)
    (planes,) = hg.build_hierarchy(nx, ny, nz, levels=1)
    planes = planes * jnp.asarray(rng.uniform(0.5, 1.5, planes.shape), jnp.float32)
    space = hg.ColourMajor((nz, ny, nx))
    x = space.blocks(jnp.asarray(rng.standard_normal(nx * ny * nz), jnp.float32))
    r = space.blocks(jnp.asarray(rng.standard_normal(nx * ny * nz), jnp.float32))
    got = hg._rows(planes, x, None, hg._params(range(8)), "product")
    want = jnp.stack([hg._row_sum(planes, x, c, True) for c in range(8)])
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-4)
    for start in (None, x):
        got = jax.jit(hg._symgs_kernel)(planes, r, start)
        want = hg._symgs(planes, r, start)
        assert np.allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)


@pytest.mark.parametrize("start", ["zero", "given"])
@pytest.mark.parametrize("nx,ny,nz", KERNEL_GRIDS)
def test_the_kernels_sweep_is_the_sweep(nx, ny, nz, start):
    (planes,) = hg.build_hierarchy(nx, ny, nz, levels=1)
    space = hg.ColourMajor((nz, ny, nx))
    rng = np.random.default_rng(13)
    r = space.blocks(jnp.asarray(rng.standard_normal(nx * ny * nz), jnp.float32))
    x = None if start == "zero" else space.blocks(
        jnp.asarray(rng.standard_normal(nx * ny * nz), jnp.float32))
    before = UPDATES.value
    got = jax.jit(hg._symgs_kernel)(planes, r, x)
    assert UPDATES.value - before == 15
    want = hg._symgs(planes, r, x)
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)


def test_the_kernels_cycle_and_solve_are_the_plain_ones(monkeypatch):
    """What a TPU runs, driven on the CPU: the operators declared over
    float32 planes take the kernel, float64 ones do not, and the answers
    agree."""
    nx, ny, nz = 32, 16, 16
    hier = hg.build_hierarchy(nx, ny, nz, levels=3)
    plain_A, plain_M = hg.grid_operator(hier), hg.make_vcycle(hier)
    assert not plain_A.apply.kernel and not plain_M.apply.kernel
    monkeypatch.setattr(hg, "_KERNEL_PLATFORM", "cpu")
    A, M = hg.grid_operator(hier), hg.make_vcycle(hier)
    assert A.apply.kernel and M.apply.kernel
    wide = hg.build_hierarchy(nx, ny, nz, levels=3, dtype=jnp.float64)
    assert not hg.make_vcycle(wide).apply.kernel
    assert not hg.grid_operator([np.asarray(hier[0])]).apply.kernel
    rng = np.random.default_rng(14)
    v = jnp.asarray(rng.standard_normal(nx * ny * nz), jnp.float32)
    assert np.allclose(np.asarray(A @ v), np.asarray(plain_A @ v), rtol=0, atol=2e-4)
    assert np.allclose(np.asarray(M @ v), np.asarray(plain_M @ v), rtol=0, atol=2e-5)
    b = plain_A @ jnp.ones(nx * ny * nz, jnp.float32)
    x, iters = linalg.cg(A, b, tol=0.0, maxiter=12, M=M)
    want, _ = linalg.cg(plain_A, b, tol=0.0, maxiter=12, M=plain_M)
    assert iters == 12 and np.allclose(np.asarray(x), np.asarray(want), atol=2e-5)
    assert np.abs(np.asarray(x) - 1).max() < 1e-3
