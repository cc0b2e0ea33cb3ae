"""The grid-space multigrid laid over a mesh (PR 46): the row-block forms of
``models/gmg_grid.py`` (each stencil apply and transfer on a shard's own rows
and one row from each neighbour), the fine level's kernel a shard, and
``linalg.cg(A, b, M=M)`` over such a hierarchy, on the CPU's eight virtual
devices.

The judge of the solves is the benchmark's plain reference
(``benchmark/operators/gmg_poisson.py``: one device, no sharding, no kernel,
nothing of the program imported), tied to scipy in
``tests/test_gmg_reference.py``.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparse_tpu import linalg, telemetry
from sparse_tpu.kernels import grid_stencil
from sparse_tpu.models import gmg_grid as gg
from sparse_tpu.parallel.mesh import get_mesh
from sparse_tpu.telemetry import _metrics

from .test_gmg_grid import FIVE, USES, _edge_heavy
from .utils.spd import operator_module

ref = operator_module("gmg_poisson")
TRACES = _metrics.counter("cg.precond.traces")
EXCHANGES = _metrics.counter("gmg.mesh.halo_exchanges")
# a three-level iteration's exchanges, by hand: five stencil applies (A p;
# the residual and the post-smoothing of levels 0 and 1), a row up and a row
# down each, and four transfers (two restrictions, two prolongations), one
# row each: 5 x 2 + 4
HAND_COUNT = 14


def laid_out(n, levels, shards, **kw):
    """``(A, M, b's sharding, hierarchy)`` of a float32 hierarchy over a mesh."""
    hier = gg.build_hierarchy(n, levels)
    hs, vec = gg.shard_hierarchy_grid(hier, get_mesh(shards), **kw)
    return gg.grid_operator(hs), gg.make_vcycle(hs), vec, hs


def in_row_blocks(a) -> bool:
    spec = tuple(a.sharding.spec)
    return spec[:1] == ("shards",) and not any(spec[1:])


def rhs(n, seed):
    return np.random.default_rng(seed).random(n * n).astype(np.float32)


@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("n,iters", [(64, 12), (192, 25)])
def test_the_sharded_solve_agrees_with_the_plain_reference(n, iters, shards):
    """Three levels; at 64^2 the coarsest level (256 points) is replicated,
    at 192^2 every level lies in row blocks. Short of float32's floor (as
    tests/test_gmg_reference.py argues), so that x can be held to 1e-4."""
    A, M, vec, _ = laid_out(n, 3, shards)
    b = rhs(n, 11 + shards)
    x, its = linalg.cg(A, jax.device_put(b, vec), maxiter=iters, M=M)
    want = ref.reference_cg(b, n, 3, iters)
    assert int(its) == iters
    assert in_row_blocks(x) and len(x.sharding.device_set) == shards
    err = np.linalg.norm(np.asarray(x, np.float64) - want) / np.linalg.norm(want)
    assert err <= 1e-4, err
    # and with the one-device solve of the same hierarchy, iteration for iteration
    hier = gg.build_hierarchy(n, 3)
    x1, its1 = linalg.cg(gg.grid_operator(hier), jnp.asarray(b), maxiter=iters,
                         M=gg.make_vcycle(hier))
    assert int(its1) == int(its)
    assert float(jnp.linalg.norm(x - x1) / jnp.linalg.norm(x1)) <= 1e-4


@pytest.mark.parametrize("shards", [2, 4, 8])
@pytest.mark.parametrize("coef", [None, FIVE], ids=["poisson", "five-distinct"])
@pytest.mark.parametrize("use", list(USES))
def test_the_shard_kernel_equals_stencil_apply(use, coef, shards):
    """Each of the fine level's three uses through the kernel on a shard's
    block (interpreted on the CPU) with its neighbours' edge rows as the
    halo, against ``stencil_apply`` on the whole grid: the mesh's first and
    last shard read zero beyond the grid, every other edge a neighbour's
    row. Edge-heavy inputs and five distinct coefficients tell the rows
    apart."""
    n = 256
    st = gg.poisson_stencil(n) if coef is None else {
        d: jnp.asarray(c, jnp.float32) for d, c in coef.items()}
    rows = gg._Rows(get_mesh(shards), "shards")
    w = jnp.asarray(0.3, jnp.float32)
    x, r = jnp.asarray(_edge_heavy(n, 1)), jnp.asarray(_edge_heavy(n, 2))
    got = jax.jit(lambda x, r: gg._fine_stencil(
        use, tuple(st), tuple(st.values()), None if use == "apply" else w, x,
        r if use == "smooth" else None, rows=rows))(x, r)
    want = USES[use](lambda v: gg.stencil_apply(st, v), w, x, r)
    assert got.shape == (n, n) and in_row_blocks(got)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 2e-6 * scale
    m = n // shards  # the rows on both sides of every cut between two shards
    cuts = np.r_[0, n - 1, [k * m + e for k in range(1, shards) for e in (-1, 0)]]
    assert np.abs(np.asarray(got)[cuts] - np.asarray(want)[cuts]).max() <= 2e-6 * scale


def test_a_shards_block_rows_divide_its_rows():
    assert grid_stencil.block_rows(4480) == 128  # one chip: as it was
    assert grid_stencil.block_rows(5120, 1280) == 128
    assert grid_stencil.block_rows(6400, 1600) == 64  # 1600 = 25 x 64
    assert grid_stencil.block_rows(8960, 2240) == 64  # VMEM, and 2240 = 35 x 64
    assert grid_stencil.block_rows(128, 16) == 16  # two row groups at least


@pytest.mark.parametrize("piece", ["apply", "restrict", "prolong"])
@pytest.mark.parametrize("shards", [2, 8])
def test_a_row_block_form_is_the_one_device_form(piece, shards):
    """Nine plane coefficients (a coarse level's operator) and both
    transfers on a mesh against the same functions on one device, float64:
    the halo rows are the only thing that differs, so the results agree to
    rounding's last bit or two."""
    n = 64
    hier = gg.build_hierarchy(n, 2, dtype=jnp.float64)
    planes = hier[1][0]  # nine [32, 32] planes
    rows = gg._Rows(get_mesh(shards), "shards")
    rng = np.random.default_rng(3)
    if piece == "apply":
        X = jnp.asarray(rng.standard_normal((32, 32)))
        got, want = gg.stencil_apply(planes, X, rows=rows), gg.stencil_apply(planes, X)
    elif piece == "restrict":
        X = jnp.asarray(rng.standard_normal((n, n)))
        got, want = gg.restrict_grid(X, 32, "linear", rows=rows), gg.restrict_grid(X, 32, "linear")
    else:
        X = jnp.asarray(rng.standard_normal((32, 32)))
        got = gg.prolong_grid(X, n, 32, "linear", rows=rows)
        want = gg.prolong_grid(X, n, 32, "linear")
    assert in_row_blocks(got)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-13)


def test_levels_the_shards_do_not_divide_fall_back_and_still_agree():
    """24 -> 12 -> 6 over four shards, every level sharded that can be: level
    2's six rows cannot, so level 1's transfers are the partitioner's and
    its applies still the row-block form; the cycle is the one-device
    cycle."""
    hier = gg.build_hierarchy(24, 3, dtype=jnp.float64)
    hs, vec = gg.shard_hierarchy_grid(hier, get_mesh(4), replicate_below=1)
    M = gg.make_vcycle(hs)
    rows = M.apply.rows
    assert rows[0] and rows[1] and rows[2] is None
    assert rows[0].transfers(24, 12) and not rows[1].transfers(12, 6)
    assert M.describe["halo_exchanges"] == (4 + 2) + 4
    r = np.random.default_rng(9).random(24 * 24)
    got = jax.jit(M.apply)(M.operands, jax.device_put(jnp.asarray(r), vec))
    want = jax.jit(gg.make_vcycle(hier))(jnp.asarray(r))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-12)


def test_the_lay_out_is_read_off_the_arrays():
    hier = gg.build_hierarchy(64, 3)
    assert [gg._level_rows(st, n, w) for st, w, n in hier] == [None] * 3
    mesh = get_mesh(4)
    hs, _ = gg.shard_hierarchy_grid(hier, mesh)
    want = gg._Rows(mesh, "shards")
    # 64 and 32 in row blocks; 16^2 = 256 points are replicated
    assert [gg._level_rows(st, n, w) for st, w, n in hs] == [want, want, None]
    assert gg._Rows(get_mesh(4), "shards") == want  # by value: one program
    # a side the shards do not divide; scalars over a mesh of two axes, which
    # does not say whose rows are cut; numpy scalars
    assert gg._level_rows(hs[0][0], 66, hs[0][1]) is None
    two = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
    st2 = {d: jax.device_put(c, NamedSharding(two, P())) for d, c in hier[0][0].items()}
    assert gg._level_rows(st2, 64) is None
    assert gg._level_rows({d: np.float32(c) for d, c in hier[0][0].items()}, 64) is None
    # planes cut over their columns are not row blocks
    cols = {d: jax.device_put(p, NamedSharding(mesh, P(None, "shards")))
            for d, p in hier[1][0].items()}
    assert gg._level_rows(cols, 32) is None


def test_who_takes_the_shard_kernel(monkeypatch):
    """With the kernel's platform set to this process's: a float32 hierarchy
    of side 128 over eight shards (16 rows each: two row groups) takes the
    kernel a shard; off that platform it keeps ``stencil_apply`` in row
    blocks; its operators' ``apply`` objects are equal by value either
    way, and differ from the one-device hierarchy's."""
    hier = gg.build_hierarchy(128, 2)
    hs, _ = gg.shard_hierarchy_grid(hier, get_mesh(8))
    rows = gg._Rows(get_mesh(8), "shards")
    A0, M0 = gg.grid_operator(hs), gg.make_vcycle(hs)
    assert A0.apply == gg._GridApply(128, tuple(hs[0][0]), False, rows)
    assert not M0.apply.fine_kernel and M0.apply.rows == (rows, rows)
    assert A0.describe == {"fine_stencil_kernels": 0, "halo_exchanges": 2}
    assert M0.describe == {"precond": "gmg_grid", "levels": 2,
                           "fine_stencil_kernels": 0, "halo_exchanges": 6}
    monkeypatch.setattr(gg, "_KERNEL_PLATFORM", "cpu")
    A, M = gg.grid_operator(hs), gg.make_vcycle(hs)
    assert A.apply == gg._GridApply(128, tuple(hs[0][0]), True, rows)
    assert M.apply.fine_kernel and M.describe["fine_stencil_kernels"] == 2
    assert A.describe == {"fine_stencil_kernels": 1, "halo_exchanges": 2}
    assert gg.grid_operator(hier).apply == gg._GridApply(128, tuple(hier[0][0]), True)
    # the kernel's cycle over the mesh is the plain cycle on one device
    r = jnp.asarray(_edge_heavy(128, 4).reshape(-1))
    want = gg._Cycle(M.apply.static, "linear")(gg.make_vcycle(hier).operands, r)
    got = M.matvec(r)
    assert float(jnp.abs(got - want).max()) <= 2e-6 * float(jnp.abs(want).max())


@pytest.fixture
def solve_256():
    A, M, vec, _ = laid_out(256, 3, 4)
    return A, M, jax.device_put(rhs(256, 5), vec)


def test_the_sharded_program_exchanges_halo_rows_and_gathers_nothing(solve_256):
    """The compiled text at 256^2 over four shards: no all-gather and no
    all-to-all of a float32 operand, and in the loop at most the hand count
    of collective-permutes, each of one grid row."""
    A, M, b = solve_256
    text = linalg._pcg_compiled(A, b, M).as_text()
    gathered = [ln for ln in text.splitlines()
                if re.search(r" = .*f32\[.* (all-gather|all-to-all)(-start)?\(", ln)]
    assert not gathered, gathered[:2]
    loop = [ln for ln in text.splitlines() if "/while/body/" in ln
            and re.search(r" collective-permute(-start)?\(", ln)]
    assert 0 < len(loop) <= HAND_COUNT, len(loop)
    assert all(re.search(r" = \(?f32\[1,(256|128|64)\]", ln) for ln in loop), loop[:2]
    assert A.describe["halo_exchanges"] + M.describe["halo_exchanges"] == HAND_COUNT
    for lvl in (0, 1):  # the regions stand under their level's scope
        assert re.search(rf"/gmg\.l{lvl}/[^\"]*shard_map[^\"]*ppermute", text)


def test_a_second_call_traces_and_writes_nothing(solve_256):
    A, M, b = solve_256
    linalg.cg(A, b, maxiter=5, M=M)
    t0, e0 = TRACES.value, EXCHANGES.value
    # another hierarchy of the same sizes over an equal mesh, another b
    A2, M2, vec, _ = laid_out(256, 3, 4)
    x, its = linalg.cg(A2, jax.device_put(rhs(256, 6), vec), maxiter=7, M=M2)
    assert int(its) == 7 and (TRACES.value, EXCHANGES.value) == (t0, e0)


def test_the_solves_span_says_devices_and_exchanges(solve_256):
    from sparse_tpu.config import settings

    A, M, b = solve_256
    was = settings.telemetry
    settings.telemetry = True
    try:
        telemetry.reset()
        linalg.cg(A, b, maxiter=5, M=M)
        hier = gg.build_hierarchy(64, 2)
        linalg.cg(gg.grid_operator(hier), jnp.asarray(rhs(64, 1)), maxiter=5,
                  M=gg.make_vcycle(hier))
        spans = [e for e in telemetry.events("span") if e.get("name") == "cg.solve"]
        solves = telemetry.events("solver.solve")
    finally:
        settings.telemetry = was
        telemetry.reset()
    mesh, one = spans
    assert mesh["devices"] == 4 and mesh["halo_exchanges"] == HAND_COUNT
    assert mesh["path"] == "device" and mesh["precond"] == "gmg_grid"
    assert "devices" not in one and "halo_exchanges" not in one
    assert solves[0]["devices"] == 4 and "devices" not in solves[1]
