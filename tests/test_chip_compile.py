"""Compile rehearsal for the chip: the main path's programs, at the sizes
``chip_smoke.py`` runs, handed to the TPU's own compiler for a *described*
``v5e:2x2`` (no chip attached; nothing executes).

A compile that passes is not a chip run — it only says the chip's compiler
accepts the program and how many bytes it plans per device. What runs and
how fast is ``chip_smoke.py``'s business.

Discipline (one process may hold libtpu): the topology is described inside
a module-scoped fixture, never at import; every case compiles in this
process from ``ShapeDtypeStruct``s carrying a described device's sharding,
``interpret=False``, with the persistent compile cache off (an entry
written for a described chip cannot be read back without one).
"""

import json
import os
import re

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

HBM_BYTES = 16 * 1024**3  # one v5e chip

# the sizes chip_smoke.py runs (kept in step with its SERVED_GRID/DIST_GRID)
PDE_N = 6000  # BASELINE.md: 5-pt Laplacian, 6000^2 unknowns per chip
SERVED_GRID = 2048
SERVED_BUCKET = 16
DIST_GRID = 4096  # per-chip grid of the 4-chip weak-scaled dist_cg


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def chip(topo):
    """The described topology under the chip process's defaults: x64 off
    (tests/conftest.py turns it on for the scipy oracles; ``chip_smoke.py``
    runs f32, and with x64 on Mosaic's lowering of the DIA kernels' int32
    ``program_id % 2`` recurses without end)."""
    with jax.enable_x64(False):
        yield topo


@pytest.fixture
def one_chip(chip):
    return SingleDeviceSharding(chip.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )


def _five_point(g: int) -> sp.csr_matrix:
    """5-point Laplacian pattern on a g x g grid (host, scipy)."""
    T = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(g, g))
    I = sp.identity(g)
    return (sp.kron(I, T) + sp.kron(T, I)).tocsr().astype(np.float32)


# ---------------------------------------------------------------------------
# DIA SpMV (A @ x under spmv_mode='pallas', and the SpMV microbenchmark row)
# ---------------------------------------------------------------------------
# SuiteSparse atmosmodd's box, 148 x 148 x 58: rows and the seven offsets
ATMOSMODD = (1_270_432, (-21904, -148, -1, 0, 1, 148, 21904))


@pytest.mark.parametrize(
    "rows, offsets",
    [
        (10_000_000, tuple(range(-5, 6))),  # BASELINE SpMV row: 10M x 11
        (PDE_N * PDE_N, (-PDE_N, -1, 0, 1, PDE_N)),  # PDE operator: 36M x 5
        ATMOSMODD,  # the nonsymmetric cell's box: a band of 21,904
    ],
    ids=["10Mx11", "36Mx5", "atmosmodd"],
)
def test_dia_spmv_packed_compiles(one_chip, rows, offsets):
    from sparse_tpu.kernels.dia_spmv import dia_plan, dia_spmv_packed

    plan = dia_plan(offsets, (rows, rows), tile=65536)
    m_pad = plan.G * plan.TM
    planes = _sds((plan.D * m_pad,), jnp.float32, one_chip)
    xpad = _sds((m_pad + 2 * plan.B,), jnp.float32, one_chip)
    c = dia_spmv_packed.lower(planes, xpad, plan, interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()
    assert _device_bytes(c) < HBM_BYTES


@pytest.mark.parametrize(
    "rows, offsets, tile",
    [
        (*ATMOSMODD, (64512, 22528, 20)),
        (3200 * 3200, (-3200, -1, 0, 1, 3200), (65536, 4096, 157)),
        (10_000_000, tuple(range(-5, 6)), (65536, 1024, 153)),
        (300_000, tuple(range(-13, 14)), (33792, 1024, 9)),  # 27 diagonals
        (5000, (-70, -1, 0, 1, 70), (5120, 1024, 1)),  # one step, both halos cut
    ],
    ids=["atmosmodd", "10Mx5", "10Mx11", "300Kx27", "5Kx5"],
)
def test_dia_spmv_rows_compiles_at_the_tile_the_rule_picks(
        one_chip, rows, offsets, tile):
    """The layout ``dia``'s own product on the chip (PR 50): the kernel at
    the plan ``csr_array._dia_operands`` makes from the geometry, x and y
    the rows in whole tiles."""
    from sparse_tpu import csr
    from sparse_tpu.kernels.dia_spmv import dia_rows_plan, dia_spmv_rows

    plan = dia_rows_plan(offsets, rows, csr._DIA_VMEM_BYTES)
    assert (plan.TM, plan.B, plan.G) == tile
    planes = _sds((plan.D * plan.G * plan.TM,), jnp.float32, one_chip)
    vec = _sds((-(-rows // 1024) * 1024,), jnp.float32, one_chip)
    c = dia_spmv_rows.lower(planes, vec, plan, interpret=False).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and f"f32[{vec.shape[0]}]" in text
    assert _device_bytes(c) < HBM_BYTES


# ---------------------------------------------------------------------------
# fused CG: what linalg.cg takes on a TPU for a banded f32 operator
# ---------------------------------------------------------------------------
def _linalg_cg_tile(D: int) -> int:
    """The tile ``linalg._try_fused_cg`` picks for a D-diagonal operator."""
    from sparse_tpu.config import settings

    return max(16384, min(int(settings.fused_cg_tile),
                          (6 << 20) // (max(2 * D + 10, 1) * 4)))


def _fused_cg_programs(g: int, sharding):
    """The three programs ``linalg._try_fused_cg`` runs for a g x g 5-point
    operator, lowered from shapes as it calls them: the pack (once an
    operator), the start (once a solve) and a 25-iteration chunk (the loop;
    the chip's compilation, which donates the state). Also D * m_pad and L."""
    from sparse_tpu.kernels import cg_dia

    n, offsets = g * g, (-g, -1, 0, 1, g)
    tile = _linalg_cg_tile(len(offsets))
    TM, _, G = cg_dia._plan(n, offsets, tile=tile)
    flat, L = len(offsets) * G * TM, (G + 2) * TM
    planes = _sds((len(offsets), n), jnp.float32, sharding)
    b = _sds((n,), jnp.float32, sharding)
    vec, rho = _sds((L,), jnp.float32, sharding), _sds((), jnp.float32, sharding)
    pack = cg_dia.cg_dia_pack.lower(planes, offsets, n, tile, jnp.dtype(jnp.float32))
    start = cg_dia.cg_dia_start.lower(planes, offsets, b, None, n, tile)
    chunk = cg_dia._chunk_donating.lower(
        _sds((flat,), jnp.float32, sharding), (vec, vec, vec, rho, rho),
        offsets=offsets, m=n, iters=25, tile=tile, interpret=False,
    )
    return pack, start, chunk, flat, L


def test_cg_dia_fused_compiles_at_pde_size(one_chip):
    pack, start, chunk, _, _ = _fused_cg_programs(PDE_N, one_chip)
    for lowered in (pack, start):
        assert _device_bytes(lowered.compile()) < HBM_BYTES
    c = chunk.compile()
    assert "tpu_custom_call" in c.as_text()
    # the loop program beside what stays resident: the scipy-layout planes
    assert _device_bytes(c) + 5 * PDE_N * PDE_N * 4 < HBM_BYTES


# ---------------------------------------------------------------------------
# the served bucket program (SolveSession._build_program), from shapes
# ---------------------------------------------------------------------------
def test_served_bucket_program_compiles(one_chip, monkeypatch):
    from sparse_tpu.batch import service

    # the chip donates the staged value stack / rhs / x0; jax.default_backend()
    # is the CPU here, so steer the one backend question the builder asks
    monkeypatch.setattr(service, "donate_argnums", lambda: (0, 1, 2))
    A = _five_point(SERVED_GRID)
    n = A.shape[0]
    ses = service.SolveSession("cg", batch_max=SERVED_BUCKET, warm_start=False)
    pattern = ses.pattern_of(A)
    run = ses._build_program(pattern, SERVED_BUCKET, np.dtype(np.float32))
    B = SERVED_BUCKET
    c = run.lower(
        _sds((B, pattern.nnz), jnp.float32, one_chip),
        _sds((B, n), jnp.float32, one_chip),
        _sds((B, n), jnp.float32, one_chip),
        _sds((B,), jnp.float32, one_chip),
        _sds((), jnp.int32, one_chip),
    ).compile()
    used = _device_bytes(c)
    print(f"served g={SERVED_GRID} B={B}: {used / 2**30:.2f} GiB planned")
    # a real share of HBM, and it fits
    assert 0.25 * HBM_BYTES < used < HBM_BYTES


# ---------------------------------------------------------------------------
# names on the device (PR 25): what a profile of the chip can print. The
# kernels' `name=` becomes the HLO instruction's name (it was
# `closed_call.N`), the scopes go into each op's `op_name` metadata.
# ---------------------------------------------------------------------------
CELL_N = 3200  # pde_cg_1chip's grid: the row-indexed planes are 205 MB


def test_cg_dia_pack_program_carries_the_repack_scope(one_chip):
    """The scope `cg_dia.repack` marks the pack program alone (PR 30), and
    that program is not one `benchmark/xplane.py` would take for the loop
    (it matches programs by prefix)."""
    pack, start, _, flat, _ = _fused_cg_programs(CELL_N, one_chip)
    hlo = pack.compile().as_text()
    assert hlo.startswith("HloModule jit_cg_dia_pack")
    assert "jit(cg_dia_pack)/cg_dia.repack/" in hlo
    assert f"->f32[{flat}]" in hlo.splitlines()[0]
    assert "cg_dia.repack" not in start.compile().as_text()


def test_cg_dia_fused_kernels_and_repack_are_named(one_chip):
    """The chunk program as `linalg._try_fused_cg` calls it: still
    `jit_cg_dia_fused` with `%cg_dia_a`/`%cg_dia_b` (the benchmark's
    roofline reads them by these names), and nothing in it but the
    iterations: no op of the pack, and the row-indexed planes only as a
    parameter that the loop hands to `%cg_dia_a`."""
    import re

    _, _, chunk, flat, L = _fused_cg_programs(CELL_N, one_chip)
    text = chunk.as_text()
    assert "cg_dia_a" in text and "cg_dia_b" in text
    hlo = chunk.compile().as_text()
    assert hlo.startswith("HloModule jit_cg_dia_fused,")
    assert "input_output_alias" in hlo.splitlines()[0]  # the donated state
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert any(ln.lstrip().startswith("%cg_dia_a") for ln in calls)
    assert any(ln.lstrip().startswith("%cg_dia_b") for ln in calls)
    assert "cg_dia.repack" not in hlo
    made = dict(re.findall(rf"(%[\w.-]+) = f32\[{flat}\]\S* ([\w-]+)\(", hlo))
    assert made and set(made.values()) <= {"parameter", "get-tuple-element"}, made
    readers = {
        ln.split(" = ")[0].strip().removeprefix("ROOT ")
        for ln in hlo.splitlines() if " = " in ln
        and any(re.search(rf"{re.escape(nm)}[,)]", ln.split(" = ", 1)[1]) for nm in made)
        and not re.search(r" (tuple|while|get-tuple-element)\(", ln)
    }
    assert readers and all(r.startswith("%cg_dia_a") for r in readers), readers
    # the loop's state ping-pongs between two sets of buffers (two
    # iterations a trip): a trip that copied a vector to make room for a
    # kernel's result was a quarter of the parent's device time
    bodies = [blk for blk in hlo.split("\n\n")
              if "tpu_custom_call" in blk and not blk.lstrip().startswith("ENTRY")]
    assert bodies
    for blk in bodies:
        assert not re.search(rf"= f32\[{L}\]\S* (copy|fusion)\(", blk)


def test_bucket_program_ops_carry_their_scope(one_chip, monkeypatch):
    from sparse_tpu.batch import service

    monkeypatch.setattr(service, "donate_argnums", lambda: (0, 1, 2))
    A = _five_point(64)
    n, B = A.shape[0], 4
    ses = service.SolveSession("cg", batch_max=B, warm_start=False)
    pattern = ses.pattern_of(A)
    run = ses._build_program(pattern, B, np.dtype(np.float32))
    hlo = run.lower(
        _sds((B, pattern.nnz), jnp.float32, one_chip),
        _sds((B, n), jnp.float32, one_chip),
        _sds((B, n), jnp.float32, one_chip),
        _sds((B,), jnp.float32, one_chip),
        _sds((), jnp.int32, one_chip),
    ).compile().as_text()
    for scope in ("bucket.matvec", "bucket.dots", "bucket.axpy"):
        assert f"/{scope}/" in hlo, scope
    # a banded pattern's loop multiplies by planes (PR 28): no gather inside
    # an iteration, and the loop's vectors are laid out row-minor (the gather
    # form's were lane-minor: 4 lanes in a 128-wide tile)
    body = [ln for ln in hlo.splitlines() if "/while/body/" in ln]
    assert body and not any("gather" in ln for ln in body)
    assert any(f"f32[{B},{n}]{{1,0:" in ln for ln in body)
    assert not any(f"f32[{B},{n}]{{0,1:" in ln for ln in body)


@pytest.mark.parametrize("precond, in_body, outside", [
    ("none", 0, 3),    # the loop in the SELL pack's order: rhs, x0 in, X out
    # the caller's order: `pos` closes every product, the first residual's
    # too; the other one outside is Jacobi's gather of the diagonals
    ("jacobi", 1, 2),
])
def test_gather_bucket_program_permutes_rows_outside_the_loop(
        one_chip, monkeypatch, precond, in_body, outside):
    """The gather program as the chip's compiler leaves it (PR 36): a gather
    fusion a slot plane of a slab in the while body, and the whole-vector
    row permutations (`[rows, lanes]`, lane-minor) where the builder put
    them. An unstructured FEM pattern, as `fem_heat_served_closed` serves."""
    from sparse_tpu.batch import service

    from .utils.spd import fem_heat_data

    monkeypatch.setattr(service, "donate_argnums", lambda: (0, 1, 2))
    P = fem_heat_data(72, 3, clients=1)["pattern"]
    n, B = P.shape[0], 8
    ses = service.SolveSession("cg", batch_max=B, warm_start=False)
    pattern = ses.pattern_of(P)
    run = ses._build_program(pattern, B, np.dtype(np.float32),
                             precond=precond)
    assert run.matvec == "sell"
    hlo = _bucket_program_text(run, pattern, B, one_chip)
    pack = pattern.sell_pack()
    plan = pack.plan
    # the pack's order is 704 rows longer than the caller's (the pad rows
    # of the three slabs past 1024 rows and the space's trailing ones), so
    # `enter`'s results are told from `leave`'s
    space = pack.own_order().rows.shape[0]
    assert space == n + 704
    assert all(r not in (n, space) for _k, r, _p in plan.slab_meta)
    gathers = _gather_fusions(hlo)
    whole = [ln for ln in gathers
             if re.search(rf"= f32\[({n}|{space}),{B}\]", ln)]
    body = [ln for ln in gathers if "/while/body/" in ln]
    assert len(body) == sum(k for k, _r, _p in plan.slab_meta) + in_body
    assert sum("/while/body/" in ln for ln in whole) == in_body
    assert sum("/while/body/" not in ln for ln in whole) == outside
    # in the pack's order two of the three are `enter`'s, of the space's rows
    assert sum(f"= f32[{space},{B}]" in ln for ln in whole) == (
        2 if precond == "none" else 0)


# ---------------------------------------------------------------------------
# the gather's step (PR 39). The TPU compiler lowers a gather of `[R, lanes]`
# rows on a step of 256 rows or of 128, chosen from R; on the chip the narrow
# one runs at 9.9 ns a row and the wide one at 4.0 (PERF.md section 5).
# `kernels.sell_spmv.slab_rows` moves a slab's row count into a band of
# remainders mod 1024 where every count tried got the wide step (since PR 41
# onto a multiple of 256 rows in it: the slab fusions' windows, below). These
# cases hold that observation: once the compiler stops telling the two apart,
# or tells them apart elsewhere, they fail, and the pad rows are dead weight.
# ---------------------------------------------------------------------------
def _bucket_program_text(run, pattern, B, one_chip) -> str:
    n = pattern.shape[0]
    return run.lower(
        _sds((B, pattern.nnz), jnp.float32, one_chip),
        _sds((B, n), jnp.float32, one_chip),
        _sds((B, n), jnp.float32, one_chip),
        _sds((B,), jnp.float32, one_chip),
        _sds((), jnp.int32, one_chip),
    ).compile().as_text()


def _gather_fusions(hlo: str) -> list:
    return [ln for ln in hlo.splitlines()
            if "kind=kCustom" in ln and " fusion(" in ln]


def _gather_step(line: str) -> int:
    return int(re.search(r'"integer_config":\{"integer":"(\d+)"\}', line).group(1))


def _fusion_rows(line: str) -> int:
    return int(re.search(r"= f32\[(\d+),\d+\]", line).group(1))


# what `sell_pack` gives the cell's pattern class (`fem_heat_data`,
# `pattern_seed` 3200000103): side 960 (`fem_heat_served_closed`; real rows
# 128, 59,560, 229,512, 345,112, 229,344, 57,944), its space of 922,880
# rows, and side 1108 (thermal2's rows) with its space
CELL_SLABS = (128, 59_648, 229_632, 345_344, 229_632, 58_112, 922_880)
THERMAL2_SLABS = (80, 78_336, 307_456, 460_032, 305_408, 78_080, 1_229_568)
# a dozen more, over the multiples of 1024 and the band's multiples of 256
BAND_ROWS = tuple(1024 * b + o for b, o in (
    (1, 256), (1, 768), (2, 512), (56, 256), (56, 768), (75, 768), (128, 768),
    (223, 256), (297, 768), (512, 512), (899, 768), (1200, 256)))


@pytest.mark.parametrize(
    "rows", sorted(set(CELL_SLABS + THERMAL2_SLABS + BAND_ROWS)))
def test_gather_takes_the_wide_step_at_the_pack_row_counts(one_chip, rows):
    """The standalone gather of a slab's slot plane at 64 lanes: one
    `kCustom` fusion, on the 256-row step at every row count the pack's
    rule gives (slabs of 1024 rows or fewer are microseconds either way and
    get whatever step the compiler likes)."""
    from sparse_tpu.kernels.sell_spmv import slab_rows

    assert slab_rows(rows) == rows
    (fusion,) = _gather_fusions(_standalone_gather(one_chip, rows))
    assert _fusion_rows(fusion) == rows
    if rows > 1024:
        assert _gather_step(fusion) == 256, fusion


def _standalone_gather(one_chip, rows, lanes=64, length=921_600):
    run = jax.jit(lambda X, idx: jax.vmap(lambda x: x[idx])(X) * 2.0)
    return run.lower(_sds((lanes, length), jnp.float32, one_chip),
                     _sds((rows,), jnp.int32, one_chip)).compile().as_text()


@pytest.mark.parametrize("rows, moved_to", [
    (229_344, 229_632),   # the cell's slab: 2.275 ms a gather on the chip
    (921_600, 921_856),   # a multiple of 1024
    (304_920, 305_408),   # thermal2's rows, 792 past a multiple
])
def test_gather_takes_the_narrow_step_off_the_band(one_chip, rows, moved_to):
    """The other side: the row counts ROW_ALIGN alone gave are on the
    128-row step. A compiler that no longer tells them apart fails here,
    so that the rule's pad rows do not outlive their reason unseen."""
    from sparse_tpu.kernels.sell_spmv import slab_rows

    assert slab_rows(rows) == moved_to
    (fusion,) = _gather_fusions(_standalone_gather(one_chip, rows))
    assert _gather_step(fusion) == 128, fusion


_SIDE_600 = {}  # the rule's WINDOW_ROWS it was built under -> (run, pack, hlo)


def _side_600_program(one_chip, monkeypatch):
    """`(run, pack, hlo)`: one whole bucket program at 64 lanes on an FEM
    pattern of 360,000 rows (side 600; under 270,000 rows the loop's
    vectors fit the chip's fast memory and the compiler lowers the gathers
    another way, with no step to read), compiled for the described chip
    under `slab_rows` as it is at the call; once a WINDOW_ROWS."""
    from sparse_tpu.batch import service
    from sparse_tpu.kernels import sell_spmv

    from .utils.spd import fem_heat_data

    if sell_spmv.WINDOW_ROWS in _SIDE_600:
        return _SIDE_600[sell_spmv.WINDOW_ROWS]
    monkeypatch.setattr(service, "donate_argnums", lambda: (0, 1, 2))
    P = fem_heat_data(600, 3, clients=1)["pattern"]
    B = 64
    ses = service.SolveSession("cg", batch_max=B, warm_start=False)
    pattern = ses.pattern_of(P)
    run = ses._build_program(pattern, B, np.dtype(np.float32))
    built = run, pattern.sell_pack(), _bucket_program_text(run, pattern, B, one_chip)
    return _SIDE_600.setdefault(sell_spmv.WINDOW_ROWS, built)


SIDE_600_SLABS = ((4, 224, 0), (5, 23_808, 144), (6, 90_368, 384),
                  (7, 134_400, 328), (8, 89_600, 184), (9, 22_784, 144))


def test_gather_bucket_program_takes_the_wide_step_in_its_loop(
        one_chip, monkeypatch):
    """Under ROW_ALIGN alone the side-600 pattern's 7-slot slab has 134,072
    rows and sits on the 128-row step; `slab_rows` stores it with 134,400,
    every other slab past 1024 rows with a multiple of 256 rows in the band
    too, and the space gets 32 trailing pad rows: every gather fusion of
    more than 1024 rows in the while body is on the 256-row step, and so
    are the two permutations into the pack's order."""
    run, pack, hlo = _side_600_program(one_chip, monkeypatch)
    n, B = 360_000, 64
    assert pack.plan.slab_meta == SIDE_600_SLABS
    space = pack.own_order().rows.shape[0]
    assert space == 361_216
    assert run.pad_rows == pack.own_order().pad_rows == 1184 + 32
    gathers = _gather_fusions(hlo)
    body = [ln for ln in gathers if "/while/body/" in ln]
    assert len(body) == sum(k for k, _r, _p in pack.plan.slab_meta)
    wide = [ln for ln in body if _fusion_rows(ln) > 1024]
    assert len(wide) == 5 + 6 + 7 + 8 + 9
    assert {_gather_step(ln) for ln in wide} == {256}, [
        ln for ln in wide if _gather_step(ln) != 256]
    enter = [ln for ln in gathers if f"= f32[{space},{B}]" in ln]
    assert len(enter) == 2
    assert {_gather_step(ln) for ln in enter} == {256}


# ---------------------------------------------------------------------------
# the slab fusions' windows (PR 41). A slab's multiply-sum is a `kLoop`
# fusion rooted in a `dynamic-update-slice` into the whole `f32[lanes, space]`
# product, and for such a fusion the TPU compiler takes as the window of a
# trip an exact divisor of the slab's count of 8-row tiles, R / 8: the
# largest one its fast memory holds beside the slab's operands. A count with
# no such divisor is left one tile a trip, and on the chip those fusions ran
# at 2.8 and 3.4 ns a slot row where the windowed ones ran at 1.3 to 1.5
# (PERF.md section 5). `slab_rows` gives a slab past 1024 rows a multiple of
# 256 rows, whose tiles every power of two up to 32 divides. Both sides are
# held here, as for the gather's step.
# ---------------------------------------------------------------------------
def _slab_windows(hlo: str, lanes: int) -> dict:
    """`{rows: (window, trips)}` in 8-row tiles, of every `kLoop` fusion of
    the program whose root writes an `f32[lanes, rows]` update into a larger
    array by `dynamic-update-slice` (the slabs' multiply-sums: the while
    body's and the first residual's are the same fusions twice)."""
    computations = {
        m.group(1): m.group(2) for m in re.finditer(
            r"^%([\w.\-]+) \(.*?\{\n(.*?)^\}", hlo, re.S | re.M)}
    out = {}
    for ln in hlo.splitlines():
        if "kind=kLoop" not in ln or " fusion(" not in ln:
            continue
        lines = computations[
            re.search(r"calls=%([\w.\-]+)", ln).group(1)].splitlines()
        (root,) = [l for l in lines if l.lstrip().startswith("ROOT ")]
        operands = re.search(r" dynamic-update-slice\(([^)]*)\)", root)
        if operands is None:
            continue
        update = operands.group(1).split(", ")[1]
        (made,) = [l for l in lines
                   if l.lstrip().removeprefix("ROOT ").startswith(update + " = ")]
        shape = re.search(rf"= f32\[{lanes},(\d+)\]", made)
        if shape is None:
            continue
        window = json.loads(re.search(
            r"backend_config=(\{.*\})\s*$", ln).group(1))["window_config"]
        got = (int(window["output_window_bounds"][0]),
               int(window["iteration_bounds"][0]))
        rows = int(shape.group(1))
        assert out.setdefault(rows, got) == got, (rows, got, out)
    return out


def test_gather_bucket_program_windows_every_slab_fusion(one_chip, monkeypatch):
    """At the rule's row counts every slab past 1024 rows of the side-600
    program multiplies and sums over a window of WINDOW_ROWS or more (here
    32 to 124 tiles), and window x trips is the slab."""
    from sparse_tpu.kernels.sell_spmv import WINDOW_ROWS

    _run, pack, hlo = _side_600_program(one_chip, monkeypatch)
    assert pack.plan.slab_meta == SIDE_600_SLABS
    windows = _slab_windows(hlo, 64)
    large = [r for _k, r, _p in pack.plan.slab_meta if r > 1024]
    assert len(large) == 5 and set(large) <= set(windows), windows
    for rows in large:
        window, trips = windows[rows]
        assert window * trips * 8 == rows
        assert window * 8 >= WINDOW_ROWS, (rows, window, trips)


def test_a_slab_of_eight_times_a_prime_rows_is_left_one_tile_a_trip(
        one_chip, monkeypatch):
    """The other side: under PR 39's rule (the band alone, which is this
    rule at a WINDOW_ROWS of ROW_ALIGN) the same pattern's 8-slot slab has
    89,416 rows = 8 x 11,177, a prime, and its fusion is left a window of
    one tile; the 7-slot slab beside it (134,152 = 8 x 41 x 409) gets 41. A
    compiler that windows such a fusion some other way fails here, so that
    the rule's pad rows do not outlive their reason unseen."""
    from sparse_tpu.kernels import sell_spmv

    monkeypatch.setattr(sell_spmv, "WINDOW_ROWS", sell_spmv.ROW_ALIGN)
    _run, pack, hlo = _side_600_program(one_chip, monkeypatch)
    assert pack.plan.slab_meta == (
        (4, 224, 0), (5, 23_664, 0), (6, 90_120, 136), (7, 134_152, 80),
        (8, 89_416, 0), (9, 22_640, 0))
    windows = _slab_windows(hlo, 64)
    assert windows[89_416] == (1, 11_177)
    assert windows[134_152] == (41, 409)


# ---------------------------------------------------------------------------
# four chips: make_dist_cg's program over a Mesh of described devices
# ---------------------------------------------------------------------------
def test_dist_cg_program_compiles_on_four_chips(chip):
    from sparse_tpu.parallel import dist

    mesh = Mesh(np.array(chip.devices[:4]), ("shards",))
    S = 4
    R = DIST_GRID * DIST_GRID  # rows per shard (weak scaling: g^2 per chip)
    H = 2 * DIST_GRID  # 5-point halo: one grid row each side
    k = 5
    A = dist.DistCSR(
        mesh=mesh, axis="shards", shape=(S * R, S * R),
        row_splits=np.arange(S + 1) * R, col_splits=np.arange(S + 1) * R,
        R=R, C=R, HL=H // 2, HR=H // 2, mode="halo", layout="ell",
        dtype=np.dtype(np.float32),
    )
    s3 = NamedSharding(mesh, P("shards", None, None))
    s1 = NamedSharding(mesh, P("shards"))
    idx = _sds((S, R, k), jnp.int32, s3)
    val = _sds((S, R, k), jnp.float32, s3)
    vec = _sds((S * R,), jnp.float32, s1)

    @jax.jit
    def run(bp, xp, ell_idx, ell_val):
        # a described device holds no arrays: hand the DistCSR the traced
        # blocks, which make_dist_cg passes to its loop as arguments
        A.ell_idx, A.ell_val = ell_idx, ell_val
        return dist.make_dist_cg(A, tol=0.0, maxiter=300)(bp, xp)

    c = run.lower(vec, vec, idx, val).compile()
    hlo = c.as_text()
    assert "collective-permute" in hlo  # the halo exchange
    assert "all-reduce" in hlo  # the CG dot products
    used = _device_bytes(c)
    print(f"dist_cg g={DIST_GRID}/chip: {used / 2**30:.2f} GiB per device")
    assert used < HBM_BYTES


def test_dist_cg_dia_program_compiles_on_four_chips(chip):
    """The banded mesh CG of the cell pde_cg_4chip at the largest per-chip
    grid its rule allows (3200^2), with uneven row blocks as nnz-balanced
    splits give them (a block's halo is cut and placed dynamically): the
    halo exchange and the reductions are in the program, nothing gathers,
    and the program is named after the layout."""
    from sparse_tpu.parallel import dist

    mesh = Mesh(np.array(chip.devices[:4]), ("shards",))
    S, g = 4, 3200
    n = 2 * g  # weak scaling: the global side grows as sqrt(chips)
    rows = np.array([g * g + n // 5, g * g - n // 5, g * g - n // 5,
                     g * g + n // 5])
    splits = np.concatenate([[0], np.cumsum(rows)])
    R = int(rows.max())
    A = dist.DistCSR(
        mesh=mesh, axis="shards", shape=(S * g * g, S * g * g),
        row_splits=splits, col_splits=splits, R=R, C=R, HL=n, HR=n,
        mode="halo", layout="dia", dtype=np.dtype(np.float32),
        dia_offsets=(-n, -1, 0, 1, n),
    )
    vec = _sds((S * R,), jnp.float32, NamedSharding(mesh, P("shards")))

    solve = dist._cg_program(A, maxiter=300, conv_test_iters=25, M=None)
    assert solve.__name__ == "dist_cg_dia"
    c = solve.lower(vec, vec, 0.0, 0.0, *(vec,) * 5).compile()  # 5 planes
    hlo = c.as_text()
    assert "collective-permute" in hlo  # the halo exchange
    assert "all-reduce" in hlo  # the CG dot products
    assert "gather(" not in hlo  # shifted slices, no index loads
    assert "dist.halo" in hlo and "dist.local_spmv" in hlo
    used = _device_bytes(c)
    print(f"dist_cg dia g={g}/chip: {used / 2**30:.2f} GiB per device")
    assert used < HBM_BYTES


# ---------------------------------------------------------------------------
# the general CG: what linalg.cg takes for a matrix that is not banded, at
# the size of the benchmark's unstructured SPD cell (1108^2 rows, rows padded
# to 9 entries)
# ---------------------------------------------------------------------------
def test_cg_general_compiles_with_the_matrix_as_an_argument(one_chip):
    import re

    from sparse_tpu import linalg

    n, width = 1108 * 1108, 9
    arrays = (_sds((n, width), jnp.int32, one_chip),
              _sds((n, width), jnp.float32, one_chip))
    b = _sds((n,), jnp.float32, one_chip)
    lowered = linalg._cg_general_program.lower(
        arrays, b, None, 1e-8, 50,
        kind="ell", meta=None, conv_test_iters=25, tapped=False)
    c = lowered.compile()
    text = c.as_text()
    assert "jit_cg_general" in text
    assert _device_bytes(c) < HBM_BYTES
    # no constant of the compiled program is larger than a scalar: the
    # layout's planes are its parameters (PR 22: 2.68 GB of captured blocks)
    for dims in re.findall(r"\b[a-z]+\d+\[([\d,]*)\][^=\n]*\bconstant\(", text):
        assert int(np.prod([int(d) for d in dims.split(",") if d] or [1],
                           dtype=np.int64)) <= 1, dims


# ---------------------------------------------------------------------------
# the windowed step-major layout (kernels/well_spmv.py): the kernel alone and
# the general CG through it, at the benchmark cell's size and at the rule's
# far corner (x filling its VMEM budget, the unit lists their SMEM budget,
# the fullest grid step's block its VMEM budget)
# ---------------------------------------------------------------------------
WELL_CELL = (1108 * 1108, 39015, 582)  # spd_general_1chip: rows, units, block


def _well_arrays(n, units, block, sharding):
    """The layout's arrays as ``csr_array._well_build`` hands them over:
    ``units`` units in all, ``block`` in the fullest grid step."""
    from sparse_tpu.kernels import well_spmv as ws

    n_pad = ws.padded_size(n)
    stored = (n_pad // (ws.TILE * ws.STEP_TILES) * block, 8, ws.LANES)
    return n_pad, {
        "uptr": _sds((n_pad // ws.TILE + 1,), jnp.int32, sharding),
        "ustart": _sds((units,), jnp.int32, sharding),
        "lane": _sds(stored, jnp.int32, sharding),
        "val": _sds(stored, jnp.float32, sharding),
        "perm": _sds((n,), jnp.int32, sharding),
        "inv_perm": _sds((n,), jnp.int32, sharding),
    }


@pytest.mark.parametrize("n,units,block", [
    WELL_CELL,
    # csr._WELL_X_BYTES of x, _WELL_MAX_UNITS stored, one grid step's block
    # at _WELL_BLOCK_BYTES (the stored units are then past the rule's cap:
    # the corner of every budget at once)
    (4 << 20, 1 << 17, 2048),
], ids=["cell", "rule-corner"])
def test_well_spmv_kernel_compiles(one_chip, n, units, block):
    from sparse_tpu import csr
    from sparse_tpu.kernels import well_spmv as ws

    assert 4 * n <= csr._WELL_X_BYTES and units <= csr._WELL_MAX_UNITS
    assert 2 * ws.UNIT_BYTES * block <= csr._WELL_BLOCK_BYTES
    n_pad, a = _well_arrays(n, units, block, one_chip)
    x2 = _sds((n_pad // ws.LANES, ws.LANES), jnp.float32, one_chip)
    c = ws.well_spmv.lower(a["uptr"], a["ustart"], a["lane"], a["val"], x2).compile()
    assert "well_spmv" in c.as_text() and "tpu_custom_call" in c.as_text()
    assert _device_bytes(c) < HBM_BYTES


def test_cg_general_compiles_through_the_windowed_layout(one_chip, monkeypatch):
    import re

    from sparse_tpu import linalg

    # `csr.form_space` interprets the kernel off a TPU; this process's
    # backend is the CPU and the program is compiled for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = WELL_CELL[0]
    n_pad, arrays = _well_arrays(*WELL_CELL, one_chip)
    lowered = linalg._cg_general_program.lower(
        arrays, _sds((n,), jnp.float32, one_chip), None, 1e-8, 50,
        kind="well", meta=(n, n_pad), conv_test_iters=25, tapped=False)
    c = lowered.compile()
    text = c.as_text()
    assert "jit_cg_general" in text and _device_bytes(c) < HBM_BYTES
    # the kernel is the loop's product; XLA's gathers are the two
    # permutations, once a solve, outside the loop
    body = re.search(r"\n%[\w.]*region_0[\w.]* \(.*?\n}\n", text, re.S)
    assert body and "well_spmv" in body.group(0)
    assert " gather(" not in body.group(0) and "kCustom" not in body.group(0)
    assert len(re.findall(r"= f32\[\d+\][^\n]* fusion\([^\n]*kind=kCustom", text)) == 2


# ---------------------------------------------------------------------------
# restarted GMRES over declared operators (PR 42): the whole-solve program
# jit_gmres at the size of the benchmark's nonsymmetric cell, SuiteSparse
# atmosmodd's 148 x 148 x 58 box (1,270,432 rows, seven planes), restart 30
# ---------------------------------------------------------------------------
GMRES_BOX = (148, 148, 58)
GMRES_BASIS_ROWS = 9928  # 8 * ceil(1,270,432 / 1024): a basis row is [9928, 128]
GMRES_SCOPES = ("gmres.spmv", "gmres.orth", "gmres.small", "gmres.update")


def _gmres_compiled(one_chip, monkeypatch, restart=30, kernel=True,
                    orth_kernel=True):
    """``jit_gmres`` at the cell's box as the chip runs it since PR 50: the
    matrix's operand the packed rows of the layout ``dia`` at the plan the
    rule picks (``kernel=False``: the scipy-layout planes, the XLA form the
    rule leaves everywhere else), and since PR 54 the orthogonalisation's
    middle through ``orth_update_project``, with the blocks the rule gives
    the call that builds the program (``orth_kernel=False``: none, as off a
    TPU, and the four contractions stay)."""
    from sparse_tpu import csr, linalg
    from sparse_tpu.kernels.dia_spmv import DiaRows, dia_rows_plan

    # `csr.form_matvec` and the orthogonalisation's kernel are interpreted
    # off a TPU, and `linalg._orth_blocks` declines there; this process's
    # backend is the CPU and the program is compiled for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    a, b, c = GMRES_BOX
    n = a * b * c
    offsets = (-a * b, -a, -1, 0, 1, a, a * b)
    assert (n, offsets) == ATMOSMODD
    vec = _sds((n,), jnp.float32, one_chip)
    operand = _sds((len(offsets), n), jnp.float32, one_chip)
    if kernel:
        plan = dia_rows_plan(offsets, n, csr._DIA_VMEM_BYTES)
        operand = DiaRows(
            _sds((plan.D * plan.G * plan.TM,), jnp.float32, one_chip), plan)
    return n, linalg._gmres_program.lower(
        operand, (), vec, vec, _sds((), jnp.float32, one_chip), 10,
        a_apply=linalg._FormApply("dia", (offsets, (n, n))),
        m_apply=linalg._identity_apply, restart=restart, tapped=False,
        orth_blocks=(linalg._orth_blocks(restart, np.float32, n, (operand, vec))
                     if orth_kernel else None)).compile()


def _computations(text: str) -> dict:
    return {m.group(1): m.group(0) for m in re.finditer(
        r"\n%([\w.\-]+) \([^\n]*\{\n.*?\n\}\n", text, re.S)}


def _fusions(computation: str) -> list:
    """(name, result, the rest of the line) of a computation's fusions."""
    return re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = ([^\n]*?) fusion\(([^\n]*)$",
        computation, re.M)


def _called(fusion_rest: str) -> str:
    """The name of the computation a fusion's line calls."""
    return re.search(r"calls=%([\w.\-]+)", fusion_rest).group(1)


def _arnoldi_body(computations: dict) -> str:
    """The Arnoldi loop's body: the computation that chooses the
    orthogonalisation's stage."""
    (body,) = [c for c in computations.values()
               if re.search(r" conditional\([^\n]*gmres\.orth/", c)]
    return body


def _stage_branches(body: str, computations: dict, scope: str) -> list:
    """The branch computations of the one ``conditional`` in a loop body: the
    orthogonalisation's stages, in order. The ``conditional`` itself stands
    under the orthogonalisation's ``scope``: its own time is part of what
    the cell's share of that scope reads."""
    (names,) = re.findall(
        r" conditional\([^\n]*branch_computations=\{([^}]*)\}[^\n]*%s/"
        % re.escape(scope), body)
    return [computations[b.strip().lstrip("%")] for b in names.split(",")]


def _assert_float32_and_no_copy_of(text: str, basis: str):
    """No copy of the basis is planned anywhere, and nothing is there for the
    MXU's default bfloat16 pass to touch."""
    assert not [ln for ln in text.splitlines()
                if re.search(r"\bcopy(-start)?\(", ln) and basis in ln]
    assert "convolution" not in text and "bf16" not in text
    assert not re.search(r"\bdot\(", text)


def test_gmres_program_compiles_at_atmosmodd_size(one_chip, monkeypatch):
    n, c = _gmres_compiled(one_chip, monkeypatch)
    text = c.as_text()
    assert "jit_gmres" in text and _device_bytes(c) < HBM_BYTES
    ma = c.memory_analysis()
    # the matrix and the vectors are arguments: 7 planes, b and the start
    assert ma.argument_size_in_bytes >= 9 * 4 * n
    # the Krylov basis, a row to a tile (PR 47): [31, R, 128] with R = 8 *
    # ceil(n / 1024), tiled (8, 128) on its two minor dimensions, so a row is
    # whole tiles of its own (157.6 MB); it is updated in place, a row a
    # step: a copy a step, or a second basis, would pass 0.3 GB
    rows = GMRES_BASIS_ROWS
    assert rows == 8 * -(-n // 1024)
    basis, row = f"f32[31,{rows},128]", f"f32[1,{rows},128]"
    assert basis + "{2,1,0:T(8,128)}" in text
    assert f"f32[31,{n}]" not in text  # the layout the tree had
    assert 31 * rows * 128 * 4 < ma.temp_size_in_bytes < 0.25e9
    computations = _computations(text)
    body = _arnoldi_body(computations)
    # the step's write: the root of a fusion whose result is the basis, a
    # dynamic-update-slice of one [1, R, 128] row into its own parameter, the
    # division by the norm inside it, and no read of the basis beside it
    (write,) = [(res, rest) for _name, res, rest in _fusions(body)
                if res.startswith(basis)]
    assert "gmres.update/" in write[1]
    inner = computations[_called(write[1])]
    (root,) = re.findall(r"ROOT [^\n]*", inner)
    assert re.search(r"%s\S* dynamic-update-slice\(" % re.escape(basis), root)
    (param,) = re.findall(r"%%([\w.\-]+) = %s\S* parameter\(" % re.escape(basis),
                          inner)
    (update,) = re.findall(r"dynamic-update-slice\(%([\w.\-]+), %([\w.\-]+),", root)
    assert update[0] == param
    assert re.search(r"%%%s = %s\S* bitcast\(" % (re.escape(update[1]),
                                                 re.escape(row)), inner)
    assert " divide(" in inner and "dynamic-slice(" not in inner
    assert len(re.findall(r"%" + re.escape(param) + r"\b", inner)) == 2
    # the row's read for the product: a dynamic-slice of one row, handed on
    # flat by a bitcast (the same bytes)
    reads = [inner for inner in (computations[_called(rest)]
                                 for _name, _res, rest in _fusions(body)
                                 if "gmres.spmv/" in rest) if basis in inner]
    assert reads and all(
        "dynamic_slice_sizes={1,%d,128}" % rows in r for r in reads)
    # nothing in the Arnoldi body, its fusions or the stages' branches
    # reshapes a basis row other than by a bitcast, or shapes it for the write
    (branch_names,) = re.findall(
        r" conditional\([^\n]*branch_computations=\{([^}]*)\}", body)
    step = [body] + [computations[b.strip().lstrip("%")]
                     for b in branch_names.split(",")]
    step += [computations[_called(rest)] for comp in list(step)
             for _name, _res, rest in _fusions(comp)]
    for comp in step:
        assert not re.search(r" (reshape|transpose)\(", comp)
        assert f"f32[1,{n}]" not in comp
    # the product (PR 50): one kernel call a step, under the product's
    # scope, on the row in whole 1024-element tiles (the same length as its
    # flat view); no [7, n] intermediate, padded or not, anywhere in the
    # program. Around it two small fusions: the row's read with the cut to n
    # in it (a select: the kernel's operand), and the result's cut and pad
    # back to a row
    calls = [ln for ln in body.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "gmres.spmv/" in calls[0], calls
    # (the orthogonalisation's kernel is in the stages' branches: the test
    # of the scopes below)
    flat = f"f32[{rows * 128}]"
    assert re.match(r"\s*%%?[\w.\-]+ = %s\S* custom-call\(" % re.escape(flat),
                    calls[0])
    assert "dia_spmv_rows" in calls[0]
    assert not re.search(r"= f32\[7,\d+\]", text)
    in_scope = [ln for ln in body.splitlines() if "gmres.spmv/" in ln
                and re.search(r" (fusion|custom-call|pad|slice|copy)\(", ln)]
    assert len(in_scope) == 3, in_scope
    # the accumulated rotations are one matrix among the Arnoldi loop's
    # carried values (PR 52), not lists an inner loop walks: no `while` under
    # the scalars' scope in the body (the 30-trip loop the tree had there was
    # 181 of a step's 771 us: my chip runs, PR 50), the column through them
    # one multiply-and-sum over the matrix, read from fast memory
    assert "f32[31,31]" in body.lstrip().splitlines()[0]  # its signature
    assert not [ln for ln in body.splitlines()
                if re.search(r" while\([^\n]*gmres\.small/", ln)]
    (through,) = [computations[_called(rest)] for _name, _res, rest
                  in _fusions(body) if re.search(
                      r'op_name="[^"]*gmres\.small/[^"]*reduce_sum"', rest)]
    assert re.search(r"= f32\[31,31\]\{1,0:T\(8,128\)S\(1\)\} parameter\(",
                     through)
    assert " multiply(" in through and re.search(r"f32\[31\]\S* reduce\(",
                                                 through)
    # the contractions against the basis, XLA's and the kernel's, run in
    # float32 on the vector unit: nothing for the MXU's default bfloat16 pass
    assert "convolution" not in text and "bf16" not in text
    assert not re.search(r"\bdot\(", text)
    # the orthogonalisation's stages read static slices of the basis that
    # fuse into their contractions (PR 43), and the kernel takes the basis
    # whole and picks the stage's rows by its blocks (PR 54): no stage plans
    # a copy of it
    assert not [ln for ln in text.splitlines()
                if re.search(r"\bcopy(-start)?\(", ln) and basis in ln]
    # no constant of the program is larger than the (iters, cycles) pair:
    # nothing of the matrix is folded into it
    for dims in re.findall(r"\b[a-z]+\d+\[([\d,]*)\][^=\n]*\bconstant\(", text):
        assert int(np.prod([int(d) for d in dims.split(",") if d] or [1],
                           dtype=np.int64)) <= 2, dims


def test_gmres_program_without_the_kernel_is_the_parents(one_chip, monkeypatch):
    """The other side of the rule (float64, a band too wide, a rectangular
    matrix on a TPU): the scipy-layout planes as the operand, the XLA form,
    whose planes x x is a ``[7, n]`` array written and read again."""
    n, c = _gmres_compiled(one_chip, monkeypatch, kernel=False,
                           orth_kernel=False)
    text = c.as_text()
    assert "jit_gmres" in text and _device_bytes(c) < HBM_BYTES
    assert "tpu_custom_call" not in text
    body = _arnoldi_body(_computations(text))
    assert [ln for ln in body.splitlines()
            if "gmres.spmv/" in ln and re.search(r"= f32\[7,%d\]" % n, ln)]


def _stage_ops(branch: str, computations: dict, basis: str, scope: str):
    """Of one stage's branch: the results of its contraction fusions over
    the basis (``reduce_sum`` fusions under ``scope`` whose computation has
    the whole basis as a parameter) with those computations, and the lines
    of the orthogonalisation's kernel."""
    sums = r'op_name="[^"]*%s/[^"]*reduce_sum"' % re.escape(scope)
    orth = [(result, inner) for result, inner in (
        (result, computations[_called(rest)])
        for _name, result, rest in _fusions(branch) if re.search(sums, rest))
        if basis in inner]
    calls = [ln for ln in branch.splitlines()
             if "tpu_custom_call" in ln and "orth_update_project" in ln]
    return orth, calls


@pytest.mark.parametrize("orth_kernel", [True, False],
                         ids=["orth-kernel", "four-contractions"])
def test_gmres_program_ops_carry_their_scope(one_chip, monkeypatch, orth_kernel):
    """What ``benchmark/reducers/op_scope_share.py`` reads the cell's
    per-scope shares from: in the Arnoldi loop's body, and in the branches
    of the orthogonalisation's ``conditional`` (one a stage: PR 43; eight
    stages of four rows since PR 47), every
    fusion that carries an ``op_name`` stands under exactly one of the four
    scopes, each scope has one, and the compiler's own fusions without an
    ``op_name`` are few. Every stage reads its rows of the basis ``[31, R,
    128]`` (PR 47) three times (PR 54): two contractions, multiplies and
    sums sliced to the stage's rows of the major dimension inside the fusion
    (the first projection and the last update), and between them one call of
    the kernel ``orth_update_project`` on the whole basis, under the same
    scope. With the kernel's rule off the stage is the parent's four
    contractions and no kernel, and ``orth_passes`` says which."""
    from sparse_tpu import linalg

    n, c = _gmres_compiled(one_chip, monkeypatch, orth_kernel=orth_kernel)
    # the field of the span, from the program's own static argument
    blocks = linalg._orth_blocks(30, np.float32, n)
    assert blocks == (1248, 768, 552, 432, 360, 304, 264, 240)
    assert linalg._gmres_counts(np.zeros(2, np.int32), {
        "restart": 30, "orth_blocks": blocks if orth_kernel else None,
    })["orth_passes"] == (3 if orth_kernel else 4)
    assert linalg._orth_blocks(30, np.float64, n) is None
    rows = GMRES_BASIS_ROWS
    text = c.as_text()
    computations = _computations(text)
    body = _arnoldi_body(computations)
    assert "gmres.update/dynamic_update_slice" in body
    branches = _stage_branches(body, computations, "gmres.orth")
    _block, his = linalg._orth_stages(30)
    assert his == (4, 8, 12, 16, 20, 24, 28, 31) and len(branches) == len(his)

    named, unnamed = {}, []
    for name, _result, rest in [f for comp in [body, *branches]
                                for f in _fusions(comp)]:
        m = re.search(r'op_name="([^"]*)"', rest)
        if m:
            named[name] = m.group(1)
        else:
            unnamed.append(name)
    assert len(named) + len(unnamed) >= 12 + (2 if orth_kernel else 4) * len(his)
    for name, op_name in named.items():
        # (a fusion of two ops lists both names, under the one scope)
        under = {s for s in GMRES_SCOPES if f"/{s}/" in op_name}
        assert len(under) == 1, (name, op_name)
    for scope in GMRES_SCOPES:
        assert any(f"/{scope}/" in v for v in named.values()), scope
    assert len(unnamed) <= 6, unnamed
    basis = f"f32[31,{rows},128]"
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 + (len(his) if orth_kernel else 0))  # the product's two
    for hi, branch in zip(his, branches):
        # the stage's contractions read the stage's rows of the basis and no
        # others: half give the stage's coefficients, half a vector
        orth, calls = _stage_ops(branch, computations, basis, "gmres.orth")
        each = 1 if orth_kernel else 2
        assert len(orth) == 2 * each, (hi, orth)
        assert sorted(re.match(r"f32\[[\d,]+\]", r).group(0) for r, _ in orth) == \
            sorted([f"f32[{hi}]"] * each + [f"f32[{rows},128]"] * each)
        for _result, inner in orth:
            # the whole basis is the fusion's parameter (handed on by
            # reference), the stage's rows its slice of it: whole tiles for
            # any count of rows
            assert re.search(r"= f32\[31,%d,128\]\S* parameter\(" % rows, inner)
            if hi < 31:
                assert f"slice={{[0:{hi}], [0:{rows}], [0:128]}}" in inner, (hi, inner)
        # the kernel: one call a stage, under the orthogonalisation's scope,
        # the WHOLE basis its operand (no slice in front of it, so no copy),
        # w1 and the partial sums of h2 a tile a row its results
        assert len(calls) == (1 if orth_kernel else 0), (hi, calls)
        for call in calls:
            assert "/gmres.orth/" in call
            assert re.search(
                r"= \(f32\[%d,128\]\S*, f32\[%d,8,128\]\S*\) custom-call\("
                % (rows, hi), call), call
            assert ("operand_layout_constraints={f32[%d]{0}, %s{2,1,0}, "
                    "f32[%d,128]{1,0}}" % (hi, basis, rows)) in call
    _assert_float32_and_no_copy_of(text, basis)


# ---------------------------------------------------------------------------
# multigrid-preconditioned CG over declared operators (PR 40), the fine
# level's three stencil applies through kernels/grid_stencil.py (PR 44): the
# whole-solve program jit_pcg at the size of the benchmark's multigrid cell,
# 4480^2 unknowns, three levels, full weighting
# ---------------------------------------------------------------------------
GMG_GRID, GMG_LEVELS = 4480, 3
GMG_COMPILE_SECONDS = 30.0  # 2.7 s here alone; the parent's form 6 s


# ---------------------------------------------------------------------------
# the session's GMRES bucket program (PR 49): the library's pins, with a
# lane axis in front
# ---------------------------------------------------------------------------
BUCKET_GMRES_BOX = (40, 32, 24)  # 30,720 rows: 30 tile rows of 1024
BUCKET_GMRES_LANES = 4
BUCKET_GMRES_SCOPES = tuple("bucket." + s for s in GMRES_SCOPES)


def _bucket_gmres_compiled(one_chip, monkeypatch, restart=30, orth_kernel=True,
                           box=BUCKET_GMRES_BOX, lanes=BUCKET_GMRES_LANES):
    """``jit_bucket_gmres`` as the chip runs it: the plane product XLA's, the
    orthogonalisation's middle through ``orth_update_project`` since PR 54
    (``orth_kernel=False``: the rule's platform says no, as off a TPU). The
    program's ``orth_passes`` field says which."""
    from sparse_tpu import linalg
    from sparse_tpu.batch import service

    from .utils.spd import operator_module

    monkeypatch.setattr(service, "donate_argnums", lambda: (0, 1, 2))
    # the orthogonalisation's kernel is interpreted off a TPU and its rule
    # declines there; this process's backend is the CPU and the program is
    # compiled for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if not orth_kernel:
        monkeypatch.setattr(linalg, "_orth_platform", lambda: False)
    d = operator_module("cfd_7pt").make(
        {"box": box, "restart": restart, "cycles": 1}, 1)
    n, B = d["rows"], lanes
    P = sp.csr_matrix((d["data"], d["indices"], d["indptr"]), shape=(n, n))
    ses = service.SolveSession("gmres", restart=restart, batch_max=B,
                               warm_start=False)
    pattern = ses.pattern_of(P)
    run = ses._build_program(pattern, B, np.dtype(np.float32))
    assert run.matvec == "planes"
    assert run.event_fields(1, np.ones(1), 1)["orth_passes"] == (
        3 if orth_kernel else 4)
    return n, run.lower(
        _sds((B, pattern.nnz), jnp.float32, one_chip),
        _sds((B, n), jnp.float32, one_chip),
        _sds((B, n), jnp.float32, one_chip),
        _sds((B,), jnp.float32, one_chip),
        _sds((), jnp.int32, one_chip),
    ).compile()


def test_bucket_gmres_program_writes_one_row_a_lane_and_copies_no_basis(
        one_chip, monkeypatch):
    n, c = _bucket_gmres_compiled(one_chip, monkeypatch)
    text = c.as_text()
    B, rows = BUCKET_GMRES_LANES, 8 * -(-n // 1024)
    assert "jit_bucket_gmres" in text and _device_bytes(c) < HBM_BYTES
    # every lane's basis a row to a tile, the lane axis in front
    basis = f"f32[{B},31,{rows},128]"
    assert basis + "{3,2,1,0:T(8,128)}" in text
    assert f"f32[{B},31,{n}]" not in text  # the einsum form's layout
    ma = c.memory_analysis()
    assert B * 31 * rows * 128 * 4 < ma.temp_size_in_bytes
    computations = _computations(text)
    (body,) = [comp for comp in computations.values() if re.search(
        r" conditional\([^\n]*bucket\.gmres\.orth/", comp)]
    # the step's write: a dynamic-update-slice of one [B, 1, R, 128] row a
    # lane into the basis, in place (its own operand), the division by the
    # norm in the same fusion or the one that feeds it
    writes = [ln for comp in computations.values() for ln in comp.splitlines()
              if re.search(r"%s\S* dynamic-update-slice\(" % re.escape(basis),
                           ln)]
    assert len(writes) == 1 and "bucket.gmres.update/" in writes[0]
    (update,) = re.findall(r"dynamic-update-slice\(%[\w.\-]+, %([\w.\-]+),",
                           writes[0])
    (row,) = [ln for comp in computations.values() for ln in comp.splitlines()
              if re.match(r"\s*%%%s = " % re.escape(update), ln)]
    assert f"f32[{B},1,{rows},128]" in row
    # the row's read for the product: one row a lane
    assert "dynamic_slice_sizes={%d,1,%d,128}" % (B, rows) in text
    # one conditional, eight stages, each with the static slice inside its
    # contractions and the whole basis the operand of its kernel (PR 54): no
    # copy of the basis is planned anywhere
    (branch_names,) = re.findall(
        r" conditional\([^\n]*branch_computations=\{([^}]*)\}", body)
    assert len(branch_names.split(",")) == 8
    assert not [ln for ln in text.splitlines()
                if re.search(r"\bcopy(-start)?\(", ln) and basis in ln]
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    # float32 on the vector unit: nothing for the MXU's bfloat16 pass
    assert "convolution" not in text and "bf16" not in text
    assert not re.search(r"\bdot\(", text)
    # every lane's accumulated rotations one matrix among the Arnoldi loop's
    # carried values, and no loop over them under the scalars' scope (PR 52)
    assert f"f32[{B},31,31]" in body.lstrip().splitlines()[0]  # its signature
    assert not [ln for ln in body.splitlines()
                if re.search(r" while\([^\n]*bucket\.gmres\.small/", ln)]
    # the restarts are inside: a while over cycles around the Arnoldi while
    assert "while/body/while/body/bucket.gmres.orth" in text
    # nothing of a dispatch's values is folded into the program
    for dims in re.findall(r"\b[a-z]+\d+\[([\d,]*)\][^=\n]*\bconstant\(", text):
        assert int(np.prod([int(d) for d in dims.split(",") if d] or [1],
                           dtype=np.int64)) <= 7 * n, dims


def test_bucket_gmres_program_ops_carry_their_scope(one_chip, monkeypatch):
    """What ``benchmark/reducers/op_scope_share.py`` reads the served cell's
    shares from: the four scopes are in the program's text, and every fusion
    of the Arnoldi loop's body that carries an ``op_name`` stands under one
    of them."""
    _n, c = _bucket_gmres_compiled(one_chip, monkeypatch)
    text = c.as_text()
    for scope in BUCKET_GMRES_SCOPES:
        assert f"/{scope}/" in text, scope
    computations = _computations(text)
    (body,) = [comp for comp in computations.values() if re.search(
        r" conditional\([^\n]*bucket\.gmres\.orth/", comp)]
    named = [rest for _name, _res, rest in _fusions(body)
             if 'op_name="' in rest]
    assert named
    for rest in named:
        assert sum(f"/{s}/" in rest for s in BUCKET_GMRES_SCOPES) == 1, rest


@pytest.mark.parametrize("orth_kernel", [True, False],
                         ids=["orth-kernel", "four-contractions"])
def test_bucket_gmres_program_at_the_cells_size_reads_a_stage_three_times(
        one_chip, monkeypatch, orth_kernel):
    """The served cell's own program, 32 lanes of the 148 x 148 x 58 box
    (PR 54): in every stage's branch one call of ``orth_update_project``
    under the orthogonalisation's scope, the lanes' WHOLE bases its operand,
    and beside it exactly two contractions over them (the first projection,
    the last update); no copy of the 5 GB of bases, no ``dot``, no
    ``bf16``, and no more HBM than the parent's program plans (9.59 GB:
    ``bucket_program_hbm_gb``). With the rule off: four contractions a
    stage, no kernel, the field 4."""
    from sparse_tpu import linalg

    B = 32
    n, c = _bucket_gmres_compiled(one_chip, monkeypatch, box=GMRES_BOX, lanes=B,
                                  orth_kernel=orth_kernel)
    assert n == ATMOSMODD[0]
    text = c.as_text()
    rows = GMRES_BASIS_ROWS
    basis = f"f32[{B},31,{rows},128]"
    assert basis + "{3,2,1,0:T(8,128)}" in text
    # (what the compiler plans here: 9.430 GB with the kernel, 9.431 without)
    assert 9.3e9 < _device_bytes(c) < 9.6e9
    computations = _computations(text)
    (body,) = [comp for comp in computations.values() if re.search(
        r" conditional\([^\n]*bucket\.gmres\.orth/", comp)]
    branches = _stage_branches(body, computations, "bucket.gmres.orth")
    _block, his = linalg._orth_stages(30)
    assert len(branches) == len(his) == 8
    each = 1 if orth_kernel else 2
    for hi, branch in zip(his, branches):
        orth, calls = _stage_ops(branch, computations, basis, "bucket.gmres.orth")
        assert sorted(re.match(r"f32\[[\d,]+\]", r).group(0) for r, _ in orth) == \
            sorted([f"f32[{B},{hi}]"] * each + [f"f32[{B},{rows},128]"] * each)
        assert len(calls) == (1 if orth_kernel else 0), (hi, calls)
        for call in calls:
            assert "/bucket.gmres.orth/" in call
            assert re.search(
                r"= \(f32\[%d,%d,128\]\S*, f32\[%d,%d,8,128\]\S*\) custom-call\("
                % (B, rows, B, hi), call), call
            assert basis + "{3,2,1,0}" in call
    assert text.count('custom_call_target="tpu_custom_call"') == (
        len(his) if orth_kernel else 0)
    _assert_float32_and_no_copy_of(text, basis)


def _fleet_bucket_gmres_lowered(chip, monkeypatch, sharded: bool):
    """The fleet's GMRES bucket program for four described chips, float32:
    what ``SolveSession("gmres", fleet=...)`` dispatches under the batch
    strategy, its lanes sharded on the mesh's ``lanes`` axis and the rest
    left to GSPMD (``fleet.build_batch_program``). ``sharded=False``: the
    one-device program handed the same sharded lanes, the other side."""
    from sparse_tpu import fleet
    from sparse_tpu.batch import service

    from .utils.spd import operator_module

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d = operator_module("cfd_7pt").make(
        {"box": BUCKET_GMRES_BOX, "restart": 30, "cycles": 1}, 1)
    n, B = d["rows"], 8
    pat = sp.csr_matrix((d["data"], d["indices"], d["indptr"]), shape=(n, n))
    mesh = Mesh(np.array(chip.devices[:4]), (fleet.FLEET_AXIS,))
    ses = service.SolveSession("gmres", restart=30, batch_max=B,
                               warm_start=False)
    pattern = ses.pattern_of(pat)
    plan = fleet.FleetPlan("batch", mesh, "v5e-2x2") if sharded else None
    run = ses._build_program(pattern, B, np.dtype(np.float32), plan=plan)
    lanes = NamedSharding(mesh, P(fleet.FLEET_AXIS))
    # (the fleet's program puts its arguments on the mesh itself, a
    # constraint under this jit; the one-device program takes them as given)
    return run, jax.jit(run).lower(
        _sds((B, pattern.nnz), jnp.float32, lanes),
        _sds((B, n), jnp.float32, lanes),
        _sds((B, n), jnp.float32, lanes),
        _sds((B,), jnp.float32, lanes),
        _sds((), jnp.int32, NamedSharding(mesh, P())))


def test_fleet_bucket_gmres_program_compiles_on_four_chips(chip, monkeypatch):
    """A Mosaic kernel cannot be partitioned by GSPMD, so the fleet's batch
    strategy builds the bucket program without the orthogonalisation's
    kernel: four contractions a stage, ``orth_passes`` 4, two lanes' bases a
    device. The one-device program, with the kernel, is refused for sharded
    lanes at its lowering: what a fleet dispatch on the chip would have met
    (and degraded from) had the rule not known how the program is
    partitioned."""
    run, lowered = _fleet_bucket_gmres_lowered(chip, monkeypatch, sharded=True)
    assert run.event_fields(1, np.ones(1), 1)["orth_passes"] == 4
    text = lowered.compile().as_text()
    assert "tpu_custom_call" not in text and "orth_update_project" not in text
    rows = 8 * -(-30720 // 1024)
    assert f"f32[2,31,{rows},128]" in text and f"f32[8,31,{rows},128]" not in text
    assert "/bucket.gmres.orth/" in text and "all-reduce" in text
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        _fleet_bucket_gmres_lowered(chip, monkeypatch, sharded=False)


def _gmg_pcg_compiled(one_chip, monkeypatch, fine_kernel: bool):
    import time

    from sparse_tpu import linalg
    from sparse_tpu.models import gmg_grid

    # `gmg_grid._fine_stencil` interprets the kernel off a TPU; this
    # process's backend is the CPU and the program is compiled for the
    # described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    five = tuple(gmg_grid.poisson_stencil(GMG_GRID))
    nine = tuple((a, b) for a in (-1, 0, 1) for b in (-1, 0, 1))
    sides = [GMG_GRID // 2 ** k for k in range(GMG_LEVELS)]
    scalar = _sds((), jnp.float32, one_chip)
    plane = lambda s: _sds((s, s), jnp.float32, one_chip)  # noqa: E731
    m_operands = ((tuple(scalar for _ in five), scalar),) + tuple(
        (tuple(plane(s) for _ in nine), plane(s)) for s in sides[1:])
    static = ((GMG_GRID, five),) + tuple((s, nine) for s in sides[1:])
    vec = _sds((GMG_GRID ** 2,), jnp.float32, one_chip)
    lowered = linalg._pcg_program.lower(
        tuple(scalar for _ in five), m_operands, vec, vec, scalar, 50,
        a_apply=gmg_grid._GridApply(GMG_GRID, five, fine_kernel),
        m_apply=gmg_grid._Cycle(static, "linear", fine_kernel),
        conv_test_iters=25, tapped=False)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, time.perf_counter() - t0


def _loop_body(text: str) -> str:
    """The computation of the program's one ``while`` that holds the
    V-cycle: CG's loop body."""
    bodies = [m.group(0) for m in re.finditer(
        r"\n%[\w.\-]+ \([^\n]*\{\n.*?\n\}\n", text, re.S)
        if "/while/body/gmg.l0/" in m.group(0) and "fused_computation" not in
        m.group(0).split("(", 1)[0]]
    (body,) = bodies
    return body


def _fine_stencil_ops(text: str) -> list:
    """The lines of XLA's own form of the fine level's stencil: the pad of
    the grid to 4482^2 and the slices of it, which the compiler names by
    ``stencil_apply``'s frame (``restrict_grid`` pads to 4482^2 too, under
    its own name, and stays)."""
    return [ln for ln in text.splitlines()
            if re.search(r'op_name="[^"]*stencil_apply', ln)
            and re.search(r"f32\[448[02],448[02]\]", ln)]


def test_gmg_pcg_program_takes_the_fine_levels_kernels(one_chip, monkeypatch):
    c, seconds = _gmg_pcg_compiled(one_chip, monkeypatch, fine_kernel=True)
    text = c.as_text()
    assert "jit_pcg" in text and _device_bytes(c) < HBM_BYTES
    assert seconds < GMG_COMPILE_SECONDS, seconds
    ma = c.memory_analysis()
    # the hierarchy's planes and weights are arguments (level 1's ten grids;
    # of the coarsest level, which only smooths, the weight alone is read),
    # with b and the start; the loop's temporaries are the parent's (778.7
    # MB there), no grid more for the kernels
    held = 10 * 4 * 2240 ** 2 + 4 * 1120 ** 2 + 2 * 4 * GMG_GRID ** 2
    assert held <= ma.argument_size_in_bytes < 0.4e9
    assert ma.temp_size_in_bytes < 0.8e9
    # no pad of the fine grid and no slice of it is left anywhere
    assert not _fine_stencil_ops(text)
    # the loop's three applies are the kernel's custom calls: the cycle's
    # two under level 0's scope, A p under no level's
    calls = [ln for ln in _loop_body(text).splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = sorted(re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls)
    assert len(names) == 3, names
    scoped = [n for n in names if "/gmg.l0/" in n]
    assert len(scoped) == 2 and not [n for n in names if re.search(r"gmg\.l[1-9]", n)]
    assert sorted(re.search(r"grid_stencil5_(\w+)", n).group(1) for n in scoped) == [
        "residual", "smooth"]
    (product,) = [n for n in names if n not in scoped]
    assert "grid_stencil5_apply" in product and "gmg." not in product
    # a fourth, outside the loop: the start's residual b - A x0
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 4
    # level 1 keeps stencil_apply under its own scope (level 2 only smooths)
    assert re.search(r'op_name="[^"]*/gmg\.l1/jit\(stencil_apply\)', text)


def test_gmg_pcg_program_without_the_kernels_is_the_parents(one_chip, monkeypatch):
    """The other side: the program every other hierarchy keeps (a side off
    128, planes, a mesh) pads the fine grid three times in the loop and
    slices each pad off the tile."""
    c, _seconds = _gmg_pcg_compiled(one_chip, monkeypatch, fine_kernel=False)
    text = c.as_text()
    assert _device_bytes(c) < HBM_BYTES and "tpu_custom_call" not in text
    body_pads = [ln for ln in _fine_stencil_ops(text)
                 if " pad(" in ln and "/while/body/" in ln]
    assert len(body_pads) == 3, body_pads
    assert len([ln for ln in body_pads if "/gmg.l0/" in ln]) == 2
    assert [ln for ln in _fine_stencil_ops(text)
            if "slice={[1:4481], [2:4482]}" in ln]


# ---------------------------------------------------------------------------
# the same program over a hierarchy laid over four chips in row blocks (PR
# 46), 1280 x 5120 a chip (the smallest side ISSUE 46 lists): every apply and transfer under
# shard_map with its neighbours' edge rows, the fine level's kernel a shard
# ---------------------------------------------------------------------------
GMG_MESH_GRID = 5120


def _gmg_pcg_mesh_compiled(chip, monkeypatch, row_blocks: bool):
    from sparse_tpu import linalg
    from sparse_tpu.models import gmg_grid

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(chip.devices[:4]), ("shards",))
    blocks, whole, flat = (NamedSharding(mesh, s) for s in (
        P("shards", None), P(), P("shards")))
    rows = gmg_grid._Rows(mesh, "shards") if row_blocks else None
    g = GMG_MESH_GRID
    five = tuple(gmg_grid.poisson_stencil(g))
    nine = tuple((a, b) for a in (-1, 0, 1) for b in (-1, 0, 1))
    sides = [g // 2 ** k for k in range(GMG_LEVELS)]
    scalar = _sds((), jnp.float32, whole)
    plane = lambda s: _sds((s, s), jnp.float32, blocks)  # noqa: E731
    m_operands = ((tuple(scalar for _ in five), scalar),) + tuple(
        (tuple(plane(s) for _ in nine), plane(s)) for s in sides[1:])
    static = ((g, five),) + tuple((s, nine) for s in sides[1:])
    vec = _sds((g * g,), jnp.float32, flat)
    return linalg._pcg_program.lower(
        tuple(scalar for _ in five), m_operands, vec, vec, scalar, 50,
        a_apply=gmg_grid._GridApply(g, five, row_blocks, rows),
        m_apply=gmg_grid._Cycle(static, "linear", row_blocks,
                                (rows,) * GMG_LEVELS if row_blocks else ()),
        conv_test_iters=25, tapped=False).compile()


def _loop_collectives(text: str) -> dict:
    """{opcode: result types} of the collectives under ``/while/body/``."""
    out: dict = {}
    for ln in text.splitlines():
        m = re.search(r" = (.*?) (collective-permute|all-reduce|all-gather|"
                      r"all-to-all)(?:-start)?\(", ln)
        if m and "/while/body/" in ln:
            out.setdefault(m.group(2), []).append(m.group(1))
    return out


def test_gmg_pcg_mesh_program_exchanges_one_row_a_side(chip, monkeypatch):
    c = _gmg_pcg_mesh_compiled(chip, monkeypatch, row_blocks=True)
    text = c.as_text()
    assert _device_bytes(c) < HBM_BYTES
    loop = _loop_collectives(text)
    # an iteration: five applies x 2 and four transfers x 1 exchanges, each
    # of one row of its level's side; CG's two dot products; no gather
    assert set(loop) == {"collective-permute", "all-reduce"}, loop
    assert len(loop["collective-permute"]) == 14 and len(loop["all-reduce"]) == 2
    rows = sorted(int(re.search(r"f32\[1,(\d+)\]", t).group(1))
                  for t in loop["collective-permute"])
    assert rows == [1280] + [2560] * 6 + [5120] * 7, rows
    assert not re.search(r" (all-gather|all-to-all)(-start)?\(", text)
    # the fine level's three applies are the kernel's, on a shard's block
    calls = [ln for ln in _loop_body(text).splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 3 and all("f32[1280,5120]" in ln.split(" custom-call(")[0]
                                   for ln in calls)
    assert not _fine_stencil_ops(text.replace("f32[5120", "f32[4480"))


def test_gmg_pcg_mesh_program_left_to_the_partitioner_is_the_other_side(
        chip, monkeypatch):
    """What the parent ran over the same lay-out, and why the row-block forms
    exist: the partitioner's own answer to ``stencil_apply``'s pad and
    slices is five exchanges an apply (29 an iteration with the transfers'),
    and the restriction's strided slices become gathers of index vectors."""
    c = _gmg_pcg_mesh_compiled(chip, monkeypatch, row_blocks=False)
    loop = _loop_collectives(c.as_text())
    assert len(loop["collective-permute"]) > 2 * 14
    assert all("f32[" not in t for t in loop.get("all-gather", []))



# ---------------------------------------------------------------------------
# jit_pcg over HPCG's pair (PR 53: models/hpcg_grid.py) at the cell's size,
# 256^3 and four levels: 27 stored planes a level in the colour-major order,
# the 8-colour symmetric Gauss-Seidel cycle through kernels/hpcg_colour.py,
# the solve's crossings into that order outside the loop
# ---------------------------------------------------------------------------
HPCG_SIDE, HPCG_LEVELS = 256, 4
HPCG_COMPILE_SECONDS = 45.0  # 8 s here alone; with the colour a constant of
# 105 fusions a cycle (hpcg_grid._row_sum, what the CPU runs) 77 s


def test_hpcg_pcg_program_compiles_at_the_cells_size(one_chip, monkeypatch):
    import time

    from sparse_tpu import linalg
    from sparse_tpu.models import hpcg_grid

    # `hpcg_grid._rows` interprets the kernel off a TPU; this process's
    # backend is the CPU and the program is compiled for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dims = (HPCG_SIDE,) * 3
    planes = tuple(
        _sds((8, 27) + tuple(d >> (k + 1) for d in dims), jnp.float32, one_chip)
        for k in range(HPCG_LEVELS))
    vec = _sds((HPCG_SIDE ** 3,), jnp.float32, one_chip)
    t0 = time.perf_counter()
    lowered = linalg._pcg_program.lower(
        planes[0], planes, vec, vec, _sds((), jnp.float32, one_chip), 64,
        a_apply=hpcg_grid._Product(dims, True),
        m_apply=hpcg_grid._Cycle(dims, HPCG_LEVELS, True),
        conv_test_iters=25, tapped=False)
    c = lowered.compile()
    seconds = time.perf_counter() - t0
    print(f"jit_pcg over the HPCG pair at {HPCG_SIDE}^3: traced, lowered and "
          f"compiled in {seconds:.1f} s")
    assert seconds < HPCG_COMPILE_SECONDS, seconds
    text = c.as_text()
    assert "jit_pcg" in text and _device_bytes(c) < HBM_BYTES
    ma = c.memory_analysis()
    # the planes are arguments (the fine level's twice: A's and the cycle's
    # parameter, one buffer in a run), with b and the start; the coarser
    # levels' half-grids of 64, 32 and 16 lanes are padded to the tile's 128
    held = 27 * 4 * sum((HPCG_SIDE >> k) ** 3 for k in range(HPCG_LEVELS))
    fine = 27 * 4 * HPCG_SIDE ** 3
    assert held + fine <= ma.argument_size_in_bytes < held + fine + 0.5e9
    # the loop's temporaries are CG's vectors and a cycle's blocks: no
    # array padded from a lane axis of 2 (4.3 GB a crossing, as a reshape
    # to [.., nx/2, 2] was laid out)
    assert ma.temp_size_in_bytes < 0.5e9
    # what the design promises: nothing in the program gathers or scatters
    assert not re.search(r" (gather|scatter)\(", text)
    # a level's symmetric steps are a loop each around one kernel, its
    # residual one more call of it, and A p one: 7 + 3 + 1 in the loop at
    # four levels, and the start's residual outside it
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]
    assert len(names) == 2 * HPCG_LEVELS - 1 + HPCG_LEVELS - 1 + 2, names
    for lvl in range(HPCG_LEVELS):
        steps = [n for n in names
                 if f"/while/body/hpcg.l{lvl}/hpcg.l{lvl}.symgs/while/body/" in n]
        assert len(steps) == (2 if lvl < HPCG_LEVELS - 1 else 1), (lvl, names)
        assert all("hpcg_colour_update" in n for n in steps)
    for lvl in range(HPCG_LEVELS - 1):
        (res,) = [n for n in names if f"/hpcg.l{lvl}/hpcg.l{lvl}.spmv/" in n]
        assert "hpcg_colour_update" in res
    products = [n for n in names if "/hpcg.spmv/" in n]
    assert len(products) == 2 and all("hpcg_colour_product" in n for n in products)
    assert sum("/while/body/" in n for n in products) == 1
    # the loop reorders no vector of the fine level: its transposes are the
    # re-colourings between a level's block 0 and the next level
    moved = {n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.endswith("/transpose") and "/while/body/" in n}
    assert moved and all(re.search(r"hpcg\.l\d\.transfer/transpose$", n)
                         for n in moved), moved


# ---------------------------------------------------------------------------
# jit_batched_bicgstab (PR 55: batch/krylov.py) at the cell's shapes: 32,768
# lanes of XGC's collision system, 992 rows and nine planes a lane, Jacobi
# ---------------------------------------------------------------------------
XGC_LANES, XGC_ROWS = 32768, 992
XGC_OFFSETS = (-33, -32, -31, -1, 0, 1, 31, 32, 33)


def test_batched_bicgstab_program_compiles_at_the_cells_shapes(one_chip):
    """The cell's program (PR 55), since PR 56 a ladder of four stages at
    halving widths (``krylov._bicgstab_ladder``)."""
    import time

    from sparse_tpu.batch import krylov
    from sparse_tpu.batch.operator import _PlanesApply
    from sparse_tpu.precond.jacobi import _scale

    B, n, D = XGC_LANES, XGC_ROWS, len(XGC_OFFSETS)
    widths = krylov._ladder(B)
    assert widths == (32768, 16384, 8192, 4096)
    vec = _sds((B, n), jnp.float32, one_chip)
    t0 = time.perf_counter()
    c = krylov._bicgstab_program.lower(
        (_sds((B, D, n), jnp.float32, one_chip),), (vec,), vec, vec,
        _sds((B,), jnp.float32, one_chip), 200,
        a_apply=_PlanesApply(XGC_OFFSETS), m_apply=_scale, conv_test_iters=1,
        tapped=False, lane_operands=((True,), (True,))).compile()
    seconds = time.perf_counter() - t0
    ma = c.memory_analysis()
    print(f"jit_batched_bicgstab at {B} lanes, stages {widths}: traced, "
          f"lowered and compiled in {seconds:.1f} s; {_device_bytes(c) / 1e9:.2f} "
          f"GB (arguments {ma.argument_size_in_bytes / 1e9:.2f}, temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.2f})")
    assert seconds < 30, seconds  # 6 s here alone (2 s with one stage)
    text = c.as_text()
    assert "jit_batched_bicgstab" in text
    # the planes, the diagonal, b and the start are arguments (nothing an
    # operator holds is a constant), unpadded: the runtime lays the lanes
    # along the 128-wide axis of a tile and the 992 rows along the 8-wide one
    held = 4 * B * n * (D + 3)
    assert held <= ma.argument_size_in_bytes < held + 1e6
    assert re.search(rf"f32\[{B},{D},{n}\]\{{0,2,1:T\(8,128\)\}} parameter\(0\)", text)
    # the stages' gathered copies of the planes and of a dozen vectors, each
    # stage's half the one before, and the lanes-major copies a compaction
    # gathers from: under 7 GB with the arguments
    assert _device_bytes(c) < 7e9 < HBM_BYTES
    # four loops, one a stage. Stage 0 carries the arguments' layout, the
    # lanes along the 128-wide axis; stages 1 to 3 come out lanes-major
    # ({1,0}: a compaction gathers whole rows of a lanes-major copy and
    # nothing ties the result to the arguments' layout). Read on the chip
    # (PERF.md section 6, PR 56): a lanes-major step takes 0.360 us a lane
    # where stage 0's takes 0.351, and a program told to keep every stage
    # lanes-minor (a layout constraint on the gathered arrays and on each
    # step's carried ones) pays that back in the copies around its gathers:
    # 185.6 against 186.1 ms a call. So the compiler's layouts stand.
    loops = re.findall(r" while\(.*?body=(%[\w.\-]+)", text)
    carried = re.findall(r"= \((f32\[\d+,\d+\]\{[^}]*\}), .*? while\(", text)
    assert len(loops) == len(carried) == len(widths)
    assert sorted(carried, key=lambda t: -int(t[4:t.index(",")])) == [
        f"f32[{B},{n}]{{0,1:T(8,128)}}"] + [
        f"f32[{w},{n}]{{1,0:T(8,128)}}" for w in widths[1:]]
    # and no copy inside a loop's body
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"\n(%[\w.\-]+) \([^\n]*\{\n(.*?)\n\}", text, re.S)}
    for body in loops:
        assert " copy(" not in bodies[body], body
    # one fetch: the lanes' counts and the stages' trips leave as one [4, B]
    # array beside X, of integers (the residuals by their bits): the chip
    # flushes float32 denormals to zero, which is what a small count's bits
    # would be
    assert re.search(rf"ROOT %\S+ = \(f32\[{B},{n}\]\S+ s32\[4,{B}\]\S+ tuple\(", text)
    # the scopes the cell's shares read are on every loop's ops
    for w in widths:
        body = [ln for ln in text.splitlines()
                if "/while/body/" in ln and f"[{w}" in ln]
        for scope in ("/batch.spmv/", "/batch.precond/", "/bucket.dots/"):
            assert any(scope in ln for ln in body), (w, scope)
    # the program gathers and scatters between the stages and nowhere else
    moved = [ln for ln in text.splitlines()
             if re.search(r" (gather|scatter)\(", ln)]
    assert moved and all("/batch.compact/" in ln for ln in moved)
    assert "tpu_custom_call" not in text
