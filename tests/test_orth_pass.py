"""The orthogonalisation's kernel (PR 54): ``kernels/orth_pass.py``
``orth_update_project`` is the second and third of ``linalg._orth_against``'s
four contractions, ``w1 = w - hcol Vs`` and ``h2 = Vs^H w1``, over one read
of the stage's rows, and ``_orth_against`` runs them through it in a program
built with the stages' blocks, which ``linalg._orth_blocks`` gives where the
program is built: a TPU, float32, blocks that fit, everything on one device.
Here, on the CPU, the kernel runs interpreted and the platform's half of the
rule is a monkeypatch; with the rule left alone every program is the four
``jnp`` lines.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sparse_tpu
from sparse_tpu import linalg, telemetry
from sparse_tpu.batch import SolveSession
from sparse_tpu.config import settings
from sparse_tpu.kernels import orth_pass

from .utils.spd import operator_module

GEN = operator_module("cfd_7pt")
STEP = operator_module("cfd_step")

# every stage of restart 30 and of restart 5: (restart, hi)
STAGES = [(m, hi) for m in (30, 5) for hi in linalg._orth_stages(m)[1]]
# (R, tr): a block that divides the rows and one that leaves a tail of one
# tile; at restart 5 also blocks of more than one chunk (128 rows) whose last
# chunk is moved back, with a tail of its own
BLOCKS = [(64, 32), (72, 32)]
CASES = [(m, hi, R, tr, lead) for m, hi in STAGES for R, tr in BLOCKS
         for lead in ((), (3,)) if not lead or R % tr]
CASES += [(5, hi, 424, 152, lead) for hi in (4, 6) for lead in ((), (3,))]


def _operands(lead, rows, R, hi, k, seed=0):
    rng = np.random.default_rng(seed)
    V = jnp.asarray(rng.standard_normal((*lead, rows, R, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((*lead, R, 128)), jnp.float32)
    hcol = jnp.asarray(rng.standard_normal((*lead, hi)), jnp.float32)
    return V, w, hcol * (jnp.arange(hi) <= k)  # masked past k, as a step's


@pytest.fixture
def kernel_on(monkeypatch):
    """The platform's half of the rule says yes."""
    monkeypatch.setattr(linalg, "_orth_platform", lambda: True)


# -- the kernel against the two jnp lines ----------------------------------------
@pytest.mark.parametrize(
    "restart,hi,R,tr,lead", CASES,
    ids=[f"m{m}-hi{hi}-R{r}-tr{t}-{len(ld) and ld[0]}-lanes"
         for m, hi, r, t, ld in CASES])
def test_the_kernel_is_the_two_contractions(restart, hi, R, tr, lead):
    V, w, hcol = _operands(lead, restart + 1, R, hi, k=hi - 2, seed=hi + R)
    w1, h2 = orth_pass.orth_update_project(V, w, hcol, hi=hi, tr=tr,
                                           interpret=True)
    Vs = V[..., :hi, :, :]
    w1_ref = w - linalg._basis_combine(hcol, Vs)
    h2_ref = linalg._basis_project(Vs, w1_ref)
    assert w1.shape == w.shape and h2.shape == hcol.shape
    assert w1.dtype == h2.dtype == jnp.float32
    # the same products; the sums in another order (hi terms, R * 128 terms)
    np.testing.assert_allclose(w1, w1_ref, rtol=0, atol=1e-5)
    np.testing.assert_allclose(h2, h2_ref, rtol=0,
                               atol=2e-5 * float(jnp.abs(h2_ref).max()))


def test_rows_past_the_stage_and_past_the_tail_take_no_part():
    """The basis is handed over whole: rows ``hi`` and on are never read, and
    neither is anything past the last block's ``R`` rows."""
    hi, R, tr = 6, 40, 32
    V, w, hcol = _operands((), 9, R, hi, k=4)
    poisoned = V.at[hi:].set(jnp.nan)
    a = orth_pass.orth_update_project(V, w, hcol, hi=hi, tr=tr, interpret=True)
    b = orth_pass.orth_update_project(poisoned, w, hcol, hi=hi, tr=tr,
                                      interpret=True)
    for x, y in zip(a, b):
        assert np.all(np.isfinite(x)) and np.array_equal(x, y)


# -- the block's rows ------------------------------------------------------------
@pytest.mark.parametrize("hi,tr", [(4, 1248), (8, 768), (16, 432), (28, 264),
                                   (31, 240)])
def test_the_block_follows_from_the_budget_at_the_cells_rows(hi, tr):
    R = 9928  # 8 * ceil(1,270,432 / 1024): both GMRES cells
    assert orth_pass.block_rows(hi, R) == tr
    held = (2 * hi + 4) * tr * 128 * 4 + 2 * hi * 8 * 128 * 4
    assert held <= orth_pass.ORTH_VMEM_BYTES < 16 << 20
    # whole tiles; no block of fewer steps would fit; the steps evened out
    steps = -(-R // tr)
    assert tr % 8 == 0 and (steps - 1) * tr < R <= steps * tr
    fewer = 8 * -(-R // (8 * (steps - 1)))
    assert (2 * hi + 4) * fewer * 512 + 2 * hi * 4096 > orth_pass.ORTH_VMEM_BYTES
    assert tr - 8 < R / steps <= tr


@pytest.mark.parametrize("hi,R,budget,tr", [
    (4, 8, None, 8),  # a basis row of one tile
    (31, 24, None, 24),  # the whole of R, no chunk of 32 in it
    (6, 264, None, 264),  # one step
    (31, 9928, 600 * 1024, 8),  # tiles where no chunk fits
    (31, 9928, 300 * 1024, None),  # nothing fits: the jnp lines
])
def test_the_block_at_the_edges_of_the_rule(hi, R, budget, tr):
    got = (orth_pass.block_rows(hi, R) if budget is None
           else orth_pass.block_rows(hi, R, budget))
    assert got == tr


# -- the rule ---------------------------------------------------------------------
@pytest.mark.parametrize("dtype,platform,passes", [
    (np.float32, True, 3), (np.float32, False, 4), (np.float64, True, 4),
    (np.complex64, True, 4)], ids=["f32-tpu", "f32-cpu", "f64", "c64"])
def test_the_rule_reads_dtype_and_platform(monkeypatch, dtype, platform, passes):
    monkeypatch.setattr(linalg, "_orth_platform", lambda: platform)
    blocks = linalg._orth_blocks(30, np.dtype(dtype), 5000)
    assert linalg._orth_passes(blocks) == passes
    # a block a stage, or none at all
    assert blocks == ((40,) * 8 if passes == 3 else None)


def test_off_a_tpu_the_rule_declines():
    assert not linalg._orth_platform()
    assert linalg._orth_blocks(30, np.dtype(np.float32), 5000) is None


def test_a_stage_without_a_block_declines_every_stage(monkeypatch):
    """One count for the program: where the last stage's rows fit no block
    the early stages keep their contractions too."""
    monkeypatch.setattr(linalg, "_orth_platform", lambda: True)
    f32, n = np.dtype(np.float32), 1_270_432
    assert len(linalg._orth_blocks(30, f32, n)) == 8
    real = orth_pass.block_rows
    monkeypatch.setattr(orth_pass, "block_rows",
                        lambda hi, R: real(hi, R, 300 * 1024))
    assert orth_pass.block_rows(4, 9928) and not orth_pass.block_rows(31, 9928)
    assert linalg._orth_blocks(30, f32, n) is None


@pytest.mark.parametrize("where", ["b", "operand"])
def test_operands_on_more_than_one_device_decline(monkeypatch, where):
    """GSPMD partitions a program over arrays that live on a mesh, and a
    Mosaic kernel it cannot: the rule reads where the program's arguments
    live, any leaf of them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(linalg, "_orth_platform", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
    spread = jax.device_put(jnp.zeros(4096, jnp.float32),
                            NamedSharding(mesh, P("shards")))
    here = jnp.zeros(4096, jnp.float32)
    f32 = np.dtype(np.float32)
    assert linalg._orth_blocks(30, f32, 4096, (here, {"a": here}, 3, None))
    placed = (spread, here) if where == "b" else (here, ({"planes": spread},))
    assert linalg._orth_blocks(30, f32, 4096, placed) is None


@pytest.mark.parametrize("x64", [False, True], ids=["x64-off", "x64-on"])
def test_the_platform_is_a_tpu_with_x64_off(monkeypatch, x64):
    """With x64 on the chip's compiler refuses the kernel (an int64 loop
    index beside int32 offsets: read off a compile for a described v5e)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(x64):
        assert linalg._orth_platform() == (not x64)


def _orth_jaxpr(dtype, lead=()):
    """The second stage's step of a restart 30 over 2048 rows, built as a
    program's builder builds it: by the rule."""
    V = jnp.zeros((*lead, 31, 16, 128), dtype)
    w = jnp.zeros((*lead, 16, 128), dtype)
    stage = linalg._orth_stage_steps(
        30, linalg._orth_blocks(30, np.dtype(dtype), 2048))[1]
    assert stage.keywords["hi"] == 8
    return str(jax.make_jaxpr(lambda V, w: stage(V, w, 5))(V, w))


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.complex64])
def test_declined_the_step_is_the_four_contractions(monkeypatch, dtype):
    """float64 and complex decline on a TPU too; float32 declines off one.
    The jaxpr then holds no kernel and is the one the rule's other half
    leaves."""
    off = _orth_jaxpr(dtype)
    assert "pallas_call" not in off and off.count("reduce_sum") == 5
    monkeypatch.setattr(linalg, "_orth_platform", lambda: True)
    on = _orth_jaxpr(dtype)
    if dtype == np.float32:
        assert on.count("pallas_call") == 1
    else:
        assert on == off


# -- _orth_against with the kernel and without -------------------------------------
STEPS = [(m, hi, lead) for m, hi in STAGES for lead in ((), (3,))
         if not lead or hi in (4, 6, 16, 31)]


@pytest.mark.parametrize(
    "restart,hi,lead", STEPS,
    ids=[f"m{m}-hi{hi}-{len(ld) and ld[0]}-lanes" for m, hi, ld in STEPS])
def test_the_step_with_the_kernel_is_the_step_without(restart, hi, lead):
    k = hi - 2
    V, w, _ = _operands(lead, restart + 1, 24, hi, k, seed=hi)
    # an orthonormal basis' scale: rows of norm about one
    V = V / jnp.sqrt(jnp.float32(24 * 128))
    V = V * (jnp.arange(restart + 1) <= k)[:, None, None]  # rows past k zero
    step = lambda tr: linalg._orth_against(  # noqa: E731
        V, w, k, hi=hi, restart=restart, tr=tr)
    h0, w0, ww0 = step(None)
    h1, w1, ww1 = step(orth_pass.block_rows(hi, 24))
    assert h1.shape == (*lead, restart + 1) and w1.shape == w.shape
    scale = float(jnp.abs(h0).max())
    # (sums of 3072 products of random data, in another order)
    np.testing.assert_allclose(h1, h0, rtol=0, atol=5e-6 * scale)
    np.testing.assert_allclose(w1, w0, rtol=0, atol=1e-5)
    np.testing.assert_allclose(ww1, ww0, rtol=1e-5)
    assert np.all(np.asarray(h1)[..., k + 1:] == 0)


# -- whole solves -------------------------------------------------------------------
@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield
    telemetry.configure(None)
    telemetry.reset()


def _box(box, seed=3):
    d = GEN.make({"box": list(box), "restart": 30, "cycles": 1}, seed)
    n = d["rows"]
    return (lambda: sparse_tpu.csr_array(
        (d["data"], d["indices"], d["indptr"]), shape=(n, n))), jnp.asarray(d["b"])


def _as_closure(A):
    op = linalg.make_linear_operator(A)
    return linalg.LinearOperator(op.shape, matvec=op.matvec, dtype=op.dtype)


@pytest.mark.parametrize("path", ["program", "cycle-path"])
@pytest.mark.parametrize("restart,kw", [
    (30, {"maxiter": 2, "tol": 1e-30}),  # whole cycles, every stage
    (5, {"maxiter": 40, "tol": 1e-5}),  # converges inside a cycle
], ids=["m30-whole", "m5-converges"])
def test_a_library_solve_with_the_kernel_is_the_solve_without(
        kernel_on, monkeypatch, tel, restart, kw, path):
    """The same steps and the same answer to the tolerance the GMRES tests
    hold two programs of one cycle to (``tests/test_gmres_program.py``:
    1e-6 of the answer), and the span says which program ran."""
    make, b = _box((12, 7, 5))
    wrap = (lambda A: A) if path == "program" else _as_closure
    x1, it1 = linalg.gmres(wrap(make()), b, restart=restart, **kw)
    monkeypatch.setattr(linalg, "_orth_platform", lambda: False)
    x0, it0 = linalg.gmres(wrap(make()), b, restart=restart, **kw)
    assert it1 == it0 and (restart == 30) == (it0 == 60)
    assert float(jnp.linalg.norm(x1 - x0)) <= 1e-6 * float(jnp.linalg.norm(x0))
    on, off = [e for e in telemetry.events("span") if e["name"] == "gmres.solve"]
    assert on["path"] == off["path"] == ("device" if path == "program" else "cycle")
    assert (on["orth_passes"], off["orth_passes"]) == (3, 4)
    assert on["orth_rows"] == off["orth_rows"]
    assert telemetry.schema.validate(on) == []


@pytest.mark.parametrize("path", ["program", "cycle-path"])
def test_a_library_solve_over_a_mesh_keeps_the_four_contractions(
        kernel_on, tel, path):
    """``b`` sharded over four devices: GSPMD partitions the solve's program,
    so it is built without the kernel, and the span says so."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    make, b = _box((12, 7, 5))
    mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
    spread = jax.device_put(b, NamedSharding(mesh, P("shards")))
    wrap = (lambda A: A) if path == "program" else _as_closure
    x1, it1 = linalg.gmres(wrap(make()), b, restart=5, maxiter=40, tol=1e-5)
    x4, it4 = linalg.gmres(wrap(make()), spread, restart=5, maxiter=40, tol=1e-5)
    assert it4 == it1
    assert float(jnp.linalg.norm(x4 - x1)) <= 1e-6 * float(jnp.linalg.norm(x1))
    one, four = [e for e in telemetry.events("span") if e["name"] == "gmres.solve"]
    assert (one["orth_passes"], four["orth_passes"]) == (3, 4)
    assert len(x4.sharding.device_set) == 4


@pytest.mark.parametrize("members", [1, 3])
def test_a_session_solve_with_the_kernel_is_the_solve_without(
        monkeypatch, tel, members):
    """``SolveSession("gmres")``: the lanes' steps and answers, and the
    bucket's ``batch.dispatch`` says which program ran."""
    d = STEP.make({"box": (9, 7, 6), "clients": members, "shift": 0.125,
                   "restart": 12, "rel_tol": 1e-5, "check_sample": members}, 7)
    rhs = [np.float32(d["carry"]) * d["initial"][k] + d["source"][k]
           for k in range(members)]

    def solve():
        ses = SolveSession("gmres", restart=12, batch_max=4, warm_start=False)
        pat = ses.pattern_of(d["pattern"])
        tickets = [ses.submit(d["values"][k], rhs[k], pattern=pat,
                              tol=1e-5 * float(np.linalg.norm(rhs[k])))
                   for k in range(members)]
        ses.flush()
        return [(np.asarray(t.result()[0]), int(t.result()[1]), t.converged)
                for t in tickets]

    off = solve()
    monkeypatch.setattr(linalg, "_orth_platform", lambda: True)
    on = solve()
    for (x1, it1, ok1), (x0, it0, ok0) in zip(on, off):
        assert ok1 and ok0 and it1 == it0 > 12  # a second cycle
        assert np.linalg.norm(x1 - x0) <= 1e-5 * np.linalg.norm(x0)
    first, second = telemetry.events("batch.dispatch")
    assert (first["orth_passes"], second["orth_passes"]) == (4, 3)
    assert first["matvec"] == second["matvec"] == "planes"
    assert telemetry.schema.validate(second) == []


def test_a_fleet_session_keeps_the_four_contractions(monkeypatch, tel):
    """``SolveSession("gmres", fleet=...)`` hands the bucket program lanes
    sharded on its mesh and GSPMD partitions it
    (``fleet.build_batch_program``): that program is built without the
    kernel wherever it runs, its dispatch says 4, and its lanes' answers are
    the one-device program's."""
    from sparse_tpu import fleet

    members = 4
    d = STEP.make({"box": (9, 7, 6), "clients": members, "shift": 0.125,
                   "restart": 12, "rel_tol": 1e-5, "check_sample": members}, 7)
    rhs = [np.float32(d["carry"]) * d["initial"][k] + d["source"][k]
           for k in range(members)]
    monkeypatch.setattr(linalg, "_orth_platform", lambda: True)

    def solve(**kw):
        ses = SolveSession("gmres", restart=12, batch_max=4, warm_start=False,
                           **kw)
        pat = ses.pattern_of(d["pattern"])
        tickets = [ses.submit(d["values"][k], rhs[k], pattern=pat,
                              tol=1e-5 * float(np.linalg.norm(rhs[k])))
                   for k in range(members)]
        ses.flush()
        return [(np.asarray(t.result()[0]), int(t.result()[1]), t.converged)
                for t in tickets]

    one = solve()
    four = solve(fleet="auto", fleet_mesh=fleet.fleet_mesh(4), fleet_min_b=2)
    for (x1, it1, ok1), (x4, it4, ok4) in zip(one, four):
        assert ok1 and ok4 and it1 == it4 > 12
        assert np.linalg.norm(x4 - x1) <= 1e-5 * np.linalg.norm(x1)
    first, second = telemetry.events("batch.dispatch")
    assert (first["strategy"], first["orth_passes"]) == ("single", 3)
    assert (second["strategy"], second["orth_passes"]) == ("batch", 4)
    assert not telemetry.events("batch.degraded")


# -- the two metrics that read the field -----------------------------------------
@pytest.mark.parametrize("metric,kind,cell,moves", [
    ("gmres_orth_passes", "span", "nonsym_gmres_1chip", "solve_s"),
    ("served_gmres_orth_passes", "batch.dispatch", "nonsym_served_gmres_closed",
     "solves_per_s")])
def test_the_metric_reads_the_field_and_nothing_on_a_tree_without_it(
        metric, kind, cell, moves):
    """A data file each over the benchmark's own reducer: the median of the
    window's events' ``orth_passes``; an event without the field (the
    parent's) leaves nothing to read."""
    import importlib.util
    import json
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert spec["params"] == {"kind": kind, "field": "orth_passes"}
    with open(os.path.join(os.path.dirname(bench), "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
    assert (entry["workloads"], entry["moves"], entry["better"],
            entry["source"], entry["layer"]) == (
        [cell], moves, "lower", "program_counter", "kernels")
    sys.path.insert(0, bench)  # the reducer imports its neighbour `stats`
    try:
        mod = importlib.util.spec_from_file_location(
            "_reducer", os.path.join(bench, "reducers", spec["reducer"] + ".py"))
        reducer = importlib.util.module_from_spec(mod)
        mod.loader.exec_module(reducer)
    finally:
        sys.path.remove(bench)
    events = [{"name": "x", "orth_passes": 3}] * 3
    assert reducer.read({"events": {kind: events}}, spec["params"]) == 3
    assert reducer.read({"events": {kind: [{"name": "x"}]}}, spec["params"]) is None
