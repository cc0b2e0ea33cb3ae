"""The banded layout's own product on the chip (PR 50): ``form_matvec("dia")``
through the windowed kernel ``kernels.dia_spmv.dia_spmv_rows`` on row-indexed
planes packed once with the layout, and the rule that takes it
(``csr_array._dia_operands``).

Off a TPU the rule says no and the product is ``dia_spmv_xla`` on the
scipy-layout planes, the parent's bits; these tests open the platform gate
(``csr._dia_platform``) and the kernel then runs interpreted. The shapes are
SuiteSparse atmosmodd's offsets (0, +-1, +-nx, +-nx*ny) on small boxes whose
rows are no multiple of 1024, nonsymmetric values.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import sparse_tpu
from sparse_tpu import csr, linalg, precond, telemetry
from sparse_tpu.config import settings
from sparse_tpu.kernels import dia_spmv as kd
from sparse_tpu.ops.dia_spmv import dia_spmv_xla
from sparse_tpu.telemetry import _metrics

TRACES = _metrics.counter("gmres.traces")
ATMOSMODD = (1_270_432, (-21904, -148, -1, 0, 1, 148, 21904))


def _box(box, seed=0):
    """(scipy CSR, offsets) of the seven-point nonsymmetric box."""
    nx, ny, nz = box
    n = nx * ny * nz
    rng = np.random.default_rng(seed)
    offs = (-nx * ny, -nx, -1, 0, 1, nx, nx * ny)
    diags = [rng.uniform(-1.0, -0.2, n - abs(o)) if o else np.full(n, 6.5)
             for o in offs]
    return sp.diags(diags, offs, format="csr").astype(np.float32), offs


def _junk_planes(S, offs, seed=1):
    """scipy-layout planes of ``S`` with junk in the slots scipy ignores
    (column j of plane k where row j - o_k is outside the matrix)."""
    n = S.shape[0]
    data = np.asarray(S.todia().data, np.float32)
    assert tuple(S.todia().offsets) == tuple(offs)
    junk = np.random.default_rng(seed).standard_normal(data.shape) * 1e6
    cols = np.arange(n)
    for k, o in enumerate(offs):
        outside = (cols - o < 0) | (cols - o >= n)
        data[k, outside] = junk[k, outside]
    return data


@pytest.fixture
def gate(monkeypatch):
    """As on a TPU with x64 off: the rule's platform test says yes, the
    kernel runs interpreted."""
    monkeypatch.setattr(csr, "_dia_platform", lambda: True)


@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield lambda name: [e for e in telemetry.events("span") if e["name"] == name]
    telemetry.configure(None)
    telemetry.reset()


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------
KERNEL_CASES = [
    # box, tile: one step; three steps with a ragged tail; the last two steps
    # both short of their right halo; a halo of two tiles
    ((13, 11, 9), 65536), ((13, 11, 9), 1024), ((14, 14, 13), 1024),
    ((20, 16, 10), 2048), ((37, 30, 5), 2048), ((60, 40, 3), 4096),
]


@pytest.mark.parametrize("box,tile", KERNEL_CASES,
                         ids=[f"{'x'.join(map(str, b))}-t{t}" for b, t in KERNEL_CASES])
def test_the_kernel_agrees_with_the_xla_form_and_with_scipy(box, tile):
    S, offs = _box(box, seed=sum(box))
    n = S.shape[0]
    assert n % 1024
    data = jnp.asarray(_junk_planes(S, offs))
    x = np.random.default_rng(2).standard_normal(n).astype(np.float32)
    plan = kd.dia_rows_plan(offs, n, csr._DIA_VMEM_BYTES, tile=tile)
    assert plan.TM % 1024 == 0 and plan.B % 1024 == 0 and plan.B <= plan.TM
    assert (plan.G - 1) * plan.TM < -(-n // 1024) * 1024 <= plan.G * plan.TM
    rows = kd.DiaRows(kd.dia_pack(data, plan), plan)
    y = np.asarray(rows.matvec(jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(y, S @ x, rtol=2e-6, atol=2e-5)
    xla = np.asarray(dia_spmv_xla(data, offs, jnp.asarray(x), (n, n)))
    np.testing.assert_allclose(y, xla, rtol=2e-6, atol=2e-5)
    # the same product on the vector held in whole tiles: the pad of the
    # result is exactly zero whatever x's pad holds, the rows the same bits
    xt = jnp.pad(jnp.asarray(x), (0, rows.n_tiles - n), constant_values=3.0)
    yt = np.asarray(kd.dia_spmv_rows(rows.planes, xt, plan, interpret=True))
    assert yt.shape == (rows.n_tiles,) and not yt[n:].any()
    np.testing.assert_array_equal(yt[:n], y)


def test_the_kernel_on_rows_that_are_whole_tiles():
    S, offs = _box((16, 16, 8))
    n = S.shape[0]
    plan = kd.dia_rows_plan(offs, n, csr._DIA_VMEM_BYTES, tile=1024)
    rows = kd.DiaRows(kd.dia_pack(jnp.asarray(S.todia().data), plan), plan)
    assert rows.n_tiles == n == 2048 and plan.G == 2
    x = np.random.default_rng(3).standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(rows.matvec(jnp.asarray(x), interpret=True)), S @ x,
        rtol=2e-6, atol=2e-5)


def test_the_plan_at_atmosmodd_size():
    n, offs = ATMOSMODD
    plan = kd.dia_rows_plan(offs, n, csr._DIA_VMEM_BYTES)
    assert (plan.TM, plan.B, plan.G) == (64512, 22528, 20)
    held = 2 * plan.D * plan.TM + 2 * (plan.TM + 2 * plan.B) + 2 * plan.TM
    assert 4 * held <= csr._DIA_VMEM_BYTES and 4 * held == 5_005_312
    # a band that leaves no room for a row tile as long as itself
    assert kd.dia_rows_plan((-200_000, 0, 200_000), 10**6, csr._DIA_VMEM_BYTES) is None
    # many diagonals: a smaller tile, still under the budget
    wide = kd.dia_rows_plan(tuple(range(-13, 14)), 300_000, csr._DIA_VMEM_BYTES)
    assert wide.TM < 65536 and 4 * (
        (2 * 27 + 4) * wide.TM + 4 * wide.B) <= csr._DIA_VMEM_BYTES


def test_the_packed_rows_are_a_pytree_whose_plan_is_static():
    S, offs = _box((13, 11, 9))
    plan = kd.dia_rows_plan(offs, S.shape[0], csr._DIA_VMEM_BYTES)
    rows = kd.DiaRows(kd.dia_pack(jnp.asarray(S.todia().data), plan), plan)
    leaves, treedef = jax.tree_util.tree_flatten(rows)
    assert len(leaves) == 1 and leaves[0] is rows.planes
    again = jax.tree_util.tree_unflatten(treedef, leaves)
    assert type(again) is kd.DiaRows and again.plan == plan
    other = kd.dia_rows_plan(offs, S.shape[0], csr._DIA_VMEM_BYTES, tile=1024)
    assert treedef != jax.tree_util.tree_structure(kd.DiaRows(rows.planes, other))


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
def _matrix(box=(13, 11, 9), dtype=np.float32, seed=0):
    S, offs = _box(box, seed)
    return sparse_tpu.csr_array(S.astype(dtype)), S.astype(dtype), offs


def test_on_the_chip_the_layout_keeps_its_name_and_gains_its_rows(gate):
    A, S, offs = _matrix()
    n = S.shape[0]
    x = np.random.default_rng(4).standard_normal(n).astype(np.float32)
    y = np.asarray(A @ x)
    kind, arrays, meta = A._spmv_form(np.float32)
    assert kind == "dia" and meta == (offs, (n, n))
    assert type(arrays) is kd.DiaRows and arrays.plan.offsets == offs
    assert csr.form_kernels(kind, arrays) == 1
    # the scipy-layout planes stay who they are for every other reader
    assert isinstance(A._dia, tuple) and A._dia[0].shape == (7, n)
    np.testing.assert_allclose(y, S @ x, rtol=2e-6, atol=2e-5)
    assert np.array_equal(
        y, np.asarray(csr.form_matvec(kind, meta, arrays, jnp.asarray(x))))


@pytest.mark.parametrize("refusal", [
    "not-a-tpu", "float64-operand", "float64-matrix", "band-past-the-budget",
    "rectangular"])
def test_the_rule_refuses_and_the_product_is_the_xla_form(refusal, monkeypatch):
    if refusal != "not-a-tpu":
        monkeypatch.setattr(csr, "_dia_platform", lambda: True)
    dtype, xdtype = np.float32, np.float32
    if refusal == "float64-operand":
        xdtype = np.float64
    if refusal == "float64-matrix":
        dtype = xdtype = np.float64
    if refusal == "band-past-the-budget":
        monkeypatch.setattr(csr, "_DIA_VMEM_BYTES", 64 * 1024)
    S, offs = _box((13, 11, 9))
    if refusal == "rectangular":
        S = S[:, :-5].tocsr()
        offs = tuple(S.todia().offsets)
    A = sparse_tpu.csr_array(S.astype(dtype))
    x = jnp.asarray(np.random.default_rng(5).standard_normal(S.shape[1]), xdtype)
    kind, arrays, meta = A._spmv_form(x.dtype)
    assert kind == "dia" and meta[0] == tuple(int(o) for o in offs)
    assert arrays is A._dia[0] and csr.form_kernels(kind, arrays) == 0
    want = dia_spmv_xla(A._dia[0], meta[0], x, meta[1])  # the parent's product
    np.testing.assert_array_equal(np.asarray(A @ x), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(csr.form_matvec(kind, meta, arrays, x)), np.asarray(want))


def test_the_platform_is_a_tpu_with_x64_off(monkeypatch):
    assert not csr._dia_platform()  # this backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.config.jax_enable_x64 and not csr._dia_platform()
    with jax.enable_x64(False):
        assert csr._dia_platform()


def test_the_rule_reads_no_setting():
    import inspect

    src = inspect.getsource(csr.csr_array._dia_operands) + inspect.getsource(
        csr._dia_platform) + inspect.getsource(kd.dia_rows_plan)
    assert "settings" not in src and "environ" not in src


def test_the_pack_is_built_once_a_matrix(gate, tel, monkeypatch):
    packs = []
    real = kd.dia_pack
    monkeypatch.setattr(kd, "dia_pack", lambda *a, **k: packs.append(1) or real(*a, **k))
    A, S, _offs = _matrix()
    n = S.shape[0]
    b = jnp.asarray(np.random.default_rng(6).uniform(0.5, 1.5, n), jnp.float32)
    A @ b
    rows = A._spmv_form(np.float32)[1]
    A @ b
    linalg.gmres(A, b, restart=10, maxiter=2, tol=1e-30)
    t0 = TRACES.value
    linalg.gmres(A, 2 * b, restart=10, maxiter=2, tol=1e-30)
    assert A._spmv_form(b.dtype)[1] is rows and packs == [1]
    assert TRACES.value == t0  # the pack is an argument: nothing traced again
    (span,) = tel("layout.dia_pack")
    assert span["fits"] is True
    # new values are a new matrix: its own pack, the program that is there
    A2 = sparse_tpu.csr_array((2 * A.data, A.indices, A.indptr), shape=A.shape)
    linalg.gmres(A2, b, restart=10, maxiter=2, tol=1e-30)
    assert packs == [1, 1] and TRACES.value == t0


def test_a_first_use_inside_a_trace_packs_nothing(gate):
    A, S, _offs = _matrix()
    n = S.shape[0]
    x = jnp.asarray(np.random.default_rng(7).standard_normal(n), jnp.float32)
    y = jax.jit(lambda v: A @ v)(x)
    assert A._dia_rows is None
    np.testing.assert_allclose(np.asarray(y), S @ np.asarray(x), rtol=2e-6, atol=2e-5)
    # once packed eagerly, a traced product multiplies through the pack
    A @ x
    assert type(A._dia_rows[1]) is kd.DiaRows
    assert "pallas_call" in str(jax.make_jaxpr(lambda v: A @ v)(x))


def test_a_matrix_packs_again_when_its_planes_are_replaced(gate):
    A, S, offs = _matrix()
    x = jnp.ones(S.shape[0], jnp.float32)
    A @ x
    first = A._dia_rows
    A._dia = (A._dia[0] + 0.0, A._dia[1])  # other arrays, the same values
    A @ x
    assert A._dia_rows[0] is A._dia[0] and A._dia_rows[1] is not first[1]


def test_pallas_mode_keeps_its_eager_route_and_compiled_solves_take_dia(
        gate, monkeypatch):
    monkeypatch.setattr(settings, "spmv_mode", "pallas")
    A, S, offs = _matrix()
    n = S.shape[0]
    assert A._spmv_form(np.float32)[0] == "dia+"
    op = linalg.make_linear_operator(A)
    kind, arrays, meta = linalg._matrix_form(op, np.float32)
    assert kind == "dia" and type(arrays) is kd.DiaRows and meta == (offs, (n, n))
    b = jnp.asarray(np.random.default_rng(8).uniform(0.5, 1.5, n), jnp.float32)
    x, iters = linalg.gmres(A, b, restart=10, maxiter=3, tol=1e-30)
    assert iters == 30
    assert np.linalg.norm(S @ np.asarray(x) - np.asarray(b)) < 1e-3 * np.linalg.norm(b)


# ---------------------------------------------------------------------------
# the solvers over it
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("vmem", [csr._DIA_VMEM_BYTES, 160 * 1024],
                         ids=["one-step", "tiles-of-2048"])
@pytest.mark.parametrize("path", ["device", "cycle", "jacobi"])
def test_gmres_through_the_kernel_is_the_xla_forms_to_rounding(
        path, vmem, tel, monkeypatch):
    monkeypatch.setattr(csr, "_DIA_VMEM_BYTES", vmem)
    box = (14, 14, 13)
    _A, S, _offs = _matrix(box)
    n = S.shape[0]
    b = jnp.asarray(np.random.default_rng(9).uniform(0.5, 1.5, n), jnp.float32)

    def solve(A):
        kw = dict(restart=30, maxiter=2, tol=1e-30)
        if path == "cycle":
            kw["callback"] = lambda _x: None
        if path == "jacobi":
            kw["M"] = precond.make_M(A, "jacobi")
        return linalg.gmres(A, b, **kw)

    want, it0 = solve(sparse_tpu.csr_array(S))
    monkeypatch.setattr(csr, "_dia_platform", lambda: True)
    A = sparse_tpu.csr_array(S)
    got, it1 = solve(A)
    rows = A._spmv_form(np.float32)[1]
    assert type(rows) is kd.DiaRows and rows.plan.G == (1 if vmem > 1 << 20 else 2)
    assert it0 == it1 == 60
    err = np.linalg.norm(np.asarray(got) - np.asarray(want)) / np.linalg.norm(want)
    assert err < 2e-5, err
    first, second = tel("gmres.solve")
    assert first["spmv_kernels"] == 0 and second["spmv_kernels"] == 1
    assert first["path"] == second["path"] == ("cycle" if path == "cycle" else "device")
    assert second["orth_rows"] == 17.0 and second["basis_write_rows"] == 1


def test_the_compiled_solve_has_the_pack_as_an_argument_and_no_plane_product(gate):
    """``jit_gmres`` over the packed rows: the kernel is the step's product,
    no ``[7, n]`` array is made, and nothing of the matrix is a constant."""
    A, S, offs = _matrix((14, 14, 13))
    n = S.shape[0]
    b = jnp.zeros(n, jnp.float32)
    args, static = linalg._declared_call(
        linalg.make_linear_operator(A),
        linalg.IdentityOperator(A.shape, dtype=A.dtype), b, b, jnp.float32(0), 1,
        restart=30)
    assert type(static["a_apply"]) is linalg._FormApply
    assert static["a_apply"].kernels(args[0]) == 1 and args[0] is A._dia_rows[1]
    jaxpr = jax.make_jaxpr(lambda *a: linalg._gmres(*a, **static))(*args)
    text = str(jaxpr)
    n_tiles = -(-n // 1024) * 1024
    assert f"f32[7,{n}]" not in text and f"f32[7,{n_tiles}]" not in text
    arnoldi = text[text.rindex("body_jaxpr"):]  # the inner `while`
    assert "name=dia_spmv_rows" in arnoldi
    assert all(np.size(c) <= 2 for c in jaxpr.consts)
    # with a preconditioner after the product: the same kernel
    M = precond.make_M(A, "jacobi")
    x, iters = linalg.gmres(A, b + 1, restart=5, maxiter=1, tol=1e-30, M=M)
    assert iters == 5 and np.isfinite(np.asarray(x)).all()


def test_cg_general_takes_the_kernel_for_a_banded_matrix(gate, tel):
    n = 24
    T = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    S = (sp.kron(sp.identity(n), T) + sp.kron(T, sp.identity(n))).tocsr()
    S = (S + 0.5 * sp.identity(n * n)).tocsr().astype(np.float32)
    A = sparse_tpu.csr_array(S)
    b = jnp.asarray(np.random.default_rng(10).uniform(0.5, 1.5, n * n), jnp.float32)
    x, _iters = linalg.cg(A, b, tol=1e-5, maxiter=200)
    assert type(A._spmv_form(np.float32)[1]) is kd.DiaRows
    assert np.linalg.norm(S @ np.asarray(x) - np.asarray(b)) < 1e-3
    (span,) = tel("cg.solve")
    assert span["path"] == "device" and span["layout"] == "dia"
