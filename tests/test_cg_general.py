"""``linalg.cg`` on a matrix that is not banded: one compiled whole-solve
program (``jit_cg_general``) with the layout's arrays as arguments.

The systems are the benchmark's unstructured SPD class at a few thousand
rows (``benchmark/operators/spd_unstructured.py``, through
``tests/utils/spd.py``), whose plain reference the program is compared with.
The three gather layouts of ``csr._LAYOUTS``: padded rows (ELL) for the
class's tight row profile, SELL slabs once a skewed row is added, the
segment form under ``spmv_mode='segment'``.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sparse_tpu
from sparse_tpu import linalg, plan_cache
from sparse_tpu.config import settings
from sparse_tpu.telemetry import _metrics

from .utils.spd import as_scipy, operator_module, spd_data

# layout -> (spmv_mode, further edges of vertex 0 for each row of the grid)
LAYOUTS = {"ell": ("auto", 0), "sell": ("auto", 5), "segment": ("segment", 0)}
TRACES = _metrics.counter("cg.general.traces")


@pytest.fixture(autouse=True)
def _fresh_general_program():
    """These tests count traces of ``jit_cg_general``; a test of another
    file that solved a system of the same shapes earlier in this process
    (``tests/test_spans.py`` does) would leave them none to count."""
    linalg._cg_general_program.clear_cache()


def _system(layout, side, seed, dtype=np.float32, monkeypatch=None, skew=None):
    mode, per_row = LAYOUTS[layout]
    if monkeypatch is not None:
        monkeypatch.setattr(settings, "spmv_mode", mode)
    data = spd_data(side, seed, skew=per_row * side if skew is None else skew)
    n = data["rows"]
    A = sparse_tpu.csr_array(
        (data["data"].astype(dtype), data["indices"], data["indptr"]),
        shape=(n, n))
    return A, data["b"].astype(dtype), data


def _layout_of(A):
    if A._dia:
        return "dia"
    if plan_cache.lookup(A, "sell") is not None:
        return "sell"
    return "ell" if A._ell is not None else "segment"


def _sha(x):
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


class _Compiles:
    """Programs compiled while the block runs (persistent-cache hits too:
    a hit is still a program this process had not built)."""

    def __enter__(self):
        from jax import monitoring

        self.n = 0
        self._on = True

        def on_event(name, **_):
            if self._on and name == "/jax/compilation_cache/compile_requests_use_cache":
                self.n += 1

        monitoring.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        self._on = False
        return False


# ---------------------------------------------------------------------------
# against the plain reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [21, 22, 23])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_cg_agrees_with_the_plain_reference(layout, seed, monkeypatch):
    A, b, data = _system(layout, 48, seed, monkeypatch=monkeypatch)
    its = data["iterations"]
    x, iters = linalg.cg(A, jnp.asarray(b), maxiter=its)
    assert _layout_of(A) == layout and iters == its
    ref = operator_module()
    x_ref = ref.reference_cg(data, its)
    rr_ref = ref.true_relres(data, x_ref)
    nums = ref.compare(np.asarray(x), x_ref, rr_ref, data)
    # rounding differences, amplified by 50 iterations on a system of
    # condition ~ side^2: read 2e-7 to 3e-5, and 4e-5 to 3e-2 for the gap of
    # the residuals, which at an unconverged iterate swing with the last bits
    assert nums["x_vs_reference"] < 5e-4 and nums["relres_gap"] < 0.5, nums
    # the control, the reference in bfloat16, is far from both (read: at
    # least 0.017 and 250)
    (ctl,) = ref.control_answers(data, [])
    nums = ref.compare(ctl["x"], x_ref, rr_ref, data)
    assert nums["x_vs_reference"] > 5e-3 and nums["relres_gap"] > 10, nums


# ---------------------------------------------------------------------------
# the parent's iterates, bit for bit
# ---------------------------------------------------------------------------
# What the parent tree (1a52d7d: an eager lax.while_loop over closures that
# held the matrix as constants) gave on this sandbox's CPU: iterations and
# sha256(x)[:16] of linalg.cg. name: (layout, side, seed, dtype, tol, maxiter,
# x0 given, iterations, sha); the SELL cases' vertex 0 has 300 and 120 further
# edges.
_PARENT = {
    "ell-maxiter": ("ell", 40, 11, "float32", 1e-8, 50, False, 50, "6f211eb0665a5bc0"),
    "ell-tol": ("ell", 24, 12, "float32", 1e-4, 400, False, 75, "964ffde7dc884636"),
    "ell-x0": ("ell", 40, 13, "float32", 1e-8, 60, True, 60, "47ea1a4bac528954"),
    "ell-f64": ("ell", 32, 14, "float64", 1e-8, 50, False, 50, "4c379d5a3d5f7a0f"),
    "sell-maxiter": ("sell", 40, 15, "float32", 1e-8, 50, False, 50, "439680dc943834b2"),
    "sell-tol": ("sell", 24, 16, "float32", 1e-4, 400, False, 75, "1770905f4ef63a8c"),
    "segment-maxiter": ("segment", 40, 17, "float32", 1e-8, 50, False, 50, "3e858d88b87a29e2"),
    "segment-tol": ("segment", 24, 18, "float32", 1e-4, 400, False, 75, "e0993feaa9d846a3"),
}


@pytest.mark.parametrize("case", sorted(_PARENT))
def test_compiled_solve_gives_the_closure_loops_and_the_parents_bits(
        case, monkeypatch):
    """The closure loop is still in the tree (a preconditioned solve, an
    operator that is no matrix): on the same system it gives the same bits,
    stops at the same test, and both are what the parent recorded."""
    layout, side, seed, dtype, tol, maxiter, x0, iters_p, sha_p = _PARENT[case]
    A, b, _ = _system(layout, side, seed, np.dtype(dtype), monkeypatch,
                      skew={"sell-maxiter": 300, "sell-tol": 120}.get(case, 0))
    x0 = np.random.default_rng(seed).random(b.shape[0]).astype(dtype) if x0 else None
    t0 = TRACES.value
    x, iters = linalg.cg(A, b, x0=x0, tol=tol, maxiter=maxiter)
    assert TRACES.value == t0 + 1 and _layout_of(A) == layout
    op = linalg.LinearOperator(A.shape, matvec=A.dot, dtype=A.dtype)
    x_loop, iters_loop = linalg.cg(op, b, x0=x0, tol=tol, maxiter=maxiter)
    assert TRACES.value == t0 + 1  # no matrix: the closure loop
    assert iters == iters_loop == iters_p
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_loop))
    if _sha(x_loop) != sha_p:
        pytest.skip("this CPU is not the one the parent's bits were read on")
    assert _sha(x) == sha_p


# ---------------------------------------------------------------------------
# one program a pattern
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_second_call_and_new_values_trace_and_compile_nothing(
        layout, monkeypatch):
    A, b, _ = _system(layout, 36, 31, monkeypatch=monkeypatch)
    b = jnp.asarray(b)
    t0 = TRACES.value
    x1, it1 = linalg.cg(A, b, maxiter=40)
    assert TRACES.value == t0 + 1 and it1 == 40
    # same pattern, new values: another operator, which builds a layout of
    # its own (outside the block: the build compiles its own eager ops)
    A2 = sparse_tpu.csr_array((A.data * 2.0, A.indices, A.indptr), shape=A.shape)
    A2 @ b
    with _Compiles() as c:
        x2, it2 = linalg.cg(A, b, maxiter=40)
        # tolerance, iteration limit and right-hand side are arguments
        x3, it3 = linalg.cg(A, 2.0 * b, tol=1e-3, maxiter=300)
        x4, it4 = linalg.cg(A2, 2.0 * b, maxiter=40)
    assert TRACES.value == t0 + 1 and c.n <= 1  # 2.0 * b: one multiply
    assert it2 == it4 == 40 and it3 < 300 and it3 % 25 == 0
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x4))
    r3 = np.asarray(A @ x3) - 2.0 * np.asarray(b)
    assert np.linalg.norm(r3) < 1e-2  # float32: the true residual drifts
    # another test cadence is another program
    linalg.cg(A, b, maxiter=40, conv_test_iters=10)
    assert TRACES.value == t0 + 2


def test_nothing_of_the_matrix_is_a_constant_of_the_program(monkeypatch):
    A, b, _ = _system("ell", 36, 32, monkeypatch=monkeypatch)
    kind, arrays, meta = A._spmv_form()
    assert kind == "ell"
    lowered = linalg._cg_general_program.lower(
        arrays, jnp.asarray(b), None, 1e-8, 40,
        kind=kind, meta=meta, conv_test_iters=25, tapped=False)
    text = lowered.as_text()
    assert "cg_general" in text
    # the largest constant of the program is a scalar
    import re

    for shape in re.findall(r"stablehlo.constant dense<[^>]*> : tensor<([^>]*)>", text):
        dims = [int(d) for d in shape.split("x")[:-1]]
        assert int(np.prod(dims, dtype=np.int64)) <= 1, shape


# ---------------------------------------------------------------------------
# who keeps the old paths
# ---------------------------------------------------------------------------
def test_M_callback_and_a_non_matrix_operator_take_their_old_paths(
        monkeypatch, tmp_path):
    from sparse_tpu import telemetry

    A, b, _ = _system("ell", 24, 33, monkeypatch=monkeypatch)
    S = as_scipy(spd_data(24, 33)).astype(np.float64)
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    try:
        t0 = TRACES.value
        M = linalg.LinearOperator(
            A.shape, matvec=lambda v: v / jnp.asarray(S.diagonal(), v.dtype),
            dtype=A.dtype)
        xs = [linalg.cg(A, b, tol=1e-5, maxiter=600, M=M)[0],
              linalg.cg(A, b, tol=1e-5, maxiter=600, callback=lambda x: None)[0],
              linalg.cg(linalg.LinearOperator(A.shape, matvec=A.dot, dtype=A.dtype),
                        b, tol=1e-5, maxiter=600)[0]]
        assert TRACES.value == t0
        paths = [e["path"] for e in telemetry.events("solver.solve")]
        assert paths == ["device", "host", "device"]
        assert not [e for e in telemetry.events("span") if e["name"] == "cg.solve"]
        linalg.cg(A, b, tol=1e-5, maxiter=600)
        assert TRACES.value == t0 + 1
        (ev,) = [e for e in telemetry.events("span") if e["name"] == "cg.solve"]
        assert ev["path"] == "device" and ev["layout"] == "ell"
    finally:
        telemetry.configure(None)
        telemetry.reset()
    x_ref = np.linalg.solve(S.toarray(), b.astype(np.float64))
    for x in xs:
        assert np.linalg.norm(np.asarray(x) - x_ref) / np.linalg.norm(x_ref) < 1e-3


def test_a_banded_matrix_off_the_fused_path_runs_the_program_on_its_planes():
    """float64, or any backend but a TPU: ``_try_fused_cg`` declines and the
    general program multiplies by the DIA planes (the XLA form)."""
    import scipy.sparse as sp

    # a 19 x 21 grid: the program is traced once a shape and a process, and
    # under the driver's workers another file's 20 x 20 grid had traced this
    # test's before it (the counter then stands still)
    m, n = 19, 21

    def lap(k):
        return sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(k, k))

    S = (sp.kron(sp.identity(m), lap(n)) + sp.kron(lap(m), sp.identity(n))).tocsr()
    A = sparse_tpu.csr_array(S)
    b = np.random.default_rng(0).random(m * n)
    t0 = TRACES.value
    x, iters = linalg.cg(A, b, tol=1e-10)
    assert TRACES.value == t0 + 1 and _layout_of(A) == "dia"
    assert np.linalg.norm(S @ np.asarray(x) - b) < 1e-8
    x_loop, iters_loop = linalg.cg(
        linalg.LinearOperator(A.shape, matvec=A.dot, dtype=A.dtype), b, tol=1e-10)
    assert iters == iters_loop
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_loop))


def test_the_banded_rule_turns_a_general_matrix_away_by_a_sample(monkeypatch):
    """More diagonals in a strided sample than a banded matrix has in all:
    the whole matrix's diagonals are not counted (no second fetch)."""
    from sparse_tpu.csr import csr_array

    A, _b, _ = _system("ell", 40, 34)
    fetched = []
    real = csr_array._fetch_offsets

    def spy(offs):
        fetched.append(int(offs.shape[0]))
        return real(offs)

    monkeypatch.setattr(csr_array, "_fetch_offsets", staticmethod(spy))
    assert A._maybe_dia() is None
    assert len(fetched) == 1 and fetched[0] <= 2 * 8192
