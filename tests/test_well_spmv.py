"""The windowed step-major layout (``well``): the host's reordering and
build, the Pallas kernel in interpret mode, the rule that offers it
(``csr_array._maybe_well``), and ``linalg.cg`` through it.

The kernel's platform is a TPU; where the whole path is driven here the
platform gate (``csr._well_platform``) and the row floor are monkeypatched,
and the kernel then runs interpreted. The systems are the benchmark's
unstructured SPD class (a triangulated grid under a random permutation).
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

import sparse_tpu
from sparse_tpu import csr, linalg, telemetry
from sparse_tpu.csgraph import band_order
from sparse_tpu.config import settings
from sparse_tpu.kernels import well_spmv as ws
from sparse_tpu.ops import spmv as spmv_ops
from sparse_tpu.telemetry import _metrics

from .utils.spd import as_scipy, spd_data

TRACES = _metrics.counter("cg.general.traces")


def _layout(S, order=None):
    """The layout of scipy CSR ``S`` built directly (no rule), as
    ``csr_array._well_build`` builds it."""
    S = S.tocsr()
    n = S.shape[0]
    if order is None:
        order = band_order(S.indptr, S.indices, n)
    n_pad = ws.padded_size(n)
    new_ptr, rows, cols, data, rank = ws.permuted_csr(
        S.indptr, S.indices, S.data, order)
    uptr, ustart, unit, stats = ws.windows(new_ptr, rows, cols, n, n_pad)
    lane, val = ws.step_units(uptr, rows, cols, data, unit)
    return dict(uptr=uptr.astype(np.int32), ustart=ustart.astype(np.int32),
                lane=lane, val=val, perm=order, inv_perm=rank + ws.LEAD, n=n,
                n_pad=n_pad, stats=stats)


def _product(lay, x, **other):
    """The kernel's product in the caller's order (``other``: arrays that
    take the layout's place)."""
    lay = {**lay, **other}
    xp = np.zeros(lay["n_pad"], np.float32)
    xp[ws.LEAD: ws.LEAD + lay["n"]] = x[lay["perm"]]
    y = ws.well_spmv(lay["uptr"], lay["ustart"], lay["lane"], lay["val"],
                     jnp.asarray(xp.reshape(-1, 128)), interpret=True)
    y = np.asarray(y).reshape(-1)
    # the lead and the tail multiply to zero
    assert not y[: ws.LEAD].any() and not y[ws.LEAD + lay["n"]:].any()
    return y[lay["inv_perm"]]


def _uneven(seed):
    """Rows of 0 to 12 entries, symmetric pattern, one row empty."""
    rng = np.random.default_rng(seed)
    n = 2500
    R = sp.random(n, n, density=3.0 / n, random_state=rng, dtype=np.float32)
    band = sp.diags([rng.random(n - 1), rng.random(n - 40)], [1, 40])
    S = (R + R.T + band + band.T).tolil()
    S[7, :] = 0
    S[:, 7] = 0
    S = S.tocsr().astype(np.float32)
    S.eliminate_zeros()
    assert np.diff(S.indptr)[7] == 0 and np.diff(S.indptr).max() > 8
    return S


def _long_rows():
    """Rows longer than twelve entries (the plane-major kernel gathered
    twelve planes a pass; a unit list has no such seam)."""
    rng = np.random.default_rng(3)
    n = 1500
    R = sp.random(n, n, density=10.0 / n, random_state=rng, dtype=np.float32)
    S = (R + R.T).tocsr()
    assert np.diff(S.indptr).max() > 12
    return S


SYSTEMS = {
    # 3600 rows: three and a half tiles of 1024, padded to one grid step
    "grid-60": lambda: as_scipy(spd_data(60, 5)),
    # 10000 rows: nine tiles and three quarters
    "grid-100": lambda: as_scipy(spd_data(100, 6)),
    "uneven": lambda: _uneven(7),
}
PRODUCTS = {**SYSTEMS, "rows-longer-than-twelve-entries": _long_rows}


def _rel(y, S, x):
    ref = S.astype(np.float64) @ x.astype(np.float64)
    return np.abs(y - ref).max() / np.abs(ref).max()


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("order", ["reordered", "given"])
@pytest.mark.parametrize("system", sorted(PRODUCTS))
def test_kernel_product_against_scipy_in_float64(system, order):
    """The layout is sound under any ordering (the identity: the cell's
    random order, every window all of x); the ordering only shortens it."""
    S = PRODUCTS[system]()
    n = S.shape[0]
    lay = _layout(S, order=np.arange(n) if order == "given" else None)
    assert lay["n_pad"] % ws.GRID_ROWS == 0 and n % ws.TILE
    if order == "given" and system != "uneven":  # (its band is in the order given)
        assert lay["stats"]["window_chunks_max"] >= -(-n // 128) - 8
    x = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    assert _rel(_product(lay, x), S, x) < 1e-6


@pytest.mark.parametrize("system", sorted(SYSTEMS))
def test_kernel_product_against_the_ell_product(system):
    S = SYSTEMS[system]()
    x = np.random.default_rng(1).standard_normal(S.shape[0]).astype(np.float32)
    y = _product(_layout(S), x)
    ell = sparse_tpu.csr_array(S)._maybe_ell()
    y_ell = np.asarray(spmv_ops.csr_spmv_ell(*ell, jnp.asarray(x)))
    assert np.abs(y - y_ell).max() / np.abs(y_ell).max() < 1e-6


# ---------------------------------------------------------------------------
# the host builder, on hand-made patterns
# ---------------------------------------------------------------------------
def _by_hand(n, entries):
    """A float32 CSR matrix of ``entries`` = {(row, col): value}."""
    r, c = (np.array(a) for a in zip(*entries))
    return sp.csr_matrix((np.array(list(entries.values()), np.float32), (r, c)),
                         shape=(n, n))


def _fullest_rows(S):
    """{(tile, chunk a sublane-0 row would read): the most entries one row
    of the tile has there}, counted entry by entry."""
    per_row = {}
    coo = S.tocoo()
    for r, c in zip(coo.row.tolist(), coo.col.tolist()):
        at = r + ws.LEAD
        key = (at // ws.TILE, (c + ws.LEAD) // 128 - (at // 128) % 8, r)
        per_row[key] = per_row.get(key, 0) + 1
    fullest = {}
    for (tile, start, _r), count in per_row.items():
        fullest[tile, start] = max(fullest.get((tile, start), 0), count)
    return fullest


HAND_MADE = {
    # row 5: five entries in the chunk of columns 0 to 127 (depths 0 to 4),
    # one in the next chunk; row 6 two and one; row 1500 (another tile) alone
    "five-in-one-chunk": lambda: _by_hand(2100, {
        **{(5, c): 1.0 + c for c in (0, 3, 64, 100, 127)}, (5, 128): -2.0,
        (6, 1): 0.5, (6, 2): 0.25, (6, 200): 4.0, (1500, 1400): 3.0}),
    # rows 1100 to 2999 hold nothing: the tiles between are without rows
    "tiles-without-rows": lambda: _by_hand(3000, {
        (0, 0): 1.0, (3, 2999): 2.0, (1099, 5): 3.0, (2999, 3): 4.0}),
    "one-entry": lambda: _by_hand(1000, {(999, 0): 7.0}),
}


@pytest.mark.parametrize("case", sorted(HAND_MADE) + sorted(SYSTEMS))
def test_builder_counts_a_unit_for_every_entry_of_a_steps_fullest_row(case):
    S = {**HAND_MADE, **SYSTEMS}[case]()
    lay = _layout(S, order=np.arange(S.shape[0]))
    fullest = _fullest_rows(S)
    uptr, ustart = lay["uptr"], lay["ustart"]
    assert lay["stats"]["steps"] == len(fullest)
    assert lay["stats"]["units"] == sum(fullest.values()) == ustart.shape[0]
    for t in range(uptr.shape[0] - 1):
        # a tile's units: step after step in the chunks' order, a step as
        # many as its fullest row has entries; a tile without rows has none
        mine = sorted(s for (tile, s) in fullest if tile == t)
        units = [s for s in mine for _ in range(fullest[t, s])]
        assert ustart[uptr[t]:uptr[t + 1]].tolist() == units
    # every entry stored once, the other slots value 0 and lane 0
    assert np.count_nonzero(lay["val"]) == S.nnz
    assert not lay["lane"][lay["val"] == 0].any()
    x = np.random.default_rng(8).standard_normal(S.shape[0]).astype(np.float32)
    assert _rel(_product(lay, x), S, x) < 1e-6


def test_builder_gives_a_row_of_five_in_one_chunk_five_depths():
    S = HAND_MADE["five-in-one-chunk"]()
    lay = _layout(S, order=np.arange(S.shape[0]))
    # tile 1 holds rows 0 to 1023 (after the lead): a step of five units
    # (row 5's five; row 6's two lie in the first two), then one of one
    assert lay["ustart"].tolist() == [8] * 5 + [9, 15] and lay["uptr"][:4].tolist() == [0, 0, 6, 7]
    first = lay["uptr"][1]
    at_5 = (0, 5)  # sublane 0, lane 5 of the tile's vreg
    assert lay["lane"][first: first + 5, 0, 5].tolist() == [0, 3, 64, 100, 127]
    assert lay["val"][first: first + 5][(..., *at_5)].tolist() == [1.0, 4.0, 65.0, 101.0, 128.0]
    assert lay["lane"][first: first + 5, 0, 6].tolist() == [1, 2, 0, 0, 0]
    assert lay["val"][first: first + 5, 0, 6].tolist() == [0.5, 0.25, 0.0, 0.0, 0.0]
    x = np.arange(1.0, S.shape[0] + 1, dtype=np.float32)
    y = _product(lay, x)
    assert y[5] == 1 * 1 + 4 * 4 + 65 * 65 + 101 * 101 + 128 * 128 - 2 * 129
    assert y[6] == 0.5 * 2 + 0.25 * 3 + 4 * 201 and y[1500] == 3 * 1401


def test_padding_units_add_zero_and_are_never_read():
    """A grid step's block is as long as the fullest grid step's: its rest
    is zeros, and the kernel walks a tile's own units alone."""
    S = as_scipy(spd_data(130, 8))  # 16,900 rows: two grid steps
    lay = _layout(S)
    steps = lay["n_pad"] // ws.GRID_ROWS
    block = lay["val"].shape[0] // steps
    own = np.diff(lay["uptr"][:: ws.STEP_TILES])
    assert steps == 2 and own.max() == block and own.min() < block
    assert lay["stats"]["units_stored"] == steps * block > lay["stats"]["units"]
    rest = np.ones(lay["val"].shape[0], dtype=bool)
    for g in range(steps):
        rest[g * block: g * block + own[g]] = False
    assert rest.any() and not lay["val"][rest].any() and not lay["lane"][rest].any()
    x = np.random.default_rng(9).standard_normal(S.shape[0]).astype(np.float32)
    y = _product(lay, x)
    val = lay["val"].copy()
    val[rest] = np.nan
    np.testing.assert_array_equal(y, _product(lay, x, val=val))


# ---------------------------------------------------------------------------
# the ordering
# ---------------------------------------------------------------------------
def _bandwidth(S, order):
    rank = np.empty(S.shape[0], dtype=np.int64)
    rank[order] = np.arange(S.shape[0])
    coo = S.tocoo()
    return int(np.abs(rank[coo.row] - rank[coo.col]).max())


@pytest.mark.parametrize("system", sorted(SYSTEMS))
def test_ordering_is_a_permutation_and_lowers_the_bandwidth(system):
    S = SYSTEMS[system]()
    n = S.shape[0]
    order = band_order(S.indptr, S.indices, n)
    assert np.array_equal(np.sort(order), np.arange(n))
    before, after = _bandwidth(S, np.arange(n)), _bandwidth(S, order)
    assert after < before
    if system.startswith("grid"):
        # a side x side triangulated grid: its levels are about a side wide
        side = int(system.split("-")[1])
        assert after <= 3 * side < before


def test_ordering_matches_scipys_rcm_to_a_factor():
    from scipy.sparse.csgraph import reverse_cuthill_mckee

    S = SYSTEMS["grid-100"]()
    ours = _bandwidth(S, band_order(S.indptr, S.indices, S.shape[0]))
    theirs = _bandwidth(S, reverse_cuthill_mckee(S, symmetric_mode=True))
    assert ours <= 1.25 * theirs


def test_ordering_places_every_component_and_gives_up_on_too_many():
    blocks = [as_scipy(spd_data(12, s)) for s in (1, 2, 3)]
    S = sp.block_diag(blocks + [sp.identity(5, dtype=np.float32)]).tocsr()
    order = band_order(S.indptr, S.indices, S.shape[0])
    assert np.array_equal(np.sort(order), np.arange(S.shape[0]))
    eye = sp.identity(20000, dtype=np.float32, format="csr")
    assert band_order(eye.indptr, eye.indices, 20000, budget=4096) is None
    assert band_order(eye.indptr, eye.indices, 200).shape == (200,)


def test_symmetric_pattern():
    S = SYSTEMS["grid-60"]()
    assert ws.symmetric_pattern(S.indptr, S.indices, S.shape[0])
    L = S.tolil()
    L[3, 1000] = 1.0
    L = L.tocsr()
    assert not ws.symmetric_pattern(L.indptr, L.indices, L.shape[0])
    # as many above the diagonal as below, in other places
    L = L.tolil()
    L[2000, 5] = 1.0
    L = L.tocsr()
    assert not ws.symmetric_pattern(L.indptr, L.indices, L.shape[0])


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
@pytest.fixture
def on_the_chip(monkeypatch):
    """The platform gate open and the row floor at the tests' sizes: the
    whole path as the chip drives it, the kernel interpreted."""
    monkeypatch.setattr(csr, "_well_platform", lambda: True)
    monkeypatch.setattr(csr, "_WELL_MIN_ROWS", 1000)


def _wide():
    """A random symmetric pattern: no ordering leaves it a band."""
    rng = np.random.default_rng(11)
    n = 6000
    R = sp.random(n, n, density=3.0 / n, random_state=rng, dtype=np.float32)
    return (R + R.T + sp.identity(n, dtype=np.float32)).tocsr()


def _nonsymmetric():
    S = SYSTEMS["grid-60"]().tolil()
    S[3, 1000] = 0.25
    return S.tocsr()


def _hub():
    return as_scipy(spd_data(60, 5, skew=900))


DECLINED = {
    "nonsymmetric": (_nonsymmetric, {}),
    "hub-row": (_hub, {}),
    # 118 units a tile of 1024 rows of 17 slots here: 0.0068 a slot
    "wide-window": (_wide, {"_WELL_MAX_UNITS_A_SLOT": 0.005}),
    "units-past-the-cap": (SYSTEMS["grid-60"], {"_WELL_MAX_UNITS": 64}),
    "block-past-vmem": (SYSTEMS["grid-60"], {"_WELL_BLOCK_BYTES": 1 << 20}),
    "small": (SYSTEMS["grid-60"], {"_WELL_MIN_ROWS": 4000}),
    "x-past-vmem": (SYSTEMS["grid-60"], {"_WELL_X_BYTES": 8192}),
    "float64": (lambda: SYSTEMS["grid-60"]().astype(np.float64), {}),
    "rectangular": (lambda: SYSTEMS["grid-60"]()[:, :3000], {}),
}


@pytest.mark.parametrize("case", sorted(DECLINED))
def test_rule_declines_and_the_product_is_todays(case, on_the_chip, monkeypatch):
    make, consts = DECLINED[case]
    for name, value in consts.items():
        monkeypatch.setattr(csr, name, value)
    S = make()
    x = np.random.default_rng(4).standard_normal(S.shape[1]).astype(S.dtype)
    A = sparse_tpu.csr_array(S)
    y = np.asarray(A @ x)
    assert not A._well
    kind = A._spmv_form(x.dtype)[0]
    assert kind in ("ell", "sell")
    # today's product, bit for bit: the same matrix with the gate shut
    monkeypatch.setattr(csr, "_well_platform", lambda: False)
    B = sparse_tpu.csr_array(S)
    np.testing.assert_array_equal(y, np.asarray(B @ x))
    assert B._spmv_form(x.dtype)[0] == kind


def test_rule_declines_on_the_cpu_backend(monkeypatch):
    monkeypatch.setattr(csr, "_WELL_MIN_ROWS", 1000)
    S = SYSTEMS["grid-60"]()
    A = sparse_tpu.csr_array(S)
    A.prepare()
    assert A._well is None and A._ell is not None
    assert A._spmv_form()[0] == "ell"


def test_rule_offers_counts_and_records(on_the_chip, monkeypatch, tmp_path):
    S = SYSTEMS["grid-60"]()
    n = S.shape[0]
    x = np.random.default_rng(5).standard_normal(n).astype(np.float32)
    def products():
        return telemetry.counters().get("kernel.well_spmv", 0)

    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    try:
        A = sparse_tpu.csr_array(S)
        p0 = products()
        y = np.asarray(A @ x)
        y2 = np.asarray(A @ x)
        assert products() == p0 + 2
        spans = [e for e in telemetry.events("span")
                 if e["name"] in ("layout.reorder", "layout.ell_build")]
    finally:
        telemetry.configure(None)
        telemetry.reset()
    assert [e["name"] for e in spans] == ["layout.reorder", "layout.ell_build"]
    ev = spans[0]  # once an operator, with what the reordering left
    assert ev["offered"] is True and ev["tile"] == ws.TILE
    assert 0 < ev["window_chunks_mean"] <= ev["window_chunks_max"] <= 16
    # a step has a unit at least, and no more than the longest row's entries
    k = int(np.diff(S.indptr).max())
    assert ev["steps"] <= ev["units"] <= ev["steps"] * k
    assert ev["units"] <= ev["units_stored"] == A._well.arrays["val"].shape[0]
    assert ev["units"] == A._well.arrays["ustart"].shape[0] == 71
    assert 60 <= ev["bandwidth"] <= 180
    assert A._spmv_form(x.dtype)[0] == "well" and A._ell is None
    ref = S.astype(np.float64) @ x.astype(np.float64)
    assert np.abs(y - ref).max() / np.abs(ref).max() < 1e-6
    np.testing.assert_array_equal(y, y2)
    # another result type than the kernel's float32 passes the layout over
    assert A._spmv_form(np.complex64)[0] == "ell"
    z = np.asarray(A @ (x + 1j * x).astype(np.complex64))
    assert np.abs(z.real - ref).max() / np.abs(ref).max() < 1e-5


# ---------------------------------------------------------------------------
# linalg.cg through the layout
# ---------------------------------------------------------------------------
def _pair(side, seed, monkeypatch, shift=0.0):
    """The same system twice: offered the layout, and with the gate shut.
    ``shift`` is added to the diagonal (a better-conditioned system)."""
    data = spd_data(side, seed)
    n = data["rows"]
    S = (as_scipy(data) + shift * sp.identity(n, dtype=np.float32)).tocsr()
    parts = (S.data, S.indices, S.indptr)
    A = sparse_tpu.csr_array(parts, shape=(n, n))
    assert A.prepare()._well is not None
    monkeypatch.setattr(csr, "_well_platform", lambda: False)
    B = sparse_tpu.csr_array(parts, shape=(n, n))
    assert B.prepare()._well is None
    return A, B, jnp.asarray(data["b"])


# the class itself after 50 iterations is far from converged (condition about
# side^2): the two paths' rounding differences are amplified, read 1.1e-5 from
# zero and 7.0e-5 from a start; with the diagonal shifted by 1 the 50
# iterations converge and the paths agree to float32's floor (read 1e-7)
@pytest.mark.parametrize("shift,agree", [(0.0, 3e-4), (1.0, 1e-5)],
                         ids=["the-class", "shifted"])
@pytest.mark.parametrize("with_x0", [False, True], ids=["from-zero", "x0"])
def test_cg_through_the_layout_agrees_with_the_ell_path(
        with_x0, shift, agree, on_the_chip, monkeypatch):
    A, B, b = _pair(60, 41, monkeypatch, shift)
    x0 = None
    if with_x0:
        x0 = np.random.default_rng(6).random(b.shape[0]).astype(np.float32)
    x, iters = linalg.cg(A, b, x0=x0, maxiter=50)
    x_ell, iters_ell = linalg.cg(B, b, x0=x0, maxiter=50)
    assert iters == iters_ell and iters == (49 if shift else 50)  # the last test stops a converged one
    assert A._spmv_form(b.dtype)[0] == "well" and B._spmv_form(b.dtype)[0] == "ell"
    # x in the caller's order: the same iterate, rounded another way
    x, x_ell = np.asarray(x), np.asarray(x_ell)
    assert x.shape == b.shape
    assert np.linalg.norm(x - x_ell) / np.linalg.norm(x_ell) < agree
    if with_x0:  # the start is honoured: zero iterations return it
        x_none, it0 = linalg.cg(A, b, x0=x0, maxiter=0)
        assert it0 == 0
        np.testing.assert_array_equal(np.asarray(x_none), x0)


def test_cg_through_the_layout_traces_once_and_reports_it(
        on_the_chip, monkeypatch, tmp_path):
    A, _B, b = _pair(60, 42, monkeypatch)
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    try:
        t0 = TRACES.value
        x1, _ = linalg.cg(A, b, maxiter=30)
        assert TRACES.value == t0 + 1
        x2, _ = linalg.cg(A, b, maxiter=30)
        x3, it3 = linalg.cg(A, 2.0 * b, tol=1e-3, maxiter=400)
        assert TRACES.value == t0 + 1  # flat from the second call on
        events = [e for e in telemetry.events("span") if e["name"] == "cg.solve"]
    finally:
        telemetry.configure(None)
        telemetry.reset()
    assert [(e["path"], e["layout"]) for e in events] == [("device", "well")] * 3
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    assert it3 < 400 and it3 % 25 == 0
    r3 = np.asarray(A @ x3) - 2.0 * np.asarray(b)
    assert np.linalg.norm(r3) < 5e-2  # float32: the true residual drifts


def test_cg_converges_to_the_solution_in_the_callers_order(
        on_the_chip, monkeypatch):
    A, _B, b = _pair(40, 43, monkeypatch)
    S = as_scipy(spd_data(40, 43)).astype(np.float64)
    x, iters = linalg.cg(A, b, tol=1e-4, maxiter=2000)
    x_ref = np.linalg.solve(S.toarray(), np.asarray(b, dtype=np.float64))
    assert iters < 2000
    assert np.linalg.norm(np.asarray(x) - x_ref) / np.linalg.norm(x_ref) < 1e-3


def test_other_solvers_multiply_through_the_layout(on_the_chip, monkeypatch):
    """``make_linear_operator`` prepares the layout for every solver: gmres
    meets it through ``A.matvec``, both permutations around each product."""
    A, _B, b = _pair(40, 44, monkeypatch)
    S = as_scipy(spd_data(40, 44)).astype(np.float64)
    x, _ = linalg.gmres(A, b, tol=1e-6, restart=60, maxiter=40)
    r = S @ np.asarray(x, dtype=np.float64) - np.asarray(b, dtype=np.float64)
    assert np.linalg.norm(r) / np.linalg.norm(np.asarray(b)) < 1e-3
