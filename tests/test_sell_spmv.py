"""SELL-C-sigma prepared SpMV: pack correctness, mode parity, fallbacks.

The prepared general-matrix path of ISSUE 2: every ``spmv_mode`` must agree
with the dense/scipy oracle on the awkward shapes (empty rows, zero-nnz,
duplicate columns, dtype axis, power-law row-length skew), with the plan
cache enabled and disabled.
"""

import gc

import numpy as np
import pytest
import scipy.sparse as sp

import sparse_tpu
from sparse_tpu import plan_cache
from sparse_tpu.config import Settings, settings
from sparse_tpu.kernels import sell_spmv
from sparse_tpu.kernels.sell_spmv import sell_pack
from sparse_tpu.ops.spmv import csr_spmv_sell

from .utils.sample import sample_csr, sample_vec

MODES = ("segment", "ell", "sell", "pallas", "auto")


def powerlaw_csr(m=300, seed=5, dtype=np.float64):
    """Pathological power-law row-length profile (plus one near-dense row):
    the shape where ELL's global-max padding explodes."""
    rng = np.random.default_rng(seed)
    deg = np.minimum((rng.pareto(1.0, m) * 3 + 1).astype(int), m - 1)
    deg[0] = m - 1  # one near-dense row pins the global max
    rows = np.repeat(np.arange(m), deg)
    cols = rng.integers(0, m, rows.shape[0])
    vals = rng.standard_normal(rows.shape[0])
    if np.issubdtype(dtype, np.complexfloating):
        vals = vals + 1j * rng.standard_normal(rows.shape[0])
    return sp.coo_matrix((vals.astype(dtype), (rows, cols)), shape=(m, m)).tocsr()


def _cases():
    """(label, scipy_csr) pairs for the parity sweep."""
    out = [
        ("random_f64", sample_csr(37, 29, density=0.25, seed=1)),
        ("random_f32", sample_csr(23, 31, dtype=np.float32, seed=2)),
        ("c64", sample_csr(19, 19, dtype=np.complex64, seed=3)),
        ("powerlaw", powerlaw_csr(120, seed=4)),
        ("zero_nnz", sp.csr_matrix((7, 5), dtype=np.float64)),
        (
            "empty_rows",
            sp.csr_matrix(
                (np.array([1.0, 2.0]), np.array([1, 3]),
                 np.array([0, 0, 2, 2, 2, 2])),
                shape=(5, 4),
            ),
        ),
        (
            # duplicate column ids within a row (from_parts skips the
            # COO-dedup canonicalization) must sum, not drop
            "dup_cols",
            sp.csr_matrix(
                (np.array([1.0, 2.0, 4.0]), np.array([1, 1, 0]),
                 np.array([0, 2, 3, 3])),
                shape=(3, 3),
            ),
        ),
    ]
    return out


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("cache_on", [True, False], ids=["cache", "nocache"])
def test_spmv_mode_parity(mode, cache_on, monkeypatch):
    """Every mode x every awkward shape x cache on/off == dense reference."""
    monkeypatch.setattr(settings, "spmv_mode", mode)
    monkeypatch.setattr(settings, "plan_cache", cache_on)
    for label, s in _cases():
        A = sparse_tpu.csr_array.from_parts(
            s.data, s.indices, s.indptr, s.shape
        )
        rng = np.random.default_rng(11)
        x = rng.standard_normal(s.shape[1])
        if np.issubdtype(s.dtype, np.complexfloating):
            x = (x + 1j * rng.standard_normal(s.shape[1])).astype(s.dtype)
        dense = s.toarray()
        for rep in range(2):  # second call exercises the cached plan
            got = np.asarray(A @ x)
            np.testing.assert_allclose(
                got, dense @ x, rtol=2e-5, atol=2e-5,
                err_msg=f"{label} mode={mode} cache={cache_on} rep={rep}",
            )
        B = rng.standard_normal((s.shape[1], 4))
        np.testing.assert_allclose(
            np.asarray(A @ B), dense @ B, rtol=2e-5, atol=2e-5,
            err_msg=f"{label} spmm mode={mode} cache={cache_on}",
        )


@pytest.mark.parametrize("C,sigma,max_slabs", [(4, 0, 16), (8, 32, 16), (8, 64, 3), (16, 1000, 16)])
def test_sell_pack_geometry(C, sigma, max_slabs):
    """Pack invariants across chunk/window/slab-budget settings: exact SpMV,
    every nonzero stored once, pad bounded by the quantization guarantee."""
    s = powerlaw_csr(130, seed=9)
    plan, slabs, pos = sell_pack(
        s.indptr, s.indices, s.data, s.shape, C=C, sigma=sigma,
        max_slabs=max_slabs,
    )
    assert len(plan.slab_meta) <= max(max_slabs, 33)  # pow2 fallback bound
    total_vals = sum(int((np.asarray(vt) != 0).sum()) for _, vt in slabs)
    assert total_vals == int((s.data != 0).sum())
    x = np.random.default_rng(0).standard_normal(s.shape[1])
    from sparse_tpu.ops.spmv import csr_spmv_sell

    got = np.asarray(csr_spmv_sell(slabs, pos, np.asarray(x), plan.zero_rows))
    np.testing.assert_allclose(got, s @ x, rtol=1e-10, atol=1e-10)


def test_sell_beats_ell_padding_on_skew():
    """The point of the format: on the power-law profile the SELL stored
    slots stay near nnz while ELL's global-max padding is >10x."""
    s = powerlaw_csr(300, seed=5)
    plan, _, _ = sell_pack(s.indptr, s.indices, s.data, s.shape)
    kmax = int(np.diff(s.indptr).max())
    ell_slots = s.shape[0] * kmax
    assert plan.pad_ratio < 3.0
    assert ell_slots / max(s.nnz, 1) > 10 * plan.pad_ratio


def test_auto_mode_routes_skewed_to_sell(monkeypatch):
    """'auto' folds the SELL option in: a skewed profile packs a SELL plan,
    a tight (banded-free, bounded-degree) profile keeps the ELL path."""
    monkeypatch.setattr(settings, "spmv_mode", "auto")
    skewed = sparse_tpu.csr_array(powerlaw_csr(100, seed=8))
    x = np.random.default_rng(2).standard_normal(100)
    skewed @ x
    assert plan_cache.lookup(skewed, "sell") is not None

    tight = sparse_tpu.csr_array(sample_csr(40, 40, density=0.2, seed=3))
    tight @ np.random.default_rng(3).standard_normal(40)
    assert tight._ell is not None
    assert plan_cache.lookup(tight, "sell") is None


def test_sell_mode_env_roundtrip(monkeypatch):
    """SPARSE_TPU_SPMV_MODE round-trips through config for the new mode."""
    monkeypatch.setenv("SPARSE_TPU_SPMV_MODE", "sell")
    assert Settings().spmv_mode == "sell"
    monkeypatch.delenv("SPARSE_TPU_SPMV_MODE")
    assert Settings().spmv_mode == "auto"
    monkeypatch.setenv("SPARSE_TPU_PLAN_CACHE", "0")
    assert Settings().plan_cache is False


def test_prepare_api(monkeypatch):
    """csr_array.prepare() warms the mode's plan eagerly and returns self."""
    monkeypatch.setattr(settings, "spmv_mode", "sell")
    A = sparse_tpu.csr_array(powerlaw_csr(80, seed=10))
    assert A.prepare() is A
    assert plan_cache.lookup(A, "sell") is not None
    # explicit mode override does not disturb the ambient setting
    monkeypatch.setattr(settings, "spmv_mode", "segment")
    B = sparse_tpu.csr_array(powerlaw_csr(80, seed=11))
    B.prepare(mode="sell")
    assert settings.spmv_mode == "segment"
    assert plan_cache.lookup(B, "sell") is not None


def test_in_trace_cold_start_degrades_then_warm(monkeypatch):
    """First use inside a trace cannot pack (host syncs) and must still be
    correct; an eager warm then serves the compiled path the plan."""
    import jax

    monkeypatch.setattr(settings, "spmv_mode", "sell")
    s = powerlaw_csr(60, seed=12)
    A = sparse_tpu.csr_array(s)
    x = np.random.default_rng(4).standard_normal(60)
    y_cold = np.asarray(jax.jit(A._spmv)(np.asarray(x)))
    np.testing.assert_allclose(y_cold, s @ x, rtol=1e-10)
    assert plan_cache.lookup(A, "sell") is None  # no cache write in-trace
    A.prepare()
    y_warm = np.asarray(jax.jit(A._spmv)(np.asarray(x)))
    np.testing.assert_allclose(y_warm, s @ x, rtol=1e-10)


def test_dia_detection_fetch_failure_raises(monkeypatch):
    """A failed offsets fetch is an error, not a quiet trip down the gather
    path — and it does not cache 'not banded': once the fetch works the
    same matrix is detected."""
    import jax

    offs = [-1, 0, 1]
    e = np.ones(32)
    s = sp.diags([e[:-1], 2 * e, e[:-1]], offs, format="csr")
    A = sparse_tpu.csr_array(s)

    def boom(offs_dev):
        raise jax.errors.JaxRuntimeError("UNIMPLEMENTED: transfer failed")

    good = sparse_tpu.csr_array._fetch_offsets
    monkeypatch.setattr(sparse_tpu.csr_array, "_fetch_offsets", staticmethod(boom))
    with pytest.raises(jax.errors.JaxRuntimeError, match="transfer failed"):
        A @ np.ones(32)
    assert A._dia is False  # unchecked, not "not banded"
    monkeypatch.setattr(sparse_tpu.csr_array, "_fetch_offsets", staticmethod(good))
    np.testing.assert_allclose(np.asarray(A @ np.ones(32)), s @ np.ones(32))
    assert A._dia is not None and A._dia[1] == (-1, 0, 1)


def test_sell_plan_dies_with_matrix(monkeypatch):
    """_with_data / fresh objects never inherit a stale plan; collected
    matrices evict their plans (weak-ref keyed cache)."""
    monkeypatch.setattr(settings, "spmv_mode", "sell")
    s = powerlaw_csr(50, seed=13)
    A = sparse_tpu.csr_array(s)
    x = np.random.default_rng(5).standard_normal(50)
    A @ x
    A2 = A * 2.0  # fresh object -> fresh (cold) plan
    assert plan_cache.lookup(A2, "sell") is None
    np.testing.assert_allclose(np.asarray(A2 @ x), 2 * (s @ x), rtol=1e-10)
    before = plan_cache.stats()["size"]
    del A, A2
    gc.collect()
    assert plan_cache.stats()["size"] < before


# ---------------------------------------------------------------------------
# the rule that gives a slab its row count (PR 39: the gather's wide band;
# PR 41: a multiple of WINDOW_ROWS inside it)
# ---------------------------------------------------------------------------
BAND_LO, BAND_HI = sell_spmv.WIDE_BAND
WINDOW = sell_spmv.WINDOW_ROWS
MAX_PAD = 2 * WINDOW - 1  # up to the next multiple, and over one off the band

ROW_COUNTS = [
    0, 1, 7, 8, 9, 1000, 1017, 1024,                  # what ROW_ALIGN gives
    1025, 1032, 1033,                                 # the band's lower end
    1024 + BAND_HI - 1, 1024 + BAND_HI, 1024 + BAND_HI + 1,  # ... its upper
    2040, 2047, 2048, 2049, 2056,                     # about a multiple of 1024
    # fem_heat_served_closed's slabs under ROW_ALIGN alone, its rows, and
    # thermal2's rows' three narrow slabs (PERF.md section 5)
    229_344, 229_512, 345_112, 921_600, 921_640, 77_680, 304_920, 307_144,
    459_584, 5_000_000,
    # PR 41: about a multiple of WINDOW_ROWS, the most pad rows, and the
    # cell's slabs whose count of 8-row tiles had no divisor to window by
    1024 + WINDOW, 1024 + WINDOW + 1, 5 * 1024 + BAND_HI + 1, 57_944, 59_560,
    89_416,
]


@pytest.mark.parametrize("n", ROW_COUNTS)
def test_slab_rows_is_aligned_close_and_in_the_band(n):
    R = sell_spmv.slab_rows(n)
    aligned = -(-n // sell_spmv.ROW_ALIGN) * sell_spmv.ROW_ALIGN
    assert R % sell_spmv.ROW_ALIGN == 0
    assert n <= R <= n + MAX_PAD
    if aligned <= sell_spmv.WIDE_PERIOD:
        assert R == aligned  # a small slab keeps the count it had
    else:
        # every power of two up to WINDOW_ROWS / 8 divides its 8-row tiles
        assert R % WINDOW == 0
        assert BAND_LO <= R % sell_spmv.WIDE_PERIOD <= BAND_HI
        # the least such count
        assert R == next(
            r for r in range(-(-aligned // WINDOW) * WINDOW,
                             aligned + MAX_PAD + 1, WINDOW)
            if BAND_LO <= r % sell_spmv.WIDE_PERIOD <= BAND_HI)
    assert sell_spmv.slab_rows(R) == R  # idempotent


def test_slab_rows_is_a_function_of_the_row_count_alone():
    """Every count up to a few thousand and a random draw of large ones:
    never fewer rows than asked, never more than MAX_PAD more, monotone,
    and the same on a second asking (the pack is a vault artifact)."""
    rng = np.random.default_rng(41)
    ns = np.concatenate([np.arange(0, 6000),
                         rng.integers(6000, 5_000_000, size=4000)])
    ns.sort()
    Rs = np.array([sell_spmv.slab_rows(int(n)) for n in ns])
    assert (Rs >= ns).all() and (Rs - ns <= MAX_PAD).all()
    assert (np.diff(Rs) >= 0).all()
    assert (Rs == [sell_spmv.slab_rows(int(R)) for R in Rs]).all()
    big = Rs > sell_spmv.WIDE_PERIOD
    assert (Rs[big] % WINDOW == 0).all()
    assert (Rs[~big] - ns[~big] < sell_spmv.ROW_ALIGN).all()


@pytest.mark.parametrize("n,want", [
    (229_344, 229_632), (229_512, 229_632), (921_600, 921_856),
    (77_680, 78_080), (304_920, 305_408), (307_144, 307_456),
    (459_584, 460_032), (78_256, 78_336),
    # PR 41: the cell's two slabs whose tiles had no divisor (8 x 179 x 241,
    # 8 x a prime), side 600's (8 x a prime), the cell's other slabs
    (345_112, 345_344), (57_944, 58_112), (89_416, 89_600),
    (59_560, 59_648), (1024, 1024), (1032, 1280),
])
def test_slab_rows_at_the_cells_row_counts(n, want):
    assert sell_spmv.slab_rows(n) == want


def three_widths():
    """600, 2040 and 504 rows of 2, 3 and 5 entries, shuffled: under
    ``C=8, sigma=0`` one slab a length, and the middle one's 2040 rows sit
    8 short of 2048, a multiple of 256 rows off the band."""
    rng = np.random.default_rng(6)
    deg = rng.permutation(np.repeat((2, 3, 5), (600, 2040, 504)))
    m = deg.shape[0]
    r = np.repeat(np.arange(m), deg)
    c = np.concatenate([rng.choice(m, size=d, replace=False) for d in deg])
    v = rng.standard_normal(r.shape[0])
    return sp.coo_matrix((v, (r, c)), shape=(m, m)).tocsr()


def test_a_slab_past_1024_rows_gets_pad_rows_and_multiplies_as_scipy():
    s = three_widths()
    plan, slabs, pos, srcs = sell_pack(
        s.indptr, s.indices, s.data, s.shape, C=8, sigma=0, with_srcs=True)
    # 2040 rows -> 2304: the first multiple of 256 past them in the band;
    # the two small slabs keep their rows
    assert plan.slab_meta == ((2, 600, 0), (3, 2304, 264), (5, 504, 0))
    assert plan.pad_rows == 264
    (_i2, _v2), (it, vt), _ = slabs
    assert it.shape == vt.shape == (3, 2304)
    # the pad rows are ROW_ALIGN's own kind: index 0, value 0, no source
    assert not np.asarray(it)[:, 2040:].any()
    assert not np.asarray(vt)[:, 2040:].any()
    assert (np.asarray(srcs[1])[:, 2040:] == -1).all()
    pos = np.asarray(pos)
    assert len(np.unique(pos)) == s.shape[0]
    assert not ((pos >= 600 + 2040) & (pos < 600 + 2304)).any()
    x = np.random.default_rng(1).standard_normal(s.shape[1])
    got = np.asarray(csr_spmv_sell(slabs, pos, x, plan.zero_rows))
    np.testing.assert_allclose(got, s @ x, rtol=1e-10, atol=1e-10)
    packed = np.asarray(csr_spmv_sell(slabs, None, x, plan.zero_rows))
    assert packed.shape == (600 + 2304 + 504,)
    assert not packed[600 + 2040:600 + 2304].any()  # a pad row comes out zero
    # ... and through the matrix's own prepared operator
    A = sparse_tpu.csr_array(s)
    A.prepare(mode="sell")
    np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=1e-10,
                               atol=1e-10)


def test_the_vault_keys_carry_the_row_rule(monkeypatch):
    """A pack written under another rounding rule has other slabs: it must
    not be loaded in this one's place."""
    from sparse_tpu.batch.operator import SparsityPattern
    from sparse_tpu.vault import _codecs

    s = three_widths()
    pattern = SparsityPattern.from_csr(s)
    mine = (_codecs.sell_pattern_key(pattern),
            _codecs.prepared_csr_key(s.indptr, s.indices, s.data, s.shape))
    # the parent's rule: ROW_ALIGN alone, nothing of the band in the key
    monkeypatch.setattr(_codecs, "_sell_settings", lambda: (
        "C", settings.sell_chunk, "sigma", settings.sell_sigma,
        "slabs", settings.sell_max_slabs))
    parents = (_codecs.sell_pattern_key(pattern),
               _codecs.prepared_csr_key(s.indptr, s.indices, s.data, s.shape))
    assert parents[0] != mine[0] and parents[1] != mine[1]
    monkeypatch.undo()
    monkeypatch.setattr(sell_spmv, "WIDE_BAND", (8, 760))
    assert _codecs.sell_pattern_key(pattern) != mine[0]
    monkeypatch.undo()
    # ... and PR 39's, the band without the multiple of WINDOW_ROWS in it
    monkeypatch.setattr(sell_spmv, "WINDOW_ROWS", sell_spmv.ROW_ALIGN)
    assert _codecs.sell_pattern_key(pattern) != mine[0]
    assert (_codecs.prepared_csr_key(s.indptr, s.indices, s.data, s.shape)
            != mine[1])
