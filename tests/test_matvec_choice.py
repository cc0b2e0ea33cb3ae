"""How a matrix multiplies: ``spmv_mode`` x row profile -> layout.

The table below is ``csr._LAYOUTS`` (docs/performance.md) read from the
outside: each case multiplies a fresh matrix and names the layout from what
the product left behind on it (``_dia``, the plan cache's ``_dia_prepared``
and ``sell`` entries, ``_ell``, ``_well``), so the file does not depend on
how the choice is written.

``well`` (the windowed step-major units, kernels/well_spmv.py) is a TPU's: on this
backend nothing offers it, and with its platform gate opened by a test it is
offered by the matrix alone (``MESH``), never by a mode that names a layout.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import sparse_tpu
from sparse_tpu import csr, plan_cache
from sparse_tpu.config import settings

from .test_sell_spmv import powerlaw_csr
from .utils.sample import sample_csr


def _banded():
    n = 12  # a 5-point grid's five diagonals
    main = np.full(n * n, 4.0)
    side = np.full(n * n - 1, -1.0)
    side[n - 1 :: n] = 0.0
    far = np.full(n * n - n, -1.0)
    s = sp.diags([far, side, main, side, far], [-n, -1, 0, 1, n], format="csr")
    s.eliminate_zeros()
    return s.astype(np.float32)


PROFILES = {
    # five diagonals: banded by dia.few_diagonals
    "banded": _banded,
    # no band, longest row within ell_max_ratio (4) x the mean
    "tight": lambda: sample_csr(40, 40, density=0.2, seed=3).astype(np.float32),
    # one near-dense row far past 4 x the mean
    "skewed": lambda: powerlaw_csr(100, seed=8).astype(np.float32),
    "empty": lambda: sp.csr_matrix((7, 5), dtype=np.float32),
}

# mode -> profile -> (layout of A @ x, layout of A @ X)
TABLE = {
    "auto": {
        "banded": ("dia", "ell"),
        "tight": ("ell", "ell"),
        "skewed": ("sell", "sell"),
        "empty": ("segment", "segment"),
    },
    "pallas": {
        "banded": ("dia_packed", "ell"),
        "tight": ("sell", "ell"),
        # before PR 29 the 2-D product took a forced full-width ELL here
        "skewed": ("sell", "sell"),
        "empty": ("segment", "segment"),
    },
    "sell": {
        "banded": ("sell", "sell"),
        "tight": ("sell", "sell"),
        "skewed": ("sell", "sell"),
        "empty": ("segment", "segment"),
    },
    "ell": {
        "banded": ("ell", "ell"),
        "tight": ("ell", "ell"),
        "skewed": ("ell", "ell"),
        "empty": ("segment", "segment"),
    },
    "segment": {p: ("segment", "segment") for p in PROFILES},
}


def left_behind(A):
    """The layout the last product built on ``A``; "segment" builds none."""
    built = []
    if plan_cache.lookup(A, "_dia_prepared") is not None:
        built.append("dia_packed")
    elif isinstance(A._dia, tuple):
        built.append("dia")
    if A._ell is not None:
        built.append("ell")
    if plan_cache.lookup(A, "sell") is not None:
        built.append("sell")
    if A._well:
        built.append("well")
    assert len(built) <= 1, built
    return built[0] if built else "segment"


def test_profiles_are_what_they_say():
    for name, make in PROFILES.items():
        s = make()
        deg = np.diff(s.indptr)
        tight = s.nnz and deg.max() <= settings.ell_max_ratio * max(deg.mean(), 1.0)
        assert bool(tight) == (name in ("banded", "tight")), name
        banded = sparse_tpu.csr_array(s)._maybe_dia() is not None
        assert banded == (name == "banded"), name


def _open_the_well_gate(monkeypatch):
    """As on a TPU, at these sizes: the kernel then runs interpreted."""
    monkeypatch.setattr(csr, "_well_platform", lambda: True)
    monkeypatch.setattr(csr, "_WELL_MIN_ROWS", 1)


def _mesh():
    """A triangulated grid under a random permutation (the benchmark's
    unstructured SPD class): square, symmetric pattern, tight rows, and a
    band once reordered."""
    from .utils.spd import as_scipy, spd_data

    return as_scipy(spd_data(40, 9))


# mode -> (layout of A @ x, layout of A @ X) for ``_mesh`` with the gate open
MESH = {
    "auto": ("well", "ell"),
    "pallas": ("well", "ell"),
    "sell": ("sell", "sell"),
    "ell": ("ell", "ell"),
    "segment": ("segment", "segment"),
}


@pytest.mark.parametrize("gate", ["this-backend", "well-gate-open"])
@pytest.mark.parametrize("profile", list(PROFILES) + ["mesh"])
@pytest.mark.parametrize("mode", list(TABLE))
def test_mode_and_profile_choose_the_layout(mode, profile, gate, monkeypatch):
    monkeypatch.setattr(settings, "spmv_mode", mode)
    if gate == "well-gate-open":
        _open_the_well_gate(monkeypatch)
    if profile == "mesh":
        s = _mesh()
        # with the gate shut the mesh is one more tight matrix
        want_vec, want_mat = (MESH[mode] if gate == "well-gate-open"
                              else TABLE[mode]["tight"])
    else:
        # what the rule turns away (banded: dia comes first; not symmetric;
        # a hub row; no entries) multiplies as if the layout were not there
        s = PROFILES[profile]()
        want_vec, want_mat = TABLE[mode][profile]
    rng = np.random.default_rng(17)
    x = rng.standard_normal(s.shape[1]).astype(np.float32)
    X = rng.standard_normal((s.shape[1], 3)).astype(np.float32)

    A = sparse_tpu.csr_array(s)
    np.testing.assert_allclose(np.asarray(A @ x), s @ x, rtol=2e-4, atol=2e-4)
    assert left_behind(A) == want_vec

    A = sparse_tpu.csr_array(s)
    np.testing.assert_allclose(np.asarray(A @ X), s @ X, rtol=2e-4, atol=2e-4)
    assert left_behind(A) == want_mat


def test_pallas_spmm_of_a_skewed_matrix_builds_no_full_width_ell(monkeypatch):
    """Under 'pallas' a 2-D product of a non-banded matrix takes what 'auto'
    takes (SELL slabs past the ELL gate), not an m x max_row ELL."""
    monkeypatch.setattr(settings, "spmv_mode", "pallas")
    s = PROFILES["skewed"]()
    A = sparse_tpu.csr_array(s)
    X = np.random.default_rng(2).standard_normal((s.shape[1], 4)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(A @ X), s @ X, rtol=2e-4, atol=2e-4)
    assert A._ell is None
    assert plan_cache.lookup(A, "sell") is not None


def test_prepare_warms_what_a_product_takes(monkeypatch):
    """``prepare`` stops each row's walk where a product would: the mesh
    gets the windowed layout for its vector product and the padded rows for
    its 2-D one; with the gate shut the padded rows serve both."""
    _open_the_well_gate(monkeypatch)
    A = sparse_tpu.csr_array(_mesh()).prepare()
    assert A._well and A._ell is not None
    assert plan_cache.lookup(A, "sell") is None
    monkeypatch.setattr(csr, "_well_platform", lambda: False)
    B = sparse_tpu.csr_array(_mesh()).prepare()
    assert B._well is None and B._ell is not None


def test_prepare_mode_writes_no_global(monkeypatch):
    """``prepare(mode=)`` passes the mode down: the session's warm-replay
    thread and ``auto_flush`` read ``settings.spmv_mode`` in this process."""
    import sparse_tpu.csr as csr_mod

    class ReadOnlyMode:
        def __init__(self, real):
            object.__setattr__(self, "_real", real)

        def __getattr__(self, name):
            return getattr(self._real, name)

        def __setattr__(self, name, value):
            if name == "spmv_mode":
                raise AssertionError("prepare(mode=) assigned settings.spmv_mode")
            setattr(self._real, name, value)

    monkeypatch.setattr(settings, "spmv_mode", "segment")
    monkeypatch.setattr(csr_mod, "settings", ReadOnlyMode(settings))
    A = sparse_tpu.csr_array(PROFILES["skewed"]())
    assert A.prepare(mode="sell") is A
    assert plan_cache.lookup(A, "sell") is not None and A._ell is None
    assert settings.spmv_mode == "segment"


def test_poisson_cg_step_pallas_matches_xla():
    """``make_cg_step_dia(use_pallas=True)`` (the packed kernel, interpreted
    here) makes the iterates of the XLA step."""
    import jax
    import jax.numpy as jnp

    from sparse_tpu.models.poisson import cg_dia, laplacian_2d_dia, make_cg_step_dia

    n = 16
    N = n * n
    planes, offsets = laplacian_2d_dia(n)
    b = jax.random.normal(jax.random.PRNGKey(0), (N,), dtype=jnp.float32)
    zeros = jnp.zeros((N,), jnp.float32)
    state = (planes, zeros, b, zeros, jnp.zeros((), jnp.float32))
    got = {}
    for use_pallas in (False, True):
        step = make_cg_step_dia(offsets, n, use_pallas=use_pallas)
        got[use_pallas] = [np.asarray(v) for v in cg_dia(step, *state, iters=12)]
    for a, b_ in zip(got[True], got[False]):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-5)
