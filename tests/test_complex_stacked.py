"""Complex dtypes through the public API (VERDICT r3 #5).

c64/c128 host data moves to the device and back through ``utils.asjnp`` /
``utils.tohost`` like any other dtype; these pin complex SpMV, CG and
``solve_ivp`` to scipy. The on-chip complex round trip is a line of
``chip_smoke.py``.

Reference analog: the {c64, c128} accelerator dispatch lanes of
``src/sparse/util/dispatch.h:53-75``.
"""

import numpy as np

import sparse_tpu as sparse
import sparse_tpu.linalg as linalg
from sparse_tpu import integrate, utils


def test_asjnp_tohost_roundtrip():
    z = (np.arange(6) + 1j * np.arange(6)[::-1]).astype(np.complex128)
    d = utils.asjnp(z)
    assert np.iscomplexobj(d)
    np.testing.assert_allclose(utils.tohost(d), z)
    r = np.arange(4.0)
    np.testing.assert_allclose(utils.tohost(utils.asjnp(r)), r)


def test_complex_spmv_via_asjnp():
    n = 32
    rng = np.random.default_rng(1)
    hop = rng.random(n - 1) + 1j * rng.random(n - 1)
    H = sparse.diags([np.conj(hop), np.full(n, 2.0 + 0j), hop], [-1, 0, 1]).tocsr()
    x = rng.random(n) + 1j * rng.random(n)
    import scipy.sparse as sp

    Hs = sp.diags([np.conj(hop), np.full(n, 2.0 + 0j), hop], [-1, 0, 1]).tocsr()
    np.testing.assert_allclose(
        utils.tohost(H @ utils.asjnp(x)), Hs @ x, rtol=1e-10
    )


def test_complex_cg_via_asjnp():
    n = 64
    rng = np.random.default_rng(2)
    hop = rng.random(n - 1) + 1j * rng.random(n - 1)
    A = sparse.diags(
        [np.conj(hop), np.full(n, 6.0 + 0j), hop], [-1, 0, 1]
    ).tocsr()
    b = rng.random(n) + 1j * rng.random(n)
    x, iters = linalg.cg(A, b, tol=1e-10, maxiter=500)
    import scipy.sparse as sp

    As = sp.diags([np.conj(hop), np.full(n, 6.0 + 0j), hop], [-1, 0, 1]).tocsr()
    resid = np.linalg.norm(As @ utils.tohost(x) - b)
    assert resid < 1e-7, resid


def test_complex_solve_ivp_via_asjnp():
    n = 16
    rng = np.random.default_rng(3)
    hop = rng.random(n - 1) + 1j * rng.random(n - 1)
    H = sparse.diags([np.conj(hop), np.full(n, 1.0 + 0j), hop], [-1, 0, 1]).tocsr()
    psi0 = np.zeros(n, dtype=complex)
    psi0[n // 2] = 1.0
    out = integrate.solve_ivp(
        lambda t, p: -1j * (H @ p), (0.0, 0.4), psi0, rtol=1e-9, atol=1e-11
    )
    psiT = utils.tohost(out.y)[:, -1]
    assert abs(np.linalg.norm(psiT) - 1.0) < 1e-6
    import scipy.integrate as si
    import scipy.sparse as sp

    Hs = sp.diags([np.conj(hop), np.full(n, 1.0 + 0j), hop], [-1, 0, 1]).tocsr()
    ref = si.solve_ivp(
        lambda t, p: -1j * (Hs @ p), (0.0, 0.4), psi0, rtol=1e-9, atol=1e-11
    )
    np.testing.assert_allclose(psiT, ref.y[:, -1], rtol=1e-5, atol=1e-7)
