"""Spectral norm estimation via the power method.

Reference analog: ``examples/spectral_norm.py`` (derived from
github.com/pericycle/normest): dense vs CSR power iteration must agree.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from sparse_tpu import csr_array


def normest(M, tol=1e-4):
    """2-norm of M (PSD) by power iteration."""
    max_it = 10
    res = 1.0
    it_count = 0
    rng = np.random.default_rng(15210)
    x = rng.random((M.shape[1], 1))
    y = np.asarray(M.dot(x))
    pnorm = np.sqrt(np.sum(y**2))
    x = y / pnorm
    while (res > tol) and (it_count < max_it):
        y = np.asarray(M.dot(x))
        ynorm = np.sqrt(np.sum(y**2))
        res = abs(pnorm - ynorm)
        pnorm = ynorm.copy()
        x = y / ynorm
        it_count += 1
    v = np.asarray(M.dot(x))
    return np.sqrt(np.sum(v**2))


if __name__ == "__main__":
    rng = np.random.default_rng(15210)
    M = rng.random((100, 100))
    A = csr_array(M)
    dense_est = normest(M)
    sparse_est = normest(A)
    print(f"dense normest:  {dense_est:.6f}")
    print(f"sparse normest: {sparse_est:.6f}")
    assert np.isclose(sparse_est, dense_est), (sparse_est, dense_est)
    print("OK")
