"""Seeded unstructured SPD systems for the general-CG tests, made by the
benchmark's own generator (``benchmark/operators/spd_unstructured.py``, which
imports nothing of the program and holds the plain reference too)."""

import importlib.util
import os

import numpy as np
import scipy.sparse as sp

_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                     "operators", "spd_unstructured.py")


def operator_module():
    spec = importlib.util.spec_from_file_location("bench_spd_unstructured", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def spd_data(side, seed, iterations=50, skew=0):
    """The generator's host data; with ``skew``, vertex 0 also gets an edge of
    weight 0.01 to each of ``skew`` further vertices (a Laplacian update, so
    the system stays SPD), which makes its row the longest by far."""
    gen = operator_module()
    data = gen.make({"side": side, "iterations": iterations}, seed)
    if skew:
        A = as_scipy(data).tolil()
        far = np.random.default_rng(seed).choice(
            np.arange(1, data["rows"]), size=skew, replace=False)
        for j in far:
            A[0, j] -= 0.01
            A[j, 0] -= 0.01
            A[0, 0] += 0.01
            A[j, j] += 0.01
        A = A.tocsr().astype(np.float32)
        A.sort_indices()
        data.update(indptr=A.indptr.astype(np.int32), data=A.data,
                    indices=A.indices.astype(np.int32), nnz=int(A.nnz))
    return data


def as_scipy(data, dtype=None):
    n = data["rows"]
    A = sp.csr_matrix((data["data"], data["indices"], data["indptr"]),
                      shape=(n, n))
    return A if dtype is None else A.astype(dtype)
