"""Seeded unstructured SPD systems for the general-CG tests, and the FEM heat
steps of the served general pattern, made by the benchmark's own generators
(``benchmark/operators/spd_unstructured.py`` and ``fem_heat_step.py``, which
import nothing of the program and hold the plain references too)."""

import importlib.util
import os

import numpy as np
import scipy.sparse as sp

_OPERATORS = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                          "operators")


def operator_module(name="spd_unstructured"):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(_OPERATORS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def spd_data(side, seed, iterations=50, skew=0):
    """The generator's host data; with ``skew``, vertex 0 also gets an edge of
    weight 0.01 to each of ``skew`` further vertices (a Laplacian update, so
    the system stays SPD), which makes its row the longest by far."""
    gen = operator_module()
    data = gen.make({"side": side, "iterations": iterations,
                     "pattern_seed": seed}, seed)
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


def fem_heat_data(side, seed, clients=8, pattern_seed=3200000103):
    """The FEM heat step's host data at a small side, with the coefficient
    range and the tolerance of the configuration ``fem-heat-1m2``: ``pattern``
    (scipy CSR), ``values (clients, nnz)``, ``coef``, ``initial``, ``source``,
    ``carry``, ``rel_tol``, ``kappa_bound``."""
    return operator_module("fem_heat_step").make(
        {"side": side, "pattern_seed": pattern_seed, "clients": clients,
         "coefficient_range": [1.5, 4.5], "rel_tol": 1e-5,
         "check_sample": clients}, seed)


def as_scipy(data, dtype=None):
    n = data["rows"]
    A = sp.csr_matrix((data["data"], data["indices"], data["indptr"]),
                      shape=(n, n))
    return A if dtype is None else A.astype(dtype)
