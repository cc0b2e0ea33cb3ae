"""The three compiled whole solves of ``linalg`` behind their one host
driver (PR 45): ``cg`` on a matrix (``jit_cg_general``), ``cg`` over a declared
``A`` and ``M`` (``jit_pcg``) and ``gmres`` over declared operators
(``jit_gmres``). What ``linalg._run_compiled_solve`` and
``linalg._declared_pair`` own is the same for the three, so each property is
one case a solve: a call under an outer ``jax.jit`` or with a 2-D ``b`` is
declined (no trace of the program, no span of the compiled path), a call that
is taken leaves exactly one ``<solver>.solve`` span with ``path``,
``dispatch_s``, ``fetch_s`` and ``iters`` and exactly one ``solver.solve``
event, and two calls trace the program once.

What is one solver's own (the answers, the programs' keys, the fields
``layout``, ``precond``, ``cycles``, ``orth_rows``) is in
``test_cg_general.py``, ``test_pcg_program.py`` and ``test_gmres_program.py``.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import sparse_tpu
from sparse_tpu import linalg, precond, telemetry
from sparse_tpu.config import settings
from sparse_tpu.models import gmg_grid as gg
from sparse_tpu.telemetry import _metrics


def _matrix(n=300, seed=3):
    rng = np.random.default_rng(seed)
    S = sp.random(n, n, density=0.03, random_state=rng, dtype=np.float32)
    S = S + S.T  # symmetric, then strictly diagonally dominant: SPD
    S = S + sp.diags(np.asarray(abs(S).sum(axis=1)).ravel() + 1.0)
    A = sparse_tpu.csr_array(S.tocsr().astype(np.float32))
    return A, jnp.asarray(rng.random(n), jnp.float32)


def _cg_on_a_matrix():
    A, b = _matrix()
    return b, lambda v: linalg.cg(A, v, maxiter=20)


def _cg_over_declared_a_and_m():
    hier = gg.build_hierarchy(32, 2)
    A, M = gg.grid_operator(hier), gg.make_vcycle(hier)
    b = jnp.asarray(np.random.default_rng(0).random(32 * 32), jnp.float32)
    return b, lambda v: linalg.cg(A, v, maxiter=8, M=M)


def _gmres_over_declared_operators():
    A, b = _matrix(seed=5)
    M = precond.make_M(A, "jacobi")
    return b, lambda v: linalg.gmres(A, v, restart=10, maxiter=2, M=M,
                                     tol=1e-30)


# solve -> (its set-up, the span's solver, its jit, its counter of traces)
SOLVES = {
    "cg-matrix": (_cg_on_a_matrix, "cg", "_cg_general_program",
                  "cg.general.traces"),
    "cg-declared": (_cg_over_declared_a_and_m, "cg", "_pcg_program",
                    "cg.precond.traces"),
    "gmres-declared": (_gmres_over_declared_operators, "gmres",
                       "_gmres_program", "gmres.traces"),
}
PROPERTIES = ["declines-under-an-outer-jit", "declines-a-2d-b",
              "one-solve-span-a-call", "one-solve-event-a-call",
              "two-calls-trace-once"]


@pytest.fixture
def tel(tmp_path, monkeypatch):
    telemetry.reset()
    monkeypatch.setattr(settings, "telemetry", True)
    telemetry.configure(str(tmp_path / "records.jsonl"))
    yield
    telemetry.configure(None)
    telemetry.reset()


def _device_spans(solver):
    return [e for e in telemetry.events("span")
            if e["name"] == f"{solver}.solve" and e["path"] == "device"]


@pytest.mark.parametrize("prop", PROPERTIES)
@pytest.mark.parametrize("solve", sorted(SOLVES))
def test_a_compiled_whole_solve_through_the_one_driver(solve, prop, tel):
    setup, solver, program, counter = SOLVES[solve]
    # an earlier test of this process that solved the same structure would
    # leave these calls nothing to trace
    getattr(linalg, program).clear_cache()
    traces = _metrics.counter(counter)
    b, run = setup()
    t0 = traces.value
    if prop.startswith("declines"):
        # whatever the older path then makes of the call (under a trace its
        # host fetch raises; a block of vectors it solves or refuses), the
        # program was not tried
        try:
            if prop == "declines-a-2d-b":
                run(jnp.stack([b, b], axis=1))
            else:
                jax.jit(lambda v: run(v)[0])(b)
        except (TypeError, ValueError, jax.errors.ConcretizationTypeError):
            pass
        assert traces.value == t0
        assert _device_spans(solver) == []
        return
    for call in range(2):
        s0 = len(telemetry.events("span"))
        e0 = len(telemetry.events("solver.solve"))
        _x, iters = run(b)
        if prop == "one-solve-span-a-call":
            (ev,) = [e for e in telemetry.events("span")[s0:]
                     if e["name"].endswith(".solve")]
            assert (ev["name"], ev["path"], ev["iters"]) == (
                f"{solver}.solve", "device", iters)
            assert 0 < ev["dispatch_s"] and 0 <= ev["fetch_s"]
            assert ev["dispatch_s"] + ev["fetch_s"] <= ev["dur_s"]
            assert telemetry.schema.validate(ev) == []
        if prop == "one-solve-event-a-call":
            (ev,) = telemetry.events("solver.solve")[e0:]
            assert (ev["solver"], ev["path"], ev["iters"], ev["n"]) == (
                solver, "device", iters, b.shape[0])
    assert traces.value == t0 + 1
