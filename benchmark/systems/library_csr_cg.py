"""System under test: the library path of a user who read a matrix from a
Matrix Market file.

``sparse.csr_array((data, indices, indptr), shape=...)`` from host arrays,
then ``linalg.cg(A, b, maxiter=...)`` with the program's defaults, b resident
on the device. One call is one solve, ending in ``block_until_ready``. The
configuration guarantees the compiled general CG (``cg.general.traces``, the
``device`` path): a program without it is refused before anything is built,
because its loop would compile in every call of the window."""

from __future__ import annotations

import numpy as np


class System:
    def __init__(self, cfg, data, ctx):
        import jax

        import sparse_tpu as sparse
        from sparse_tpu import linalg, telemetry
        from sparse_tpu.telemetry import _metrics

        if not _metrics.family("cg.general.traces"):
            raise RuntimeError(
                "this program has no compiled CG for a general layout (no "
                "counter cg.general.traces): linalg.cg would trace and "
                "compile its loop in every call of the window")
        self.jax, self.sparse = jax, sparse
        self.linalg, self.telemetry = linalg, telemetry
        self.ctx = ctx
        self.traces = _metrics.counter("cg.general.traces")
        self.traces0 = None
        self.reseed(data)

    def reseed(self, data) -> None:
        """Another seed is other values and another b on the configuration's
        one pattern (the triangulation and the permutation come from its
        ``pattern_seed``): the operator is built anew all the same, from the
        three host arrays, and its layout with its first solve."""
        import jax.numpy as jnp

        self.maxiter = data["iterations"]
        N = data["rows"]
        with self.ctx.span("operator_build"):
            self.A = self.sparse.csr_array(
                (data["data"], data["indices"], data["indptr"]), shape=(N, N))
            self.jax.block_until_ready(self.A.data)
        self.b = self.jax.block_until_ready(jnp.asarray(data["b"]))
        self.shape = {"rows": N, "nnz": data["nnz"]}

    def call(self):
        x, iters = self.linalg.cg(self.A, self.b, maxiter=self.maxiter)
        self.jax.block_until_ready(x)
        return {"x": x, "iters": int(iters)}

    def warm(self):
        """First call (banded detection, layout build, commit to the chip,
        compile) and a second one with the layout and the program in place,
        which has to name the compiled path; the window's calls must trace
        nothing."""
        ctx = self.ctx
        with ctx.span("first_call"):
            self.call()
        ctx.events_on()
        n0 = len(self.telemetry.events("solver.solve"))
        with ctx.span("warm_call"):
            self.call()
        paths = [e.get("path")
                 for e in self.telemetry.events("solver.solve")[n0:]]
        ctx.events_default()
        ctx.guarantee("solver_path_not_device", 0.0 if paths == ["device"] else 1.0)
        self.traces0 = self.traces.value
        # the set-up's spans with their fields (``layout.reorder``'s step
        # count): a window's events push them out of the recorder's ring
        self.setup_spans = self.telemetry.events("span")

    def check_events(self, events: dict) -> None:
        """A traced run records the window's own ``cg.solve`` spans: each of
        them has to name the compiled path too. The set-up's spans join the
        window's events under a kind of their own, ``setup.span``, for the
        metrics that read a set-up span's field."""
        events["setup.span"] = self.setup_spans
        solves = [e for e in events.get("span", []) if e.get("name") == "cg.solve"]
        off = [e for e in solves if e.get("path") != "device"]
        self.ctx.guarantee("window_solver_path_not_device",
                           float(len(off)) if solves else 1.0)

    def answer(self, out) -> dict:
        return {"x": np.asarray(out["x"]), "iters": out["iters"]}

    def close(self):
        if self.traces0 is not None:
            self.ctx.guarantee("cg_general_traces_in_window",
                               self.traces.value - self.traces0)
        self.A = self.b = None
