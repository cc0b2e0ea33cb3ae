"""System under test: the library path of a user who ports upstream's
``examples/gmg.py``.

What this repo's ``examples/gmg.py`` builds by default: the grid-space
hierarchy (``gmg_grid.build_hierarchy``), the fine operator and the V-cycle
as operators that declare what they hold (``grid_operator``, ``make_vcycle``),
then ``linalg.cg(A, b, maxiter=..., M=M)`` with the program's defaults, b
resident on the device. One call is one solve, ending in
``block_until_ready``. The configuration guarantees the compiled program over
declared operators (``cg.precond.traces``, the ``device`` path, ``precond``
``gmg_grid``): a program without it is refused before anything is built,
because its loop would compile in every call of the window."""

from __future__ import annotations

import numpy as np


class System:
    def __init__(self, cfg, data, ctx):
        import jax

        from sparse_tpu import linalg, telemetry
        from sparse_tpu.models import gmg_grid
        from sparse_tpu.telemetry import _metrics

        if not _metrics.family("cg.precond.traces"):
            raise RuntimeError(
                "this program has no compiled CG over declared operators (no "
                "counter cg.precond.traces): linalg.cg(A, b, M=M) would trace "
                "and compile its loop in every call of the window")
        self.jax, self.linalg, self.telemetry = jax, linalg, telemetry
        self.ctx = ctx
        self.traces = _metrics.counter("cg.precond.traces")
        self.traces0 = None
        n, self.levels = data["grid"], data["levels"]
        with ctx.span("operator_build"):
            hier = gmg_grid.build_hierarchy(n, self.levels, data["gridop"])
            self.A = gmg_grid.grid_operator(hier)
            self.M = gmg_grid.make_vcycle(hier, data["gridop"])
            jax.block_until_ready((self.A.operands, self.M.operands))
        self.shape = {"rows": data["rows"], "grid": n, "levels": self.levels}
        self.reseed(data)

    def reseed(self, data) -> None:
        """Another seed is another right-hand side: the hierarchy and the
        program are functions of the sizes alone."""
        import jax.numpy as jnp

        self.maxiter = data["iterations"]
        self.b = self.jax.block_until_ready(jnp.asarray(data["b"]))

    def call(self):
        x, iters = self.linalg.cg(self.A, self.b, maxiter=self.maxiter, M=self.M)
        self.jax.block_until_ready(x)
        return {"x": x, "iters": int(iters)}

    def _off_path(self, spans) -> list:
        """The ``cg.solve`` spans among ``spans`` that do not name the
        compiled program over this hierarchy."""
        return [e for e in spans if e.get("name") == "cg.solve" and (
            e.get("path") != "device" or e.get("precond") != "gmg_grid"
            or e.get("levels") != self.levels)]

    def warm(self):
        """First call (the trace and the compile of ``jit_pcg``) and a
        second one with the program in place, which has to name the compiled
        path and the preconditioner; the window's calls must trace
        nothing."""
        ctx = self.ctx
        with ctx.span("first_call"):
            self.call()
        ctx.events_on()
        n0 = len(self.telemetry.events("solver.solve"))
        s0 = len(self.telemetry.events("span"))
        with ctx.span("warm_call"):
            self.call()
        paths = [e.get("path")
                 for e in self.telemetry.events("solver.solve")[n0:]]
        spans = self.telemetry.events("span")[s0:]
        solves = [e for e in spans if e.get("name") == "cg.solve"]
        ctx.events_default()
        ctx.guarantee("solver_path_not_device", 0.0 if paths == ["device"] else 1.0)
        ctx.guarantee("warm_call_not_jit_pcg_over_gmg_grid",
                      float(len(self._off_path(solves))) if len(solves) == 1 else 1.0)
        self.traces0 = self.traces.value
        # the set-up's spans with their fields: a window's events push them
        # out of the recorder's ring
        self.setup_spans = self.telemetry.events("span")

    def check_events(self, events: dict) -> None:
        """A traced run records the window's own ``cg.solve`` spans: each of
        them has to name the compiled path and the preconditioner too. The
        set-up's spans join the window's events under a kind of their own,
        ``setup.span``, as ``library_csr_cg.py`` hands them over."""
        events["setup.span"] = self.setup_spans
        # the text of the executable the window ran (jit's own: nothing is
        # traced or compiled for it), whose op_names carry the cycle's
        # named scopes: what reducers/op_scope_share.py reads a level's
        # share of the device time from
        try:
            text = self.linalg._pcg_compiled(self.A, self.b, self.M).as_text()
            events["program.hlo"] = [{"program": "jit_pcg", "text": text}]
        except Exception as e:  # noqa: BLE001 - the shares then read nothing
            self.ctx.say(f"no text of the compiled program: {e!r}")
        solves = [e for e in events.get("span", []) if e.get("name") == "cg.solve"]
        self.ctx.guarantee("window_solve_not_jit_pcg_over_gmg_grid",
                           float(len(self._off_path(solves))) if solves else 1.0)

    def answer(self, out) -> dict:
        return {"x": np.asarray(out["x"]), "iters": out["iters"]}

    def close(self):
        if self.traces0 is not None:
            self.ctx.guarantee("cg_precond_traces_in_window",
                               self.traces.value - self.traces0)
        self.A = self.M = self.b = None
