"""System under test: the library path of a user who read a nonsymmetric
matrix from a Matrix Market file and solves with restarted GMRES.

``sparse.csr_array((data, indices, indptr), shape=...)`` from host arrays,
then ``linalg.gmres(A, b, restart=..., maxiter=cycles, tol=1e-30)``, b
resident on the device. One call is one solve, ending in
``block_until_ready``. The configuration guarantees the compiled whole
solve over declared operators (``gmres.traces``, the span ``gmres.solve``
with ``path`` ``device`` and one fetch): a program without it is refused
before anything is built, because its cycle would compile in every call of
the window."""

from __future__ import annotations

import numpy as np


class System:
    def __init__(self, cfg, data, ctx):
        import jax

        import sparse_tpu as sparse
        from sparse_tpu import linalg, telemetry
        from sparse_tpu.telemetry import _metrics

        if not _metrics.family("gmres.traces"):
            raise RuntimeError(
                "this program has no compiled GMRES over declared operators "
                "(no counter gmres.traces): linalg.gmres would trace and "
                "compile its restart cycle in every call of the window")
        self.jax, self.sparse = jax, sparse
        self.linalg, self.telemetry = linalg, telemetry
        self.ctx = ctx
        self.traces = _metrics.counter("gmres.traces")
        self.traces0 = None
        self.reseed(data)
        # the sizes' own, whatever the seed; the warm call reads the diagonals
        # off the layout the program built
        self.shape = {"rows": data["rows"], "diagonals": 0,
                      "restart": self.restart, "cycles": self.cycles}

    def reseed(self, data) -> None:
        """Another seed is other values and another b on the box's one
        pattern: the operator is built anew all the same, from the three
        host arrays, and its layout with its first solve."""
        import jax.numpy as jnp

        self.restart, self.cycles = data["restart"], data["cycles"]
        N = data["rows"]
        with self.ctx.span("operator_build"):
            self.A = self.sparse.csr_array(
                (data["data"], data["indices"], data["indptr"]), shape=(N, N))
            self.jax.block_until_ready(self.A.data)
        self.b = self.jax.block_until_ready(jnp.asarray(data["b"]))

    def call(self):
        x, iters = self.linalg.gmres(self.A, self.b, restart=self.restart,
                                     maxiter=self.cycles, tol=1e-30)
        self.jax.block_until_ready(x)
        return {"x": x, "iters": int(iters)}

    def _off_path(self, spans) -> list:
        """The ``gmres.solve`` spans among ``spans`` that do not name the
        compiled whole solve: one fetch, every cycle and every step run."""
        want = {"path": "device", "fetches": 1, "restart": self.restart,
                "cycles": self.cycles, "iters": self.restart * self.cycles}
        return [e for e in spans if e.get("name") == "gmres.solve"
                and any(e.get(k) != v for k, v in want.items())]

    def warm(self):
        """First call (banded detection, the host's DIA build, commit to the
        chip, the trace and the compile of ``jit_gmres``) and a second one
        with the layout and the program in place, which has to name the
        compiled path; the window's calls must trace nothing."""
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
        solves = [e for e in self.telemetry.events("span")[s0:]
                  if e.get("name") == "gmres.solve"]
        ctx.events_default()
        ctx.guarantee("solver_path_not_device", 0.0 if paths == ["device"] else 1.0)
        ctx.guarantee("warm_call_not_jit_gmres",
                      float(len(self._off_path(solves))) if len(solves) == 1 else 1.0)
        kind, _arrays, meta = self.A._spmv_form(self.b.dtype)
        ctx.guarantee("layout_not_dia", 0.0 if kind == "dia" else 1.0)
        self.shape["diagonals"] = len(meta[0]) if kind == "dia" else 0
        self.traces0 = self.traces.value
        # the set-up's spans with their fields: a window's events push them
        # out of the recorder's ring
        self.setup_spans = self.telemetry.events("span")

    def check_events(self, events: dict) -> None:
        """A traced run records the window's own ``gmres.solve`` spans: each
        of them has to name the compiled path too. The set-up's spans join
        the window's events under a kind of their own, ``setup.span``, as
        ``library_csr_cg.py`` hands them over."""
        events["setup.span"] = self.setup_spans
        # the text of the executable the window ran (jit's own: nothing is
        # traced or compiled for it), whose op_names carry the cycle's
        # named scopes: what reducers/op_scope_share.py reads a scope's
        # share of the device time from
        try:
            text = self.linalg._gmres_compiled(
                self.A, self.b, self.restart).as_text()
            events["program.hlo"] = [{"program": "jit_gmres", "text": text}]
        except Exception as e:  # noqa: BLE001 - the shares then read nothing
            self.ctx.say(f"no text of the compiled program: {e!r}")
        solves = [e for e in events.get("span", [])
                  if e.get("name") == "gmres.solve"]
        self.ctx.guarantee("window_solve_not_jit_gmres",
                           float(len(self._off_path(solves))) if solves else 1.0)

    def answer(self, out) -> dict:
        return {"x": np.asarray(out["x"]), "iters": out["iters"]}

    def close(self):
        if self.traces0 is not None:
            self.ctx.guarantee("gmres_traces_in_window",
                               self.traces.value - self.traces0)
        self.A = self.b = None
