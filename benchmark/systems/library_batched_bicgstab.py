"""System under test: the library path of a code whose every mesh vertex
solves its own small nonsymmetric system at each implicit step, all of them
in one call, as ``examples/xgc_collision.py`` does.

The six calls a user makes: ``SparsityPattern(indptr, indices, shape)``,
``BatchedCSR(pattern, values).todia()``, ``precond.make_factory(pattern,
"jacobi")(values, op.matvec)``, then ``linalg.batched_bicgstab(op, b, x0=b,
tol=tol_lanes, maxiter=..., M=Mvec, conv_test_iters=...)`` with the value
stack and b resident on the device, one call one solve of every lane, ending
in ``block_until_ready``. The configuration guarantees the compiled batched
solve (``batch.bicgstab.traces``, the span ``batched_bicgstab.solve`` with
``path`` ``device`` and one fetch, the values arguments of the program): a
program without it is refused before anything is built, because its loop
would trace, lower and compile in every call of the window."""

from __future__ import annotations

import manifest
import numpy as np


class System:
    def __init__(self, cfg, data, ctx):
        import jax

        from sparse_tpu import linalg, precond, telemetry
        from sparse_tpu.batch import BatchedCSR, SparsityPattern
        from sparse_tpu.telemetry import _metrics

        if not _metrics.family("batch.bicgstab.traces"):
            raise RuntimeError(
                "this program has no compiled batched BiCGStab (no counter "
                "batch.bicgstab.traces): linalg.batched_bicgstab would trace, "
                "lower and compile its loop in every call of the window")
        self.jax, self.linalg, self.telemetry = jax, linalg, telemetry
        self.precond, self.BatchedCSR = precond, BatchedCSR
        self.ctx = ctx
        self.sizes = cfg["sizes"]
        self.operator = manifest.load_module("operators", cfg["operator"])
        self.traces = _metrics.counter("batch.bicgstab.traces")
        self.traces0 = None
        n = data["rows"]
        with ctx.span("operator_build"):
            self.pattern = SparsityPattern(data["indptr"], data["indices"], (n, n))
        self.shape = {"rows": n, "diags": data["diags"], "nnz": data["nnz"],
                      "systems": data["systems"]}
        self.reseed(data)

    def _build(self, values):
        """Operator and preconditioner over one value stack, as a user
        builds them."""
        op = self.BatchedCSR(self.pattern, values).todia()
        M = self.precond.make_factory(self.pattern, "jacobi")(values, op.matvec)
        self.jax.block_until_ready((op.operands, M.operands))
        return op, M

    def reseed(self, data) -> None:
        """Another seed is another value stack and other old states on the
        one pattern: operator and preconditioner are built anew, the
        program is not."""
        import jax.numpy as jnp

        self.seed = data["seed"]
        self.maxiter, self.cti = data["maxiter"], data["conv_test_iters"]
        with self.ctx.span("operator_build"):
            self.op, self.M = self._build(data["values"])
        self.b = data["b"]
        self.tol = self.jax.block_until_ready(
            data["tol_rel"] * jnp.linalg.norm(self.b, axis=1))

    def _solve(self, op, M):
        X, info = self.linalg.batched_bicgstab(
            op, self.b, x0=self.b, tol=self.tol, maxiter=self.maxiter, M=M,
            conv_test_iters=self.cti)
        self.jax.block_until_ready(X)
        return {"x": X, "iters": int(np.max(info.iters)), "info": info}

    def call(self):
        return self._solve(self.op, self.M)

    def _off_path(self, spans) -> list:
        """The ``batched_bicgstab.solve`` spans among ``spans`` that do not
        name the compiled solve of every lane in one fetch."""
        want = {"path": "device", "fetches": 1, "B": self.shape["systems"],
                "precond": "jacobi"}
        return [e for e in spans if e.get("name") == "batched_bicgstab.solve"
                and any(e.get(k) != v for k, v in want.items())]

    def warm(self):
        """First call (the trace and the compile of ``jit_batched_bicgstab``),
        a second one with the program in place, which has to name the
        compiled path, and a third over a second value stack (another
        assembly of the same mesh: seed + 1), which must trace nothing
        because the values are arguments; the window's calls must trace
        nothing either."""
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
                  if e.get("name") == "batched_bicgstab.solve"]
        with ctx.span("second_values"):
            second = self._build(self.operator.value_stack(self.sizes,
                                                           self.seed + 1))
            traces = self.traces.value
            self._solve(*second)
            del second
        ctx.events_default()
        ctx.guarantee("solver_path_not_device", 0.0 if paths == ["device"] else 1.0)
        ctx.guarantee("warm_call_not_jit_batched_bicgstab",
                      float(len(self._off_path(solves))) if len(solves) == 1 else 1.0)
        ctx.guarantee("second_values_traced", self.traces.value - traces)
        self.traces0 = self.traces.value
        # the set-up's spans with their fields: a window's events push them
        # out of the recorder's ring
        self.setup_spans = self.telemetry.events("span")

    def check_events(self, events: dict) -> None:
        """A traced run records the window's own ``batched_bicgstab.solve``
        spans: each of them has to name the compiled path too. The set-up's
        spans join the window's events under a kind of their own,
        ``setup.span``, as ``library_csr_cg.py`` hands them over."""
        events["setup.span"] = self.setup_spans
        # the text of the executable the window ran (jit's own: nothing is
        # traced or compiled for it), whose op_names carry the loop's named
        # scopes: what reducers/op_scope_share.py reads a scope's share of
        # the device time from
        try:
            text = self.linalg._batched_bicgstab_compiled(
                self.op, self.b, self.M, self.cti).as_text()
            events["program.hlo"] = [{"program": "jit_batched_bicgstab",
                                      "text": text}]
        except Exception as e:  # noqa: BLE001 - the shares then read nothing
            self.ctx.say(f"no text of the compiled program: {e!r}")
        solves = [e for e in events.get("span", [])
                  if e.get("name") == "batched_bicgstab.solve"]
        self.ctx.guarantee("window_solve_not_jit_batched_bicgstab",
                           float(len(self._off_path(solves))) if solves else 1.0)

    def answer(self, out) -> dict:
        info = out["info"]
        return {"x": np.asarray(out["x"]), "iters": out["iters"],
                "iters_lanes": np.asarray(info.iters),
                "converged": np.asarray(info.converged)}

    def close(self):
        if self.traces0 is not None:
            self.ctx.guarantee("batch_bicgstab_traces_in_window",
                               self.traces.value - self.traces0)
        self.op = self.M = self.b = self.tol = None
