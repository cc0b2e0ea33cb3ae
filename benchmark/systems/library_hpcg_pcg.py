"""System under test: the library path of a user who runs HPCG's problem,
as ``examples/hpcg.py`` does.

The hierarchy of stored planes (``hpcg_grid.build_hierarchy``), the fine
operator and the V-cycle as operators that declare what they hold
(``grid_operator``, ``make_vcycle``), then ``linalg.cg(A, b, maxiter=...,
M=M)`` with the program's defaults, b resident on the device and
lexicographic, as HPCG numbers its unknowns. One call is one solve, ending in
``block_until_ready``. The configuration guarantees the compiled program over
declared operators (``cg.precond.traces``, the ``device`` path, ``precond``
``hpcg_mg``) and a matrix that is stored (27 planes a level among the
declared operands): a program without the model is refused before anything is
built.
The rest is the 2-D multigrid cell's adaptor (``library_gmg_pcg.py``: the warm
call, the window's spans, the program's text handed to a traced run, the
trace counter), with its two guarantees renamed for this preconditioner."""

from __future__ import annotations

import manifest

_gmg = manifest.load_module("systems", "library_gmg_pcg")


class System(_gmg.System):
    def __init__(self, cfg, data, ctx):
        import jax

        from sparse_tpu import linalg, telemetry
        from sparse_tpu.telemetry import _metrics

        try:
            from sparse_tpu.models import hpcg_grid
        except ImportError as e:
            raise RuntimeError(
                "this program has no sparse_tpu.models.hpcg_grid: no stored "
                "27-point hierarchy and no Gauss-Seidel smoother to run "
                "HPCG's problem with") from e
        self.jax, self.linalg, self.telemetry = jax, linalg, telemetry
        self.ctx = ctx
        self.traces = _metrics.counter("cg.precond.traces")
        self.traces0 = None
        (nx, ny, nz), self.levels = data["grid"], data["levels"]
        with ctx.span("operator_build"):
            hier = hpcg_grid.build_hierarchy(nx, ny, nz, levels=self.levels)
            self.A = hpcg_grid.grid_operator(hier)
            self.M = hpcg_grid.make_vcycle(hier)
            jax.block_until_ready((self.A.operands, self.M.operands))
        # the rule against a matrix-free product: each level's declared
        # operands hold 27 coefficients a row, the product's as the cycle's
        rows = [(nx >> k) * (ny >> k) * (nz >> k) for k in range(self.levels)]
        held = [sum(int(a.size) for a in jax.tree_util.tree_leaves(level))
                for level in (self.A.operands, *self.M.operands)]
        ctx.guarantee("levels_not_27_stored_planes", float(
            len(held) != self.levels + 1
            or sum(h != 27 * n for h, n in zip(held, [rows[0], *rows]))))
        self.shape = {"rows": data["rows"], "grid": [nx, ny, nz],
                      "levels": self.levels}
        self.reseed(data)

    def call(self):
        # tol 0, as HPCG's timed sets pass it: every call runs its iterations
        x, iters = self.linalg.cg(self.A, self.b, tol=0.0, maxiter=self.maxiter,
                                  M=self.M)
        self.jax.block_until_ready(x)
        return {"x": x, "iters": int(iters)}

    def _off_path(self, spans) -> list:
        """The ``cg.solve`` spans among ``spans`` that do not name the
        compiled program over this hierarchy."""
        return [e for e in spans if e.get("name") == "cg.solve" and (
            e.get("path") != "device" or e.get("precond") != "hpcg_mg"
            or e.get("levels") != self.levels or e.get("colours") != 8)]

    def _name_the_preconditioner(self) -> None:
        for check in self.ctx.checks:
            check["name"] = check["name"].replace("_over_gmg_grid", "_over_hpcg_mg")

    def warm(self):
        super().warm()
        self._name_the_preconditioner()

    def check_events(self, events: dict) -> None:
        super().check_events(events)
        self._name_the_preconditioner()
