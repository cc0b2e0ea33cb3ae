"""System under test: the library path a user of ``examples/pde.py`` takes.

``sparse.diags(...).tocsr()`` then ``linalg.cg(A, b, maxiter=...)`` with the
program's defaults, b resident on the device as upstream's is. One call is one
solve, ending in ``block_until_ready``."""

from __future__ import annotations

import time

import numpy as np


class System:
    def __init__(self, cfg, data, ctx):
        import jax
        import jax.numpy as jnp

        import sparse_tpu as sparse
        from sparse_tpu import linalg, telemetry

        self.jax, self.linalg, self.telemetry = jax, linalg, telemetry
        self.data, self.ctx = data, ctx
        self.maxiter = data["iterations"]
        N = data["rows"]
        with ctx.span("operator_build"):
            self.A = sparse.diags(data["diagonals"], data["offsets"],
                                  shape=(N, N)).tocsr()
            jax.block_until_ready(self.A.data)
        self.b = jax.block_until_ready(jnp.asarray(data["b"]))
        self.shape = {"rows": N, "nnz": data["nnz"],
                      "diagonals": len(data["offsets"])}

    def call(self):
        x, iters = self.linalg.cg(self.A, self.b, maxiter=self.maxiter)
        self.jax.block_until_ready(x)
        return {"x": x, "iters": int(iters)}

    def warm(self):
        """First call (layout build, commit to the chip, compiles) and a
        second one with every program in memory; what the first took beyond
        the second and beyond its compiles is the layout build."""
        ctx = self.ctx
        ctx.events_on()
        c0 = ctx.compile_seconds()
        with ctx.span("first_call") as first:
            self.call()
        compiles = ctx.compile_seconds() - c0
        paths = sorted({e.get("path") for e in
                        self.telemetry.events("solver.iter")})
        ctx.events_default()
        with ctx.span("warm_call") as warm:
            self.call()
        ctx.add_span("layout_build",
                     max(first.seconds - compiles - warm.seconds, 0.0))
        ctx.guarantee("solver_path_not_fused", 0.0 if paths == ["fused"] else 1.0)

    def check_events(self, events: dict) -> None:
        """A traced run records the window's own ``solver.iter`` events: each
        of them has to name the fused path too."""
        off = [e for e in events.get("solver.iter", [])
               if e.get("path") != "fused"]
        self.ctx.guarantee("window_solver_path_not_fused",
                           float(len(off)) if events.get("solver.iter") else 1.0)

    def reseed(self, data) -> None:
        """Another seed's right-hand side on the operator already built
        (tools/read_limits.py: a dozen seeds for one set-up)."""
        import jax.numpy as jnp

        self.data = data
        self.b = self.jax.block_until_ready(jnp.asarray(data["b"]))

    def answer(self, out) -> dict:
        return {"x": np.asarray(out["x"]), "iters": out["iters"]}

    def close(self):
        self.A = self.b = None
