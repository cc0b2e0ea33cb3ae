"""System under test: the library's multi-chip path, as ``chip_smoke.py
--chips 4`` and ``examples/weak_scaling.py`` take it.

``sparse.diags(...).tocsr()`` as upstream does, ``shard_csr(A,
mesh=get_mesh(chips))`` once, the right-hand side padded and resident, then
``dist_cg(D, bp, tol=0.0, maxiter=...)`` with the program's defaults. One call
is one solve, ending in ``dist_cg``'s own fence (the fetch of the iteration
count). The configuration guarantees the banded layout over the halo
exchange: anything else is refused before the first solve."""

from __future__ import annotations

import numpy as np


class System:
    def __init__(self, cfg, data, ctx):
        import jax

        import sparse_tpu as sparse
        from sparse_tpu.parallel import dist_cg, get_mesh, shard_csr
        from sparse_tpu.telemetry import _metrics

        self.jax, self.dist_cg = jax, dist_cg
        self.data, self.ctx = data, ctx
        self.maxiter = data["iterations"]
        self.chips = int(cfg["chips"])
        N = data["rows"]
        with ctx.span("operator_build"):
            A = sparse.diags(data["diagonals"], data["offsets"],
                             shape=(N, N)).tocsr()
            jax.block_until_ready(A.data)
        with ctx.span("shard_build"):
            self.D = D = shard_csr(A, mesh=get_mesh(self.chips))
        if (D.layout, D.mode) != ("dia", "halo"):
            raise RuntimeError(
                f"the configuration guarantees layout 'dia' over mode 'halo'; "
                f"shard_csr gave {D.layout!r} over {D.mode!r}")
        jax.block_until_ready(D._blocks())
        self.bp = jax.block_until_ready(D.pad_out_vector(data["b"]))
        self.traces = _metrics.counter("dist.cg.traces")
        self.traces0 = None
        self.shape = {"rows": N, "nnz": data["nnz"],
                      "diagonals": len(data["offsets"]),
                      "rows_per_chip": D.R, "iterations": self.maxiter,
                      "shards": D.S, "halo": [D.HL, D.HR]}

    def call(self):
        xp, iters, _ = self.dist_cg(self.D, self.bp, tol=0.0,
                                    maxiter=self.maxiter)
        return {"x": xp, "iters": iters}

    def warm(self):
        """First call (trace and compile) and a second one with the program
        kept on the layout; the window's calls must trace nothing."""
        ctx = self.ctx
        with ctx.span("first_call"):
            self.call()
        with ctx.span("warm_call"):
            out = self.call()
        ctx.guarantee("iterate_not_on_every_chip",
                      0.0 if len(out["x"].sharding.device_set) == self.chips
                      else 1.0)
        self.traces0 = self.traces.value

    def reseed(self, data) -> None:
        """Another seed's right-hand side on the layout already built
        (tools/read_limits.py: a dozen seeds for one set-up)."""
        self.data = data
        self.bp = self.jax.block_until_ready(self.D.pad_out_vector(data["b"]))

    def answer(self, out) -> dict:
        return {"x": self.D.unpad_vector(out["x"]), "iters": out["iters"]}

    def close(self):
        if self.traces0 is not None:
            self.ctx.guarantee("dist_cg_traces_in_window",
                               self.traces.value - self.traces0)
        self.D = self.bp = None
