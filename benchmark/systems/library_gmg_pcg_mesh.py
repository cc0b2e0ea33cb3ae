"""System under test: the library path of a user who ports upstream's
``examples/gmg.py`` and runs it on one four-chip host (``examples/gmg.py
-dist``).

``library_gmg_pcg.py``'s adaptor with the lay-out between the build and the
solve: ``gmg_grid.build_hierarchy`` on one device (span ``operator_build``),
``gmg_grid.shard_hierarchy_grid(h, get_mesh(chips))`` (span ``mesh_layout``:
every level's planes in row blocks, the fine level's scalars replicated), the
right-hand side under the vector sharding it returns, then
``linalg.cg(A, b, maxiter=..., M=M)`` unchanged. One call is one solve, ending
in ``block_until_ready``.

Besides the one-chip adaptor's guarantees (the compiled program over declared
operators, nothing traced in the window) it holds the mesh's, read off the
arrays and off the compiled program's text, not off the span's ``devices``
field, so that a commit without that field can be run in the cell:

- the iterate and every level's planes live on all the chips, in row blocks;
- the program gathers no grid, plane or vector: no ``all-gather`` and no
  ``all-to-all`` with a float32 result (a program that gathers is another,
  wrong deployment: the partitioner's index vectors are int32 and pass);
- where the operators declare their halo exchanges (``describe``'s
  ``halo_exchanges``, which the ``cg.solve`` span repeats and
  ``gmg_mesh_permutes_per_iter`` reads), the loop of the compiled program
  holds that many ``collective-permute``s. A program that declares none, as
  the partitioner's own form, is not held to a count: it is printed."""

from __future__ import annotations

import re

import manifest

_one_chip = manifest.load_module("systems", "library_gmg_pcg")

_GATHERS = re.compile(r" = .*f32\[.* (all-gather|all-to-all)(-start)?\(")
_COLLECTIVE = re.compile(
    r" (collective-permute|all-reduce|all-gather|all-to-all)(-start)?\(")


def collectives(text: str) -> tuple:
    """(collectives in the loop's body by opcode, float32 results gathered)
    of a compiled program's text. An op of the loop carries ``/while/body/``
    in its ``op_name``; an asynchronous pair counts once, at its start."""
    loop: dict = {}
    gathered = []
    for ln in text.splitlines():
        m = _COLLECTIVE.search(ln)
        if m and "/while/body/" in ln:
            loop[m.group(1)] = loop.get(m.group(1), 0) + 1
        if _GATHERS.search(ln):
            gathered.append(ln.strip()[:160])
    return loop, gathered


class System(_one_chip.System):
    def __init__(self, cfg, data, ctx):
        import jax

        from sparse_tpu import linalg, telemetry
        from sparse_tpu.models import gmg_grid
        from sparse_tpu.parallel.mesh import get_mesh
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
        self.text = None
        self.chips = int(cfg["chips"])
        n, self.levels = data["grid"], data["levels"]
        with ctx.span("operator_build"):
            hier = gmg_grid.build_hierarchy(n, self.levels, data["gridop"])
            jax.block_until_ready(hier)
        with ctx.span("mesh_layout"):
            self.hier, self.vec = gmg_grid.shard_hierarchy_grid(
                hier, get_mesh(self.chips))
            self.A = gmg_grid.grid_operator(self.hier)
            self.M = gmg_grid.make_vcycle(self.hier, data["gridop"])
            jax.block_until_ready((self.A.operands, self.M.operands))
        del hier
        self.shape = {"rows": data["rows"], "grid": n, "levels": self.levels,
                      "chips": self.chips}
        self.reseed(data)

    def reseed(self, data) -> None:
        self.maxiter = data["iterations"]
        self.b = self.jax.block_until_ready(
            self.jax.device_put(data["b"], self.vec))

    def _in_row_blocks(self, a) -> bool:
        """On every chip, split along its rows as the solve's vectors are."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        rows = NamedSharding(self.vec.mesh,
                             P(*self.vec.spec, *[None] * (a.ndim - 1)))
        return (len(a.sharding.device_set) == self.chips and bool(self.vec.spec)
                and a.sharding.is_equivalent_to(rows, a.ndim))

    def warm(self):
        super().warm()
        ctx = self.ctx
        out = self.call()
        ctx.guarantee("iterate_not_in_row_blocks_on_every_chip",
                      0.0 if self._in_row_blocks(out["x"]) else 1.0)
        planes = [a for st, w, _n in self.hier for a in (*st.values(), w)
                  if getattr(a, "ndim", 0) == 2]
        ctx.guarantee("planes_not_in_row_blocks_on_every_chip",
                      float(sum(not self._in_row_blocks(a) for a in planes))
                      if planes else 1.0)
        self.text = self.linalg._pcg_compiled(self.A, self.b, self.M).as_text()
        loop, gathered = collectives(self.text)
        declared = sum((op.describe or {}).get("halo_exchanges", 0)
                       for op in (self.A, self.M))
        ctx.say(f"the program's collectives an iteration: {loop}; declared "
                f"halo exchanges: {declared or 'none'}; float32 results "
                f"gathered: {gathered}")
        ctx.guarantee("program_gathers_a_grid_or_a_vector", float(len(gathered)))
        if declared:
            ctx.guarantee("loop_permutes_not_the_declared_halo_exchanges",
                          float(abs(loop.get("collective-permute", 0) - declared)))
        self.traces0 = self.traces.value

    def check_events(self, events: dict) -> None:
        """What the one-chip adaptor's hands a traced run, with the program's
        text as set-up read it."""
        events["setup.span"] = self.setup_spans
        events["program.hlo"] = [{"program": "jit_pcg", "text": self.text}]
        solves = [e for e in events.get("span", []) if e.get("name") == "cg.solve"]
        self.ctx.guarantee("window_solve_not_jit_pcg_over_gmg_grid",
                           float(len(self._off_path(solves))) if solves else 1.0)

    def close(self):
        super().close()
        self.hier = self.text = None
