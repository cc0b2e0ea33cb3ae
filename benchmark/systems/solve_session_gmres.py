"""System under test: a ``SolveSession("gmres")`` serving an ensemble's
time-stepping members on one nonsymmetric pattern.

``systems/solve_session.py``'s adaptor (loaded by path, subclassed, neither
copied nor edited: the same submit, kick, wait and answer) plus the path this
configuration guarantees: the session's GMRES bucket is ONE compiled program,
the whole solve, whose product is the plane form the session takes from the
banded pattern. Before the ramp the adaptor asks the session's own builder
for the pattern's bucket program and raises at once when that is not such a
program (a host-driven closure has no ``matvec``): the guaranteed path is
checked in seconds, and a tree without it fails instead of grinding through a
ramp on one host round trip a cycle. Nothing here or in the configuration
chooses the product or the program.

After the window it asks the program the ramp's first dispatch left in the
plan cache for its form again, and holds the always-on counter
``batch.gmres.traces`` to what it was when the window opened. In a traced run
every ``batch.dispatch`` of the window has to carry ``matvec`` ``planes``,
``fetches`` 1 and an ``iters_max`` of at most two cycles' steps (a bucket that
needed a third cycle's worth would take half as long again), and there has to
be one; the text of the executable the window ran goes to the reducers that
read a scope's share. No answer may report more steps than two cycles hold,
traced or not. The guarantee counts steps and not passes: where float32's
recurrence says a lane converged at the end of the second cycle and the true
residual at the restart says not quite, a third pass makes a step or two
(``cycles_max`` 3 with ``iters_max`` 50, read on the chip, PR 49); the passes
are printed."""

from __future__ import annotations

import manifest

_base = manifest.load_module("systems", "solve_session")

FORM = "planes"
CYCLES = 2


class System(_base.System):
    def __init__(self, cfg, data, ctx):
        super().__init__(cfg, data, ctx)
        import numpy as np

        from sparse_tpu.telemetry import _metrics

        self._metrics = _metrics
        if not _metrics.family("batch.gmres.traces"):
            raise RuntimeError(
                "this program's SolveSession('gmres') has no compiled bucket "
                "program (no counter batch.gmres.traces): its GMRES bucket "
                "is driven from the host, one blocking fetch a restart cycle")
        self.bucket = int(self.ses.batch_max)
        self.dtype = np.dtype(cfg["sizes"]["dtype"])
        # what the session's dispatch will build for this pattern and bucket:
        # the builder's own answer, nothing traced or compiled yet
        with ctx.span("operator_build"):
            program = self.ses._build_program(self.pattern, self.bucket,
                                              self.dtype)
        form = getattr(program, "matvec", None)
        if not hasattr(program, "lower") or form != FORM:
            raise RuntimeError(
                "this program's SolveSession('gmres') has no compiled bucket "
                f"program with the plane product (builder gave {program!r}, "
                f"matvec {form!r}): its GMRES bucket is driven from the host, "
                "one blocking fetch a restart cycle")
        self.traces = _metrics.counter("batch.gmres.traces")
        self.traces0 = None
        self.shape.update(diagonals=len(self.pattern.plane_pack().offsets),
                          restart=int(self.ses.restart))

    def reseed(self, data) -> None:
        super().reseed(data)
        self.iters: list = []

    def answer(self, ticket) -> dict:
        if self.traces0 is None and getattr(self.ctx, "window_t0", None):
            self.traces0 = self.traces.value  # the window's first answer
        a = super().answer(ticket)
        self.iters.append(a["iters"])
        return a

    def _cached_program(self):
        from sparse_tpu import plan_cache

        key = f"batch.{self.solver}.B{self.bucket}.{self.dtype.str}"
        return plan_cache.lookup(self.pattern, key)

    def close(self):
        program = self._cached_program()
        form = getattr(program, "matvec", None)
        its = sorted(self.iters)
        self.ctx.say(
            f"the pattern's bucket product: {form}; steps of the answers "
            f"taken: min {its[0] if its else None}, median "
            f"{its[len(its) // 2] if its else None}, max "
            f"{its[-1] if its else None}")
        self.ctx.guarantee("session_matvec_not_planes",
                           0.0 if form == FORM else 1.0)
        # a lane's steps past two cycles: its bucket ran a third
        self.ctx.guarantee("answers_past_two_cycles", float(sum(
            i > CYCLES * self.shape["restart"] for i in its)))
        peaks = [g.value for g in self._metrics.family(
            "plan_cache.program_peak_bytes") if g.value]
        if peaks:
            self.ctx.say("bucket_program_hbm_gb (the compiler's analysis of "
                         f"the largest cached program): {max(peaks) / 1e9:.3f}")
        if self.traces0 is not None:
            self.ctx.guarantee("gmres_traces_in_window",
                               self.traces.value - self.traces0)
        super().close()

    def check_events(self, events: dict) -> None:
        sent = events.get("batch.dispatch", [])
        self.ctx.guarantee("window_matvec_not_planes", float(sum(
            e.get("matvec") != FORM for e in sent)) if sent else 1.0)
        self.ctx.guarantee("window_fetches_not_one", float(sum(
            e.get("fetches") != 1 for e in sent)) if sent else 1.0)
        most = CYCLES * self.shape["restart"]
        self.ctx.guarantee("window_past_two_cycles", float(sum(
            not 1 <= e.get("iters_max", 0) <= most or "cycles_max" not in e
            for e in sent)) if sent else 1.0)
        self.ctx.say(f"{len(sent)} dispatches in the window; iters_max "
                     f"{sorted({e.get('iters_max', -1) for e in sent})}, "
                     "cycles_max "
                     f"{sorted(e.get('cycles_max', -1) for e in sent)}")
        # the text of the executable the window ran (the plan cache's own:
        # nothing is traced or compiled for it), whose op_names carry the
        # cycle's named scopes: what reducers/op_scope_share.py reads
        try:
            text = self._cached_program().compiled.as_text()
            events["program.hlo"] = [{"program": "jit_bucket_gmres",
                                      "text": text}]
        except Exception as e:  # noqa: BLE001 - the shares then read nothing
            self.ctx.say(f"no text of the compiled program: {e!r}")
