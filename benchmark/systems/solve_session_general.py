"""System under test: a ``SolveSession`` serving time-stepping clients on a
pattern that is no stencil.

``systems/solve_session.py``'s adaptor (loaded by path, subclassed, neither
copied nor edited: the same submit, kick, wait and answer) plus the path this
configuration guarantees. Nothing here or in the configuration chooses the
bucket program's product: the session takes it from the pattern
(``sparse_tpu.batch.operator.pattern_matvec``), and for this pattern it has
to come out as the gather form, ``sell``. That is asked of the pattern's pack
once the window has closed (the pack the ramp's first dispatch built, found
again in the plan cache) and, in a traced run, of every ``batch.dispatch``
event of the window. A run whose product is planes or anything else is not
``correct``.

The configuration also guarantees that every bucket ends at the masked
loop's first convergence test (``conv_test_iters`` iterations): a bucket that
needed a second block would double the period. Every answer taken has to
report that count, and in a traced run every ``batch.dispatch`` of the window
has to carry it as ``iters_max``."""

from __future__ import annotations

import manifest

_base = manifest.load_module("systems", "solve_session")

FORM = "sell"


class System(_base.System):
    def reseed(self, data) -> None:
        super().reseed(data)
        self.iters: set = set()
        self.block = int(self.ses.conv_test_iters)

    def answer(self, ticket) -> dict:
        a = super().answer(ticket)
        self.iters.add(a["iters"])
        return a

    def close(self):
        from sparse_tpu.batch.operator import pattern_matvec

        # what the session's builder asked when it built the bucket program
        # in the ramp; a hit in the plan cache now, so nothing is packed here
        form = getattr(pattern_matvec(self.pattern)[0], "form", None)
        self.ctx.say(f"the pattern's bucket product: {form}; iterations of "
                     f"the answers taken: {sorted(self.iters)}")
        self.ctx.guarantee("session_matvec_not_sell",
                           0.0 if form == FORM else 1.0)
        self.ctx.guarantee("answers_past_first_test",
                           float(self.iters != {self.block}))
        super().close()

    def check_events(self, events: dict) -> None:
        """A traced run records the window's ``batch.dispatch`` events: each
        of them has to name the gather form and the first test's iteration
        count, and there has to be one."""
        sent = events.get("batch.dispatch", [])
        off = [e for e in sent if e.get("matvec") != FORM]
        self.ctx.guarantee("window_matvec_not_sell",
                           float(len(off)) if sent else 1.0)
        late = [e for e in sent if e.get("iters_max") != self.block]
        self.ctx.guarantee("window_dispatch_past_first_test",
                           float(len(late)) if sent else 1.0)
