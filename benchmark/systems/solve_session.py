"""System under test: a ``SolveSession`` serving time-stepping clients.

Session options come from the configuration's ``session`` group and nothing
else is set. Each client steps its own problem: ``submit`` sends the client's
next step (right-hand side ``carry`` times its last answer plus its source,
that answer as the starting iterate, tolerance relative to the right-hand
side), ``kick``
dispatches whole buckets without waiting, ``wait`` blocks on one ticket and so
drives the pipeline, as ``ticket.result()`` does for a caller, and ``answer``
takes the result and makes it the client's state."""

from __future__ import annotations

import numpy as np


class System:
    def __init__(self, cfg, data, ctx):
        from sparse_tpu.batch import service

        self._service = service
        self.ctx = ctx
        opts = dict(cfg["session"])
        self.solver = opts.pop("solver")
        with ctx.span("operator_build"):
            self.ses = service.SolveSession(self.solver, **opts)
            self.pattern = self.ses.pattern_of(data["pattern"])
        self.batch_max = int(self.ses.batch_max)
        self.shape = {"rows": data["rows"], "nnz": data["nnz"],
                      "lanes": self.batch_max}
        self.reseed(data)

    def reseed(self, data) -> None:
        """Another seed's clients on the same pattern, each at its initial
        condition."""
        self.data = data
        self.state = list(data["initial"])
        self.sent: dict = {}

    def submit(self, client: int):
        d = self.data
        u = self.state[client]
        b = np.float32(d["carry"]) * u + d["source"][client]
        tol = d["rel_tol"] * float(np.linalg.norm(b.astype(np.float64)))
        ticket = self.ses.submit(d["values"][client], b, tol=tol, x0=u,
                                 pattern=self.pattern)
        self.sent[id(ticket)] = (client, b)
        return ticket

    def kick(self) -> None:
        if self.ses.pending >= self.batch_max:
            self.ses.flush(wait=False)

    def wait(self, ticket) -> None:
        try:
            ticket.result()
        except self._service.TicketError:
            pass  # counted as failed by the loop through ``outcome``

    def outcome(self, ticket):
        """None while pending, else True for done and False for failed."""
        if ticket.done:
            return True
        return False if ticket.failed else None

    def drain(self) -> None:
        self.ses.drain()

    def warm(self):
        """Nothing: the closed loop's ramp, which is set-up, compiles the
        bucket program and fills the pipeline."""

    def answer(self, ticket) -> dict:
        x, iters, resid2 = ticket.result()
        client, b = self.sent.pop(id(ticket))
        x = np.asarray(x)
        self.state[client] = x
        return {"x": x, "b": b, "request": client, "iters": int(iters),
                "phase_ms": dict(ticket.phase_ms)}

    def close(self):
        self.ses = None
