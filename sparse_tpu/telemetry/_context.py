"""Request-scoped trace context: ticket ids propagated through events.

The serving path (``batch.SolveSession``) answers many callers over one
event stream; without a request id the stream answers "what happened"
but not "what happened to MY solve". This module is the propagation
substrate: every submitted system gets a process-unique *ticket id*
(``new_ticket_id()``), the session enters a :func:`ticket_scope` around
each dispatch AND around each deferred retire (streaming dispatch
splits the two — the launch's pack/compile events and the retire's
``batch.dispatch``/requeue/terminal events carry the same lanes'
ids), and the recorder (``_recorder.record``) stamps every event
emitted inside the scope with the active ids — so a
``kernel.failover`` five layers down in a Pallas wrapper carries the
tickets whose solve it degraded, without any layer in between knowing
tickets exist.

Design rules:

* **contextvars, not globals.** The scope nests correctly across the
  requeue path (a fallback dispatch re-enters with just the requeued
  lanes' ids) and stays correct if a session is ever driven from
  multiple threads — each thread/task sees its own stack.
* **Replace semantics.** Entering a scope *replaces* the active id set
  rather than appending: a requeue dispatch is attributed to the lanes
  it actually solves, not the whole original bucket.
* **Zero overhead when telemetry is off.** The only reader is
  ``record()``, which is already gated on ``settings.telemetry``; the
  scope itself is two contextvar operations and only the instrumented
  serving path enters it.
* **Explicit fields win.** An event that already carries ``ticket`` or
  ``tickets`` is never overwritten — call sites that know the exact
  lanes (``batch.requeue``, ``batch.deadline``) stay authoritative.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import threading

# ticket ids are process-unique and sortable: tk-<pid%0x10000 hex>-<seq>.
# The pid fragment keeps ids distinct when worker subprocesses append
# to the SAME records.jsonl as the parent.
_SEQ = itertools.count(1)
_SEQ_LOCK = threading.Lock()
_PREFIX = f"tk-{os.getpid() % 0x10000:04x}"

_TICKETS: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "sparse_tpu_tickets", default=()
)


def new_ticket_id() -> str:
    """A fresh process-unique ticket id (``tk-<pid>-<n>``)."""
    with _SEQ_LOCK:
        n = next(_SEQ)
    return f"{_PREFIX}-{n:06d}"


def current_tickets() -> tuple:
    """The active scope's ticket ids (empty tuple outside any scope)."""
    return _TICKETS.get()


@contextlib.contextmanager
def ticket_scope(*ids):
    """Make ``ids`` the active ticket set for the dynamic extent of the
    block (REPLACING any enclosing scope's ids — see module docstring).
    Events recorded inside gain a ``tickets`` field unless they carry
    their own. ``ticket_scope()`` with no ids clears the context."""
    token = _TICKETS.set(tuple(str(i) for i in ids))
    try:
        yield
    finally:
        _TICKETS.reset(token)


def annotate(ev: dict) -> dict:
    """Stamp the active ticket ids onto an event dict in place (the
    recorder's hook). Explicit ``ticket``/``tickets`` fields win; no
    allocation outside an active scope."""
    ids = _TICKETS.get()
    if ids and "tickets" not in ev and "ticket" not in ev:
        ev["tickets"] = list(ids)
    return ev
