"""Scoped wall-clock + device-sync timers (``span("cg.iter")``).

A live span is three things at once, under one name: a
``jax.profiler.TraceAnnotation`` for its extent (with a profile running it
is an event on the ``python3`` line of ``/host:CPU`` in the same
``.xplane.pb`` as the device's ops, on the same axis — the shared clock,
with nothing to align afterwards; with no profile running a level test), a
duration in the p50/p95 aggregates, and (``emit=True``) a ``span`` event
that carries its start ``t0`` on the events' ``tm`` axis next to ``dur_s``.
The trace carries the bare name; the fields live on the event.

Trace safety is the defining constraint: library code wraps hot paths
that are routinely re-entered under ``jit``/``vmap``/``scan`` tracing,
where (a) wall-clock around tracer ops measures trace construction, not
execution, and (b) a ``block_until_ready`` on a tracer raises. A span
therefore degrades to a shared no-op object whenever telemetry is
disabled OR a trace is active (``utils.in_trace``) — no allocation on
the disabled path, no tracer leaks on the traced path.

Device sync discipline: ``block_until_ready`` runs only at span exit and
only on values handed to the span (``sync=...``) — never injected into
the middle of user computations.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from ..config import settings
from . import _context, _metrics, _recorder

# Failed best-effort device syncs used to vanish silently (ISSUE 12
# satellite): a backend erroring inside block_until_ready is exactly the
# kind of degradation an operator should see. Always-on counter,
# surfaced on /healthz.
_SYNC_ERRORS = _metrics.counter(
    "telemetry.span_sync_errors",
    help="best-effort device syncs (span exit / device_sync) that "
    "raised — silent device errors surfacing",
)


class _NullSpan:
    """Shared disabled/traced span: every method is a no-op, and it holds
    no time (``t0``/``dur_s``/``t1`` are ``None``)."""

    __slots__ = ()
    t0 = dur_s = t1 = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **fields):
        return self

    def set_sync(self, value):
        return value


_NULL = _NullSpan()


class Span:
    """One timed scope. Use via :func:`span`; not constructed directly.

    ``t0`` (a reading of ``telemetry.clock``, set at entry) and ``dur_s``
    (set at exit) stay readable afterwards, so a caller that needs the
    instants for its own arithmetic reads them here and takes no
    timestamp of its own."""

    __slots__ = ("name", "fields", "t0", "dur_s", "_sync", "emit", "_ann")

    def __init__(self, name: str, fields: dict, sync, emit: bool):
        self.name = name
        self.fields = fields
        self._sync = sync
        self.emit = emit
        self.t0 = self.dur_s = None
        self._ann = TraceAnnotation(name)

    @property
    def t1(self):
        """The span's end on ``telemetry.clock`` (``None`` until exit)."""
        return None if self.dur_s is None else self.t0 + self.dur_s

    def annotate(self, **fields):
        """Attach fields to the span's event after entry (e.g. results
        computed inside the scope)."""
        self.fields.update(fields)
        return self

    def set_sync(self, value):
        """Register a device value to block on at span exit; returns the
        value unchanged so call sites stay expression-shaped."""
        self._sync = value
        return value

    def __enter__(self):
        _recorder.session_info()  # the tm base precedes the first start
        self._ann.__enter__()
        self.t0 = _recorder.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        # the device sync stays best-effort on BOTH paths: a span exiting
        # on an exception still blocks on work it registered (the timing
        # is recorded either way, with the exception type in `error`)
        if self._sync is not None:
            try:
                import jax

                jax.block_until_ready(self._sync)
            except Exception:
                # sync stays best-effort (the wall clock still stands),
                # but the failure is counted — see _SYNC_ERRORS
                _SYNC_ERRORS.inc()
        self.dur_s = dur = _recorder.clock() - self.t0
        self._ann.__exit__(exc_type, exc, tb)
        _recorder.add_span(self.name, dur)
        if self.emit:
            # a span times a scope of the program (a bucket, a solve, a
            # build), which its own fields identify: it is recorded
            # outside the ticket scope, so that a bucket's seven spans do
            # not each repeat its lanes' ids. ``batch.dispatch`` carries
            # the same ``seq`` and the tickets.
            with _context.ticket_scope():
                _recorder.record(
                    "span",
                    name=self.name,
                    t0=_recorder.tm_of(self.t0),
                    dur_s=round(dur, 9),
                    **({"error": exc_type.__name__} if exc_type else {}),
                    **self.fields,
                )
        return False


def span(name: str, sync=None, emit: bool = True, **fields):
    """Scoped timer: ``with span("cg.iter"): ...``.

    Returns a shared no-op context when telemetry is disabled or a jax
    trace is active (see module docstring). When live, annotates the
    profiler's trace with ``name``, records the duration into the
    p50/p95 aggregates and (``emit=True``) emits a ``span`` event.
    ``sync`` is an optional array/pytree blocked on at exit so device
    work attributes to the span rather than a later fence; pass
    ``emit=False`` for hot scopes that should aggregate (and annotate)
    without flooding the event log.
    """
    if not settings.telemetry:
        return _NULL
    from ..utils import in_trace

    if in_trace():
        return _NULL
    return Span(name, fields, sync, emit)


def device_sync(value):
    """Block on ``value`` when telemetry is enabled outside a trace —
    the free-standing boundary fence for code not using spans. Returns
    ``value`` unchanged; a pure pass-through when disabled/traced."""
    if not settings.telemetry:
        return value
    from ..utils import in_trace

    if in_trace():
        return value
    try:
        import jax

        jax.block_until_ready(value)
    except Exception:
        _SYNC_ERRORS.inc()
    return value
