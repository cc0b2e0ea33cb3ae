"""Unified Pallas->XLA failover registry.

The one place failover state lives, for every site that tries a Pallas
kernel and has an XLA formulation to fall back on (today one:
``kernels/dia_spmv.cached_prepared_spmv``, kernel ``dia_spmv``):

* ``failed(kernel, obj)`` — is the Pallas path latched off for this
  (kernel, operator) pair? Checked at dispatch, one dict probe.
* ``handle(kernel, obj, e)`` — the shared failure ladder. On the TPU
  backend a Pallas error is an error: everything but an injected
  failure re-raises (a kernel that quietly gives way to a reference on
  the one platform it exists for hides the device). Off the TPU
  (CPU/interpret): classify the error (``classify_unavailable``'s
  vocabulary match), honor ``SPARSE_TPU_STRICT_PALLAS``, warn once, emit a
  consistent ``kernel.failover`` event + ``kernel.failovers`` metrics
  counter, and latch. Returns when the caller should take the XLA
  path; re-raises otherwise.
* ``maybe_inject(kernel)`` — the fault-injection hook: raises
  :class:`InjectedPallasFailure` when a ``fail:pallas`` clause fires
  (:mod:`.faults`), which then rides the exact production failover path.
* ``probe(kernel, obj, fn)`` — the reinstate hook: run a real kernel
  attempt; on success the latch clears and a ``kernel.reinstate`` event
  records the recovery, so a transiently-broken backend doesn't pay the
  XLA slow path for the rest of the process lifetime.

Entries keyed by an operator object are weak-ref finalized (same
discipline as ``sparse_tpu.plan_cache``) so the registry cannot leak or
resurrect state across object lifetimes.
"""

from __future__ import annotations

import os
import threading
import weakref

from ..telemetry import _metrics
from . import faults

__all__ = [
    "InjectedPallasFailure",
    "classify_unavailable",
    "clear",
    "failed",
    "handle",
    "latches",
    "mark_failed",
    "maybe_inject",
    "probe",
    "reinstate",
    "snapshot",
    "strict",
]

_LOCK = threading.RLock()
# (kernel, id(obj) or 0) -> error repr
_FAILED: dict = {}
_FINALIZERS: dict = {}

_FAILOVERS = _metrics.counter("kernel.failovers")
_REINSTATES = _metrics.counter("kernel.reinstates")


class InjectedPallasFailure(NotImplementedError):
    """A forced Pallas launch failure from the fault injector. Subclasses
    ``NotImplementedError`` so every existing failover handler treats it
    as the canonical lowering-unavailable signal (strict mode included —
    an injected failure must exercise the production failover, not the
    strict re-raise)."""


def _key(kernel: str, obj) -> tuple:
    return (kernel, 0 if obj is None else id(obj))


def _finalize_obj(oid: int) -> None:
    with _LOCK:
        for k in [k for k in _FAILED if k[1] == oid]:
            del _FAILED[k]
        _FINALIZERS.pop(oid, None)


def strict() -> bool:
    """``SPARSE_TPU_STRICT_PALLAS``: pattern-matched ``ValueError``s
    re-raise instead of failing over (this repo's CI default — see
    tests/conftest.py)."""
    return bool(os.environ.get("SPARSE_TPU_STRICT_PALLAS"))


def failed(kernel: str, obj=None) -> bool:
    """True when the Pallas path is latched off for ``(kernel, obj)``
    (or kernel-wide with ``obj=None``)."""
    with _LOCK:
        return _key(kernel, obj) in _FAILED or (kernel, 0) in _FAILED


def mark_failed(kernel: str, obj=None, error: str = "") -> None:
    """Latch the Pallas path off and record the consistent failover
    telemetry (``kernel.failover`` event + ``kernel.failovers`` metrics
    counter). Idempotent per (kernel, obj)."""
    import jax

    key = _key(kernel, obj)
    with _LOCK:
        fresh = key not in _FAILED
        _FAILED[key] = error
        if obj is not None and id(obj) not in _FINALIZERS:
            try:
                _FINALIZERS[id(obj)] = weakref.finalize(
                    obj, _finalize_obj, id(obj)
                )
            except TypeError:
                pass  # un-weakref-able key: entry lives for the process
    if not fresh:
        return
    _FAILOVERS.inc()
    _metrics.counter("kernel.failovers.by_kernel", kernel=kernel).inc()
    from ..config import settings

    if settings.telemetry:
        from .. import telemetry

        telemetry.record(
            "kernel.failover", kernel=kernel, error=error[:200],
            backend=jax.default_backend(),
        )


def reinstate(kernel: str, obj=None) -> bool:
    """Clear the latch (the probe hook's success path); returns whether
    anything was latched. Emits ``kernel.reinstate``."""
    with _LOCK:
        had = _FAILED.pop(_key(kernel, obj), None) is not None
        # an obj-level reinstate also clears a kernel-wide latch: the
        # probe proved the kernel lowers on this backend again
        if obj is not None:
            had = (_FAILED.pop((kernel, 0), None) is not None) or had
    if had:
        _REINSTATES.inc()
        from ..config import settings

        if settings.telemetry:
            from .. import telemetry

            telemetry.record("kernel.reinstate", kernel=kernel)
    return had


def probe(kernel: str, obj, probe_fn) -> bool:
    """Probe-based reinstate: run one real kernel attempt (``probe_fn``,
    zero-arg). Success clears the latch and returns True; any exception
    leaves the latch in place and returns False (the probe is the safe
    place to fail)."""
    try:
        probe_fn()
    except Exception:
        return False
    reinstate(kernel, obj)
    return True


def maybe_inject(kernel: str) -> None:
    """Raise :class:`InjectedPallasFailure` when a ``fail:pallas`` fault
    clause fires for ``kernel`` (no-op otherwise; one boolean read when
    injection is inactive)."""
    if faults.ACTIVE and faults.should_fail_pallas(kernel):
        raise InjectedPallasFailure(
            f"injected Pallas launch failure for kernel {kernel!r}"
        )


def classify_unavailable(e: Exception) -> bool:
    """Backend-aware classification of a Pallas error as
    lowering-unavailable (failover-eligible) vs a genuine caller/kernel
    bug (must re-raise): on the TPU backend nothing but an injected
    failure is benign; off-TPU any lowering-availability wording (or a
    bare ``NotImplementedError``) qualifies."""
    import jax

    if isinstance(e, InjectedPallasFailure):
        return True
    if jax.default_backend() == "tpu":
        return False
    msg = str(e).lower()
    return isinstance(e, NotImplementedError) or any(
        s in msg
        for s in (
            "interpret mode",
            "lowering",
            "not implemented",
            "unsupported backend",
            "unimplemented",
            "mosaic",
        )
    )


def handle(kernel: str, obj, e: Exception) -> None:
    """The shared failover ladder for a caught Pallas error.

    On the TPU backend anything but an :class:`InjectedPallasFailure`
    re-raises; elsewhere so does an error :func:`classify_unavailable`
    does not know as lowering-unavailable (a Mosaic compile regression
    stays LOUD). Strict mode re-raises pattern-matched ``ValueError``s; a
    bare ``NotImplementedError`` (including injected failures) always
    takes the failover. On return the caller takes the XLA path;
    otherwise this re-raises ``e``.
    """
    if not classify_unavailable(e):
        raise e
    if strict() and not isinstance(e, NotImplementedError):
        raise e
    from ..utils import user_warning

    user_warning(
        f"Pallas kernel {kernel!r} unavailable; failing over to the XLA "
        f"formulation for this operator: {e!r}"
    )
    mark_failed(kernel, obj, error=repr(e))


def snapshot() -> dict:
    """Current latches: ``{(kernel, keyed): error}`` with ``keyed`` the
    object id (0 = kernel-wide) — introspection/debugging surface."""
    with _LOCK:
        return {f"{k}[{oid or '*'}]": err for (k, oid), err in _FAILED.items()}


def latches() -> dict:
    """JSON-friendly per-kernel latch view for serving surfaces
    (``/healthz``): ``{kernel: {"scoped": n per-operator latches,
    "kernel_wide": bool, "error": the most recent latch's error}}`` —
    operator ids stay internal (they are meaningless across processes
    and would churn every scrape)."""
    with _LOCK:
        items = list(_FAILED.items())
    out: dict = {}
    for (kernel, oid), err in items:
        st = out.setdefault(
            kernel, {"scoped": 0, "kernel_wide": False, "error": ""}
        )
        if oid == 0:
            st["kernel_wide"] = True
        else:
            st["scoped"] += 1
        st["error"] = str(err)[:200]
    return out


def clear() -> None:
    """Drop every latch (tests)."""
    with _LOCK:
        _FAILED.clear()
        for f in _FINALIZERS.values():
            try:
                f.detach()
            except Exception:
                pass
        _FINALIZERS.clear()
