"""Seeded, spec-driven fault injection for chaos-testing the solve stack.

The ROADMAP north star is production traffic; production solves meet
NaN-producing operator data, kernels whose backend lowering vanishes,
stragglers, and preemption. This module makes every one of those failure
modes *reproducible on demand* so the recovery machinery
(:mod:`.policy`, :mod:`.failover`, the resilient
:class:`~sparse_tpu.batch.service.SolveSession`) can be exercised in CI
instead of discovered in an incident.

Faults are described by ``SPARSE_TPU_FAULTS`` (``settings.faults``), a
semicolon-separated list of clauses::

    fault:site[:key=value[,key=value...]]

    nonfinite:matvec:p=0.01,seed=7     # NaN-poison matvec outputs
    inf:matvec:p=0.005                 # Inf instead of NaN
    bitflip:matvec:p=0.01,scale=1e18   # scale one element (bitflip-like)
    fail:pallas                        # force Pallas launch failure
    fail:pallas:kernel=dia_spmv,n=1    # ...for one kernel, first try only
    drop:dispatch:p=0.5                # SolveSession dispatch failure
    delay:dispatch:ms=25               # dispatch latency injection
    preempt:chunk:p=0.1,seed=3         # preemption at chunk boundaries
    shrink:mesh:to=4                   # serving mesh forged down to 4
    swap:mesh                          # same-size mesh, devices replaced
    flap:mesh:n=6                      # topology toggles per disruption
    truncate:io:p=0.5                  # vault write survives torn/half
    bitflip:io:p=0.1,seed=5            # flip one byte on artifact read
    stale:io                           # write with an outdated format
    enospc:io:n=1                      # artifact write hits ENOSPC

Each clause fires with probability ``p`` (default 1) from its own seeded
``numpy`` Generator (``seed``, default 0) so a chaos run is bit-for-bit
repeatable; ``n=`` bounds the total number of fires. Every fire bumps
the always-on ``faults.injected`` metrics counter and (telemetry
enabled) emits a ``fault.injected`` event — the head of the
``fault.injected -> solver.retry -> solver.recovered`` chains
``scripts/chaos_check.py`` asserts.

**Zero overhead / zero code-path change when unset.** Every hook in the
library is gated on the module-level :data:`ACTIVE` boolean (a single
attribute read, host-side only); the matvec corruption wrapper is only
*installed* when a matvec clause is active, so with the env unset the
traced solver programs are byte-identical to a build without this
module (``tests/test_resilience.py`` pins jaxpr equality and the
host-sync count).
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field

import numpy as np

from ..config import settings
from ..telemetry import _metrics

__all__ = [
    "ACTIVE",
    "FaultClause",
    "FaultSpecError",
    "InjectedMeshFailure",
    "Preempted",
    "TopologyError",
    "active",
    "check_preempt",
    "clear",
    "configure",
    "corrupt_array",
    "corrupt_traced",
    "dispatch_actions",
    "io_actions",
    "is_topology_error",
    "mesh_disrupt",
    "mesh_view",
    "parse_spec",
    "reload_from_env",
    "should_fail_pallas",
    "stats",
    "suspended",
    "targets",
    "wrap_batched_matvec",
    "wrap_precond",
]

#: site -> admissible faults (the grammar's type table)
SITES = {
    "matvec": ("nonfinite", "inf", "bitflip"),
    # preconditioner application (sparse_tpu.precond): same corruption
    # grammar as matvec, but the wrapper installs INSIDE the M apply —
    # so the chaos drills can corrupt the preconditioner while the
    # operator stays pristine (the recovery ladder's drop-preconditioner
    # rung, docs/resilience.md)
    "precond": ("nonfinite", "inf", "bitflip"),
    "pallas": ("fail",),
    "dispatch": ("drop", "delay"),
    "chunk": ("preempt",),
    # persistent plan-cache tier (sparse_tpu.vault): disk failure modes.
    # Write path: truncate (torn write left on disk), stale (artifact
    # from an outdated format), enospc (OSError at write). Read path:
    # bitflip (one corrupted byte). Every one must quarantine + rebuild,
    # never crash or mis-serve (docs/resilience.md).
    "io": ("truncate", "bitflip", "stale", "enospc"),
    # serving-mesh topology (sparse_tpu.fleet.elastic): forge a
    # deterministic topology change on the forced CPU mesh so the
    # elastic-mesh path (detect -> quiesce -> migrate -> re-plan) is
    # drillable in CI. ``shrink:mesh:to=4`` — the forged world lost
    # devices (default: half the mesh); ``swap:mesh`` — same count,
    # different physical devices (a slice replacement); ``flap:mesh`` —
    # the topology toggles between shrunk and original on every
    # disruption, the flap-guard drill (docs/resilience.md "Elastic
    # topology").
    "mesh": ("shrink", "swap", "flap"),
}

#: which io faults apply on which half of the artifact IO path
_IO_WRITE_FAULTS = ("truncate", "stale", "enospc")
_IO_READ_FAULTS = ("bitflip",)

_INJECTED = _metrics.counter("faults.injected")

#: module-level hot-path gate: True iff an injector is configured.
#: Library hooks read this one attribute and do nothing else when False.
ACTIVE = False

_LOCK = threading.RLock()
_INJECTOR = None
_SUSPEND = 0  # >0: injection temporarily disabled (policy verification)


class FaultSpecError(ValueError):
    """A ``SPARSE_TPU_FAULTS`` clause that does not parse/validate."""


class Preempted(RuntimeError):
    """Raised by :func:`check_preempt` at a chunk boundary — the injected
    analog of the process being preempted mid-solve. Recovery drivers
    (``resilience.policy``) catch it and resume from the last
    checkpoint/iterate."""


class TopologyError(RuntimeError):
    """A failure attributable to the device topology itself — a lost
    slice, a replaced device, a mesh the program was compiled for that
    no longer exists. The classification the elastic-mesh machinery
    (``fleet/elastic.py``, the recovery ladder's ``remesh`` rung) keys
    off, as distinct from numeric failures."""


class InjectedMeshFailure(TopologyError):
    """A ``mesh``-site fault clause fired (:func:`mesh_disrupt`) — the
    injected stand-in for a dispatch lost to a topology change."""


#: substrings that mark a backend error as topology-caused; deliberately
#: narrow — a mis-classified numeric failure would spend a remesh where
#: a solver escalation was owed
_TOPOLOGY_MARKERS = (
    "topology changed", "slice lost", "device unavailable",
    "device failure", "data_loss", "mesh mismatch",
)


def is_topology_error(exc) -> bool:
    """Classify an exception as a device/topology failure (vs numeric):
    the :class:`TopologyError` family, or a backend ``RuntimeError``/
    ``OSError`` carrying one of the known topology markers. The gate
    ahead of the recovery ladder's ``remesh`` rung and the session's
    dispatch-error revalidation."""
    if isinstance(exc, TopologyError):
        return True
    if isinstance(exc, (RuntimeError, OSError)):
        msg = str(exc).lower()
        return any(m in msg for m in _TOPOLOGY_MARKERS)
    return False


@dataclass(frozen=True)
class FaultClause:
    """One parsed clause of the fault spec."""

    fault: str
    site: str
    p: float = 1.0
    seed: int = 0
    kernel: str | None = None  # pallas clauses: restrict to one kernel name
    scale: float = 1e18  # bitflip multiplier
    ms: float = 10.0  # delay duration
    n: int | None = None  # max total fires (None = unbounded)
    extras: tuple = field(default_factory=tuple)

    def describe(self) -> str:
        opts = [f"p={self.p:g}", f"seed={self.seed}"]
        if self.kernel:
            opts.append(f"kernel={self.kernel}")
        if self.n is not None:
            opts.append(f"n={self.n}")
        return f"{self.fault}:{self.site}:" + ",".join(opts)


def parse_spec(spec: str) -> tuple:
    """Parse a ``SPARSE_TPU_FAULTS`` string into clauses (see module doc).

    Raises :class:`FaultSpecError` on unknown sites/faults, site/fault
    mismatches, or malformed options — a chaos run with a typo'd spec
    must fail loudly, not silently inject nothing.
    """
    clauses = []
    for raw in str(spec).split(";"):
        raw = raw.strip()
        if not raw:
            continue
        parts = raw.split(":", 2)
        if len(parts) < 2:
            raise FaultSpecError(
                f"clause {raw!r}: expected fault:site[:options]"
            )
        fault, site = parts[0].strip().lower(), parts[1].strip().lower()
        if site not in SITES:
            raise FaultSpecError(
                f"clause {raw!r}: unknown site {site!r} "
                f"(one of {sorted(SITES)})"
            )
        if fault not in SITES[site]:
            raise FaultSpecError(
                f"clause {raw!r}: fault {fault!r} not valid for site "
                f"{site!r} (one of {SITES[site]})"
            )
        kw: dict = {}
        extras = []
        if len(parts) == 3 and parts[2].strip():
            for opt in parts[2].split(","):
                opt = opt.strip()
                if not opt:
                    continue
                if "=" not in opt:
                    raise FaultSpecError(
                        f"clause {raw!r}: option {opt!r} is not key=value"
                    )
                k, v = (s.strip() for s in opt.split("=", 1))
                try:
                    if k == "p":
                        kw["p"] = float(v)
                    elif k == "seed":
                        kw["seed"] = int(v)
                    elif k == "kernel":
                        kw["kernel"] = v
                    elif k == "scale":
                        kw["scale"] = float(v)
                    elif k == "ms":
                        kw["ms"] = float(v)
                    elif k == "n":
                        kw["n"] = int(v)
                    else:
                        extras.append((k, v))
                except ValueError as e:
                    raise FaultSpecError(
                        f"clause {raw!r}: bad value for {k!r}: {v!r}"
                    ) from e
        p = kw.get("p", 1.0)
        if not (0.0 <= p <= 1.0):
            raise FaultSpecError(f"clause {raw!r}: p={p} outside [0, 1]")
        clauses.append(
            FaultClause(fault=fault, site=site, extras=tuple(extras), **kw)
        )
    return tuple(clauses)


class _Injector:
    """Clause set + per-clause seeded RNGs and fire budgets."""

    def __init__(self, clauses):
        self.clauses = tuple(clauses)
        self._rngs = [np.random.default_rng(c.seed) for c in clauses]
        self._fires = [0] * len(clauses)
        self.by_site: dict = {}
        for i, c in enumerate(clauses):
            self.by_site.setdefault(c.site, []).append(i)

    def _draw(self, i: int) -> bool:
        """One Bernoulli draw for clause ``i`` honoring its fire budget.
        The RNG always advances (determinism does not depend on budget
        state), the budget only gates whether the fire takes effect."""
        c = self.clauses[i]
        hit = bool(self._rngs[i].random() < c.p)
        if not hit:
            return False
        if c.n is not None and self._fires[i] >= c.n:
            return False
        self._fires[i] += 1
        return True

    def stats(self) -> dict:
        return {
            c.describe(): f for c, f in zip(self.clauses, self._fires)
        }


def _record_fire(clause: FaultClause, **extra) -> None:
    _INJECTED.inc()
    _metrics.counter(
        "faults.injected.by_site", site=clause.site, fault=clause.fault
    ).inc()
    if settings.telemetry:
        from .. import telemetry

        telemetry.record(
            "fault.injected", site=clause.site, fault=clause.fault, **extra
        )


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------
def configure(spec: str | None) -> None:
    """Install an injector from a spec string (tests / chaos drivers).
    ``None``/empty clears injection entirely."""
    global _INJECTOR, ACTIVE
    with _LOCK:
        if not spec:
            _INJECTOR = None
            ACTIVE = False
            return
        _INJECTOR = _Injector(parse_spec(spec))
        ACTIVE = True


def clear() -> None:
    """Remove all fault injection (hooks go back to their one-boolean
    disabled path)."""
    configure(None)


def reload_from_env() -> None:
    """Re-read ``SPARSE_TPU_FAULTS`` from the environment (the settings
    object caches env at import; tests monkeypatching the env call this)."""
    import os

    configure(os.environ.get("SPARSE_TPU_FAULTS", ""))


def active() -> bool:
    return ACTIVE


def targets(site: str) -> bool:
    """True when a clause targets ``site`` — the hook-installation gate
    (e.g. the matvec wrapper only exists when ``targets('matvec')``)."""
    inj = _INJECTOR
    return bool(inj and site in inj.by_site)


@contextlib.contextmanager
def suspended():
    """Temporarily disable every injection (depth-counted). The recovery
    policy verifies residuals under this guard so a verification matvec
    through a fault-wrapped operator is pristine."""
    global _SUSPEND
    with _LOCK:
        _SUSPEND += 1
    try:
        yield
    finally:
        with _LOCK:
            _SUSPEND -= 1


def stats() -> dict:
    """Per-clause fire counts (``{clause-description: fires}``)."""
    inj = _INJECTOR
    return inj.stats() if inj else {}


# ---------------------------------------------------------------------------
# injection points
# ---------------------------------------------------------------------------
def corrupt_array(a: np.ndarray, site: str = "matvec") -> np.ndarray:
    """Host-side corruption of one array per the active matvec clauses
    (NaN / Inf / scale-one-element). Returns the (possibly copied) array;
    the input is never mutated in place."""
    inj = _INJECTOR
    if inj is None or _SUSPEND > 0:
        return a
    out = a
    for i in inj.by_site.get(site, ()):
        c = inj.clauses[i]
        with _LOCK:
            fire = inj._draw(i)
            if not fire:
                continue
            idx = int(inj._rngs[i].integers(max(out.size, 1)))
        if out is a:
            out = np.array(a, copy=True)
        if out.size == 0:
            continue
        if c.fault == "nonfinite":
            out.flat[idx] = np.nan
        elif c.fault == "inf":
            out.flat[idx] = np.inf
        elif c.fault == "bitflip":
            out.flat[idx] = out.flat[idx] * c.scale
        _record_fire(c, index=idx, size=int(out.size))
    return out


def corrupt_traced(y, site: str = "matvec"):
    """Trace-safe corruption of a device array: routes through
    ``jax.pure_callback`` so the seeded host RNG decides per *execution*
    (works inside ``lax.while_loop`` bodies on the CPU backend, where
    chaos runs live). Only ever called from wrappers that are installed
    when a matvec clause is active — never present in clean traces."""
    import jax

    def _cb(a):
        return corrupt_array(np.asarray(a), site=site)

    return jax.pure_callback(
        _cb, jax.ShapeDtypeStruct(y.shape, y.dtype), y
    )


def wrap_batched_matvec(mv):
    """Wrap a batched ``(B, n) -> (B, m)`` matvec with output corruption
    (the hook :mod:`sparse_tpu.batch.krylov` installs when active)."""

    def faulty_mv(X):
        return corrupt_traced(mv(X), site="matvec")

    faulty_mv._fault_wrapped = True
    return faulty_mv


def wrap_precond(mvec):
    """Wrap a preconditioner apply (batched ``(B, n) -> (B, n)``, or
    unbatched ``(n,) -> (n,)``) with output corruption — the hook
    :mod:`sparse_tpu.precond` installs when a ``precond`` clause is
    active. Distinct from the matvec site so a drill can poison M while
    A stays pristine."""

    def faulty_apply(R):
        return corrupt_traced(mvec(R), site="precond")

    faulty_apply._fault_wrapped = True
    return faulty_apply


def should_fail_pallas(kernel: str) -> bool:
    """Draw the forced-Pallas-failure clauses for ``kernel``; a fire is
    recorded here (the failover site raises and emits the matching
    ``kernel.failover``)."""
    inj = _INJECTOR
    if inj is None or _SUSPEND > 0:
        return False
    for i in inj.by_site.get("pallas", ()):
        c = inj.clauses[i]
        if c.kernel is not None and c.kernel != kernel:
            continue
        with _LOCK:
            fire = inj._draw(i)
        if fire:
            _record_fire(c, kernel=kernel)
            return True
    return False


def dispatch_actions() -> list:
    """Actions for one SolveSession dispatch: ``[("drop",)]`` and/or
    ``[("delay", ms)]`` per the active dispatch clauses (a fired drop is
    recorded here; the session raises its injected dispatch failure)."""
    inj = _INJECTOR
    if inj is None or _SUSPEND > 0:
        return []
    acts = []
    for i in inj.by_site.get("dispatch", ()):
        c = inj.clauses[i]
        with _LOCK:
            fire = inj._draw(i)
        if not fire:
            continue
        if c.fault == "drop":
            _record_fire(c)
            acts.append(("drop",))
        elif c.fault == "delay":
            _record_fire(c, ms=c.ms)
            acts.append(("delay", c.ms))
    return acts


def _mesh_to(c: FaultClause) -> int | None:
    """The ``to=`` option of a mesh clause (rides the extras path —
    ``to`` is grammar only this site understands). ``None`` = the
    consumer's default (half the current mesh)."""
    for k, v in c.extras:
        if k == "to":
            try:
                return int(v)
            except ValueError as e:
                raise FaultSpecError(
                    f"mesh clause: bad value for 'to': {v!r}"
                ) from e
    return None


def mesh_view():
    """The forged topology the active mesh clause currently presents,
    WITHOUT consuming a fire: ``None`` when no mesh clause is live, else
    ``(kind, to)`` — ``('shrink', n)`` for a world that lost devices,
    ``('swap', None)`` for same-count replaced devices, ``('none',
    None)`` for a flap clause currently back on the original topology.
    Deterministic and idempotent: the session's :class:`~sparse_tpu.
    fleet.elastic.MeshMonitor` polls this to decide whether the forged
    world differs from the mesh it is serving on; only when it does is
    a fire consumed (:func:`mesh_disrupt`). A flap clause alternates
    its view on the clause's fire parity — each consumed disruption
    toggles the forged world, so remeshes ping-pong until the flap
    guard latches."""
    inj = _INJECTOR
    if inj is None or _SUSPEND > 0:
        return None
    for i in inj.by_site.get("mesh", ()):
        c = inj.clauses[i]
        if c.n is not None and inj._fires[i] >= c.n:
            continue  # budget spent: the forged world is gone
        if c.fault == "shrink":
            return ("shrink", _mesh_to(c))
        if c.fault == "swap":
            return ("swap", None)
        if c.fault == "flap":
            return (
                ("shrink", _mesh_to(c)) if inj._fires[i] % 2 == 0
                else ("none", None)
            )
    return None


def mesh_disrupt():
    """Consume one mesh-site fire: the budget-counted draw behind a
    topology disruption (the session raises its
    :class:`InjectedMeshFailure` / migrates on a fired draw). Returns
    the clause's ``(kind, to)`` directive or ``None``. Call only after
    :func:`mesh_view` said the forged world differs from the serving
    mesh — a remeshed session whose mesh already matches the forged
    topology draws nothing, so fire counts equal actual disruptions."""
    inj = _INJECTOR
    if inj is None or _SUSPEND > 0:
        return None
    for i in inj.by_site.get("mesh", ()):
        c = inj.clauses[i]
        with _LOCK:
            fire = inj._draw(i)
        if not fire:
            continue
        to = _mesh_to(c)
        _record_fire(c, **({"to": to} if to is not None else {}))
        if c.fault == "flap":
            # the fire just consumed toggled the forged world; report
            # the view the session must now migrate TO
            return (
                ("shrink", to) if (inj._fires[i] - 1) % 2 == 0
                else ("none", None)
            )
        return (c.fault, to)
    return None


def io_actions(op: str) -> list:
    """Fired ``io``-site actions for one vault operation; ``op`` is
    ``'write'`` or ``'read'``. Returns ``[(fault, frac), ...]`` where
    ``frac`` (bitflip only) positions the flipped byte as a fraction of
    the blob length — drawn from the clause's seeded RNG so a chaos run
    corrupts the same byte every time."""
    inj = _INJECTOR
    if inj is None or _SUSPEND > 0:
        return []
    admissible = _IO_WRITE_FAULTS if op == "write" else _IO_READ_FAULTS
    acts = []
    for i in inj.by_site.get("io", ()):
        c = inj.clauses[i]
        if c.fault not in admissible:
            continue
        with _LOCK:
            fire = inj._draw(i)
            frac = (
                float(inj._rngs[i].random()) if fire and c.fault == "bitflip"
                else 0.0
            )
        if not fire:
            continue
        _record_fire(c, op=op)
        acts.append((c.fault, frac))
    return acts


def check_preempt(where: str) -> None:
    """Raise :class:`Preempted` when a chunk-boundary preemption clause
    fires (called from the host chunk loops: ``checkpointed_cg``,
    ``linalg._try_fused_cg``)."""
    inj = _INJECTOR
    if inj is None or _SUSPEND > 0:
        return
    for i in inj.by_site.get("chunk", ()):
        c = inj.clauses[i]
        with _LOCK:
            fire = inj._draw(i)
        if fire:
            _record_fire(c, where=where)
            raise Preempted(f"injected preemption at {where}")


# env-configured at import so `SPARSE_TPU_FAULTS=... python app.py` needs
# no code changes anywhere
if settings.faults:
    configure(settings.faults)
