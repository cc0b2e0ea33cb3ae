"""Vault: the crash-safe persistent tier of the plan cache (ISSUE 9).

A cold process pays a serving tax (a CPU reading of the measuring
script the tree had until PR 45: 16x cold vs ~109x warm over the
sequential loop) — everything between those numbers is SELL
packs, DIA preps and per-bucket compiles a fresh process re-derives
from scratch. The vault persists those prepared artifacts across
processes (ROADMAP item 4's second cache tier), and treats persistence
as a *robustness* feature: a server killed mid-traffic comes back warm
by replaying the warm-start manifest, and no corrupt, truncated or
stale on-disk artifact can ever crash or mis-serve the process — every
read is verify-then-load, every failure quarantines and degrades to a
rebuild (docs/performance.md for the layout and operational recipe,
docs/resilience.md for the failure contract and the ``io:*`` chaos
grammar).

Layout under ``SPARSE_TPU_VAULT=<dir>``::

    objects/<kind>/<content-key>.stv   verified artifacts (_store.py)
    manifest.json                      warm-start manifest (_manifest.py)
    quarantine/                        failed-verification sidecar
    tmp/                               per-process atomic-write staging

Integration points:

* ``plan_cache.get(..., vault_kind=, vault_key=)`` — the two-tier read
  path: in-process weak-ref LRU first, then this disk tier
  (``plan_cache.stats()['disk_hits']``), then build + deposit.
* ``SolveSession(warm_start=...)`` — manifest replay on construction
  plus per-program noting at every bucket-program build.
* ``scripts/vault_gc.py`` / :func:`gc` — size-budgeted LRU sweep
  (``SPARSE_TPU_VAULT_CAP_MB``).
"""

from __future__ import annotations

from . import _codecs, _manifest, _store
from ._manifest import clear as clear_manifest  # noqa: F401
from ._manifest import entries as manifest_entries  # noqa: F401
from ._store import (  # noqa: F401
    FORMAT,
    MAGIC,
    SUFFIX,
    artifact_path,
    enabled,
    gc,
    load,
    quarantine,
    quarantine_dir,
    reset_stats,
    stats,
    store,
    vault_dir,
)

__all__ = [
    "artifact_path", "clear_manifest", "deposit", "enabled", "fetch",
    "gc", "load", "load_pattern", "manifest_entries", "note_program",
    "quarantine", "quarantine_dir", "reset_stats", "stats", "store",
    "store_pattern", "vault_dir",
]


def fetch(kind: str, key: str, expect: dict | None = None):
    """Decode one artifact through its registered codec; ``None`` on any
    miss/verify failure (the caller rebuilds)."""
    c = _codecs.codec(kind)
    if c is None:
        return None
    out = _store.load(kind, key, expect=expect)
    if out is None:
        return None
    meta, arrays = out
    try:
        return c[1](meta, arrays)
    except Exception:
        # decodable bytes that don't reconstruct (codec drift within one
        # format version) are corruption too: quarantine what we read
        _store.quarantine(_store.artifact_path(kind, key), "decode-error",
                          kind)
        return None


def deposit(kind: str, key: str, obj) -> bool:
    """Encode + persist one object through its registered codec;
    best-effort (False on any failure, never raises)."""
    c = _codecs.codec(kind)
    if c is None or not _store.enabled():
        return False
    try:
        meta, arrays = c[0](obj)
    except Exception:
        return False
    return _store.store(kind, key, meta, arrays)


# -- warm-start manifest helpers (SolveSession) -----------------------------
def store_pattern(pattern) -> str:
    """Persist a pattern's raw structure (idempotent); returns its key."""
    key = _codecs.pattern_key(pattern)
    import os

    if not os.path.exists(_store.artifact_path("pattern", key)):
        deposit("pattern", key, pattern)
    return key


def load_pattern(key: str):
    """The manifest replay's pattern loader: a verified
    ``SparsityPattern`` or ``None``."""
    if not key:
        return None
    return fetch("pattern", key)


def note_program(pattern, solver: str, bucket: int, dtype: str,
                 mesh: str | None = None,
                 strategy: str | None = None,
                 precond: str | None = None,
                 dtype_policy: str | None = None,
                 precond_dtype: str | None = None) -> None:
    """Record one freshly built bucket program in the warm-start
    manifest (and ensure its pattern artifact exists). Best-effort.

    ``mesh``/``strategy`` are the fleet tier's topology fingerprint and
    sharding strategy (ISSUE 10): a mesh-keyed entry only replays in a
    process whose serving mesh carries the SAME fingerprint — a restart
    on a different topology skips it (clean cold start) instead of
    compiling a program the new mesh cannot dispatch. ``None`` (the
    default) marks a single-device program, replayable anywhere.

    ``precond`` is the program's resolved preconditioner kind
    (ISSUE 14): recorded so the replay rebuilds the SAME precond-keyed
    program — its pattern-level maps load from their own vault artifact
    kinds, so a warm restart pays zero symbolic factorizations. ``None``
    (the default) marks an unpreconditioned program (pre-precond
    manifests stay valid).

    ``dtype_policy`` is the program's resolved mixed-precision policy
    (ISSUE 15): recorded so the replay rebuilds the SAME
    precision-keyed (``.P``-suffixed) program and a warm restart serves
    the reduced-precision fast path at zero plan-cache misses. ``None``
    (the default) marks an exact program (pre-mixed manifests stay
    valid).

    ``precond_dtype`` is the program's resolved preconditioner storage
    dtype (ISSUE 16): ``'storage'`` marks the compounding arm whose
    factors live at the reduced storage dtype (``.W``-suffixed key);
    ``None`` (the default) marks compute-dtype factors (pre-autopilot
    manifests stay valid)."""
    if not _store.enabled():
        return
    try:
        key = store_pattern(pattern)
        entry = {
            "pattern": key,
            "solver": solver,
            "bucket": int(bucket),
            "dtype": dtype,
            "n": int(pattern.shape[0]),
            "nnz": int(pattern.nnz),
        }
        if mesh:
            entry["mesh"] = str(mesh)
            entry["strategy"] = str(strategy or "batch")
        if precond:
            entry["precond"] = str(precond)
        if dtype_policy:
            entry["dtype_policy"] = str(dtype_policy)
        if precond_dtype:
            entry["precond_dtype"] = str(precond_dtype)
        _manifest.note(entry)
    except Exception:
        return
