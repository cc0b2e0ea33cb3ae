"""Warm-start manifest: the vault's record of hot serving programs.

One JSON file (``<vault>/manifest.json``) listing the
``(pattern-fingerprint, solver, bucket, dtype)`` bucket programs a
``SolveSession`` has built, most-recently-noted last. A fresh process
replays it on session construction (``SolveSession(warm_start=...)``):
each entry's pattern structure loads from its ``pattern`` artifact, the
SELL pack loads from the disk tier, and the bucket program re-builds /
re-compiles ahead of traffic (hitting jax's persistent compilation
cache, ``utils.enable_compilation_cache``) — so a killed server comes
back warm instead of paying its whole cold start on the first request.

Same trust model as artifacts: writes are atomic (tmp + fsync +
rename, per-process tmp names) and loads verify before use — a
checksum over the canonical entries JSON plus a format version. A
missing or empty manifest is a clean miss; a corrupt one is quarantined
(``vault.quarantine`` evidence) and replay degrades to nothing — a
fresh process can ALWAYS construct a session, warm or cold. Entries are
bounded (:data:`MANIFEST_KEEP`, LRU by note order); noting is
best-effort under concurrency (two servers sharing a vault may each
drop the other's freshest note; both files stay valid).
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import threading
import time

from . import _store

MANIFEST_FORMAT = 1
MANIFEST_KEEP = 64

_LOCK = threading.RLock()
_SEQ = itertools.count()


def path() -> str:
    return os.path.join(_store.vault_dir(), "manifest.json")


def _entries_checksum(entries: list) -> str:
    blob = json.dumps(entries, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def _entry_key(e: dict) -> tuple:
    # `mesh` is the fleet tier's topology fingerprint (ISSUE 10): the
    # same (pattern, solver, bucket, dtype) program compiled for a
    # different mesh is a DIFFERENT executable and must dedup separately
    # (absent == single-device, so pre-fleet manifests stay valid).
    # `precond` (ISSUE 14), `dtype_policy` (ISSUE 15) and
    # `precond_dtype` (ISSUE 16) extend the key the same back-compatible
    # way: absent == unpreconditioned / exact / compute-dtype factors,
    # and a precond-, precision- or storage-factor-keyed program dedups
    # apart from its plain sibling.
    return (e.get("pattern"), e.get("solver"), e.get("bucket"),
            e.get("dtype"), e.get("mesh"), e.get("precond"),
            e.get("dtype_policy"), e.get("precond_dtype"))


def entries() -> list:
    """Verified manifest entries, oldest first. Missing/empty file =>
    ``[]`` (a clean miss); invalid content => quarantine + ``[]``."""
    if not _store.enabled():
        return []
    p = path()
    try:
        with open(p, "rb") as f:
            raw = f.read()
    except OSError:
        return []
    if not raw.strip():
        return []  # empty manifest: a miss, not corruption
    try:
        doc = json.loads(raw.decode())
        if not isinstance(doc, dict):
            raise ValueError("manifest not a dict")
        if doc.get("format") != MANIFEST_FORMAT:
            raise ValueError("stale manifest format")
        ents = doc.get("entries")
        if not isinstance(ents, list):
            raise ValueError("entries not a list")
        if doc.get("sha256") != _entries_checksum(ents):
            raise ValueError("manifest checksum mismatch")
    except Exception:
        _store.quarantine(p, "manifest", "manifest")
        return []
    return [e for e in ents if isinstance(e, dict)]


def _write(ents: list) -> bool:
    import jax

    doc = {
        "format": MANIFEST_FORMAT,
        "jax": jax.__version__,
        "updated": time.time(),
        "entries": ents,
        "sha256": _entries_checksum(ents),
    }
    blob = json.dumps(doc, sort_keys=True, indent=1).encode() + b"\n"
    p = path()
    tmp = None
    try:
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with _LOCK:
            seq = next(_SEQ)
        tmp = f"{p}.{os.getpid()}.{seq}.tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, p)
        return True
    except Exception:
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        return False


def note(entry: dict) -> bool:
    """Upsert one program entry (dedup on pattern/solver/bucket/dtype,
    moved to the fresh end; bounded to :data:`MANIFEST_KEEP`). Atomic
    rewrite; best-effort — a failed note never raises."""
    if not _store.enabled():
        return False
    with _LOCK:
        ents = [e for e in entries() if _entry_key(e) != _entry_key(entry)]
        ents.append(dict(entry, noted=time.time()))
        return _write(ents[-MANIFEST_KEEP:])


def clear() -> None:
    try:
        os.unlink(path())
    except OSError:
        pass
