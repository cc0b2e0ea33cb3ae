"""On-disk artifact store: atomic writes, verify-then-load, quarantine.

The disk tier of the two-tier plan cache (``sparse_tpu.plan_cache``).
One artifact = one file under ``<vault>/objects/<kind>/<key>.stv``:

    MAGIC  header-JSON  "\\n"  payload (npz bytes)

The header carries the contract every load re-verifies *before* any
payload byte is interpreted: format version, the writing process's jax /
numpy versions, the artifact kind and key, the payload length and its
sha256. A verify failure of ANY step — bad magic, unparseable header,
stale format/jax, key mismatch, truncation, checksum, npz decode, or an
``expect=`` field mismatch — NEVER raises into the caller: the file is
moved into ``<vault>/quarantine/`` (bounded; oldest pruned), counted
(``vault.verify_failed`` / ``vault.quarantined``), optionally recorded
(``vault.quarantine`` event), and the load returns ``None`` — a miss the
caller answers by rebuilding. Worst case is recompute, never a crash or
a wrong artifact.

Writes are crash-safe and concurrency-safe: the blob lands in
``<vault>/tmp/<name>.<pid>.<seq>.tmp`` (per-process names — concurrent
servers sharing a vault never collide), is flushed + fsync'd, then
``os.replace``'d into place (atomic on POSIX; readers see the old file
or the new file, never a torn one). A failed write (``ENOSPC``,
permissions, injected ``io`` faults) cleans up its tmp file, counts
``vault.write_failed``, and the process continues without persistence.

Chaos hooks: the ``io`` fault site (``resilience.faults``, grammar
``truncate:io`` / ``stale:io`` / ``enospc:io`` on the write path and
``bitflip:io`` on the read path) injects exactly the disk failure modes
the verify ladder exists for — docs/resilience.md.
"""

from __future__ import annotations

import errno
import hashlib
import io
import itertools
import json
import os
import threading
import time

import numpy as np

from ..config import settings
from ..telemetry import _metrics

MAGIC = b"STPUVAULT\x01"
#: bump on any incompatible artifact layout change; old files quarantine
FORMAT = 1
SUFFIX = ".stv"
#: max files kept in quarantine/ before the oldest are pruned
QUARANTINE_KEEP = 32

_LOCK = threading.RLock()
_SEQ = itertools.count()

_COUNTERS = {
    "hits": _metrics.counter("vault.hits"),
    "misses": _metrics.counter("vault.misses"),
    "writes": _metrics.counter("vault.writes"),
    "write_failed": _metrics.counter("vault.write_failed"),
    "verify_failed": _metrics.counter("vault.verify_failed"),
    "quarantined": _metrics.counter("vault.quarantined"),
    "evictions": _metrics.counter("vault.evictions"),
    "replayed": _metrics.counter("vault.replayed"),
}
_SIZE_GAUGE = _metrics.gauge("vault.size_bytes")


def _telemetry():
    """The telemetry facade iff events are enabled (lazy import — the
    vault must stay importable before the package facade exists)."""
    if not settings.telemetry:
        return None
    from .. import telemetry

    return telemetry


def enabled() -> bool:
    """True when a persistent tier is configured (``SPARSE_TPU_VAULT``)."""
    return bool(settings.vault)


def vault_dir() -> str:
    return os.path.abspath(settings.vault)


def _objects_dir(kind: str) -> str:
    return os.path.join(vault_dir(), "objects", kind)


def _tmp_dir() -> str:
    return os.path.join(vault_dir(), "tmp")


def quarantine_dir() -> str:
    return os.path.join(vault_dir(), "quarantine")


def artifact_path(kind: str, key: str) -> str:
    return os.path.join(_objects_dir(kind), key + SUFFIX)


# ---------------------------------------------------------------------------
# encode / decode
# ---------------------------------------------------------------------------
def _encode(kind: str, key: str, meta: dict, arrays: dict) -> bytes:
    """Serialize one artifact to its on-disk blob (see module doc)."""
    import jax

    buf = io.BytesIO()
    # deterministic member order so equal artifacts are byte-comparable
    np.savez(buf, **{k: np.asarray(arrays[k]) for k in sorted(arrays)})
    payload = buf.getvalue()
    header = {
        "format": FORMAT,
        "kind": kind,
        "key": key,
        "jax": jax.__version__,
        "numpy": np.__version__,
        "meta": meta,
        "payload_len": len(payload),
        "payload_sha256": hashlib.sha256(payload).hexdigest(),
        "writer_pid": os.getpid(),
        "created": time.time(),
    }
    return MAGIC + json.dumps(header, sort_keys=True).encode() + b"\n" + payload


def _verify(blob: bytes, kind: str, key: str, expect: dict | None):
    """Verify-then-decode one artifact blob.

    Returns ``(meta, arrays)`` on success or a problem string — every
    failure mode gets a distinct reason (the quarantine file name and the
    ``vault.quarantine`` event carry it)."""
    import jax

    if not blob.startswith(MAGIC):
        return "bad-magic"
    try:
        nl = blob.index(b"\n", len(MAGIC))
        header = json.loads(blob[len(MAGIC):nl].decode())
        if not isinstance(header, dict):
            raise ValueError("header not a dict")
    except Exception:
        return "bad-header"
    if header.get("format") != FORMAT:
        return "stale-format"
    if header.get("jax") != jax.__version__:
        # a jax upgrade invalidates traced/packed layouts wholesale
        return "stale-jax"
    if header.get("kind") != kind or header.get("key") != key:
        return "key-mismatch"
    payload = blob[nl + 1:]
    if header.get("payload_len") != len(payload):
        return "truncated"
    if header.get("payload_sha256") != hashlib.sha256(payload).hexdigest():
        return "checksum"
    meta = header.get("meta")
    if not isinstance(meta, dict):
        return "bad-header"
    if expect:
        for k, v in expect.items():
            if meta.get(k) != v:
                return f"expect-{k}"
    try:
        with np.load(io.BytesIO(payload), allow_pickle=False) as z:
            arrays = {k: z[k] for k in z.files}
    except Exception:
        return "decode-error"
    return meta, arrays


# ---------------------------------------------------------------------------
# store / load
# ---------------------------------------------------------------------------
def _io_actions(op: str) -> list:
    from ..resilience import faults

    if not faults.ACTIVE:
        return []
    return faults.io_actions(op)


def store(kind: str, key: str, meta: dict, arrays: dict) -> bool:
    """Atomically persist one artifact; returns True on success.

    Never raises: any failure (real ENOSPC, permissions, injected ``io``
    faults) counts ``vault.write_failed`` and leaves the vault exactly as
    it was (the tmp file is removed; the previous artifact version, if
    any, stays in place)."""
    if not enabled():
        return False
    tmp = None
    try:
        blob = _encode(kind, key, meta, arrays)
        for act in _io_actions("write"):
            if act[0] == "enospc":
                raise OSError(errno.ENOSPC, "injected ENOSPC (io fault)")
            if act[0] == "truncate":
                # models a torn write that survived on disk: the verify
                # ladder must catch it on the next load
                blob = blob[: max(len(blob) // 2, len(MAGIC) + 1)]
            if act[0] == "stale":
                # models an artifact left behind by an older build
                head, _, payload = blob.partition(b"\n")
                hdr = json.loads(head[len(MAGIC):].decode())
                hdr["format"] = FORMAT - 1
                blob = (
                    MAGIC + json.dumps(hdr, sort_keys=True).encode()
                    + b"\n" + payload
                )
        final = artifact_path(kind, key)
        os.makedirs(os.path.dirname(final), exist_ok=True)
        os.makedirs(_tmp_dir(), exist_ok=True)
        with _LOCK:
            seq = next(_SEQ)
        tmp = os.path.join(
            _tmp_dir(),
            f"{key}{SUFFIX}.{os.getpid()}.{seq}.tmp",
        )
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
        tmp = None
        _fsync_dir(os.path.dirname(final))
    except Exception:
        _COUNTERS["write_failed"].inc()
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        return False
    _COUNTERS["writes"].inc()
    gc()  # size-budgeted LRU sweep; no-op while under the cap
    return True


def load(kind: str, key: str, expect: dict | None = None):
    """Verify-then-load one artifact; ``(meta, arrays)`` or ``None``.

    A missing file is a plain miss. An unreadable or invalid file is a
    miss PLUS a quarantine — the bad bytes are moved aside so they can
    never be re-read, and the caller's rebuild re-deposits a good copy."""
    if not enabled():
        return None
    path = artifact_path(kind, key)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        _COUNTERS["misses"].inc()
        return None
    for act in _io_actions("read"):
        if act[0] == "bitflip" and blob:
            idx = min(int(act[1] * len(blob)), len(blob) - 1)
            b = bytearray(blob)
            b[idx] ^= 0x40
            blob = bytes(b)
    out = _verify(blob, kind, key, expect)
    if isinstance(out, str):
        _COUNTERS["misses"].inc()
        quarantine(path, out, kind)
        return None
    _COUNTERS["hits"].inc()
    try:
        os.utime(path, None)  # LRU touch for the mtime-ordered GC sweep
    except OSError:
        pass
    return out


def quarantine(path: str, reason: str, kind: str = "?") -> None:
    """Move a failed-verification file into the quarantine sidecar dir
    (named ``<basename>.<reason>.<pid>.<seq>``), bounded to
    ``QUARANTINE_KEEP`` files. Best-effort: a racing reader may have
    quarantined it first."""
    _COUNTERS["verify_failed"].inc()
    _metrics.counter("vault.verify_failed.by_reason", reason=reason).inc()
    qdir = quarantine_dir()
    try:
        os.makedirs(qdir, exist_ok=True)
        with _LOCK:
            seq = next(_SEQ)
        dest = os.path.join(
            qdir,
            f"{os.path.basename(path)}.{reason}.{os.getpid()}.{seq}",
        )
        os.replace(path, dest)
        _COUNTERS["quarantined"].inc()
    except OSError:
        return  # already moved/removed by a concurrent process
    tel = _telemetry()
    if tel is not None:
        tel.record("vault.quarantine", artifact=kind, reason=reason,
                   path=os.path.basename(dest))
    # bound the sidecar: quarantined files are debugging evidence, not an
    # unbounded archive
    try:
        entries = sorted(
            (e for e in os.scandir(qdir) if e.is_file()),
            key=lambda e: e.stat().st_mtime,
        )
        for e in entries[:-QUARANTINE_KEEP]:
            os.unlink(e.path)
    except OSError:
        pass


def _fsync_dir(path: str) -> None:
    """Best-effort directory fsync so the rename itself is durable."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


# ---------------------------------------------------------------------------
# GC
# ---------------------------------------------------------------------------
def _artifacts():
    """Every artifact file as ``(path, size, mtime)``."""
    root = os.path.join(vault_dir(), "objects")
    out = []
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            if not name.endswith(SUFFIX):
                continue
            p = os.path.join(dirpath, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            out.append((p, st.st_size, st.st_mtime))
    return out


def gc(cap_mb: float | None = None, dry_run: bool = False) -> int:
    """Size-budgeted LRU sweep: evict oldest-mtime artifacts until the
    vault fits ``cap_mb`` (default ``settings.vault_cap_mb``; loads
    touch mtime, so recently-used artifacts survive). Returns the number
    of evicted files; stale tmp files (> 1 h — a crashed writer's
    leftovers) are always pruned."""
    if not enabled():
        return 0
    cap = float(settings.vault_cap_mb if cap_mb is None else cap_mb)
    try:
        now = time.time()
        for e in os.scandir(_tmp_dir()):
            if e.is_file() and now - e.stat().st_mtime > 3600:
                os.unlink(e.path)
    except OSError:
        pass
    files = _artifacts()
    total = sum(s for _, s, _ in files)
    _SIZE_GAUGE.set(total)
    if total <= cap * 2**20:
        return 0
    evicted = 0
    for path, size, _mt in sorted(files, key=lambda t: t[2]):
        if total <= cap * 2**20:
            break
        if not dry_run:
            try:
                os.unlink(path)
            except OSError:
                continue
        total -= size
        evicted += 1
        _COUNTERS["evictions"].inc()
    _SIZE_GAUGE.set(max(total, 0))
    return evicted


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------
def stats() -> dict:
    """Always-on vault counters (the same numbers a Prometheus scrape of
    ``telemetry.metrics_text()`` sees as ``sparse_tpu_vault_*``)."""
    out = {k: int(c.value) for k, c in _COUNTERS.items()}
    out["enabled"] = enabled()
    out["size_bytes"] = int(_SIZE_GAUGE.value)
    return out


def reset_stats() -> None:
    for c in _COUNTERS.values():
        c.reset()
    _SIZE_GAUGE.reset()
