"""Library-wide two-tier operator plan cache: prepare once, execute everywhere.

Reference analog: legate.sparse caches partitions and images per Store
(``set_key_partition``, SURVEY §1) so a solve derives its layout once and
every subsequent task launch reuses it. The TPU reproduction's "layouts"
are packed operators (SELL slabs, prepared DIA planes) and compiled
shard_map programs; this module is the one place they live, so
``csr.dot``, ``LinearOperator`` and every solver in ``linalg`` reuse the
same plan across a whole solve instead of re-deriving it per matvec.

Two tiers (ISSUE 9): the in-process weak-ref LRU below is tier 1; when
``SPARSE_TPU_VAULT`` points at a directory, :mod:`sparse_tpu.vault` is
tier 2 — a crash-safe on-disk store of serialized prepared artifacts
keyed by CONTENT fingerprints. A lookup that misses in-process consults
the disk tier before building (``disk_hits`` in :func:`stats`); a build
deposits its artifact back so the NEXT process skips the pack. Disk
reads are verify-then-load with quarantine on any corruption — a bad
artifact degrades to a rebuild, never an error (docs/performance.md,
docs/resilience.md).

Design:

* **Weak-ref keyed.** Entries are keyed by the operator *object* (a
  ``csr_array``, a ``DistCSR``, ...) and die with it — a
  ``weakref.finalize`` evicts all of an object's plans when it is
  collected, so mutation-by-replacement (``_with_data``, fresh
  constructions) invalidates for free and the cache can never resurrect
  a stale layout. Objects that don't support weak references are never
  cached (every lookup builds).
* **Bounded.** LRU over ``settings.plan_cache_capacity`` (object, kind)
  entries; eviction is counted.
* **Observable.** Hit/miss/evict counters are always maintained and
  surfaced via :func:`stats`; they live on the always-on metrics
  registry (``telemetry/_metrics.py`` — ``plan_cache.hits`` /
  ``plan_cache.misses`` / ``plan_cache.evictions`` counters plus a lazy
  ``plan_cache.size`` gauge, all visible in
  ``telemetry.metrics_text()``). With telemetry enabled they also
  mirror into ``telemetry.summary()["counts"]`` under
  ``plan_cache.hit`` / ``plan_cache.miss`` / ``plan_cache.evict``
  (docs/telemetry.md).
* **Switchable.** ``SPARSE_TPU_PLAN_CACHE=0`` (``settings.plan_cache``)
  disables caching entirely: every lookup misses and builds, correctness
  unchanged — the parity suite runs both ways.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict

from .config import settings
from .telemetry import _metrics

_LOCK = threading.RLock()
# (id(obj), kind) -> (weakref | None, plan); OrderedDict for LRU order
_ENTRIES: OrderedDict = OrderedDict()
_FINALIZERS: dict[int, object] = {}  # id(obj) -> weakref.finalize handle
# the always-on counters live on the metrics registry (one metrics
# surface — telemetry.metrics_text() exposes them as
# sparse_tpu_plan_cache_{hits,misses,evictions}_total + a size gauge)
_COUNTERS = {
    "hits": _metrics.counter("plan_cache.hits"),
    "misses": _metrics.counter("plan_cache.misses"),
    "evictions": _metrics.counter("plan_cache.evictions"),
    # tier-2 hits: the in-process tier missed but the vault's verified
    # artifact load replaced the build ("miss" stays = "had to build")
    "disk_hits": _metrics.counter("plan_cache.disk_hits"),
}
_metrics.gauge("plan_cache.size", fn=lambda: len(_ENTRIES))
_TELEMETRY_NAMES = {"hits": "plan_cache.hit", "misses": "plan_cache.miss",
                    "evictions": "plan_cache.evict",
                    "disk_hits": "plan_cache.disk_hit"}


def _count(which: str) -> None:
    _COUNTERS[which].inc()
    if settings.telemetry:
        from . import telemetry

        # counters are the cheap aggregate channel; one event per lookup
        # would flood the ring on hot paths
        telemetry.count(_TELEMETRY_NAMES[which])


def _evict_object(oid: int) -> None:
    """Drop every plan of a collected (or invalidated) object. Runs from
    ``weakref.finalize`` at GC time, so it must tolerate entries already
    gone (a concurrent ``clear()``/eviction) rather than ever raise.

    The RLock does NOT protect against re-entrancy here: an allocation
    inside this function can trigger GC, which can run ANOTHER object's
    finalizer on the same thread (the lock re-enters) and mutate
    ``_ENTRIES`` under our iteration — so the scan retries on the
    resulting KeyError/RuntimeError instead of leaking it into the
    interpreter's unraisable hook."""
    with _LOCK:
        for _ in range(4):
            try:
                dead = [k for k in _ENTRIES if k[0] == oid]
                break
            except (KeyError, RuntimeError):  # re-entrant finalizer race
                continue
        else:
            dead = []  # give up cleanly; the LRU cap bounds orphans
        for k in dead:
            if _ENTRIES.pop(k, None) is not None:
                _count("evictions")
        _FINALIZERS.pop(oid, None)


def get(obj, kind: str, build=None, *, vault_kind: str | None = None,
        vault_key=None, expect: dict | None = None):
    """Return the cached plan for ``(obj, kind)``, building on miss.

    ``build`` is a zero-arg callable producing the plan; with
    ``build=None`` a miss returns ``None`` (the trace-safe lookup form —
    in-trace callers may not build, packing needs host syncs, and the
    disk tier is never consulted). Lookups count exactly one of
    hit / disk_hit / miss each ("miss" always means "built"). With the
    cache disabled every call counts a miss and builds (when it can) —
    both tiers off, correctness unchanged.

    ``vault_kind``/``vault_key`` opt a build site into the persistent
    tier (:mod:`sparse_tpu.vault`): ``vault_key`` is the artifact's
    content fingerprint — a string, or a zero-arg callable evaluated
    only when the vault is enabled (fingerprinting hashes the operator's
    buffers; sites must not pay that when there is no disk tier).
    ``expect`` adds load-time meta assertions (e.g. dtype) on top of the
    store's own verify ladder. An in-process miss then tries a verified
    disk load before building; a build deposits its artifact back.
    Disk-tier failures of any kind degrade to the build path.
    """
    key = (id(obj), kind)
    if settings.plan_cache:
        with _LOCK:
            ent = _ENTRIES.get(key)
            if ent is not None and (ent[0] is None or ent[0]() is obj):
                _ENTRIES.move_to_end(key)
                _count("hits")
                return ent[1]
    plan = None
    vk = None
    use_vault = (
        build is not None and vault_kind is not None and settings.plan_cache
        and settings.vault
    )
    if use_vault:
        from . import vault

        try:
            vk = vault_key() if callable(vault_key) else vault_key
        except Exception:
            vk = None  # unfingerprintable content: tier 1 + build only
        if vk:
            plan = vault.fetch(vault_kind, vk, expect=expect)
    if plan is not None:
        _count("disk_hits")
    else:
        _count("misses")
        if build is None:
            return None
        plan = build()
        if use_vault and vk and plan is not None:
            from . import vault

            vault.deposit(vault_kind, vk, plan)
    if not settings.plan_cache or plan is None:
        return plan
    try:
        ref = weakref.ref(obj)
    except TypeError:
        return plan  # un-weakref-able key: never cached (id reuse unsafe)
    with _LOCK:
        _ENTRIES[key] = (ref, plan)
        _ENTRIES.move_to_end(key)
        oid = id(obj)
        if oid not in _FINALIZERS:
            _FINALIZERS[oid] = weakref.finalize(obj, _evict_object, oid)
        cap = max(int(settings.plan_cache_capacity), 1)
        while len(_ENTRIES) > cap:
            old_key, _ = _ENTRIES.popitem(last=False)
            _count("evictions")
    return plan


def lookup(obj, kind: str):
    """Trace-safe cached-plan lookup (never builds). See :func:`get`."""
    return get(obj, kind, None)


def put(obj, kind: str, plan) -> None:
    """Store/replace a plan directly (no hit/miss accounting).
    Silently a no-op when caching is off or ``obj`` is un-weakref-able."""
    if not settings.plan_cache:
        return
    try:
        ref = weakref.ref(obj)
    except TypeError:
        return
    with _LOCK:
        _ENTRIES[(id(obj), kind)] = (ref, plan)
        _ENTRIES.move_to_end((id(obj), kind))
        oid = id(obj)
        if oid not in _FINALIZERS:
            _FINALIZERS[oid] = weakref.finalize(obj, _evict_object, oid)


def invalidate(obj, kind: str | None = None) -> None:
    """Drop an object's cached plans (one kind, or all of them)."""
    with _LOCK:
        if kind is None:
            _evict_object(id(obj))
            return
        if _ENTRIES.pop((id(obj), kind), None) is not None:
            _count("evictions")


def stats() -> dict:
    """Always-on counters: ``{hits, misses, disk_hits, evictions, size,
    hit_rate, compile_s}`` (read back from the metrics registry — same
    numbers a Prometheus scrape of ``telemetry.metrics_text()`` sees).
    ``disk_hits`` counts persistent-tier loads that replaced a build
    (``misses`` always means "built"); ``hit_rate`` counts both tiers'
    hits. ``compile_s`` is the session's cold-start budget: total
    wall-clock seconds spent building/compiling attributed programs
    (telemetry/_cost.py), so bench session records carry the compile
    tax next to the hit rate it bought."""
    with _LOCK:
        out = {k: int(c.value) for k, c in _COUNTERS.items()}
        out["size"] = len(_ENTRIES)
    total = out["hits"] + out["disk_hits"] + out["misses"]
    out["hit_rate"] = (
        (out["hits"] + out["disk_hits"]) / total if total else 0.0
    )
    from .telemetry import _cost

    out["compile_s"] = round(_cost.total_compile_s(), 6)
    return out


def snapshot() -> dict:
    """Copy of the raw always-on counters, for delta accounting without a
    global reset (``batch.SolveSession`` dispatch telemetry, tests —
    concurrent users must not clobber each other's baselines)."""
    with _LOCK:
        return {k: int(c.value) for k, c in _COUNTERS.items()}


def delta(since: dict) -> dict:
    """Counter movement since a :func:`snapshot`:
    ``{hits, misses, evictions, disk_hits}``."""
    with _LOCK:
        return {k: int(_COUNTERS[k].value) - since.get(k, 0)
                for k in ("hits", "misses", "evictions", "disk_hits")}


def reset_stats() -> None:
    with _LOCK:
        for c in _COUNTERS.values():
            c.reset()


def clear() -> None:
    """Drop every entry (counters untouched; evictions not counted —
    this is a test/debug reset, not cache pressure)."""
    with _LOCK:
        _ENTRIES.clear()
        for f in _FINALIZERS.values():
            try:
                f.detach()
            except Exception:
                pass
        _FINALIZERS.clear()
