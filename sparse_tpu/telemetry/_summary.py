"""Aggregation API: one dict summarizing the session's telemetry.

Counts, per-kind
event totals, span latency percentiles, and structural bytes moved per
collective family. Pure host arithmetic over the recorder's in-memory
state; never touches a device.
"""

from __future__ import annotations

from . import _recorder


def _percentile(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile on an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(int(q * (len(sorted_vals) - 1) + 0.5), len(sorted_vals) - 1)
    return sorted_vals[idx]


def summary() -> dict:
    """Aggregate the session's telemetry.

    Returns::

        {
          "enabled": bool,
          "events": total events currently in the ring,
          "dropped": events evicted from the full ring (0 = none lost),
          "events_by_kind": {kind: n},
          "counts": {name: n},              # count() counters
          "bytes_by_kind": {kind: bytes},   # structural comm volumes
          "spans": {name: {"n", "total_s", "p50_s", "p95_s", "max_s"}},
        }

    Works (returns zeros) even when telemetry is disabled, so callers
    can embed it unconditionally.
    """
    evs = _recorder.events()
    by_kind: dict = {}
    for e in evs:
        k = e.get("kind", "?")
        by_kind[k] = by_kind.get(k, 0) + 1
    spans = {}
    for name, (n, total, mx, durs) in _recorder.span_aggregates().items():
        ds = sorted(durs)  # the last `telemetry_ring` spans of this name
        spans[name] = {
            "n": n,
            "total_s": round(total, 6),
            "p50_s": round(_percentile(ds, 0.50), 6),
            "p95_s": round(_percentile(ds, 0.95), 6),
            "max_s": round(mx, 6),
        }
    return {
        "enabled": _recorder.enabled(),
        "events": len(evs),
        "dropped": _recorder.dropped(),
        "events_by_kind": by_kind,
        "counts": _recorder.counters(),
        "bytes_by_kind": _recorder.bytes_by_kind(),
        "spans": spans,
    }
