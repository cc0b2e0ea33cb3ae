"""Always-on metrics registry: counters, gauges, log-bucket histograms.

The event stream (:mod:`._recorder`) answers "what happened"; this module
answers "what is the level right now" — the surface a serving stack
scrapes. Before it existed the library kept three ad-hoc dicts
(``plan_cache._STATS``, the recorder's ``_COUNTS``/``_BYTES``, and
whatever ``SolveSession`` stashed per dispatch); they all live here now,
behind one registry with Prometheus text exposition
(:func:`metrics_text` / ``telemetry.metrics_text()``).

Design rules:

* **Always on.** Unlike the event stream, metrics are not gated by
  ``settings.telemetry`` — a counter bump is one dict hit plus one int
  add under a lock, cheap enough to leave on everywhere (the plan cache
  has counted always-on since PR 2). Call sites that *are*
  telemetry-gated (the recorder's ``count()``/``add_bytes``) keep their
  own gate; the registry itself never checks it.
* **Allocation-light.** Metric objects are created once
  (get-or-create keyed on ``(name, labels)``) and mutate plain
  ints/floats in place; histograms pre-allocate their bucket array.
  The hot path never builds strings or dicts.
* **Dotted names in, Prometheus names out.** Library code uses the
  repo's dotted convention (``plan_cache.hits``, ``batch.queue_depth``);
  :func:`metrics_text` sanitizes to ``sparse_tpu_plan_cache_hits_total``
  etc. at exposition time only.
"""

from __future__ import annotations

import math
import threading

_LOCK = threading.RLock()
# (name, ((label, value), ...)) -> metric object
_REGISTRY: dict = {}
# name -> metric class, for TYPE lines and family grouping
_FAMILIES: dict = {}
# name -> help text, for HELP lines (optional, set via help= at creation)
_HELP: dict = {}

# Log-2 histogram geometry: upper bounds 2**k for k in [_BK_MIN, _BK_MAX),
# plus a +Inf overflow bucket. Spans ~1e-6 .. ~1e9 — microseconds to
# gigabytes/iteration-counts on one fixed grid, so histograms never
# allocate per observation.
_BK_MIN = -20
_BK_MAX = 31
_BOUNDS = tuple(2.0 ** k for k in range(_BK_MIN, _BK_MAX))


def _labels_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


class Counter:
    """Monotonic counter. ``inc(n)`` under the registry lock."""

    __slots__ = ("name", "labels", "_v")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels
        self._v = 0

    def inc(self, n=1) -> None:
        with _LOCK:
            self._v += n

    add = inc  # byte-total call sites read better as .add(nbytes)

    @property
    def value(self):
        return self._v

    def reset(self) -> None:
        with _LOCK:
            self._v = 0


class Gauge:
    """Point-in-time level. ``fn`` makes a lazy gauge (sampled at read
    time — e.g. ``plan_cache.size`` reads ``len(_ENTRIES)`` live)."""

    __slots__ = ("name", "labels", "_v", "fn")

    def __init__(self, name: str, labels: dict, fn=None):
        self.name = name
        self.labels = labels
        self._v = 0.0
        self.fn = fn

    def set(self, v) -> None:
        with _LOCK:
            self._v = v

    def inc(self, n=1) -> None:
        with _LOCK:
            self._v += n

    def dec(self, n=1) -> None:
        with _LOCK:
            self._v -= n

    @property
    def value(self):
        if self.fn is not None:
            try:
                return self.fn()
            except Exception:
                return 0
        return self._v

    def reset(self) -> None:
        with _LOCK:
            self._v = 0.0


class Histogram:
    """Fixed log-2 bucket histogram (see ``_BOUNDS``): ``observe(v)``
    finds the bucket via ``math.frexp`` — no log calls, no allocation."""

    __slots__ = ("name", "labels", "_counts", "_sum", "_n")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels
        self._counts = [0] * (len(_BOUNDS) + 1)  # +1: the +Inf bucket
        self._sum = 0.0
        self._n = 0

    def observe(self, v) -> None:
        v = float(v)
        if math.isnan(v):
            return
        if v <= 0.0:
            idx = 0
        elif math.isinf(v):
            idx = len(_BOUNDS)
        else:
            m, e = math.frexp(v)  # v = m * 2**e, m in [0.5, 1)
            k = e - 1 if m == 0.5 else e  # smallest k with v <= 2**k
            idx = min(max(k - _BK_MIN, 0), len(_BOUNDS))
        with _LOCK:
            self._counts[idx] += 1
            self._sum += v
            self._n += 1

    @property
    def count(self) -> int:
        return self._n

    @property
    def sum(self) -> float:
        return self._sum

    def buckets(self) -> list:
        """Cumulative ``(upper_bound, count)`` pairs, Prometheus-style
        (the last pair is ``(inf, total)``)."""
        with _LOCK:
            counts = list(self._counts)
        out = []
        acc = 0
        for b, c in zip(_BOUNDS, counts):
            acc += c
            out.append((b, acc))
        out.append((math.inf, acc + counts[-1]))
        return out

    def reset(self) -> None:
        with _LOCK:
            self._counts = [0] * (len(_BOUNDS) + 1)
            self._sum = 0.0
            self._n = 0


def _get(cls, name: str, labels: dict, help=None, **kw):
    key = (name, _labels_key(labels))
    with _LOCK:
        if help:
            _HELP.setdefault(name, str(help))
        m = _REGISTRY.get(key)
        if m is None:
            m = cls(name, dict(labels), **kw)
            _REGISTRY[key] = m
            _FAMILIES.setdefault(name, cls)
        return m


def counter(name: str, /, help=None, **labels) -> Counter:
    """Get-or-create a counter (same name+labels => same object).
    ``help`` registers the family's HELP text (first writer wins)."""
    return _get(Counter, name, labels, help=help)


def gauge(name: str, /, fn=None, help=None, **labels) -> Gauge:
    """Get-or-create a gauge; ``fn`` makes it lazily sampled."""
    g = _get(Gauge, name, labels, help=help)
    if fn is not None:
        g.fn = fn
    return g


def histogram(name: str, /, help=None, **labels) -> Histogram:
    """Get-or-create a log-2 bucket histogram."""
    return _get(Histogram, name, labels, help=help)


def family(name: str) -> list:
    """Every metric object registered under ``name`` (all label sets) —
    the readback windowed consumers (the SLO watchdog) aggregate over,
    e.g. total ticket count across per-solver/tenant latency histograms."""
    with _LOCK:
        return [m for (n, _), m in _REGISTRY.items() if n == name]


def label_values(name: str, label: str) -> dict:
    """``{label_value: metric_value}`` over a family — the readback the
    recorder's ``counters()``/``bytes_by_kind()`` use."""
    with _LOCK:
        items = [m for (n, _), m in _REGISTRY.items() if n == name]
    return {m.labels.get(label, ""): m.value for m in items}


def remove(name: str) -> None:
    """Drop a whole family from the registry (``telemetry.reset()`` uses
    this for the dynamic-name recorder families; metrics held as module
    globals should ``reset()`` their values instead)."""
    with _LOCK:
        for key in [k for k in _REGISTRY if k[0] == name]:
            del _REGISTRY[key]
        _FAMILIES.pop(name, None)
        _HELP.pop(name, None)


def zero(prefix: str = "") -> None:
    """Reset every matching metric's value in place (objects stay
    registered and call-site references stay live)."""
    with _LOCK:
        metrics = [m for (n, _), m in _REGISTRY.items() if n.startswith(prefix)]
    for m in metrics:
        m.reset()


def _sanitize(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if (ch.isalnum() and ch.isascii()) or ch in "_:" else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return s


def _escape_label(v) -> str:
    """Prometheus label-value escaping (exposition format 0.0.4):
    backslash, double-quote and newline must be escaped — unescaped they
    corrupt the whole scrape, not just one series."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _escape_help(s: str) -> str:
    """HELP-text escaping: backslash and newline only (quotes are legal
    in HELP lines per the format spec)."""
    return str(s).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{_sanitize(str(k))}="{_escape_label(v)}"'
        for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


def _fmt_value(v) -> str:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return "0"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def metrics_text() -> str:
    """Prometheus text exposition (format 0.0.4) of the whole registry.

    Dotted names become ``sparse_tpu_<name>`` with non-alphanumerics
    mapped to ``_``; counters gain the conventional ``_total`` suffix,
    histograms expose ``_bucket``/``_sum``/``_count`` series. Every
    family gets ``# HELP`` + ``# TYPE`` lines (registered help text, or
    the dotted name as the fallback description), and label values are
    escaped per the format spec (:func:`_escape_label`).
    """
    with _LOCK:
        families = dict(_FAMILIES)
        helps = dict(_HELP)
        by_name: dict = {}
        for (name, _), m in sorted(_REGISTRY.items()):
            by_name.setdefault(name, []).append(m)
    lines = []
    for name in sorted(by_name):
        cls = families.get(name, Counter)
        base = "sparse_tpu_" + _sanitize(name)
        help_text = _escape_help(helps.get(name, f"sparse_tpu {name}"))
        if cls is Counter:
            lines.append(f"# HELP {base}_total {help_text}")
            lines.append(f"# TYPE {base}_total counter")
            for m in by_name[name]:
                lines.append(
                    f"{base}_total{_fmt_labels(m.labels)} {_fmt_value(m.value)}"
                )
        elif cls is Gauge:
            lines.append(f"# HELP {base} {help_text}")
            lines.append(f"# TYPE {base} gauge")
            for m in by_name[name]:
                lines.append(
                    f"{base}{_fmt_labels(m.labels)} {_fmt_value(m.value)}"
                )
        else:  # Histogram
            lines.append(f"# HELP {base} {help_text}")
            lines.append(f"# TYPE {base} histogram")
            for m in by_name[name]:
                for bound, acc in m.buckets():
                    lb = dict(m.labels)
                    lb["le"] = _fmt_value(bound)
                    lines.append(f"{base}_bucket{_fmt_labels(lb)} {acc}")
                lines.append(
                    f"{base}_sum{_fmt_labels(m.labels)} {_fmt_value(m.sum)}"
                )
                lines.append(
                    f"{base}_count{_fmt_labels(m.labels)} {m.count}"
                )
    return "\n".join(lines) + ("\n" if lines else "")


def snapshot() -> dict:
    """JSON-friendly flat view: ``{name{labels}: value}`` for counters
    and gauges, ``{name{labels}: {"count", "sum"}}`` for histograms."""
    with _LOCK:
        items = list(_REGISTRY.items())
    out = {}
    for (name, lkey), m in sorted(items):
        key = name + _fmt_labels(dict(lkey))
        if isinstance(m, Histogram):
            out[key] = {"count": m.count, "sum": round(m.sum, 9)}
        else:
            v = m.value
            out[key] = round(v, 9) if isinstance(v, float) else v
    return out
