"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time, per-program and per-op device
time, and the idle gaps labelled by what the host was doing.

What a TPU v5e trace holds (read by hand, PR 24): one plane per chip,
``/device:TPU:<i>``, with the lines ``XLA Modules`` (one event per run of a
compiled program, named ``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per
HLO op, named by the op's whole HLO text; a ``while`` event spans the ops of
its body). The host is the plane ``/host:CPU``; its line ``python3`` carries
the Python tracer's frames (``$file.py:line func``) and every
``jax.profiler.TraceAnnotation``. All planes share one time axis in ns.

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_ANNOTATION = "bench.traced"
EDGE_NS = 50e6
OUTSIDE = "(outside a whole program)"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """{plane name: {line name: [(name, start_ns, duration_ns), ...]}},
    events of a line sorted by start."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            evs.sort(key=lambda e: (e[1], -e[2]))
            lines.setdefault(line.name, []).extend(evs)
    return planes


def program_name(module_event: str) -> str:
    """``jit_cg_dia_fused(748277464632240032)`` -> ``jit_cg_dia_fused``."""
    return module_event.split("(", 1)[0]


def op_label(hlo_text: str) -> tuple:
    """(result name, opcode, note) of an ``XLA Ops`` event, whose name is the
    op's HLO text: ``%fusion.76 = f32[..] fusion(...), kind=kCustom, ...``."""
    if " = " not in hlo_text:
        return hlo_text.lstrip("%")[:60], "", ""
    result, rest = hlo_text.split(" = ", 1)
    rest = rest.lstrip()
    if rest.startswith("("):  # tuple shape: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    opcode = re.match(r"[\w\-]+", rest)
    note = ""
    m = re.search(r'custom_call_target="([^"]+)"', hlo_text)
    if m:
        note = m.group(1)
    else:
        m = re.search(r"kind=(\w+)", hlo_text)
        if m:
            note = m.group(1)
    return result.lstrip("%"), opcode.group(0) if opcode else "", note


def merged(intervals) -> list:
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events) -> list:
    """(name, start, self_ns) per event of one line, where nested events
    (a ``while`` and the ops of its body) are charged to the innermost."""
    out = []
    stack: list = []  # [name, start, end, child_ns]

    def close():
        name, s, e, child = stack.pop()
        out.append((name, s, max(e - s - child, 0.0)))
        if stack:
            stack[-1][3] += e - s

    for name, s, d in events:
        while stack and s >= stack[-1][2]:
            close()
        stack.append([name, s, s + d, 0.0])
    while stack:
        close()
    return out


def _window(planes: dict):
    """(start, end, annotated): the ``bench.traced`` annotation, or the
    extent of all device program events when the trace has none."""
    for evs in planes.get(HOST_PLANE, {}).values():
        for name, s, d in evs:
            if name == WINDOW_ANNOTATION:
                return s, s + d, True
    spans = [(s, s + d) for pn, lines in planes.items()
             if DEVICE_PLANE.match(pn)
             for s, d in ((e[1], e[2]) for e in lines.get(MODULES_LINE, []))]
    if not spans:
        raise ValueError("trace has no device program events")
    return min(s for s, _ in spans), max(e for _, e in spans), False


def _host_label(host_events, t: float) -> str:
    """Innermost host frame or annotation that covers time ``t``."""
    best = None
    for name, s, d in host_events:
        if s > t:
            break
        if s + d >= t and (best is None or d <= best[1]):
            best = (name, d)
    return best[0] if best else "(no host frame)"


def reduce(path: str, min_gap_s: float = 50e-6) -> dict:
    """The reduced trace: window, per-device busy seconds, device seconds per
    program and per op (self time), and idle gaps."""
    planes = load(path)
    w0, w1, annotated = _window(planes)
    # A program already running when the trace starts is recorded from the
    # trace's start, and one still running when it stops up to there (with a
    # length near 0): both lie at the edges of the annotated window, and
    # neither is a whole run. Runs this close to an edge are left out of the
    # per-program sums; busy time counts them.
    edge = EDGE_NS if annotated else 0.0
    devices = {}
    for pn, lines in planes.items():
        m = DEVICE_PLANE.match(pn)
        if not m:
            continue
        mods = [(n, max(s, w0), min(s + d, w1)) for n, s, d in
                lines.get(MODULES_LINE, []) if s + d > w0 and s < w1]
        whole = [(n, s, d) for n, s, d in lines.get(MODULES_LINE, [])
                 if s >= w0 + edge and s + d <= w1 - edge]
        busy = merged((s, e) for _, s, e in mods)
        programs: dict = {}
        for n, s, d in whole:
            p = programs.setdefault(program_name(n), [0, 0.0])
            p[0] += 1
            p[1] += d * 1e-9
        # each op belongs to the program whose module event contains it
        ops: dict = {}
        mod_iv = sorted((s, s + d, program_name(n)) for n, s, d in whole)
        ops_in = [e for e in lines.get(OPS_LINE, [])
                  if e[1] >= w0 and e[1] + e[2] <= w1]
        mi = 0
        for name, s, self_ns in sorted(self_times(ops_in), key=lambda x: x[1]):
            while mi < len(mod_iv) and mod_iv[mi][1] <= s:
                mi += 1
            prog = (mod_iv[mi][2] if mi < len(mod_iv) and mod_iv[mi][0] <= s
                    else OUTSIDE)
            res, opcode, note = op_label(name)
            key = (prog, res, opcode, note)
            o = ops.setdefault(key, [0, 0.0])
            o[0] += 1
            o[1] += self_ns * 1e-9
        devices[int(m.group(1))] = {
            "runs": [(program_name(n), (st - w0) * 1e-9, d * 1e-9)
                     for n, st, d in lines.get(MODULES_LINE, [])],
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "busy_intervals": busy,
            "programs": programs,
            "ops": ops,
        }
    if not devices:
        raise ValueError("trace has no /device:TPU plane")
    host = sorted((e for line, evs in planes.get(HOST_PLANE, {}).items()
                   if line.startswith("python") for e in evs),
                  key=lambda e: e[1])
    gaps: dict = {}
    first = devices[min(devices)]
    edges = [w0] + [t for iv in first["busy_intervals"] for t in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if (b - a) * 1e-9 >= min_gap_s:
            label = _host_label(host, (a + b) / 2)
            gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / len(devices),
        "devices": devices,
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1]),
    }


def program_seconds(reduced: dict, prefix: str) -> tuple:
    """(runs, device seconds) of the programs whose name starts with
    ``prefix``, whole runs inside the window, on the first device."""
    dev = reduced["devices"][min(reduced["devices"])]
    runs = secs = 0
    for name, (c, s) in dev["programs"].items():
        if name.startswith(prefix):
            runs += c
            secs += s
    return runs, secs


def op_seconds(reduced: dict, program_prefix: str, note: str) -> tuple:
    """(events, self seconds) of the ops of those programs whose note (custom
    call target or fusion kind) is ``note``, on the first device."""
    dev = reduced["devices"][min(reduced["devices"])]
    n = secs = 0
    for (prog, _res, _opcode, nt), (c, s) in dev["ops"].items():
        if prog.startswith(program_prefix) and nt == note:
            n += c
            secs += s
    return n, secs


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device ops that took most self time
    (first device) and the longest idle gaps by host frame."""
    dev = reduced["devices"][min(reduced["devices"])]
    # an op of a run cut by the window's edge goes to the program that has an
    # op of the same name, so that it does not take a second entry
    named = {k[1:]: k for k in dev["ops"] if k[0] != OUTSIDE}
    secs: dict = {}
    for k, (_c, s) in dev["ops"].items():
        k = named.get(k[1:], k)
        secs[k] = secs.get(k, 0.0) + s
    ops = sorted(secs.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[f"{p}/{r} {oc} {nt}".strip(), s]
                       for (p, r, oc, nt), s in ops],
        "idle_gaps": [[k, v] for k, v in reduced["idle_gaps"][:top]],
    }
