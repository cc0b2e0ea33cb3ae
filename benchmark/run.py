#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by name, makes its data from the seed, builds the
system under test through the calls a user makes, warms up the cell's shapes
(all of that is ``setup_s``), measures for ``--seconds``, then decides
``correct`` against the plain reference outside the window. The last line of
standard output is the result; everything else worth reading is above it.

It refuses to run (exit 2, no result line) unless JAX reports a TPU with
exactly the cell's chip count. ``--rehearse`` is the builder's CPU rehearsal
at the configuration's ``rehearse`` sizes: it reports no metric and always
ends ``"correct": false``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402
import xplane  # noqa: E402


class Span:
    seconds = 0.0


class Context:
    """What the harness hands to systems, loops and reducers: its clock, its
    own spans, the switch of the program's telemetry recorder, the compile
    counters and the profiler."""

    clock = staticmethod(time.perf_counter)

    def __init__(self, trace: bool):
        self.trace = trace
        self.spans: dict = {}
        self.checks: list = []
        self._compile = {"requests": 0, "hits": 0, "seconds": 0.0}
        self._trace_dir = None
        self._traced = None
        self.trace_state = "off" if not trace else "armed"
        self.tmp = tempfile.mkdtemp(prefix="bench-")

    def say(self, msg: str) -> None:
        print(msg, flush=True)

    # -- the benchmark's own spans ---------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        s = Span()
        t = self.clock()
        try:
            yield s
        finally:
            s.seconds = self.clock() - t
            self.add_span(name, s.seconds)

    def add_span(self, name: str, seconds: float) -> None:
        self.spans[name] = self.spans.get(name, 0.0) + seconds

    def guarantee(self, name: str, value: float) -> None:
        """A guarantee the configuration states, as an exact comparison:
        ``value`` counts violations and its limit is 0."""
        self.checks.append({"name": name, "value": float(value), "limit": 0.0,
                            "ok": float(value) <= 0.0})

    # -- the program's telemetry recorder --------------------------------
    def events_on(self) -> None:
        from sparse_tpu import telemetry
        from sparse_tpu.config import settings

        settings.telemetry = True
        telemetry.configure(os.path.join(self.tmp, "telemetry.jsonl"))

    def events_default(self) -> None:
        """Back to what a user has by default, except in a traced run."""
        from sparse_tpu.config import settings

        if not self.trace:
            settings.telemetry = False

    # -- compiles ----------------------------------------------------------
    def listen_for_compiles(self) -> None:
        from jax import monitoring

        def on_event(name, **_):
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                self._compile["requests"] += 1
            elif name == "/jax/compilation_cache/cache_hits":
                self._compile["hits"] += 1

        def on_duration(name, secs, **_):
            if name.startswith("/jax/core/compile/"):  # trace, lower, compile
                self._compile["seconds"] += secs

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    def compiles(self) -> int:
        """Programs compiled so far, not counting persistent-cache hits."""
        return self._compile["requests"] - self._compile["hits"]

    def compile_seconds(self) -> float:
        return self._compile["seconds"]

    # -- the profiler --------------------------------------------------------
    def annotate(self, name: str):
        if self.trace_state != "on":
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_window(self, t: float) -> float:
        """Called by the loop at the instant ``t`` (on ``clock``) at which
        its window opens: everything before it is set-up."""
        self.window_t0 = t
        self.window_wall0 = time.time() - (self.clock() - t)
        self.window_compiles0 = self.compiles()
        return t

    def tick(self, elapsed: float, seconds: float, traced: float) -> None:
        """Called by the loop between requests: traces the last ``traced``
        seconds of the window and stops at its end."""
        if self.trace_state == "armed" and elapsed >= seconds - traced:
            import jax

            self._trace_dir = os.path.join(self.tmp, "trace")
            jax.profiler.start_trace(self._trace_dir)
            self._traced = jax.profiler.TraceAnnotation(xplane.WINDOW_ANNOTATION)
            self._traced.__enter__()
            self.trace_state = "on"
        elif self.trace_state == "on" and elapsed >= seconds:
            self.stop_trace()

    def stop_trace(self) -> None:
        if self.trace_state == "on":
            import jax

            self._traced.__exit__(None, None, None)
            self.trace_state = "done"
            jax.profiler.stop_trace()

    def reduced_trace(self):
        if self.trace_state != "done":
            return None
        return xplane.reduce(xplane.find_xplane(self._trace_dir))

    def close(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)


def window_events(t0_wall: float, t1_wall: float) -> dict:
    """The program's recorded events of the window, by kind."""
    from sparse_tpu import telemetry

    out: dict = {}
    for e in telemetry.events():
        if t0_wall <= e.get("ts", 0.0) <= t1_wall:
            out.setdefault(e["kind"], []).append(e)
    return out


def read_metrics(group: str, specs: list, run: dict, say) -> dict:
    out = {}
    for m in specs:
        read, params = manifest.metric_reader(group, m["name"])
        value = read(run, params)
        if value is None:
            say(f"  {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(args, on_result=None) -> tuple:
    """(exit code, result line or None). ``on_result`` is for the tests under
    this directory, which look at the whole run record."""
    cell = manifest.cell(args.workload, rehearse=args.rehearse)
    cfg, traffic, wl = cell["config"], cell["traffic"], cell["workload"]
    sizes = cfg["sizes"]
    ctx = Context(bool(args.trace))
    say = ctx.say
    try:
        with ctx.span("imports"):
            import jax

            devs = jax.devices()
            device = {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)}
        on_chip = device["platform"] == "tpu" and device["count"] == wl["chips"]
        say(f"cell {wl['name']}: config {wl['config']}, traffic "
            f"{wl['traffic']}, seed {args.seed}, {args.seconds} s, trace "
            f"{args.trace}; device {device}")
        if not on_chip and not args.rehearse:
            print(f"refused: need platform 'tpu' with {wl['chips']} device(s), "
                  f"have {device}", file=sys.stderr, flush=True)
            return 2, None
        if args.rehearse:
            say("REHEARSAL at the configuration's 'rehearse' sizes: no metric "
                "is reported and the run ends correct=false")
            from sparse_tpu.config import settings as _s

            _s.fused_cg = "force"  # run the fused chunk logic in interpret mode
        with ctx.span("imports"):
            from sparse_tpu.telemetry import _metrics
            from sparse_tpu.utils import enable_compilation_cache

            enable_compilation_cache()
            ctx.listen_for_compiles()
        say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
        failovers = _metrics.counter("kernel.failovers")
        requeues = _metrics.counter("batch.requeues")
        f0, q0 = failovers.value, requeues.value

        operator = manifest.load_module("operators", cfg["operator"])
        system = manifest.load_module("systems", cfg["system"])
        loop = manifest.load_module("loops", traffic["loop"])
        with ctx.span("data"):
            data = operator.make(sizes, args.seed)
        if args.trace:
            ctx.events_on()
        sut = system.System(cfg, data, ctx)
        sut.warm()

        # -- the window, which the loop opens ----------------------------
        result = loop.run(sut, traffic, args.seed, float(args.seconds), ctx)
        ctx.stop_trace()
        w0, w1 = ctx.window_wall0, time.time()
        compiles_in_window = ctx.compiles() - ctx.window_compiles0
        setup_s = ctx.window_t0 - T_START
        say("setup_s %.3f = " % setup_s + ", ".join(
            f"{k} {v:.3f}" for k, v in ctx.spans.items()))
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
        events = window_events(w0, w1) if args.trace else {}
        if args.trace and hasattr(sut, "check_events"):
            sut.check_events(events)
        say(f"window {result['window_s']:.3f} s: {len(result['completions'])} "
            f"completed, {result['attempted']} attempted, {result['failed']} "
            f"failed, {compiles_in_window} compiles, {len(result['answers'])} "
            "answers kept for the comparison")
        instants = sorted({round(c["t_done"] - result["t0"], 3)
                           for c in result["completions"]})
        say(f"completion instants (s into the window): {instants[:40]}")
        lat = sorted(c["t_done"] - c["t_submit"] for c in result["completions"])
        if lat:
            say("submit -> done (s): min %.4f, quartiles %.4f %.4f %.4f, max %.4f"
                % (lat[0], *(lat[len(lat) * k // 4] for k in (1, 2, 3)), lat[-1]))
        sut.close()

        # -- correct, outside the window ------------------------------------
        ctx.guarantee("kernel_failovers", failovers.value - f0)
        ctx.guarantee("batch_requeues", requeues.value - q0)
        ctx.guarantee("compiles_in_window", compiles_in_window)
        ctx.guarantee("failed", result["failed"])
        ctx.guarantee("nothing_completed", 0.0 if result["answers"] else 1.0)
        t = ctx.clock()
        checks = ctx.checks + operator.check(data, result["answers"],
                                             cfg["limits"], say)
        say(f"reference and comparison took {ctx.clock() - t:.2f} s")
        for c in checks:
            say(f"  check {c['name']}: {c['value']:.6e} (limit "
                f"{c['limit']:.6e}) {'ok' if c['ok'] else 'FAILED'}")
        checks_ok = all(c["ok"] for c in checks)

        run = {
            "cell": cell, "sizes": sizes, "shape": sut.shape,
            "result": result, "spans": ctx.spans,
            "events": events, "setup_s": setup_s, "checks": checks,
            "checks_ok": checks_ok, "trace": None, "peaks": None,
        }
        line = {"correct": bool(checks_ok and on_chip),
                "attempted": int(result["attempted"]),
                "failed": int(result["failed"]), "metrics": {},
                "device": dict(device, memory_peak_bytes=int(peak))}
        if on_chip:
            run["peaks"] = manifest.peaks(device["kind"])
            if args.trace:
                run["trace"] = tr = ctx.reduced_trace()
                line["metrics"] = read_metrics(
                    "layer_metrics", cell["per_layer"], run, say)
                line["device"].update(busy_s=tr["busy_s"],
                                      window_s=tr["window_s"])
                line["breakdown"] = xplane.breakdown(tr)
                runs = tr["devices"][min(tr["devices"])]["runs"]
                say("program runs on the first device (name, s into the "
                    "traced window, s): " + ", ".join(
                        f"{n} {st:.3f} {d:.3f}" for n, st, d in runs[:24]))
            else:
                line["metrics"] = read_metrics(
                    "end_to_end", cell["end_to_end"], run, say)
        if on_result is not None:
            on_result(run)
        return (0 if line["correct"] else 1), line
    finally:
        ctx.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    code, line = run_cell(args)
    if line is not None:
        print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
