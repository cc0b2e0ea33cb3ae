"""One caller, back-to-back blocking calls for the length of the window: the
traffic of ``caller.py``, with a traced part sized for a program whose calls
are short and whose chips are many.

``caller.py`` traces at least two seconds. The profiler's stop costs 20 to 40
us for every device op event, and four chips that run a 15-op iteration five
to twenty thousand times a second make one to two million events in two
seconds: the stop alone then takes a minute, and a traced run misses its time
limit (PERF.md section 6, PR 27). Here the traced last part of the window
holds ``trace_calls`` whole calls (the traffic file's), reckoned from the
length of the system's warm call with one call to spare, plus the two edges
that ``xplane.reduce`` leaves out of its per-program sums; at most half the
window. Three things keep that part as short as it is reckoned:

- the profiler's start (2.4 s on four chips, the chips idle) is not the
  system's time: the window closes that much later, so a traced window holds
  the calls an untraced one does, and the call after the start is timed from
  its own beginning;
- the trace is stopped as the window closes, before the kept answers are
  converted, so that the conversion's idle chips are not in it;
- nothing else differs from ``caller.py``: three answers of the window are
  kept for the comparison with the reference, the first, the last and a
  seeded one between."""

from __future__ import annotations

import random

import xplane


def run(sut, traffic: dict, seed: int, seconds: float, ctx) -> dict:
    clock = ctx.clock
    pick = random.Random(seed).randrange(1, 12)
    calls = int(traffic["trace_calls"]) + 1
    traced = min(seconds / 2, calls * ctx.spans.get("warm_call", 0.0)
                 + 2 * xplane.EDGE_NS * 1e-9)
    kept: dict = {}
    last = None
    completions = []
    failed = 0
    t0 = ctx.open_window(clock())
    end = t0 + seconds
    i = 0
    paused = 0.0  # the profiler's start, which the window is lengthened by
    while True:
        ts = clock()
        if ts >= end:
            break
        state = ctx.trace_state
        ctx.tick(ts - t0 - paused, seconds, traced)
        if ctx.trace_state != state:  # the profiler started: its time is its own
            paused = clock() - ts
            end += paused
            ts = clock()
        try:
            with ctx.annotate("bench.call"):
                out = sut.call()
        except Exception as e:  # noqa: BLE001 - a failed call is counted
            ctx.say(f"call {i} failed: {e!r}")
            failed += 1
            i += 1
            continue
        te = clock()
        completions.append({"index": i, "t_submit": ts, "t_done": te,
                            "iters": out["iters"]})
        if i in (0, pick):
            kept[i] = out
        last = (i, out)
        i += 1
    t1 = clock()
    ctx.stop_trace()
    if last is not None:
        kept.setdefault(*last)
    answers = []
    for idx, out in sorted(kept.items()):
        a = sut.answer(out)
        a["index"] = idx
        a["request"] = 0
        answers.append(a)
    return {"t0": t0, "t1": t1, "window_s": t1 - t0, "completions": completions,
            "attempted": i, "failed": failed, "answers": answers}
