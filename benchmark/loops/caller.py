"""One caller, back-to-back blocking calls for the length of the window.

Three answers of the window are kept for the comparison with the reference
(the first, the last and a seeded one between), since each answer of a
library cell is as large as the problem. The traced last part of the window
holds about eight whole calls, reckoned from the length of the system's warm
call: at least two seconds and at most half the window."""

from __future__ import annotations

import random


def run(sut, traffic: dict, seed: int, seconds: float, ctx) -> dict:
    clock = ctx.clock
    pick = random.Random(seed).randrange(1, 12)
    traced = min(seconds / 2, max(2.0, 10.0 * ctx.spans.get("warm_call", 0.0)))
    kept: dict = {}
    last = None
    completions = []
    failed = 0
    t0 = ctx.open_window(clock())
    end = t0 + seconds
    i = 0
    while True:
        ts = clock()
        if ts >= end:
            break
        ctx.tick(ts - t0, seconds, traced)
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
