"""Closed loop: ``clients`` callers, each submits one request, waits for its
result, then submits its next, which the system makes from that result. One
driver thread plays every client, as the session is driven from the thread
that owns it: it blocks on the oldest outstanding ticket (which drives the
pipeline), then collects every client whose ticket has resolved and sends
that client's next request.

The loop starts during set-up: the window opens once every client has had
its first request resolved (the pipeline is full and every program compiled),
at the instant of that resolution. Tickets resolve a bucket at a
time, so a window cut at a fixed instant would count one bucket more or fewer
by chance. The window therefore lasts at least ``seconds`` and closes with the
first tickets that resolve at or after that: they count, and the window's
length is the time to that instant, a whole number of the pipeline's periods.
Every ticket that resolves inside the window counts, with its latency from
its own submission. Tickets still out at the close are drained after it and
counted as attempted only."""

from __future__ import annotations


def run(sut, traffic: dict, seed: int, seconds: float, ctx) -> dict:
    clock = ctx.clock
    clients = int(traffic["clients"])
    resolved = [0] * clients
    sent = before = failed = 0
    outstanding = []  # [client, ticket, t_submit]
    completions, answers = [], []
    t0 = end = closed_at = None

    def send(client):
        nonlocal sent
        sent += 1
        outstanding.append([client, sut.submit(client), clock()])

    t_ramp = clock()
    with ctx.annotate("bench.submit"):
        for c in range(clients):
            send(c)
            sut.kick()
    while outstanding:
        if t0 is not None:
            ctx.tick(clock() - t0, seconds, float(traffic["trace_seconds"]))
        with ctx.annotate("bench.wait"):
            sut.wait(outstanding[0][1])
        now = clock()
        still, free = [], []
        for o in outstanding:
            ok = sut.outcome(o[1])
            if ok is None:
                still.append(o)
                continue
            if not ok:
                failed += 1
            elif closed_at is None:
                a = sut.answer(o[1])  # also makes it the client's state
                if t0 is not None:
                    answers.append(a)
                    completions.append({"index": len(completions),
                                        "t_submit": o[2], "t_done": now,
                                        "iters": a["iters"],
                                        "phase_ms": a.pop("phase_ms", {})})
            resolved[o[0]] += 1
            free.append(o[0])
        if len(still) == len(outstanding):
            # the oldest ticket neither resolved nor failed: nothing moves
            failed += len(still)
            ctx.say(f"{len(still)} tickets never resolved")
            break
        outstanding[:] = still
        if t0 is None and min(resolved) >= 1:
            t0 = ctx.open_window(now)
            end = t0 + seconds
            before = sum(resolved)
            ctx.add_span("ramp", t0 - t_ramp)
        elif t0 is not None and closed_at is None and now >= end:
            closed_at = now
        if closed_at is None:
            with ctx.annotate("bench.submit"):
                for c in free:
                    send(c)
                    sut.kick()
    if t0 is None:
        t0 = ctx.open_window(clock())
    t1 = closed_at if closed_at is not None else clock()
    sut.drain()
    return {"t0": t0, "t1": t1, "window_s": t1 - t0, "completions": completions,
            "attempted": sent - before, "failed": failed, "answers": answers}
