"""Pad lanes over lanes dispatched, in percent, from the window's
``batch.dispatch`` events."""


def read(run, params):
    evs = run["events"].get("batch.dispatch", [])
    lanes = sum(e["bucket"] for e in evs)
    return 100.0 * sum(e["pad_waste"] for e in evs) / lanes if lanes else None
