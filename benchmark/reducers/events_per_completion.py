"""Recorded events of ``kind`` in the window per completed request."""


def read(run, params):
    evs = run["events"].get(params["kind"], [])
    n = len(run["result"]["completions"])
    return len(evs) / n if evs and n else None
