"""Solver iterations returned over the seconds the calls took."""


def read(run, params):
    comp = run["result"]["completions"]
    secs = sum(c["t_done"] - c["t_submit"] for c in comp)
    return sum(c["iters"] for c in comp) / secs if secs > 0 else None
