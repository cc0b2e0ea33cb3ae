"""Requests completed inside the window over the window's length."""


def read(run, params):
    res = run["result"]
    if not res["completions"]:
        return None
    return len(res["completions"]) / res["window_s"]
