"""Median of submit -> done over the window's completions, times ``scale``
(1 for seconds, 1000 for ms)."""
import stats


def read(run, params):
    lat = [c["t_done"] - c["t_submit"] for c in run["result"]["completions"]]
    if not lat:
        return None
    return stats.median(lat) * float(params.get("scale", 1.0))
