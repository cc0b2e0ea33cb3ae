"""Median of ``field`` over the window's recorded events of ``kind``."""
import stats


def read(run, params):
    vals = [e[params["field"]] for e in run["events"].get(params["kind"], [])
            if params["field"] in e]
    return stats.median(vals) if vals else None
