"""Median of ``field`` (default ``dur_s``) over the window's recorded
``span`` events named ``name``, times ``scale`` (1000 for ms)."""
import stats


def read(run, params):
    field = params.get("field", "dur_s")
    vals = [e[field] for e in run["events"].get("span", [])
            if e.get("name") == params["name"] and field in e]
    return stats.median(vals) * float(params.get("scale", 1.0)) if vals else None
