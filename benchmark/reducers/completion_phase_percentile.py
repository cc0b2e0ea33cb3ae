"""Nearest-rank percentile ``q`` of one phase of the program's per-ticket
``phase_ms`` over the window's completions."""
import stats


def read(run, params):
    vals = [c["phase_ms"][params["phase"]] for c in run["result"]["completions"]
            if params["phase"] in c.get("phase_ms", {})]
    return stats.percentile(vals, float(params["q"])) if vals else None
