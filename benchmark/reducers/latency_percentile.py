"""Nearest-rank percentile ``q`` of submit -> done over all of the window's
completions, times ``scale``."""
import stats


def read(run, params):
    lat = [c["t_done"] - c["t_submit"] for c in run["result"]["completions"]]
    if not lat:
        return None
    print(f"  latency percentile {params['q']} over {len(lat)} completions",
          flush=True)
    return stats.percentile(lat, float(params["q"])) * float(params.get("scale", 1.0))
