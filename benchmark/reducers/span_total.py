"""Total seconds the program spent in its span ``name`` since the process
started, from the program's own aggregate (``telemetry.summary()``): for
spans of set-up, which end before the window opens."""


def read(run, params):
    from sparse_tpu import telemetry

    agg = telemetry.summary().get("spans", {}).get(params["name"])
    return agg.get("total_s") if agg else None
