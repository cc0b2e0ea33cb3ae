"""Largest value of the program's always-on gauges ``name`` (every label
set), times ``scale``: read from the program's metrics registry after the
window, as ``span_total`` reads its span aggregates, for a level the program
sets once, in set-up, and no event of the window carries. No such gauge, or
none set, reads nothing."""


def read(run, params):
    from sparse_tpu import telemetry

    vals = [g.value for g in telemetry.metrics.family(params["name"])]
    vals = [v for v in vals if v]
    return max(vals) * float(params.get("scale", 1.0)) if vals else None
