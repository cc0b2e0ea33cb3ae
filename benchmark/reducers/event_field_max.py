"""Largest ``field`` over the window's recorded events of ``kind``:
``event_field_median``'s twin, for what one outlier of the window says (a
stalled period) and a median hides."""


def read(run, params):
    vals = [e[params["field"]] for e in run["events"].get(params["kind"], [])
            if params["field"] in e]
    return max(vals) if vals else None
