"""The host's share of a pipeline's period, in percent, from the window's
recorded ``span`` events: over the dispatches (``seq``) that have every span
named in ``host`` and the span ``wait`` inside the window,

    100 * sum(host spans) / (sum(host spans) + sum(wait spans))

100: the host never waited for the device, it sets the pace; near 0: the
host's work hides behind the device's."""


def read(run, params):
    names = set(params["host"]) | {params["wait"]}
    by_seq: dict = {}
    for e in run["events"].get("span", []):
        if e.get("name") in names and "seq" in e and "dur_s" in e:
            by_seq.setdefault(e["seq"], {})[e["name"]] = e["dur_s"]
    whole = [d for d in by_seq.values() if set(d) == names]
    host = sum(d[n] for d in whole for n in params["host"])
    wait = sum(d[params["wait"]] for d in whole)
    return 100.0 * host / (host + wait) if host + wait > 0 else None
