"""Device microseconds one solver iteration takes inside a compiled program:

    device seconds of the program's whole runs / iterations those runs made

on the first device, over the traced window. ``program``: prefix of the
compiled program's name in the trace. ``iterations``: ``{"event": kind,
"field": f}``, the mean of the field over the window's events, times the
program's whole runs in the trace. For a loop that no one roof bounds (state
that partly stays on chip, collectives on the critical path), where a share
of a roofline would name a roof the loop is not held to. A program that is
not in the trace, as on a commit that has no such program, reads nothing."""
import xplane


def read(run, params):
    tr = run["trace"]
    if tr is None:
        return None
    runs, secs = xplane.program_seconds(tr, params["program"])
    it = params["iterations"]
    vals = [e[it["field"]] for e in run["events"].get(it["event"], [])]
    if not secs or not vals or not sum(vals):
        return None
    iterations = runs * sum(vals) / len(vals)
    print(f"  {params['program']}: {runs} runs, {iterations:g} iterations in "
          f"{secs:.6f} s of device time", flush=True)
    return 1e6 * secs / iterations
