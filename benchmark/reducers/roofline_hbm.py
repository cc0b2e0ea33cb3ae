"""A memory-bound program's share of the HBM roofline, in percent:

    bytes the iterations must move / device seconds / peak bytes per second

``program``: prefix of the compiled program's name in the trace. ``op_note``:
when given, only the ops of that program with this custom-call target are the
kernel and their self time is the time; otherwise the program's whole device
time. ``bytes``: module under ``bytes/`` whose ``bytes_per_iteration`` takes
the shape values named in ``bytes_args``. ``iterations``: either
``{"ops_per_iteration": k}`` (kernel events in the trace / k) or
``{"event": kind, "field": f}`` (mean of the field over the window's events,
times the program's whole runs in the trace)."""
import manifest
import xplane


def read(run, params):
    tr = run["trace"]
    if tr is None:
        return None
    runs, secs = xplane.program_seconds(tr, params["program"])
    it = params["iterations"]
    if "op_note" in params:
        n_ops, secs = xplane.op_seconds(tr, params["program"], params["op_note"])
        iterations = n_ops / float(it["ops_per_iteration"])
    else:
        vals = [e[it["field"]] for e in run["events"].get(it["event"], [])]
        iterations = runs * sum(vals) / len(vals) if vals else 0
    if not secs or not iterations:
        return None
    per_it = manifest.load_module("bytes", params["bytes"]).bytes_per_iteration(
        *(run["shape"][k] for k in params["bytes_args"]))
    print(f"  {params['program']}: {iterations:g} iterations x {per_it} B in "
          f"{secs:.6f} s of device time", flush=True)
    return 100.0 * per_it * iterations / secs / run["peaks"]["hbm_bytes_per_s"]
