"""Share of a program's device time that its collectives take, in percent:

    self time of the program's ops whose opcode starts with one of
    ``opcodes`` / device seconds of the program's whole runs

on the first device, both over the traced window. ``program``: prefix of the
compiled program's name in the trace. The self time of an asynchronous
collective's ``-start`` and ``-done`` ops both count: what overlaps other
work is not in them. A program that is not in the trace, as on a commit
that has no such program, reads nothing."""
import xplane


def read(run, params):
    tr = run["trace"]
    if tr is None:
        return None
    _runs, secs = xplane.program_seconds(tr, params["program"])
    if not secs:
        return None
    dev = tr["devices"][min(tr["devices"])]
    prefixes = tuple(params["opcodes"])
    n = coll = 0
    for (prog, _res, opcode, _note), (c, s) in dev["ops"].items():
        if prog.startswith(params["program"]) and opcode.startswith(prefixes):
            n += c
            coll += s
    print(f"  {params['program']}: {n} collective ops, {coll:.6f} s of "
          f"{secs:.6f} s of device time", flush=True)
    return 100.0 * coll / secs
