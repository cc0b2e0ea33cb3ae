"""Share of a program's device time that its ops of one kind take, in
percent:

    self time of the program's ops whose note (a custom call's target, a
    fusion's kind) is ``note`` / device seconds of the program's whole runs

on the first device, both over the traced window. ``program``: prefix of the
compiled program's name in the trace. For ops the trace tells apart by their
note alone: the TPU compiler makes each gather of a padded-row product a
``fusion`` of kind ``kCustom``, and every other fusion of that program is a
``kLoop``, so their opcode is shared (``collective_share`` would count both).
The note is a compiler's artefact, not the operation: a metric that uses this
reducer says in its file for which compiled ops the note was checked against
the HLO (``kCustom`` is also the kind of the bucket program's value gather).
A program that is not in the trace, as on a commit that has no such program,
reads nothing."""
import xplane


def read(run, params):
    tr = run["trace"]
    if tr is None:
        return None
    _runs, secs = xplane.program_seconds(tr, params["program"])
    if not secs:
        return None
    n, part = xplane.op_seconds(tr, params["program"], params["note"])
    print(f"  {params['program']}: {n} ops of note {params['note']}, "
          f"{part:.6f} s of {secs:.6f} s of device time", flush=True)
    return 100.0 * part / secs
