"""Share of a program's device time that its ops under one ``jax.named_scope``
take, in percent:

    self time of the program's ops whose HLO ``op_name`` matches ``scope`` /
    device seconds of the program's whole runs

on the first device, both over the traced window. ``program``: prefix of the
compiled program's name in the trace; ``scope``: a regular expression searched
in the op's ``op_name`` (``jit(pcg)/while/body/gmg.l1/sub``).

Where the scope comes from. An ``XLA Ops`` event of a TPU trace is named by
the op's HLO text, which holds the result's name and shapes and no scope (read
by hand in ``testdata/cg_1024_two_calls.xplane.pb``, PR 40; the trace file
keeps the HLO of the host's programs only), and shapes do not tell a V-cycle's
fine-level stencil from CG's own ``A p``. The compiled program's text does:
every instruction the tracer named carries ``metadata={op_name="..."}`` with
the scopes of the Python that made it, under the result name the trace's
event has. The system's adaptor hands that text over among the run's events
(``program.hlo``: ``{"program", "text"}``), of the very executable the window
ran. The compiler's own ops (copies, the start/done pairs of its prefetches
and the custom calls that join them) carry no ``op_name`` and match no scope:
the reducer prints their time so that it is seen. A run without the text, or
a program that is not in the trace, as on a commit without it, reads
nothing."""
import re

import xplane

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of a compiled program's text; an
    instruction without metadata maps to ''. The instructions inside fused
    computations are in it too: a module's names are unique, so none of
    them shadows an op the trace shows."""
    out: dict = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            n = OP_NAME.search(line)
            out[m.group(1)] = n.group(1) if n else ""
    return out


def scope_seconds(reduced: dict, names: dict, program_prefix: str,
                  scope: str) -> tuple:
    """(seconds under the scope, seconds of ops without an ``op_name``,
    seconds of all ops) of the programs' ops on the first device of a
    reduced trace (``xplane.reduce``: self times inside whole runs)."""
    dev = reduced["devices"][min(reduced["devices"])]
    pat = re.compile(scope)
    mine = unnamed = total = 0.0
    for (prog, res, _opcode, _note), (_c, s) in dev["ops"].items():
        if not prog.startswith(program_prefix):
            continue
        total += s
        op_name = names.get(res, "")
        if not op_name:
            unnamed += s
        elif pat.search(op_name):
            mine += s
    return mine, unnamed, total


def read(run, params):
    tr = run["trace"]
    texts = [e["text"] for e in run["events"].get("program.hlo", [])
             if e.get("program", "").startswith(params["program"])]
    if tr is None or not texts:
        return None
    _runs, secs = xplane.program_seconds(tr, params["program"])
    if not secs:
        return None
    names = op_names(texts[0])
    mine, unnamed, total = scope_seconds(tr, names, params["program"],
                                         params["scope"])
    pat = re.compile(params["scope"])
    dev = tr["devices"][min(tr["devices"])]
    largest = sorted(((s, res) for (prog, res, _o, _n), (_c, s) in dev["ops"].items()
                      if prog.startswith(params["program"])
                      and pat.search(names.get(res, ""))), reverse=True)[:12]
    print(f"  {params['program']}: {mine:.6f} s under {params['scope']!r}, "
          f"{unnamed:.6f} s in ops without an op_name, {total:.6f} s in all "
          f"ops, {secs:.6f} s of device time; the largest under the scope: "
          + ", ".join(f"{res} {s:.6f}" for s, res in largest), flush=True)
    return 100.0 * mine / secs
