#!/usr/bin/env python3
"""Drive one rehearsal run of a cell with the timed path broken underneath,
or unbroken, and print what the comparison decided (helper of
test_benchmark.py; a process of its own so that each run starts JAX afresh
and a four-chip cell can ask for four virtual devices).

``--break answer`` alters every answer where the program produces it (scaled
by 1 + 1e-2). ``--break stale`` makes the solver return its starting state
unchanged (zeros)."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


def break_program(how: str) -> None:
    from sparse_tpu import linalg
    from sparse_tpu.batch import service

    def alter(x):
        return x * 0 if how == "stale" else x * (1.0 + 1e-2)

    cg, result = linalg.cg, service.SolveTicket.result

    def broken_cg(*a, **k):
        x, it = cg(*a, **k)
        return alter(x), it

    def broken_result(self, timeout=None):
        x, it, r2 = result(self, timeout)
        return alter(x), it, r2

    linalg.cg = broken_cg
    service.SolveTicket.result = broken_result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--break", dest="how", default="none",
                    choices=("none", "answer", "stale"))
    args = ap.parse_args()
    import run as harness

    if args.how != "none":
        break_program(args.how)
    seen = {}
    ns = argparse.Namespace(workload=args.workload, seed=2147483659,
                            seconds=1.0, trace=0, rehearse=True)
    code, line = harness.run_cell(ns, on_result=seen.update)
    print(json.dumps({
        "exit": code, "correct": line["correct"],
        "checks_ok": seen["checks_ok"],
        "failed_checks": [c["name"] for c in seen["checks"] if not c["ok"]],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
