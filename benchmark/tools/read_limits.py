#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (builder's tool; the
benchmark's own runs never run it).

    python3 benchmark/tools/read_limits.py --workload <name> --seeds 11 12 ...

One process, one set-up: for each seed it re-seeds the data, drives the
cell's loop for a short window at the cell's own load, and prints every
compared number for the program's answers and for the control's (the plain
reference in the program's place, in bfloat16). ``--rehearse`` as in run.py.
The last lines give, per number, the largest sound reading and the smallest
control reading: a limit goes between them, with room on both sides."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402
import run as harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.cell(args.workload, rehearse=args.rehearse)
    cfg, traffic = cell["config"], cell["traffic"]
    import jax

    print("devices", jax.devices(), flush=True)
    from sparse_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    if args.rehearse:
        from sparse_tpu.config import settings

        settings.fused_cg = "force"
    ctx = harness.Context(False)
    ctx.listen_for_compiles()
    operator = manifest.load_module("operators", cfg["operator"])
    system = manifest.load_module("systems", cfg["system"])
    loop = manifest.load_module("loops", traffic["loop"])
    loose = {k: float("inf") for k in cfg["limits"]}
    sound, control = {}, {}
    sut = None
    for i, seed in enumerate(args.seeds):
        data = operator.make(cfg["sizes"], seed)
        quiet = lambda *_: None  # noqa: E731
        if sut is None:
            sut = system.System(cfg, data, ctx)
            sut.warm()
        else:
            sut.reseed(data)
        res = loop.run(sut, traffic, seed, args.seconds, ctx)
        for c in operator.check(data, res["answers"], loose, quiet):
            sound.setdefault(c["name"], []).append(c["value"])
        row = {"seed": seed, "answers": len(res["answers"]),
               "sound": {k: v[-1] for k, v in sound.items()}}
        if i < args.control_seeds:
            ctl = operator.control_answers(data, res["answers"])
            for c in operator.check(data, ctl, loose, quiet):
                control.setdefault(c["name"], []).append(c["value"])
            row["control"] = {k: v[-1] for k, v in control.items()}
        print(json.dumps(row), flush=True)
    for k in sound:
        line = f"{k}: sound max {max(sound[k]):.6e} over {len(sound[k])} seeds"
        if control.get(k):
            line += (f"; control min {min(control[k]):.6e} over "
                     f"{len(control[k])} seeds; ratio "
                     f"{min(control[k]) / max(max(sound[k]), 1e-300):.3g}")
        print(line, flush=True)
    ctx.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
