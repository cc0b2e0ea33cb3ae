#!/usr/bin/env python3
"""Two sets of runs of one cell, with the same seeds in both sets, and the
spread of each end-to-end metric (builder's tool: the bounds in
BENCHMARK.json are set from what this prints; the driver measures anew).

    python3 benchmark/tools/sets.py --workload <name> --seeds 1 2 3 4 5 6 \
        [--sets 2] [--traced-seed 7] [--out chiprun_out/sets]

Every run is a process of its own, as the driver's are; this parent never
touches JAX. A spread is the distance between the first and third quartile
as a share of the median (statistics.quantiles, n=4)."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import stats  # noqa: E402


def one_run(workload, seed, seconds, trace, log):
    t = time.perf_counter()
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t
    log.write(p.stdout + "\n--- stderr tail ---\n" + p.stderr[-1500:] + "\n")
    log.flush()
    line = None
    if p.stdout.strip():
        try:
            line = json.loads(p.stdout.strip().splitlines()[-1])
        except ValueError:
            pass
    return p.returncode, wall, line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traced-seed", type=int, default=None)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "sets"))
    args = ap.parse_args()
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    seconds = args.seconds or bm["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, args.workload + ".log"), "a")
    rows = open(os.path.join(args.out, args.workload + ".jsonl"), "a")
    sets = []
    for s in range(args.sets):
        vals: dict = {}
        for seed in args.seeds:
            rc, wall, line = one_run(args.workload, seed, seconds, 0, log)
            row = {"set": s, "seed": seed, "rc": rc, "wall_s": round(wall, 1),
                   "line": line}
            rows.write(json.dumps(row) + "\n")
            rows.flush()
            ok = rc == 0 and line and line["correct"]
            print(f"set {s} seed {seed}: rc {rc} wall {wall:.0f} s "
                  + (json.dumps({k: v["value"] for k, v in
                                 line["metrics"].items()}) if line else "NO LINE")
                  + ("" if ok else "  <-- NOT CORRECT"), flush=True)
            if line:
                for k, v in line["metrics"].items():
                    vals.setdefault(k, []).append(v["value"])
        sets.append(vals)
    if args.traced_seed is not None:
        rc, wall, line = one_run(args.workload, args.traced_seed, seconds, 1, log)
        rows.write(json.dumps({"set": "traced", "seed": args.traced_seed,
                               "rc": rc, "wall_s": round(wall, 1),
                               "line": line}) + "\n")
        print(f"traced seed {args.traced_seed}: rc {rc} wall {wall:.0f} s "
              + json.dumps(line), flush=True)
    for k in sets[0]:
        per_set = []
        for s, vals in enumerate(sets):
            v = vals.get(k, [])
            if len(v) >= 2:
                per_set.append((stats.median(v), stats.spread(v), v))
        desc = "; ".join(f"set {i}: median {m:.6g} spread {100 * sp:.3f}%"
                         for i, (m, sp, _) in enumerate(per_set))
        # the first run of each set apart, as the driver reads setup_s
        later = "; ".join(
            f"set {i} without its first run: median {stats.median(v[1:]):.6g}"
            for i, (_, _, v) in enumerate(per_set) if len(v) > 2)
        widest = max((sp for _, sp, _ in per_set), default=float("nan"))
        print(f"{k}: {desc}; widest {100 * widest:.3f}% -> five times "
              f"{100 * 5 * widest:.2f}%" + (f"; {later}" if k == "setup_s" else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
