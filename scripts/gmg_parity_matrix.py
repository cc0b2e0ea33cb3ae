"""GMG grid-pipeline vs generic-hierarchy parity matrix (VERDICT r4 #9).

Runs ``examples/gmg.py`` both ways — the structured-grid stencil pipeline
(``models/gmg_grid.py``, the default) and ``--no-grid`` (the generic
sparse-matrix hierarchy) — across a {n, levels, gridop} matrix on the CPU
backend, and compares:

- **iterations**: must MATCH, AND **residuals must agree** to 1% — for
  runs that hit the -maxiter cap the iteration count alone is vacuous,
  but an identical residual after the same number of iterations pins the
  whole CG trajectory (the stronger iterate-parity statement; small-n
  exact-iterate oracle in tests/test_gmg_grid.py);
- **init/solve speedup**: the CPU-measurable part of the r4 claim that the
  grid pipeline is ~3x faster, so the first live TPU window only needs to
  measure, not debug.

Writes ``results/gmg_parity_matrix.json`` and prints a table. Pure-CPU by
construction.

Run:  python scripts/gmg_parity_matrix.py [-quick]
"""

import argparse
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


MAXITER = 100


def run_one(n, levels, gridop, no_grid, maxiter=MAXITER):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [
        sys.executable, os.path.join(REPO, "examples", "gmg.py"),
        "-n", str(n), "-levels", str(levels), "-gridop", gridop,
        "-maxiter", str(maxiter),
    ]
    if no_grid:
        cmd.append("--no-grid")
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=1800, env=env, cwd=REPO
    )
    out = proc.stdout
    m_it = re.search(r"Iterations:\s+(\d+)\s+residual:\s+([0-9.e+-]+)", out)
    m_init = re.search(r"GMG init time:\s+([0-9.]+)\s+ms", out)
    m_solve = re.search(r"Solve time:\s+([0-9.]+)\s+ms", out)
    if not (m_it and m_init and m_solve):
        raise RuntimeError(
            f"unparseable gmg.py output (rc={proc.returncode}):\n"
            f"{out[-800:]}\n{proc.stderr[-800:]}"
        )
    return {
        "iters": int(m_it.group(1)),
        "residual": float(m_it.group(2)),
        "init_ms": float(m_init.group(1)),
        "solve_ms": float(m_solve.group(1)),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-quick", action="store_true", help="small-n subset")
    args = ap.parse_args()

    if args.quick:
        configs = [(128, 3, "linear"), (128, 3, "injection")]
    else:
        configs = [
            (n, lv, op)
            for n in (128, 256)
            for lv in (3, 5)
            for op in ("linear", "injection")
        ] + [(512, 5, "linear")]

    rows = []
    ok = True
    for n, lv, op in configs:
        grid = run_one(n, lv, op, no_grid=False)
        gen = run_one(n, lv, op, no_grid=True)
        iters_match = grid["iters"] == gen["iters"]
        resid_rel = abs(grid["residual"] - gen["residual"]) / max(
            abs(gen["residual"]), 1e-30
        )
        converged = grid["iters"] < MAXITER and gen["iters"] < MAXITER
        # capped rows: the residual IS the parity evidence (same count is
        # vacuous at the cap) — require near-exact agreement (observed
        # Δ0.0). Converged rows: both residuals sit at ~tol*||b|| where a
        # few percent of relative difference is FP noise between the
        # stencil and CSR formulations of the same tiny number; iteration
        # match is the parity statement, 5% residual agreement the sanity
        # bound.
        resid_match = resid_rel < (0.05 if converged else 1e-2)
        row_ok = iters_match and resid_match
        ok = ok and row_ok
        row = {
            "n": n, "levels": lv, "gridop": op,
            "iters_grid": grid["iters"], "iters_generic": gen["iters"],
            "iters_match": iters_match,
            "residual_grid": grid["residual"],
            "residual_generic": gen["residual"],
            "residual_rel_diff": float(f"{resid_rel:.2e}"),
            "residual_match": resid_match,
            "init_speedup": round(gen["init_ms"] / max(grid["init_ms"], 1e-9), 2),
            "solve_speedup": round(
                gen["solve_ms"] / max(grid["solve_ms"], 1e-9), 2
            ),
        }
        rows.append(row)
        print(
            f"n={n:4d} L={lv} {op:9s}  iters {grid['iters']:3d}"
            f"{'==' if iters_match else '!='}{gen['iters']:<3d}"
            f" resid Δ{resid_rel:.1e}{'ok' if resid_match else ' MISMATCH'}"
            f"  init x{row['init_speedup']:<6}  solve x{row['solve_speedup']}"
        )

    artifact = {"parity_ok": ok, "quick": bool(args.quick),
                "configs": [list(c) for c in configs], "rows": rows}
    os.makedirs(os.path.join(REPO, "results"), exist_ok=True)
    # quick smoke runs must not clobber the committed full-matrix evidence
    name = ("gmg_parity_matrix_quick.json" if args.quick
            else "gmg_parity_matrix.json")
    path = os.path.join(REPO, "results", name)
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"parity_ok={ok}  -> {path}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
