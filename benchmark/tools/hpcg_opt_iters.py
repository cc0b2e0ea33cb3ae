#!/usr/bin/env python3
"""HPCG's optimised iteration count for the 8-colour sweep (builder's tool;
the benchmark's own runs never run it).

    python3 benchmark/tools/hpcg_opt_iters.py --sides 16 32 64 [--levels 4]

The specification lets an implementation reorder the unknowns, and makes it
pay: where its sweep order is not the reference's lexicographic one, a set
runs as many iterations as it needs to reach the residual reduction that the
reference reaches in 50, never fewer than 50. This reads that count for the
order ``hpcg-27pt-256`` runs (colour = 4 (z % 2) + 2 (y % 2) + x % 2, forward
``--forward``, by default 7 3 5 6 1 2 4 0, backward the reverse), in float64
on the host with numpy and scipy
alone, sharing no code with the program or with the benchmark's reference:

- the operator by HPCG's rule (26 on the diagonal, -1 to every neighbour
  inside the grid) as a scipy CSR matrix on each level's own grid;
- the V-cycle of ``ComputeMG_ref``: one symmetric Gauss-Seidel step from
  zero, injection of ``r - A x`` at the even points, the coarse correction
  added there, one more step; the coarsest level one step;
- the symmetric step two ways: ``lexicographic``, the two triangular solves
  of the matrix as it is; coloured, eight colour updates forward and eight
  backward, which is the two triangular solves of the matrix permuted colour
  by colour in the forward order;
- preconditioned CG on b = A 1 from x = 0, the recurrence's residual norm
  over its first, as ``CG_ref`` reports it.

Per side it prints the reference's relative residual after 50 iterations and
the smallest count, at least 50, at which the coloured order's is at or under
it. The last line is JSON."""

from __future__ import annotations

import argparse
import json
import time

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spl

SET = 50


def operator(nz: int, ny: int, nx: int) -> sp.csr_matrix:
    n = nz * ny * nx
    idx = np.arange(n).reshape(nz, ny, nx)
    rows, cols, vals = [], [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                here = idx[max(0, -dz):nz - max(0, dz),
                           max(0, -dy):ny - max(0, dy),
                           max(0, -dx):nx - max(0, dx)].ravel()
                rows.append(here)
                cols.append(here + (dz * ny + dy) * nx + dx)
                vals.append(np.full(here.size,
                                    26.0 if (dz, dy, dx) == (0, 0, 0) else -1.0))
    return sp.csr_matrix((np.concatenate(vals),
                          (np.concatenate(rows), np.concatenate(cols))),
                         shape=(n, n))


class Level:
    def __init__(self, dims):
        nz, ny, nx = dims
        self.dims = dims
        self.A = operator(*dims)
        self.diag = self.A.diagonal()
        # lexicographic: the two triangular factors, solved by SuperLU with
        # no reordering and no pivoting (a triangular matrix is its own
        # factor)
        kw = dict(permc_spec="NATURAL", diag_pivot_thresh=0.0,
                  options=dict(SymmetricMode=False))
        self.lower = spl.splu(sp.tril(self.A, 0).tocsc(), **kw)
        self.upper = spl.splu(sp.triu(self.A, 0).tocsc(), **kw)
        self.strict_lower = sp.tril(self.A, -1).tocsr()
        self.strict_upper = sp.triu(self.A, 1).tocsr()
        # coloured: the rows of each colour
        z, y, x = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                              indexing="ij")
        colour = (4 * (z % 2) + 2 * (y % 2) + x % 2).ravel()
        self.rows = [np.flatnonzero(colour == c) for c in range(8)]
        self.blocks = [self.A[r] for r in self.rows]
        # injection: the even points, in the coarse grid's own order
        self.even = np.flatnonzero(colour == 0)

    def symgs(self, r, x, order):
        if order == "lexicographic":
            x = self.lower.solve(r - self.strict_upper @ x)
            return self.upper.solve(r - self.strict_lower @ x)
        x = x.copy()
        for c in tuple(order) + tuple(order)[::-1]:
            rows = self.rows[c]
            d = self.diag[rows]
            x[rows] = (r[rows] - self.blocks[c] @ x + d * x[rows]) / d
        return x


def vcycle(levels, r, order, lvl=0):
    L = levels[lvl]
    x = L.symgs(r, np.zeros_like(r), order)
    if lvl == len(levels) - 1:
        return x
    rc = (r - L.A @ x)[L.even]
    x[L.even] += vcycle(levels, rc, order, lvl + 1)
    return L.symgs(r, x, order)


def residuals(levels, order, iterations: int) -> list:
    """Relative residual norms after 1, 2, ... iterations of CG."""
    A = levels[0].A
    b = A @ np.ones(A.shape[0])
    x = np.zeros_like(b)
    r = b.copy()
    norm0 = np.linalg.norm(r)
    out = []
    p = rho = None
    for _ in range(iterations):
        z = vcycle(levels, r, order)
        rho_new = r @ z
        p = z if p is None else z + (rho_new / rho) * p
        rho = rho_new
        q = A @ p
        alpha = rho / (p @ q)
        x += alpha * p
        r -= alpha * q
        out.append(float(np.linalg.norm(r) / norm0))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sides", type=int, nargs="+", required=True)
    ap.add_argument("--levels", type=int, default=4)
    ap.add_argument("--forward", type=int, nargs=8,
                    default=[7, 3, 5, 6, 1, 2, 4, 0],
                    help="the forward sweep's order of the colours")
    ap.add_argument("--most", type=int, default=100,
                    help="iterations of the coloured order at most")
    args = ap.parse_args()
    readings = {}
    for side in args.sides:
        t = time.perf_counter()
        levels = [Level((side >> k,) * 3) for k in range(args.levels)]
        target = residuals(levels, "lexicographic", SET)[-1]
        coloured = residuals(levels, tuple(args.forward), args.most)
        count = next((i + 1 for i, rr in enumerate(coloured)
                      if i + 1 >= SET and rr <= target), None)
        readings[str(side)] = {
            "levels": args.levels, "forward": list(args.forward),
            "reference_relres_at_50": target,
            "coloured_relres_at_50": coloured[SET - 1], "iterations": count,
            "coloured_relres_at_count": coloured[count - 1] if count else None}
        print(f"side {side}, {args.levels} levels: reference relres at 50 "
              f"{target:.3e}, coloured at 50 {coloured[SET - 1]:.3e}, "
              f"optimised count {count} ({time.perf_counter() - t:.0f} s)",
              flush=True)
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
