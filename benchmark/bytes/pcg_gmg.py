"""Bytes one V-cycle-preconditioned CG iteration on the grid hierarchy of
``examples/gmg.py`` must move: a fine grid of side ``grid`` (N = grid^2
points, five scalar coefficients), ``levels`` levels, each coarser level of
half the side with nine coefficient planes and a weight plane.

Counted low, so that the share stays under 100 %: an array counts only where
the data flow cannot avoid it. A vector that a stencil or a transfer reads at
neighbouring points, or that crosses a level, has to exist: written once,
read once. A coefficient plane is read once a stencil apply, the weight plane
once a smoothing step; scalars are free; everything else (the products q and
A x, the dot products, a second read of an array already counted) is
transient in a perfect fusion.

- CG's own recurrence, 8 N values: x, r and p read and written, z read, p
  read once more for A p.
- A level above the coarsest, n points: the residual and the corrected x
  written and read (4 n), the level's output written (n; on the fine level
  its read is CG's z). The fine level's smoothed x is w r with a scalar w and
  needs no array; a coarser level's is written and read (2 n), its input
  written by the restriction and read (2 n), its output read by the
  prolongation (n), its nine planes read in each of the two stencil applies
  (18 n), its weight plane in each of the two smoothing steps (2 n).
- The coarsest level, one smoothing step: input written and read, the weight
  plane read, the output written and read (5 n).

At grid 4500 and three levels: 8 + 5 + 30/4 + 5/16 = 20.81 N values,
1.686 GB an iteration."""


def bytes_per_iteration(grid: int, levels: int, itemsize: int = 4) -> int:
    values = 8 * grid * grid
    n = grid
    for lvl in range(levels):
        fine, coarsest = lvl == 0, lvl == levels - 1
        if coarsest:
            per_point = 1 if fine else 5  # one level: z = w r, w a scalar
        else:
            per_point = 5 if fine else 30
        values += per_point * n * n
        n //= 2
    return values * itemsize
