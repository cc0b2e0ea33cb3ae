"""Bytes ONE LANE of a same-pattern batch must move in one step of
Jacobi-preconditioned BiCGStab on a banded system of ``diags`` planes and
``rows`` unknowns, whatever implements it.

Counted low, by what the method needs and not by what a program does, so
that the share stays under 100 % and a change of the program leaves the
yardstick where it is:

- the step's two products read the lane's ``diags`` planes once each: 2 D n
  values (the vectors they read and write are counted below);
- x, r, p, v, s and t are each read once and written once: 12 n;
- the shadow residual is read once: n;
- Jacobi's reciprocal diagonal is read twice (p_hat = M p, s_hat = M s): 2 n;
- scalars (rho, alpha, omega, the five reductions' results) are free.

(2 D + 15) n values: at D = 9 and n = 992 in float32, 130,944 B a lane and
step. A lane shares nothing but the pattern's offsets with its neighbours
(planes, vectors and diagonal are its own), so a batch's floor is this times
the lane-steps its ANSWERS needed, the sum of the lanes' iteration counts
(``batch.solve``'s ``iters_sum``): a program that goes on stepping lanes
which have converged, frozen under their masks, reads low, and one that
stops stepping them reads higher and never past 100."""


def bytes_per_iteration(rows: int, diags: int, itemsize: int = 4) -> int:
    return (2 * diags + 15) * rows * itemsize
