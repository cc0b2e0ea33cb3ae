"""Bytes one call of restarted GMRES(m) on a banded matrix of ``diagonals``
planes must move, whatever implements it: ``cycles`` restart cycles of
``restart`` (m) steps on ``rows`` (n) unknowns.

Counted low, by what the method needs and not by what a program does, so
that the share stays under 100 % and a change of the program leaves the
yardstick where it is:

- a product reads the D planes and x and writes y: (D + 2) n values a step;
- the new vector is made orthogonal to the j vectors before it in ONE pass,
  triangular: they are read once for the coefficients and once for the
  update, 2 j n, which sums to m (m + 1) n over a cycle's steps j = 1..m (a
  second pass is an implementation's choice for its precision and not part
  of the floor; a masked pass that reads all m + 1 rows whatever the step is
  not either);
- the new basis vector is written once: n a step;
- a cycle ends with x += V y (m vectors read, x read and written) and the
  next residual b - A x (the planes, x and b read, A x and r written):
  (m + D + 5) n;
- scalars (the Hessenberg column, the rotations, the small solve) are free.

A cycle: m n (D + 3 + (m + 1)) + (m + D + 5) n values. At D = 7, m = 30:
(30 * 41 + 42) n = 1272 n; at n = 1,270,432 in float32 6.46 GB a cycle,
64.6 GB a call of 10 cycles, 79 ms at 819 GB/s."""


def bytes_per_call(rows: int, diagonals: int, restart: int, cycles: int,
                   itemsize: int = 4) -> int:
    m, d = restart, diagonals
    per_cycle = m * (d + 3 + (m + 1)) + (m + d + 5)
    return cycles * per_cycle * rows * itemsize


def bytes_per_iteration(rows: int, diagonals: int, restart: int, cycles: int,
                        itemsize: int = 4) -> float:
    """A call's bytes over its ``restart * cycles`` steps: the form
    ``reducers/roofline_hbm.py`` takes, which counts a window's steps."""
    return bytes_per_call(rows, diagonals, restart, cycles, itemsize) / (
        restart * cycles)
