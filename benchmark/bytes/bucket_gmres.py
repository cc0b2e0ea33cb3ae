"""Bytes one step of restarted GMRES(m) on a same-pattern bucket must move,
whatever implements it: ``lanes`` independent banded systems of ``diagonals``
planes and ``rows`` unknowns each.

A lane shares nothing but the pattern's offsets with its neighbours (its
planes, its vectors and its Krylov basis are its own), so the bucket's floor
is ``bytes/gmres_dia.py``'s count of one system's step, a cycle's end spread
over its ``restart`` steps, times the lanes: at D = 7, m = 30, 42.4 n values a
step and lane. Counted low (one triangular orthogonalisation pass, no masks,
no pad lanes, a lane that has converged and waits for its bucket's last counted
as if it worked), so that the share stays under 100 % and a change of the
program leaves the yardstick where it is. The steps are the bucket's: the
slowest lane's."""
import manifest

gmres_dia = manifest.load_module("bytes", "gmres_dia")


def bytes_per_iteration(rows: int, diagonals: int, restart: int, lanes: int,
                        itemsize: int = 4) -> float:
    return lanes * gmres_dia.bytes_per_iteration(rows, diagonals, restart, 1,
                                                 itemsize)
