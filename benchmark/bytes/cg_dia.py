"""Bytes one CG iteration on a D-diagonal operator of n rows must move.

The least any formulation can do while the state does not fit on chip: stream
the D coefficient planes once, and read and write each of the three state
vectors x, r and p once. q = A p and both dot products are transient in a
perfect fusion, so they are not counted (the repo's two-pass kernel moves 15
passes, this floor is D + 6). Counting low keeps the share under 100 %."""


def bytes_per_iteration(n: int, diagonals: int, itemsize: int = 4) -> int:
    return (diagonals + 6) * n * itemsize
