"""Bytes one CG iteration on a general operator of n rows and nnz stored
entries must move.

The least a gather formulation can do while the state does not fit on chip:
read each stored entry's value and its column index once (padding is not
counted), read and write each of the three state vectors x, r and p once, and
read p once more for the product (its gathers, counted as one pass, as if
every entry of p were fetched once). q = A p and both dot products are
transient in a perfect fusion, so they are not counted. Counting low keeps
the share under 100 %."""


def bytes_per_iteration(n: int, nnz: int, itemsize: int = 4,
                        index_itemsize: int = 4) -> int:
    return (itemsize + index_itemsize) * nnz + 7 * n * itemsize
