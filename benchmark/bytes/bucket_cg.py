"""Bytes one masked-CG iteration of a same-pattern bucket must move.

B lanes over one pattern of n rows and nnz entries: each lane's value plane
once (B * nnz), the shared column indices once (nnz, int32), and each lane's
x, r and p read once and written once (6 * B * n). The gathered reads of p and
q = A p are transient in a perfect fusion and not counted: a floor, so the
share stays under 100 %."""


def bytes_per_iteration(n: int, nnz: int, lanes: int, itemsize: int = 4) -> int:
    return lanes * nnz * itemsize + nnz * 4 + 6 * lanes * n * itemsize
