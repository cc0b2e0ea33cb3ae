"""Bytes one chip must move in one V-cycle-preconditioned CG iteration on the
grid hierarchy of ``examples/gmg.py`` laid over ``chips`` chips in row blocks:
``bytes/pcg_gmg.py``'s count, which is of the whole grid, divided by the
chips. Every level is split evenly (the lay-out shards a level only where the
chips divide its side), the halo rows are one row of n against a block of
n / chips and are left out, and the share is taken against ONE chip's HBM peak
with the device time of one chip's program: the whole mesh's bytes over one
chip's peak would read four times too high."""

import manifest

_whole = manifest.load_module("bytes", "pcg_gmg")


def bytes_per_iteration(grid: int, levels: int, chips: int,
                        itemsize: int = 4) -> int:
    return _whole.bytes_per_iteration(grid, levels, itemsize) // chips
