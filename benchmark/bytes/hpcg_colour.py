"""Bytes the calls of ``kernels/hpcg_colour.py`` in one iteration of HPCG's
preconditioned CG must move: what ``bytes/pcg_hpcg.py`` counts, in its blocks
and by its rules, less what is not the kernel's (CG's recurrence and the
prolongation's read of the coarse correction).

- a symmetric step, 15 calls of ``"update"``: 540 blocks from a given x, 408
  from a zero start; two steps a level above the coarsest, one there;
- a level's residual at its even points, one more call of ``"update"``, its
  parameters asking for the residual: 37;
- ``A p``, one call of ``"product"`` for all eight colours: 27 planes and one
  block written a colour, x read once: 232.

Counted low against what the kernel is brought: a source block is counted
once an update, and the kernel reads the four that it moves along z one slice
in five more (the slice beyond a step's four); an update's result is counted
once, and its place among the eight is a second write outside the kernel.

At 256^3 and four levels 105 updates, 3 residuals and 1 product, 109 calls:
(985 + 232) x 2,097,152 + 985 x 262,144 + 985 x 32,768 + 408 x 4,096 =
2,844,393,472 values, 11,377,573,888 B an iteration: 13.89 ms at 819 GB/s; at
the cell's 256 x 256 x 128 half of each."""

STEP, STEP_FROM_ZERO, RESIDUAL, PRODUCT = 540, 408, 37, 232


def bytes_per_iteration(grid, levels: int, itemsize: int = 4) -> int:
    nx, ny, nz = grid
    blocks = 0
    for lvl in range(levels):
        block = (nx >> lvl) * (ny >> lvl) * (nz >> lvl) // 8
        per_level = STEP_FROM_ZERO
        if lvl < levels - 1:
            per_level += RESIDUAL + STEP
        if lvl == 0:
            per_level += PRODUCT
        blocks += per_level * block
    return blocks * itemsize
