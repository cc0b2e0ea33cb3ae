"""Bytes one iteration of HPCG's preconditioned CG must move on the stored
hierarchy of ``sparse_tpu/models/hpcg_grid.py``: a grid ``grid = [nx, ny,
nz]`` of n points, ``levels`` levels, each level's matrix 27 stored
coefficient planes in eight colour blocks of n/8 points.

Counted low, so that the share stays under 100 %, in blocks (one colour's
n/8 values of one level). A coefficient is read once wherever the arithmetic
uses it; a block of a vector that another colour's update, a transfer or CG's
reductions read has to exist: written once, read once a use. What a perfect
fusion keeps on the chip (the re-colouring of a restricted residual, the
read-modify-write of the prolongation's block 0, any second read inside one
update) is not counted.

- A colour's update reads the 26 planes towards its neighbours and the
  diagonal (27 blocks), the seven other colours' blocks, its block of r, and
  writes its own: 36 blocks. A symmetric step is 15 updates (the backward
  sweep's repeat of colour 7 rewrites the same bits and is not run): 540
  blocks from a given x. From a zero start the forward sweep reads only what
  it has written: colour c reads c blocks of x and the planes towards them,
  104 of the 208 planes and 28 of the 56 blocks, so the step is 408 blocks.
- The cycle's residual on a level above the coarsest is computed at the
  even points alone, block 0: 27 planes, the eight blocks of x, r's block 0,
  the restricted residual written: 37 blocks. The prolongation reads the
  coarse correction: 1 block of this level.
- A level above the coarsest: 408 + 37 + 1 + 540 = 986 blocks. The coarsest,
  one step from zero: 408.
- CG's own on the fine level: the product A p reads 27 planes a colour and
  writes q (the step length needs p . q whole before r can take q): 28 n;
  the recurrence 9 n (x, r and p read and written, z, q and p for the
  product read): 37 n = 296 blocks.

At 256^3 and four levels: (986 + 296) x 2,097,152 + 986 x 262,144 + 986 x
32,768 + 408 x 4,096 = 2,981,003,264 values, 11,924,013,056 B an iteration:
14.56 ms at 819 GB/s; at the cell's 256 x 256 x 128 half of each."""

STEP, STEP_FROM_ZERO, RESIDUAL, PROLONG, CG = 540, 408, 37, 1, 296


def bytes_per_iteration(grid, levels: int, itemsize: int = 4) -> int:
    nx, ny, nz = grid
    blocks = 0
    for lvl in range(levels):
        block = (nx >> lvl) * (ny >> lvl) * (nz >> lvl) // 8
        per_level = STEP_FROM_ZERO
        if lvl < levels - 1:
            per_level += RESIDUAL + PROLONG + STEP
        if lvl == 0:
            per_level += CG
        blocks += per_level * block
    return blocks * itemsize
