"""Pallas TPU kernel: the middle of GMRES's orthogonalisation over one read
of the basis.

Classical Gram-Schmidt with one re-orthogonalisation pass
(``linalg._orth_against``) is four contractions against the stage's rows
``Vs = V[..., :hi, :, :]`` of the Krylov basis::

    hcol = Vs^H w            # read 1
    w1   = w - hcol Vs       # read 2
    h2   = Vs^H w1           # read 3
    w2   = w1 - h2 Vs        # read 4

Reads 2 and 3 are one sweep here: with ``hcol`` known, ``w1`` on a block of
columns depends on that block of ``Vs`` alone, and ``h2`` is a sum over the
blocks of ``Vs[:, block] w1[block]``. A grid step brings one block ``[hi,
tr, 128]`` of the basis into VMEM (once from HBM), forms its ``w1`` from it
and adds its part of ``h2`` from it again (twice from VMEM): the products
and sums of the two ``jnp`` lines, the partial sums in another order.
float32 multiplies and adds on the vector unit, no ``dot``.

The basis is handed over WHOLE, ``[..., restart + 1, R, 128]`` (the layout
of ``linalg._basis_tiles``): the stage's rows are the block ``(hi, tr,
128)`` at block index ``(0, c, 0)``, so no slice (a copy, in front of a
custom call) is made of it. Leading axes (the session's lanes) are grid
axes in front of the column blocks'.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the kernel holds in VMEM: two blocks ``[hi, tr, 128]`` of the basis
# (Pallas double-buffers an operand's block), two each of w and w1 ``[tr,
# 128]`` and two of the partial sums ``[hi, 8, 128]``, 4 bytes an element.
# That stays under this, half of the 16 MiB Mosaic plans within (the other
# half is the body's own temporaries); the block's rows ``tr`` follow from it
# (:func:`block_rows`: at restart 30's last stage, 31 rows, 240), nothing is
# probed.
ORTH_VMEM_BYTES = 8 << 20

_SUB, _LANES = 8, 128  # a float32 tile
# rows of the block a trip of the body's loop takes at most: sixteen tiles of
# every basis row, so that a trip of the rolled loops over the basis' rows
# has sixteen multiply-adds to its name (2.4 cycles a tile by the v5e
# compiler's schedule, where HBM's rate leaves 7.5)
_CHUNK = 16 * _SUB


def block_rows(hi: int, R: int, vmem_bytes: int = ORTH_VMEM_BYTES):
    """The rows ``tr`` of a column block for a stage of ``hi`` basis rows of
    ``R`` tile rows each (``R`` a multiple of 8), or None where not even one
    tile row fits: the largest whole number of tiles that keeps ``(2 hi +
    4) tr`` rows of 128 and ``2 hi`` tiles under ``vmem_bytes``, then evened
    out over the steps. ``tr`` need not divide ``R``: the last block's tail
    is left out inside the kernel."""
    row = _LANES * 4
    fit = (vmem_bytes - 2 * hi * _SUB * row) // ((2 * hi + 4) * row)
    fit = min(fit, R) // _SUB * _SUB
    if fit < _SUB:
        return None
    steps = -(-R // fit)
    return _SUB * -(-R // (steps * _SUB))


def _kernel(h_ref, v_ref, w_ref, w1_ref, acc_ref, *, hi, tr, R, nlead):
    lead = tuple(pl.program_id(a) for a in range(nlead))
    c = pl.program_id(nlead)
    steps = -(-R // tr)

    @pl.when(c == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def sweep(nrows):
        """The block's first ``nrows`` rows (static), a chunk a trip. The
        loops over the basis' rows are rolled, so that a kernel's trace does
        not grow with ``hi``. The last chunk is moved back to end with the
        rows: what it shares with the chunk before gives the same w1 again
        and, masked, nothing to h2."""
        ch = min(_CHUNK, nrows)

        def chunk(j, carry):
            due = j * ch
            start = pl.multiple_of(jnp.minimum(due, nrows - ch), _SUB)
            sl = pl.ds(start, ch)

            def combine(i, s):
                return s + h_ref[(*lead, i)] * v_ref[i, sl, :]

            w1 = w_ref[sl, :] - jax.lax.fori_loop(
                0, hi, combine, jnp.zeros((ch, _LANES), w1_ref.dtype))
            w1_ref[sl, :] = w1
            row = start + jax.lax.broadcasted_iota(jnp.int32, (ch, _LANES), 0)
            new = jnp.where(row >= due, w1, 0.0)

            def project(i, carry):
                p = v_ref[i, sl, :] * new
                tiles = [p[r:r + _SUB] for r in range(0, ch, _SUB)]
                while len(tiles) > 1:  # pairwise: a short chain of adds
                    pairs = [a + b for a, b in zip(tiles[::2], tiles[1::2])]
                    tiles = pairs + tiles[2 * len(pairs):]
                acc_ref[i] += tiles[0]
                return carry

            return jax.lax.fori_loop(0, hi, project, carry)

        jax.lax.fori_loop(0, -(-nrows // ch), chunk, 0)

    # the last block's rows past R are not the basis' (nor zeros): they are
    # left out, of w1 by the write-back and of h2 here
    last = R - (steps - 1) * tr
    if last == tr:
        sweep(tr)
    else:
        pl.when(c < steps - 1)(lambda: sweep(tr))
        pl.when(c == steps - 1)(lambda: sweep(last))


@partial(jax.jit, static_argnames=("hi", "tr", "interpret"))
def orth_update_project(V, w, hcol, *, hi: int, tr: int, interpret=None):
    """``(w1, h2)``: ``w1 = w - hcol Vs`` and ``h2 = Vs^H w1`` for the rows
    ``Vs = V[..., :hi, :, :]`` of the whole basis ``V [..., rows, R, 128]``,
    ``w [..., R, 128]`` and the coefficients ``hcol [..., hi]`` (float32,
    real), over one read of ``Vs`` in column blocks of ``tr`` rows
    (:func:`block_rows`). ``h2`` is not masked. Off a TPU (a test's CPU
    drive) the kernel is interpreted unless ``interpret`` says otherwise."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lead = V.shape[:-3]
    R = V.shape[-2]
    assert V.shape[-1] == _LANES and R % _SUB == 0 and tr % _SUB == 0
    assert w.shape == (*lead, R, _LANES) and hcol.shape == (*lead, hi)
    nlead = len(lead)
    steps = -(-R // tr)
    none = (None,) * nlead
    w_spec = pl.BlockSpec((*none, tr, _LANES), lambda *g: (*g, 0))
    w1, acc = pl.pallas_call(
        partial(_kernel, hi=hi, tr=tr, R=R, nlead=nlead),
        name="orth_update_project",
        grid=(*lead, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((*none, hi, tr, _LANES),
                         lambda *g: (*g[:-1], 0, g[-1], 0)),
            w_spec,
        ],
        out_specs=[
            w_spec,
            pl.BlockSpec((*none, hi, _SUB, _LANES),
                         lambda *g: (*g[:-1], 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct((*lead, hi, _SUB, _LANES), w.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * nlead + ("arbitrary",)),
        interpret=interpret,
    )(hcol, V, w)
    # the partial sums a tile a row: a tiny sum finishes them
    return w1, jnp.sum(acc, axis=(-2, -1))
