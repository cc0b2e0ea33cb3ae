"""DIA (diagonal-format) SpMV — the zero-gather SpMV for banded matrices.

Every reference benchmark matrix is banded (5-pt/9-pt Laplacians, the
11-diagonal SpMV microbenchmark), and for banded matrices the diagonal
layout turns SpMV into pure shifted vector arithmetic:

    y[i] = sum_k data[k, i + o_k] * x[i + o_k]

i.e. one [D, n] elementwise multiply and D statically-shifted adds — no
index loads at all, halving HBM traffic vs any gather-based CSR/ELL kernel.
This is the TPU-native answer to the reference's cuSPARSE SpMV path
(``src/sparse/array/csr/spmv.cu``). A Pallas variant with explicit VMEM
windowing lives in ``sparse_tpu.kernels.dia_spmv``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("offsets", "shape", "acc_dtype"))
def dia_spmv_xla(data, offsets: tuple, x, shape: tuple, acc_dtype=None):
    """y = A @ x with A in DIA layout (scipy convention: data[k, j] holds
    A[j - o_k, j]). ``offsets`` is a static tuple, so every slice below is a
    static-shape op and the whole SpMV fuses into one XLA pass.

    ``acc_dtype`` is the storage/accumulation split (ISSUE 15): bf16/f32
    diagonal planes widen at the multiply so the shifted adds accumulate
    at ``acc_dtype`` while HBM moves the narrow planes. ``None`` (the
    default) keeps the historic result-type behavior byte-identical."""
    m, n = shape
    D = len(offsets)
    if acc_dtype is not None:
        prod = data.astype(acc_dtype) * x[None, :n].astype(acc_dtype)
    else:
        prod = data * x[None, :n]  # [D, n]
    B = max(max((abs(int(o)) for o in offsets), default=0), max(m - n, 0))
    padded = jnp.pad(prod, ((0, 0), (B, B + max(m - n, 0))))
    y = jnp.zeros((m,), dtype=prod.dtype)
    for k, o in enumerate(offsets):
        y = y + jax.lax.dynamic_slice_in_dim(padded[k], B + int(o), m)
    return y


def dia_planes_matvec(planes, offsets: tuple, X):
    """``Y[..., i] = sum_k planes[..., k, i] * X[..., i + o_k]``: the banded
    product in the ROW layout (plane ``k`` holds ``A[i, i + o_k]`` at slot
    ``i``, zero where the diagonal leaves the matrix), over any leading
    (batch) axes. ``X`` is padded once by the band's overhangs and every
    plane multiplies a static slice of it: no index loads, and no
    ``(…, D, n)`` temporary (the product-then-shift of
    :func:`dia_spmv_xla` makes two). ``offsets`` is a static tuple."""
    m, n = planes.shape[-1], X.shape[-1]
    if not offsets:
        return jnp.zeros(X.shape[:-1] + (m,),
                         jnp.result_type(planes.dtype, X.dtype))
    left = max(-min(offsets), 0)
    right = max(max(offsets) + m - n, 0)
    Xp = jnp.pad(X, ((0, 0),) * (X.ndim - 1) + ((left, right),))
    out = None
    for k, o in enumerate(offsets):
        seg = jax.lax.slice_in_dim(Xp, left + o, left + o + m, axis=X.ndim - 1)
        term = planes[..., k, :] * seg
        out = term if out is None else out + term
    return out
