"""Pow2 bucketing + padding: bound the compiled-program count of batching.

Serving traffic is ragged — batch sizes, problem sizes and nnz counts all
vary per flush — and every distinct shape a compiled batched program sees
is a fresh XLA compile. This module quantizes the ragged dimensions to
power-of-two buckets so the number of compiled programs stays
logarithmic, and pads honestly:

* **Batch lanes** (:func:`bucket_batch`, :func:`pad_lanes`): pad lanes
  replicate lane 0's values with a zero right-hand side and a huge
  tolerance — they converge at the first test point and never extend the
  batch's runtime. The number of batched programs per (pattern, solver)
  is then at most ``log2(settings.batch_max)``. Under the fleet serving
  tier buckets additionally round up to a multiple of the mesh size so
  lane stacks split evenly across devices (``multiple_of``); the extra
  mesh-pad lanes carry the same instant-converge contract, and pad
  accounting (occupancy, pad waste) counts against the final rounded
  bucket.
* **Pattern shape/nnz** (:func:`pad_pattern`): a pattern padded with
  empty trailing rows/columns (to a pow2 row count) and explicit zero
  entries (to a pow2 nnz) is *exactly* equivalent for Krylov solves —
  the padded region contributes zeros to every inner product and matvec,
  so the iterates restricted to the real rows are unchanged (pinned by
  ``tests/test_batch.py``). This lets near-sized patterns share compiled
  programs when traffic carries many one-off meshes.

Every ``(pattern, solver, bucket)`` triple is one plan-cache key
(:mod:`sparse_tpu.plan_cache`) — the always-on cache stats are the
instrument that shows exactly one compile/pack per bucket.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..config import settings


def pow2_ceil(v: int) -> int:
    """Smallest power of two >= v (v <= 1 -> 1)."""
    v = int(v)
    if v <= 1:
        return 1
    return 1 << (v - 1).bit_length()


def bucket_batch(b: int, policy: str | None = None,
                 batch_max: int | None = None,
                 multiple_of: int = 1) -> int:
    """Padded lane count for a batch of ``b`` real requests under the
    bucket policy ('pow2' quantizes up, 'exact' keeps ``b``), clamped to
    ``settings.batch_max``.

    ``multiple_of`` is the mesh constraint of the fleet serving tier
    (``sparse_tpu.fleet``): a batch-sharded bucket must split evenly
    over the mesh's S devices, so the bucket additionally rounds up to a
    multiple of S *after* the policy quantization. The ``batch_max``
    clamp is then applied in mesh units — a cap that is not itself a
    multiple of S rounds up rather than producing an unshardable bucket
    (the pad-accounting bugfix: callers must count pad lanes against the
    FINAL bucket this returns, never against ``batch_max``)."""
    cap = int(batch_max if batch_max is not None else settings.batch_max)
    m = max(int(multiple_of), 1)
    b = min(int(b), cap)
    policy = policy or settings.batch_bucket
    if policy == "exact":
        bkt = b
    elif policy == "pow2":
        bkt = min(pow2_ceil(b), cap)
    else:
        raise ValueError(f"unknown bucket policy {policy!r}")
    if m > 1:
        bkt = -(-bkt // m) * m  # ceil to the mesh multiple
    return bkt


def pad_lanes(values, rhs, tols, bucket: int, x0=None, big_tol=1e30):
    """Pad stacked per-lane arrays up to ``bucket`` lanes.

    ``values`` is ``(b, nnz)``, ``rhs`` ``(b, n)``, ``tols`` ``(b,)``.
    Pad lanes replicate lane 0's values (a well-posed operator), solve
    ``A x = 0`` from ``x0 = 0`` and carry ``big_tol`` — converged at the
    first test point, frozen thereafter, zero effect on real lanes.
    Returns ``(values, rhs, tols, x0, nreal)``.
    """
    values = np.asarray(values)
    rhs = np.asarray(rhs)
    tols = np.asarray(tols, dtype=np.float64)
    b = values.shape[0]
    if rhs.shape[0] != b or tols.shape[0] != b:
        raise ValueError("values/rhs/tols lane counts disagree")
    if bucket < b:
        raise ValueError(f"bucket {bucket} smaller than batch {b}")
    if x0 is None:
        x0 = np.zeros_like(rhs)
    else:
        x0 = np.asarray(x0)
    pad = bucket - b
    if pad:
        values = np.concatenate(
            [values, np.repeat(values[:1], pad, axis=0)], axis=0
        )
        rhs = np.concatenate(
            [rhs, np.zeros((pad, rhs.shape[1]), dtype=rhs.dtype)], axis=0
        )
        x0 = np.concatenate(
            [x0, np.zeros((pad, x0.shape[1]), dtype=x0.dtype)], axis=0
        )
        tols = np.concatenate([tols, np.full(pad, big_tol)], axis=0)
    return values, rhs, tols, x0, b


#: Lanes of at least this many bytes (``values`` + right-hand side of
#: one lane, as staged) go to the device one by one and are stacked
#: there; smaller lanes are stacked on the host and go up as four
#: arrays. A host stack costs one memcpy of the bucket; the device form
#: costs one transfer a lane array instead, whose bookkeeping is what a
#: memcpy of about this much costs (the crossing measured on the v5e:
#: PERF.md section 6, PR 26).
DEVICE_STACK_LANE_BYTES = 1 << 20


def lanes_stack_on(values, rhs) -> str:
    """Where a bucket of these lanes is stacked, ``"device"`` or
    ``"host"``: by the bytes of one lane's values and right-hand side
    against :data:`DEVICE_STACK_LANE_BYTES`."""
    lane_nbytes = values[0].nbytes + rhs[0].nbytes
    return "device" if lane_nbytes >= DEVICE_STACK_LANE_BYTES else "host"


@jax.jit
def _assemble(values, rhs, x0):
    """The three lane stacks of one bucket from its per-lane device
    arrays: one trace and one executable per (bucket, lane shapes,
    dtype), kept by ``jax.jit``'s own cache."""
    return jnp.stack(values), jnp.stack(rhs), jnp.stack(x0)


def stage_lanes(values, rhs, tols, bucket: int, x0=None, big_tol=1e30):
    """The padded lane stacks of one bucket, on the device (the
    streaming-dispatch entry, ISSUE 13; assembled there, ISSUE 26).

    ``values`` and ``rhs`` are sequences of ``b`` lanes of one dtype
    (a 2-D array passes as its rows), ``x0`` is ``None`` or a sequence
    whose entries may be ``None`` (that lane starts from zero). Returns
    ``(values, rhs, tols, x0, nreal)``, the first four as device arrays
    equal bit for bit to :func:`pad_lanes`' output of the stacked lanes
    (pinned by ``tests/test_pipeline.py``), uncommitted on the default
    device whichever way they were made:

    * lanes under :data:`DEVICE_STACK_LANE_BYTES` are stacked and
      padded on the host and uploaded as four arrays;
    * larger lanes are uploaded as they are, in one ``jax.device_put``
      of the list, and one jitted program stacks them on the device: no
      lane-sized host array is made. Pad lanes name lane 0's device
      array again as an operand, and they and the real lanes without an
      ``x0`` take one zero vector made on the device, so nothing is
      uploaded for them and the program's signature depends on the
      bucket alone. The per-lane arrays are dropped once it is
      dispatched.

    Either way the transfers start here, so they overlap the solve of
    whatever bucket is in flight. The transfer reads the caller's own
    arrays, possibly after this returns: they must not change until the
    tickets are terminal (docs/batching.md, "Streaming dispatch").
    """
    nreal = len(values)
    x0 = [None] * nreal if x0 is None else list(x0)
    if len(rhs) != nreal or len(tols) != nreal or len(x0) != nreal:
        raise ValueError("values/rhs/tols lane counts disagree")
    if bucket < nreal:
        raise ValueError(f"bucket {bucket} smaller than batch {nreal}")
    if lanes_stack_on(values, rhs) == "host":
        values, rhs = _stack(values), _stack(rhs)
        if all(x is None for x in x0):
            x0 = None
        else:
            x0 = np.stack(
                [np.zeros_like(rhs[0]) if x is None else x for x in x0]
            )
        values, rhs, tols, x0, _ = pad_lanes(
            values, rhs, tols, bucket, x0=x0, big_tol=big_tol
        )
        return (
            jax.device_put(values), jax.device_put(rhs),
            jax.device_put(tols), jax.device_put(x0), nreal,
        )
    pad = bucket - nreal
    tols = np.concatenate(
        [np.asarray(tols, dtype=np.float64), np.full(pad, big_tol)]
    )
    v, r, given = jax.device_put(
        (list(values), list(rhs), [x for x in x0 if x is not None])
    )
    zero = jnp.zeros_like(r[0]) if pad or len(given) < nreal else None
    given = iter(given)
    values, rhs, x0 = _assemble(
        (*v, *[v[0]] * pad),
        (*r, *[zero] * pad),
        (*(zero if x is None else next(given) for x in x0), *[zero] * pad),
    )
    del v, r, given
    return values, rhs, jax.device_put(tols), x0, nreal


def _stack(lanes):
    return lanes if isinstance(lanes, np.ndarray) else np.stack(lanes)


def pattern_bucket(n: int, nnz: int) -> tuple:
    """The pow2 (rows, nnz) bucket of a pattern — the shape key under
    which near-sized patterns can share compiled programs."""
    return (pow2_ceil(n), pow2_ceil(nnz))


def pad_pattern(pattern, n_to: int | None = None, nnz_to: int | None = None):
    """Pad a :class:`~sparse_tpu.batch.operator.SparsityPattern` to a
    (pow2) row count and nnz with empty rows and explicit zero entries.

    The extra entries live in the last padded row pointing at column 0
    (so no new column extent is needed beyond the padded square), and the
    extra rows are empty: for CG/BiCGStab/GMRES with zero-padded values
    and right-hand sides the solve restricted to the real rows is exactly
    the unpadded solve. Returns ``(padded_pattern, pad_values_fn,
    pad_rhs_fn)`` where the two callables lift ``(B, nnz)`` value stacks
    and ``(B, n)`` right-hand sides into the padded shapes with zeros.
    """
    from .operator import SparsityPattern

    n, nnz = pattern.shape[0], pattern.nnz
    n_to = int(n_to if n_to is not None else pow2_ceil(n))
    nnz_to = int(nnz_to if nnz_to is not None else pow2_ceil(nnz))
    if n_to < n or nnz_to < nnz:
        raise ValueError("pad target smaller than the pattern")
    if pattern.shape[0] != pattern.shape[1]:
        raise ValueError("pad_pattern expects a square pattern")
    extra_nnz = nnz_to - nnz
    indptr = np.concatenate([
        pattern.indptr.astype(np.int64),
        np.full(n_to - n, nnz, dtype=np.int64),
    ])
    # all pad entries sit in the last (padded) row — or extend the last
    # real row when n_to == n; either way they are zero-valued
    indptr[-1] = nnz_to
    indices = np.concatenate([
        pattern.indices.astype(np.int64),
        np.zeros(extra_nnz, dtype=np.int64),  # zero-valued, col 0
    ])
    padded = SparsityPattern(indptr, indices, (n_to, n_to))

    def pad_values(values):
        values = np.asarray(values)
        if values.shape[-1] != nnz:
            raise ValueError(f"expected nnz={nnz} values")
        pad = np.zeros(values.shape[:-1] + (extra_nnz,), dtype=values.dtype)
        return np.concatenate([values, pad], axis=-1)

    def pad_rhs(rhs):
        rhs = np.asarray(rhs)
        if rhs.shape[-1] != n:
            raise ValueError(f"expected n={n} rhs")
        pad = np.zeros(rhs.shape[:-1] + (n_to - n,), dtype=rhs.dtype)
        return np.concatenate([rhs, pad], axis=-1)

    return padded, pad_values, pad_rhs
