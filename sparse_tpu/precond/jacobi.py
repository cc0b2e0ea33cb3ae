"""Pattern-shared batched Jacobi preconditioners (point and block).

The Ginkgo batched recipe (PAPERS.md §2) split into the repo's
prepare/execute idiom: everything that depends only on the
*sparsity pattern* — which nnz position holds each row's diagonal,
which positions fall inside each diagonal block — is computed ONCE per
:class:`~sparse_tpu.batch.operator.SparsityPattern` on the host, lives
in :mod:`sparse_tpu.plan_cache` (vault-persisted, so a warm restart
skips it), and enters the compiled bucket programs as replicated
closure constants. The *numeric* half — extracting the diagonal /
blocks from a ``(B, nnz)`` value stack and inverting the small dense
blocks — is pure batched jnp executed inside the jitted program, so
every dispatch factorizes its fresh coefficients at device speed with
no host round trip.

* **Point Jacobi** (``jacobi``): ``M r = r / diag(A)`` per lane — one
  gather through the pattern's diagonal position map plus a broadcast
  multiply per application.
* **Block Jacobi** (``bjacobi``): the diagonal ``bs x bs`` blocks
  gather through a pattern-shared ``(blocks, bs, bs)`` source map into
  a ``(B, blocks, bs, bs)`` stack, invert with one batched
  ``jnp.linalg.inv``, and apply as a batched block matmul. Rows past
  ``n`` (the ragged last block) and structurally missing diagonal
  entries are patched with identity on the host map, so the inverses
  are well-defined for any pattern.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import plan_cache, telemetry
from ..utils import commit_to_exec_device, host_scope


def _pattern_rows(pattern) -> np.ndarray:
    counts = pattern.indptr[1:] - pattern.indptr[:-1]
    return np.repeat(np.arange(pattern.shape[0], dtype=np.int64), counts)


def diag_map(pattern):
    """Per-pattern diagonal position map, via the plan cache: device
    arrays ``(dpos (n,), has (n,))`` where ``values[:, dpos]`` gathers
    each row's diagonal entry (``has`` False where the pattern has no
    structural diagonal — those rows precondition as identity)."""

    def build():
        import time

        from . import _build_event

        t0 = time.perf_counter()
        with host_scope():
            n = pattern.shape[0]
            rows = _pattern_rows(pattern)
            cols = pattern.indices.astype(np.int64)
            dpos = np.full(n, -1, dtype=np.int64)
            on_diag = rows == cols
            dpos[rows[on_diag]] = np.nonzero(on_diag)[0]
            has = dpos >= 0
        out = commit_to_exec_device((
            jnp.asarray(np.maximum(dpos, 0).astype(np.int32)),
            jnp.asarray(has),
        ))
        _build_event("jacobi", pattern, time.perf_counter() - t0,
                     stage="diag_map")
        return out

    def vault_key():
        from ..vault import _codecs

        return _codecs.digest("preconddiag", pattern.fingerprint[2])

    return plan_cache.get(
        pattern, "precond.diag", build,
        vault_kind="precond_diag", vault_key=vault_key,
    )


def _safe_recip(d):
    one = jnp.ones((), dtype=d.dtype)
    return jnp.where(d == 0, one, one / jnp.where(d == 0, one, d))


def diag_of(pattern, values):
    """``(B, n)`` diagonal stack of a ``(B, nnz)`` value stack (1 where
    the pattern has no diagonal entry) — jit-safe given a warm map."""
    dpos, has = diag_map(pattern)
    d = values[..., dpos]
    return jnp.where(has, d, jnp.ones((), dtype=values.dtype))


def _scale(operands, v):
    """``apply`` of a declared diagonal preconditioner: one lane's vector
    or a lane stack times the reciprocal diagonal ``operands[0]``."""
    return v * operands[0]


def jacobi_factory(pattern, storage_dtype=None, acc_dtype=None):
    """Point-Jacobi numeric factory: ``factory(values, matvec) -> Mvec``
    with ``Mvec(R) = R / diag(A)`` per lane. The map build (host) runs
    here, once per pattern; the returned factory is pure jnp. ``Mvec``
    declares what it holds, as ``linalg.LinearOperator`` does: ``Mvec.apply``
    is :func:`_scale`, ``Mvec.operands`` the reciprocal diagonal ``(dinv
    [B, n],)``, ``Mvec.lane_operands`` says that its leading axis is the
    lanes' (``precond.make_M``'s one-lane wrapper shares ``_scale`` over an
    array the lanes would share, and says nothing), ``Mvec.describe`` names
    the kind, so that a batched solver can hand the array to a compiled
    program as an argument and gather it when it compacts its lanes.

    ``storage_dtype`` / ``acc_dtype`` (ISSUE 16): the reciprocal is
    computed at ``acc_dtype`` and STORED at ``storage_dtype`` — the
    apply's multiply widens back through jnp promotion, so a bf16
    factor under an f32 sweep costs bf16 memory traffic and f32 math.
    ``None`` (default) is byte-identical to the historic factory."""
    diag_map(pattern)  # host build outside any trace
    sdt = None if storage_dtype is None else jnp.dtype(storage_dtype)
    adt = None if acc_dtype is None else jnp.dtype(acc_dtype)

    def factory(values, matvec=None):
        # a no-op inside a bucket program's trace; live, the span waits
        # for the diagonal so that the gather's time is in it
        with telemetry.span("batch.values_pack", form="jacobi",
                            n=pattern.shape[0]) as sp:
            d = diag_of(pattern, values)
            if adt is not None:
                d = d.astype(adt)
            dinv = _safe_recip(d)
            if sdt is not None:
                dinv = dinv.astype(sdt)
            sp.set_sync(dinv)

        def Mvec(R):
            return R * dinv

        Mvec.apply, Mvec.operands, Mvec.lane_operands = _scale, (dinv,), (True,)
        Mvec.describe = {"precond": "jacobi"}
        return Mvec

    return factory


def block_map(pattern, bs: int):
    """Pattern-shared block extraction map for ``bs x bs`` diagonal
    blocks, via the plan cache (vault-persisted): device arrays
    ``(src (nb, bs, bs) int32, fix (nb, bs, bs))`` where ``src`` holds
    the nnz position feeding each in-block slot (0 where absent — the
    gathered value is masked by ``src >= 0`` pre-clip) and ``fix`` adds
    identity at padded rows (beyond ``n``) and structurally missing
    diagonal slots so every block inverts."""
    bs = int(bs)

    def build():
        import time

        from . import _build_event

        t0 = time.perf_counter()
        with host_scope():
            n = pattern.shape[0]
            nb = -(-n // bs)
            rows = _pattern_rows(pattern)
            cols = pattern.indices.astype(np.int64)
            inblk = (rows // bs) == (cols // bs)
            src = np.full((nb, bs, bs), -1, dtype=np.int64)
            r, c, p = rows[inblk], cols[inblk], np.nonzero(inblk)[0]
            src[r // bs, r % bs, c % bs] = p
            fix = np.zeros((nb, bs, bs), dtype=np.float64)
            # identity at ragged pad rows and missing structural diagonals
            flat = np.arange(nb * bs)
            missing = (flat >= n) | (src[flat // bs, flat % bs, flat % bs] < 0)
            fix[flat[missing] // bs, flat[missing] % bs, flat[missing] % bs] = 1.0
        out = commit_to_exec_device((
            jnp.asarray(src.astype(np.int32)), jnp.asarray(fix),
        ))
        _build_event("bjacobi", pattern, time.perf_counter() - t0,
                     stage="block_map", bs=bs)
        return out

    def vault_key():
        from ..vault import _codecs

        return _codecs.digest("precondblk", pattern.fingerprint[2], bs)

    return plan_cache.get(
        pattern, f"precond.block.{bs}", build,
        vault_kind="precond_block", vault_key=vault_key,
    )


def bjacobi_factory(pattern, bs: int | None = None, storage_dtype=None,
                    acc_dtype=None):
    """Block-Jacobi numeric factory over ``bs x bs`` diagonal blocks:
    gathers the block stack from the value stack through the
    pattern-shared map, inverts it batched, and applies as a batched
    block matmul. ``factory(values, matvec) -> Mvec``.

    ``storage_dtype`` / ``acc_dtype`` (ISSUE 16): the block inversion
    runs at ``acc_dtype`` (a bf16 ``linalg.inv`` would lose the
    factorization's whole point), the inverse STACK is stored at
    ``storage_dtype``, and the apply einsum accumulates at
    ``acc_dtype`` — narrow memory, wide math. ``None`` (default) is
    byte-identical to the historic factory."""
    from ..config import settings

    n = pattern.shape[0]
    bs = max(min(int(bs or settings.precond_block), max(n, 1)), 1)
    if bs == 1:
        return jacobi_factory(pattern, storage_dtype=storage_dtype,
                              acc_dtype=acc_dtype)
    block_map(pattern, bs)  # host build outside any trace
    nb = -(-n // bs)
    n_pad = nb * bs
    sdt = None if storage_dtype is None else jnp.dtype(storage_dtype)
    adt = None if acc_dtype is None else jnp.dtype(acc_dtype)

    def factory(values, matvec=None):
        src, fix = block_map(pattern, bs)
        gathered = jnp.where(
            src >= 0,
            values[..., jnp.maximum(src, 0)],
            jnp.zeros((), dtype=values.dtype),
        )  # (B, nb, bs, bs)
        blocks = gathered + fix.astype(values.dtype)
        if adt is not None:
            blocks = blocks.astype(adt)
        inv = jnp.linalg.inv(blocks)
        if sdt is not None:
            inv = inv.astype(sdt)

        def Mvec(R):
            B = R.shape[0]
            Rp = jnp.pad(R, ((0, 0), (0, n_pad - n)))
            Z = jnp.einsum(
                "bkij,bkj->bki", inv, Rp.reshape(B, nb, bs),
                **({} if adt is None
                   else {"preferred_element_type": adt}),
            )
            return Z.reshape(B, n_pad)[:, :n]

        return Mvec

    return factory
