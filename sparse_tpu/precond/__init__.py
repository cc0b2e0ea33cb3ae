"""Precond: pattern-shared batched preconditioners as a serving subsystem.

Every bench gain before this subsystem was per-iteration throughput;
this package attacks iteration *count* (ROADMAP item 3) the way the
Ginkgo batched line pairs every batched Krylov solver with a batched
preconditioner built once per sparsity pattern:

* pattern-level (symbolic) work — diagonal maps, block extraction
  indices, ILU(0)/IC(0) dependency closures — happens ONCE per
  :class:`~sparse_tpu.batch.operator.SparsityPattern` on the host,
  lives in :mod:`sparse_tpu.plan_cache` and persists as vault artifact
  kinds (``precond_diag`` / ``precond_block`` / ``ilu_symbolic``), so a
  warm restart skips it;
* numeric work — extracting diagonals/blocks, inverting the small
  dense block stack, Chow–Patel factorization sweeps — is pure batched
  jnp over the ``(B, nnz)`` value stack, executed INSIDE the compiled
  bucket programs (replicated closure constants under the fleet's
  ``shard_map`` programs — lane-local, no collectives);
* application is jit-safe and fixed-shape: diagonal scaling, batched
  block matmul, fixed-sweep Jacobi–Richardson triangular solves, or
  polynomial matvec chains — no data-dependent control flow anywhere.

:class:`~sparse_tpu.precond.policy.PrecondPolicy` resolves
``SPARSE_TPU_PRECOND`` / ``SolveSession(precond=...)`` / per-ticket
overrides into a per-(pattern, solver, bucket, dtype) choice that joins
the bucket-program plan-cache key and the vault warm-start manifest —
docs/preconditioners.md for the choice table and operational notes.
"""

from __future__ import annotations

import numpy as np

from .. import telemetry
from ..telemetry import _metrics
from .ilu import (  # noqa: F401
    IluSymbolic,
    factorize,
    ilu0_reference,
    ilu0_symbolic,
    ilu_factory,
)
from .jacobi import (  # noqa: F401
    _scale,
    bjacobi_factory,
    block_map,
    diag_map,
    diag_of,
    jacobi_factory,
)
from .policy import (  # noqa: F401
    KINDS,
    NONE,
    PRECOND_DTYPES,
    PrecondPolicy,
    canonical_kind,
    canonical_precond_dtype,
    dtype_suffix,
    key_suffix,
)
from .poly import cheby_factory, estimate_lmax, neumann_factory  # noqa: F401

__all__ = [
    "KINDS", "NONE", "PRECOND_DTYPES", "PrecondPolicy",
    "bjacobi_factory", "block_map", "canonical_kind",
    "canonical_precond_dtype", "cheby_factory", "diag_map", "diag_of",
    "dtype_suffix", "estimate_lmax", "factorize", "ilu0_reference",
    "ilu0_symbolic", "ilu_factory", "jacobi_factory", "key_suffix",
    "make_M", "make_factory", "neumann_factory",
]

# always-on build accounting (telemetry/_metrics.py): one count per
# pattern-level build by kind, plus the cumulative host build seconds —
# the cold-start share preconditioning adds (next to plan_cache's
# compile_s)
_BUILD_SECONDS = _metrics.counter(
    "precond.build_seconds",
    help="cumulative host-side pattern-level preconditioner build "
    "seconds (symbolic factorizations, extraction maps)",
)


def _build_event(kind: str, pattern, build_s: float = 0.0, **fields) -> None:
    """One pattern-level build: always-on counters + cost attribution +
    (telemetry on) a ``precond.build`` event. Called from the
    plan-cache build closures, so the cadence is exactly one per
    (pattern, kind) per vault — the instrument a
    one-symbolic-factorization assertion reads."""
    _metrics.counter(
        "precond.builds", kind=kind,
        help="pattern-level preconditioner builds by kind",
    ).inc()
    _BUILD_SECONDS.add(float(build_s))
    from ..telemetry import _cost

    _cost.record_pack(
        f"precond.{kind}.{pattern.fingerprint[2][:12]}", float(build_s),
        precond=kind, n=int(pattern.shape[0]), nnz=int(pattern.nnz),
    )
    if telemetry.enabled():
        telemetry.record(
            "precond.build", precond=kind, n=int(pattern.shape[0]),
            nnz=int(pattern.nnz),
            build_ms=round(float(build_s) * 1e3, 3), **fields,
        )


def make_factory(pattern, kind: str, policy: PrecondPolicy | None = None):
    """Resolve ``kind`` to a numeric factory over ``pattern`` (``None``
    for 'none'/off) — the module-level form of
    :meth:`PrecondPolicy.factory`."""
    pol = policy or PrecondPolicy(kind)
    return pol.factory(pattern, canonical_kind(kind, allow_auto=False))


def make_M(A, kind: str = "jacobi", solver: str = "cg",
           policy: PrecondPolicy | None = None):
    """Unbatched convenience: build a preconditioner for ONE CSR-shaped
    matrix as a :class:`~sparse_tpu.linalg.LinearOperator` usable as the
    ``M=`` of :func:`sparse_tpu.linalg.cg` / ``gmres`` (and the recovery
    ladder). Internally the B=1 lane of the batched machinery — the
    same maps, factors and apply code the bucket programs run, so the
    B=1 parity contract holds by construction."""
    from ..batch.operator import BatchedCSR, SparsityPattern
    from ..linalg import LinearOperator
    from ..utils import asjnp

    pattern = SparsityPattern.from_csr(A)
    data = A.data if hasattr(A, "data") else A
    values = asjnp(np.asarray(data))[None, :]
    pol = policy or PrecondPolicy(kind)
    resolved = pol.decide(pattern, solver, 1, values.dtype, override=kind)
    fac = pol.factory(pattern, resolved)
    if fac is None:
        raise ValueError(f"precond kind {kind!r} resolves to none here")
    bmv = BatchedCSR(pattern, values).matvec
    Mvec = fac(values, bmv)
    n = pattern.shape[0]
    from ..resilience import faults as _faults

    if resolved == "jacobi" and not (_faults.ACTIVE and _faults.targets("precond")):
        # point-Jacobi is a scaling by one array, Mvec(1): an operator
        # that declares it, so that linalg.cg(A, b, M=M) runs its compiled
        # program (jit_pcg) with the array as an argument. The other kinds
        # hold factors and maps inside their closures and stay closures.
        import jax.numpy as jnp

        return LinearOperator(
            (n, n), dtype=np.dtype(values.dtype), apply=_scale,
            operands=(Mvec(jnp.ones((1, n), values.dtype))[0],),
            describe={"precond": "jacobi"},
        )

    def mv(x):
        return Mvec(asjnp(x)[None, :])[0]

    return LinearOperator((n, n), matvec=mv, dtype=np.dtype(values.dtype))
