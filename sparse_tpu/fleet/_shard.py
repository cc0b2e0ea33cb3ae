"""Batch-sharded bucket programs: one masked-Krylov loop over the pod.

The serving-shape observation that makes this the cheap strategy: lanes
of a same-pattern bucket are *independent* systems, so sharding the
batch axis moves ZERO solver data over the interconnect — the SELL
pattern plan is a replicated closure constant, every matvec/inner
product is lane-local, and the only collective in the whole program is
the all-converged exit (one lane-count ``psum`` per iteration) that
keeps all shards on the same global step. Per-lane iterates are
therefore bit-identical to the single-device program, which is the
parity contract ``tests/test_fleet.py`` pins at machine eps.

The psum routes through :mod:`sparse_tpu.parallel.comm`, so its
trace-time payload lands on a per-(mesh, solver, bucket, dtype)
:class:`~sparse_tpu.parallel.comm.SiteLedger` under the ``fleet.batch``
site; ``SolveSession`` commits the observed execution count after each
dispatch (always-on ``comm.collectives`` / ``comm.collective_bytes``)
and reconciles against :func:`batch_comm_model_bytes` in a
``comm.measured`` event. The model counts one psum per *iteration*, the
measurement one per while-condition evaluation (iterations + 1) — the
same small-positive expected divergence convention as ``dist.cg``.

GMRES's fleet form shards the *data* instead of the program: inputs are
``device_put`` onto the mesh batch axis and GSPMD partitions the
session's compiled GMRES bucket program, the whole solve (lanes
independent ⇒ no resharding; the two loops' ``jnp.any(~done)`` become
the inserted all-reduces). Its collective traffic is GSPMD-inserted and
thus model-only — the documented wrapper blind spot (docs/telemetry.md).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops import spmv as spmv_ops
from ..parallel import comm
from ..parallel.mesh import shard_map

#: the fleet mesh's batch axis name (bucket lane stacks shard over it)
FLEET_AXIS = "lanes"


def batch_ledger(fingerprint: str, solver: str, bucket: int, dtype):
    """The shared :class:`~sparse_tpu.parallel.comm.SiteLedger` of one
    batch-sharded program geometry — keyed so a jit-cached program for
    one (mesh, solver, bucket, dtype) never commits against bytes a
    different geometry's trace noted."""
    return comm.ledger(
        "fleet.batch",
        key=(str(fingerprint), str(solver), int(bucket), np.dtype(dtype).str),
    )


def batch_comm_model_bytes(S: int, iters: int, itemsize: int = 4) -> int:
    """Analytic collective model of a batch-sharded solve: one lane-count
    psum (a single int32 per shard, logical-payload convention) per
    iteration, across ``S`` shards. The measured side additionally pays
    the final while-condition evaluation — divergence ``~ 1/iters``,
    inside the 10% gate for any real solve."""
    return int(itemsize) * int(iters) * int(S)


def shard_inputs(mesh, *arrays):
    """``device_put`` each array onto the mesh batch axis (leading dim).
    The GSPMD entry of the gmres strategy, also used by benches/tests to
    stage pre-sharded traffic."""
    sh = NamedSharding(mesh, P(mesh.axis_names[0]))
    return tuple(jax.device_put(jnp.asarray(a), sh) for a in arrays)


def build_batch_program(pattern, bkt: int, dt, solver: str, mesh,
                        conv_test_iters: int, gmres_inner=None,
                        m_factory=None, mixed=None):
    """The mesh-sharded analog of ``SolveSession._build_program``: one
    compiled program whose arguments are the bucket's ``(B, nnz)`` value
    stack, ``(B, n)`` rhs/x0, per-lane tolerances and maxiter, with the
    batch axis sharded over ``mesh``. ``bkt`` must be a multiple of the
    mesh size (``bucket.bucket_batch(..., multiple_of=S)``).

    cg/bicgstab run under ``shard_map`` with the global psum exit;
    gmres wraps ``gmres_inner`` (the session's compiled GMRES bucket
    program, ``SolveSession._build_gmres_program``) with input sharding and
    lets GSPMD partition the whole solve.

    ``m_factory`` is the resolved preconditioner's numeric factory
    (ISSUE 14, :mod:`sparse_tpu.precond`): its pattern-level maps are
    closure constants — REPLICATED across the mesh exactly like the
    SELL pattern plan — and the numeric factorization runs inside the
    ``shard_map`` body over each device's LOCAL ``(B/S, nnz)`` value
    shard. Preconditioning is lane-local (diag/block extraction,
    fixed-sweep factorization sweeps, triangular sweeps are all
    per-lane), so it adds ZERO collectives to the sharded program and
    per-lane iterates stay bit-identical to the single-device
    preconditioned program.

    ``mixed`` is the resolved reduced-precision policy's knob dict
    (ISSUE 15, ``{'policy', 'inner_iters', 'max_outer', 'eta'}``):
    when given, the shard_map body runs the fused iterative-refinement
    loop instead — each device downcasts its LOCAL value shard, the
    all-converged psum exit threads through BOTH the f64 outer loop and
    the reduced inner sweeps (every shard runs the same global sweep
    schedule, frozen lanes bit-stable), and the program returns the
    refinement sweep count as a 5th (replicated) output.
    """
    from ..batch import krylov

    S = int(mesh.devices.size)
    if int(bkt) % S:
        raise ValueError(f"bucket {bkt} not a multiple of mesh size {S}")
    axis = mesh.axis_names[0]

    if solver == "gmres":
        if gmres_inner is None:
            raise ValueError("gmres strategy needs the inner closure")

        def run_gmres(values, rhs, x0, tols, maxiter):
            """The session's GMRES bucket program over inputs sharded on
            the mesh batch axis: one compiled whole solve, partitioned by
            GSPMD, that returns device arrays at once (nothing here waits
            for the solve)."""
            values, rhs, x0, tols = shard_inputs(mesh, values, rhs, x0, tols)
            return gmres_inner(values, rhs, x0, tols, maxiter)

        # as the single-device program tags it
        for tag in ("matvec", "pad_rows", "event_fields"):
            setattr(run_gmres, tag, getattr(gmres_inner, tag))
        return run_gmres

    from ..parallel.mesh import mesh_fingerprint

    loop = krylov._cg_loop if solver == "cg" else krylov._bicgstab_loop
    cti = int(conv_test_iters)
    led = batch_ledger(mesh_fingerprint(mesh), solver, bkt, dt)

    def lane_reduce(active):
        # the GLOBAL all-converged exit: per-iteration lane-count psum
        # through the accounting wrapper (4 bytes/shard/evaluation on
        # the ledger; SolveSession commits the observed executions)
        # dtype pinned: jnp.sum would promote to int64 under x64 and
        # silently double the psum payload vs batch_comm_model_bytes
        n_active = comm.psum(
            jnp.sum(active, dtype=jnp.int32), axis,
            ledger=led, tag="all_converged",
        )
        return n_active > 0

    if mixed is not None:
        from .. import mixed as mixed_mod

        storage_dt, compute_dt = mixed_mod.inner_dtypes(mixed["policy"])
        sdt = jnp.dtype(storage_dt)
        cdt = jnp.dtype(compute_dt)
        wdt = jnp.dtype(mixed_mod.outer_dtype())
        inner_iters = int(mixed["inner_iters"])
        max_outer = int(mixed["max_outer"])
        eta = float(mixed["eta"])
        pack = pattern.sell_pack()
        idx_slabs, pos, zero_rows = (
            pack.idx_slabs, pack.pos, pack.plan.zero_rows
        )

        def body(values, rhs, x0, tols, maxiter):
            req_dt = values.dtype
            vals_w = pack.pack_values(values.astype(wdt))
            vals_l = pack.pack_values(values.astype(sdt))

            def mv_wide(X):
                return spmv_ops.csr_spmv_sell_batched(
                    idx_slabs, vals_w, pos, X, zero_rows
                )

            def mv_low(X):
                return spmv_ops.csr_spmv_sell_batched(
                    idx_slabs, vals_l, pos, X, zero_rows, acc_dtype=cdt
                )

            fmv_low = krylov._maybe_faulty_mv(mv_low)
            Mvec = (
                None if m_factory is None
                else m_factory(values.astype(cdt), fmv_low)
            )
            X, iters, resid2, conv, outer = mixed_mod.ir_loop(
                mv_wide, fmv_low, rhs, x0, tols, maxiter, cti,
                inner_iters, max_outer, eta, cdt, Mvec=Mvec,
                solver=solver, lane_reduce=lane_reduce,
            )
            return X.astype(req_dt), iters, resid2, conv, outer

        out_specs = (P(axis), P(axis), P(axis), P(axis), P())
    else:
        # the single-device program's matvec, so its lanes stay
        # bit-identical to that program's: chosen from the pattern
        from ..batch.operator import pattern_matvec

        pack, product = pattern_matvec(pattern)

        def body(values, rhs, x0, tols, maxiter):
            fmv = krylov._maybe_faulty_mv(
                partial(product, pack.pack_values(values))
            )
            # lane-local numeric factorization from this shard's value
            # stack; the factory's maps ride in as replicated constants
            Mvec = None if m_factory is None else m_factory(values, fmv)
            return loop(
                fmv, rhs, x0, tols, maxiter, cti, Mvec=Mvec,
                lane_reduce=lane_reduce,
            )

        out_specs = (P(axis), P(axis), P(axis), P(axis))

    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=out_specs,
        check_vma=False,
    )

    # sharded programs accept donated inputs too (ISSUE 13): the
    # value-stack/rhs/x0 shards are consumed once per dispatch, so on
    # TPU/GPU their HBM recycles exactly like the single-device
    # program's (no-op on CPU — see batch.service.donate_argnums)
    from ..batch.service import donate_argnums

    @partial(jax.jit, donate_argnums=donate_argnums())
    def run(values, rhs, x0, tols, maxiter):
        return sharded(values, rhs, x0, tols, jnp.asarray(maxiter))

    run.matvec = pack.form  # as the single-device program tags it
    return run
