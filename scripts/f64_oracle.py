"""f32-vs-f64-vs-IR accuracy oracle for the PDE/CG headline (VERDICT r2 #6).

The headline benchmark runs the 6000^2 5-point Poisson CG in f32 on TPU and
compares throughput against the reference's f64 V100 number. This script
quantifies what the dtype asymmetry costs in ACCURACY: it runs the identical
300-iteration CG (the same `models.poisson` step the bench times) in both
dtypes on CPU and reports, per grid size:

  - true relative residual ||b - A x_300|| / ||b|| for f32 and f64
  - relative iterate distance ||x_f32 - x_300_f64|| / ||x_f64||
  - relative error vs the sampled ground-truth xtrue for both
  - the MIXED-PRECISION columns (ISSUE 15): the `ir` solver — f32 (and
    bf16-storage) inner Krylov sweeps under the f64 iterative-refinement
    outer loop (sparse_tpu.mixed) — driven to the SAME absolute residual
    target the plain f64 run achieved, with its refinement sweep count.
    This is the pinned oracle for the serving stack's `f32ir`/`bf16ir`
    dtype policies: reduced-precision storage, f64-verified accuracy.

The fused Pallas CG used for the TPU headline computes the same recurrence as
this step loop (residual parity asserted in tests/test_cg_fused.py), so
the step loop stands in for it here.

``tests/test_mixed.py`` imports :func:`run` and pins the per-size table's
accuracy claims in CI (the satellite contract: the table lives in a test
fixture).

Usage: python scripts/f64_oracle.py [n ...]   (default: 512 2000 6000)
Prints one JSON line per size.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from sparse_tpu.models.poisson import cg_dia, poisson_cg_state_dia
from sparse_tpu.ops.dia_spmv import dia_spmv_xla

ITERS = 300


def run(n: int, ir_policies=("f32ir", "bf16ir")) -> dict:
    N = n * n
    offsets = (-n, -1, 0, 1, n)
    out = {"n": n, "iters": ITERS}
    sols = {}
    # ONE problem, built in f64 (jax.random draws different streams per
    # dtype, so the f32 run must downcast this b — not resample it)
    state64, step = poisson_cg_state_dia(n, dtype=jnp.float64)
    planes64, _, b64, _, _ = state64
    xtrue = jax.random.normal(jax.random.PRNGKey(0), (N,), dtype=jnp.float64)
    for dtype in (jnp.float64, jnp.float32):
        planes = planes64.astype(dtype)
        b = b64.astype(dtype)
        zero_v = jnp.zeros((N,), dtype=dtype)
        zero_s = jnp.zeros((), dtype=dtype)
        x, r, p, rho = cg_dia(step, planes, zero_v, b, zero_v, zero_s, iters=ITERS)
        # residual and norms evaluated in f64 regardless of solve dtype
        x64 = x.astype(jnp.float64)
        resid = dia_spmv_xla(planes64, offsets, x64, (N, N)) - b64
        rel_resid = float(jnp.linalg.norm(resid) / jnp.linalg.norm(b64))
        xerr = float(jnp.linalg.norm(x64 - xtrue) / jnp.linalg.norm(xtrue))
        tag = "f64" if dtype == jnp.float64 else "f32"
        out[f"rel_resid_{tag}"] = rel_resid
        out[f"rel_err_vs_xtrue_{tag}"] = xerr
        sols[tag] = np.asarray(x64)
    out["rel_iterate_dist_f32_vs_f64"] = float(
        np.linalg.norm(sols["f32"] - sols["f64"]) / np.linalg.norm(sols["f64"])
    )

    # the IR columns (ISSUE 15): drive the mixed-precision solver to the
    # SAME absolute residual the plain f64 run achieved — matching
    # achieved tolerance, reduced-precision inner sweeps
    from sparse_tpu.mixed import ir_solve

    bnorm = float(jnp.linalg.norm(b64))
    target = max(out["rel_resid_f64"], 1e-14) * bnorm

    def mk(planes):
        def mv(X):
            return jax.vmap(
                lambda v: dia_spmv_xla(planes, offsets, v, (N, N))
            )(X)

        return mv

    for policy in ir_policies:
        low_dt = jnp.float32 if policy == "f32ir" else jnp.bfloat16
        x_ir, info = ir_solve(
            (mk(planes64), mk(planes64.astype(low_dt))), b64,
            tol=target, maxiter=6 * ITERS, policy=policy,
        )
        resid = dia_spmv_xla(planes64, offsets, x_ir.astype(jnp.float64),
                             (N, N)) - b64
        out[f"rel_resid_{policy}"] = float(
            jnp.linalg.norm(resid) / jnp.linalg.norm(b64)
        )
        out[f"{policy}_converged"] = bool(np.asarray(info.converged).all())
        out[f"{policy}_inner_iters"] = int(np.asarray(info.iters).max())
        out[f"{policy}_outer"] = int(info.outer)
    return out


if __name__ == "__main__":
    sizes = [int(a) for a in sys.argv[1:]] or [512, 2000, 6000]
    for n in sizes:
        print(json.dumps(run(n)))
        sys.stdout.flush()
