"""Weak-scaling harness: constant per-chip problem size over a growing mesh.

Reference analog: the Summit sweep scripts (``scripts/summit/run_legate_pde.sh``
— grid side scales as n*sqrt(g)) behind every BASELINE.md scaling row. On a
real TPU pod this measures ICI-scaling of the distributed CG (halo ppermute +
GSPMD psums); on the virtual CPU mesh it validates the harness itself.

Run:  python examples/weak_scaling.py -n 512 -shards 1,2,4,8 -iters 100
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-n", type=int, default=512, help="grid side per chip")
    parser.add_argument("-shards", default="1,2,4,8")
    parser.add_argument("-iters", type=int, default=100)
    args, _ = parser.parse_known_args()

    import jax

    import numpy as np

    from sparse_tpu.models.poisson import laplacian_2d_csr_host
    from sparse_tpu.parallel.dist import make_dist_cg, shard_csr
    from sparse_tpu.parallel.mesh import get_mesh

    shards = [int(s) for s in args.shards.split(",")]
    results = []
    base_rate = None
    for S in shards:
        side = int(round(args.n * math.sqrt(S)))
        A = laplacian_2d_csr_host(side, dtype=np.float32)
        mesh = get_mesh(S)
        D = shard_csr(A, mesh=mesh, balanced=True)
        b = np.random.default_rng(0).standard_normal(A.shape[0]).astype(np.float32)
        bp = D.pad_out_vector(b)
        run = make_dist_cg(D, tol=0.0, maxiter=args.iters, conv_test_iters=args.iters)
        import jax.numpy as jnp

        xp, iters, _ = run(bp, jnp.zeros_like(bp))
        int(iters)  # compile + warm
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            xp, iters, _ = run(bp, jnp.zeros_like(bp))
            int(iters)
            best = max(best, args.iters / (time.perf_counter() - t0))
        if base_rate is None:
            base_rate = best
        eff = best / base_rate
        from sparse_tpu.parallel.dist import comm_stats

        st = comm_stats(D, conv_test_iters=args.iters)
        results.append(
            {"shards": S, "rows": A.shape[0], "layout": D.layout,
             "iters_per_s": round(best, 2),
             "efficiency": round(eff, 3),
             "halo_entries": st["halo_entries_per_spmv"],
             "collective_bytes_per_iter":
                 st["cg_iter_collective_bytes_per_shard"],
             "mode": st["mode"]}
        )
        print(
            f"S={S:3d}  rows={A.shape[0]:>10,}  {best:8.2f} iters/s  "
            f"efficiency {eff:6.1%}  {D.layout}  "
            f"halo {st['halo_entries_per_spmv']}  "
            f"{st['cg_iter_collective_bytes_per_shard']} B/iter"
        )
    print(json.dumps({"weak_scaling": results}))


def comm_models(args):
    """Predicted alltoallv traffic vs S for the shuffle-shaped components
    (no devices needed — the models are exact and structural): samplesort
    at constant L keys/shard, and the 2-D SpGEMM on a growing grid with a
    constant per-device Laplacian block. The signal mirrors the CG
    harness's comm columns: per-shard exchange bytes must track the
    per-shard WORKLOAD, never the mesh size."""
    # this path truly needs no devices: pin CPU unconditionally
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    # jax is already imported when main() ran first: the knob, not the env
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from sparse_tpu.models.poisson import laplacian_2d_csr_host
    from sparse_tpu.parallel.sort import sort_comm_stats
    from sparse_tpu.parallel.spgemm import spgemm2d_comm_stats
    from sparse_tpu.utils import factor_int

    rng = np.random.default_rng(0)
    shards = [int(s) for s in args.shards.split(",")]
    sort_rows, spg_rows = [], []
    for S in shards:
        keys = rng.integers(0, 1 << 24, args.n * S).astype(np.int64)
        st = sort_comm_stats(keys, S, payloads=(np.ones(args.n * S, np.float32),))
        sort_rows.append(
            {"shards": S, "keys": args.n * S,
             "exchange_bytes_per_shard": st["exchange_bytes_per_shard_max"],
             "sample_bytes_per_shard": st["sample_allgather_bytes_per_shard"],
             "fallback": st["fallback_odd_even"]}
        )
        side = int(round(math.sqrt(args.n * S)))
        import sparse_tpu

        A = sparse_tpu.csr_array(laplacian_2d_csr_host(side, dtype=np.float32))
        gx, gy = factor_int(S)
        sg = spgemm2d_comm_stats(A, A, (gx, gy))
        spg_rows.append(
            {"shards": S, "grid": sg["grid"], "c_nnz": sg["c_nnz"],
             "replicate_bytes_per_device": sg["replicate_bytes_per_device"],
             "shuffle_bytes_per_device": sg["shuffle_bytes_per_device_max"]}
        )
        print(f"S={S:3d}  sort {st['exchange_bytes_per_shard_max']:>9,} B/shard"
              f"  spgemm2d grid={gx}x{gy} repl"
              f" {sg['replicate_bytes_per_device']:>10,} B"
              f" shuffle {sg['shuffle_bytes_per_device_max']:>9,} B")
    print(json.dumps({"sort_model": sort_rows, "spgemm2d_model": spg_rows}))


if __name__ == "__main__":
    import argparse as _ap

    _p = _ap.ArgumentParser(add_help=False)
    _p.add_argument("-models", action="store_true",
                    help="print predicted comm bytes vs S (no devices)")
    _p.add_argument("-n", type=int, default=512)
    _p.add_argument("-shards", default="1,2,4,8")
    _args, _ = _p.parse_known_args()
    if _args.models:
        comm_models(_args)
    else:
        main()
