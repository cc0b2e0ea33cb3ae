"""Quantum MIS benchmark: Hamiltonian build + RK time evolution.

Reference analog: the BASELINE.md "Quantum" row (MIS Hamiltonian build + RK
evolution, 1.85 iters/s @1 V100; driven by the quantum demo script). The
state evolves under H(t) = a(t) H_MIS + b(t) H_driver — an adiabatic-style
sweep from the driver toward the cost Hamiltonian — integrated with DOP853
in complex arithmetic; every RHS evaluation is one sparse SpMV (§3.5).

Run:  python examples/quantum_evolution.py -nodes 16 -t 1.0
"""

import argparse
import time

import networkx as nx
import numpy as np

from benchmark import get_phase_procs, parse_common_args

parser = argparse.ArgumentParser()
parser.add_argument("-nodes", type=int, default=14)
parser.add_argument("-prob", type=float, default=0.35)
parser.add_argument("-t", type=float, default=1.0)
parser.add_argument("-seed", type=int, default=0)
parser.add_argument(
    "-graph", choices=("er", "cycle"), default="er",
    help="cycle: C_n ring (L_n independent sets — '-graph cycle -nodes 25' "
    "is the >=1e5-state scale shape of VERDICT r2 #10)",
)
parser.add_argument(
    "-dist_shards", type=int, default=0,
    help="route the build's group sorts + COO->CSR through the mesh "
    "samplesort with this many shards (0 = single-host build)",
)
args, _ = parser.parse_known_args()
common, timer, _np, sparse, linalg, use_tpu = parse_common_args()

from sparse_tpu import integrate, quantum  # noqa: E402

if args.graph == "cycle":
    graph = nx.cycle_graph(args.nodes)
else:
    graph = nx.erdos_renyi_graph(args.nodes, args.prob, seed=args.seed)

build_scope, solve_scope = get_phase_procs(use_tpu)

# --precision f32 (TPU-native) evolves in complex64 with f32-scaled
# tolerances; f64/complex128 matches the reference's dtype (emulated,
# slow on TPU — documented deviation, same stance as the PDE/GMG rows)
if use_tpu and common.precision == "f32":
    cdtype = np.complex64
    rtol, atol = 1e-5, 1e-7
else:
    cdtype = np.complex128
    rtol, atol = 1e-8, 1e-10

timer.start()
with build_scope:
    # construction stays on the host CPU backend (the reference's
    # build-on-CPU/solve-on-GPU machine scoping): eagerly dispatching
    # the build's sorts through a remote accelerator is round-trip-bound
    driver = quantum.HamiltonianDriver(
        graph=graph, dtype=cdtype,
        dist_shards=args.dist_shards or None,
    )
    mis = quantum.HamiltonianMIS(graph=graph, poly=driver.ip, dtype=cdtype)
    H_driver = driver.hamiltonian
    H_cost = mis.hamiltonian
print(f"Hamiltonian build: {timer.stop():.1f} ms  "
      f"(nstates={driver.nstates}, nnz={H_driver.nnz})")

T = args.t


nst = driver.nstates

if cdtype == np.complex64:
    # TPU-native form: both Hamiltonians are REAL (bit-flip couplings and
    # diagonal costs), so i dy/dt = H y splits into the stacked real
    # system (dyr, dyi) = (H yi, -H yr) — f32 end to end, and the SpMVs
    # ride the real f32 fast path.
    import jax.numpy as jnp

    with build_scope:
        Hc = H_cost.astype(np.float32).tocsr()
        Hd = H_driver.astype(np.float32).tocsr()

    def rhs(t, y):
        a = t / T
        b = 1.0 - t / T
        yr, yi = y[:nst], y[nst:]
        Hyr = a * (Hc @ yr) + b * (Hd @ yr)
        Hyi = a * (Hc @ yi) + b * (Hd @ yi)
        return jnp.concatenate([Hyi, -Hyr])

    y0 = np.zeros(2 * nst, dtype=np.float32)
    y0[nst - 1] = 1.0  # start in the empty-set state (real part)
else:
    def rhs(t, y):
        a = t / T          # ramp the cost Hamiltonian up
        b = 1.0 - t / T    # ...and the driver down
        return -1j * (a * (H_cost @ y) + b * (H_driver @ y))

    y0 = np.zeros(nst, dtype=cdtype)
    y0[-1] = 1.0  # start in the empty-set state

with build_scope:
    # one eager RHS call primes the operators' layout caches ON THE CPU
    # backend — layout builds are trains of small eager ops, which belong
    # on the host
    np.asarray(rhs(0.0, y0))
with solve_scope:
    # compile outside the clock (the reference's CUDA tasks are prebuilt;
    # a compile inside the clock would swamp the 13-step run)
    integrate.solve_ivp(
        rhs, (0, T * 1e-6), y0, method="DOP853", rtol=rtol, atol=atol
    )
    t0 = time.perf_counter()
    out = integrate.solve_ivp(
        rhs, (0, T), y0, method="DOP853", rtol=rtol, atol=atol
    )
    wall = time.perf_counter() - t0

final = np.asarray(out.y)[:, -1]
if cdtype == np.complex64:
    final = final[:nst] + 1j * final[nst:]
print(f"steps: {len(out.t) - 1}  nfev: {out.nfev}  wall: {wall:.2f} s")
print(f"norm drift: {abs(np.linalg.norm(final) - 1.0):.2e}")
print(f"MIS size: {int(mis.optimum)}  "
      f"optimum overlap: {mis.optimum_overlap(final):.4f}  "
      f"cost: {mis.cost_function(final):.4f}")
print(f"Iterations / sec: {(len(out.t) - 1) / wall:.3f}")
