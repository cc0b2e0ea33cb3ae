"""Implicit heat-equation integration: BDF + sparse Laplacian Jacobian.

u_t = alpha * Lap(u) on an n x n grid (Dirichlet), semidiscretized to the
stiff linear ODE y' = alpha * L y with L this library's 5-point Laplacian.
The explicit RK methods need h ~ 1/||L|| steps (CFL); BDF takes steps
bounded only by accuracy, with each Newton solve an MXU-tiled LU apply —
the workload the reference's explicit-only integrate.py cannot run at
this stiffness. Usage:

    python examples/heat_implicit.py -n 24 -alpha 1.0 -t 0.1 [-explicit]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)  # stiff Newton wants f64

from sparse_tpu import csr_array  # noqa: E402
from sparse_tpu.integrate import solve_ivp  # noqa: E402
from sparse_tpu.models.poisson import laplacian_2d_csr_host  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", type=int, default=24)
    ap.add_argument("-alpha", type=float, default=1.0)
    ap.add_argument("-t", type=float, default=0.5)
    ap.add_argument("-rtol", type=float, default=1e-6)
    ap.add_argument("-explicit", action="store_true",
                    help="also time RK45 for the stiffness comparison")
    args = ap.parse_args()

    n = args.n
    A = laplacian_2d_csr_host(n)  # positive-definite 5-point stencil
    scale = args.alpha * (n + 1) ** 2  # 1/h^2: the true discrete Laplacian
    L = csr_array((-scale) * A.tocsr())  # y' = -alpha/h^2 A y (decay)
    N = n * n
    # interior Dirichlet nodes i/(n+1): sin(pi x)sin(pi y) sampled here
    # IS the discrete mode-1 eigenvector, so the decay check is exact
    x = np.linspace(0, 1, n + 2)[1:-1]
    X, Y = np.meshgrid(x, x, indexing="ij")
    y0 = (np.sin(np.pi * X) * np.sin(np.pi * Y)).ravel()

    def rhs(t, y):
        return L @ y

    t0 = time.perf_counter()
    sol = solve_ivp(rhs, (0.0, args.t), y0, method="BDF", jac=L,
                    rtol=args.rtol, atol=1e-9)
    dt_bdf = time.perf_counter() - t0
    print(f"BDF:  status={sol.status} steps={len(sol.t) - 1} "
          f"nfev={sol.nfev} nlu={sol.nlu} wall={dt_bdf:.2f}s")

    # the lowest Laplacian mode decays as exp(-lam1*t); compare
    lam1 = 4 * scale * (1 - np.cos(np.pi / (n + 1)))
    u_T = np.asarray(sol.y)[:, -1]
    decay = float(u_T @ y0 / (y0 @ y0))
    print(f"mode-1 decay: measured {decay:.6f} vs exp(-lam1*t) "
          f"{np.exp(-lam1 * args.t):.6f}")

    if args.explicit:
        t0 = time.perf_counter()
        rk = solve_ivp(rhs, (0.0, args.t), y0, method="RK45",
                       rtol=args.rtol, atol=1e-9)
        dt_rk = time.perf_counter() - t0
        print(f"RK45: status={rk.status} steps={len(rk.t) - 1} "
              f"nfev={rk.nfev} wall={dt_rk:.2f}s "
              f"(stiffness ratio nfev: {rk.nfev / max(sol.nfev, 1):.1f}x)")


if __name__ == "__main__":
    main()
