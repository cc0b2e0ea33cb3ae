"""No-x64 test lane (VERDICT r2 #7): real TPUs run WITHOUT x64.

The conftest enables x64 globally for exact scipy-oracle comparisons, so
these scenarios run in SUBPROCESSES with x64 disabled and
``-W error::UserWarning`` — any int64-truncation warning (the silent
downcast hazard of the real-TPU config) fails the lane, not just wrong
results. Covers the marked subset VERDICT names: conversions, sort,
solvers, dist.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_ENABLE_X64", None)
import jax
jax.config.update("jax_platforms", "cpu")
assert not jax.config.jax_enable_x64
import json
import numpy as np
import scipy.sparse as sp
import sparse_tpu as sparse
"""


def run_nox64(code: str, ndev: int = 8, timeout: int = 900) -> dict:
    env = dict(os.environ)
    env.pop("JAX_ENABLE_X64", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    proc = subprocess.run(
        [sys.executable, "-W", "error::UserWarning", "-c", PRELUDE + code],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, (
        f"no-x64 payload rc={proc.returncode}\n--- stderr ---\n"
        f"{proc.stderr[-4000:]}"
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_nox64_conversions_and_sort():
    """COO->CSR (device sort path), CSR<->CSC<->dense round trips in f32."""
    rec = run_nox64(r"""
rng = np.random.default_rng(0)
As = sp.random(60, 45, density=0.2, random_state=1, format="coo").astype(np.float32)
C = sparse.coo_array((As.data.copy(), (As.row.copy(), As.col.copy())), shape=As.shape)
csr = C.tocsr()
csc = csr.tocsc()
back = csc.tocsr()
dense_ok = bool(np.allclose(np.asarray(csr.toarray()), As.toarray()))
rt_ok = bool(np.allclose(np.asarray(back.toarray()), As.toarray()))
print(json.dumps({"ok": dense_ok and rt_ok}))
""")
    assert rec["ok"]


def test_nox64_spgemm_and_elemwise():
    rec = run_nox64(r"""
a = sp.random(40, 30, density=0.2, random_state=2, format="csr").astype(np.float32)
b = sp.random(30, 35, density=0.2, random_state=3, format="csr").astype(np.float32)
A = sparse.csr_array(a)
B = sparse.csr_array(b)
prod_ok = bool(np.allclose(np.asarray((A @ B).toarray()), (a @ b).toarray(), atol=1e-5))
c = sp.random(40, 30, density=0.2, random_state=4, format="csr").astype(np.float32)
Cm = sparse.csr_array(c)
add_ok = bool(np.allclose(np.asarray((A + Cm).toarray()), (a + c).toarray(), atol=1e-6))
mul_ok = bool(np.allclose(np.asarray(A.multiply(Cm).toarray()), (a.multiply(c)).toarray(), atol=1e-6))
print(json.dumps({"ok": prod_ok and add_ok and mul_ok}))
""")
    assert rec["ok"]


def test_nox64_solvers():
    """cg / gmres / lsqr / eigsh in f32 without x64."""
    rec = run_nox64(r"""
import sparse_tpu.linalg as linalg
n = 64
s = sp.diags([np.full(n - 1, -1.0), np.full(n, 2.1), np.full(n - 1, -1.0)],
             [-1, 0, 1], format="csr").astype(np.float32)
A = sparse.csr_array(s)
b = np.ones(n, dtype=np.float32)
x, iters = linalg.cg(A, b, tol=1e-4)
cg_ok = bool(np.linalg.norm(np.asarray(A @ x) - b) < 1e-2)
xg, _ = linalg.gmres(A, b, tol=1e-5)
gm_ok = bool(np.linalg.norm(np.asarray(A @ xg) - b) < 1e-2)
xl = linalg.lsqr(A, b)[0]
ls_ok = bool(np.linalg.norm(np.asarray(A @ xl) - b) < 1e-2)
w = linalg.eigsh(A, k=3, tol=1e-4, return_eigenvectors=False)
dense_w = np.linalg.eigvalsh(s.toarray().astype(np.float64))
ei_ok = bool(np.allclose(np.sort(np.abs(np.asarray(w, dtype=np.float64))),
                         np.sort(np.abs(dense_w))[-3:], rtol=1e-3))
print(json.dumps({"ok": cg_ok and gm_ok and ls_ok and ei_ok,
                  "parts": [cg_ok, gm_ok, ls_ok, ei_ok]}))
""")
    assert rec["ok"], rec


def test_nox64_dist():
    """Distributed CG (halo SpMV) + image-gather SpGEMM + 2-D shuffle on
    the 8-device mesh without x64 — the exact real-TPU configuration of
    the multi-chip dryrun."""
    rec = run_nox64(r"""
from sparse_tpu.models.poisson import laplacian_2d_csr_host
from sparse_tpu.parallel import dist_spgemm, dist_spgemm_2d
from sparse_tpu.parallel.dist import dist_cg, shard_csr
from sparse_tpu.parallel.mesh import get_mesh, get_mesh_2d

A = laplacian_2d_csr_host(24, dtype=np.float32)  # 576 rows
D = shard_csr(A, mesh=get_mesh(8), balanced=True)
rng = np.random.default_rng(0)
b = rng.standard_normal(A.shape[0]).astype(np.float32)
xp, iters, conv = dist_cg(D, b, tol=1e-4, maxiter=600, conv_test_iters=25)
x = D.unpad_vector(xp)
As = sp.csr_matrix((np.asarray(A.data), np.asarray(A.indices), np.asarray(A.indptr)), A.shape)
cg_ok = bool(np.linalg.norm(As @ x - b) < 1e-2 * np.linalg.norm(b))
C1 = dist_spgemm(A, A, mesh=get_mesh(8))
g1_ok = bool(np.allclose(np.asarray(C1.toarray()), (As @ As).toarray(), atol=1e-3))
C2 = dist_spgemm_2d(A, A, mesh2d=get_mesh_2d(8))
g2_ok = bool(np.allclose(np.asarray(C2.toarray()), (As @ As).toarray(), atol=1e-3))
print(json.dumps({"ok": cg_ok and g1_ok and g2_ok,
                  "parts": [cg_ok, g1_ok, g2_ok]}))
""")
    assert rec["ok"], rec
