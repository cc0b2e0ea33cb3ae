"""SELL-C-sigma packing + Pallas row-block SpMV for general (non-banded) CSR.

Older records put a ~1000x gap between the banded fast path and the
general one (not measured on the current chip; ROADMAP S3). DIA only covers
banded matrices, so
every non-banded workload (eigsh, integrate Jacobians, csgraph, AMG
hierarchies) paid the slow path per matvec. SELL-C-sigma (Kreutzer et al.,
SISC 2014) is the standard SIMD-friendly packing for skewed row profiles
on wide-vector hardware:

  * rows are sorted by degree within sigma-row windows (bounded reordering
    keeps cache locality of x), then sliced into chunks of C rows;
  * each chunk is padded to its OWN max degree — near-zero pad waste even
    under power-law skew, where plain ELL pads every row to the global max;
  * chunks of equal padded width are grouped into **slabs**, each stored as
    plane-major ``[K, R]`` index/value planes, so SpMV is contiguous 1-D
    gathers + VPU adds per plane (the shape TPUs like; no scatter, no
    segment ids) with a bounded number of static shapes per matrix.

Packing is one-time host-side work (the prepare/execute split — the
reference keeps its CSR stores resident across task launches the same
way; legate.sparse ``set_key_partition``, SURVEY §1); the packed operator
is cached library-wide in ``sparse_tpu.plan_cache`` so solvers reuse it
across a whole solve. The pure-XLA formulation (``ops.spmv.csr_spmv_sell``)
is the portable default; the Pallas row-block kernel here additionally
pins x and the slab planes in VMEM (grid over row blocks of chunks) and
runs in interpret mode off-TPU like ``dia_spmv.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.spmv import csr_spmm_sell, csr_spmv_sell


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


# Slab rows pad to a sublane multiple so the Pallas row blocks tile exactly
# (the row-block tile is the largest power-of-two divisor, see
# ``sell_spmv_pallas``); pad rows carry idx 0 / val 0 (contribute 0 * x[0])
# and are dropped by the pos-gather, which only addresses real rows. Kept
# small: slab-count x ROW_ALIGN x K is pure pad storage.
ROW_ALIGN = 8
# Pallas attempt gates (beyond these the XLA formulation is simply better
# suited: x must fit VMEM whole, and every plane is unrolled in the trace).
PALLAS_MAX_X = 1 << 20
PALLAS_MAX_K = 128


class SellPlan:
    """Static geometry of a packed SELL operator (hashable => jit-static).

    ``slab_meta`` is a tuple of ``(K, rows, pad_rows)`` per slab —
    ``rows`` includes the alignment padding, ``pad_rows`` counts it.
    """

    __slots__ = ("m", "n", "C", "sigma", "slab_meta", "zero_rows", "nnz")

    def __init__(self, m, n, C, sigma, slab_meta, zero_rows, nnz):
        self.m, self.n, self.C, self.sigma = m, n, C, sigma
        self.slab_meta = tuple((int(k), int(r), int(p)) for k, r, p in slab_meta)
        self.zero_rows = int(zero_rows)
        self.nnz = int(nnz)

    @property
    def stored_slots(self) -> int:
        return sum(k * r for k, r, _ in self.slab_meta)

    @property
    def pad_ratio(self) -> float:
        """Stored slots per nonzero (1.0 = zero pad waste)."""
        return self.stored_slots / max(self.nnz, 1)

    def _key(self):
        return (self.m, self.n, self.C, self.sigma, self.slab_meta, self.zero_rows)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, SellPlan) and self._key() == other._key()

    def __repr__(self):
        return (
            f"SellPlan(m={self.m}, n={self.n}, C={self.C}, sigma={self.sigma}, "
            f"slabs={len(self.slab_meta)}, pad_ratio={self.pad_ratio:.3f})"
        )


def sell_pack(indptr, indices, data, shape, C=None, sigma=None, max_slabs=None,
              with_srcs=False):
    """Pack host CSR buffers into the SELL-C-sigma slab layout.

    Pure numpy (construction-time, never inside solver loops — the same
    discipline as ``ops.conv``). Returns ``(plan, slabs, pos)`` where
    ``slabs`` is a tuple of plane-major ``(idx_t, val_t)`` jnp pairs and
    ``pos`` maps original row -> packed position. Chunk widths are grouped
    exactly; if that yields more than ``max_slabs`` distinct widths
    (pathological profiles), widths quantize up to powers of two first —
    at most 2x pad on the affected chunks, bounded compile size always.

    ``with_srcs=True`` additionally returns a tuple of per-slab ``[K, R]``
    source maps (packed slot -> original nnz position, -1 for pad slots):
    the pattern-reuse handle of the batched subsystem
    (``sparse_tpu.batch.operator``) — a whole stack of same-pattern value
    vectors repacks on device as one gather through these maps, so the
    host-side pack runs once per *pattern*, not once per matrix.
    """
    from ..config import settings

    C = int(C or settings.sell_chunk)
    sigma = int(sigma if sigma is not None else settings.sell_sigma)
    max_slabs = int(max_slabs or settings.sell_max_slabs)
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    data = np.asarray(data)
    m, n = int(shape[0]), int(shape[1])
    nnz = int(data.shape[0])
    counts = (indptr[1:] - indptr[:-1]).astype(np.int64)

    # sigma-window degree sort (descending, stable): bounded reordering.
    sigma_eff = max(min(sigma if sigma > 0 else m, m), 1) if m else 1
    perm = np.arange(m, dtype=np.int64)
    for lo in range(0, m, sigma_eff):
        hi = min(lo + sigma_eff, m)
        order = np.argsort(-counts[lo:hi], kind="stable")
        perm[lo:hi] = lo + order

    # C-row chunks, each padded to its own max degree.
    nchunks = (m + C - 1) // C
    chunk_w = np.zeros(nchunks, dtype=np.int64)
    for c in range(nchunks):
        rws = perm[c * C : (c + 1) * C]
        chunk_w[c] = counts[rws].max() if rws.size else 0

    widths = np.unique(chunk_w[chunk_w > 0])
    if len(widths) > max_slabs:
        chunk_w = np.where(
            chunk_w > 0, 2 ** np.ceil(np.log2(chunk_w.clip(1))).astype(np.int64), 0
        )
        widths = np.unique(chunk_w[chunk_w > 0])

    idt = indices.dtype if indices.dtype in (np.int32, np.int64) else np.int32
    src_dt = np.int32 if nnz < 2**31 else np.int64
    slabs = []
    srcs = []
    slab_meta = []
    packed_rows = []  # original row ids, slab-major packed order
    for K in widths.tolist():
        chunks = np.nonzero(chunk_w == K)[0]
        rws = np.concatenate([perm[c * C : (c + 1) * C] for c in chunks])
        R = _round_up(len(rws), ROW_ALIGN)
        idx_t = np.zeros((K, R), dtype=idt)
        val_t = np.zeros((K, R), dtype=data.dtype)
        L = counts[rws]
        rr = np.repeat(np.arange(len(rws), dtype=np.int64), L)
        slot = np.arange(int(L.sum()), dtype=np.int64) - np.repeat(
            np.cumsum(L) - L, L
        )
        src = np.repeat(indptr[rws].astype(np.int64), L) + slot
        idx_t[slot, rr] = indices[src]
        val_t[slot, rr] = data[src]
        slabs.append((jnp.asarray(idx_t), jnp.asarray(val_t)))
        if with_srcs:
            src_t = np.full((K, R), -1, dtype=src_dt)
            src_t[slot, rr] = src.astype(src_dt)
            srcs.append(jnp.asarray(src_t))
        slab_meta.append((K, R, R - len(rws)))
        packed_rows.append(rws)
        packed_rows.append(np.full(R - len(rws), -1, dtype=np.int64))  # pad rows

    # trailing zero block for all-empty rows (chunk width 0)
    zero_chunks = np.nonzero(chunk_w == 0)[0]
    zero_rws = (
        np.concatenate([perm[c * C : (c + 1) * C] for c in zero_chunks])
        if len(zero_chunks)
        else np.zeros(0, dtype=np.int64)
    )
    packed_rows.append(zero_rws)

    flat = np.concatenate(packed_rows) if packed_rows else np.zeros(0, np.int64)
    pos = np.zeros(m, dtype=np.int64)
    real = flat >= 0
    pos[flat[real]] = np.nonzero(real)[0]
    pos_dt = np.int32 if len(flat) < 2**31 else np.int64

    plan = SellPlan(m, n, C, sigma_eff, slab_meta, len(zero_rws), nnz)
    if with_srcs:
        return plan, tuple(slabs), jnp.asarray(pos.astype(pos_dt)), tuple(srcs)
    return plan, tuple(slabs), jnp.asarray(pos.astype(pos_dt))


# ---------------------------------------------------------------------------
# Pallas row-block kernel: x + one slab's [K, TM] plane window in VMEM,
# grid over TM-row blocks of the slab (TM rows = TM/C chunks per step).
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("K", "TM", "interpret", "acc_dtype"))
def _sell_slab_pallas(idx_t, val_t, x, K: int, TM: int, interpret: bool = False,
                      acc_dtype=None):
    R = idx_t.shape[1]
    out_dt = acc_dtype or jnp.result_type(val_t.dtype, x.dtype)

    def kernel(x_ref, idx_ref, val_ref, y_ref):
        acc = jnp.zeros((TM,), dtype=out_dt)
        for k in range(K):  # static per slab: plane loads unroll
            # value planes load at their storage width; the in-register
            # convert widens the product to the accumulation dtype
            # (a no-op when acc_dtype is None — ISSUE 15)
            acc = acc + (
                val_ref[k, :].astype(out_dt)
                * x_ref[idx_ref[k, :]].astype(out_dt)
            )
        y_ref[:] = acc

    return pl.pallas_call(
        kernel,
        name="sell_slab_pallas",
        grid=(R // TM,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # x resident whole
            pl.BlockSpec((K, TM), lambda g: (0, g), memory_space=pltpu.VMEM),
            pl.BlockSpec((K, TM), lambda g: (0, g), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((TM,), lambda g: (g,), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((R,), out_dt),
        interpret=interpret,
    )(x, idx_t, val_t)


def sell_spmv_pallas(plan: SellPlan, slabs, pos, x, interpret=None,
                     acc_dtype=None):
    """y = A @ x via the per-slab Pallas row-block kernel (+ XLA glue for
    the concat/pos-gather). ``interpret=None`` auto-selects interpret mode
    off-TPU like ``dia_spmv.py``. Raises when Mosaic cannot lower the
    in-VMEM gather — callers go through :class:`PreparedCSR`, which fails
    over to the XLA formulation once and remembers. ``acc_dtype`` is the
    storage/accumulation split (ISSUE 15): narrow value planes, wide
    in-register accumulation."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out_dt = acc_dtype or jnp.result_type(
        slabs[0][1].dtype if slabs else x.dtype, x.dtype
    )
    parts = []
    for (idx_t, val_t), (K, R, _) in zip(slabs, plan.slab_meta):
        TM = ROW_ALIGN  # rows are ROW_ALIGN-padded, so this always divides
        while TM * 2 <= 1024 and R % (TM * 2) == 0:
            TM *= 2
        parts.append(
            _sell_slab_pallas(idx_t, val_t, x, K, TM, interpret,
                              acc_dtype=acc_dtype).astype(out_dt)
        )
    if plan.zero_rows:
        parts.append(jnp.zeros((plan.zero_rows,), dtype=out_dt))
    if not parts:
        return jnp.zeros((plan.m,), dtype=out_dt)
    packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return packed[pos]


@partial(jax.jit, static_argnames=("K", "TM", "interpret", "acc_dtype"))
def _sell_slab_pallas_batched(idx_t, val_bt, X, K: int, TM: int,
                              interpret: bool = False, acc_dtype=None):
    """Batched form of :func:`_sell_slab_pallas`: the grid gains a leading
    batch dimension, the shared ``[K, R]`` index planes stay resident while
    value planes ``[B, K, R]`` and per-lane x vectors ``[B, n]`` stream one
    lane at a time — the whole same-pattern stack runs as one kernel launch
    instead of B dispatches. ``acc_dtype`` widens the per-plane products
    in-register (ISSUE 15) while the value planes stream at storage
    width."""
    B, _, R = val_bt.shape
    out_dt = acc_dtype or jnp.result_type(val_bt.dtype, X.dtype)

    def kernel(x_ref, idx_ref, val_ref, y_ref):
        acc = jnp.zeros((TM,), dtype=out_dt)
        for k in range(K):  # static per slab: plane loads unroll
            acc = acc + (
                val_ref[0, k, :].astype(out_dt)
                * x_ref[0, idx_ref[k, :]].astype(out_dt)
            )
        y_ref[0, :] = acc

    return pl.pallas_call(
        kernel,
        name="sell_slab_pallas_batched",
        grid=(B, R // TM),
        in_specs=[
            # one lane of x resident per grid step
            pl.BlockSpec((1, X.shape[1]), lambda b, g: (b, 0),
                         memory_space=pltpu.VMEM),
            # index planes are PATTERN state: shared by every lane
            pl.BlockSpec((K, TM), lambda b, g: (0, g),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, K, TM), lambda b, g: (b, 0, g),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, TM), lambda b, g: (b, g),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, R), out_dt),
        interpret=interpret,
    )(X, idx_t, val_bt)


def sell_spmv_pallas_batched(plan: SellPlan, idx_slabs, val_slabs, pos, X,
                             interpret=None, acc_dtype=None):
    """Y = A_b @ x_b per lane via the batch-grid Pallas row-block kernel.

    ``idx_slabs`` are the shared pattern index planes, ``val_slabs`` the
    stacked ``[B, K, R]`` value planes (``sparse_tpu.batch.operator`` packs
    them through the pattern's source maps), ``X`` is ``[B, n]``. Same
    failover contract as :func:`sell_spmv_pallas` — callers catch the
    Mosaic lowering error once and fall back to the XLA formulation.
    ``acc_dtype`` is the storage/accumulation split (ISSUE 15)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B = X.shape[0]
    out_dt = acc_dtype or jnp.result_type(
        val_slabs[0].dtype if val_slabs else X.dtype, X.dtype
    )
    parts = []
    for idx_t, val_bt, (K, R, _) in zip(idx_slabs, val_slabs, plan.slab_meta):
        TM = ROW_ALIGN  # rows are ROW_ALIGN-padded, so this always divides
        while TM * 2 <= 1024 and R % (TM * 2) == 0:
            TM *= 2
        parts.append(
            _sell_slab_pallas_batched(idx_t, val_bt, X, K, TM, interpret,
                                      acc_dtype=acc_dtype)
            .astype(out_dt)
        )
    if plan.zero_rows:
        parts.append(jnp.zeros((B, plan.zero_rows), dtype=out_dt))
    if not parts:
        return jnp.zeros((B, plan.m), dtype=out_dt)
    packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return packed[:, pos]


class PreparedCSR:
    """A general CSR operator packed once into the SELL slab layout.

    The prepare/execute split for non-banded SpMV (the counterpart of
    round-3's :class:`~sparse_tpu.kernels.dia_spmv.PreparedDia`): one-time
    host packing, then every call is gathers + adds over resident planes.
    Format classes obtain one through ``sparse_tpu.plan_cache`` so solver
    loops (and repeated ``A @ x`` calls) never repack.

    ``__call__`` dispatches per ``settings.spmv_mode``: the Pallas kernel
    under ``'pallas'`` (gated on f32 / VMEM-resident x / bounded plane
    count, failing over to XLA once — remembered — when the backend has no
    lowering), the XLA slab formulation otherwise.
    """

    __slots__ = ("plan", "slabs", "pos", "__weakref__")

    #: failover-registry kernel name (resilience/failover.py)
    KERNEL = "sell_spmv"

    def __init__(self, indptr, indices, data, shape, C=None, sigma=None,
                 max_slabs=None):
        self.plan, self.slabs, self.pos = sell_pack(
            indptr, indices, data, shape, C=C, sigma=sigma, max_slabs=max_slabs
        )
        from .. import telemetry

        telemetry.count("kernel.sell_pack")

    @classmethod
    def from_parts(cls, plan: SellPlan, slabs, pos) -> "PreparedCSR":
        """Reassemble a prepared operator from already-packed parts —
        the vault codec's constructor (``sparse_tpu.vault._codecs``): a
        verified disk artifact re-enters without re-running the host
        pack (and without counting a fresh ``kernel.sell_pack``)."""
        prep = object.__new__(cls)
        prep.plan = plan
        prep.slabs = tuple((it, vt) for it, vt in slabs)
        prep.pos = pos
        return prep

    @property
    def shape(self):
        return (self.plan.m, self.plan.n)

    def _pallas_viable(self, x) -> bool:
        from ..resilience import failover

        if jax.default_backend() == "tpu":
            # Mosaic refuses the in-VMEM gather ("Cannot do int indexing
            # on TPU", pinned by tests/test_chip_compile.py): on a TPU
            # the XLA slab form is the choice, not a failover
            return False
        if failover.failed(self.KERNEL, self) or not self.slabs:
            return False
        if x.shape[0] > PALLAS_MAX_X:
            return False
        if any(K > PALLAS_MAX_K for K, _, _ in self.plan.slab_meta):
            return False
        dt = jnp.result_type(self.slabs[0][1].dtype, x.dtype)
        return dt == jnp.float32

    def matvec_xla(self, x):
        return csr_spmv_sell(
            self.slabs, self.pos, jnp.asarray(x), self.plan.zero_rows
        )

    def matvec_pallas(self, x, interpret=None):
        return sell_spmv_pallas(
            self.plan, self.slabs, self.pos, jnp.asarray(x), interpret
        )

    def matmat(self, B):
        return csr_spmm_sell(
            self.slabs, self.pos, jnp.asarray(B), self.plan.zero_rows
        )

    def probe_pallas(self, x=None) -> bool:
        """Probe-based reinstate hook: run one real Pallas matvec; on
        success any failover latch for this operator clears
        (``kernel.reinstate`` event) and later calls retry the kernel."""
        from ..resilience import failover

        if x is None:
            x = jnp.zeros((self.plan.n,), dtype=jnp.float32)
        return failover.probe(
            self.KERNEL, self,
            lambda: jax.block_until_ready(self.matvec_pallas(x)),
        )

    def __call__(self, x):
        from .. import telemetry
        from ..config import settings
        from ..resilience import failover

        telemetry.count("kernel.sell_spmv")
        if settings.spmv_mode == "pallas" and self._pallas_viable(x):
            try:
                # forced-failure injection + the shared one-time
                # Pallas->XLA failover ladder (resilience/failover.py)
                failover.maybe_inject(self.KERNEL)
                return self.matvec_pallas(x)
            except (ValueError, NotImplementedError) as e:
                failover.handle(self.KERNEL, self, e)
        return self.matvec_xla(x)
