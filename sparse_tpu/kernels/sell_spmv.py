"""SELL-C-sigma packing and the prepared operator for general (non-banded) CSR.

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
across a whole solve. The product is the pure-XLA slab formulation
(``ops.spmv.csr_spmv_sell``): Mosaic has no lowering for a gather inside
VMEM ("Cannot do int indexing on TPU"), so there is no Pallas kernel here.

The slabs produce ``A @ x`` in the pack's own row order;
``csr_spmv_sell`` ends in one more gather, through ``pos``, back into the
caller's. A caller that keeps its vectors in the pack's order needs none
(``ops.spmv.csr_spmv_sell_packed`` is the product without it, and
``batch.operator._PackOrder`` the session's gather bucket program built
on it); the pack itself, and its vault artifact, are the same for both.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..ops.spmv import csr_spmm_sell, csr_spmv_sell


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


# Slab rows pad to a sublane multiple. A pad row carries idx 0 / val 0 in
# every slot (it contributes 0 * x[0]: a pad row of the packed output is
# zero), `pos` addresses real rows only and so drops it, and a loop that
# keeps its vectors in the pack's order holds zero there
# (``batch.operator._PackOrder``). Kept small: slab-count x ROW_ALIGN x K
# is pure pad storage.
ROW_ALIGN = 8

# Past WIDE_PERIOD rows a slab's row count is a multiple of WINDOW_ROWS inside
# the band WIDE_BAND of remainders mod WIDE_PERIOD (:func:`slab_rows`). The
# numbers are observed properties of one TPU compiler (libtpu 0.0.34, v5e),
# not of the format, and the row count buys two things with them:
#
# * the gather's step. A gather of ``[R, lanes]`` rows at 64 lanes or more is
#   lowered on a step of 256 rows or of 128, chosen from R; the narrow step
#   runs at 9.9 ns a row where the wide one runs at 4.0 (PERF.md sections 5
#   and 7). R = 1024 b is narrow, so is a stretch below each multiple of
#   1024 whose start wanders (776 to 904 by b); every remainder in [8, 768]
#   compiled to the wide step. Sufficient, not necessary.
# * the multiply-sum's window. A slab's product is written into its rows of
#   the whole product in place (a loop fusion rooted in a
#   dynamic-update-slice), and for such a fusion the compiler takes as the
#   window of a trip an exact divisor of the slab's count of 8-row tiles,
#   R / 8, the largest its fast memory holds beside the slab's operands
#   (152 tiles at 7 operands, 89 at 11). A count with no such divisor
#   (8 x a prime: 57,944; 8 x 179 x 241: 345,112) is left the window of one
#   tile, 4 KB an operand a trip, and ran at 2.8 and 3.4 ns a slot row on
#   the chip where its neighbours ran at 1.3 to 1.5. A multiple of
#   WINDOW_ROWS has every power of two up to WINDOW_ROWS / 8 tiles to
#   offer; on the chip a window of 32 tiles ran as fast as one of 152, one
#   of 8 three percent slower (PERF.md section 6, PR 41).
#
# Held by ``tests/test_chip_compile.py`` (the cases on the gather's step and
# on the slab fusions' windows, each with its other side), which fail once
# the compiler stops telling the counts apart. If the numbers stop being
# true they cost the pad rows and nothing else: at most 511 zero rows a
# slab. At or below 1024 rows a gather and a multiply-sum are microseconds
# either way, and the count stays what ROW_ALIGN gives.
WIDE_PERIOD = 1024
WIDE_BAND = (8, 768)
WINDOW_ROWS = 256


def slab_rows(n: int) -> int:
    """Rows a slab (or a space made of slabs) of ``n`` real rows is stored
    with: ``n`` rounded up to ``ROW_ALIGN``, and past ``WIDE_PERIOD`` rows
    to the next multiple of ``WINDOW_ROWS`` whose remainder mod
    ``WIDE_PERIOD`` is in ``WIDE_BAND``. A function of the row count alone:
    the pack is a vault artifact and must not depend on where it was built.
    Idempotent."""
    R = _round_up(n, ROW_ALIGN)
    if R <= WIDE_PERIOD:
        return R
    lo, hi = WIDE_BAND
    R = _round_up(R, WINDOW_ROWS)
    while not lo <= R % WIDE_PERIOD <= hi:  # twice at most
        R += WINDOW_ROWS
    return R


class SellPlan:
    """Static geometry of a packed SELL operator (hashable => jit-static).

    ``slab_meta`` is a tuple of ``(K, rows, pad_rows)`` per slab —
    ``rows`` is what :func:`slab_rows` gives the slab's real rows (the
    alignment to ``ROW_ALIGN`` and, past 1024 rows, to a multiple of
    ``WINDOW_ROWS`` in the gather's wide band), ``pad_rows`` counts the
    zero rows that added.
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
    def pad_rows(self) -> int:
        """Zero rows the pack added to its slabs (:func:`slab_rows`)."""
        return sum(p for _k, _r, p in self.slab_meta)

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
        R = slab_rows(len(rws))
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


class PreparedCSR:
    """A general CSR operator packed once into the SELL slab layout.

    The prepare/execute split for non-banded SpMV (the counterpart of
    round-3's :class:`~sparse_tpu.kernels.dia_spmv.PreparedDia`): one-time
    host packing, then every call is gathers + adds over resident planes.
    Format classes obtain one through ``sparse_tpu.plan_cache`` so solver
    loops (and repeated ``A @ x`` calls) never repack.

    ``__call__`` is the XLA slab formulation, whatever the mode.
    """

    __slots__ = ("plan", "slabs", "pos", "__weakref__")

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

    def matvec_xla(self, x):
        return csr_spmv_sell(
            self.slabs, self.pos, jnp.asarray(x), self.plan.zero_rows
        )

    def matmat(self, B):
        return csr_spmm_sell(
            self.slabs, self.pos, jnp.asarray(B), self.plan.zero_rows
        )

    def __call__(self, x):
        from .. import telemetry

        telemetry.count("kernel.sell_spmv")
        return self.matvec_xla(x)
