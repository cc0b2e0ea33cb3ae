"""Distributed CSR: mesh-sharded matrices, halo-exchange SpMV, padded vectors.

This is the TPU-native replacement for the reference's partitioning layer
(``sparse/partition.py`` + ``sparse/base.py:194-296``): Legion's dependent
partitioning (CompressedImagePartition / MinMaxImagePartition / DensePreimage)
becomes a one-time host-side layout decision, after which every operation is a
static-shape SPMD program over a ``jax.sharding.Mesh``.

Layout (S = mesh size):
  * rows are split into S blocks at ``row_splits`` (equal or nnz-balanced —
    the ``DenseSparseBase.balance`` analog, base.py:198-282), each padded to
    ``R = max`` rows so shards are uniform;
  * dense vectors live in **padded row-block layout**: shape ``[S*R]`` sharded
    ``P('shards')``, entries beyond a block's real rows are zero;
  * column ids are remapped into the same padded coordinate space at
    construction, so x-gathers are direct indexed loads;
  * per-shard nonzeros are stored as D diagonal planes ``[S*R]``
    (banded operators: D vectors in the padded row layout, the local
    product is D shifted multiply-adds over the halo slab, no index loads at
    all), as stacked ELL planes ``[S, R, k]``
    (bounded-degree: gather + VPU reduce) or as stacked padded CSR
    ``[S, K]`` + row ids (general profile);
  * the x-window each shard needs (the MinMaxImagePartition analog,
    partition.py:139-214) becomes a **static halo width H**: SpMV fetches the
    H-wide tails of its mesh neighbors with ``lax.ppermute`` over ICI and runs
    a purely local kernel. Matrices whose windows exceed the halo budget fall
    back to an ``all_gather`` of x (the replicate-x fallback).

All comms are XLA collectives (ppermute / all_gather / psum) riding ICI; the
only host work is the one-time layout construction.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..dia import banded_offsets
from ..telemetry import _metrics
from ..utils import asjnp
from . import comm
from .mesh import get_mesh
from .partition import balanced_row_splits, column_windows, equal_row_splits

from .mesh import shard_map  # version-portable (check_vma/check_rep shim)


@dataclass(eq=False)
class DistCSR:
    """A CSR matrix laid out over a 1-D device mesh.

    Square solver-facing matrices (m == n) share a single padded coordinate
    space for rows and columns; rectangular matrices keep separate row/column
    splits (columns follow the equal split of the x vector they multiply).
    """

    mesh: Mesh
    axis: str
    shape: tuple  # logical (m, n)
    row_splits: np.ndarray  # [S+1] host
    col_splits: np.ndarray  # [S+1] host (x-vector layout)
    R: int  # padded rows per shard
    C: int  # padded cols (x entries) per shard
    HL: int  # left halo width (cols), 0 when mode == "gather"
    HR: int  # right halo width; == HL unless settings.precise_windows
    mode: str  # "halo" | "gather"
    layout: str  # "dia" | "ell" | "csr"
    dtype: np.dtype
    # device arrays, all sharded P(axis) on their leading dim:
    ell_idx: jax.Array | None = None  # [S, R, k] padded-space col ids (rel. to window)
    ell_val: jax.Array | None = None  # [S, R, k]
    nz_rows: jax.Array | None = None  # [S, K] local row ids (csr layout)
    nz_cols: jax.Array | None = None  # [S, K] padded-space col ids (rel. to window)
    nz_vals: jax.Array | None = None  # [S, K]
    # D vectors [S*R] in the padded row layout (not one [S, D, R] stack: the
    # chip's compiler re-tiles and slices a stack inside every CG iteration);
    # plane d holds A[row, row + dia_offsets[d]] at the row's padded position
    dia_planes: tuple | None = None
    dia_offsets: tuple = ()  # static (col - row) of each plane, ascending
    _spmv_fn: object = field(default=None, repr=False, compare=False)
    _spmm_fn: object = field(default=None, repr=False, compare=False)
    _rspmm_fn: object = field(default=None, repr=False, compare=False)
    # compiled CG programs of this layout, by what changes the program
    # (_cg_program): dist_cg's second and later calls neither trace nor compile
    _cg_fns: dict = field(default_factory=dict, repr=False, compare=False)

    @property
    def S(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def m_pad(self) -> int:
        return self.S * self.R

    @property
    def n_pad(self) -> int:
        return self.S * self.C

    @property
    def H(self) -> int:
        return max(self.HL, self.HR)

    # -- compiled-program plans -------------------------------------------
    def _plan_fn(self, field_name: str, kind: str, build):
        """Resolve a compiled SPMD program through the library-wide plan
        cache (``sparse_tpu.plan_cache``) — the distributed opt-in: eager
        local-shard matvecs account one cache hit each, and the plan dies
        with this layout object. The per-object field stays authoritative
        for build-once semantics (a compiled ``shard_map`` program must
        never be rebuilt per call — ``jax.jit`` keys on the wrapper
        object), so disabling the cache costs only the counters."""
        from .. import plan_cache

        if getattr(self, field_name) is None:
            setattr(self, field_name, build())
        fn = getattr(self, field_name)
        cached = plan_cache.get(self, kind, lambda: fn)
        return cached if cached is not None else fn

    # -- vector layout helpers --------------------------------------------
    def pad_vector(self, x, splits=None, width=None) -> jax.Array:
        """Host/global vector [n] -> padded row-block layout [S*width], sharded."""
        splits = self.col_splits if splits is None else splits
        width = self.C if width is None else width
        x = np.asarray(x)
        S = self.S
        out = np.zeros((S, width), dtype=x.dtype)
        for s in range(S):
            lo, hi = int(splits[s]), int(splits[s + 1])
            out[s, : hi - lo] = x[lo:hi]
        return jax.device_put(
            out.reshape(S * width), NamedSharding(self.mesh, P(self.axis))
        )

    def pad_out_vector(self, y) -> jax.Array:
        """Pad a vector living in the *row* space (length m)."""
        return self.pad_vector(y, splits=self.row_splits, width=self.R)

    def unpad_vector(self, xp, splits=None, width=None) -> np.ndarray:
        splits = self.row_splits if splits is None else splits
        width = self.R if width is None else width
        xs = np.asarray(xp).reshape(self.S, width)
        return np.concatenate(
            [
                xs[s, : int(splits[s + 1]) - int(splits[s])]
                for s in range(self.S)
            ]
        )

    # -- SpMV --------------------------------------------------------------
    def _spmv_comm_bytes(self) -> int:
        """Structural per-SpMV collective volume (bytes across all shards),
        memoized — the counter ``spmv_padded`` accumulates per eager call."""
        b = getattr(self, "_spmv_bytes_cache", None)
        if b is None:
            b = int(comm_stats(self)["spmv_collective_bytes_per_shard"]) * self.S
            self._spmv_bytes_cache = b
        return b

    def _commit_comm(self, attr: str) -> None:
        """Fold one eager execution of a compiled program into the
        always-on measured-comm metrics (``comm.collective_bytes{op,site}``,
        ``parallel/comm.py``). Traced inner-loop calls are accounted at
        the solver level instead (``dist_cg``)."""
        led = getattr(self, attr, None)
        if led is not None and led.entries:
            from ..utils import in_trace

            if not in_trace():
                led.commit(1, self.S)

    def _blocks(self) -> tuple:
        """The mesh-sharded matrix blocks the compiled SpMV takes as
        ARGUMENTS (per layout)."""
        if self.layout == "dia":
            return self.dia_planes
        if self.layout == "ell":
            return (self.ell_idx, self.ell_val)
        return (self.nz_rows, self.nz_cols, self.nz_vals)

    def spmv_padded(self, xp: jax.Array, blocks: tuple | None = None) -> jax.Array:
        """y = A @ x entirely in padded layout ([n_pad] -> [m_pad]).

        This is the jit-safe inner-loop primitive; solvers call it inside
        ``lax.while_loop`` without any host sync. Telemetry counts eager
        dispatches and their structural comm volume (traced inner-loop
        calls are accounted at the solver level instead — ``comm.cg``).

        ``blocks`` (default: this matrix's own, :meth:`_blocks`) lets a
        compiled solver thread the blocks through as its own jit
        arguments instead of capturing them as constants.
        """
        from .. import telemetry

        if telemetry.enabled():
            from ..utils import in_trace

            if not in_trace():
                telemetry.count("comm.spmv.calls")
                telemetry.add_bytes("comm.spmv.total", self._spmv_comm_bytes())
        fn = self._plan_fn("_spmv_fn", "dist.spmv", lambda: _build_spmv(self))
        out = fn(xp, *(self._blocks() if blocks is None else blocks))
        # measured accounting: the trace populated the ledger by the time
        # the dispatch returns, so an eager call commits exactly one
        # program execution's collective volume
        self._commit_comm("_comm_ledger")
        return out

    # -- SpMM --------------------------------------------------------------
    def pad_matrix(self, B, splits=None, width=None) -> jax.Array:
        """Host [n, nB] -> padded row-block layout [S*width, nB], sharded."""
        splits = self.col_splits if splits is None else splits
        width = self.C if width is None else width
        B = np.asarray(B)
        S = self.S
        out = np.zeros((S, width, B.shape[1]), dtype=B.dtype)
        for s in range(S):
            lo, hi = int(splits[s]), int(splits[s + 1])
            out[s, : hi - lo] = B[lo:hi]
        return jax.device_put(
            out.reshape(S * width, B.shape[1]),
            NamedSharding(self.mesh, P(self.axis, None)),
        )

    def unpad_matrix(self, Cp, splits=None, width=None) -> np.ndarray:
        splits = self.row_splits if splits is None else splits
        width = self.R if width is None else width
        Cs = np.asarray(Cp).reshape(self.S, width, -1)
        return np.concatenate(
            [Cs[s, : int(splits[s + 1]) - int(splits[s])] for s in range(self.S)]
        )

    def spmm_padded(self, Bp: jax.Array) -> jax.Array:
        """C = A @ B in padded layout ([n_pad, nB] -> [m_pad, nB]).

        Row-split SpMM (reference SPMM_CSR_DENSE, csr.py:1151-1205): B rows
        follow x's layout; each shard halo-exchanges (or all_gathers) the B
        row-window it needs, then runs the local ELL/segment kernel.
        """
        # one jitted wrapper for all widths — jax.jit caches per shape
        fn = self._plan_fn(
            "_spmm_fn", "dist.spmm", lambda: _build_spmv(self, matrix=True)
        )
        out = fn(Bp, *self._blocks())
        self._commit_comm("_comm_ledger_spmm")
        return out

    def rspmm_padded(self, Bp: jax.Array) -> jax.Array:
        """C = B @ A with dense B in padded *row-space* layout [p, m_pad].

        k-split with output reduction (reference SPMM_DENSE_CSR,
        csr.py:1209-1240): each shard contracts its row block of A against
        its column slice of B and scatters into a full [p, n_pad] output;
        one ``psum`` over the mesh replicates the result — exactly the
        reference's ADD-reduction into a broadcast C.
        """
        fn = self._plan_fn("_rspmm_fn", "dist.rspmm", lambda: _build_rspmm(self))
        out = fn(Bp)
        self._commit_comm("_comm_ledger_rspmm")
        return out

    def dot(self, x) -> np.ndarray:
        """Convenience global SpMV/SpMM (pads, multiplies, unpads)."""
        x = np.asarray(x)
        if x.ndim == 2:
            Bp = self.pad_matrix(x)
            Cp = self.spmm_padded(Bp)
            return self.unpad_matrix(Cp)
        xp = self.pad_vector(x)
        yp = self.spmv_padded(xp)
        return self.unpad_vector(yp)

    def rdot(self, B) -> np.ndarray:
        """B @ A for dense host B ([p, m] -> [p, n])."""
        B = np.asarray(B)
        squeeze = B.ndim == 1
        if squeeze:
            B = B[None]
        Bp = self.pad_matrix(B.T, splits=self.row_splits, width=self.R).T
        Cp = self.rspmm_padded(Bp)
        Cs = np.asarray(Cp)  # [p, n_pad] replicated
        out = np.concatenate(
            [
                Cs[:, s * self.C : s * self.C + int(self.col_splits[s + 1]) - int(self.col_splits[s])]
                for s in range(self.S)
            ],
            axis=1,
        )
        return out[0] if squeeze else out

    def matvec(self, x, out=None):
        return self.dot(x)

    def as_operator(self, with_rmatvec: bool = False, source=None):
        """A LinearOperator over PADDED mesh-sharded vectors.

        This is how the generic Krylov solvers (``linalg.cg``, ``bicgstab``,
        ``gmres``, ...) run distributed WITHOUT dedicated mesh variants: the
        operator maps [n_pad] -> [m_pad] sharded arrays, the solver's whole
        ``lax.while_loop`` traces over them, and GSPMD turns every vdot/norm
        into a ``psum`` automatically — the reference gets the same effect
        from Legion's implicit partitioning of its task launches. Square
        matrices only (solver iterates live in one coordinate space).

        ``with_rmatvec`` additionally shards the TRANSPOSE layout (from
        ``source``, the host ``csr_array`` this layout was built from) on
        the swapped splits, so adjoint-needing solvers (``bicg``, ``lsqr``)
        run on the mesh too.
        """
        from ..linalg import LinearOperator

        if self.shape[0] != self.shape[1]:
            raise ValueError("as_operator() needs a square matrix")

        rmatvec = None
        if with_rmatvec:
            if source is None:
                raise ValueError(
                    "with_rmatvec needs the source csr_array to build the "
                    "transpose layout"
                )
            Dt = shard_csr(
                source.T.tocsr(),
                mesh=self.mesh,
                axis=self.axis,
                row_splits=self.col_splits,
                col_splits=self.row_splits,
            )
            if np.issubdtype(self.dtype, np.complexfloating):
                rmatvec = lambda x: jnp.conj(Dt.spmv_padded(jnp.conj(x)))
            else:
                rmatvec = Dt.spmv_padded

        return LinearOperator(
            (self.m_pad, self.n_pad),
            matvec=self.spmv_padded,
            rmatvec=rmatvec,
            dtype=self.dtype,
        )


def _build_spmv(A: DistCSR, matrix: bool = False):
    """Compile the shard_map SpMV/SpMM for this matrix's layout/mode.

    ``matrix=False`` -> vector SpMV ([n_pad] -> [m_pad]);
    ``matrix=True``  -> row-split SpMM ([n_pad, nB] -> [m_pad, nB]).
    """
    mesh, axis, S, R, C = A.mesh, A.axis, A.S, A.R, A.C
    HL, HR = A.HL, A.HR
    mode, layout = A.mode, A.layout
    perm_right = [(i, i + 1) for i in range(S - 1)]  # tail -> right neighbor
    perm_left = [(i + 1, i) for i in range(S - 1)]  # head -> left neighbor
    is_mat = matrix
    # measured-comm ledger: populated at trace time with the exact payload
    # bytes of every collective this program issues (parallel/comm.py);
    # per-object so distinct layouts/geometries never collide
    led = comm.SiteLedger("dist.spmm" if matrix else "dist.spmv")
    setattr(A, "_comm_ledger_spmm" if matrix else "_comm_ledger", led)

    def gather_x(x_l):
        """Each shard's addressable x/B slab from its local block (leading
        axis = the n dimension; halo/all_gather both slice it)."""
        if mode == "gather":
            # Replicate fallback: one all_gather over the mesh axis.
            return comm.all_gather(
                x_l, axis, axis_size=S, ledger=led, tag="x", tiled=True
            )  # [S*C, ...]
        if S == 1 or HL + HR == 0:
            return x_l
        parts = []
        if HL:
            parts.append(
                comm.ppermute(
                    x_l[-HL:], axis, perm_right, ledger=led, tag="halo_l"
                )
            )
        parts.append(x_l)
        if HR:
            parts.append(
                comm.ppermute(
                    x_l[:HR], axis, perm_left, ledger=led, tag="halo_r"
                )
            )
        return jnp.concatenate(parts)  # [HL + C + HR, ...]

    if layout == "dia":
        offsets = A.dia_offsets
        n_own = np.diff(np.asarray(A.row_splits))  # real rows of each shard
        uneven = bool(np.any(n_own != C))
        # the slab a shard multiplies is contiguous in GLOBAL coordinates
        # around its real rows: [WL | its block | WR], the block padded with
        # zeros and the halos written into the margins (one shard has no
        # neighbour: its margins stay zero). A pad and two small updates,
        # not a concatenate: the chip copies a concatenate's operands
        # through HBM, 46 % of an iteration at 1.6M rows (PERF.md, PR 27).
        WL, WR = (HL, HR) if S > 1 else (
            max(-min(offsets), 0), max(max(offsets), 0))

        def dia_slab(x_l):
            slab = jnp.pad(x_l, ((WL, WR),) + ((0, 0),) * (x_l.ndim - 1))
            if S == 1:
                return slab
            # uneven row blocks: a block's real rows end before its padding
            # does, so the sender cuts its real tail and the receiver puts
            # its right neighbour's head where its own real rows end
            n_s = (jnp.asarray(n_own, jnp.int32)[jax.lax.axis_index(axis)]
                   if uneven else C)
            if WL:
                tail = jax.lax.dynamic_slice_in_dim(x_l, n_s - WL, WL)
                slab = jax.lax.dynamic_update_slice_in_dim(
                    slab, comm.ppermute(tail, axis, perm_right, ledger=led,
                                        tag="halo_l"), 0, axis=0)
            if WR:
                head = comm.ppermute(
                    x_l[:WR], axis, perm_left, ledger=led, tag="halo_r")
                slab = jax.lax.dynamic_update_slice_in_dim(
                    slab, head, WL + n_s, axis=0)
            return slab

        def shard_fn(x_l, *planes):  # D planes of [R]
            with jax.named_scope("dist.halo"):
                slab = dia_slab(x_l)
            with jax.named_scope("dist.local_spmv"):
                out = None
                for plane, off in zip(planes, offsets):
                    seg = jax.lax.slice_in_dim(slab, WL + off, WL + off + R)
                    term = (plane[:, None] if is_mat else plane) * seg
                    out = term if out is None else out + term
            return out if is_mat else out[None]

        in_specs = (P(axis),) * (1 + len(offsets))
    elif layout == "ell":

        from ..ops.spmv import csr_spmm_ell, csr_spmv_ell

        def shard_fn(x_l, ell_idx_l, ell_val_l):
            with jax.named_scope("dist.halo"):
                slab = gather_x(x_l)
            idx, val = ell_idx_l.squeeze(0), ell_val_l.squeeze(0)
            with jax.named_scope("dist.local_spmv"):
                if is_mat:
                    return csr_spmm_ell(idx, val, slab)  # [R, nB]
                return csr_spmv_ell(idx, val, slab)[None]

        in_specs = (P(axis), P(axis, None, None), P(axis, None, None))
    else:

        def shard_fn(x_l, rows_l, cols_l, vals_l):
            with jax.named_scope("dist.halo"):
                slab = gather_x(x_l)
            rows, cols, vals = (
                rows_l.squeeze(0),
                cols_l.squeeze(0),
                vals_l.squeeze(0),
            )
            with jax.named_scope("dist.local_spmv"):
                prod = (
                    vals[:, None] * slab[cols] if is_mat else vals * slab[cols]
                )
                out = jax.ops.segment_sum(
                    prod, rows, num_segments=R, indices_are_sorted=True
                )
            return out if is_mat else out[None]

        in_specs = (P(axis), P(axis, None), P(axis, None), P(axis, None))

    smapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(axis, None),
        check_vma=False,
    )

    if is_mat:
        return jax.jit(smapped)

    @jax.jit
    def spmv(xp, *blocks):
        return smapped(xp, *blocks).reshape(S * R)

    return spmv


def _build_rspmm(A: DistCSR):
    """Compile the k-split dense x sparse SpMM: C = B @ A with B [p, m_pad]
    sharded on its column (contraction) axis; each shard scatters its local
    contribution into [p, n_pad] and one ``psum`` replicates C (the
    reference's ADD reduction into a broadcast store, csr.py:1209-1240)."""
    mesh, axis, S, R, C, HL = A.mesh, A.axis, A.S, A.R, A.C, A.HL
    mode, layout = A.mode, A.layout
    n_pad = S * C
    led = comm.SiteLedger("dist.rspmm")
    A._comm_ledger_rspmm = led

    def shard_fn(B_l, *blocks):
        s = jax.lax.axis_index(axis)
        if layout == "dia":
            # the planes as (row, column, value) triples: plane d, local row
            # l is at slab position HL + l + off_d (_build_spmv's slab,
            # contiguous in global coordinates), which lies in the left
            # neighbour's real tail, in this block, or in the right
            # neighbour's head
            n_own = jnp.asarray(np.diff(np.asarray(A.row_splits)), jnp.int32)
            n_s = n_own[s]
            n_left = n_own[jnp.maximum(s - 1, 0)]
            lrow = jnp.arange(R, dtype=jnp.int32)
            rows = jnp.tile(lrow, len(A.dia_offsets))
            pos = (lrow[None, :] + jnp.asarray(
                A.dia_offsets, jnp.int32)[:, None]).reshape(-1)
            cols = jnp.where(
                pos < 0, (s - 1) * C + n_left + pos,
                jnp.where(pos < n_s, s * C + pos, (s + 1) * C + pos - n_s))
            vals = jnp.concatenate(blocks)
        elif layout == "ell":
            ell_idx, ell_val = (b.squeeze(0) for b in blocks)
            k = ell_idx.shape[1]
            rows = jnp.repeat(jnp.arange(R, dtype=jnp.int32), k)
            cols = ell_idx.reshape(-1)
            vals = ell_val.reshape(-1)
        else:
            rows, cols, vals = (b.squeeze(0) for b in blocks)
        # window-local col ids -> padded global col ids
        if mode != "gather" and layout != "dia":
            cols = cols.astype(jnp.int32) + s * C - HL
        cols = jnp.clip(cols, 0, n_pad - 1)  # padding entries carry val 0
        contrib = B_l[:, rows] * vals  # [p, Kf]
        out = jax.ops.segment_sum(contrib.T, cols, num_segments=n_pad)
        # [p, n_pad] replicated (ADD-reduction into a broadcast C)
        return comm.psum(out.T, axis, ledger=led, tag="reduce")

    if layout == "dia":
        block_specs = (P(axis),) * len(A.dia_offsets)
    elif layout == "ell":
        block_specs = (P(axis, None, None), P(axis, None, None))
    else:
        block_specs = (P(axis, None), P(axis, None), P(axis, None))

    smapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(None, axis), *block_specs),
        out_specs=P(None, None),
        check_vma=False,
    )

    @jax.jit
    def rspmm(Bp):
        return smapped(Bp, *A._blocks())

    return rspmm


# ---------------------------------------------------------------------------
# Column-split SpMV — the contraction-dim ("TP-style") strategy.
# ---------------------------------------------------------------------------
@dataclass(eq=False)
class DistCSRCol:
    """A CSR matrix laid out over the mesh by COLUMN blocks.

    The reference's domain-partitioned SpMV (csr.py:869-927,
    ``spmv_domain_part``; SURVEY §2c-4): x is sharded on the contraction
    dimension, each shard owns the nonzeros whose column falls in its x
    block, computes a full-height partial y, and a ``psum_scatter`` over
    the mesh both reduces and re-shards y into row-block layout — the
    ring-reduction shape (this is the framework's reduce-scatter analog of
    sequence parallelism).
    """

    mesh: Mesh
    axis: str
    shape: tuple
    row_splits: np.ndarray  # [S+1] layout of the OUTPUT y
    col_splits: np.ndarray  # [S+1] layout of the INPUT x (ownership)
    R: int
    C: int
    dtype: np.dtype
    nz_rows: jax.Array | None = None  # [S, K] padded-space global row ids
    nz_cols: jax.Array | None = None  # [S, K] local col ids in [0, C)
    nz_vals: jax.Array | None = None  # [S, K]
    _spmv_fn: object = field(default=None, repr=False, compare=False)

    @property
    def S(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def m_pad(self) -> int:
        return self.S * self.R

    @property
    def n_pad(self) -> int:
        return self.S * self.C

    pad_vector = DistCSR.pad_vector
    pad_out_vector = DistCSR.pad_out_vector
    unpad_vector = DistCSR.unpad_vector

    _plan_fn = DistCSR._plan_fn
    _commit_comm = DistCSR._commit_comm

    def spmv_padded(self, xp: jax.Array) -> jax.Array:
        fn = self._plan_fn(
            "_spmv_fn", "dist.spmv_col", lambda: _build_spmv_col(self)
        )
        out = fn(xp, self.nz_rows, self.nz_cols, self.nz_vals)
        self._commit_comm("_comm_ledger")
        return out

    def dot(self, x) -> np.ndarray:
        xp = self.pad_vector(np.asarray(x))
        yp = self.spmv_padded(xp)
        return self.unpad_vector(yp)

    def matvec(self, x, out=None):
        return self.dot(x)


def _build_spmv_col(A: DistCSRCol):
    mesh, axis, S, R = A.mesh, A.axis, A.S, A.R
    m_pad = S * R
    led = comm.SiteLedger("dist.spmv_col")
    A._comm_ledger = led

    def shard_fn(x_l, rows_l, cols_l, vals_l):
        x = x_l.reshape(-1)
        rows, cols, vals = (
            rows_l.squeeze(0),
            cols_l.squeeze(0),
            vals_l.squeeze(0),
        )
        prod = vals * x[cols]
        y_full = jax.ops.segment_sum(
            prod, rows, num_segments=m_pad, indices_are_sorted=True
        )
        if S == 1:
            return y_full
        # reduce partial sums across the mesh AND re-shard to row blocks in
        # one collective (rides ICI as a ring reduce-scatter)
        return comm.psum_scatter(
            y_full, axis, axis_size=S, ledger=led, tag="y", tiled=True
        )

    smapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis), P(axis, None), P(axis, None), P(axis, None)),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(smapped)


def windows_to_halo(windows, C: int, S: int, halo_max_ratio: float = 1.0):
    """Per-shard [lo, hi) padded-column windows -> (HL, HR, mode).

    The single window-to-halo policy shared by ``shard_csr`` and the 2-D
    SpGEMM's DistCSR output. ``settings.precise_windows`` keeps the
    left/right overhangs separate (tighter slabs on asymmetric bands — the
    LEGATE_SPARSE_PRECISE_IMAGES analog); the default collapses them to one
    symmetric width. Overhang beyond ``halo_max_ratio * C`` total flips to
    the all_gather fallback ('gather').
    """
    from ..config import settings

    HL = HR = 0
    mode = "halo"
    for s in range(S):
        lo, hi = windows[s]
        if hi <= lo:
            continue
        HL = max(HL, int(s * C - lo))
        HR = max(HR, int(hi - (s + 1) * C))
    if not settings.precise_windows:
        HL = HR = max(HL, HR)
    if S == 1:
        HL = HR = 0
    if HL + HR > 2 * halo_max_ratio * C:
        mode = "gather"
        HL = HR = 0
    return HL, HR, mode


def shard_csr_cols(
    A,
    mesh: Mesh | None = None,
    axis: str = "shards",
    row_splits: np.ndarray | None = None,
) -> DistCSRCol:
    """Lay a ``csr_array`` out over the mesh by column blocks (domain split).

    ``row_splits`` fixes the output layout (defaults to equal row tiles) so
    the result vector can feed a row-split matrix without repacking.
    """
    if mesh is None:
        mesh = get_mesh()
    S = int(mesh.devices.size)
    indptr = np.asarray(A.indptr)
    indices = np.asarray(A.indices)
    data = np.asarray(A.data)
    m, n = A.shape
    nnz = data.shape[0]

    col_splits = equal_row_splits(n, S)
    if row_splits is None:
        row_splits = equal_row_splits(m, S)
    R = max(int(np.max(np.diff(row_splits))), 1)
    C = max(int(np.max(np.diff(col_splits))), 1)

    counts = np.diff(indptr)
    nnz_row = np.repeat(np.arange(m, dtype=np.int64), counts)
    row_shard = np.clip(
        np.searchsorted(row_splits, nnz_row, side="right") - 1, 0, S - 1
    )
    pad_rows = row_shard * R + (nnz_row - row_splits[row_shard])
    col_shard = np.clip(
        np.searchsorted(col_splits, indices, side="right") - 1, 0, S - 1
    )
    local_cols = indices.astype(np.int64) - col_splits[col_shard]

    # Bucket nonzeros by owning column shard, row-sorted within each bucket
    # (CSR order is already row-sorted; a stable bucket argsort keeps it).
    order = np.argsort(col_shard, kind="stable")
    shard_counts = np.bincount(col_shard, minlength=S)
    K = max(int(shard_counts.max()), 1) if nnz else 1
    starts = np.zeros(S, dtype=np.int64)
    starts[1:] = np.cumsum(shard_counts)[:-1]
    slot = np.arange(nnz, dtype=np.int64) - starts[col_shard[order]]

    idt = np.int32 if S * max(R, C) < 2**31 else np.int64
    # padding: row m_pad-1 (keeps sortedness), col 0, val 0
    nz_rows = np.full((S, K), S * R - 1, dtype=idt)
    nz_cols = np.zeros((S, K), dtype=idt)
    nz_vals = np.zeros((S, K), dtype=data.dtype)
    nz_rows[col_shard[order], slot] = pad_rows[order]
    nz_cols[col_shard[order], slot] = local_cols[order]
    nz_vals[col_shard[order], slot] = data[order]

    sharding2 = NamedSharding(mesh, P(axis, None))
    return DistCSRCol(
        mesh=mesh,
        axis=axis,
        shape=(int(m), int(n)),
        row_splits=np.asarray(row_splits),
        col_splits=col_splits,
        R=R,
        C=C,
        dtype=np.dtype(data.dtype),
        nz_rows=jax.device_put(nz_rows, sharding2),
        nz_cols=jax.device_put(nz_cols, sharding2),
        nz_vals=jax.device_put(nz_vals, sharding2),
    )


def _dia_fit(indices, nnz_row, m, n, row_splits, col_splits, S, C,
             halo_max_ratio):
    """Whether the 'dia' layout can hold the operator: ``(offsets, each
    entry's plane, HL, HR)``, or the reason why not as a string."""
    from ..config import settings

    if m != n or not np.array_equal(row_splits, col_splits):
        return "its rows and columns do not share one split"
    banded = banded_offsets(indices.astype(np.int64) - nnz_row, n)
    if banded is None:
        return ("it is not banded by settings.dia_max_diags and "
                "settings.dia_max_fill")
    offs, plane = banded
    if S == 1:
        return offs, plane, 0, 0
    HL, HR = max(-int(offs[0]), 0), max(int(offs[-1]), 0)
    if not settings.precise_windows:
        HL = HR = max(HL, HR)
    # the halo exchange reaches one neighbour: its real rows must hold the band
    if (max(HL, HR) > int(np.min(np.diff(row_splits)))
            or HL + HR > 2 * halo_max_ratio * C):
        return "its band reaches past a mesh neighbour's row block"
    return offs, plane, HL, HR


def shard_csr(
    A,
    mesh: Mesh | None = None,
    axis: str = "shards",
    balanced: bool = True,
    layout: str = "auto",
    halo_max_ratio: float = 1.0,
    row_splits: np.ndarray | None = None,
    col_splits: np.ndarray | None = None,
) -> DistCSR:
    """Lay a ``csr_array`` out over a mesh.

    ``balanced`` selects nnz-balanced row splits (the balance() analog);
    ``layout`` is 'dia' | 'ell' | 'csr' | 'auto'. 'auto' takes 'dia' for a
    square operator that is banded by the one-chip rule (few distinct
    diagonals: ``settings.dia_max_diags``/``dia_max_fill``) and whose band
    every mesh neighbour's row block covers, so that the halo exchange
    brings all of it; else ELL when max row degree is within
    ``settings.ell_max_ratio`` of the mean, mirroring the single-chip
    heuristic, else padded CSR. A shard's column window overhang beyond
    ``halo_max_ratio * C`` forces the all_gather fallback. Explicit
    ``row_splits``/``col_splits`` pin the layout so chains of rectangular
    operators (AMG's R/A/P) share vector spaces without repacking.
    """
    from .. import telemetry

    # the host build, whole: splits, layout choice, block construction and
    # the device_put calls (which return before the transfers end)
    with telemetry.span("dist.shard_csr") as sp:
        dist = _shard_csr(A, mesh, axis, balanced, layout, halo_max_ratio,
                          row_splits, col_splits)
        if telemetry.enabled():
            sp.annotate(layout=dist.layout, mode=dist.mode, S=dist.S,
                        R=dist.R, HL=dist.HL, HR=dist.HR, rows=dist.shape[0],
                        nnz=int(np.asarray(A.indptr[-1])))
    if telemetry.enabled():
        # one event per sharded operator: the structural per-SpMV comm
        # model (the introspection the reference gets from Legion's
        # partition analysis) — eager SpMVs then accumulate against it
        cs = comm_stats(dist)
        telemetry.record(
            "comm.spmv", model=True, shape=list(dist.shape), S=dist.S,
            mode=dist.mode, layout=dist.layout,
            halo_entries_per_spmv=cs["halo_entries_per_spmv"],
            bytes=int(cs["spmv_collective_bytes_per_shard"]) * dist.S,
        )
    return dist


def _shard_csr(A, mesh, axis, balanced, layout, halo_max_ratio, row_splits,
               col_splits) -> DistCSR:
    """``shard_csr`` without its span and its event."""
    from ..config import settings

    if mesh is None:
        mesh = get_mesh()
    S = int(mesh.devices.size)
    indptr = np.asarray(A.indptr)
    indices = np.asarray(A.indices)
    data = np.asarray(A.data)
    m, n = A.shape
    nnz = data.shape[0]

    if row_splits is None:
        if balanced and nnz > 0:
            row_splits = balanced_row_splits(indptr, S)
        else:
            row_splits = equal_row_splits(m, S)
    else:
        row_splits = np.asarray(row_splits, dtype=np.int64)
    # x follows an equal split of the column space; for square matrices this
    # is aligned with the row space so solver vectors live in one layout.
    if col_splits is None:
        col_splits = row_splits if m == n else equal_row_splits(n, S)
    else:
        col_splits = np.asarray(col_splits, dtype=np.int64)

    R = max(int(np.max(np.diff(row_splits))), 1)
    C = max(int(np.max(np.diff(col_splits))), 1)

    counts = np.diff(indptr)
    nnz_row = np.repeat(np.arange(m, dtype=np.int64), counts)  # global row/nnz

    if layout in ("auto", "dia"):
        fit = _dia_fit(indices, nnz_row, m, n, row_splits, col_splits, S, C,
                       halo_max_ratio)
        if isinstance(fit, str):
            if layout == "dia":
                raise ValueError(f"layout='dia' cannot hold this operator: {fit}")
        else:
            offs, flat, HL, HR = fit
            D = len(offs)
            # a row's position in the padded layout, then an entry's, in
            # place: a fresh array of nnz entries is a pass of page faults
            pad_row = np.arange(m, dtype=np.int64) + np.repeat(
                np.arange(S, dtype=np.int64) * R - row_splits[:-1],
                np.diff(row_splits))
            flat *= S * R
            flat += np.repeat(pad_row, counts)

            # one weighted bincount places every entry in its plane and sums
            # duplicates as the other layouts' products do
            def place(w):
                return np.bincount(flat, weights=w, minlength=D * S * R)

            planes = (place(data.real) + 1j * place(data.imag)
                      if np.iscomplexobj(data) else place(data))
            planes = planes.astype(data.dtype).reshape(D, S * R)
            return DistCSR(
                mesh=mesh, axis=axis, shape=(int(m), int(n)),
                row_splits=row_splits, col_splits=col_splits, R=R, C=C,
                HL=HL, HR=HR, mode="halo", layout="dia",
                dtype=np.dtype(data.dtype),
                dia_planes=tuple(jax.device_put(
                    list(planes), [NamedSharding(mesh, P(axis))] * D)),
                dia_offsets=tuple(int(o) for o in offs),
            )

    # Remap global column ids -> padded coordinate space.
    col_shard = np.clip(
        np.searchsorted(col_splits, indices, side="right") - 1, 0, S - 1
    )
    pad_cols = col_shard.astype(np.int64) * C + (
        indices.astype(np.int64) - col_splits[col_shard]
    )

    # Per-shard column windows -> halo widths (MinMaxImage analog,
    # partition.py:139-214).
    windows = column_windows(indptr, pad_cols, row_splits)
    HL, HR, mode = windows_to_halo(windows, C, S, halo_max_ratio)

    # Row degree stats for layout choice.
    kmax = int(counts.max()) if m else 0
    mean = max(nnz / max(m, 1), 1.0)
    if layout == "auto":
        layout = "ell" if kmax <= settings.ell_max_ratio * mean else "csr"

    shard_nnz = np.array(
        [
            int(indptr[row_splits[s + 1]]) - int(indptr[row_splits[s]])
            for s in range(S)
        ]
    )
    dt = data.dtype
    idt = np.int32 if S * max(R, C) + HL + HR < 2**31 else np.int64
    sharding2 = NamedSharding(mesh, P(axis, None))
    sharding3 = NamedSharding(mesh, P(axis, None, None))

    dist = DistCSR(
        mesh=mesh,
        axis=axis,
        shape=(int(m), int(n)),
        row_splits=row_splits,
        col_splits=col_splits,
        R=R,
        C=C,
        HL=HL,
        HR=HR,
        mode=mode,
        layout=layout,
        dtype=np.dtype(dt),
    )

    # Vectorized layout construction: one pass of repeat/searchsorted/scatter
    # over the nnz (no per-row Python loops — a 36M-row matrix lays out in
    # seconds of host time, like ops/conv.csr_to_ell).
    nnz_shard = np.clip(
        np.searchsorted(row_splits, nnz_row, side="right") - 1, 0, S - 1
    )
    local_row = nnz_row - row_splits[nnz_shard]
    if mode == "gather":
        local_col = pad_cols  # slab is the full [S*C] gathered x
    else:  # slab is [C + 2H] starting at shard*C - H
        local_col = pad_cols - (nnz_shard * C - HL)

    if layout == "ell":
        k = max(kmax, 1)
        pos_in_row = np.arange(nnz, dtype=np.int64) - np.repeat(
            indptr[:-1].astype(np.int64), counts
        )
        ell_idx = np.zeros((S, R, k), dtype=idt)
        ell_val = np.zeros((S, R, k), dtype=dt)
        ell_idx[nnz_shard, local_row, pos_in_row] = local_col
        ell_val[nnz_shard, local_row, pos_in_row] = data
        dist.ell_idx = jax.device_put(ell_idx, sharding3)
        dist.ell_val = jax.device_put(ell_val, sharding3)
    else:
        K = max(int(shard_nnz.max()), 1)
        shard_nnz_start = indptr[row_splits[:-1]].astype(np.int64)
        slot = np.arange(nnz, dtype=np.int64) - shard_nnz_start[nnz_shard]
        # padding entries: row R-1 (>= any real local row id, keeps sorted
        # order for segment_sum), col 0, val 0
        nz_rows = np.full((S, K), R - 1, dtype=idt)
        nz_cols = np.zeros((S, K), dtype=idt)
        nz_vals = np.zeros((S, K), dtype=dt)
        nz_rows[nnz_shard, slot] = local_row
        nz_cols[nnz_shard, slot] = local_col
        nz_vals[nnz_shard, slot] = data
        dist.nz_rows = jax.device_put(nz_rows, sharding2)
        dist.nz_cols = jax.device_put(nz_cols, sharding2)
        dist.nz_vals = jax.device_put(nz_vals, sharding2)
    return dist


# ---------------------------------------------------------------------------
# Distributed CG — the full "training step" over the mesh (solver north star).
# ---------------------------------------------------------------------------
_CG_TRACES = _metrics.counter(
    "dist.cg.traces",
    help="traces of the compiled mesh-CG program (dist_cg/make_dist_cg): "
    "one per program built, none for a call that reuses one",
)
#: compiled CG programs one DistCSR keeps (a fresh ``M`` each call would
#: otherwise grow the table without bound)
_CG_PROGRAMS_KEPT = 8


def _cg_program(A: DistCSR, maxiter: int, conv_test_iters: int, M):
    """The compiled mesh-CG loop ``solve(bp, xp, tol, atol, *blocks)`` of this
    layout, kept on the ``DistCSR`` by what changes the program: the
    iteration limit, the test cadence and the preconditioner (by identity).
    ``tol``/``atol`` are traced scalars, dtypes and shapes are jit's own key.
    The trace names it ``jit_dist_cg_<layout>``."""
    key = (int(maxiter), int(conv_test_iters), id(M))
    hit = A._cg_fns.get(key)
    if hit is not None:
        return hit[0]
    # M may be a padded-vector callable (the historic contract) or a
    # LinearOperator-shaped object (ISSUE 14: e.g. a multigrid V-cycle
    # promoted via parallel.multigrid.vcycle_operator) — resolve to the
    # traceable apply either way
    precond = M.matvec if hasattr(M, "matvec") else M
    # the loop captures the layout's compiled product, not the layout: kept
    # on A, a closure over A would be a cycle that holds the device planes
    # until a cyclic collection (which device-memory pressure never starts)
    product = A._plan_fn("_spmv_fn", "dist.spmv", lambda: _build_spmv(A))
    layout = A.layout

    # The matrix blocks are ARGUMENTS of the compiled loop, never closure
    # constants: captured, they are baked into the executable (2.68 GB at
    # 8192^2 over four chips — too large to serialize, and on the TPU its
    # outputs then came back without a sharding; chip_smoke.py, PR 22).
    def solve(bp, xp, tol, atol, *blocks):
        _CG_TRACES.inc()

        def spmv(v):
            return product(v, *blocks)

        def rdot(u, v):
            return jnp.real(jnp.vdot(u, v))

        r = bp - spmv(xp)
        bnorm2 = rdot(bp, bp)
        tol2 = jnp.maximum(
            jnp.asarray(tol, dtype=bnorm2.dtype) ** 2 * bnorm2,
            jnp.asarray(atol, dtype=bnorm2.dtype) ** 2,
        )

        # rr = ||r||^2 rides in the state: the test reads it, and without a
        # preconditioner it is rho too, so an iteration reduces twice
        def body(state):
            x, r, p, rho, rr, iters = state
            if precond is None:
                z, rho_new = r, rr.astype(r.dtype)
            else:
                z = precond(r)
                rho_new = jnp.vdot(r, z)
            beta = rho_new / jnp.where(rho == 0, 1, rho)
            p = jnp.where(iters == 0, z, z + beta * p)
            q = spmv(p)
            pq = jnp.vdot(p, q)
            alpha = rho_new / jnp.where(pq == 0, 1, pq)
            r = r - alpha * q
            return x + alpha * p, r, p, rho_new, rdot(r, r), iters + 1

        def cond(state):
            *_, rr, iters = state
            tested = (iters % conv_test_iters == 0) | (iters == maxiter - 1)
            converged = tested & (iters > 0) & (rr < tol2)
            return (iters < maxiter) & ~converged

        state = (xp, r, jnp.zeros_like(bp), jnp.zeros((), bp.dtype),
                 rdot(r, r), jnp.zeros((), jnp.int32))
        x, _, _, _, rr, iters = jax.lax.while_loop(cond, body, state)
        return x, iters, rr < tol2

    solve.__name__ = solve.__qualname__ = f"dist_cg_{layout}"
    fn = jax.jit(solve)
    while len(A._cg_fns) >= _CG_PROGRAMS_KEPT:
        A._cg_fns.pop(next(iter(A._cg_fns)))
    A._cg_fns[key] = (fn, M)  # M is held so that its id stays its own
    return fn


def make_dist_cg(
    A: DistCSR,
    tol: float = 1e-8,
    atol: float = 0.0,
    maxiter: int | None = None,
    conv_test_iters: int = 25,
    M=None,
):
    """The compiled mesh-CG program with its tolerances bound: returns
    ``run(bp, xp) -> (xp, iters, converged)``, asynchronous like any jitted
    call. The explicit form of :func:`dist_cg`, for callers that fence and
    time for themselves; both share the programs kept on ``A``."""
    if maxiter is None:
        maxiter = A.shape[0] * 10
    solve = _cg_program(A, maxiter, conv_test_iters, M)

    def run(bp, xp):
        return solve(bp, xp, tol, atol, *A._blocks())

    return run


def dist_cg(
    A: DistCSR,
    b,
    x0=None,
    tol: float = 1e-8,
    atol: float = 0.0,
    maxiter: int | None = None,
    conv_test_iters: int = 25,
    M=None,
):
    """(Preconditioned) conjugate gradient over the mesh.

    Mirrors ``linalg.cg`` (reference linalg.py:499) but every vector is a
    padded mesh-sharded array and every reduction (dot products, norms) is a
    GSPMD ``psum`` inserted by XLA. One compiled ``lax.while_loop``, kept on
    ``A`` (:func:`_cg_program`): a second call with the same ``maxiter``,
    ``conv_test_iters`` and ``M`` neither traces nor compiles, whatever its
    tolerances. The host syncs once at the end — strictly less blocking
    than the reference's every-25-iterations future read.

    ``M``: optional traceable preconditioner on padded vectors
    (zp = M(rp)) — e.g. a distributed AMG V-cycle. Convergence uses scipy
    semantics: ||r|| < max(tol * ||b||, atol). Returns (xp, iters, converged).
    """
    from .. import telemetry
    from ..linalg import _call_solved, _solve_event, _solver_call

    with _solver_call():
        bp = b if isinstance(b, jax.Array) and b.shape == (A.m_pad,) else A.pad_out_vector(np.asarray(b))
        xp = (
            jnp.zeros_like(bp)
            if x0 is None
            else (x0 if isinstance(x0, jax.Array) and x0.shape == (A.m_pad,) else A.pad_out_vector(np.asarray(x0)))
        )
        run = make_dist_cg(
            A, tol=tol, atol=atol, maxiter=maxiter,
            conv_test_iters=conv_test_iters, M=M,
        )
        # One `dist.cg.solve` span a call: `dist.cg.dispatch` is the
        # program's call until it returns (asynchronous: the host's part,
        # and on a first call the trace and the compile), `dist.cg.wait`
        # the fence, the fetch of the iteration count. Both are trace
        # annotations and aggregates only; their lengths go onto the
        # solve's event, and the span to the call's account
        # (`linalg._CallAccount`: what lies before it is `prep_ms`).
        with telemetry.span("dist.cg.solve", layout=A.layout, S=A.S) as solve:
            with telemetry.span("dist.cg.dispatch", emit=False) as sp:
                xp, iters, converged = run(bp, xp)
            dispatch_s = sp.dur_s or 0.0
            with telemetry.span("dist.cg.wait", emit=False) as sp:
                iters, converged = int(iters), bool(converged)  # host fetch = fence
            wait_s = sp.dur_s or 0.0
            solve.annotate(dispatch_s=round(dispatch_s, 9),
                           wait_s=round(wait_s, 9), iters=iters)
        _call_solved(solve, dispatch_s, wait_s)
        # the compiled loop runs one SpMV per iteration plus the initial
        # residual SpMV; commit that many executions of the traced program's
        # measured collective volume into the always-on metrics
        executions = iters + 1
        led = getattr(A, "_comm_ledger", None)
        if led is not None and led.entries:
            led.commit(executions, A.S)

        if telemetry.enabled():
            # whole-solve collective volume from the structural model x the
            # measured iteration count — the Legion-profiler-style comm
            # attribution for the compiled while_loop (which is opaque to
            # per-call counters by design)
            cs = comm_stats(A, conv_test_iters)
            model_bytes = (
                int(cs["cg_iter_collective_bytes_per_shard"]) * iters * A.S
            )
            telemetry.record(
                "comm.cg", S=A.S, iters=iters, mode=A.mode,
                bytes=model_bytes,
                bytes_per_iter_per_shard=int(
                    cs["cg_iter_collective_bytes_per_shard"]
                ),
            )
            if led is not None and led.entries:
                # trace-derived measured bytes reconciled against the model:
                # divergence is the drift signal (expected residue: the model
                # counts the GSPMD scalar psums the wrappers cannot see, the
                # measurement counts the initial-residual SpMV the model
                # omits — both shrink with iteration count)
                comm.record_measured(
                    "dist.cg", led, executions=executions, shards=A.S,
                    model_bytes=model_bytes, solve_s=solve.dur_s,
                    mode=A.mode, iters=iters,
                )
        # the call's last act, after the `comm.*` events: the health
        # monitor's report (the compiled mesh loop has no per-iteration
        # visibility, but a report still closes, outcome and anomaly sweep
        # on the final residual, so last_solve_report() covers dist too),
        # then the `solver.solve` event with the call's account
        _solve_event("dist_cg", A.shape[0], iters, "device",
                     converged=converged)
    return xp, iters, converged


def comm_stats(A: DistCSR, conv_test_iters: int = 25) -> dict:
    """Per-CG-iteration collective cost model (VERDICT r2 #4).

    Derived from the compiled program's structure, not measured: one SpMV
    per iteration moves the halo (two ``ppermute`` payloads of HL/HR x
    entries per shard, ``_build_spmv.gather_x``) or, in gather mode, an
    ``all_gather`` of every other shard's x block; the CG recurrence
    ``psum``s 2 scalars per iteration (rho, p.q) plus one norm every
    ``conv_test_iters``. Weak-scaling regressions (halo width growing with
    n/S instead of the matrix band) show up here without hardware.
    """
    it = np.dtype(A.dtype).itemsize
    if A.mode == "halo":
        halo_entries = A.HL + A.HR
        spmv_bytes = halo_entries * it
    else:
        halo_entries = 0
        spmv_bytes = (A.S - 1) * A.C * it  # all_gather receives per shard
    psum_scalars = 2 + 1.0 / max(conv_test_iters, 1)
    return {
        "mode": A.mode,
        "S": A.S,
        "halo_entries_per_spmv": halo_entries,
        "spmv_collective_bytes_per_shard": spmv_bytes,
        "psum_scalars_per_iter": psum_scalars,
        "cg_iter_collective_bytes_per_shard": spmv_bytes
        + int(psum_scalars * it),
    }
