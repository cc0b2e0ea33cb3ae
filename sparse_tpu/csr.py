"""CSR sparse array — the workhorse format.

Reference analog: ``sparse/csr.py`` (1731 LoC; class at csr.py:99, op free
functions spmv csr.py:863 / add csr.py:972 / mult csr.py:1033 / spmm csr.py:1151 /
rspmm csr.py:1209 / sddmm csr.py:1244 / spgemm csr.py:1317,1495 / tropical
csr.py:366). The Legion pos/crd/vals stores become plain ``indptr/indices/data``
jax.Arrays; partition constraints become either XLA GSPMD shardings or explicit
``shard_map`` row-blocks (``sparse_tpu.parallel``).

TPU-first detail: construction optionally caches a padded-row (ELL) layout when
the row-length profile is tight (all reference benchmarks are banded), switching
SpMV/SpMM from scatter-shaped to gather-shaped kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .base import SparseArray
from .coverage import track_provenance
from .config import settings
from .ops import conv, elementwise, sddmm as sddmm_ops, spgemm as spgemm_ops, spmv as spmv_ops
from .ops.coords import expand_rows
from .utils import (
    asjnp, commit_to_exec_device, host_int, host_scope, in_trace,
)


# How a matrix multiplies. For each ``spmv_mode``: the layouts a product may
# use, for a vector and for a 2-D operand, in the order they are tried. The
# first one the matrix offers is taken; a matrix that offers none (no
# entries, or a first use inside a trace, which can build nothing) takes the
# segment form. ``ell?`` and ``sell?`` are the two sides of the one ELL-ratio
# gate (``csr_array._tight``): padded rows for a tight row profile, SELL
# slabs for a skewed one; bare ``ell``/``sell`` are built whatever the
# profile. ``dia`` needs a banded matrix (``dia.few_diagonals``). How it
# multiplies follows from what the matrix and the platform show
# (``csr_array._dia_operands``, the rule beside ``_DIA_VMEM_BYTES``): on a TPU
# a square float32 matrix whose window fits VMEM multiplies through the
# windowed Pallas kernel ``kernels.dia_spmv.dia_spmv_rows`` on row-indexed
# planes packed once with the layout (every plane, x and y cross HBM once);
# everywhere else through ``ops.dia_spmv.dia_spmv_xla`` on the scipy-layout
# planes. The layout's name and ``meta`` are the same in both. ``dia+`` is
# the user's own route to the packed kernel (``PreparedDia``: an autotuned
# tile, the vault's copy, the failover registry; a band past
# ``settings.pallas_max_band`` declines), for an eager product only: a
# compiled whole solve takes ``dia`` by its rule in its place. ``well`` is
# the windowed step-major layout, whose product is a
# Pallas kernel that gathers from x held in VMEM (kernels/well_spmv.py): the
# matrix reordered to a band once, its rows tiled, each tile's columns local
# to a short window of x and its entries stored a vreg to a step and depth.
# Nobody sets it: ``csr_array._maybe_well`` offers it from what it measures
# (a TPU, float32, square with a symmetric pattern, a tight row profile,
# enough rows, x fitting VMEM, and windows that stayed narrow after the
# reordering) and everywhere else the walk goes on to the layouts below it
# as if it were not in the table. So ``pallas`` changes a banded matrix's
# eager vector product and nothing else.
# docs/performance.md shows the table by row profile;
# tests/test_matvec_choice.py pins it.
_LAYOUTS = {
    "auto": (("dia", "well", "sell?", "ell?"), ("ell?", "sell?")),
    "pallas": (("dia+", "dia", "well", "sell", "ell?"), ("ell?", "sell?")),
    "sell": (("sell",), ("sell",)),
    "ell": (("ell",), ("ell",)),
    "segment": ((), ()),
}


def _layouts(ndim: int, mode: str | None = None) -> tuple:
    """The table's row for a ``ndim``-D operand under ``mode`` (default: the
    ambient ``settings.spmv_mode``, read here and nowhere else in this
    module)."""
    mode = settings.spmv_mode if mode is None else mode
    if mode not in _LAYOUTS:
        raise ValueError(
            f"unknown spmv_mode {mode!r}: expected one of {sorted(_LAYOUTS)}"
        )
    return _LAYOUTS[mode][ndim - 1]


# The rule of ``well`` (``csr_array._maybe_well``), from the chip's readings
# (PERF.md section 6, PR 32 and PR 48): XLA's gather costs 8.6 ns a slot of
# the padded rows (rows x the longest row); the kernel 13 ns a unit of a
# tile's window (a unit is an (8, 128) vreg of values and one of lanes: its
# 8 KB from HBM at 740 GB/s, with its lane gather, one of sixteen in flight,
# hidden beside that).
# x whole in VMEM: 4 bytes a padded row
_WELL_X_BYTES = 16 << 20
# the blocks of lanes and of values in flight beside x, two of each: 16 KB a
# unit of the fullest grid step
_WELL_BLOCK_BYTES = 32 << 20
# below this the host's build and the kernel's compile (a second or two)
# outlast what a few solves save: one ELL product here is 8.6 ns x 7 x rows
_WELL_MIN_ROWS = 1 << 17
# the units a slot of the padded rows past which the kernel no longer beats
# the ELL gather by 2x: 8.6 ns a slot over twice 13 ns a unit
_WELL_MAX_UNITS_A_SLOT = 0.33
# every unit's chunk lives in SMEM for the product (1 MiB on the v5e, of
# which the lists may take half), and a stored unit is 8 KB of HBM: the cap
# holds the units stored (every grid step the fullest one's count), so the
# layout is 1.07 GB at most
_WELL_MAX_UNITS = 1 << 17


# The rule of ``dia``'s kernel (``csr_array._dia_operands``): what
# ``dia_spmv_rows`` holds in VMEM, 2 D TM plane elements, two x windows of
# TM + 2 B and two blocks of y, 4 bytes each (at D = 7, TM = 64,512,
# B = 22,528: 5.0 MB), has to stay under this, half of the 16 MiB Mosaic
# plans within: the other half is the kernel body's own TM-long temporaries.
# The row tile follows from it (``dia_rows_plan``), nothing is probed.
_DIA_VMEM_BYTES = 8 << 20


def _dia_platform() -> bool:
    """The kernel's platform: a TPU, and x64 off (with it on, Mosaic's
    lowering of the kernel's ``program_id % 2`` does not end)."""
    return jax.default_backend() == "tpu" and not jax.config.jax_enable_x64


def _well_platform() -> bool:
    """The kernel's platform: Mosaic's lane gather exists on a TPU only."""
    return jax.default_backend() == "tpu"


def form_space(kind: str, meta, arrays):
    """``(enter, matvec, leave)`` of one layout: the product in the space
    the layout multiplies in, and the maps of a vector into it and of a
    result out of it. Every layout but ``well`` multiplies in the caller's
    own space (identities); ``well`` in its permuted, padded one, so that a
    solver pays the two permutations once a solve and not once a product.
    Pure functions of the layout's arrays, jit-safe."""
    if kind != "well":
        matvec = functools.partial(form_matvec, kind, meta, arrays)
        return (lambda v: v), matvec, (lambda v: v)
    from .kernels.well_spmv import LEAD, well_spmv

    n, n_pad = meta
    interpret = jax.default_backend() != "tpu"  # a test's CPU drive

    def matvec(v):
        return well_spmv(
            arrays["uptr"], arrays["ustart"], arrays["lane"], arrays["val"],
            v.reshape(n_pad // 128, 128), interpret=interpret,
        ).reshape(n_pad)

    return (
        lambda v: jnp.pad(v.astype(jnp.float32)[arrays["perm"]],
                          (LEAD, n_pad - n - LEAD)),
        matvec,
        lambda v: v[arrays["inv_perm"]],
    )


def form_matvec(kind: str, meta, arrays, x):
    """``A @ x`` through one layout, as ``csr_array._spmv_form`` names it: a
    pure function of the layout's arrays, jit-safe, the matrix an argument."""
    if kind == "well":  # both permutations around the kernel
        enter, matvec, leave = form_space(kind, meta, arrays)
        return leave(matvec(enter(x)))
    if kind == "dia":
        if form_kernels(kind, arrays):  # the packed planes: the kernel
            return arrays.matvec(x, interpret=jax.default_backend() != "tpu")
        from .ops.dia_spmv import dia_spmv_xla

        return dia_spmv_xla(arrays, meta[0], x, meta[1])
    if kind == "sell":
        return spmv_ops.csr_spmv_sell(*arrays, x, meta)
    if kind == "ell":
        # XLA's HBM-gather formulation, one gather a plane. Mosaic lowers
        # the single-tile take_along_axis only; the windowed gather made of
        # it is the layout ``well`` (kernels/well_spmv.py), for a matrix
        # whose reordering leaves every row tile a short window of x
        return spmv_ops.csr_spmv_ell(*arrays, x)
    return spmv_ops.csr_spmv_segment(*arrays, x, meta)


def form_kernels(kind: str, arrays) -> int:
    """Pallas kernels in one product through a layout, by what its arrays
    show: the windowed gather of ``well``, the windowed planes of a ``dia``
    that holds its packed rows (``DiaRows``); 0 for every XLA form."""
    from .kernels.dia_spmv import DiaRows

    return int(kind == "well" or (kind == "dia" and type(arrays) is DiaRows))


@jax.tree_util.register_pytree_node_class
class csr_array(SparseArray):
    format = "csr"

    def __init__(self, arg, shape=None, dtype=None, copy=False):
        from .coo import coo_array

        if isinstance(arg, csr_array):
            data, indices, indptr, shape = arg.data, arg.indices, arg.indptr, arg.shape
        elif isinstance(arg, SparseArray):
            c = arg.tocsr()
            data, indices, indptr, shape = c.data, c.indices, c.indptr, c.shape
        elif isinstance(arg, tuple) and len(arg) == 3:
            data, indices, indptr = (asjnp(a) for a in arg)
            if shape is None:
                ncols = host_int(indices.max()) + 1 if indices.shape[0] else 0
                shape = (indptr.shape[0] - 1, ncols)
        elif isinstance(arg, tuple) and len(arg) == 2 and isinstance(arg[1], tuple):
            c = coo_array(arg, shape=shape).tocsr()
            data, indices, indptr, shape = c.data, c.indices, c.indptr, c.shape
        elif isinstance(arg, tuple) and len(arg) == 2:
            shape = (int(arg[0]), int(arg[1]))
            indptr = jnp.zeros((shape[0] + 1,), dtype=np.int32)
            indices = jnp.zeros((0,), dtype=np.int32)
            data = jnp.zeros((0,), dtype=dtype or np.float32)
        elif hasattr(arg, "tocsr") and hasattr(arg, "indptr"):  # scipy csr
            s = arg.tocsr()
            data, indices, indptr = asjnp(s.data), asjnp(s.indices), asjnp(s.indptr)
            shape = s.shape
        elif hasattr(arg, "tocsr"):  # other scipy formats
            s = arg.tocsr()
            data, indices, indptr = asjnp(s.data), asjnp(s.indices), asjnp(s.indptr)
            shape = s.shape
        else:  # dense
            d = asjnp(arg)
            if d.ndim != 2:
                raise ValueError("CSR arrays must be 2-D")
            indptr, indices, data, _ = conv.dense_to_csr(d)
            shape = d.shape
        if dtype is not None:
            data = data.astype(dtype)
        self.data = asjnp(data)
        self.indices = asjnp(indices)
        self.indptr = asjnp(indptr)
        self._shape = (int(shape[0]), int(shape[1]))
        self._dtype = np.dtype(self.data.dtype)
        self._ell = None  # lazy (ell_indices, ell_data) cache
        self._dia = False  # False = unchecked, None = not banded, else planes
        self._dia_rows = None  # (planes packed from, DiaRows | None), lazy
        self._well = False  # False = unchecked, None = not offered, else WellLayout
        self._balanced_splits = None

    @classmethod
    def from_parts(cls, data, indices, indptr, shape):
        obj = object.__new__(cls)
        obj.data = asjnp(data)
        obj.indices = asjnp(indices)
        obj.indptr = asjnp(indptr)
        obj._shape = (int(shape[0]), int(shape[1]))
        obj._dtype = np.dtype(obj.data.dtype)
        obj._ell = None
        obj._dia = False
        obj._dia_rows = None
        obj._well = False
        obj._balanced_splits = None
        return obj

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.indices, self.indptr), self._shape

    @classmethod
    def tree_unflatten(cls, shape, children):
        data, indices, indptr = children
        return cls.from_parts(data, indices, indptr, shape)

    # ----------------------------------------------------------------------
    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    def _data_array(self):
        return self.data

    def _with_data(self, data):
        out = csr_array.from_parts(data, self.indices, self.indptr, self.shape)
        out._balanced_splits = self._balanced_splits
        return out

    # -- ELL fast path -----------------------------------------------------
    def _ell_width(self) -> int:
        """Max row length; host-synced once and cached."""
        if not hasattr(self, "_ell_width_cache") or self._ell_width_cache is None:
            with host_scope():  # one-time eager analysis: on the host
                counts = self.indptr[1:] - self.indptr[:-1]
                self._ell_width_cache = (
                    host_int(counts.max()) if self.shape[0] else 0
                )
        return self._ell_width_cache

    def _tight(self) -> bool:
        """The ELL-ratio gate: the longest row is within
        ``settings.ell_max_ratio`` of the mean row, so padding every row to
        it wastes little. Past it the SELL slabs take the matrix."""
        mean = max(self.nnz / self.shape[0], 1.0)
        return self._ell_width() <= settings.ell_max_ratio * mean

    def _maybe_ell(self, gated: bool = False):
        """Build/cache the padded-row layout; with ``gated``, only for a
        tight row profile (``_tight``)."""
        m = self.shape[0]
        if m == 0 or self.nnz == 0:
            return None
        if self._ell is None and in_trace():
            # in-trace first use: no host sync, and no cache write — a
            # width cache may already exist (eager call under a different
            # spmv_mode), but building ELL here would store TRACER arrays
            # on self._ell and poison every later eager matvec
            return None
        if gated and not self._tight():
            return None
        if self._ell is None:
            # one-time layout build: on the host
            with telemetry.span("layout.ell_build"), host_scope():
                self._ell = conv.csr_to_ell(
                    self.indptr, self.indices, self.data, m,
                    max(self._ell_width(), 1),
                )
        return self._ell

    # -- SELL-C-sigma prepared path ----------------------------------------
    def _maybe_sell(self, gated: bool = False):
        """Packed SELL-C-sigma operator via the library-wide plan cache.

        The prepared general-SpMV path for skewed row profiles
        (kernels/sell_spmv.py); with ``gated``, only where the padded-row
        (ELL) gate declines (``_tight`` false: max degree beyond
        ``ell_max_ratio`` x mean). One host-side pack on first eager use,
        cached in ``sparse_tpu.plan_cache`` keyed on this object; in-trace
        first use degrades to the jit-safe segment path without caching
        (same discipline as ``_maybe_ell``/``_maybe_dia``).
        """
        from . import plan_cache

        if self.shape[0] == 0 or self.nnz == 0:
            return None
        if in_trace():
            # trace-safe lookup: an eagerly-warmed plan is reusable (its
            # planes become compile-time constants, like the ELL cache);
            # packing here would need host syncs, so a cold cache skips
            return plan_cache.lookup(self, "sell")
        if gated and self._tight():
            return None  # tight profile: the ELL path takes it

        def build():
            from .kernels.sell_spmv import PreparedCSR

            # one-time pack: on the host
            with telemetry.span("layout.sell_build"), host_scope():
                prep = PreparedCSR(
                    self.indptr, self.indices, self.data, self.shape
                )
            # layouts are BUILT under host_scope; commit to the execution
            # device once so accelerator hot paths don't re-ship the
            # planes per matvec (same discipline as the DIA/ELL caches)
            prep.slabs = tuple(
                commit_to_exec_device((it, vt)) for it, vt in prep.slabs
            )
            (prep.pos,) = commit_to_exec_device((prep.pos,))
            return prep

        def vault_key():
            # content fingerprint: exact buffers + the SELL geometry
            # settings the pack depends on (sparse_tpu.vault._codecs)
            from .vault import _codecs

            return _codecs.prepared_csr_key(
                self.indptr, self.indices, self.data, self.shape
            )

        return plan_cache.get(
            self, "sell", build,
            vault_kind="prepared_csr", vault_key=vault_key,
            # canonicalized: the packed planes carry jax's dtype (f64
            # narrows to f32 without x64), and that is what a loaded
            # artifact must agree with
            expect={"dtype": str(jax.dtypes.canonicalize_dtype(self.dtype))},
        )

    # -- windowed step-major units (kernels/well_spmv.py) --------------------
    def _maybe_well(self, xdtype=None):
        """The windowed step-major layout, where this matrix offers it; the
        rule is the comment above ``_WELL_X_BYTES``. Built once an operator
        on the host (the answer, None too, is cached); a product of another
        result type than float32 passes it over."""
        if xdtype is not None and jnp.result_type(self.dtype, xdtype) != jnp.float32:
            return None  # the kernel multiplies in float32 alone
        if self._well is not False or in_trace():
            return self._well or None
        m, n = self.shape
        self._well = None
        if (_well_platform() and m == n and self.dtype == np.float32
                and _WELL_MIN_ROWS <= n and 4 * n <= _WELL_X_BYTES
                and self.nnz and self._tight()):
            self._well = self._well_build(n)
        return self._well

    def _well_build(self, n):
        from .csgraph import band_order
        from .kernels import well_spmv as ws

        indptr, indices, data = (
            np.asarray(a) for a in (self.indptr, self.indices, self.data))
        n_pad = ws.padded_size(n)
        with telemetry.span("layout.reorder") as sp:
            order = None
            if ws.symmetric_pattern(indptr, indices, n):
                # a graph of very many components or of path-like depth
                # (one numpy round trip a level) is turned away
                order = band_order(indptr, indices, n, budget=n // 64 + 4096)
            if order is None:
                sp.annotate(offered=False)
                return None
            new_ptr, rows, cols, data, rank = ws.permuted_csr(
                indptr, indices, data, order)
            uptr, ustart, unit, stats = ws.windows(new_ptr, rows, cols, n, n_pad)
            block = stats["units_stored"] // (n_pad // ws.GRID_ROWS)
            offered = (
                stats["units"] <= _WELL_MAX_UNITS_A_SLOT * n * self._ell_width()
                and stats["units_stored"] <= _WELL_MAX_UNITS
                and 2 * ws.UNIT_BYTES * block <= _WELL_BLOCK_BYTES)
            sp.annotate(offered=offered, **stats)
        if not offered:
            return None
        with telemetry.span("layout.ell_build"):
            lane, val = ws.step_units(uptr, rows, cols, data, unit)
        arrays = {
            "uptr": uptr.astype(np.int32), "ustart": ustart.astype(np.int32),
            "lane": lane, "val": val, "perm": order.astype(np.int32),
            "inv_perm": (rank + ws.LEAD).astype(np.int32),
        }
        arrays = dict(zip(arrays, commit_to_exec_device(
            tuple(jnp.asarray(a) for a in arrays.values()))))
        return ws.WellLayout(arrays, (n, n_pad), stats)

    def _offer(self, name: str, xdtype=None):
        """Build (first use) or fetch the layout one entry of ``_LAYOUTS``
        names; None where this matrix does not offer it (``xdtype``: the
        operand's type, for a layout that multiplies in one type only)."""
        kind, gated = name.rstrip("?+"), name.endswith("?")
        if kind == "well":
            return self._maybe_well(xdtype)
        if kind == "sell":
            return self._maybe_sell(gated)
        if kind == "ell":
            lay = self._maybe_ell(gated)
        else:
            lay = self._maybe_dia()
        if lay is None or in_trace():
            return lay
        # layouts are BUILT under host_scope; on accelerator hot paths
        # commit them to the execution device once (they are jit
        # arguments — CPU-resident planes would re-transfer per matvec)
        # and re-cache
        if kind == "ell":
            lay = self._ell = commit_to_exec_device(lay)
        else:
            lay = self._dia = (*commit_to_exec_device(lay[:1]), lay[1])
        return lay

    def _dia_operands(self, lay, xdtype=None):
        """What the layout ``dia`` multiplies ``xdtype`` vectors through: on
        a TPU, for a square float32 matrix and a float32 operand whose window
        fits VMEM (``_DIA_VMEM_BYTES``), the row-indexed planes packed once
        on the device (``DiaRows``, kept beside the planes they were made
        from); everywhere else the scipy-layout planes ``lay[0]``. Nothing
        sets it. A first use inside a trace packs nothing."""
        planes, offsets = lay
        m, n = self.shape
        if (m != n or planes.dtype != np.float32
                or jnp.dtype(self.dtype if xdtype is None else xdtype) != np.float32
                or not _dia_platform()):
            return planes
        kept = self._dia_rows
        if kept is None or kept[0] is not planes:
            if in_trace():
                return planes
            from .kernels.dia_spmv import DiaRows, dia_pack, dia_rows_plan

            plan = dia_rows_plan(offsets, n, _DIA_VMEM_BYTES)
            with telemetry.span("layout.dia_pack", fits=plan is not None):
                rows = plan and DiaRows(dia_pack(planes, plan), plan)
            kept = self._dia_rows = (planes, rows)
        return kept[1] or planes

    def prepare(self, mode: str | None = None):
        """One-time eager layout/pack warm for the current (or given)
        ``spmv_mode``: every layout a vector or a 2-D product may take under
        it. Returns ``self`` for chaining.

        The prepare half of the prepare/execute split: solvers whose first
        matvec happens inside a compiled loop (multigrid operators, eigsh
        Lanczos bodies) would otherwise pin the slowest kernel path for
        the whole solve — ``make_linear_operator`` calls this eagerly so
        every ``linalg`` solver starts from a packed operator.
        """
        if in_trace():
            return self  # layout detection needs host syncs; no-op in-trace
        for ndim in (1, 2):
            for name in _layouts(ndim, mode):
                if name.startswith("sell") and not settings.plan_cache:
                    # with the plan cache DISABLED the pack has nowhere to
                    # live — plan_cache.get builds and discards — so an
                    # eager warm would charge every one-shot solve the full
                    # SELL pack cost for nothing (tests/test_plan_cache.py
                    # pins this). Execute-time _maybe_sell still packs when
                    # a matvec actually needs it.
                    continue
                if self._offer(name) is not None:
                    break  # the one a product takes: the walk stops there
        return self

    # -- products ----------------------------------------------------------
    @track_provenance
    def dot(self, other, out=None, spmv_domain_part=False):
        """A @ other. Vector -> SpMV; dense 2-D -> SpMM; sparse -> SpGEMM.

        ``spmv_domain_part`` mirrors the reference's column-split SpMV flag
        (csr.py:442/869-927): the contraction dimension is split into
        ``parallel.mesh.num_procs()`` domains reduced separately
        (ops.spmv.csr_spmv_colsplit). The mesh version of the same strategy
        is ``parallel.dist.shard_csr_cols`` (psum_scatter over ICI).
        """
        from .csc import csc_array

        if isinstance(other, SparseArray):
            if out is not None:
                raise ValueError("out= is not supported for spgemm")
            if self.shape[1] != other.shape[0]:
                raise ValueError(
                    f"dimension mismatch: {self.shape} @ {other.shape}"
                )
            b = other.tocsr()
            indptr, indices, data = spgemm_ops.spgemm_csr_csr(
                self.indptr, self.indices, self.data,
                b.indptr, b.indices, b.data,
                self.shape, b.shape,
            )
            return csr_array.from_parts(
                data, indices, indptr, (self.shape[0], b.shape[1])
            )
        x = asjnp(other)
        if x.ndim == 1:
            if x.shape[0] != self.shape[1]:
                raise ValueError(
                    f"dimension mismatch: {self.shape} @ {x.shape}"
                )
            if spmv_domain_part:
                from .parallel.mesh import num_procs

                y = spmv_ops.csr_spmv_colsplit(
                    self.indptr, self.indices, self.data, x, self.shape[0],
                    max(num_procs(), 1),
                )
            else:
                y = self._spmv(x)
        elif x.ndim == 2:
            if x.shape[0] != self.shape[1]:
                raise ValueError(
                    f"dimension mismatch: {self.shape} @ {x.shape}"
                )
            y = self._spmm(x)
        else:
            raise ValueError("can only multiply by 1-D or 2-D arrays")
        if out is not None:
            # The reference writes into a pre-allocated store (csr.py:501-503);
            # jax arrays are immutable, so out= is advisory — we just check shape.
            if out.shape != y.shape:
                raise ValueError("out has the wrong shape")
        return y

    def _maybe_dia(self):
        """Detect banded structure and cache DIA planes for zero-gather SpMV.

        Matrices living on a handful of diagonals (every reference
        benchmark: Laplacians, the 11-diag microbench) skip index gathers
        entirely — SpMV becomes shifted vector adds (ops.dia_spmv). Pure
        structure detection (mode-independent; _spmv applies the mode);
        one host sync at first use, result cached (None = not banded).
        """
        if self._dia is not False:
            return self._dia
        if in_trace():
            # first use is INSIDE a trace (e.g. a multigrid prolongator
            # applied only in the compiled V-cycle): detection needs a
            # host sync, which would raise and silently demote the whole
            # solver to its host loop. Skip WITHOUT caching — an eager
            # warm call (linalg.cg does one) can still detect later.
            return None
        m, n = self.shape
        nnz = self.nnz
        if nnz == 0:
            self._dia = None
            return None
        # the cache is written only by a detection that ran to its end: a
        # failed fetch raises and leaves _dia unchecked, never "not banded"
        # one-time eager analysis, on the host. The span is the host's time
        # in it and does not wait for the planes' last ops: they overlap
        # what the caller does next (the first solve's compile), and a wait
        # here would put them in its way (6 s at 3200^2, PERF.md section 5)
        with telemetry.span("layout.dia_build"), host_scope():
            self._dia = self._maybe_dia_detect(m, n, nnz)
        return self._dia

    @staticmethod
    def _fetch_offsets(offs_dev):
        """Host fetch of diagonal offsets — the device->host transfer of
        banded detection (one for a matrix the strided sample turns away, a
        second, of the bounded unique, for one it does not), split out so
        tests can make it fail."""
        return np.unique(np.asarray(offs_dev))

    def _maybe_dia_detect(self, m, n, nnz):
        from .dia import _coo_to_dia

        # `layout.detect` is the decision alone, banded or not: all that a
        # general matrix pays here
        with telemetry.span("layout.detect"):
            banded = self._few_diagonals(n, nnz)
        if not banded:
            return None
        # duplicate-summing plane build
        planes, offsets, _ = _coo_to_dia(self.tocoo())
        return (planes, tuple(int(o) for o in offsets))

    def _few_diagonals(self, n, nnz) -> bool:
        """The banded rule (``dia.few_diagonals``) on this matrix's arrays."""
        from .dia import few_diagonals

        # col - row fits int32 whenever both dims do (values < 2**31 each,
        # difference in (-2**31, 2**31)); int64 here would just warn-and-
        # truncate under the default no-x64 config
        idt = jnp.int32
        # a general matrix is turned away by a strided sample of its entries
        # (the rule of `dia.banded_offsets`, here on the arrays as they are):
        # more diagonals in the sample than a banded matrix has in all
        at = jnp.arange(0, nnz, max(nnz // 8192, 1), dtype=self.indptr.dtype)
        rows = jnp.searchsorted(self.indptr, at, side="right") - 1
        sample = self._fetch_offsets(
            self.indices[at].astype(idt) - rows.astype(idt))
        if len(sample) > settings.dia_max_diags:
            return False
        rows = expand_rows(self.indptr, nnz)
        # bounded-size unique: >max_diags distinct offsets still yields
        # max_diags+1 values, which the gate below rejects
        offs_dev = jnp.unique(self.indices.astype(idt) - rows.astype(idt),
                              size=min(settings.dia_max_diags + 1, nnz),
                              fill_value=jnp.iinfo(idt).max)
        # a fetch that fails raises: a banded matrix must not go down the
        # gather path in silence (tests/test_sell_spmv.py)
        offs = self._fetch_offsets(offs_dev)
        offs = offs[offs != np.iinfo(np.int32).max]
        return few_diagonals(len(offs), n, nnz)

    def _spmv_form(self, xdtype=None):
        """``(kind, arrays, meta)`` of the layout a vector product takes now:
        the ``_LAYOUTS`` walk of the ambient mode, each layout built on its
        first eager use. ``arrays`` are the layout's jax arrays and ``meta``
        its hashable rest, as :func:`form_matvec` takes them, so that a
        compiled solver can have the matrix as an argument and nothing of
        it as a constant of its program. ``xdtype`` is the operand's type
        (a layout that multiplies in one type only is passed over for
        another). The arrays of ``"dia"`` are its scipy-layout planes or,
        where the kernel multiplies, their packed rows
        (``_dia_operands``); ``"dia+"`` is the user-set packed kernel over
        the planes, which keeps an operator of its own (``_spmv``)."""
        for name in _layouts(1):
            lay = self._offer(name, xdtype)
            if lay is None:
                continue
            if name == "well":
                return name, lay.arrays, lay.meta
            if name == "dia":
                return name, self._dia_operands(lay, xdtype), (lay[1], self.shape)
            if name == "dia+":
                return name, lay[0], (lay[1], self.shape)
            if name.startswith("sell"):
                return "sell", (lay.slabs, lay.pos), lay.plan.zero_rows
            return "ell", lay, None
        return "segment", (self.indptr, self.indices, self.data), self.shape[0]

    def _spmv(self, x):
        kind, arrays, meta = self._spmv_form(x.dtype)
        if kind == "dia+":
            from .kernels.dia_spmv import cached_prepared_spmv

            y = cached_prepared_spmv(
                self, "_dia_prepared", arrays, meta[0], self.shape, x
            )
            if y is not None:
                return y
            kind = "dia"  # band too wide for VMEM: the XLA form
        if kind in ("sell", "well"):  # once a product here, as `PreparedCSR.__call__`
            telemetry.count(f"kernel.{kind}_spmv")
        elif form_kernels(kind, arrays):
            telemetry.count("kernel.dia_spmv_rows")
        return form_matvec(kind, meta, arrays, x)

    def _spmm(self, B):
        for name in _layouts(2):
            lay = self._offer(name)
            if lay is None:
                continue
            if name.startswith("sell"):  # slab gathers, XLA form
                return lay.matmat(B)
            return spmv_ops.csr_spmm_ell(lay[0], lay[1], B)
        return spmv_ops.csr_spmm_segment(
            self.indptr, self.indices, self.data, B, self.shape[0]
        )

    def _ell_idx(self):
        """The padded-row index plane where a 2-D product may take that
        layout (the tropical ops gather through it), else None."""
        for name in _layouts(2):
            if name.startswith("ell"):
                lay = self._offer(name)
                return None if lay is None else lay[0]
        return None

    def _rdot(self, other):
        """other @ A for dense other (SPMM_DENSE_CSR, csr.py:1209)."""
        B = asjnp(other)
        if B.ndim == 1:
            return spmv_ops.rspmm(
                self.indptr, self.indices, self.data, B[None, :], self.shape[1]
            )[0]
        return spmv_ops.rspmm(
            self.indptr, self.indices, self.data, B, self.shape[1]
        )

    def matvec(self, x, out=None):
        return self.dot(x, out=out)

    @track_provenance
    def sddmm(self, C, D):
        """Structure-preserving sampled dense-dense matmul (csr.py:1244)."""
        vals = sddmm_ops.csr_sddmm(
            self.indptr, self.indices, self.data, asjnp(C), asjnp(D)
        )
        return self._with_data(vals)

    @track_provenance
    def tropical_spmv(self, x):
        """(max, +) semiring SpMV over 3-tuple vectors (csr.py:366).

        Powers AMG MIS aggregation. x is [n, 3]; comparison is lexicographic on
        (x0 + a, x1, x2)? — see ops.tropical for the exact semiring.
        """
        from .ops import tropical

        return tropical.tropical_spmv(
            self.indptr, self.indices, self.data, asjnp(x), self.shape[0],
            ell_idx=self._ell_idx(),
        )

    @track_provenance
    def mis_tropical(self, k=1, invalid=None, seed=0):
        """Maximal independent set MIS(k) flags, one compiled tournament.

        Device-side analog of the AMG aggregation driver (reference
        amg.py:199-257): the whole round loop is a ``lax.while_loop``
        over tropical SpMV hops — no host fetch per round. Returns the
        [m] int32 flag vector (2 = MIS, 0 = dominated, -1 = invalid).
        """
        from .ops import tropical

        return tropical.mis_flags(
            self.indptr, self.indices, self.data, self.shape[0], k=k,
            invalid=invalid, seed=seed,
            ell_idx=self._ell_idx(),
        )

    @track_provenance
    def mis_aggregate_cols(self, flags):
        """(aggregate column per node, n_coarse) from MIS flags — the
        nearest-root routing (reference amg.py:259-283), on device."""
        from .ops import tropical

        return tropical.mis_aggregate_cols(
            self.indptr, self.indices, self.data, self.shape[0], flags,
            ell_idx=self._ell_idx(),
        )

    # -- elementwise -------------------------------------------------------
    @track_provenance
    def __add__(self, other):
        if np.isscalar(other):
            if other == 0:
                return self.copy()
            raise NotImplementedError("adding a nonzero scalar densifies")
        if isinstance(other, SparseArray):
            b = other.tocsr()
            indptr, indices, data = elementwise.csr_add_csr(
                self.indptr, self.indices, self.data,
                b.indptr, b.indices, b.data, self.shape,
            )
            return csr_array.from_parts(data, indices, indptr, self.shape)
        # dense other -> dense result
        return self.toarray() + asjnp(other)

    def __radd__(self, other):
        return self.__add__(other)

    def __mul__(self, other):
        if np.isscalar(other) or getattr(other, "ndim", 1) == 0:
            return self._with_data(self.data * other)
        return self.multiply(other)

    @track_provenance
    def multiply(self, other):
        if np.isscalar(other) or getattr(other, "ndim", 1) == 0:
            return self._with_data(self.data * other)
        if isinstance(other, SparseArray):
            b = other.tocsr()
            indptr, indices, data = elementwise.csr_mult_csr(
                self.indptr, self.indices, self.data,
                b.indptr, b.indices, b.data, self.shape,
            )
            return csr_array.from_parts(data, indices, indptr, self.shape)
        d = asjnp(other)
        m, n = self.shape
        if d.ndim == 1:
            d = d[None, :]
        if d.ndim != 2 or d.shape[0] not in (1, m) or d.shape[1] not in (1, n):
            raise ValueError(
                f"inconsistent shapes: {self.shape} and {np.shape(other)}"
            )
        # broadcast operands stay per-nnz: materializing the [m, n]
        # broadcast of a column vector is O(m*n) memory (512 GB at the
        # AMG example's 512^2 grid); scale rows/columns directly instead
        if d.shape == (1, 1):
            return self._with_data(self.data * d[0, 0])
        if d.shape[1] == 1:  # column vector: scale rows
            rows = expand_rows(self.indptr, int(self.data.shape[0]))
            return self._with_data(self.data * d[rows, 0])
        if d.shape[0] == 1:  # row vector: scale columns
            return self._with_data(self.data * d[0, self.indices])
        vals = elementwise.csr_mult_dense(
            self.indptr, self.indices, self.data, d, self.shape
        )
        return self._with_data(vals)

    # -- reductions / extraction -------------------------------------------
    def sum(self, axis=None):
        return elementwise.csr_sum(
            self.indptr, self.indices, self.data, self.shape, axis=axis
        )

    def diagonal(self, k=0):
        return elementwise.csr_diagonal(
            self.indptr, self.indices, self.data, self.shape, k=k
        )

    # -- conversions -------------------------------------------------------
    def tocsr(self):
        return self

    def tocoo(self):
        from .coo import coo_array

        rows, cols, data = conv.csr_to_coo(
            self.indptr, self.indices, self.data, self.shape
        )
        out = coo_array((data, (rows, cols)), shape=self.shape)
        # CSR expands to row-major-sorted, duplicate-free triples — mark
        # canonical so reductions skip the re-canonicalization pass
        out.has_sorted_indices = True
        out.has_canonical_format = True
        return out

    def tocsc(self):
        from .csc import csc_array

        indptr, indices, data = conv.csr_to_csc(
            self.indptr, self.indices, self.data, self.shape
        )
        return csc_array.from_parts(data, indices, indptr, self.shape)

    def todia(self):
        return self.tocoo().todia()

    def toarray(self):
        return conv.csr_to_dense(self.indptr, self.indices, self.data, self.shape)

    def transpose(self, axes=None):
        """Zero-copy transpose: reinterpret the same buffers as CSC (like scipy)."""
        if axes is not None:
            raise ValueError("transpose with axes != None is unsupported")
        from .csc import csc_array

        return csc_array.from_parts(
            self.data, self.indices, self.indptr, (self.shape[1], self.shape[0])
        )

    @property
    def T(self):
        return self.transpose()

    # -- distribution ------------------------------------------------------
    def balance(self, num_shards=None):
        """Compute nnz-balanced row-block boundaries and cache them.

        Reference: ``DenseSparseBase.balance`` (base.py:198-282) — preimage of an
        equal nnz split back to rows. On TPU: one host-side searchsorted over
        indptr; the splits are consumed by ``sparse_tpu.parallel`` when sharding.
        """
        from .parallel.partition import balanced_row_splits

        if num_shards is None:
            num_shards = len(jax.devices())
        self._balanced_splits = balanced_row_splits(self.indptr, num_shards)
        return self

    def __str__(self):
        return (
            f"<{self.shape[0]}x{self.shape[1]} CSR array, nnz={self.nnz},"
            f" dtype={self.dtype}>"
        )

    __repr__ = __str__


def spmv(A: csr_array, x, y=None):
    """Free-function SpMV, mirroring the reference's ``spmv`` (csr.py:863)."""
    return A.dot(x, out=y)
