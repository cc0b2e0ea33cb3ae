"""Artifact codecs: prepared-operator objects <-> (meta, arrays).

Each codec maps one plan-cache-able object to a numpy-array payload plus
a small JSON meta dict (plan geometry, dtype), and back. Decodes mirror
the build sites they replace: arrays re-enter as jnp arrays committed to
the execution device (``utils.commit_to_exec_device``), so a disk hit
hands the caller exactly what a fresh pack would have — same types, same
residency — without the host-side pack.

Keys are CONTENT fingerprints (sha256 over the exact buffers plus every
setting the pack depends on), computed lazily only when the vault is
enabled. Two operators with equal content share one artifact; any
content or settings change is a different key, so the disk tier can
never serve a stale layout — the in-process tier's weak-ref identity
semantics are unaffected.

Registered kinds:

* ``pattern``       — raw ``SparsityPattern`` structure (indptr/indices/
                      shape): what the warm-start manifest replays.
* ``sell_pattern``  — a pattern's ``_SellPatternPack`` (plan, idx slabs,
                      pos, per-slab nnz source maps).
* ``plane_pattern`` — a pattern's ``_PlanePatternPack`` (offsets, the
                      ``[D, m]`` slot map), or the banded rule's "no":
                      what ``SparsityPattern.plane_pack`` answers, so a
                      warm restart neither counts diagonals nor rebuilds
                      the map.
* ``prepared_csr``  — a full ``PreparedCSR`` (plan, idx+val slabs, pos).
* ``prepared_dia``  — a ``PreparedDia`` (DiaPlan geometry incl. the
                      autotuned row tile, packed plane buffer) — the
                      tile choice persists across sessions, so a warm
                      restart also skips the autotune probe.
* ``precond_diag`` / ``precond_block`` / ``ilu_symbolic`` — the
                      pattern-level preconditioner maps and symbolic
                      factorizations (``sparse_tpu.precond``, ISSUE 14):
                      structure-only, one artifact per (pattern, knobs),
                      so warm restarts skip every symbolic build.
* ``autopilot_policy`` — a converged autopilot :class:`PolicyDecision`
                      (``sparse_tpu.autopilot``, ISSUE 16): pure-meta
                      (no arrays), keyed by (pattern fingerprint,
                      solver, bucket, dtype, SLO class, mesh
                      fingerprint, candidate-grid fingerprint), so a
                      restart serves the tuned policy from the first
                      request instead of re-exploring.
* ``ingest_fpindex`` — the ingest dedup index
                      (``sparse_tpu.ingest.fingerprint``, ISSUE 18):
                      pure-meta ``structure key -> pattern key`` map
                      under the single well-known key ``fpindex``, so a
                      fresh process recognizes a re-arriving matrix
                      structure before ever holding it in memory.
"""

from __future__ import annotations

import hashlib

import numpy as np

from ..config import settings

_CODECS: dict = {}


def register(kind: str, encode, decode) -> None:
    _CODECS[kind] = (encode, decode)


def codec(kind: str):
    return _CODECS.get(kind)


def digest(*parts) -> str:
    """Content fingerprint over arrays (dtype+shape+bytes) and scalars."""
    h = hashlib.sha256()
    for p in parts:
        if isinstance(p, np.ndarray):
            h.update(str(p.dtype).encode())
            h.update(str(p.shape).encode())
            h.update(np.ascontiguousarray(p).tobytes())
        else:
            h.update(str(p).encode())
        h.update(b"|")
    return h.hexdigest()[:40]


def _sell_settings() -> tuple:
    from ..kernels import sell_spmv

    return (
        "C", settings.sell_chunk, "sigma", settings.sell_sigma,
        "slabs", settings.sell_max_slabs,
        # the rule that gives a slab its row count (`slab_rows`): a pack
        # written under another rule has other slabs
        "rows", sell_spmv.ROW_ALIGN, sell_spmv.WIDE_PERIOD,
        *sell_spmv.WIDE_BAND, sell_spmv.WINDOW_ROWS,
    )


# -- keys -------------------------------------------------------------------
def pattern_key(pattern) -> str:
    """Structure-only key (``SparsityPattern.fingerprint`` already hashes
    shape+indptr+indices)."""
    return "p" + pattern.fingerprint[2][:39]


def sell_pattern_key(pattern) -> str:
    return digest("sellpat", pattern.fingerprint[2], *_sell_settings())


def plane_pattern_key(pattern) -> str:
    return digest(
        "planepat", pattern.fingerprint[2],
        "diags", settings.dia_max_diags, "fill", settings.dia_max_fill,
    )


def prepared_csr_key(indptr, indices, data, shape) -> str:
    return digest(
        "prepcsr", np.asarray(indptr), np.asarray(indices),
        np.asarray(data), int(shape[0]), int(shape[1]), *_sell_settings(),
    )


def prepared_dia_key(data, offsets, shape) -> str:
    return digest(
        "prepdia", np.asarray(data),
        tuple(int(o) for o in offsets),
        int(shape[0]), int(shape[1]),
    )


# -- SellPlan / DiaPlan meta ------------------------------------------------
def _sell_plan_meta(plan) -> dict:
    return {
        "m": plan.m, "n": plan.n, "C": plan.C, "sigma": plan.sigma,
        "slab_meta": [list(t) for t in plan.slab_meta],
        "zero_rows": plan.zero_rows, "nnz": plan.nnz,
    }


def _sell_plan_from_meta(meta: dict):
    from ..kernels.sell_spmv import SellPlan

    return SellPlan(
        int(meta["m"]), int(meta["n"]), int(meta["C"]), int(meta["sigma"]),
        [tuple(t) for t in meta["slab_meta"]],
        int(meta["zero_rows"]), int(meta["nnz"]),
    )


def _commit(arrays):
    import jax.numpy as jnp

    from ..utils import commit_to_exec_device, host_scope

    with host_scope():
        out = tuple(jnp.asarray(a) for a in arrays)
    return commit_to_exec_device(out)


# -- pattern (raw structure) ------------------------------------------------
def _enc_pattern(pattern):
    meta = {"shape": [pattern.shape[0], pattern.shape[1]],
            "dtype": "structure", "nnz": pattern.nnz}
    return meta, {"indptr": pattern.indptr, "indices": pattern.indices}


def _dec_pattern(meta, arrays):
    from ..batch.operator import SparsityPattern

    return SparsityPattern(
        arrays["indptr"], arrays["indices"], tuple(meta["shape"])
    )


# -- sell_pattern (_SellPatternPack) ----------------------------------------
def _enc_sell_pattern(pack):
    meta = {"plan": _sell_plan_meta(pack.plan), "dtype": "structure",
            "nslabs": len(pack.idx_slabs), "nsrcs": len(pack.srcs)}
    arrays = {"pos": np.asarray(pack.pos)}
    for i, it in enumerate(pack.idx_slabs):
        arrays[f"idx{i}"] = np.asarray(it)
    for i, s in enumerate(pack.srcs):
        arrays[f"src{i}"] = np.asarray(s)
    return meta, arrays


def _dec_sell_pattern(meta, arrays):
    from ..batch.operator import _SellPatternPack

    plan = _sell_plan_from_meta(meta["plan"])
    ns = int(meta["nslabs"])
    idx_slabs = _commit([arrays[f"idx{i}"] for i in range(ns)])
    srcs = _commit([arrays[f"src{i}"] for i in range(int(meta["nsrcs"]))])
    (pos,) = _commit([arrays["pos"]])
    return _SellPatternPack(plan, idx_slabs, pos, srcs)


# -- plane_pattern (_PlanePatternPack, or False: not laid out as planes) -----
def _enc_plane_pattern(pack):
    if pack is False:
        return {"banded": False, "dtype": "structure"}, {}
    meta = {"banded": True, "dtype": "structure",
            "offsets": [int(o) for o in pack.offsets]}
    return meta, {"src": np.asarray(pack.src)}


def _dec_plane_pattern(meta, arrays):
    from ..batch.operator import _PlanePatternPack

    if not meta["banded"]:
        return False
    (src,) = _commit([arrays["src"]])
    return _PlanePatternPack(tuple(int(o) for o in meta["offsets"]), src)


# -- prepared_csr (PreparedCSR) ---------------------------------------------
def _enc_prepared_csr(prep):
    vdt = str(prep.slabs[0][1].dtype) if prep.slabs else "none"
    meta = {"plan": _sell_plan_meta(prep.plan), "dtype": vdt,
            "nslabs": len(prep.slabs)}
    arrays = {"pos": np.asarray(prep.pos)}
    for i, (it, vt) in enumerate(prep.slabs):
        arrays[f"idx{i}"] = np.asarray(it)
        arrays[f"val{i}"] = np.asarray(vt)
    return meta, arrays


def _dec_prepared_csr(meta, arrays):
    from ..kernels.sell_spmv import PreparedCSR

    plan = _sell_plan_from_meta(meta["plan"])
    slabs = []
    for i in range(int(meta["nslabs"])):
        slabs.append(_commit([arrays[f"idx{i}"], arrays[f"val{i}"]]))
    (pos,) = _commit([arrays["pos"]])
    return PreparedCSR.from_parts(plan, tuple(slabs), pos)


# -- prepared_dia (PreparedDia) ---------------------------------------------
def _enc_prepared_dia(prep):
    p = prep.plan
    meta = {
        "plan": {"offsets": list(p.offsets), "m": p.m, "n": p.n,
                 "TM": p.TM, "B": p.B, "G": p.G},
        "dtype": str(prep.planes.dtype),
    }
    return meta, {"planes": np.asarray(prep.planes)}


def _dec_prepared_dia(meta, arrays):
    from ..kernels.dia_spmv import DiaPlan, PreparedDia

    pm = meta["plan"]
    plan = DiaPlan(
        tuple(int(o) for o in pm["offsets"]), int(pm["m"]), int(pm["n"]),
        int(pm["TM"]), int(pm["B"]), int(pm["G"]),
    )
    (planes,) = _commit([arrays["planes"]])
    return PreparedDia.from_parts(plan, planes)


# -- precond maps (sparse_tpu.precond, ISSUE 14) ----------------------------
# Pattern-level preconditioner artifacts: the diagonal position map
# (point Jacobi), the block extraction map (block Jacobi) and the
# ILU(0)/IC(0) symbolic dependency closure. All structure-only (keyed on
# the pattern fingerprint plus the variant/block knobs), so one artifact
# serves every value stack and dtype over the pattern.
def _enc_precond_diag(pack):
    dpos, has = pack
    return {"dtype": "structure"}, {
        "dpos": np.asarray(dpos), "has": np.asarray(has),
    }


def _dec_precond_diag(meta, arrays):
    return _commit([arrays["dpos"], arrays["has"]])


def _enc_precond_block(pack):
    src, fix = pack
    return {"dtype": "structure"}, {
        "src": np.asarray(src), "fix": np.asarray(fix),
    }


def _dec_precond_block(meta, arrays):
    return _commit([arrays["src"], arrays["fix"]])


_ILU_FIELDS = ("dep_a", "dep_b", "dep_mask", "udiag", "udiag_ok", "lower",
               "isdiag", "upper", "tpos", "dpos", "has_diag")


def _enc_ilu_symbolic(sym):
    meta = {"variant": sym.variant, "symmetric": bool(sym.symmetric),
            "dtype": "structure"}
    return meta, {f: np.asarray(getattr(sym, f)) for f in _ILU_FIELDS}


def _dec_ilu_symbolic(meta, arrays):
    from ..precond.ilu import IluSymbolic

    committed = _commit([arrays[f] for f in _ILU_FIELDS])
    return IluSymbolic(
        str(meta["variant"]), *committed, bool(meta["symmetric"])
    )


def _enc_autopilot_policy(obj):
    return dict(obj), {}


def _dec_autopilot_policy(meta, arrays):
    return dict(meta)


def _enc_ingest_fpindex(obj):
    return {str(k): str(v) for k, v in dict(obj).items()}, {}


def _dec_ingest_fpindex(meta, arrays):
    return {str(k): str(v) for k, v in dict(meta).items()}


register("pattern", _enc_pattern, _dec_pattern)
register("sell_pattern", _enc_sell_pattern, _dec_sell_pattern)
register("plane_pattern", _enc_plane_pattern, _dec_plane_pattern)
register("prepared_csr", _enc_prepared_csr, _dec_prepared_csr)
register("prepared_dia", _enc_prepared_dia, _dec_prepared_dia)
register("precond_diag", _enc_precond_diag, _dec_precond_diag)
register("precond_block", _enc_precond_block, _dec_precond_block)
register("ilu_symbolic", _enc_ilu_symbolic, _dec_ilu_symbolic)
register("autopilot_policy", _enc_autopilot_policy, _dec_autopilot_policy)
register("ingest_fpindex", _enc_ingest_fpindex, _dec_ingest_fpindex)
