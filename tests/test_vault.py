"""Vault persistent plan-cache tier (ISSUE 9): crash-safe artifacts,
corruption quarantine, warm restart, disk-fault chaos.

The load-bearing contracts:

* **Corruption never escapes** — every corrupt/truncated/stale/
  mistyped artifact (and every injected ``io:*`` fault) loads as a
  clean miss: quarantined, counted, rebuilt. No exception reaches the
  caller; the rebuilt layout is identical to a cold pack.
* **Round-trip parity** — a disk-loaded ``PreparedCSR`` /
  ``PreparedDia`` / SELL pattern pack computes exactly what the fresh
  pack computes, across f32/f64/c64.
* **Warm restart** — a new "process" (cleared in-process tier) replays
  the manifest and serves at zero plan-cache misses.
* **Inert when off / invisible to traces** — ``SPARSE_TPU_VAULT``
  unset writes nothing; vault on vs off never changes a traced program
  (jaxpr string equality).
"""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest
import scipy.sparse as sp

import sparse_tpu
from sparse_tpu import plan_cache, telemetry, vault
from sparse_tpu.batch import SolveSession
from sparse_tpu.batch.operator import SparsityPattern
from sparse_tpu.config import settings
from sparse_tpu.resilience import faults
from sparse_tpu.vault import _codecs, _manifest, _store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state(tmp_path):
    """Each test gets a scratch vault + sink, a cold in-process tier,
    and ends with the vault disabled again."""
    faults.clear()
    old_vault = settings.vault
    old_tel = settings.telemetry
    settings.vault = str(tmp_path / "vault")
    telemetry.configure(str(tmp_path / "records.jsonl"))
    telemetry.reset()
    plan_cache.clear()
    yield
    faults.clear()
    settings.vault = old_vault
    settings.telemetry = old_tel
    telemetry.configure(None)
    telemetry.reset()
    plan_cache.clear()


def _spd(n=48, seed=0):
    rng = np.random.default_rng(seed)
    e = np.ones(n)
    A = sp.diags([-e[:-1], 3.0 * e, -e[:-1]], [-1, 0, 1], format="csr")
    A = A.copy()
    A.setdiag(3.0 + rng.random(n))
    A.sort_indices()
    return A


def _skewed(n=120, seed=0, dtype=np.float64):
    """A matrix the SELL path takes (one heavy row defeats the ELL gate)."""
    rng = np.random.default_rng(seed)
    A = sp.random(n, n, density=0.05, format="lil", random_state=seed)
    A[0, : n // 2] = 1.0
    A = A.tocsr().astype(dtype)
    A.setdiag(np.abs(A.diagonal()) + n)
    A.sort_indices()
    return A.tocsr()


def _quarantine_files():
    try:
        return sorted(os.listdir(vault.quarantine_dir()))
    except OSError:
        return []


# ---------------------------------------------------------------------------
# raw store
# ---------------------------------------------------------------------------
class TestStore:
    def test_roundtrip(self):
        arrays = {"a": np.arange(6, dtype=np.int64),
                  "b": np.ones((2, 3), dtype=np.float32)}
        assert vault.store("pattern", "k1", {"dtype": "structure"}, arrays)
        out = vault.load("pattern", "k1")
        assert out is not None
        meta, loaded = out
        assert meta["dtype"] == "structure"
        np.testing.assert_array_equal(loaded["a"], arrays["a"])
        np.testing.assert_array_equal(loaded["b"], arrays["b"])

    def test_missing_is_clean_miss(self):
        st0 = vault.stats()
        assert vault.load("pattern", "nope") is None
        st = vault.stats()
        assert st["misses"] == st0["misses"] + 1
        assert st["quarantined"] == st0["quarantined"]

    def test_disabled_writes_nothing(self, tmp_path):
        settings.vault = ""
        assert not vault.enabled()
        assert not vault.store("pattern", "k", {}, {"a": np.zeros(1)})
        assert vault.load("pattern", "k") is None
        A = _skewed(60)
        SparsityPattern.from_csr(A).sell_pack()
        assert not (tmp_path / "vault").exists()

    def test_plan_cache_off_bypasses_vault(self, monkeypatch):
        monkeypatch.setattr(settings, "plan_cache", False)
        st0 = vault.stats()
        SparsityPattern.from_csr(_spd(40)).sell_pack()
        st = vault.stats()
        assert st["writes"] == st0["writes"]
        assert st["hits"] == st0["hits"]

    def test_atomic_no_tmp_left_behind(self):
        vault.store("pattern", "k", {}, {"a": np.zeros(4)})
        tmp_dir = os.path.join(vault.vault_dir(), "tmp")
        assert os.listdir(tmp_dir) == []


# ---------------------------------------------------------------------------
# corruption matrix: every bad artifact = miss + quarantine, never a raise
# ---------------------------------------------------------------------------
def _stored_artifact():
    arrays = {"a": np.arange(128, dtype=np.float64)}
    assert vault.store("pattern", "kc", {"dtype": "structure"}, arrays)
    return vault.artifact_path("pattern", "kc")


def _truncate(path):
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[: len(blob) // 2])


def _bitflip(path):
    blob = bytearray(open(path, "rb").read())
    blob[-10] ^= 0x20
    open(path, "wb").write(bytes(blob))


def _flip_header_byte(path):
    blob = bytearray(open(path, "rb").read())
    blob[len(_store.MAGIC) + 2] ^= 0xFF
    open(path, "wb").write(bytes(blob))


def _patch_header(path, **kv):
    blob = open(path, "rb").read()
    nl = blob.index(b"\n", len(_store.MAGIC))
    hdr = json.loads(blob[len(_store.MAGIC):nl].decode())
    hdr.update(kv)
    open(path, "wb").write(
        _store.MAGIC + json.dumps(hdr, sort_keys=True).encode()
        + b"\n" + blob[nl + 1:]
    )


def _bad_magic(path):
    blob = open(path, "rb").read()
    open(path, "wb").write(b"NOTAVAULT!" + blob[10:])


def _empty(path):
    open(path, "wb").close()


@pytest.mark.parametrize("corrupt,reason", [
    (_truncate, "truncated"),
    (_bitflip, "checksum"),
    (_flip_header_byte, "bad-header"),
    (lambda p: _patch_header(p, format=_store.FORMAT + 1), "stale-format"),
    (lambda p: _patch_header(p, jax="0.0.0"), "stale-jax"),
    (lambda p: _patch_header(p, key="other"), "key-mismatch"),
    (_bad_magic, "bad-magic"),
    (_empty, "bad-magic"),
])
def test_corruption_matrix(corrupt, reason):
    path = _stored_artifact()
    corrupt(path)
    st0 = vault.stats()
    assert vault.load("pattern", "kc") is None  # clean miss, no raise
    st = vault.stats()
    assert st["verify_failed"] == st0["verify_failed"] + 1
    assert st["quarantined"] == st0["quarantined"] + 1
    assert not os.path.exists(path)  # moved aside, never re-read
    qf = _quarantine_files()
    assert len(qf) == 1 and reason in qf[0]


def test_wrong_dtype_expect_quarantines():
    path = _stored_artifact()
    st0 = vault.stats()
    assert vault.load("pattern", "kc", expect={"dtype": "float32"}) is None
    st = vault.stats()
    assert st["quarantined"] == st0["quarantined"] + 1
    assert not os.path.exists(path)
    assert any("expect-dtype" in f for f in _quarantine_files())


def test_quarantine_emits_event_and_is_bounded():
    settings.telemetry = True
    for i in range(_store.QUARANTINE_KEEP + 4):
        arrays = {"a": np.arange(4)}
        vault.store("pattern", f"q{i}", {"dtype": "structure"}, arrays)
        _bitflip(vault.artifact_path("pattern", f"q{i}"))
        assert vault.load("pattern", f"q{i}") is None
    assert len(_quarantine_files()) <= _store.QUARANTINE_KEEP
    kinds = [e["kind"] for e in telemetry.events()]
    assert "vault.quarantine" in kinds
    from sparse_tpu.telemetry import _schema

    for ev in telemetry.events():
        if ev["kind"].startswith("vault."):
            assert _schema.validate(ev) == []


# ---------------------------------------------------------------------------
# codec round trips
# ---------------------------------------------------------------------------
class TestRoundTrip:
    def test_sell_pattern_pack(self):
        A = _skewed(100)
        pat = SparsityPattern.from_csr(A)
        p0 = pat.sell_pack()
        assert vault.stats()["writes"] >= 1
        plan_cache.clear()
        snap = plan_cache.snapshot()
        pat2 = SparsityPattern.from_csr(A)
        p1 = pat2.sell_pack()
        d = plan_cache.delta(snap)
        assert d["disk_hits"] == 1 and d["misses"] == 0
        assert p1.plan == p0.plan
        np.testing.assert_array_equal(np.asarray(p1.pos), np.asarray(p0.pos))
        for a, b in zip(p1.idx_slabs, p0.idx_slabs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(p1.srcs, p0.srcs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("banded", [True, False])
    def test_plane_pattern_pack(self, banded):
        """The banded rule's answer is an artifact, its "no" included: a
        restart neither counts the diagonals nor rebuilds the slot map."""
        A = _spd(100) if banded else _skewed(100)
        p0 = SparsityPattern.from_csr(A).plane_pack()
        assert (p0 is not None) == banded
        plan_cache.clear()
        snap = plan_cache.snapshot()
        p1 = SparsityPattern.from_csr(A).plane_pack()
        d = plan_cache.delta(snap)
        assert d["disk_hits"] == 1 and d["misses"] == 0
        assert (p1 is not None) == banded
        if banded:
            assert p1.offsets == p0.offsets == (-1, 0, 1)
            np.testing.assert_array_equal(np.asarray(p1.src),
                                          np.asarray(p0.src))
            assert p1.src.dtype == p0.src.dtype

    def test_plane_pattern_key_separates_settings(self, monkeypatch):
        pat = SparsityPattern.from_csr(_spd(80))
        k1 = _codecs.plane_pattern_key(pat)
        monkeypatch.setattr(settings, "dia_max_fill", 1.0)
        assert _codecs.plane_pattern_key(pat) != k1

    @pytest.mark.parametrize("dtype", [np.float32, np.float64, np.complex64])
    def test_prepared_csr_matvec_parity(self, dtype, monkeypatch):
        monkeypatch.setattr(settings, "spmv_mode", "sell")
        S = _skewed(90, dtype=np.float64)
        S = S.astype(dtype)
        if np.issubdtype(dtype, np.complexfloating):
            S = S + 1j * S
        rng = np.random.default_rng(3)
        x = rng.standard_normal(90).astype(
            np.float32 if dtype == np.complex64 else dtype
        )
        y0 = np.asarray(sparse_tpu.csr_array(S) @ x)
        plan_cache.clear()
        snap = plan_cache.snapshot()
        y1 = np.asarray(sparse_tpu.csr_array(S) @ x)
        assert plan_cache.delta(snap)["disk_hits"] >= 1
        np.testing.assert_array_equal(y0, y1)  # bit-identical layouts

    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_prepared_dia_matvec_parity(self, dtype, monkeypatch):
        monkeypatch.setattr(settings, "spmv_mode", "pallas")
        D = _spd(200).astype(dtype)
        x = np.random.default_rng(4).standard_normal(200).astype(dtype)
        y0 = np.asarray(sparse_tpu.csr_array(D) @ x)
        plan_cache.clear()
        snap = plan_cache.snapshot()
        y1 = np.asarray(sparse_tpu.csr_array(D) @ x)
        assert plan_cache.delta(snap)["disk_hits"] >= 1
        np.testing.assert_array_equal(y0, y1)

    def test_prepared_dia_c64_codec_parity(self):
        """Complex plane round trip at the codec level (the Pallas DIA
        kernel itself is exercised by the f32/f64 matvec parities)."""
        from sparse_tpu.kernels.dia_spmv import PreparedDia

        rng = np.random.default_rng(5)
        data = (rng.standard_normal((3, 64))
                + 1j * rng.standard_normal((3, 64))).astype(np.complex64)
        prep = PreparedDia(data, (-1, 0, 1), (64, 64))
        key = _codecs.prepared_dia_key(data, (-1, 0, 1), (64, 64))
        assert vault.deposit("prepared_dia", key, prep)
        prep2 = vault.fetch("prepared_dia", key)
        assert prep2 is not None
        assert prep2.plan == prep.plan
        np.testing.assert_array_equal(
            np.asarray(prep2.planes), np.asarray(prep.planes)
        )

    def test_dia_tile_choice_persists(self):
        """The stored DiaPlan carries the (autotuned) row tile: a disk
        hit reuses it without re-probing."""
        from sparse_tpu.kernels.dia_spmv import PreparedDia, dia_plan

        data = np.ones((3, 64), dtype=np.float32)
        prep = PreparedDia(data, (-1, 0, 1), (64, 64), tile=131072)
        key = _codecs.prepared_dia_key(data, (-1, 0, 1), (64, 64))
        assert vault.deposit("prepared_dia", key, prep)
        prep2 = vault.fetch("prepared_dia", key)
        assert prep2.plan == dia_plan((-1, 0, 1), (64, 64), tile=131072)

    def test_content_key_separates_settings(self, monkeypatch):
        """A different SELL geometry is a different artifact — the disk
        tier can never serve a pack built under other settings."""
        pat = SparsityPattern.from_csr(_skewed(80))
        k1 = _codecs.sell_pattern_key(pat)
        monkeypatch.setattr(settings, "sell_chunk", settings.sell_chunk * 2)
        assert _codecs.sell_pattern_key(pat) != k1


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------
class TestManifest:
    def test_missing_and_empty_are_clean(self):
        assert vault.manifest_entries() == []
        os.makedirs(vault.vault_dir(), exist_ok=True)
        open(_manifest.path(), "w").close()
        st0 = vault.stats()
        assert vault.manifest_entries() == []
        assert vault.stats()["quarantined"] == st0["quarantined"]

    def test_corrupt_manifest_quarantines(self):
        os.makedirs(vault.vault_dir(), exist_ok=True)
        with open(_manifest.path(), "w") as f:
            f.write('{"format": 1, "entries": "garbage"')
        st0 = vault.stats()
        assert vault.manifest_entries() == []
        assert vault.stats()["quarantined"] == st0["quarantined"] + 1
        assert not os.path.exists(_manifest.path())

    def test_checksum_guards_entries(self):
        pat = SparsityPattern.from_csr(_spd(40))
        vault.note_program(pat, solver="cg", bucket=4, dtype="<f8")
        assert len(vault.manifest_entries()) == 1
        doc = json.load(open(_manifest.path()))
        doc["entries"][0]["solver"] = "gmres"  # tamper without re-checksum
        json.dump(doc, open(_manifest.path(), "w"))
        assert vault.manifest_entries() == []  # quarantined

    def test_note_dedupes_and_bounds(self):
        pat = SparsityPattern.from_csr(_spd(40))
        for _ in range(3):
            vault.note_program(pat, solver="cg", bucket=4, dtype="<f8")
        assert len(vault.manifest_entries()) == 1
        for i in range(_manifest.MANIFEST_KEEP + 10):
            vault.note_program(pat, solver="cg", bucket=4,
                               dtype=f"d{i}")
        ents = vault.manifest_entries()
        assert len(ents) == _manifest.MANIFEST_KEEP
        assert ents[-1]["dtype"] == f"d{_manifest.MANIFEST_KEEP + 9}"


# ---------------------------------------------------------------------------
# warm restart
# ---------------------------------------------------------------------------
def _traffic(n=64, B=4, seed=9):
    rng = np.random.default_rng(seed)
    mats = []
    for _ in range(B):
        M = _spd(n, seed=seed)
        M.setdiag(3.0 + rng.random(n))
        M.sort_indices()
        mats.append(M.tocsr())
    return mats, rng.standard_normal((B, n))


class TestWarmRestart:
    def test_replay_serves_at_zero_misses(self):
        mats, rhs = _traffic()
        ses = SolveSession("cg", warm_start=False)
        X0, _, _ = ses.solve_many(mats, rhs, tol=1e-10)
        assert len(vault.manifest_entries()) >= 1
        plan_cache.clear()  # "the process died"
        ses2 = SolveSession("cg")  # warm_start defaults on: vault enabled
        assert ses2.warm_replayed >= 1
        snap = plan_cache.snapshot()
        X1, _, _ = ses2.solve_many(mats, rhs, tol=1e-10)
        d = plan_cache.delta(snap)
        assert d["misses"] == 0 and d["hits"] >= 1
        np.testing.assert_allclose(X0, X1, atol=1e-12)

    def test_replay_packs_only_the_form_the_program_compiles(self):
        """A banded pattern's exact program multiplies by planes: neither
        the first process nor the replay builds (or stores) a SELL pack."""
        mats, rhs = _traffic()
        SolveSession("cg", warm_start=False).solve_many(mats, rhs, tol=1e-10)
        plan_cache.clear()
        ses = SolveSession("cg", warm_async=False)
        assert ses.warm_replayed >= 1
        (pat,) = ses._patterns.values()
        assert plan_cache.lookup(pat, "planes.pattern") is not None
        assert plan_cache.lookup(pat, "sell.pattern") is None
        kinds = set(os.listdir(os.path.join(settings.vault, "objects")))
        assert "plane_pattern" in kinds and "sell_pattern" not in kinds

    def test_replay_emits_event_and_counts(self):
        settings.telemetry = True
        mats, rhs = _traffic()
        SolveSession("cg", warm_start=False).solve_many(mats, rhs, tol=1e-10)
        plan_cache.clear()
        telemetry.reset()
        ses = SolveSession("cg", warm_start=True)
        assert ses.warm_replayed >= 1
        evs = [e for e in telemetry.events() if e["kind"] == "vault.replay"]
        assert evs and evs[0]["programs"] >= 1

    def test_warm_start_false_skips(self):
        mats, rhs = _traffic()
        SolveSession("cg", warm_start=False).solve_many(mats, rhs, tol=1e-10)
        plan_cache.clear()
        ses = SolveSession("cg", warm_start=False)
        assert ses.warm_replayed == 0

    def test_corrupt_manifest_degrades_to_cold(self):
        mats, rhs = _traffic()
        SolveSession("cg", warm_start=False).solve_many(mats, rhs, tol=1e-10)
        with open(_manifest.path(), "w") as f:
            f.write("not json at all")
        plan_cache.clear()
        ses = SolveSession("cg", warm_start=True)  # must not raise
        assert ses.warm_replayed == 0
        X, _, _ = ses.solve_many(mats, rhs, tol=1e-10)
        r = max(np.linalg.norm(m @ x - b)
                for m, x, b in zip(mats, X, rhs))
        assert r <= 1e-4

    @pytest.mark.parametrize("env_set", [True, False])
    def test_compile_cache_rule(self, tmp_path, monkeypatch, env_set):
        """One rule: JAX_COMPILATION_CACHE_DIR set -> the session sets no
        directory; unset -> the fixed <repo>/.jax_cache."""
        from sparse_tpu import utils

        old = jax.config.jax_compilation_cache_dir
        placed = str(tmp_path / "placed_from_outside")
        try:
            jax.config.update("jax_compilation_cache_dir", placed)
            if env_set:
                monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
            else:
                monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            SolveSession("cg", warm_start=False)
            want = placed if env_set else utils._REPO_CACHE
            assert jax.config.jax_compilation_cache_dir == want
            assert os.path.basename(utils._REPO_CACHE) == ".jax_cache"
        finally:
            jax.config.update("jax_compilation_cache_dir", old)


# ---------------------------------------------------------------------------
# io fault injection (the chaos grammar, unit-level)
# ---------------------------------------------------------------------------
class TestIoFaults:
    def test_enospc_write_fails_cleanly(self):
        faults.configure("enospc:io:p=1,n=1")
        st0 = vault.stats()
        pack = SparsityPattern.from_csr(_skewed(70)).sell_pack()
        assert pack is not None  # the pack itself must survive
        st = vault.stats()
        assert st["write_failed"] == st0["write_failed"] + 1
        tmp_dir = os.path.join(vault.vault_dir(), "tmp")
        assert not os.path.isdir(tmp_dir) or os.listdir(tmp_dir) == []

    def test_truncate_on_write_quarantines_on_read(self):
        faults.configure("truncate:io:p=1,n=1")
        p0 = SparsityPattern.from_csr(_skewed(72)).sell_pack()
        faults.clear()
        st0 = vault.stats()
        plan_cache.clear()
        p1 = SparsityPattern.from_csr(_skewed(72)).sell_pack()
        st = vault.stats()
        assert st["quarantined"] == st0["quarantined"] + 1
        assert p1.plan == p0.plan

    def test_bitflip_on_read_quarantines(self):
        p0 = SparsityPattern.from_csr(_skewed(74)).sell_pack()
        faults.configure("bitflip:io:p=1,seed=3,n=1")
        st0 = vault.stats()
        plan_cache.clear()
        p1 = SparsityPattern.from_csr(_skewed(74)).sell_pack()
        faults.clear()
        st = vault.stats()
        assert st["quarantined"] == st0["quarantined"] + 1
        assert p1.plan == p0.plan

    def test_stale_write_quarantines_on_read(self):
        faults.configure("stale:io:p=1,n=1")
        SparsityPattern.from_csr(_skewed(76)).sell_pack()
        faults.clear()
        st0 = vault.stats()
        plan_cache.clear()
        SparsityPattern.from_csr(_skewed(76)).sell_pack()
        st = vault.stats()
        assert st["quarantined"] == st0["quarantined"] + 1
        assert any("stale-format" in f for f in _quarantine_files())

    def test_io_fires_are_counted_and_seeded(self):
        faults.configure("bitflip:io:p=1,seed=7")
        a1 = faults.io_actions("read")
        faults.configure("bitflip:io:p=1,seed=7")
        a2 = faults.io_actions("read")
        assert a1 == a2 and a1[0][0] == "bitflip"
        assert faults.io_actions("write") == []  # read-only fault

    def test_bad_io_spec_rejected(self):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec("bitflip:io2")
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec("drop:io")


# ---------------------------------------------------------------------------
# GC
# ---------------------------------------------------------------------------
class TestGC:
    def test_cap_evicts_oldest(self):
        for i in range(6):
            vault.store("pattern", f"g{i}", {"dtype": "structure"},
                        {"a": np.zeros(64 * 1024 // 8)})  # ~64 KB payload
            t = time.time() - 1000 + i
            os.utime(vault.artifact_path("pattern", f"g{i}"), (t, t))
        st0 = vault.stats()
        evicted = vault.gc(cap_mb=0.2)  # ~3 artifacts fit
        assert evicted >= 2
        assert vault.stats()["evictions"] == st0["evictions"] + evicted
        left = sorted(os.listdir(os.path.join(
            vault.vault_dir(), "objects", "pattern")))
        assert f"g5{_store.SUFFIX}" in left  # newest survives
        assert f"g0{_store.SUFFIX}" not in left  # oldest went first

    def test_store_triggers_sweep(self, monkeypatch):
        monkeypatch.setattr(settings, "vault_cap_mb", 1)
        payload = {"a": np.zeros(600 * 1024 // 8)}  # ~600 KB each
        st0 = vault.stats()
        for i in range(3):
            vault.store("pattern", f"s{i}", {"dtype": "structure"}, payload)
        assert vault.stats()["evictions"] > st0["evictions"]

    def test_gc_script_matches_library_policy(self, tmp_path):
        for i in range(4):
            vault.store("pattern", f"c{i}", {"dtype": "structure"},
                        {"a": np.zeros(64 * 1024 // 8)})
            t = time.time() - 100 + i
            os.utime(vault.artifact_path("pattern", f"c{i}"), (t, t))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "vault_gc.py"),
             "--dir", vault.vault_dir(), "--cap-mb", "0.15"],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        assert "evicted" in r.stdout
        left = sorted(os.listdir(os.path.join(
            vault.vault_dir(), "objects", "pattern")))
        assert f"c3{_store.SUFFIX}" in left


# ---------------------------------------------------------------------------
# concurrency: per-process tmp names, atomic replace
# ---------------------------------------------------------------------------
_WRITER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from sparse_tpu.config import settings
from sparse_tpu import vault
settings.vault = sys.argv[1]
fill = float(sys.argv[2])
for i in range(25):
    vault.store("pattern", "shared",
                {"dtype": "structure", "writer": fill},
                {"a": np.full(2048, fill)})
print("WROTE")
"""


def test_concurrent_writers_never_tear():
    """Two processes hammering ONE key while this process loads: every
    load is either a verified artifact from one writer or a miss —
    never an exception, never a quarantine (no torn reads)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WRITER, vault.vault_dir(), str(fill)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for fill in (1.0, 2.0)
    ]
    st0 = vault.stats()
    deadline = time.time() + 120
    seen = 0
    try:
        while any(p.poll() is None for p in procs):
            out = vault.load("pattern", "shared")
            if out is not None:
                meta, arrays = out
                fill = float(meta["writer"])
                assert fill in (1.0, 2.0)
                np.testing.assert_array_equal(
                    arrays["a"], np.full(2048, fill)
                )
                seen += 1
            assert time.time() < deadline, "writers hung"
            time.sleep(0.01)
    finally:
        for p in procs:
            p.wait(timeout=120)
    for p in procs:
        assert "WROTE" in p.stdout.read(), p.stderr.read()
    # final read sees one of the two writers, intact
    meta, arrays = vault.load("pattern", "shared")
    np.testing.assert_array_equal(
        arrays["a"], np.full(2048, float(meta["writer"]))
    )
    assert vault.stats()["quarantined"] == st0["quarantined"]


# ---------------------------------------------------------------------------
# trace invisibility
# ---------------------------------------------------------------------------
def test_vault_never_changes_traced_programs():
    """The disk tier is host-side only: the bucket program a session
    builds is jaxpr-identical with the vault on and off."""
    mats, rhs = _traffic()
    pat = SparsityPattern.from_csr(mats[0])
    pat.sell_pack()
    ses = SolveSession("cg", warm_start=False)
    prog_on = ses._build_program(pat, 4, np.dtype(np.float64))
    args = (
        np.zeros((4, pat.nnz)), np.zeros((4, 64)), np.zeros((4, 64)),
        np.zeros(4), 10,
    )
    jaxpr_on = str(jax.make_jaxpr(prog_on)(*args))
    settings.vault = ""
    plan_cache.clear()
    pat2 = SparsityPattern.from_csr(mats[0])
    pat2.sell_pack()
    prog_off = SolveSession(
        "cg", warm_start=False
    )._build_program(pat2, 4, np.dtype(np.float64))
    assert str(jax.make_jaxpr(prog_off)(*args)) == jaxpr_on


def test_store_load_raw_bytes_shapes():
    """npz payloads preserve dtype/shape exactly (incl. complex)."""
    arrays = {
        "f32": np.linspace(0, 1, 7, dtype=np.float32),
        "f64": np.linspace(0, 1, 7, dtype=np.float64),
        "c64": (np.arange(5) + 1j * np.arange(5)).astype(np.complex64),
        "i32": np.arange(12, dtype=np.int32).reshape(3, 4),
    }
    vault.store("pattern", "raw", {"dtype": "structure"}, arrays)
    _meta, loaded = vault.load("pattern", "raw")
    for k, a in arrays.items():
        assert loaded[k].dtype == a.dtype
        np.testing.assert_array_equal(loaded[k], a)
