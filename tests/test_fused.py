"""Device-fused GET kernel suite (`pmdfc_tpu/ops/fused.py`).

What it pins:

1. **Parity** — `fused.get_core` is a bit-exact drop-in twin of
   `kv._get_core` on seeded mixed workloads (present / absent / deleted
   probes, so the evicted sketch and every miss plane carry weight):
   pages, found mask, the folded stats vector, and the whole state tree.
   Tier-1 keeps three representative (family × pool × shape) combos;
   the full linear+cceh × flat/tiered × lean/counting grid and the
   recovering reattribution drill also carry `slow`.
2. **Cause taxonomy** — `misses == Σ causes` stays bit-exact under the
   fused classifier, and the at-rest corruption drill pins that every
   digest refusal the composed verify attributes, the fused verify
   attributes identically (zero wrong bytes served either way).
3. **Mode plumbing** — `fused_mode` strictness (a typo'd `PMDFC_FUSED`
   raises rather than silently running the other kernel), `supports()`
   gates (unpaged pools / non-pow2 geometry silently ride composed even
   when forced), `KVConfig.fused_get` validation.
4. **Kill switch** — `PMDFC_FUSED=off` pins the composed program at the
   KV seam (tier-1) and collapses the 4-shard serving plane to a verb
   transcript bit-identical to the forced-fused plane with zero fused
   programs tracked (`slow`, the PMDFC_MESH2D=off drill pattern).
5. **Recompile signatures** — a cold (family, w, tile, value-width)
   rung bumps exactly two named counters once each — the jitted program
   (`recompile.kv.get_fused*`) and the Pallas kernel build
   (`recompile.kv.get_fused.kernel`) — and a repeated shape bumps
   nothing (the PR-8 tracker discipline, fused edition).

Off-chip (the CI posture) the fused side runs in Pallas interpret mode:
a conformance vehicle with the SAME trace, so parity here is parity of
the program the chip runs.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pmdfc_tpu import kv as kv_mod
from pmdfc_tpu.config import (IndexConfig, IndexKind, KVConfig, MeshConfig,
                              TelemetryConfig, TierConfig, fused_mode)
from pmdfc_tpu.ops import fused
from pmdfc_tpu.runtime import telemetry as tele

pytestmark = pytest.mark.fused

W = 64  # pow2 page words: inside the fused support set


def _cfg(kind=IndexKind.LINEAR, tiered=False, capacity=2048,
         page_words=W, fused_get="auto", paged=True):
    return KVConfig(index=IndexConfig(kind=kind, capacity=capacity),
                    paged=paged, page_words=page_words,
                    tier=TierConfig() if tiered else None,
                    fused_get=fused_get)


def _keys(n, rng):
    return np.stack([rng.integers(0, 1 << 30, n, dtype=np.uint32),
                     rng.integers(0, 1 << 30, n, dtype=np.uint32)], -1)


def _pages_of(keys, w=W):
    return ((keys[:, 0] * np.uint32(31) + keys[:, 1])[:, None]
            + np.arange(1, w + 1, dtype=np.uint32)[None, :])


def _seeded_kv(cfg, seed=7, n=192, deleted=10):
    """Insert `n` rows, delete a tail slice (evicted-sketch mass), and
    return (kv, probe) where probe mixes present, deleted, and absent
    keys — every miss cause the classifier knows gets lanes."""
    rng = np.random.default_rng(seed)
    kv = kv_mod.KV(cfg)
    keys = _keys(n, rng)
    pages = rng.integers(0, 1 << 32, (n, cfg.page_words), dtype=np.uint32)
    kv.insert(keys, pages)
    kv.delete(keys[n - deleted:])
    probe = np.concatenate([keys[:n // 2], keys[n - deleted:],
                            _keys(48, rng)])
    return kv, probe


def _stat(stats_vec, name):
    return int(np.asarray(stats_vec)[list(kv_mod.STAT_NAMES).index(name)])


def _assert_core_parity(kind, tiered, lean, recovering=False, damage=None):
    """The conformance unit: drive the SAME padded probe through the
    composed `_get_core` and `fused.get_core` (eager — interpret mode
    off-chip) and require bit-identical pages, found mask, stats vector,
    and state tree. Returns the fused-side state for cause checks."""
    cfg = _cfg(kind, tiered)
    assert fused.supports(cfg)
    kv, probe = _seeded_kv(cfg)
    state = kv.state
    if damage is not None:
        state = damage(state)
    pk = kv._pad_keys(jnp.asarray(probe), 256)
    s1, o1, f1 = kv_mod._get_core(state, cfg, pk, lean=lean,
                                  recovering=recovering)
    s2, o2, f2 = fused.get_core(state, cfg, pk, lean=lean,
                                recovering=recovering)
    assert jnp.array_equal(o1, o2), "page bytes drift"
    assert jnp.array_equal(f1, f2), "found mask drift"
    assert jnp.array_equal(s1.stats, s2.stats), (
        "stats delta (fused - composed): "
        f"{np.asarray(s2.stats) - np.asarray(s1.stats)}")
    for a, b in zip(jax.tree_util.tree_leaves(s1),
                    jax.tree_util.tree_leaves(s2)):
        assert jnp.array_equal(a, b), "state tree drift"
    # the disjoint cause partition reconciles on the folded vector
    total = sum(_stat(s2.stats, c) for c in kv_mod.MISS_CAUSE_NAMES)
    assert _stat(s2.stats, "misses") == total
    if damage is None:  # the all-corrupt drill legitimately serves 0 hits
        assert _stat(s2.stats, "hits") > 0
    assert _stat(s2.stats, "misses") > 0
    return s2


# --- 1. parity -------------------------------------------------------------


@pytest.mark.parametrize("kind,tiered,lean", [
    (IndexKind.LINEAR, False, True),
    (IndexKind.LINEAR, True, False),
    (IndexKind.CCEH, False, True),
], ids=["linear-flat-lean", "linear-tiered-counting", "cceh-flat-lean"])
def test_fused_core_parity_representative(kind, tiered, lean):
    _assert_core_parity(kind, tiered, lean)


@pytest.mark.slow
@pytest.mark.parametrize("kind", [IndexKind.LINEAR, IndexKind.CCEH])
@pytest.mark.parametrize("tiered", [False, True])
@pytest.mark.parametrize("lean", [False, True])
def test_fused_core_parity_full_grid(kind, tiered, lean):
    _assert_core_parity(kind, tiered, lean)


@pytest.mark.slow
@pytest.mark.parametrize("kind", [IndexKind.LINEAR, IndexKind.CCEH])
def test_fused_core_parity_recovering(kind):
    """Warm-restart reattribution (cold → miss_recovering) is a static
    branch AROUND the kernel — the fused program must fold it the same."""
    _assert_core_parity(kind, True, False, recovering=True)


@pytest.mark.slow
@pytest.mark.parametrize("kind", [IndexKind.LINEAR, IndexKind.CCEH])
def test_fused_digest_cause_matches_composed(kind):
    """At-rest corruption: flip one bit in every resident page. The
    fused in-VMEM digest recompute must refuse the SAME rows the
    composed verify refuses and attribute them to the SAME cause lane
    (miss_digest == corrupt_pages, zero corrupt bytes served)."""
    def damage(state):
        pool = state.pool
        return dataclasses.replace(
            state, pool=dataclasses.replace(
                pool, pages=pool.pages ^ jnp.uint32(1 << 7)))

    st = _assert_core_parity(kind, False, False, damage=damage)
    assert _stat(st.stats, "miss_digest") > 0
    assert _stat(st.stats, "miss_digest") == _stat(st.stats,
                                                   "corrupt_pages")


# --- 2. the KV seam: stats surface + tier-1 kill-switch pin ---------------


def test_fused_kv_stats_parity_and_reconcile(monkeypatch):
    """`PMDFC_FUSED=on` vs `off` over the same mixed workload through
    the public KV API: identical serving, identical stats surface
    (uptime is host wall clock), `misses == Σ causes` bit-exact. `off`
    IS today's composed path, so this doubles as the tier-1 kill-switch
    pin — the 4-shard plane transcript drill below is `slow`."""
    outs = {}
    for mode in ("on", "off"):
        monkeypatch.setenv("PMDFC_FUSED", mode)
        kv, probe = _seeded_kv(_cfg())
        assert kv._fused_on() is (mode == "on")
        pages, found = kv.get(probe)
        outs[mode] = (np.asarray(pages), np.asarray(found), kv.stats())
    (po, fo, so), (pc, fc, sc) = outs["on"], outs["off"]
    assert np.array_equal(fo, fc), "found mask drift"
    assert np.array_equal(po, pc), "page bytes drift"
    drift = {k: (so.get(k), sc.get(k)) for k in set(so) | set(sc)
             if k != "uptime_s" and so.get(k) != sc.get(k)}
    assert not drift, f"stats lanes drifted: {drift}"
    assert int(so["misses"]) == sum(int(so[c])
                                    for c in kv_mod.MISS_CAUSE_NAMES)
    assert int(so["hits"]) > 0 and int(so["misses"]) > 0


# --- 3. mode plumbing ------------------------------------------------------


def test_fused_mode_env_parsing_is_strict(monkeypatch):
    for v, want in (("off", "off"), ("0", "off"), ("false", "off"),
                    ("no", "off"), ("on", "on"), ("1", "on"),
                    ("true", "on"), ("yes", "on"), ("auto", "auto")):
        monkeypatch.setenv("PMDFC_FUSED", v)
        assert fused_mode() == want
    monkeypatch.delenv("PMDFC_FUSED")
    assert fused_mode() == "auto"
    assert fused_mode("off") == "off"   # config default flows through
    # a typo'd flag must raise, never silently run the other kernel
    monkeypatch.setenv("PMDFC_FUSED", "fused")
    with pytest.raises(ValueError, match="PMDFC_FUSED"):
        fused_mode()


def test_fused_config_field_validated():
    with pytest.raises(ValueError, match="fused_get"):
        _cfg(fused_get="yes")


def test_unsupported_configs_ride_composed(monkeypatch):
    """The fallback matrix: outside `supports()` the composed program
    serves even under a forced `on` — silently, by design."""
    monkeypatch.setenv("PMDFC_FUSED", "on")
    # unpaged (u64 values) pools: no fused program, even forced
    assert not fused.supports(_cfg(paged=False))
    assert not fused.resolve(_cfg(paged=False))
    assert kv_mod.KV(_cfg(paged=False))._fused_on() is False
    # non-pow2 page geometry: the xor tree-fold digest requires pow2
    assert not fused.supports(_cfg(page_words=48))
    # supported + forced: fused anywhere (interpret mode off-chip)
    assert fused.resolve(_cfg())
    monkeypatch.delenv("PMDFC_FUSED")
    if jax.default_backend() != "tpu":
        # auto off-chip resolves composed: interpret mode is a parity
        # vehicle, never the serving kernel
        assert not fused.resolve(_cfg())


@pytest.mark.parametrize("kind,slots,ok", [
    (IndexKind.LINEAR, 16, False),   # 64-lane rows: Mosaic refuses them
    (IndexKind.LINEAR, 32, True),
    (IndexKind.LINEAR, 64, True),
    (IndexKind.CCEH, 16, False),
    (IndexKind.CCEH, 32, True),
])
def test_rows_narrower_than_a_tile_ride_composed(monkeypatch, kind, slots,
                                                 ok):
    """A bucket row narrower than 128 lanes cannot take the kernel's 8-row
    group DMA on the chip, so `supports()` leaves it to the composed
    program even when forced, and a KV built on it never fuses."""
    idx = (IndexConfig(kind=kind, capacity=2048, cluster_slots=slots)
           if kind == IndexKind.LINEAR else
           IndexConfig(kind=kind, capacity=2048, segment_slots=256,
                       probe_window=slots))
    cfg = KVConfig(index=idx, paged=True, page_words=W, fused_get="on")
    monkeypatch.delenv("PMDFC_FUSED", raising=False)
    assert fused.supports(cfg) is ok
    assert fused.resolve(cfg) is ok
    assert kv_mod.KV(cfg)._fused_on() is ok


# --- 4/5. recompile signatures + the plane kill switch --------------------


@pytest.fixture()
def fresh_registry(tmp_path):
    reg = tele.configure(TelemetryConfig(ring_capacity=1 << 15,
                                         dump_dir=str(tmp_path)))
    yield reg
    tele.configure()


def _fused_recompiles(reg) -> dict:
    snap = reg.snapshot()["counters"]
    return {k: v for k, v in snap.items()
            if k.startswith("recompile.kv.get_fused")}


def test_fused_cold_rung_bumps_program_and_kernel_once(
        fresh_registry, monkeypatch):
    """A batch outside the warmed pad ladder is exactly two named
    builds — the jitted GET program (signature: w, value width, family,
    tile) and the Pallas kernel behind it — each counted once; the same
    shape again is a known signature and counts nothing."""
    monkeypatch.setenv("PMDFC_FUSED", "on")
    kv, probe = _seeded_kv(_cfg())
    kv.get(probe[:16])                 # warms the w=16 fused rung
    before = _fused_recompiles(fresh_registry)
    kv.get(probe[:33])                 # w=64: OUTSIDE the ladder
    after = _fused_recompiles(fresh_registry)
    bumped = {k: after[k] - before.get(k, 0) for k in after
              if after[k] != before.get(k, 0)}
    assert sorted(bumped.values()) == [1, 1], bumped
    assert "recompile.kv.get_fused.kernel" in bumped
    prog = next(k for k in bumped
                if k != "recompile.kv.get_fused.kernel")
    assert prog.startswith("recompile.kv.get_fused")
    # the rung's ring event carries the (family, tile) signature knobs
    evs = [r for r in fresh_registry.ring if r.get("kind") == "recompile"
           and r["program"] == prog[len("recompile."):]]
    assert any("family=linear" in r["sig"] and "tile=64" in r["sig"]
               for r in evs), evs
    # same shape again: the signature is known, no further counting
    kv.get(probe[:40])                 # pads to w=64 again
    assert _fused_recompiles(fresh_registry) == after


def _plane(cfg):
    from pmdfc_tpu.parallel.plane import make_serving_backend

    return make_serving_backend(cfg, MeshConfig(n_shards=4))


def _verb_transcript(be, seed=11, steps=20):
    """Seeded mixed workload straight against the plane verbs, folded
    into a comparable transcript (the test_mesh conformance idiom)."""
    rng = np.random.default_rng(seed)
    universe = _keys(192, np.random.default_rng(3))
    out = []
    for _ in range(steps):
        op = int(rng.integers(4))
        lo = int(rng.integers(0, 176))
        n = int(rng.integers(1, 16))
        sel = universe[lo:lo + n]
        if op == 0:
            be.put(sel, _pages_of(sel))
            out.append(("put", n))
        elif op in (1, 2):
            pages, found = be.get(sel)
            out.append(("get", found.tolist(), pages[found].tolist()))
        else:
            out.append(("inval", be.invalidate(sel).tolist()))
    st = be.stats()
    out.append(("stats", {k: int(v) for k, v in st.items()
                          if isinstance(v, (int, np.integer))},
                st["shard_report"]["stats"]))  # per-shard attribution too
    return out


@pytest.mark.slow
def test_fused_off_kill_switch_plane_is_conformant(
        fresh_registry, monkeypatch):
    """`PMDFC_FUSED=off` must pin the 4-shard serving plane to the
    composed program: the SAME factory call yields a bit-identical verb
    transcript vs the forced-fused plane, and zero fused programs are
    ever tracked under `off` (the PMDFC_MESH2D=off drill pattern).

    Slow tier per the PR 13/16 budget notes — tier-1 keeps the KV-seam
    kill-switch pin (`test_fused_kv_stats_parity_and_reconcile`)."""
    monkeypatch.setenv("PMDFC_FUSED", "off")
    off = _plane(_cfg(capacity=1 << 10))
    assert off.skv._fused_on() is False
    got_off = _verb_transcript(off)
    snap = fresh_registry.snapshot()["counters"]
    assert not any("get_fused" in k for k in snap), \
        "fused programs tracked under the kill switch"
    monkeypatch.setenv("PMDFC_FUSED", "on")
    on = _plane(_cfg(capacity=1 << 10))
    assert on.skv._fused_on() is True
    got_on = _verb_transcript(on)
    assert got_off == got_on, "kill switch is not conformant"
    snap = fresh_registry.snapshot()["counters"]
    assert "recompile.kv.get_fused.kernel" in snap, \
        "forced-fused plane never built the Pallas kernel"
