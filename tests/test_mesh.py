"""Mesh-sharded serving plane drills (marker: mesh).

Runs on the forced multi-device CPU host mesh the suite-wide conftest
sets up (`--xla_force_host_platform_device_count=8`, the
`bench/multihost_bench.py` trick) — the CI stand-in for a real chip
mesh. Four layers:

1. **Partitioning subsystem** — the axis-rule tables cover every state
   leaf for every pool layout, rules validate against the live mesh,
   and the host router's binning is loss-free and order-stable
   (bit-identical owners to the device hash).
2. **Plane verbs** — routed phases produce single-device results, the
   read-only GET path accounts its stats host-side, and per-shard
   attribution (shard_report / mesh scope) adds up.
3. **The serving drill** — a seeded mixed workload through the
   coalesced NetServer on a 4-shard plane is verb-for-verb
   BIT-IDENTICAL to the single-device path, and `PMDFC_MESH=off`
   collapses the whole plane back to that path (the kill-switch
   conformance discipline applied to topology). KVServer's `mesh=`
   engine path rides the same drill.
4. **Reshard restore** — snapshot on N shards, restore on M≠N: zero
   lost live pages, deleted keys stay deleted (legal misses only),
   extents still resolve, counters carried.
"""

from __future__ import annotations

import numpy as np
import pytest

from pmdfc_tpu.config import (BloomConfig, IndexConfig, KVConfig,
                              MeshConfig, NetConfig, TierConfig)

pytestmark = pytest.mark.mesh

W = 16


def _cfg(capacity=1 << 10, tier=None, bloom=True, paged=True):
    return KVConfig(
        index=IndexConfig(capacity=capacity),
        bloom=BloomConfig(num_bits=1 << 15) if bloom else None,
        paged=paged, page_words=W, tier=tier)


def _keys(n, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(1 << 20, size=n, replace=False)
    return np.stack([flat >> 10, flat & 0x3FF], -1).astype(np.uint32)


def _pages(keys):
    return ((keys[:, 0] * np.uint32(31) + keys[:, 1])[:, None]
            + np.arange(1, W + 1, dtype=np.uint32)[None, :])


def _mesh(n):
    import jax

    from pmdfc_tpu.parallel.shard import make_mesh

    return make_mesh(np.array(jax.devices()[:n]))


# --- 1. partitioning subsystem --------------------------------------------


@pytest.mark.parametrize("cfg", [
    _cfg(), _cfg(tier=TierConfig(ghost_rows=32)), _cfg(bloom=False),
    _cfg(paged=False),
], ids=["flat", "tiered", "no-bloom", "unpaged"])
def test_axis_rules_cover_every_leaf(cfg):
    from pmdfc_tpu.parallel import partitioning as pt

    rows = pt.describe(cfg)
    assert rows, "empty state?"
    for r in rows:
        # every leaf resolves to a spec whose leading axis is the mesh
        # axis (the shard dimension is what partitions)
        assert r["axes"][0] == pt.SHARD
        assert "kv" in r["spec"], r


def test_rules_validate_against_mesh():
    from pmdfc_tpu.parallel import partitioning as pt

    mesh = _mesh(2)
    pt.validate_rules(pt.DEFAULT_AXIS_RULES, mesh)
    with pytest.raises(ValueError, match="names a mesh axis"):
        pt.validate_rules((("shard", "model"),), mesh)
    with pytest.raises(ValueError, match="no axis rule"):
        pt.leaf_axes(".nonsense.leaf", 1)


def test_sharded_kv_rejects_bad_rules():
    from pmdfc_tpu.parallel.shard import ShardedKV

    with pytest.raises(ValueError, match="names a mesh axis"):
        ShardedKV(_cfg(), mesh=_mesh(2),
                  axis_rules=(("page_word", "nope"),))


def test_router_binning_is_loss_free_and_stable():
    from pmdfc_tpu.parallel import partitioning as pt
    from pmdfc_tpu.parallel.shard import ShardedKV

    keys = _keys(500, seed=3)
    router = pt.ShardRouter(4, pad_floor=8)
    rb = router.build(keys, _pages(keys))
    # loss-free: every request owns a distinct routed lane
    assert rb.b == 500 and len(np.unique(rb.pos)) == 500
    assert rb.counts.sum() == 500
    # scatter round-trips both payloads
    np.testing.assert_array_equal(rb.scatter(rb.keys), keys)
    np.testing.assert_array_equal(rb.scatter(rb.values), _pages(keys))
    # owners bit-identical to the device hash (the GetNodeID contract)
    skv = ShardedKV(_cfg(), mesh=_mesh(4))
    np.testing.assert_array_equal(router.owners(keys), skv.node_of(keys))
    # stable order within a shard: lanes ascend in request order
    own = router.owners(keys)
    for s in range(4):
        lanes = rb.pos[own == s]
        assert (np.diff(lanes) > 0).all()


# --- 2. plane verbs --------------------------------------------------------


def test_plane_matches_single_device_results():
    from pmdfc_tpu import kv as kv_mod
    from pmdfc_tpu.parallel.shard import ShardedKV

    keys = _keys(300, seed=11)
    pages = _pages(keys)
    skv = ShardedKV(_cfg(), mesh=_mesh(4))
    ref = kv_mod.KV(_cfg())

    res = skv.plane_insert(keys, pages).fetch()
    rres = ref.insert(keys, pages)
    np.testing.assert_array_equal(np.asarray(res.dropped),
                                  np.asarray(rres.dropped))
    g = skv.plane_get(keys).fetch()
    rout, rfound = ref.get(keys)
    np.testing.assert_array_equal(g.found, np.asarray(rfound))
    np.testing.assert_array_equal(g.dense()[g.found],
                                  np.asarray(rout)[rfound])
    # hit_rows slices agree with the dense request-order form
    np.testing.assert_array_equal(g.hit_rows(50, 200),
                                  g.dense()[50:200][g.found[50:200]])
    hit = skv.plane_delete(keys[:64]).fetch()
    rhit = ref.delete(keys[:64])
    np.testing.assert_array_equal(hit, np.asarray(rhit))
    # stats agree though the plane accounted its lean gets host-side
    s, r = skv.stats(), ref.stats()
    for k in ("puts", "gets", "hits", "misses", "deletes"):
        assert s[k] == r[k], (k, s, r)


def test_plane_per_shard_attribution_sums_to_truth():
    from pmdfc_tpu.parallel.shard import ShardedKV

    keys = _keys(400, seed=7)
    skv = ShardedKV(_cfg(), mesh=_mesh(4))
    skv.plane_insert(keys, _pages(keys)).fetch()
    h = skv.plane_get(keys)
    assert h.counts.sum() == 400  # routed-op attribution per shard
    assert (h.counts > 0).all()   # murmur3 spreads a 400-key batch
    h.fetch()
    rep = skv.shard_report()
    assert sum(rep["stats"]["gets"]) == 400
    assert sum(rep["stats"]["hits"]) == 400
    assert sum(rep["stats"]["puts"]) == 400


def test_plane_backend_telemetry_and_warmup_are_stat_clean():
    from pmdfc_tpu.parallel.plane import PlaneBackend
    from pmdfc_tpu.parallel.shard import ShardedKV

    skv = ShardedKV(_cfg(), mesh=_mesh(2))
    be = PlaneBackend(skv)
    assert be.warmup(32) > 0
    # warmup's all-INVALID batches must not count as traffic
    s = skv.stats()
    assert s["gets"] == 0 and s["puts"] == 0, s
    keys = _keys(100, seed=9)
    be.put(keys, _pages(keys))
    out, found = be.get(keys)
    assert found.all()
    np.testing.assert_array_equal(out, _pages(keys))
    st = be.stats()
    assert st["shard_report"]["n_shards"] == 2
    # per-shard routed-op counters landed on the shared mesh scope
    ops = sum(be._tele.get(f"shard{i}_ops", 0) for i in range(2))
    assert ops > 0


def test_plane_counting_path_still_migrates_tier():
    # tiered pool: the GET phase's counting (non-lean) path must still
    # run under the plane so promotions happen at the sampled cadence
    from pmdfc_tpu.parallel.shard import ShardedKV

    cfg = _cfg(capacity=1 << 9, tier=TierConfig(
        ghost_rows=32, promote_touches=1, max_promotes_per_batch=32))
    skv = ShardedKV(cfg, mesh=_mesh(2))
    keys = _keys(64, seed=13)
    skv.plane_insert(keys, _pages(keys)).fetch()
    for _ in range(4):
        g = skv.plane_get(keys).fetch()
        assert g.found.all()
    t = skv.tier_stats()
    assert t is not None and t["promotions"] > 0, t


# --- 3. the serving drill --------------------------------------------------


def _serve_workload(backend_factory):
    """Seeded mixed workload through a NetServer; returns the result
    transcript (the conformance unit of test_net.py, on the plane)."""
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    srv = NetServer(backend_factory,
                    net=NetConfig(flush_timeout_us=5000,
                                  settle_us=200)).start()
    results = []
    try:
        with TcpBackend("127.0.0.1", srv.port, page_words=W,
                        keepalive_s=None) as be:
            rng = np.random.default_rng(77)
            universe = _keys(256, seed=77)
            for _ in range(100):
                op = int(rng.integers(5))
                lo = int(rng.integers(0, 240))
                n = int(rng.integers(1, 16))
                sel = universe[lo:lo + n]
                if op == 0:
                    be.put(sel, _pages(sel))
                    results.append(("put", n))
                elif op in (1, 2):
                    out, found = be.get(sel)
                    results.append(("get", found.tolist(),
                                    out[found].tolist()))
                elif op == 3:
                    hit = be.invalidate(sel)
                    results.append(("inval", hit.tolist()))
                else:
                    vals, ef = be.get_extent(sel)
                    results.append(("gext", ef.tolist(),
                                    vals[ef].tolist()))
            be.insert_extent(np.array([3, 0], np.uint32),
                             np.array([0, 4096], np.uint32), 32)
            vals, ef = be.get_extent(
                np.array([[3, 5], [3, 40]], np.uint32))
            results.append(("ext", ef.tolist(), vals.tolist()))
    finally:
        srv.stop()
    return results


@pytest.mark.slow
def test_mesh_plane_bit_identical_to_single_device_serving():
    """THE CI drill: the 4-shard serving plane behind the coalesced
    NetServer reproduces the single-device path verb-for-verb on a
    seeded mixed workload.

    Slow tier (runs in full CI): the kill-switch conformance drill
    below makes the same transcript comparison — `PMDFC_MESH=off` IS
    the single-device path — so tier-1 keeps one copy of the 2×-serve
    cost, not two."""
    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.kv import KV
    from pmdfc_tpu.parallel.plane import make_serving_backend

    plane = make_serving_backend(_cfg(), MeshConfig(n_shards=4))
    single = DirectBackend(KV(_cfg()))
    got = _serve_workload(lambda: plane)
    want = _serve_workload(lambda: single)
    assert got == want, "mesh plane diverged from the single-device path"


def test_mesh_off_kill_switch_is_conformant(monkeypatch):
    """`PMDFC_MESH=off` must collapse the WHOLE plane to the current
    single-device path — same factory call, bit-identical transcript."""
    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.parallel.plane import make_serving_backend

    monkeypatch.setenv("PMDFC_MESH", "off")
    off = make_serving_backend(_cfg(), MeshConfig(n_shards=4))
    assert isinstance(off, DirectBackend)
    got_off = _serve_workload(lambda: off)
    monkeypatch.delenv("PMDFC_MESH")
    on = make_serving_backend(_cfg(), MeshConfig(n_shards=4))
    got_on = _serve_workload(lambda: on)
    assert got_off == got_on, "kill switch is not conformant"


def test_kvserver_mesh_mode_serves_engine_verbs():
    from pmdfc_tpu.client import EngineBackend
    from pmdfc_tpu.runtime import Engine, KVServer

    cfg = _cfg()
    keys = _keys(128, seed=21)
    pages = _pages(keys)
    srv = KVServer(cfg, engine=Engine(page_bytes=W * 4),
                   mesh=MeshConfig(n_shards=4, pad_floor=16))
    assert srv._plane is not None and srv.kv.n_shards == 4
    assert srv.kv._router.pad_floor == 16
    # warm the plane ladder BEFORE admitting a synchronous client: an
    # unwarmed driver compiling mid-flush can outlast the client's
    # wait (the build_backend("engine") discipline)
    srv.warmup(256)
    with srv.start():
        eb = EngineBackend(srv, timeout_us=60_000_000)
        eb.put(keys, pages)
        out, found = eb.get(keys)
        assert found.all()
        np.testing.assert_array_equal(out, pages)
        assert eb.invalidate(keys[:16]).all()
        _, f2 = eb.get(keys[:16])
        assert not f2.any()
        assert eb.insert_extent(np.array([9, 0], np.uint32),
                                np.array([0, 4096], np.uint32), 8) == 0
        _, fe = eb.get_extent(np.array([[9, 2]], np.uint32))
        assert fe[0]
        assert srv.health()["kv"]["hits"] >= 128
        eb.close()


def test_kvserver_mesh_respects_kill_switch(monkeypatch):
    from pmdfc_tpu.runtime import KVServer

    monkeypatch.setenv("PMDFC_MESH", "off")
    srv = KVServer(_cfg(), mesh=4)
    assert srv._plane is None
    srv.engine.close()


# --- 4. reshard restore ----------------------------------------------------


@pytest.mark.parametrize(
    "n_from,n_to",
    [(4, 2),
     pytest.param(2, 3, marks=pytest.mark.slow),
     pytest.param(8, 4, marks=pytest.mark.slow)])
def test_reshard_restore_loses_nothing(tmp_path, n_from, n_to):
    # (8, 4): M divides N, so every old shard's key set concentrates on
    # ONE new shard — the replay shape that overflowed the a2a per-pair
    # buckets before the replay moved to the loss-free plane router
    from pmdfc_tpu.parallel.shard import ShardedKV

    cfg = _cfg()
    keys = _keys(400, seed=31)
    pages = _pages(keys)
    src = ShardedKV(cfg, mesh=_mesh(n_from))
    src.plane_insert(keys, pages).fetch()
    assert src.plane_delete(keys[:50]).fetch().all()
    src.insert_extent(np.array([5, 0], np.uint32),
                      np.array([0, 8192], np.uint32), 16)
    stats_before = src.stats()
    path = str(tmp_path / "snap.ckpt")
    src.save(path)

    dst = ShardedKV(cfg, mesh=_mesh(n_to))
    dst.restore(path)
    # zero lost live pages, right bytes
    g = dst.plane_get(keys[50:]).fetch()
    assert g.found.all(), f"{int((~g.found).sum())} live pages lost"
    np.testing.assert_array_equal(g.dense(), pages[50:])
    # legal misses only: deleted keys STAY deleted
    gdel = dst.plane_get(keys[:50]).fetch()
    assert not gdel.found.any(), "deleted keys resurrected"
    # extents replayed
    _, ef = dst.get_extent(np.array([[5, 7]], np.uint32))
    assert ef[0]
    # counters carried (the replay's own bumps must not inflate them)
    after = dst.stats()
    for k in ("puts", "deletes", "extent_puts"):
        assert after[k] == stats_before[k], (k, after, stats_before)


def test_reshard_restore_rejects_mismatched_config(tmp_path):
    from pmdfc_tpu.parallel.shard import ShardedKV

    src = ShardedKV(_cfg(capacity=1 << 10), mesh=_mesh(2))
    keys = _keys(32, seed=41)
    src.plane_insert(keys, _pages(keys)).fetch()
    path = str(tmp_path / "snap.ckpt")
    src.save(path)
    dst = ShardedKV(_cfg(capacity=1 << 11), mesh=_mesh(4))
    # a failed restore must not wipe the live read-only-GET accounting
    dst.plane_insert(keys, _pages(keys)).fetch()
    assert dst.plane_get(keys).fetch().found.all()
    before = dst.stats()
    with pytest.raises(ValueError, match="per-shard KVConfig"):
        dst.restore(path)
    assert dst.stats() == before


@pytest.mark.slow
def test_unpaged_reshard_keeps_values_and_extents(tmp_path):
    # unpaged mode: user values replay verbatim; extent-cover REFS are
    # excluded from the value replay (they'd resurrect pointing into
    # the rebuilt ring) — covers resolve via the replayed ring instead
    from pmdfc_tpu.parallel.shard import ShardedKV

    cfg = _cfg(paged=False)
    src = ShardedKV(cfg, mesh=_mesh(4))
    keys = _keys(128, seed=47)
    vals = np.stack([keys[:, 0] ^ 7, keys[:, 1] + 1], -1).astype(np.uint32)
    src.plane_insert(keys, vals).fetch()
    src.insert_extent(np.array([11, 0], np.uint32),
                      np.array([0, 4096], np.uint32), 16)
    path = str(tmp_path / "snap.ckpt")
    src.save(path)
    dst = ShardedKV(cfg, mesh=_mesh(2))
    dst.restore(path)
    g = dst.plane_get(keys).fetch()
    assert g.found.all()
    np.testing.assert_array_equal(g.dense(), vals)
    _, ef = dst.get_extent(np.array([[11, 9]], np.uint32))
    assert ef[0]


@pytest.mark.slow
def test_tiered_reshard_drops_only_stale(tmp_path):
    # tiered pool: live hot+cold pages replay; balloon-shrunk (stale
    # generation) entries become legal misses, never wrong bytes
    from pmdfc_tpu.parallel.shard import ShardedKV

    cfg = _cfg(capacity=1 << 9, tier=TierConfig(ghost_rows=32))
    src = ShardedKV(cfg, mesh=_mesh(2))
    keys = _keys(96, seed=43)
    pages = _pages(keys)
    src.plane_insert(keys, pages).fetch()
    path = str(tmp_path / "snap.ckpt")
    src.save(path)
    dst = ShardedKV(cfg, mesh=_mesh(4))
    dst.restore(path)
    g = dst.plane_get(keys).fetch()
    assert g.found.all()
    np.testing.assert_array_equal(g.dense(), pages)
