"""Sharded KV over an 8-device virtual mesh (ref NUMA_KV, `server/NuMA_KV.cpp`).

Every behavior is checked against the single-chip `kv.KV` ground truth —
the sharded path must be semantically indistinguishable.
"""

import numpy as np
import pytest

from pmdfc_tpu.config import BloomConfig, IndexConfig, IndexKind, KVConfig
from pmdfc_tpu.kv import KV
from pmdfc_tpu.parallel import ShardedKV, make_mesh
from pmdfc_tpu.utils.hashing import shard_of
from pmdfc_tpu.utils.keys import pack_key

import jax
import jax.numpy as jnp


CFG = KVConfig(
    index=IndexConfig(capacity=1 << 12),
    bloom=BloomConfig(num_bits=1 << 15),
    paged=False,
)


def _keys(n, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(1 << 20, size=n, replace=False).astype(np.uint32)
    return np.stack([flat >> 10, flat & 0x3FF], axis=-1).astype(np.uint32)


@pytest.fixture(scope="module", params=[
    "a2a",
    pytest.param("broadcast", marks=pytest.mark.slow),
])
def skv(request):
    kv = ShardedKV(CFG, dispatch=request.param)
    assert kv.n_shards == 8, "conftest must provide 8 virtual devices"
    return kv


def test_shard_routing_balanced():
    keys = jnp.asarray(_keys(4096))
    owners = np.asarray(shard_of(keys, 8))
    counts = np.bincount(owners, minlength=8)
    assert counts.min() > 4096 // 8 * 0.7  # roughly uniform

def test_insert_get_roundtrip(skv):
    keys = _keys(500, seed=1)
    vals = np.stack([keys[:, 0] ^ 0xABCD, keys[:, 1] + 1], -1).astype(np.uint32)
    skv.insert(keys, vals)
    out, found = skv.get(keys)
    assert found.all()
    np.testing.assert_array_equal(out, vals)


def test_miss_is_legal(skv):
    out, found = skv.get(np.array([[0xDEAD, 0xBEEF]], np.uint32))
    assert not found.any()
    assert (out == 0).all()


def test_delete(skv):
    keys = _keys(64, seed=2)
    vals = np.ones((64, 2), np.uint32)
    skv.insert(keys, vals)
    hit = skv.delete(keys[:32])
    assert hit.all()
    _, found = skv.get(keys[:32])
    assert not found.any()
    _, found2 = skv.get(keys[32:])
    assert found2.all()


@pytest.mark.parametrize("dispatch", [
    "a2a",
    pytest.param("broadcast", marks=pytest.mark.slow),
])
def test_matches_single_chip_ground_truth(dispatch):
    """Same op sequence on ShardedKV and KV produces identical results."""
    skv, kv = ShardedKV(CFG, dispatch=dispatch), KV(CFG)
    keys = _keys(300, seed=3)
    vals = np.stack([keys[:, 1], keys[:, 0]], -1).astype(np.uint32)
    skv.insert(keys, vals)
    kv.insert(keys, vals)
    probe = np.concatenate([keys[:150], _keys(150, seed=4)])
    out_s, f_s = skv.get(probe)
    out_1, f_1 = kv.get(probe)
    np.testing.assert_array_equal(f_s, f_1)
    np.testing.assert_array_equal(out_s, out_1)
    assert skv.stats() == {
        k: v for k, v in kv.stats().items() if k != "uptime_s"
    }


@pytest.mark.parametrize("dispatch", [
    "a2a",
    pytest.param("broadcast", marks=pytest.mark.slow),
])
def test_dup_keys_last_wins_matches(dispatch):
    """Cross-shard batches preserve batch order for duplicate keys."""
    skv, kv = ShardedKV(CFG, dispatch=dispatch), KV(CFG)
    base = _keys(60, seed=21)
    keys = np.concatenate([base, base[::2], base[::3]])  # heavy duplication
    vals = np.stack(
        [np.arange(len(keys), dtype=np.uint32),
         np.arange(len(keys), dtype=np.uint32) * 7], -1
    )
    skv.insert(keys, vals)
    kv.insert(keys, vals)
    out_s, f_s = skv.get(base)
    out_1, f_1 = kv.get(base)
    np.testing.assert_array_equal(f_s, f_1)
    np.testing.assert_array_equal(out_s, out_1)


def test_a2a_find_anyway_utilization_recovery():
    skv = ShardedKV(CFG)
    keys = _keys(200, seed=30)
    vals = np.stack([keys[:, 1], keys[:, 0]], -1).astype(np.uint32)
    skv.insert(keys, vals)
    got_v, found, slot, shard = skv.find_anyway(keys[:50])
    assert found.all()
    np.testing.assert_array_equal(got_v, vals[:50])
    assert (slot >= 0).all()
    from pmdfc_tpu.utils.hashing import shard_of as shard_fn
    import jax.numpy as jnp
    np.testing.assert_array_equal(
        shard, np.asarray(shard_fn(jnp.asarray(keys[:50]), 8)).astype(np.int64)
    )
    # keys never inserted are not found by the scan
    _, nf, _, nsh = skv.find_anyway(_keys(20, seed=31))
    assert not nf.any() and (nsh == -1).all()
    u = skv.utilization()
    assert abs(u - 200 / skv.capacity()) < 1e-9
    assert skv.recovery()
    out, f = skv.get(keys)
    assert f.all()


@pytest.mark.parametrize("dispatch", [
    "a2a",
    pytest.param("broadcast", marks=pytest.mark.slow),
])
def test_packed_bloom_matches_single_chip(dispatch):
    """OR of per-shard packed filters == the single-chip filter, bit-for-bit
    (each key lives on exactly one shard; counters are non-negative)."""
    skv, kv = ShardedKV(CFG, dispatch=dispatch), KV(CFG)
    keys = _keys(400, seed=40)
    vals = np.ones((400, 2), np.uint32)
    skv.insert(keys, vals)
    kv.insert(keys, vals)
    skv.delete(keys[:100])
    kv.delete(keys[:100])
    np.testing.assert_array_equal(skv.packed_bloom(), kv.packed_bloom())
    per = skv.packed_bloom_per_shard()
    assert per.shape[0] == 8
    np.testing.assert_array_equal(
        np.bitwise_or.reduce(per, axis=0), kv.packed_bloom()
    )


@pytest.mark.slow  # fast-tier budget (README "Test tiers"): this invariant's cheap variant stays fast; the deep one runs in the full suite
def test_sharded_checkpoint_roundtrip(tmp_path):
    cfg = KVConfig(
        index=IndexConfig(capacity=1 << 10),
        bloom=BloomConfig(num_bits=1 << 12),
        paged=True,
        page_words=32,
    )
    skv = ShardedKV(cfg)
    keys = _keys(100, seed=50)
    rng = np.random.default_rng(51)
    pages = rng.integers(0, 1 << 32, size=(100, 32), dtype=np.uint64).astype(
        np.uint32
    )
    skv.insert(keys, pages)
    path = str(tmp_path / "sharded.npz")
    skv.save(path)
    skv2 = ShardedKV(cfg)
    skv2.restore(path)
    assert skv2.stats() == skv.stats()  # before the get bumps them
    out, found = skv2.get(keys)
    assert found.all()
    np.testing.assert_array_equal(out, pages)
    # wrong-config restore fails loudly
    other = ShardedKV(KVConfig(index=IndexConfig(capacity=1 << 11),
                               bloom=None, paged=False))
    with pytest.raises(ValueError, match="mismatch"):
        other.restore(path)


@pytest.mark.slow  # fast-tier budget (README "Test tiers"): this invariant's cheap variant stays fast; the deep one runs in the full suite
def test_a2a_bucket_overflow_is_reported_not_silent():
    """Adversarial batch: every key routed to ONE shard; overflow rows come
    back as legal drops/misses and the stats account for them."""
    from pmdfc_tpu.utils.hashing import shard_of as shard_fn
    import jax.numpy as jnp

    skv = ShardedKV(CFG)
    pool = _keys(4096, seed=60)
    owner = np.asarray(shard_fn(jnp.asarray(pool), 8))
    mine = pool[owner == 3][:256]
    assert len(mine) == 256, "need 256 keys owned by shard 3"
    vals = np.ones((len(mine), 2), np.uint32)
    res = skv.insert(mine, vals)
    # pair capacity for w=256, n=8: bl=32 -> c_pair=16; each source shard
    # holds 32 rows all destined to shard 3 -> 16 dropped per source.
    dropped = res.dropped.sum()
    assert dropped == 8 * 16
    out, found = skv.get(mine)
    placed = ~res.dropped
    assert found[placed].all()
    assert not found[res.dropped].any()
    s = skv.stats()
    assert s["puts"] == 256
    assert s["drops"] == int(dropped)
    # deletes are loss-free even for the same adversarial routing: every
    # placed key must actually invalidate (a silently failed delete would
    # leave stale data behind)
    hit = skv.delete(mine)
    np.testing.assert_array_equal(hit, placed)
    _, refound = skv.get(mine)
    assert not refound.any()


def test_extent_cross_shard():
    """Covers land on different shards; every spanned page resolves."""
    skv = ShardedKV(CFG)
    skv.insert_extent([7, 1000], [0, 1 << 20], 300)
    offsets = np.arange(0, 310, 7, dtype=np.uint32)
    probe = np.stack(
        [np.full_like(offsets, 7), 1000 + offsets], -1
    ).astype(np.uint32)
    out, found = skv.get_extent(probe)
    spanned = offsets < 300
    np.testing.assert_array_equal(found, spanned)
    expect = (1 << 20) + offsets[spanned].astype(np.uint64) * 4096
    got = out[spanned, 0].astype(np.uint64) << 32 | out[spanned, 1]
    np.testing.assert_array_equal(got, expect)


def test_extent_matches_single_chip():
    skv, kv = ShardedKV(CFG), KV(CFG)
    for store in (skv, kv):
        store.insert_extent([1, 64], [0, 4096], 100)
        store.insert_extent([2, 0], [1, 0], 17)
    probe = np.array(
        [[1, 64], [1, 163], [1, 164], [2, 0], [2, 16], [2, 17], [3, 5]],
        np.uint32,
    )
    out_s, f_s = skv.get_extent(probe)
    out_1, f_1 = kv.get_extent(probe)
    np.testing.assert_array_equal(f_s, f_1)
    np.testing.assert_array_equal(out_s, out_1)


def test_paged_mode_sharded():
    cfg = KVConfig(
        index=IndexConfig(capacity=1 << 10),
        bloom=None,
        paged=True,
        page_words=64,
    )
    skv = ShardedKV(cfg)
    keys = _keys(40, seed=5)
    rng = np.random.default_rng(6)
    pages = rng.integers(0, 1 << 32, size=(40, 64), dtype=np.uint64).astype(
        np.uint32
    )
    skv.insert(keys, pages)
    out, found = skv.get(keys)
    assert found.all()
    np.testing.assert_array_equal(out, pages)


def test_eviction_propagates(skv_=None):
    """FIFO eviction still reports evicted keys through the combine."""
    cfg = KVConfig(
        index=IndexConfig(capacity=16, cluster_slots=16),
        bloom=BloomConfig(num_bits=1 << 10),
        paged=False,
    )
    skv = ShardedKV(cfg)
    keys = _keys(256, seed=7)
    vals = np.ones((256, 2), np.uint32)
    # capacity is 16 slots/shard × 8 shards = 128 < 256. Fill in a first
    # batch, then a second batch must FIFO-evict prior residents. A batch
    # with more than 16 new keys for a shard's one cluster leaves its
    # overflow unplaced (`dropped`), which the stats count as evictions of
    # their own: what a serial order inserting them first would evict.
    first = skv.insert(keys[:128], vals[:128])
    res = skv.insert(keys[128:], vals[128:])
    evicted = (res.evicted != 0xFFFFFFFF).any(axis=-1)
    assert evicted.sum() > 0
    overflow = int(first.dropped.sum()) + int(res.dropped.sum())
    s = skv.stats()
    assert s["evictions"] == int(evicted.sum()) + overflow
    assert s["drops"] == 0


@pytest.mark.slow  # fast-tier budget (README "Test tiers"): this invariant's cheap variant stays fast; the deep one runs in the full suite
def test_sharded_cceh_roundtrip():
    from pmdfc_tpu.config import IndexKind

    cfg = KVConfig(
        index=IndexConfig(
            kind=IndexKind.CCEH, capacity=1 << 9, segment_slots=128,
            split_headroom=2,
        ),
        bloom=None,
        paged=False,
    )
    kv = ShardedKV(cfg, mesh=make_mesh())
    rng = np.random.default_rng(13)
    lo = rng.choice(1 << 20, size=700, replace=False).astype(np.uint32)
    ks = np.asarray(pack_key(np.ones(700, np.uint32), lo))
    vals = np.stack([np.zeros(700, np.uint32), lo], axis=-1)
    for i in range(0, 700, 128):
        kv.insert(ks[i : i + 128], vals[i : i + 128])
    out, found = kv.get(ks)
    s = kv.stats()
    assert (~found).sum() <= s["evictions"] + s["drops"]
    np.testing.assert_array_equal(out[found, 1], lo[found])


@pytest.mark.slow  # fast-tier budget (README "Test tiers"): this invariant's cheap variant stays fast; the deep one runs in the full suite
def test_cleancache_client_over_sharded_server():
    """The full client stack (cleancache + bloom mirror) rides the sharded
    server unchanged: DirectBackend speaks the same surface for KV and
    ShardedKV, and the OR-combined packed filter keeps mirror semantics."""
    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.client.cleancache import CleanCacheClient

    cfg = KVConfig(
        index=IndexConfig(capacity=1 << 10),
        bloom=BloomConfig(num_bits=1 << 13),
        paged=True,
        page_words=32,
    )
    skv = ShardedKV(cfg)
    cc = CleanCacheClient(DirectBackend(skv))
    rng = np.random.default_rng(70)
    pages = rng.integers(0, 1 << 32, size=(60, 32), dtype=np.uint64).astype(
        np.uint32
    )
    cc.put_pages(np.full(60, 11), np.arange(60), pages)
    out, found = cc.get_pages(np.full(60, 11), np.arange(60))
    assert found.all()
    np.testing.assert_array_equal(out, pages)
    # absent keys short-circuit on the mirrored (OR-combined) filter:
    # most must never generate server traffic, not just bump a counter
    before = cc.counters["actual_gets"]
    out2, found2 = cc.get_pages(np.full(30, 11), np.arange(500, 530))
    assert not found2.any()
    assert cc.counters["bf_short_circuits"] >= 25
    assert cc.counters["actual_gets"] - before <= 5
    hit = cc.invalidate_pages(np.full(10, 11), np.arange(10))
    assert hit.all()
    _, refound = cc.get_pages(np.full(10, 11), np.arange(10))
    assert not refound.any()


def test_node_of_and_shard_report():
    """GetNodeID + per-node load stats analogs (`NuMA_KV.cpp:136-151`,
    `CCEH_hybrid.h:202-206`): routing is consistent with where keys land,
    and the per-shard report sums to the global truth."""
    skv = ShardedKV(CFG)
    keys = _keys(256, seed=21)
    vals = np.stack([keys[:, 0] ^ 0xABCD, keys[:, 1] + 1], -1).astype(
        np.uint32
    )
    skv.insert(keys, vals)
    nodes = skv.node_of(keys)
    assert nodes.shape == (256,)
    assert nodes.min() >= 0 and nodes.max() < skv.n_shards
    # find_anyway reports the shard each key actually lives on
    _, found, _, shard = skv.find_anyway(keys)
    assert found.all()
    assert np.array_equal(shard, nodes)
    rep = skv.shard_report()
    assert rep["n_shards"] == skv.n_shards
    assert sum(rep["occupancy"]) == 256
    assert sum(rep["stats"]["puts"]) == skv.stats()["puts"]
    # murmur3 routing spreads a random key set across every shard
    assert all(o > 0 for o in rep["occupancy"])
    assert "crf" not in rep  # LRFU plane is opt-in (the reference's -DLRFU)


def test_lrfu_stats_plane():
    """Per-shard LRFU load metrics (`CCEH_hybrid.h:202-206` Metric{atime,
    crf} + freq, the -DLRFU plane the reference stubs): freq counts every
    routed request, atime tracks the last touch tick, and a shard hammered
    repeatedly accumulates more crf than one touched once."""
    skv = ShardedKV(CFG, lrfu_stats=True)
    keys = _keys(256, seed=31)
    vals = np.stack([keys[:, 0], keys[:, 1]], -1).astype(np.uint32)
    skv.insert(keys, vals)
    nodes = skv.node_of(keys)
    # hammer one shard's keys with repeated gets
    hot = int(np.bincount(nodes, minlength=skv.n_shards).argmax())
    hot_keys = keys[nodes == hot]
    for _ in range(4):
        skv.get(hot_keys)
    rep = skv.shard_report()
    assert sum(rep["freq"]) == 256 + 4 * len(hot_keys)
    assert rep["atime"][hot] == 5  # last tick that routed to the hot shard
    cold = int(np.argmin(rep["crf"]))
    assert rep["crf"][hot] > rep["crf"][cold]
    # decayed-recency: a shard untouched since insert has crf <= its count
    counts = np.bincount(nodes, minlength=skv.n_shards)
    assert rep["crf"][cold] <= counts[cold]


@pytest.mark.slow  # fast-tier budget (README "Test tiers"): this invariant's cheap variant stays fast; the deep one runs in the full suite
def test_sampled_touch_sharded():
    """ShardedKV honors touch_sample_every: identical results, counters
    bumped one batch in N across shards (parity with kv.KV sampling)."""
    cfg = KVConfig(
        index=IndexConfig(kind=IndexKind.HOTRING, capacity=1 << 12,
                          touch_sample_every=4, decay_every_gets=0),
        bloom=None, paged=False,
    )
    skv = ShardedKV(cfg, dispatch="a2a")
    keys = _keys(256, seed=9)
    skv.insert(keys, keys)
    for _ in range(8):
        out, found = skv.get(keys)
        assert found.all()
        np.testing.assert_array_equal(out, keys)
    total = int(np.asarray(skv.state.index.counters).sum())
    assert total == 2 * 256, total  # batches 4 and 8 only


def test_health_and_shard_report_tier_stats_agree():
    """ISSUE 5 satellite: `KVServer.health` and `shard_report` used to
    recompute the tier-counter block independently (the `migrated_bytes`
    derivation was forked between kv.py and shard.py and could drift).
    Both now read `tier.counters_dict` — assert the surfaces agree
    exactly, per counter, after real migration traffic."""
    from pmdfc_tpu import tier as tier_mod
    from pmdfc_tpu.config import TierConfig
    from pmdfc_tpu.runtime.engine import Engine
    from pmdfc_tpu.runtime.server import KVServer

    W = 16
    tcfg = KVConfig(
        index=IndexConfig(capacity=1 << 10), bloom=None,
        paged=True, page_words=W,
        tier=TierConfig(promote_touches=1, ghost_rows=64),
    )

    def touch(store):
        keys = _keys(192, seed=41)
        pages = np.repeat(keys[:, 1:2], W, axis=1).astype(np.uint32)
        store.insert(keys, pages)
        for _ in range(3):          # cold hits -> promotions
            _, found = store.get(keys[:64])
            assert found.all()

    # single chip: health's kv block vs the KV tier surface
    kv = KV(tcfg)
    touch(kv)
    srv = KVServer(tcfg, kv=kv, engine=Engine(
        num_queues=2, queue_cap=1 << 8, batch=128, timeout_us=200,
        arena_pages=256, page_bytes=W * 4))
    try:
        health = srv.health()
    finally:
        srv.engine.close()
    ts = kv.tier_stats()
    expect = tier_mod.counters_dict(
        np.asarray(kv.state.pool.tstats), W * 4)
    assert expect["promotions"] > 0
    for name in list(tier_mod.TIER_STAT_NAMES) + ["migrated_bytes"]:
        assert health["kv"][name] == ts[name] == expect[name], name

    # mesh: shard_report's per-shard tier block sums to tier_stats()/
    # stats(), under the same naming + derived-field rule
    skv = ShardedKV(tcfg)
    touch(skv)
    rep, ts, merged = skv.shard_report(), skv.tier_stats(), skv.stats()
    expect = tier_mod.counters_dict(
        np.asarray(skv.state.pool.tstats).sum(axis=0), W * 4)
    for name in tier_mod.TIER_STAT_NAMES:
        assert sum(rep["tier"][name]) == ts[name] == merged[name], name
    assert ts["migrated_bytes"] == merged["migrated_bytes"] \
        == expect["migrated_bytes"]
