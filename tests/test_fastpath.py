"""One-sided client fast path — directory mirror + direct validated row
reads (ISSUE 11).

The contract under test, at every layer:

- a fast read answers ONLY while the row's current at-rest digest still
  equals the directory entry's (and the directory epoch matches) — a
  recycled/re-written row, a ballooned pool, or a resharded mesh can
  degrade a fast read to the verb path (`fastpath_stale`) but can never
  serve wrong bytes;
- every fast lane is exactly one of hit/stale; server reads are
  DERIVED as `hits + stale` (never stored, so the sum cannot drift
  mid-pull) and the client cache's own counters agree lane for lane;
- `PMDFC_FASTPATH=off` is verb-for-verb the pre-fast-path protocol.
"""

import threading
import time

import numpy as np
import pytest

from pmdfc_tpu.client.backends import DirectBackend, LocalBackend
from pmdfc_tpu.client.cleancache import CleanCacheClient
from pmdfc_tpu.config import (
    BloomConfig, IndexConfig, KVConfig, NetConfig, TierConfig)
from pmdfc_tpu.kv import KV
from pmdfc_tpu.runtime.net import NetServer, TcpBackend

pytestmark = pytest.mark.fastpath

W = 16  # tiny pages keep socket traffic fast


def _keys(n, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(1 << 22, size=n, replace=False)
    return np.stack([flat >> 11, flat & 0x7FF], -1).astype(np.uint32)


def _pages(keys):
    return (keys[:, 0] * 7 + keys[:, 1])[:, None] + np.arange(
        W, dtype=np.uint32)


def _cfg(capacity=1 << 10, tier=None):
    return KVConfig(index=IndexConfig(capacity=capacity),
                    bloom=BloomConfig(num_bits=1 << 13),
                    paged=True, page_words=W, tier=tier)


def _server(kv=None, **kw):
    kv = kv or KV(_cfg())
    shared = DirectBackend(kv)
    net = NetConfig(flush_timeout_us=500, settle_us=50)
    return NetServer(lambda: shared, net=net, **kw).start(), kv


def _dial(srv, **kw):
    kw.setdefault("keepalive_s", None)
    return TcpBackend("127.0.0.1", srv.port, page_words=W, **kw)


def _fp_counters(srv):
    # reads are DERIVED server-side (hits + stale are the only stored
    # lanes — a third counter raced them under live stats pulls)
    s = srv.stats
    h, st = int(s["fastpath_hits"]), int(s["fastpath_stale"])
    return (h + st, h, st)


# ---------------------------------------------------------------------------
# KV-level surface
# ---------------------------------------------------------------------------


def test_directory_snapshot_matches_live_state():
    kv = KV(_cfg())
    keys = _keys(128, seed=3)
    pages = _pages(keys)
    kv.insert(keys, pages)
    snap = kv.directory_snapshot()
    assert snap is not None and len(snap["keys"]) > 0
    fv = kv.fast_view()
    # every directory entry validates against the live mirror and
    # gathers exactly the bytes the verb path serves
    ok = fv.validate(snap["epoch"], snap["shards"], snap["rows"],
                     snap["digs"])
    assert ok.all()
    got = fv.gather(snap["shards"], snap["rows"])
    want, found = kv.get(snap["keys"])
    assert found.all()
    assert np.array_equal(got, want)


def test_epoch_bumps_on_structural_invalidation():
    kv = KV(_cfg())
    keys = _keys(32, seed=4)
    kv.insert(keys, _pages(keys))
    e0 = kv.dir_epoch
    kv.insert(keys[:4], _pages(keys[:4]))   # puts never bump the epoch
    assert kv.dir_epoch == e0
    kv.delete(keys[:2])                     # invalidation does
    assert kv.dir_epoch == e0 + 1
    # a stale-epoch read fails every lane even for untouched rows
    snap_epoch = e0
    fv = kv.fast_view()
    assert not fv.validate(snap_epoch, np.zeros(1, np.uint32),
                           np.zeros(1, np.uint32),
                           np.zeros(1, np.uint32)).any()


def test_unpaged_config_has_no_fast_surface():
    kv = KV(KVConfig(index=IndexConfig(capacity=256), paged=False,
                     bloom=None, page_words=W))
    assert kv.fast_view() is None
    assert kv.directory_snapshot() is None
    srv = NetServer(lambda: DirectBackend(kv),
                    net=NetConfig(flush_timeout_us=200)).start()
    with srv:
        be = _dial(srv, directory=True)
        # capability requested but the backend cannot serve it -> no ack
        assert not be.fastpath and be.directory is None
        be.close()


# ---------------------------------------------------------------------------
# wire fast path
# ---------------------------------------------------------------------------


def test_fastread_end_to_end_bit_identical():
    srv, kv = _server()
    with srv:
        keys = _keys(96, seed=7)
        pages = _pages(keys)
        plain = _dial(srv)
        plain.put(keys, pages)
        fast = _dial(srv, directory=True)
        assert fast.fastpath and fast.directory is not None
        assert fast.dir_refresh()
        out_f, found_f = fast.get(keys)
        out_v, found_v = plain.get(keys)
        assert np.array_equal(found_f, found_v)
        assert np.array_equal(out_f, out_v)
        reads, hits, stale = _fp_counters(srv)
        assert reads == hits == len(keys) and stale == 0
        assert int(srv.stats["dir_pulls"]) == 1
        # the exactness pin: client cache and server scope agree lane
        # for lane
        c = fast.directory.counters
        assert (c["fastpath_gets"], c["fastpath_hits"],
                c["fastpath_stale"]) == (reads, hits, stale)
        fast.close()
        plain.close()


def test_teledump_pins_fastpath_invariant():
    from tools.check_teledump import check, check_fastpath

    srv, _ = _server()
    with srv:
        keys = _keys(32, seed=8)
        fast = _dial(srv, directory=True)
        fast.put(keys, _pages(keys))
        fast.dir_refresh()
        fast.get(keys)
        doc = fast.server_stats()
        assert check(doc) == []
        # pin drills: a producer that stores a reads counter must agree
        # with the lanes; a hits lane travelling without its stale lane
        # is malformed
        snap = doc["telemetry"]
        hits_names = [n for n in snap["counters"]
                      if n.endswith(".fastpath_hits")]
        assert hits_names
        scope = hits_names[0][: -len("fastpath_hits")]
        forged = {**snap,
                  "counters": {**snap["counters"],
                               scope + "fastpath_reads":
                               snap["counters"][hits_names[0]] + 1}}
        assert any("fast-lane drift" in e for e in check_fastpath(forged))
        broken = {**snap, "counters": dict(snap["counters"])}
        broken["counters"].pop(scope + "fastpath_stale")
        assert any("without its stale lane" in e
                   for e in check_fastpath(broken))
        fast.close()


def test_reput_stales_entry_delete_bumps_epoch():
    srv, kv = _server()
    with srv:
        keys = _keys(64, seed=9)
        pages = _pages(keys)
        a = _dial(srv, directory=True)
        b = _dial(srv)
        a.put(keys, pages)
        a.dir_refresh()
        assert a.get(keys[:8])[1].all()
        r0, h0, s0 = _fp_counters(srv)
        # a re-put from ANOTHER connection changes the row digest: a's
        # cached entry must stale-fall-back and serve the NEW bytes
        new = pages[3:4] ^ np.uint32(0xABCD)
        b.put(keys[3:4], new)
        out, found = a.get(keys[3:4])
        assert found[0] and np.array_equal(out[0], new[0])
        r1, h1, s1 = _fp_counters(srv)
        assert (r1 - r0, s1 - s0) == (1, 1)
        assert a.directory.counters["fastpath_stale"] == 1
        # an invalidate from another connection bumps the epoch: the
        # next fast read fails validation, the verb path answers the
        # truth, and the client marks its mirror dirty
        e0 = kv.dir_epoch
        assert b.invalidate(keys[5:6])[0]
        assert kv.dir_epoch == e0 + 1
        out2, found2 = a.get(keys[5:7])
        assert not found2[0] and found2[1]
        assert np.array_equal(out2[1], pages[6])
        assert not a.directory.ready()
        # refresh re-arms the fast path under the new epoch
        assert a.dir_refresh() and a.directory.ready()
        out3, found3 = a.get(keys[6:7])
        assert found3[0] and np.array_equal(out3[0], pages[6])
        a.close()
        b.close()


def test_dir_delta_upserts_and_tombstones():
    srv, kv = _server()
    with srv:
        keys = _keys(48, seed=10)
        pages = _pages(keys)
        a = _dial(srv, directory=True)
        b = _dial(srv)
        a.put(keys, pages)
        a.dir_refresh()
        n0 = len(a.directory)
        assert n0 == 48
        b.invalidate(keys[:4])                 # -> tombstones
        b.put(keys[4:6], pages[4:6] ^ np.uint32(1))  # -> changed digests
        assert a.dir_refresh()                 # delta, not full
        c = a.directory.counters
        assert c["dir_refreshes"] == 2
        # the delta shipped only the moved entries (+ tombstones), not
        # the whole table again
        assert c["dir_upserts"] < n0 + 8
        assert c["dir_tombstones"] >= 4
        assert len(a.directory) == 44
        mask, *_ = a.directory.lookup(keys[:4])
        assert not mask.any()
        out, found = a.get(keys[4:6])
        assert found.all()
        assert np.array_equal(out, pages[4:6] ^ np.uint32(1))
        a.close()
        b.close()


def test_fastpath_off_is_verb_for_verb_identical(monkeypatch):
    """`PMDFC_FASTPATH=off`: a directory-requesting client against an
    off server produces the same wire transcript as a plain client —
    no capability ack, no directory, zero fast-path verbs, identical
    results and identical server op counts."""

    def run(directory: bool):
        srv, _ = _server()
        with srv:
            be = _dial(srv, directory=directory)
            keys = _keys(40, seed=11)
            pages = _pages(keys)
            be.put(keys, pages)
            if directory:
                assert not be.dir_refresh()  # no-op: no directory built
            out, found = be.get(keys)
            miss = be.get(_keys(8, seed=12))[1]
            ops = int(srv.stats["ops"])
            fp = _fp_counters(srv)
            pulls = int(srv.stats["dir_pulls"])
            neg = be.fastpath, be.directory
            be.close()
        return out, found, miss, ops, fp, pulls, neg

    monkeypatch.setenv("PMDFC_FASTPATH", "off")
    out1, found1, miss1, ops1, fp1, pulls1, neg = run(directory=True)
    assert neg == (False, None)
    assert fp1 == (0, 0, 0) and pulls1 == 0
    out2, found2, miss2, ops2, fp2, pulls2, _ = run(directory=False)
    assert ops1 == ops2
    assert np.array_equal(out1, out2) and np.array_equal(found1, found2)
    assert not miss1.any() and not miss2.any()


# ---------------------------------------------------------------------------
# structural-change drills (balloon / reshard) — the epoch ladder
# ---------------------------------------------------------------------------


def test_tier_promotion_vacates_directory_rows():
    """A free-row promotion moves a key's value to the hot tier but
    leaves the vacated cold row's pages/sums intact — after the key is
    re-put (hot row updated in place, acked), the OLD directory entry
    still carries a matching digest for the vacated row. The liveness
    lane of `FastView.validate` is the only thing standing between
    that address and a stale read; pin it."""
    from pmdfc_tpu.config import TierConfig

    kv = KV(_cfg(capacity=256, tier=TierConfig(ghost_rows=16)))
    keys = _keys(32, seed=30)
    pages = _pages(keys)
    kv.insert(keys, pages)
    snap = kv.directory_snapshot()
    assert len(snap["keys"]) == len(keys)
    # drive promotions (inserts land cold; promote_touches default 2)
    for _ in range(6):
        kv.get(keys)
    assert (kv.tier_stats() or {})["promotions"] > 0
    # overwrite EVERY key: promoted keys update their hot row in place,
    # cold keys re-digest their row — either way no old-snapshot lane
    # may validate, because any that did would gather superseded bytes
    kv.insert(keys, pages ^ np.uint32(0x5A5A))
    fv = kv.fast_view()
    ok = fv.validate(snap["epoch"], snap["shards"], snap["rows"],
                     snap["digs"])
    assert not ok.any()
    # a fresh pull serves the new bytes (hot rows are live, gen 0)
    snap2 = kv.directory_snapshot()
    fv2 = kv.fast_view()
    ok2 = fv2.validate(snap2["epoch"], snap2["shards"], snap2["rows"],
                       snap2["digs"])
    assert ok2.all()
    got = fv2.gather(snap2["shards"], snap2["rows"])
    want, found = kv.get(snap2["keys"])
    assert found.all() and np.array_equal(got, want)


def test_balloon_shrink_drill_zero_wrong_bytes():
    """Balloon shrink mid-serve: every fast lane in flight degrades to
    a legal miss or the verb path — zero wrong bytes, `fastpath_stale`
    exact on both sides of the wire."""
    kv = KV(_cfg(capacity=256,
                 tier=TierConfig(balloon_step=32, ghost_rows=16,
                                 cold_init_rows=256)))
    srv, _ = _server(kv=kv)
    with srv:
        keys = _keys(128, seed=13)
        pages = _pages(keys)
        a = _dial(srv, directory=True)
        a.put(keys, pages)
        _, landed = a.get(keys)
        keys, pages = keys[landed], pages[landed]
        a.dir_refresh()
        assert a.get(keys[:16])[1].all()
        e0 = kv.dir_epoch
        assert kv.balloon_shrink(64)
        assert kv.dir_epoch > e0
        wrong = 0
        served = misses = 0
        for lo in range(0, len(keys), 16):
            out, found = a.get(keys[lo:lo + 16])
            served += int(found.sum())
            misses += int((~found).sum())
            wrong += int((out[found] != pages[lo:lo + 16][found])
                         .any(axis=1).sum())
        assert wrong == 0       # stale lanes fell back, never lied
        assert served > 0       # the surviving rows still serve
        reads, hits, stale = _fp_counters(srv)
        c = a.directory.counters
        assert (c["fastpath_gets"], c["fastpath_hits"],
                c["fastpath_stale"]) == (reads, hits, stale)
        # post-shrink epoch is refreshable and the fast path re-arms
        assert a.dir_refresh()
        out, found = a.get(keys[:16])
        assert wrong == 0 and (out[found] == pages[:16][found]).all()
        a.close()


def test_reshard_4_to_2_drill_zero_wrong_bytes(tmp_path):
    """4→2 reshard mid-serve: the swapped-in plane carries a different
    epoch, every outstanding directory entry (4-shard owners, old rows)
    goes stale, the verb path serves the truth, and a refresh re-arms
    the fast path against the 2-shard mesh."""
    import jax

    from pmdfc_tpu.parallel.shard import ShardedKV, make_mesh

    cfg = _cfg(capacity=256)
    skv4 = ShardedKV(cfg, mesh=make_mesh(jax.devices()[:4]))
    db = DirectBackend(skv4)
    srv = NetServer(lambda: db,
                    net=NetConfig(flush_timeout_us=500, settle_us=50))
    srv.start()
    with srv:
        keys = _keys(96, seed=14)
        pages = _pages(keys)
        a = _dial(srv, directory=True)
        a.put(keys, pages)
        _, landed = a.get(keys)
        keys, pages = keys[landed], pages[landed]
        a.dir_refresh()
        assert a.get(keys[:16])[1].all()
        assert set(np.unique(
            [e[0] for e in a.directory._map.values()])) > {0}
        # snapshot the 4-shard plane, replay onto 2 shards, swap it in
        path = str(tmp_path / "skv4.ckpt")
        skv4.save(path)
        skv2 = ShardedKV(cfg, mesh=make_mesh(jax.devices()[:2]))
        skv2.restore(path)
        db.kv = skv2
        wrong = served = 0
        for lo in range(0, len(keys), 16):
            out, found = a.get(keys[lo:lo + 16])
            served += int(found.sum())
            wrong += int((out[found] != pages[lo:lo + 16][found])
                         .any(axis=1).sum())
        assert wrong == 0
        assert served == len(keys)  # loss-free replay: all still hit
        reads, hits, stale = _fp_counters(srv)
        assert stale > 0
        # refresh against the new plane: owners now live on 2 shards
        assert a.dir_refresh() and a.directory.ready()
        out, found = a.get(keys[:32])
        assert found.all() and np.array_equal(out, pages[:32])
        owners = {e[0] for e in a.directory._map.values()}
        assert owners <= {0, 1}
        a.close()


def test_chaos_fastpath_soak_no_wrong_bytes():
    """Seeded ChaosProxy between a directory client and the coalesced
    server: bitflips/kills degrade connections, never bytes. The
    CleanCacheClient miss invariant (`miss_gets == bloom_negative +
    remote`) must hold with the fast path active underneath."""
    from pmdfc_tpu.runtime.failure import ChaosProxy, ReconnectingClient

    srv, _ = _server()
    with srv, ChaosProxy("127.0.0.1", srv.port, seed=17,
                         rates={"flip": 0.02, "truncate": 0.01},
                         delay_s=0.01, reorder_wait_s=0.02) as px:
        def factory():
            be = TcpBackend("127.0.0.1", px.port, page_words=W,
                            keepalive_s=None, op_timeout_s=1.0,
                            directory=True)
            be.dir_refresh()
            return be

        rc = ReconnectingClient(factory, page_words=W,
                                retry_delay_s=0.005, max_retry_delay_s=0.05)
        cc = CleanCacheClient(rc)
        keys = _keys(192, seed=18)
        pages = _pages(keys)
        rng = np.random.default_rng(19)
        put_ok = np.zeros(len(keys), bool)
        for step in range(30):
            lo = (step * 8) % len(keys)
            sel = slice(lo, lo + 8)
            cc.put_pages(keys[sel, 0], keys[sel, 1], pages[sel])
            put_ok[sel] = True
            idx = rng.integers(0, len(keys), 16)
            out, found = cc.get_pages(keys[idx, 0], keys[idx, 1])
            # zero wrong bytes: a found page is bit-exact, always
            assert (out[found] == pages[idx][found]).all()
            if step % 10 == 0:
                rc.dir_refresh()
        c = cc.counters
        assert c["miss_gets"] == (c["miss_bloom_negative"]
                                  + c["miss_remote"])
        cc.close()
        rc.close()


# ---------------------------------------------------------------------------
# lifecycle + stats-parity satellites
# ---------------------------------------------------------------------------


def test_cleancache_close_joins_refresher_and_dir_refresh():
    class SpyBackend(LocalBackend):
        def __init__(self):
            super().__init__(page_words=W)
            self.dir_refreshes = 0

        def dir_refresh(self):
            self.dir_refreshes += 1
            return True

    be = SpyBackend()
    with CleanCacheClient(be, bloom_refresh_s=0.01) as cc:
        t0 = time.monotonic()
        while be.dir_refreshes == 0 and time.monotonic() - t0 < 5:
            time.sleep(0.01)
        assert be.dir_refreshes > 0          # directory rides the loop
        refresher = cc._refresher
        assert refresher is not None and refresher.is_alive()
    assert not refresher.is_alive()          # close() JOINED the thread
    assert cc._refresher is None
    cc.close()                               # idempotent
    # threads that were never started: close() is a no-op
    with CleanCacheClient(SpyBackend()) as cc2:
        pass
    assert cc2._refresher is None


def test_pool_server_stats_parity():
    from pmdfc_tpu.onesided import PassivePool
    from pmdfc_tpu.runtime.net import PoolServer, RemotePool

    pool = PassivePool(num_rows=64, page_words=W, mode="host")
    srv = PoolServer(pool).start()
    with srv:
        rp = RemotePool("127.0.0.1", srv.port, page_words=W,
                        keepalive_s=None)
        lo, hi = rp.grant(8)
        rows = np.arange(lo, lo + 4, dtype=np.int32)
        rp.write_rows(rows, _pages(_keys(4, seed=20)))
        got = rp.read_rows(rows)
        assert got.shape == (4, W)
        snap = rp.server_stats()
        # the pool's own counters cross the wire...
        assert snap["writes"] == 4 and snap["reads"] == 4
        assert snap["granted_rows"] == 8
        # ...and the registry gauges mirror them (teletop/teledump see
        # the passive node like any serving surface)
        g = (snap.get("telemetry") or {}).get("gauges") or {}
        pw = {k: v for k, v in g.items() if k.endswith(".pool_writes")}
        assert pw and all(v == 4 for v in pw.values())
        gr = {k: v for k, v in g.items()
              if k.endswith(".pool_granted_rows")}
        assert gr and all(v == 8 for v in gr.values())
        rp.close()


def test_replica_group_prefers_fastpath_over_hedging():
    from pmdfc_tpu.client.replica import ReplicaGroup
    from pmdfc_tpu.config import ReplicaConfig

    srv1, _ = _server()
    srv2, _ = _server()
    with srv1, srv2:
        eps = [_dial(s, directory=True) for s in (srv1, srv2)]
        grp = ReplicaGroup(
            eps, page_words=W,
            cfg=ReplicaConfig(n_replicas=2, rf=2, hedge_ms=5000.0,
                              repair_interval_s=0.0))
        keys = _keys(64, seed=21)
        pages = _pages(keys)
        grp.put(keys, pages)
        assert grp.dir_refresh() == 2
        out, found = grp.get(keys)
        assert found.all() and np.array_equal(out, pages)
        fp = sum(_fp_counters(s)[1] for s in (srv1, srv2))
        assert fp > 0                        # primaries answered fast
        st = grp.stats()["group"]
        assert st["hedges_fired"] == 0       # nothing ever hedged
        grp.close()


def test_fastpath_under_concurrent_writers():
    """8 reader threads on the fast path while a writer re-puts and
    invalidates hot keys: every served page is bit-exact against the
    writer's journal (monotonic versions make torn serves detectable)."""
    srv, kv = _server()
    with srv:
        keys = _keys(64, seed=22)
        base = _pages(keys)
        wr = _dial(srv)
        wr.put(keys, base)
        version = np.zeros(len(keys), np.uint32)
        vlock = threading.Lock()
        stop = threading.Event()
        errs: list = []

        def writer():
            rng = np.random.default_rng(23)
            while not stop.is_set():
                i = int(rng.integers(0, len(keys)))
                with vlock:
                    v = int(version[i]) + 1  # claimed, not yet visible
                wr.put(keys[i:i + 1], base[i:i + 1] + np.uint32(v))
                with vlock:
                    version[i] = v           # completed-put journal
                time.sleep(0.001)

        def reader(seed):
            be = _dial(srv, directory=True)
            be.dir_refresh()
            rng = np.random.default_rng(seed)
            try:
                for _ in range(40):
                    idx = rng.integers(0, len(keys), 8)
                    with vlock:
                        vmin = version[idx].copy()
                    out, found = be.get(keys[idx])
                    with vlock:
                        vmax = version[idx].copy()
                    served_v = out[:, 0] - base[idx][:, 0]
                    # a put COMPLETED before the read must be visible
                    # (>= vmin); at most one claimed put can be in
                    # flight past the vmax snapshot (single writer)
                    okl = (~found) | ((served_v >= vmin)
                                      & (served_v <= vmax + 1))
                    if not okl.all():
                        raise AssertionError(
                            f"stale/wrong bytes: v={served_v[~okl]} "
                            f"window=[{vmin[~okl]},{vmax[~okl]}]")
                    if rng.random() < 0.2:
                        be.dir_refresh()
            except Exception as e:  # noqa: BLE001
                errs.append(e)
            finally:
                be.close()

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        rs = [threading.Thread(target=reader, args=(100 + i,))
              for i in range(8)]
        for t in rs:
            t.start()
        for t in rs:
            t.join()
        stop.set()
        wt.join(timeout=5)
        assert not errs, errs[0]
        assert _fp_counters(srv)[0] > 0
        wr.close()
