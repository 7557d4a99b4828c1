"""The plane router's held page buffer (`partitioning.ShardRouter`).

1. **Byte-identical routing.** For 1, 2 and 4 shards every `RoutedBatch`
   equals, byte for byte, the one the plain algorithm below builds into
   fresh arrays (keys, pages, lanes, counts, width): across sizes that
   grow, shrink across rungs and cross a rung edge, so a wider earlier
   batch's rows never leak into a narrower one's pads; at `b == 0`; and
   where the held buffer is busy, which gets an array of its own and
   leaves the held one as it was.
2. **Reuse on the plane.** Two inserts back to back through the reused
   buffer read back bit-exact; an unfetched insert makes the next one
   allocate, a fetched one lets it reuse (`route_buf_alloc` /
   `route_buf_reused` on the bound stage scope).
"""

from __future__ import annotations

import numpy as np
import pytest

from pmdfc_tpu.config import BloomConfig, IndexConfig, KVConfig
from pmdfc_tpu.parallel import partitioning as pt
from pmdfc_tpu.runtime import telemetry as tele
from pmdfc_tpu.utils.keys import INVALID_WORD

pytestmark = pytest.mark.mesh

W = 16


def _keys(n, seed):
    flat = np.random.default_rng(seed).choice(1 << 20, size=n,
                                              replace=False)
    return np.stack([flat >> 10, flat & 0x3FF], -1).astype(np.uint32)


def _pages(keys, salt=0):
    return ((keys[:, 0] * np.uint32(31) + keys[:, 1] + np.uint32(salt))
            [:, None] + np.arange(1, W + 1, dtype=np.uint32)[None, :])


def reference_build(n, pad_floor, keys, values=None):
    """The router as a plain function: owner hash, stable binning, per-
    shard pow2 pads, fresh arrays (INVALID key pads, zero page pads)."""
    keys = np.asarray(keys, np.uint32).reshape(-1, 2)
    b = len(keys)
    own = pt.shard_of_np(keys, n)
    order = np.argsort(own, kind="stable")
    counts = np.bincount(own, minlength=n).astype(np.int64)
    wl = pad_floor
    while wl < (int(counts.max()) if b else 0):
        wl <<= 1
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.empty(b, np.int64)
    pos[order] = own[order].astype(np.int64) * wl + (
        np.arange(b) - starts[own[order]])
    kp = np.full((n * wl, 2), INVALID_WORD, np.uint32)
    kp[pos] = keys
    vp = None
    if values is not None:
        vp = np.zeros((n * wl, values.shape[-1]), np.uint32)
        vp[pos] = values
    return kp, vp, pos, counts, wl


def _same(rb, ref):
    kp, vp, pos, counts, wl = ref
    assert rb.wl == wl and rb.b == len(pos)
    for got, want in ((rb.keys, kp), (rb.pos, pos), (rb.counts, counts)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    if vp is None:
        assert rb.values is None
    else:
        assert rb.values.dtype == vp.dtype and rb.values.shape == vp.shape
        assert rb.values.flags.c_contiguous
        assert rb.values.tobytes() == vp.tobytes()


# sizes that grow, shrink across rungs (a wide batch, then a narrow one
# whose pads the wide one had filled) and cross a rung edge (8 -> 9)
SIZES = (5, 8, 9, 40, 300, 17, 3, 64, 65, 1, 200, 0, 33)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_routed_batches_match_the_plain_algorithm(n):
    router = pt.ShardRouter(n, pad_floor=8)
    for i, b in enumerate(SIZES):
        keys = _keys(b, seed=100 * n + i)
        pages = _pages(keys, salt=i)
        rb = router.build(keys, pages)
        _same(rb, reference_build(n, 8, keys, pages))
        assert rb.held
        router.release(rb)
        # a keys-only route (GET, delete) takes no page buffer
        rk = router.build(keys)
        _same(rk, reference_build(n, 8, keys))
        assert not rk.held and not rk.fresh


@pytest.mark.parametrize("n", [1, 2, 4])
def test_held_buffer_is_reused_and_grown(n):
    router = pt.ShardRouter(n, pad_floor=8)
    first = router.build(_keys(300, 1), _pages(_keys(300, 1)))
    assert first.fresh and first.held
    router.release(first)
    again = router.build(_keys(20, 2), _pages(_keys(20, 2)))
    # a narrower batch: a prefix of the same memory, not a new array
    assert not again.fresh and again.held
    assert np.shares_memory(again.values, first.values)
    router.release(again)
    wider = router.build(_keys(900, 3), _pages(_keys(900, 3)))
    assert wider.fresh and wider.held   # grown once for the wider rung
    router.release(wider)
    # release is idempotent: a second call frees nothing it does not hold
    router.release(wider)
    held = router.build(_keys(10, 4), _pages(_keys(10, 4)))
    router.release(wider)
    assert router._busy and held.held


@pytest.mark.parametrize("n", [1, 2, 4])
def test_busy_buffer_gets_an_array_of_its_own(n):
    router = pt.ShardRouter(n, pad_floor=8)
    k1 = _keys(120, 5)
    rb1 = router.build(k1, _pages(k1))
    snap = rb1.values.copy()
    k2 = _keys(60, 6)
    rb2 = router.build(k2, _pages(k2, salt=9))   # rb1 not released
    assert rb2.fresh and not rb2.held
    assert not np.shares_memory(rb2.values, rb1.values)
    _same(rb2, reference_build(n, 8, k2, _pages(k2, salt=9)))
    # the held buffer is as rb1 left it
    assert rb1.values.tobytes() == snap.tobytes()
    _same(rb1, reference_build(n, 8, k1, _pages(k1)))
    # releasing the fresh batch frees nothing; releasing rb1 does
    router.release(rb2)
    assert router._busy
    router.release(rb1)
    rb3 = router.build(k2, _pages(k2))
    assert rb3.held and not rb3.fresh


def test_empty_batch_matches_and_pads_with_zeros():
    for n in (1, 2, 4):
        router = pt.ShardRouter(n, pad_floor=8)
        wide = _keys(64, 7)
        router.release(router.build(wide, _pages(wide)))
        keys = np.zeros((0, 2), np.uint32)
        rb = router.build(keys, np.zeros((0, W), np.uint32))
        _same(rb, reference_build(n, 8, keys, np.zeros((0, W), np.uint32)))
        assert not rb.values.any()
        router.release(rb)


def _stage_counts(sc):
    return sc.get("route_buf_reused", 0), sc.get("route_buf_alloc", 0)


def test_plane_inserts_reuse_the_buffer_and_read_back_exact():
    import jax

    from pmdfc_tpu.parallel.shard import ShardedKV, make_mesh

    cfg = KVConfig(index=IndexConfig(capacity=1 << 10),
                   bloom=BloomConfig(num_bits=1 << 14),
                   paged=True, page_words=W)
    skv = ShardedKV(cfg, mesh=make_mesh(np.array(jax.devices()[:1])))
    ka, kb = _keys(96, 21), _keys(40, 22)
    sc = tele.scope("routebuftest")
    tele.bind_stages(sc)
    try:
        skv.plane_insert(ka, _pages(ka, 1)).fetch()   # grows the buffer
        assert _stage_counts(sc) == (0, 1)
        skv.plane_insert(kb, _pages(kb, 2)).fetch()   # same rung: reused
        assert _stage_counts(sc) == (1, 1)
        for keys, salt in ((ka, 1), (kb, 2)):
            g = skv.plane_get(keys).fetch()
            assert g.found.all()
            assert g.dense().tobytes() == _pages(keys, salt).tobytes()
        # a launch not yet fetched holds the buffer: the next allocates
        kc, kd = _keys(50, 23), _keys(30, 24)
        h = skv.plane_insert(kc, _pages(kc, 3))
        assert _stage_counts(sc) == (2, 1)
        h2 = skv.plane_insert(kd, _pages(kd, 4))
        assert _stage_counts(sc) == (2, 2)
        h.fetch()
        h2.fetch()
        # both fetched: the next insert reuses again
        skv.plane_insert(ka, _pages(ka, 5)).fetch()
        assert _stage_counts(sc) == (3, 2)
        for keys, salt in ((kc, 3), (kd, 4), (ka, 5), (kb, 2)):
            g = skv.plane_get(keys).fetch()
            assert g.found.all()
            assert g.dense().tobytes() == _pages(keys, salt).tobytes()
    finally:
        tele.bind_stages(None)
