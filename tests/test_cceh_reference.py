"""The device CCEH (`models/cceh.py`) against a plain one-key-at-a-time
reference (`tests/cceh_reference.py`), and the index's work counters on the
served path.

- The reference's hash is the program's (`utils.hashing.hash_u64`).
- At batch size 1 the device index equals the reference exactly: every
  evicted key, every count of splits and rounds, and at the end the whole
  table, the local depths and the directory.
- At served batch sizes the contract holds: every key whose last PUT was
  neither evicted nor dropped reads back its last value, every reported
  victim reads as a miss, and the directory invariants hold.
- Through `NetServer` -> `PlaneBackend` with the fused GET forced on
  (interpret mode), every answer equals the host-dict `LocalBackend`'s or
  is a miss the store's evictions account for; the insert's work reaches
  `NetServer.stats` as `index_*` counters.
- Those counters count with the tracing tier off, and a family that
  places in one pass reports no split and one round a call.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from cceh_reference import WINDOW_SEED, PlainCCEH, murmur3_u64
from test_cceh import _check_directory_invariants

from pmdfc_tpu.config import (BloomConfig, IndexConfig, IndexKind, KVConfig,
                              MeshConfig, NetConfig)
from pmdfc_tpu.models.base import get_index_ops
from pmdfc_tpu.utils.hashing import hash_u64

OPS = get_index_ops(IndexKind.CCEH)
INVALID = 0xFFFFFFFF
PW = 16  # page words on the served path: pow2, inside the fused set


def _icfg(capacity=1 << 9, segment_slots=128, headroom=2):
    return IndexConfig(kind=IndexKind.CCEH, capacity=capacity,
                       segment_slots=segment_slots, split_headroom=headroom)


def _universe(n, seed):
    flat = np.random.default_rng(seed).choice(1 << 30, size=n, replace=False)
    return np.stack([flat >> 12, flat & 0xFFF], -1).astype(np.uint32)


def _evicted(res) -> list:
    ev = np.asarray(res.evicted)
    return [tuple(int(x) for x in k) for k in ev
            if not (k[0] == INVALID and k[1] == INVALID)]


def test_reference_hash_is_the_programs():
    keys = _universe(256, 1)
    for seed in (0, WINDOW_SEED):
        dev = np.asarray(hash_u64(jnp.asarray(keys[:, 0]),
                                  jnp.asarray(keys[:, 1]), seed=seed))
        ref = [murmur3_u64(int(h), int(lo), seed) for h, lo in keys]
        assert dev.tolist() == ref


@pytest.mark.parametrize("seed", [3, 17])
def test_batch_of_one_equals_reference(seed):
    """2,000 single-key verbs (fresh PUTs, updates, deletes) over a store
    of 2,048 slots that starts at 512: splits, doubling to depth 4, then
    eviction. Every step and the final layout agree exactly."""
    c = _icfg()
    st = OPS.init(c)
    ref = PlainCCEH(c.capacity, c.segment_slots, c.probe_window,
                    c.split_headroom)
    rng = np.random.default_rng(seed)
    universe = _universe(2600, seed)
    seen: list[int] = []
    evictions = 0
    for step in range(2000):
        r = rng.random()
        if seen and r < 0.05:
            i = seen[int(rng.integers(len(seen)))]
            key = (int(universe[i, 0]), int(universe[i, 1]))
            st, hit, _ = OPS.delete_batch(st, jnp.asarray(universe[i:i + 1]))
            assert bool(hit[0]) == ref.delete(key), step
            continue
        i = seen[int(rng.integers(len(seen)))] if seen and r < 0.25 \
            else len(seen)
        if i == len(seen):
            seen.append(i)
        key = (int(universe[i, 0]), int(universe[i, 1]))
        value = (0, step)
        st, res = OPS.insert_batch(st, jnp.asarray(universe[i:i + 1]),
                                   jnp.asarray([value], jnp.uint32))
        victim, splits, rounds = ref.put(key, value)
        assert _evicted(res) == ([] if victim is None else [victim[0]]), step
        assert (int(res.splits), int(res.rounds)) == (splits, rounds), step
        assert not bool(res.dropped[0])
        evictions += victim is not None
    assert evictions > 0 and int(st.nseg) == 1 << ref.Gmax
    keys, vals = (np.asarray(x) for x in OPS.scan(st))
    rkeys, rvals = ref.table()
    assert [None if k[0] == INVALID and k[1] == INVALID else tuple(k.tolist())
            for k in keys] == rkeys
    got = [tuple(v.tolist()) for v, k in zip(vals, rkeys) if k is not None]
    assert got == [v for v in rvals if v is not None]
    assert np.asarray(st.ld).tolist() == ref.ld
    assert np.asarray(st.dirr).tolist() == ref.dir
    _check_directory_invariants(st)


@pytest.mark.parametrize("batch", [64, 512])
def test_served_batches_keep_the_contract(batch):
    """Batches with in-batch duplicates and re-PUTs, 3x the store's slots
    in all: what the index answers is the last acknowledged value of every
    key it did not report evicted or dropped, and nothing else."""
    c = _icfg()
    st = OPS.init(c)
    rng = np.random.default_rng(batch)
    universe = _universe(4096, batch + 1)
    last: dict = {}
    gone: set = set()
    splits = 0
    for step in range(3 * 2048 // batch):
        idx = rng.integers(0, len(universe), batch)
        keys = universe[idx]
        vals = np.stack([np.zeros(batch, np.uint32),
                         np.arange(batch, dtype=np.uint32)
                         + np.uint32(step * batch)], -1)
        st, res = OPS.insert_batch(st, jnp.asarray(keys), jnp.asarray(vals))
        splits += int(res.splits)
        assert 1 <= int(res.rounds) <= c.split_headroom + 2
        for k, v in zip(keys, vals):
            last[tuple(k.tolist())] = tuple(v.tolist())
        for k, drop in zip(keys, np.asarray(res.dropped)):
            if drop:
                last.pop(tuple(k.tolist()), None)
        for k in _evicted(res):
            assert last.pop(k, None) is not None, "evicted an unknown key"
            gone.add(k)
        gone.difference_update(last)
        _check_directory_invariants(st)
    assert splits == int(st.nseg) - c.capacity // c.segment_slots
    assert gone, "the store never filled"
    live = np.asarray(list(last), np.uint32)
    got = OPS.get_batch(st, jnp.asarray(live))
    assert bool(np.asarray(got.found).all())
    assert [tuple(v) for v in np.asarray(got.values).tolist()] == \
        list(last.values())
    dead = OPS.get_batch(st, jnp.asarray(np.asarray(list(gone), np.uint32)))
    assert not bool(np.asarray(dead.found).any())


def _kv_cfg(kind=IndexKind.CCEH, fused_get="on"):
    index = (_icfg() if kind == IndexKind.CCEH
             else IndexConfig(capacity=1 << 10))
    return KVConfig(index=index, bloom=BloomConfig(num_bits=1 << 14),
                    paged=True, page_words=PW, fused_get=fused_get)


def _pages(keys, gen):
    return ((keys[:, 0] * np.uint32(31) + keys[:, 1] + np.uint32(gen))
            [:, None] + np.arange(1, PW + 1, dtype=np.uint32)[None, :])


def _index_counters(stats) -> dict:
    return {k: v for k, v in dict(stats).items() if k.startswith("index_")}


def test_served_path_fused_matches_host_dict(monkeypatch):
    """Six rounds of 512-page PUTs (new keys and re-PUTs) then GETs of
    every key, from two connections, against the same verbs on a host
    dict that never drops: each hit is the dict's page, each miss is
    accounted by an eviction, and the index's work is on the counters."""
    from pmdfc_tpu.client.backends import LocalBackend
    from pmdfc_tpu.parallel.plane import make_serving_backend
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    monkeypatch.delenv("PMDFC_FUSED", raising=False)
    be = make_serving_backend(_kv_cfg(), MeshConfig(n_shards=1))
    assert be.skv._fused_on() is True
    ref = LocalBackend(page_words=PW, capacity=1 << 16)
    universe = _universe(3072, 5)
    rng = np.random.default_rng(5)
    srv = NetServer(lambda: be, net=NetConfig()).start()
    hits = misses = 0
    try:
        with TcpBackend("127.0.0.1", srv.port, page_words=PW) as c1, \
                TcpBackend("127.0.0.1", srv.port, page_words=PW) as c2:
            for rnd in range(6):
                keys = universe[rng.integers(0, 512 * (rnd + 1), 512)]
                pages = _pages(keys, rnd)
                (c1 if rnd % 2 else c2).put(keys, pages)
                ref.put(keys, pages)
            for lo in range(0, len(universe), 512):
                keys = universe[lo:lo + 512]
                out, found = c1.get(keys)
                want, known = ref.get(keys)
                assert not (found & ~known).any()
                np.testing.assert_array_equal(out[found], want[found])
                hits += int(found.sum())
                misses += int((known & ~found).sum())
    finally:
        srv.stop()
    st = be.stats()
    assert hits > 0 and misses <= st["evictions"]
    assert st["misses"] == sum(st[k] for k in st if k.startswith("miss_"))
    ctr = _index_counters(srv.stats)
    assert ctr["index_evictions"] == st["evictions"] > 0
    assert ctr["index_splits"] == int(be.skv.state.index.nseg[0]) - 4 > 0
    assert ctr["index_insert_calls"] == 6
    assert 6 < ctr["index_insert_rounds"] <= 6 * 4


def test_index_counters_count_with_telemetry_off(monkeypatch):
    """A linear plane: inserts that evict, served with the tracing tier
    off. The counters grow all the same, with no split and one round a
    call; a flush with no PUT adds nothing."""
    from pmdfc_tpu.parallel.plane import make_serving_backend
    from pmdfc_tpu.runtime import telemetry as tele
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    be = make_serving_backend(_kv_cfg(IndexKind.LINEAR, "off"),
                              MeshConfig(n_shards=1))
    srv = NetServer(lambda: be, net=NetConfig()).start()
    tele.set_enabled(False)
    try:
        with TcpBackend("127.0.0.1", srv.port, page_words=PW) as c:
            universe = _universe(2048, 9)
            for lo in range(0, 2048, 256):
                c.put(universe[lo:lo + 256], _pages(universe[lo:lo + 256], 0))
            before = _index_counters(srv.stats)
            c.get(universe[:256])
            assert _index_counters(srv.stats) == before
    finally:
        tele.set_enabled(True)
        srv.stop()
    assert before == {"index_splits": 0, "index_insert_rounds": 8,
                      "index_evictions": be.stats()["evictions"],
                      "index_insert_calls": 8}
    assert before["index_evictions"] > 0


def test_insert_handle_leaves_no_cycle():
    """A fetched insert carries its index work and leaves nothing for
    the cyclic collector: its routed buffers and device outputs die with
    the handle, not at the next collection."""
    import gc

    from pmdfc_tpu.parallel.plane import make_serving_backend

    be = make_serving_backend(_kv_cfg(), MeshConfig(n_shards=1))
    keys = _universe(512, 11)
    for rnd in range(2):  # the first call traces and compiles
        gc.collect()
        gc.disable()
        try:
            h = be.skv.plane_insert(keys, _pages(keys, rnd))
            h.fetch()
            assert h.work is not None and h.work[3] == 1
            del h
            assert gc.collect() == 0 or rnd == 0
        finally:
            gc.enable()
