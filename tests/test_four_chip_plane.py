"""The four-chip partitioned deployment (`benchmark/configs/
linear-32g-4chip.json`) on the CPU's forced devices, and the routing
counters and readers its cell reads.

1. **The served plane.** `NetServer` -> `PlaneBackend` -> `ShardedKV` over
   4 shards (linear, 16-slot clusters, a small capacity) against a plain
   host dict on a seeded mix of PUTs and GETs: every hit is the key's last
   acknowledged page, bit for bit; every miss is of a key never written or
   of one the index evicted; no acknowledged PUT is lost otherwise.
2. **A tiny four-chip cell.** The configuration file cut to a tiny
   capacity, run through `benchmark.run` (the chip check skipped): correct
   with the six counts at 0, and the `page_flip` control not correct.
   A served PUT with more new keys for one cluster than its 16 slots, and
   a tiny cell whose set-up fill does the same: each key left out is
   counted and sketched as an eviction, none as a drop.
3. **The route counters.** On a known skewed batch every launched plane
   route, one shard or four, adds its real keys (`route_keys`), its lanes
   (`route_lanes`, n x wl) and its fullest shard's keys
   (`route_shard_max_keys`) to the bound flush scope, with the tracing
   tier off too; its `route` stage carries `wl` and the per-shard counts
   when traced, as `tools/tracetool.py` exports them.
4. **The readers** (`shard_pad_share`, `shard_key_imbalance`,
   `device_busy_imbalance`) on synthetic contexts, None where their input
   is missing.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from pmdfc_tpu.config import (BloomConfig, IndexConfig, KVConfig, MeshConfig,
                              NetConfig, TelemetryConfig)
from pmdfc_tpu.parallel import partitioning as pt
from pmdfc_tpu.runtime import telemetry as tele

pytestmark = pytest.mark.mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "linear-32g-4chip.json")
W = 16


def _cfg(capacity):
    return KVConfig(index=IndexConfig(capacity=capacity, cluster_slots=16),
                    bloom=BloomConfig(num_bits=1 << 15),
                    paged=True, page_words=W)


def _universe(n, seed):
    flat = np.random.default_rng(seed).choice(1 << 20, size=n,
                                              replace=False)
    return np.stack([flat >> 10, flat & 0x3FF], -1).astype(np.uint32)


def _pages(keys, gen):
    base = keys[:, 0] * np.uint32(2654435761) ^ keys[:, 1] * np.uint32(31) \
        ^ np.uint32(int(gen) * 0x9E3779B9 & 0xFFFFFFFF)
    return base[:, None] + np.arange(W, dtype=np.uint32)[None, :]


@pytest.fixture()
def fresh_registry():
    reg = tele.configure(TelemetryConfig(ring_capacity=1 << 14))
    yield reg
    tele.configure()


# -- 1. the served plane against a host dict ---------------------------------

def test_four_shard_served_plane_keeps_the_clean_cache_contract():
    from pmdfc_tpu.parallel.plane import make_serving_backend
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    # 4 shards x 256 slots (16 clusters of 16 a shard); 900 keys load
    # them to ~0.88, so clusters overflow and the index evicts
    be = make_serving_backend(_cfg(1 << 8), MeshConfig(n_shards=4))
    assert be.skv.n_shards == 4
    universe = _universe(900, seed=30)
    owners = pt.shard_of_np(universe, 4)
    assert set(owners.tolist()) == {0, 1, 2, 3}
    ref: dict[tuple, int] = {}           # key -> last acknowledged gen
    gens = np.zeros(len(universe), np.int64)
    rng = np.random.default_rng(31)
    s0 = be.stats()
    hits = written_misses = cold = 0
    lost = set()                          # (key, gen) a GET missed
    srv = NetServer(lambda: be, net=NetConfig()).start()
    try:
        with TcpBackend("127.0.0.1", srv.port, page_words=W) as c:
            for _ in range(40):
                sel = rng.choice(len(universe), size=int(rng.integers(1, 97)),
                                 replace=False)
                keys = universe[sel]
                if rng.random() < 0.5:
                    gens[sel] += 1
                    pages = np.concatenate([_pages(keys[i:i + 1], g)
                                            for i, g in enumerate(gens[sel])])
                    c.put(keys, pages)
                    for i in sel:
                        ref[tuple(universe[i])] = int(gens[i])
                    continue
                out, found = c.get(keys)
                for k, i in enumerate(sel):
                    g = ref.get(tuple(universe[i]))
                    if found[k]:
                        assert g is not None, "a hit for a key never written"
                        assert out[k].tobytes() == _pages(
                            universe[i:i + 1], g)[0].tobytes()
                        hits += 1
                    elif g is None:
                        cold += 1
                    else:
                        written_misses += 1
                        lost.add((tuple(universe[i]), g))
            # read every written key back once more
            keys = np.array(list(ref), np.uint32)
            out, found = c.get(keys)
            want = np.concatenate([_pages(keys[i:i + 1], g)
                                   for i, g in enumerate(ref.values())])
            assert (out[found] == want[found]).all()
            hits += int(found.sum())
            written_misses += int((~found).sum())
            lost |= {(tuple(k), g) for k, g, f in
                     zip(keys, ref.values(), found) if not f}
    finally:
        srv.stop()
    s1 = be.stats()
    d = {k: s1[k] - s0.get(k, 0) for k in
         ("gets", "hits", "misses", "miss_cold", "miss_evicted",
          "evictions", "puts", "drops")}
    assert hits > 0 and cold > 0
    assert d["hits"] == hits and d["misses"] == written_misses + cold
    assert d["drops"] == 0
    # every miss has one cause; a written key missed only by eviction, and
    # each acknowledged write missed took an eviction of its own
    assert d["misses"] == d["miss_cold"] + d["miss_evicted"]
    assert written_misses <= d["miss_evicted"]
    assert 0 < len(lost) <= d["evictions"]


def test_served_put_overflowing_one_cluster_counts_each_lost_page_as_an_eviction():
    """One PUT of 40 new keys for one cluster of one shard (16 slots),
    through the served plane: the 16 placed read back bit-exact, and each
    of the 24 left out misses as `miss_evicted`, counted in `evictions`,
    none in `drops` -- what a serial insert taking them first gives."""
    from pmdfc_tpu.models.linear import _cluster_of
    from pmdfc_tpu.parallel.plane import make_serving_backend
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    be = make_serving_backend(_cfg(1 << 8), MeshConfig(n_shards=4))
    pool = _universe(1 << 14, seed=33)
    mine = (pt.shard_of_np(pool, 4) == 2) & (
        np.asarray(_cluster_of(pool, (1 << 8) // 16)) == 5)
    keys = pool[mine][:40]
    assert len(keys) == 40
    pages = _pages(keys, 1)
    s0 = be.stats()
    srv = NetServer(lambda: be, net=NetConfig()).start()
    try:
        with TcpBackend("127.0.0.1", srv.port, page_words=W) as c:
            c.put(keys, pages)
            out, found = c.get(keys)
    finally:
        srv.stop()
    s1 = be.stats()
    d = {k: s1[k] - s0.get(k, 0) for k in
         ("puts", "hits", "misses", "miss_cold", "miss_evicted",
          "evictions", "drops")}
    assert int(found.sum()) == 16
    assert (out[found] == pages[found]).all()
    assert d == {"puts": 40, "hits": 16, "misses": 24, "miss_cold": 0,
                 "miss_evicted": 24, "evictions": 24, "drops": 0}


# -- 2. a tiny four-chip cell through the harness ---------------------------

def _tiny_root(root, rows):
    """A checkout holding the four-chip configuration cut to `rows` pool
    rows of 16 words a shard, the cell's traffic file cut to 1 host x 8
    jobs x iodepth 8, and the benchmark's metric files."""
    bench = root / "benchmark"
    shutil.copytree(os.path.join(BENCH, "metrics"), bench / "metrics")
    (bench / "traffic").mkdir()
    (bench / "configs").mkdir()
    with open(os.path.join(BENCH, "traffic", "seqread-128k.json")) as f:
        mix = json.load(f)
    mix.update(hosts=1, jobs_per_host=8, iodepth=8, client_cache_pages=4096,
               ramp_s=0.5)
    (bench / "traffic" / "tiny-seq.json").write_text(json.dumps(mix))
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["n_shards"] == cfg["mesh"]["n_shards"] == 4
    cfg["capacity"] = cfg["kv"]["index"]["capacity"] = rows
    cfg["kv"]["page_words"] = W
    cfg["kv"]["bloom"]["num_bits"] = 1 << 16
    (bench / "configs" / "tiny4.json").write_text(json.dumps(cfg))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        full = json.load(f)
    cell = {"name": "tiny4.tiny-seq", "config": "tiny4",
            "traffic": "tiny-seq", "chips": 4, "why": "test"}
    layer = [m for m in full["per_layer"]
             if m["name"] in ("shard_pad_share", "shard_key_imbalance",
                              "verbs_per_flush")]
    for m in layer:
        m["workloads"] = [cell["name"]]
    (root / "BENCHMARK.json").write_text(json.dumps(
        {**full, "run_seconds": 2,
         "configs": [{"name": "tiny4", "source": "test",
                      "file": "benchmark/configs/tiny4.json",
                      "reduced": [], "why": "test"}],
         "workloads": [cell], "per_layer": layer}))
    return str(root)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """65,536 rows a shard: the set-up fill's 4,096-key chunks put ~0.9
    keys in a cluster, as the cell's 16,384-key chunks over 131,072
    clusters put ~0.1, and a 4,096-key fill width leaves
    `devfill.chunk_rows`' 1/16 margin ~4.8 standard deviations of the
    per-shard spread."""
    return _tiny_root(tmp_path_factory.mktemp("root4"), 1 << 16)


@pytest.fixture(scope="module")
def tiny_root_256(tmp_path_factory):
    """4,096 rows a shard, 256 clusters: the fill's one chunk puts ~12 keys
    in a cluster, so many clusters get more than their 16 slots."""
    return _tiny_root(tmp_path_factory.mktemp("root4c"), 1 << 12)


@pytest.mark.parametrize("fault", [None, "page_flip"])
def test_tiny_four_chip_cell(tiny_root, capsys, fault):
    from benchmark import run

    argv = ["--workload", "tiny4.tiny-seq", "--seed", str(2**31 + 7),
            "--seconds", "2", "--trace", "1" if fault is None else "0"]
    if fault:
        argv += ["--fault", fault]
    assert run.run(argv, root=tiny_root, allow_cpu=True) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    checks = res["checks"]
    if fault is None:
        assert res["correct"], checks
        assert all(v["value"] == 0 for v in checks.values()), checks
        assert len(checks) == 6
        assert res["readback"]["pages"] > 0 and res["readback"]["wrong"] == 0
        m = res["metrics"]
        assert 0 <= m["shard_pad_share"]["value"] < 100
        assert 1.0 <= m["shard_key_imbalance"]["value"] <= 4.0
        assert m["verbs_per_flush"]["value"] >= 1
    else:
        assert not res["correct"]
        assert checks["wrong_pages"]["value"] > 0


def test_tiny_four_chip_cell_whose_fill_overflows_clusters(
        tiny_root_256, capsys, monkeypatch):
    """A fill chunk with more new keys for a cluster than its slots: the
    keys left out are counted and sketched as evictions before the window
    opens (none as drops), so their misses are explained and the run is
    correct."""
    from benchmark import refmodel, run

    seen = {}
    judge = refmodel.judge

    def spy(clients, srv_before, srv_after, net_before, net_after):
        seen.update(srv_before)
        return judge(clients, srv_before, srv_after, net_before, net_after)

    monkeypatch.setattr(refmodel, "judge", spy)
    argv = ["--workload", "tiny4.tiny-seq", "--seed", str(2**31 + 7),
            "--seconds", "2", "--trace", "0"]
    assert run.run(argv, root=tiny_root_256, allow_cpu=True) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert seen["evictions"] > 0 and seen["drops"] == 0, seen
    assert res["correct"], res["checks"]
    assert all(v["value"] == 0 for v in res["checks"].values())


# -- 3. the route counters ----------------------------------------------------

def _skewed(counts, seed=40):
    """Keys whose owners on len(counts) shards are `counts` per shard,
    interleaved."""
    n = len(counts)
    pool = _universe(4096, seed)
    own = pt.shard_of_np(pool, n)
    picks = [pool[own == s][:c] for s, c in enumerate(counts)]
    keys = np.concatenate(picks)
    return keys[np.random.default_rng(seed).permutation(len(keys))]


ROUTE = ("route_keys", "route_lanes", "route_shard_max_keys")


@pytest.mark.parametrize("counts", [[56], [40, 10, 5, 1]])
def test_route_counters_equal_the_router(fresh_registry, counts):
    import jax

    from pmdfc_tpu.parallel.shard import ShardedKV, make_mesh

    n = len(counts)
    skv = ShardedKV(_cfg(1 << 10), mesh=make_mesh(np.array(jax.devices()[:n])))
    keys = _skewed(counts)
    rb = pt.ShardRouter(n, pad_floor=skv._router.pad_floor).build(keys)
    assert rb.counts.tolist() == counts
    want = {"route_keys": rb.b, "route_lanes": n * rb.wl,
            "route_shard_max_keys": max(counts)}
    sc = tele.scope("routectr")
    tele.bind_stages(sc)
    tele.set_enabled(False)   # plain counters: live with the tracing off
    try:
        skv.plane_insert(keys, _pages(keys, 1)).fetch()
        assert {k: sc[k] for k in ROUTE} == want
        g = skv.plane_get(keys).fetch()
        assert g.found.all()
        skv.plane_delete(keys[:0]).fetch()   # nothing routed, nothing counted
        skv.plane_delete(keys).fetch()
    finally:
        tele.set_enabled(True)
        tele.bind_stages(None)
    assert {k: sc[k] for k in ROUTE} == {k: 3 * v for k, v in want.items()}


def test_route_stage_carries_width_and_counts_when_traced(fresh_registry):
    import jax

    from pmdfc_tpu.parallel.shard import ShardedKV, make_mesh

    skv = ShardedKV(_cfg(1 << 10), mesh=make_mesh(np.array(jax.devices()[:4])))
    keys = _skewed([40, 10, 5, 1])
    sc = tele.scope("routespan")
    tele.bind_stages(sc)
    try:
        skv.plane_get(keys).fetch()
        tele.stage(None, "idle")
    finally:
        tele.bind_stages(None)
    spans = [r for r in tele.get().ring if r.get("op") == "pmdfc.get.route"]
    assert spans and spans[-1]["wl"] == 64
    assert spans[-1]["counts"] == [40, 10, 5, 1]
    launch = [r for r in tele.get().ring if r.get("op") == "pmdfc.get.launch"]
    assert launch and "wl" not in launch[-1]
    # the operator's read: a flight dump's spans exported for Perfetto
    from tools.tracetool import chrome_trace

    doc = chrome_trace([(0, r) for r in tele.get().ring])
    route = [e for e in doc["traceEvents"] if e["name"] == "pmdfc.get.route"]
    assert route[-1]["args"]["wl"] == 64
    assert route[-1]["args"]["counts"] == [40, 10, 5, 1]


# -- 4. the readers -----------------------------------------------------------

def _reader(name):
    from benchmark import run

    return run.load_reader(BENCH, name)


def _ctx(n0, n1, n_shards=4, tr=None):
    return {"net0": n0, "net1": n1, "cfg": {"n_shards": n_shards},
            "trace": tr, "window_s": 1.0}


def test_shard_pad_share_reader():
    read = _reader("shard_pad_share")
    n0 = {"route_keys": 100, "route_lanes": 200}
    assert read(_ctx(n0, {"route_keys": 700, "route_lanes": 1000})) == 25.0
    assert read(_ctx(n0, {"route_keys": 100, "route_lanes": 200})) is None
    assert read(_ctx({}, {"route_keys": 5})) is None
    assert read(_ctx({}, {})) is None


def test_shard_key_imbalance_reader():
    read = _reader("shard_key_imbalance")
    n1 = {"route_keys": 400, "route_shard_max_keys": 150}
    assert read(_ctx({}, n1)) == 1.5
    assert read(_ctx({}, n1, n_shards=1)) == 0.375
    assert read(_ctx(n1, n1)) is None
    assert read(_ctx({}, {"route_keys": 400})) is None


def test_device_busy_imbalance_reader():
    from benchmark.trace import DeviceTrace, Trace

    read = _reader("device_busy_imbalance")
    ms = 1_000_000
    devs = [DeviceTrace(f"/device:TPU:{i}",
                        [("op", 10 * ms, b * ms)], [])
            for i, b in enumerate((30, 10, 10, 10))]
    tr = Trace(devs, [], (0, 100 * ms))
    assert read(_ctx({}, {}, tr=tr)) == pytest.approx(30 / 15)
    assert read(_ctx({}, {})) is None
    assert read(_ctx({}, {}, tr=Trace([], [], (0, ms)))) is None
    idle = [DeviceTrace("/device:TPU:0", [("op", 200 * ms, ms)], [])]
    assert read(_ctx({}, {}, tr=Trace(idle, [], (0, 100 * ms)))) is None
