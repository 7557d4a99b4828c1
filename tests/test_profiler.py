"""Fetch-time attribution suite — profiler seams, lanes, wire verb, tooling.

Covers `runtime/profiler.py` end to end:

- the timed-fetch seam: `profiler.fetch` splits the launch gap from the
  blocking fetch, feeds per-program `fetch_us` / `launch_gap_us`
  histograms and the phase x program x shard table.
- per-shard lane reconciliation: driving the 4-shard coalesced plane,
  the profiler's `shard_ops` lanes equal the mesh scope's
  `shard{i}_ops` counters EXACTLY (both split on the same routed-op
  counts vector, by construction).
- the windowed `shard_imbalance` gauge under seeded skew: max/mean in
  [1, n_shards].
- `MSG_PROFILE` negotiation: HOLASI-acked captures land under the
  flight recorder's dump dir with cooldown; an old peer (no ack)
  degrades `server_profile` to None without touching the wire. A
  capture runs without the Python tracer and holds the flush loop's
  `pmdfc.*` stage events.
- `tools/proftool.py`: breakdown table schema + reconciliation column,
  Perfetto export rehomes fetch spans onto per-program lanes.
- kill-switch conformance: with `PMDFC_PROF` off nothing attaches,
  snapshots stay `pmdfc-telemetry-v2` with no `profile` key, and every
  seam is a passthrough.
"""

import json
import time

import numpy as np
import pytest

from pmdfc_tpu.config import (BloomConfig, IndexConfig, KVConfig,
                              TelemetryConfig)
from pmdfc_tpu.runtime import profiler as prof_mod
from pmdfc_tpu.runtime import telemetry as tele

pytestmark = pytest.mark.prof

W = 16


def _cfg(capacity=1 << 10):
    return KVConfig(index=IndexConfig(capacity=capacity),
                    bloom=BloomConfig(num_bits=1 << 15),
                    paged=True, page_words=W)


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


@pytest.fixture()
def fresh_registry(tmp_path):
    reg = tele.configure(TelemetryConfig(ring_capacity=1 << 15,
                                         dump_dir=str(tmp_path),
                                         dump_min_interval_s=0.0))
    yield reg
    tele.configure()


# --- 1. the timed-fetch seam ----------------------------------------------


def test_fetch_splits_fetch_and_launch_gap(fresh_registry):
    p = prof_mod.install()
    t_launch = time.monotonic_ns()

    def thunk():
        time.sleep(0.002)
        return 41

    assert prof_mod.fetch("kv.get", "get", thunk, n_ops=8,
                          t_launch_ns=t_launch, ring=True) == 41
    snap = p.snapshot()
    assert snap["schema"] == "pmdfc-prof-v2"
    assert snap["launches"] == 1
    assert "cost" not in snap
    (row,) = snap["rows"]
    assert (row["phase"], row["program"], row["shard"]) == ("get", "kv.get", -1)
    assert row["ops"] == 8
    assert row["fetch_us"] >= 2000  # the 2ms sleep is blocked-fetch time
    hists = tele.get().snapshot()["histograms"]
    assert hists["prof.kv.get.fetch_us"]["count"] == 1
    # the launch stamp preceded the fetch: a real launch gap recorded
    assert hists["prof.kv.get.launch_gap_us"]["count"] == 1
    assert not any("device_us" in k or "dispatch_us" in k for k in hists)
    # fetch time is monotone with the blocked window
    def longer():
        time.sleep(0.004)
    prof_mod.fetch("kv.get", "get", longer, n_ops=8)
    h = tele.get().snapshot()["histograms"]["prof.kv.get.fetch_us"]
    assert h["max"] >= 4000 and h["count"] == 2
    # the registry snapshot carries the v3 profile block when attached
    doc = tele.get().snapshot()
    assert doc["schema"] == "pmdfc-telemetry-v3"
    assert doc["profile"]["launches"] == 2
    # the ring=True fetch also rang a device span for the timeline
    dev = [r for r in tele.get().ring_tail()
           if r.get("src") == "prof" and r.get("op") == "device"]
    assert len(dev) == 1 and dev[0]["program"] == "kv.get"


def test_kv_sync_verbs_attribute_through_the_seam(fresh_registry):
    from pmdfc_tpu.kv import KV

    prof_mod.install()
    kv = KV(_cfg())
    keys = _keys(64)
    kv.insert(keys, _pages(keys))
    out, found = kv.get(keys)
    assert found.all()
    snap = tele.get().snapshot()["profile"]
    by_prog = {(r["program"], r["phase"]) for r in snap["rows"]}
    assert ("kv.insert", "put") in by_prog
    assert ("kv.get", "get") in by_prog
    assert snap["launches"] >= 2


# --- 2. per-shard lanes reconcile with the mesh counters ------------------


def test_shard_lanes_reconcile_with_mesh_ops(fresh_registry):
    from pmdfc_tpu.parallel.plane import PlaneBackend
    from pmdfc_tpu.parallel.shard import ShardedKV

    p = prof_mod.install()
    skv = ShardedKV(_cfg(), mesh=_mesh(4))
    be = PlaneBackend(skv)
    keys = _keys(400, seed=7)
    be.put(keys, _pages(keys))
    out, found = be.get(keys)
    assert found.all()
    snap = p.snapshot()
    assert snap["n_shards"] == 4
    mesh_ops = [int(be._tele.get(f"shard{i}_ops", 0)) for i in range(4)]
    # EXACT: note_launch splits on the same routed-counts vector that
    # feeds the mesh counters — the acceptance reconciliation pin
    assert snap["shard_ops"] == mesh_ops, (snap["shard_ops"], mesh_ops)
    assert sum(mesh_ops) == 800  # 400 puts + 400 gets, fully routed
    assert all(us > 0 for us in snap["shard_fetch_us"])
    # the table's per-shard rows roll up to the same ops
    per_shard = [0] * 4
    for r in snap["rows"]:
        if r["shard"] >= 0:
            per_shard[r["shard"]] += r["ops"]
    assert per_shard == mesh_ops


# --- 3. shard-imbalance gauge under seeded skew ---------------------------


def test_imbalance_gauge_tracks_skew_within_range(fresh_registry):
    p = prof_mod.install()
    skew = np.array([30, 2, 2, 2])
    for _ in range(p.config.imbalance_window):
        p.note_launch("plane.get", "get", 100.0, counts=skew, n_shards=4)
    snap = p.snapshot()
    # max/mean of the window lanes: 30 / (36/4) = 3.333..
    assert snap["imbalance"] == pytest.approx(30 / 9, abs=1e-3)
    assert 1.0 <= snap["imbalance"] <= 4.0
    g = tele.get().snapshot()["gauges"]["prof.shard_imbalance"]
    assert g == pytest.approx(snap["imbalance"], abs=1e-3)
    # balanced traffic pulls the next window back toward 1
    for _ in range(p.config.imbalance_window):
        p.note_launch("plane.get", "get", 100.0,
                      counts=np.array([9, 9, 9, 9]), n_shards=4)
    assert p.snapshot()["imbalance"] == pytest.approx(1.0, abs=1e-3)


# --- 4. MSG_PROFILE negotiation + old-peer fallback -----------------------
# The two wire drills spin real NetServers (~20 s together on the 1-cpu
# harness host), so they also carry `slow` and ride the agenda's
# tier1_overflow step per the PR 13/16 tier-1 budget notes — tier-1
# keeps the sub-5 s attribution/reconciliation/conformance drills.


@pytest.mark.slow
def test_msg_profile_capture_cooldown_and_old_peer(
        fresh_registry, tmp_path, monkeypatch):
    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.kv import KV
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    kv = KV(_cfg())
    shared = DirectBackend(kv)

    # old peer first: the server predates the verb (PMDFC_PROF unset ->
    # off), a prof-wanting client gets no HOLASI ack and degrades to
    # None without a wire exchange
    monkeypatch.delenv("PMDFC_PROF", raising=False)
    old_srv = NetServer(lambda: shared).start()
    with old_srv:
        monkeypatch.setenv("PMDFC_PROF", "on")
        with TcpBackend("127.0.0.1", old_srv.port, page_words=W) as be:
            assert be.prof is False
            assert be.server_profile(50) is None

    # profiler-speaking server: capture lands under the dump dir
    monkeypatch.setenv("PMDFC_PROF", "on")
    prof_mod.install()
    srv = NetServer(lambda: shared).start()
    with srv, TcpBackend("127.0.0.1", srv.port, page_words=W) as be:
        assert be.prof is True
        res = be.server_profile(50)
        assert res is not None
        assert res["duration_ms"] == 50
        assert res["path"].startswith(str(tmp_path))
        # cooldown: an immediate second request is refused (NOTEXIST)
        assert be.server_profile(50) is None


@pytest.mark.slow
def test_msg_profile_refused_without_dump_dir(monkeypatch):
    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.kv import KV
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    monkeypatch.setenv("PMDFC_PROF", "on")
    tele.configure(TelemetryConfig(ring_capacity=1 << 12))  # no dump_dir
    try:
        prof_mod.install()
        shared = DirectBackend(KV(_cfg()))
        srv = NetServer(lambda: shared).start()
        with srv, TcpBackend("127.0.0.1", srv.port, page_words=W) as be:
            assert be.prof is True  # verb negotiated fine
            assert be.server_profile(50) is None  # but capture refused
    finally:
        tele.configure()


def test_capture_holds_flush_stages_without_python_tracer(fresh_registry):
    """A capture taken across one flush holds the flush loop's stage
    annotations (`pmdfc.<phase>.<stage>`, `pmdfc.flush.idle`) and no
    Python-tracer events (named `$<file>:<line> <function>`)."""
    import glob

    import jax

    from pmdfc_tpu.config import MeshConfig, NetConfig
    from pmdfc_tpu.parallel.plane import make_serving_backend
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    p = prof_mod.install()
    be = make_serving_backend(_cfg(), MeshConfig(n_shards=1))
    keys = _keys(64, seed=5)
    srv = NetServer(lambda: be, net=NetConfig()).start()
    with srv, TcpBackend("127.0.0.1", srv.port, page_words=W) as c:
        c.put(keys, _pages(keys))  # compile outside the capture
        c.get(keys)
        res = p.start_capture(400)
        assert res is not None
        c.put(keys, _pages(keys))
        _, found = c.get(keys)
        assert found.all()
        deadline = time.monotonic() + 20
        while p._trace_active and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not p._trace_active
    (pb,) = glob.glob(f"{res['path']}/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(pb).planes
             for line in plane.lines for e in line.events}
    for ph in ("put", "get"):
        for st in ("prep", "route", "launch", "fetch", "reply"):
            assert f"pmdfc.{ph}.{st}" in names, (ph, st)
    assert {"pmdfc.flush.idle", "pmdfc.flush.dwell"} <= names
    assert not any(n.startswith("$") for n in names)


# --- 5. proftool: breakdown table + Perfetto lanes ------------------------


def test_proftool_breakdown_and_perfetto(fresh_registry, tmp_path):
    import tools.proftool as proftool
    from pmdfc_tpu.parallel.plane import PlaneBackend
    from pmdfc_tpu.parallel.shard import ShardedKV

    prof_mod.install()
    skv = ShardedKV(_cfg(), mesh=_mesh(4))
    be = PlaneBackend(skv)
    keys = _keys(256, seed=3)
    be.put(keys, _pages(keys))
    be.get(keys)
    # plane launches skip the ring (their shard_program spans cover the
    # window); a sync-verb fetch rings the device span the timeline sees
    prof_mod.fetch("kv.get", "get", lambda: time.sleep(0.001), n_ops=4,
                   ring=True)
    dump = {"schema": "pmdfc-flight-v2", "rung": "manual", "detail": {},
            "ts_unix": 0.0, "telemetry": tele.get().snapshot(),
            "records": tele.get().ring_tail()}
    path = tmp_path / "flight.json"
    path.write_text(json.dumps(dump))

    agg = proftool._merge(proftool.load_docs([str(path)]))
    table = proftool.breakdown(agg)
    assert table["schema"] == "pmdfc-proftable-v2"
    assert table["launches"] > 0 and table["rows"]
    # every shard lane reconciles against the dump's mesh counters
    assert len(table["shards"]) == 4
    assert all(s["match"] == "yes" for s in table["shards"]), table["shards"]
    assert abs(sum(r["share"] for r in table["rows"]) - 1.0) < 0.01
    # the Perfetto export rehomes device spans to per-program lanes
    trace = proftool.device_lane_trace([str(path)])
    dev = [e for e in trace["traceEvents"]
           if str(e.get("tid", "")).startswith("device:")]
    assert dev and all(e["ph"] == "X" for e in dev)
    assert {e["tid"] for e in dev} == {"device:kv.get"}
    # the CLI table path renders without error
    assert proftool.main([str(path), "--json"]) == 0


# --- 6. kill-switch conformance: PMDFC_PROF=off is byte-identical v2 ------


def test_prof_off_snapshots_stay_v2(monkeypatch):
    from pmdfc_tpu.kv import KV

    monkeypatch.delenv("PMDFC_PROF", raising=False)
    tele.configure(TelemetryConfig(ring_capacity=1 << 12))
    try:
        assert prof_mod.active() is None
        kv = KV(_cfg())
        keys = _keys(32)
        kv.insert(keys, _pages(keys))
        out, found = kv.get(keys)
        assert found.all()
        snap = tele.get().snapshot()
        assert snap["schema"] == "pmdfc-telemetry-v2"
        assert "profile" not in snap
        assert not any(k.startswith("prof.") for k in snap["histograms"])
        assert not any(k.startswith("prof.") for k in snap["gauges"])
        # the seams are passthroughs: no device spans, thunk value intact
        assert prof_mod.fetch("kv.get", "get", lambda: 7, n_ops=1,
                              ring=True) == 7
        assert not any(r.get("src") == "prof" for r in tele.get().ring_tail())
        # serializes exactly like a pre-profiler tree's snapshot
        assert json.loads(json.dumps(snap)) == snap
    finally:
        tele.configure()
