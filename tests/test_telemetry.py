"""Unified telemetry layer drills (`runtime/telemetry.py`).

Four tiers of coverage:
1. Registry semantics — counters/gauges/histograms, scope Mapping reads,
   the no-collision assertion, the Prometheus render, the kill switch.
2. Trace-id propagation — negotiated via TRACE_FLAG, minted per verb,
   recovered server-side: under a seeded `ChaosProxy` soak every verb
   the CLIENT completed has a matching SERVER span (same 32-bit id),
   and verbs that died with a dropped connection are recorded as
   failed spans.
3. Flight recorder — rung 3 (phase failure / breaker open) and rung 5
   (replica-set exhausted) fire dumps that attribute the degradation to
   a concrete conn/phase/endpoint (the ISSUE 5 acceptance drill).
4. Wire export — `MSG_STATS` ships the registry snapshot; the teledump
   schema checker (`tools/check_teledump.py`) pins its shape.
"""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from pmdfc_tpu.config import NetConfig, TelemetryConfig, telemetry_enabled
from pmdfc_tpu.runtime import telemetry as tele

pytestmark = pytest.mark.telemetry

W = 16


@pytest.fixture()
def fresh_registry():
    """Isolated registry per test; restore a default one afterwards so
    other suites keep a clean namespace."""
    reg = tele.configure(TelemetryConfig(ring_capacity=1 << 15))
    yield reg
    tele.configure()


def _keys(n, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(1 << 22, size=n, replace=False)
    return np.stack([flat >> 11, flat & 0x7FF], -1).astype(np.uint32)


def _pages(keys):
    return (keys[:, 1:2].astype(np.uint32) * 3 + 1) * np.arange(
        1, W + 1, dtype=np.uint32
    )


# --- 1. registry semantics ----------------------------------------------


def test_scope_counters_and_mapping_reads(fresh_registry):
    s = tele.scope("t", {"a": 0, "b": 0})
    s.inc("a", 3)
    s.inc("c")          # lazy creation
    s.max("hwm", 7)
    s.max("hwm", 4)     # high-water: no regression
    assert s["a"] == 3 and s["b"] == 0 and s["c"] == 1 and s["hwm"] == 7
    assert dict(s) == {"a": 3, "b": 0, "c": 1, "hwm": 7}
    assert "a" in s and len(s) == 4
    with pytest.raises(KeyError):
        s["nope"]


def test_scope_instances_never_share_counters(fresh_registry):
    a = tele.scope("srv", {"ops": 0})
    b = tele.scope("srv", {"ops": 0})
    a.inc("ops", 5)
    assert a["ops"] == 5 and b["ops"] == 0
    assert a.prefix != b.prefix


def test_shared_scope_with_seed_counters(fresh_registry):
    """`unique=False` + pre-seeded counters must not self-deadlock (the
    seeding re-enters registration, which must happen OUTSIDE the
    registry lock); the first caller's seed wins, later callers get the
    existing scope unmodified."""
    s = tele.scope("sh", {"a": 2}, unique=False)
    assert s["a"] == 2
    s2 = tele.scope("sh", {"a": 5}, unique=False)
    assert s2 is s and s["a"] == 2


def test_registry_collision_asserts(fresh_registry):
    reg = fresh_registry
    reg._register("x.ops", tele.Counter)
    with pytest.raises(ValueError, match="already registered"):
        reg._register("x.ops", tele.Gauge)


def test_histogram_log2_quantiles(fresh_registry):
    h = tele.scope("h").hist("lat")
    for v in [1] * 50 + [100] * 45 + [5000] * 5:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["sum"] == pytest.approx(50 + 4500 + 25000)
    # p50 falls in the bucket holding 1 (upper bound 1), p95 in 100's
    # bucket (upper bound 128), p99 clipped to the observed max
    assert snap["p50"] <= 2
    assert 100 <= snap["p95"] <= 128
    assert snap["p99"] <= 5000
    assert snap["max"] == 5000


def test_render_prometheus_style(fresh_registry):
    s = tele.scope("net", {"bad_frames": 2})
    s.hist("lat").observe(10)
    text = tele.render()
    assert "# TYPE pmdfc_net0_bad_frames counter" in text
    assert "pmdfc_net0_bad_frames 2" in text
    assert 'pmdfc_net0_lat{quantile="p95"}' in text
    # round-trips through the snapshot renderer (teledump --format prom)
    assert tele.render_snapshot(tele.snapshot()) == text


def test_kill_switch_noops_tracing_keeps_counters():
    tele.configure(TelemetryConfig(enabled=False))
    try:
        s = tele.scope("k", {"ops": 0})
        s.inc("ops")
        assert s["ops"] == 1          # correctness counters always count
        s.hist("lat").observe(5)
        assert s.hist("lat").snapshot()["count"] == 0
        tele.record_span("client", "get", 1, True)
        tele.record_event("x")
        assert len(tele.get().ring) == 0
        tele.rung("bad_frame")        # counted, never ring-recorded
        assert tele.get()._rungs["bad_frame"] == 1
        assert tele.enabled() is False
    finally:
        tele.configure()


def test_env_kill_switch_resolution(monkeypatch):
    monkeypatch.setenv("PMDFC_TELEMETRY", "off")
    assert telemetry_enabled() is False
    assert telemetry_enabled(default=True) is False
    # env wins over a code-side enabled=True config
    reg = tele.configure(TelemetryConfig(enabled=True))
    try:
        assert tele.enabled() is False
        monkeypatch.setenv("PMDFC_TELEMETRY", "on")
        assert telemetry_enabled(default=False) is True
    finally:
        monkeypatch.delenv("PMDFC_TELEMETRY", raising=False)
        tele.configure()
    assert reg is not tele.get()


def test_set_enabled_runtime_toggle(fresh_registry):
    tele.record_span("client", "get", 1, True)
    tele.set_enabled(False)
    tele.record_span("client", "get", 2, True)
    tele.set_enabled(True)
    tele.record_span("client", "get", 3, True)
    traces = [r["trace"] for r in tele.get().ring]
    assert traces == [1, 3]


def test_mint_trace_32bit_nonzero(fresh_registry):
    seen = {tele.mint_trace() for _ in range(1000)}
    assert all(0 < t <= 0xFFFFFFFF for t in seen)
    assert len(seen) == 1000


# --- 2. trace-id propagation (wire + chaos) -----------------------------


def _span_index(reg):
    spans = [r for r in reg.ring if r.get("kind") == "span"]
    return (
        [s for s in spans if s["src"] == "client"],
        {s["trace"] for s in spans if s["src"] == "server"},
    )


def test_trace_negotiation_and_server_spans(fresh_registry):
    from pmdfc_tpu.client.backends import LocalBackend
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    shared = LocalBackend(page_words=W, capacity=1 << 12)
    with NetServer(lambda: shared, net=NetConfig()).start() as srv:
        with TcpBackend("127.0.0.1", srv.port, page_words=W,
                        keepalive_s=None) as be:
            assert be.traced
            keys = _keys(8, seed=3)
            be.put(keys, _pages(keys))
            _, found = be.get(keys)
            assert found.all()
    client, server_traces = _span_index(fresh_registry)
    ok = [s for s in client if s["ok"] and s["op"] in ("put", "get")]
    assert len(ok) == 2  # the put and the get, each traced
    for s in ok:
        assert s["trace"] != 0
        assert s["trace"] in server_traces, s
        assert s["dur_us"] > 0


def test_trace_off_when_telemetry_disabled():
    from pmdfc_tpu.client.backends import LocalBackend
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    tele.configure(TelemetryConfig(enabled=False))
    try:
        shared = LocalBackend(page_words=W, capacity=1 << 12)
        with NetServer(lambda: shared).start() as srv, \
                TcpBackend("127.0.0.1", srv.port, page_words=W,
                           keepalive_s=None) as be:
            assert not be.traced          # no TRACE_FLAG requested
            _, found = be.get(_keys(4))
            assert not found.any()
        assert len(tele.get().ring) == 0
    finally:
        tele.configure()


def test_trace_ids_match_under_chaos(fresh_registry):
    """The satellite acceptance: seeded ChaosProxy soak over a windowed
    connection — every verb the client COMPLETED has a server span with
    the same trace id, and dropped-conn verbs show as failed spans."""
    from pmdfc_tpu.client.backends import LocalBackend
    from pmdfc_tpu.runtime.failure import ChaosProxy, ReconnectingClient
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    shared = LocalBackend(page_words=W, capacity=1 << 13)
    srv = NetServer(lambda: shared, net=NetConfig()).start()
    # gentle per-frame rates: a fault still fails the whole 8-verb
    # window, so even 1% yields a steady failed-span stream while most
    # verbs complete and give the propagation check a real sample
    rates = {"flip": 0.01, "truncate": 0.005, "duplicate": 0.01}
    with srv, ChaosProxy("127.0.0.1", srv.port, seed=17,
                         rates=rates) as px:
        def factory():
            return TcpBackend("127.0.0.1", px.port, page_words=W,
                              keepalive_s=None, op_timeout_s=1.0,
                              window=8)

        rc = ReconnectingClient(factory, page_words=W,
                                retry_delay_s=0.002,
                                max_retry_delay_s=0.02, seed=17)
        keys = _keys(128, seed=17)
        pages = _pages(keys)
        rng = np.random.default_rng(17)
        for step in range(300):
            lo = int(rng.integers(0, 96))
            n = int(rng.integers(1, 16))
            if rng.integers(2):
                rc.put(keys[lo:lo + n], pages[lo:lo + n])
            else:
                rc.get(keys[lo:lo + n])
            if not rc.connected:
                time.sleep(0.003)   # let reconnect land; keep spans flowing
        rc.close()
    client, server_traces = _span_index(fresh_registry)
    verbs = [s for s in client if s["op"] in ("put", "get", "invalidate")]
    completed = [s for s in verbs if s["ok"]]
    failed = [s for s in verbs if not s["ok"]]
    assert len(completed) > 50, "soak barely ran"
    # chaos actually dropped connections -> failed spans recorded
    fired = sum(v for k, v in px.stats.items()
                if k.endswith("_frames") and k != "forwarded_frames")
    assert fired > 0 and len(failed) > 0, (fired, len(failed))
    missing = [s for s in completed if s["trace"] not in server_traces]
    assert not missing, f"{len(missing)} completed verbs lack server spans"
    for s in failed:
        assert s["err"], s
        # a chaos-killed connection must CLOSE its open spans as failed
        # tree nodes — full v2 record, not a dangling begin (ISSUE 9)
        assert s["span"] and 0 < s["span"] <= 0xFFFFFFFF
        assert s["t1_ns"] >= s["t0_ns"] and s["dur_us"] >= 0


# --- 3. flight recorder: rung dumps with attribution --------------------


def _dumps(dump_dir, rung_name):
    out = []
    for f in sorted(os.listdir(dump_dir)):
        # the recorder writes atomically (".json.tmp" then rename):
        # skip in-flight temp files — matching one here raced the
        # rename and crashed the poll loop with FileNotFoundError
        if f.startswith(f"flight_{rung_name}_") and f.endswith(".json"):
            with open(os.path.join(dump_dir, f)) as fh:
                out.append(json.load(fh))
    return out


def test_rung3_phase_failure_dump_attributes_conn_and_phase(
        tmp_path, monkeypatch):
    """Rung 3: a fused serve phase raising server-side drops the
    involved connections; the flight dump must name the phase and the
    concrete conns it took down. Containment is forced OFF so the drill
    keeps pinning the legacy conn-drop path — with PR 18's
    `PMDFC_CONTAINMENT` on (the default), a negotiated connection gets
    a rung-7 `MSG_NACK` legal miss instead (drilled in
    tests/test_containment.py)."""
    from pmdfc_tpu.client.backends import LocalBackend
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    monkeypatch.setenv("PMDFC_CONTAINMENT", "off")
    tele.configure(TelemetryConfig(ring_capacity=1 << 14,
                                   dump_dir=str(tmp_path),
                                   dump_min_interval_s=0.0))
    try:
        class Poisoned(LocalBackend):
            def get(self, keys):
                raise RuntimeError("injected phase failure")

        shared = Poisoned(page_words=W, capacity=1 << 10)
        with NetServer(lambda: shared, net=NetConfig()).start() as srv, \
                TcpBackend("127.0.0.1", srv.port, page_words=W,
                           keepalive_s=None, op_timeout_s=5.0) as be:
            keys = _keys(4, seed=9)
            be.put(keys, _pages(keys))      # put phase still works
            with pytest.raises((ConnectionError, OSError)):
                be.get(keys)                # get phase raises -> rung 3
            deadline = time.time() + 5
            while not _dumps(tmp_path, "phase_failure") \
                    and time.time() < deadline:
                time.sleep(0.02)
        docs = _dumps(tmp_path, "phase_failure")
        assert docs, "no phase_failure dump written"
        d = docs[0]
        assert d["schema"] == "pmdfc-flight-v2"
        assert d["detail"]["phase"] == "get"
        assert d["detail"]["conns"], "no conn attribution"
        assert d["detail"]["ops"] >= 1
        # the ring tail holds the failed server span for the same conn
        fails = [r for r in d["records"]
                 if r.get("kind") == "span" and r.get("src") == "server"
                 and not r.get("ok")]
        assert any(r.get("conn") in d["detail"]["conns"] for r in fails)
        assert d["telemetry"]["counters"]["rung.phase_failure"] >= 1
    finally:
        tele.configure()


def test_rung5_replica_exhausted_dump_attributes_endpoints(tmp_path):
    """Rung 5: every endpoint behind an open breaker ⇒ the GET load-
    sheds to a legal miss; breaker_open and replica_exhausted dumps
    name the concrete endpoints."""
    from pmdfc_tpu.client.replica import ReplicaGroup
    from pmdfc_tpu.config import ReplicaConfig
    from pmdfc_tpu.runtime.failure import ReconnectingClient

    tele.configure(TelemetryConfig(ring_capacity=1 << 14,
                                   dump_dir=str(tmp_path),
                                   dump_min_interval_s=0.0))
    try:
        def dead_factory():
            raise ConnectionError("server down")

        eps = [ReconnectingClient(dead_factory, page_words=W,
                                  retry_delay_s=0.001,
                                  max_retry_delay_s=0.01, seed=i)
               for i in range(2)]
        cfg = ReplicaConfig(n_replicas=2, rf=2, hedge_ms=1.0,
                            breaker_failures=2, breaker_cooldown_s=30.0,
                            repair_interval_s=0.0)
        with ReplicaGroup(eps, page_words=W, cfg=cfg, seed=5) as g:
            keys = _keys(8, seed=5)
            for _ in range(4):           # open both breakers
                out, found = g.get(keys)
                assert not found.any()
            assert all(br.state == "open" for br in g.breakers)
            out, found = g.get(keys)     # rung 5: all sets exhausted
            assert not found.any()
            assert g.counters["load_shed_gets"] > 0
        opens = _dumps(tmp_path, "breaker_open")
        assert opens and opens[0]["detail"]["endpoint"].startswith(
            "replica")
        sheds = _dumps(tmp_path, "replica_exhausted")
        assert sheds, "no replica_exhausted dump written"
        d = sheds[-1]
        assert d["detail"]["op"] == "get"
        assert sorted(d["detail"]["open_endpoints"]) == [0, 1]
        assert d["detail"]["keys"] > 0
    finally:
        tele.configure()


def test_dump_cooldown_limits_writes(tmp_path):
    tele.configure(TelemetryConfig(dump_dir=str(tmp_path),
                                   dump_min_interval_s=60.0))
    try:
        for _ in range(5):
            tele.rung("bad_frame", conn=1)
        assert len(_dumps(tmp_path, "bad_frame")) == 1
        assert tele.get()._rungs["bad_frame"] == 5  # counted regardless
    finally:
        tele.configure()


# --- 4. wire export + schema --------------------------------------------


def _load_check_teledump():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_teledump", os.path.join(root, "tools", "check_teledump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_msg_stats_ships_registry_and_schema_conforms(fresh_registry):
    from pmdfc_tpu.client.backends import LocalBackend
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    shared = LocalBackend(page_words=W, capacity=1 << 10)
    with NetServer(lambda: shared, net=NetConfig()).start() as srv, \
            TcpBackend("127.0.0.1", srv.port, page_words=W,
                       keepalive_s=None) as be:
        keys = _keys(8, seed=1)
        be.put(keys, _pages(keys))
        be.get(keys)
        doc = be.server_stats()
    assert "stored" in doc                  # backend stats untouched
    snap = doc["telemetry"]
    # v2 = v1 + optional series/workload blocks (PR 10); every v1 field
    # keeps its exact shape, so v1 consumers parse v2 unchanged
    assert snap["schema"] == "pmdfc-telemetry-v2"
    assert "workload" in doc                # the X-ray sketch block
    assert any(k.endswith(".ops") for k in snap["counters"])
    assert any(k.endswith("get_us") for k in snap["histograms"])
    checker = _load_check_teledump()
    assert checker.check(doc) == []
    # and the checker actually catches breakage
    bad = json.loads(json.dumps(doc))
    bad["telemetry"]["counters"]["net0.ops"] = "three"
    assert checker.check(bad)
    assert checker.check({}) != []


# --- 5. migrated stats surfaces -----------------------------------------


def test_reconnecting_client_counters_shim_removed(fresh_registry):
    # the one-release deprecation shim (PR 5) is gone: `stats()` is the
    # only counter surface, and the old attribute must not quietly
    # reappear as something mapping-shaped
    from pmdfc_tpu.runtime import failure

    rc = failure.ReconnectingClient(
        lambda: (_ for _ in ()).throw(ConnectionError()), page_words=W)
    rc.get(_keys(3))
    assert rc.stats()["missed_gets"] == 3
    assert not hasattr(rc, "counters")


def test_integrity_backend_namespaces_wrapper_counters(fresh_registry):
    from pmdfc_tpu.client.backends import IntegrityBackend, LocalBackend

    be = IntegrityBackend(LocalBackend(page_words=W))
    keys = _keys(4, seed=2)
    be.put(keys, _pages(keys))
    _, found = be.get(keys)
    assert found.all()
    s = be.stats()
    assert s["integrity.verified_gets"] == 4
    assert s["integrity.corrupt_pages"] == 0
    assert "client_corrupt_pages" not in s   # the old shadow-prone keys
    # corrupt the inner store: the gate degrades to a miss, bumps the
    # namespaced counter, and fires the digest rung
    inner = be._be._store
    kk = (int(keys[0][0]), int(keys[0][1]))
    inner[kk] = inner[kk] + 1
    out, found = be.get(keys)
    assert not found[0] and be.counters["corrupt_pages"] == 1
    assert tele.get()._rungs["digest_mismatch"] >= 1