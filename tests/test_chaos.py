"""Seeded chaos soak — the whole integrity/degradation ladder at once.

A real-KV NetServer serves through a `ChaosProxy` running a seeded fault
schedule (bit-flips, truncations, duplications, delays, reorders), the
client stack is the full ladder (`IntegrityBackend` over
`ReconnectingClient` over `TcpBackend`), pool bytes are poisoned mid-soak,
and the server is killed and restored from a crash-safe checkpoint (with a
torn newest snapshot that must be rejected). Three invariants, asserted
continuously:

1. NO exception escapes a page op — every fault degrades to miss/drop.
2. NO wrong bytes are ever returned — every `found` page content-verifies
   against the key-derived ground truth (checksum rung + CRC rung + the
   client's own end-to-end digest).
3. Restart serves exactly the last DURABLE checkpoint: the torn newest
   snapshot raises `CheckpointCorruptError`; the restored server's state
   equals what the durable snapshot recorded (hit set and content).

The fast tier runs a short schedule; the `slow` variant soaks longer with
higher fault rates and a second kill/restore cycle.
"""

import time

import numpy as np
import pytest

from pmdfc_tpu import checkpoint
from pmdfc_tpu.checkpoint import CheckpointCorruptError
from pmdfc_tpu.client.backends import DirectBackend, IntegrityBackend
from pmdfc_tpu.config import BloomConfig, IndexConfig, KVConfig
from pmdfc_tpu.kv import KV
from pmdfc_tpu.runtime.failure import ChaosProxy, ReconnectingClient
from pmdfc_tpu.runtime.net import NetServer, TcpBackend

W = 16
CFG = KVConfig(
    index=IndexConfig(capacity=1 << 12),
    bloom=BloomConfig(num_bits=1 << 13),
    paged=True,
    page_words=W,
)
RATES = {"flip": 0.04, "truncate": 0.02, "duplicate": 0.04,
         "delay": 0.02, "reorder": 0.02}


def _keys(n, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(1 << 22, size=n, replace=False)
    return np.stack([flat >> 11, flat & 0x7FF], -1).astype(np.uint32)


def _pages(keys):
    # ground truth derives from the key: ANY wrong byte is detectable
    return (keys[:, 1:2].astype(np.uint32) * 3 + 1) * np.arange(
        1, W + 1, dtype=np.uint32
    )


def _start_server(kv):
    return NetServer(lambda: DirectBackend(kv)).start()


def _soak(steps: int, seed: int, rates: dict, kill_at: tuple,
          tmp_path, window: int = 1) -> dict:
    rng = np.random.default_rng(seed)
    keys = _keys(256, seed=seed)
    pages = _pages(keys)

    kv = KV(CFG)
    srv = _start_server(kv)

    # Warm the serving-path device programs through a chaos-free direct
    # connection BEFORE the faulted window: a cold first compile takes
    # seconds, during which every verb times out (op_timeout_s=1.0) and
    # the client burns the whole soak reconnecting — a compile-timing
    # flake (seen only on cold per-process caches), not a chaos outcome.
    # The warm keys are invalidated again, so KV state stays empty.
    warm = TcpBackend("127.0.0.1", srv.port, page_words=W,
                      keepalive_s=None, op_timeout_s=120.0)
    warm.put(keys[224:240], pages[224:240])
    warm.get(keys[224:240])
    warm.invalidate(keys[224:240])
    warm.close()

    px = ChaosProxy("127.0.0.1", srv.port, seed=seed, rates=rates,
                    delay_s=0.02, reorder_wait_s=0.05)
    port = px.port

    def factory():
        return TcpBackend("127.0.0.1", port, page_words=W,
                          keepalive_s=None, op_timeout_s=1.0,
                          window=window)

    rc = ReconnectingClient(factory, page_words=W, retry_delay_s=0.005,
                            max_retry_delay_s=0.1, seed=seed)
    be = IntegrityBackend(rc)

    durable = str(tmp_path / f"durable_{seed}.npz")
    durable_found: np.ndarray | None = None
    stats = {"wrong_bytes": 0, "found_gets": 0, "poisoned": 0,
             "restores": 0}
    kill_steps = set(kill_at)

    for step in range(steps):
        op = rng.integers(4)
        lo = int(rng.integers(0, 224))
        n = int(rng.integers(1, 16))
        sel = slice(lo, lo + n)
        # every op must degrade, never raise (invariant 1: the soak loop
        # itself finishing is the assertion)
        if op == 0:
            be.put(keys[sel], pages[sel])
        elif op in (1, 2):
            out, found = be.get(keys[sel])
            stats["found_gets"] += int(found.sum())
            good = pages[sel]
            stats["wrong_bytes"] += int(
                (out[found] != good[found]).any(axis=1).sum())
        else:
            be.invalidate(keys[sel])

        if not rc.stats()["connected"]:
            # Disconnected ops fail locally in microseconds, so an
            # unpaced loop burns every remaining step inside the
            # client's 5-100 ms retry backoff window and the soak ends
            # before a reconnect is ever attempted (nothing but drops —
            # a degenerate run that starves the trace/hit-rate
            # assertions). Connected ops are naturally paced by the
            # chaos delays; give disconnected phases the same wall-time
            # footing so recovery is part of every run.
            time.sleep(0.02)

        if step == steps // 4:
            # poison bytes at rest: rung 1 must convert these to misses.
            # The op schedule only touches keys[:239], so keys[240:] are a
            # reserved probe set: insert them DIRECTLY (chaos-free, always
            # lands), poison everything, probe immediately — detection is
            # deterministic regardless of how much chaos-path traffic
            # actually survived to this point.
            import dataclasses

            import jax.numpy as jnp

            kv.insert(keys[240:], pages[240:])
            out0, f0 = kv.get(keys[240:])
            assert f0.all() and (out0 == pages[240:]).all()
            before = kv.stats()["corrupt_pages"]
            with kv._lock:
                pool = kv.state.pool
                kv.state = dataclasses.replace(
                    kv.state,
                    pool=dataclasses.replace(
                        pool, pages=pool.pages ^ jnp.uint32(1 << 9)),
                )
            p_out, p_found = kv.get(keys)
            detected = kv.stats()["corrupt_pages"] - before
            assert detected >= 16, "poisoned probe rows were not detected"
            assert not p_found[240:].any(), \
                "a poisoned probe page was served as a hit"
            assert (p_out[p_found] == pages[p_found]).all(), \
                "a poisoned page was served"
            stats["corrupt_detected"] = stats.get("corrupt_detected", 0) \
                + detected
            stats["poisoned"] += 1

        if step in kill_steps:
            # crash-safe checkpoint, then kill; newest snapshot is torn
            kv.snapshot(durable)
            torn = str(tmp_path / f"torn_{seed}_{step}.npz")
            kv.snapshot(torn)
            data = open(torn, "rb").read()
            open(torn, "wb").write(data[: int(len(data) * 0.7)])
            srv.stop()
            px.close()
            # invariant 3a: the torn snapshot is detected and rejected
            with pytest.raises(CheckpointCorruptError):
                checkpoint.load(torn, CFG)
            kv = KV(CFG, state=checkpoint.load(durable, CFG))
            # record exactly what the durable snapshot serves
            d_out, d_found = kv.get(keys)
            durable_found = d_found.copy()
            assert (d_out[d_found] == pages[d_found]).all(), \
                "restored state serves wrong bytes"
            srv = _start_server(kv)
            px = ChaosProxy("127.0.0.1", srv.port, seed=seed + step,
                            rates=rates, delay_s=0.02, reorder_wait_s=0.05)
            port = px.port  # factory closes over `port` via nonlocal read
            rc._factory = lambda p=px.port: TcpBackend(
                "127.0.0.1", p, page_words=W, keepalive_s=None,
                op_timeout_s=1.0, window=window)
            stats["restores"] += 1
            # invariant 3b: before any new put lands, the server's hit set
            # is the durable snapshot's hit set (direct, chaos-free probe)
            probe = KV(CFG, state=checkpoint.load(durable, CFG))
            p_out, p_found = probe.get(keys)
            assert (p_found == durable_found).all()

    px.close()
    srv.stop()
    be.close()
    stats["chaos"] = dict(px.stats)
    stats["client"] = rc.stats()
    stats["corrupt_detected"] = (
        stats.get("corrupt_detected", 0) + be.counters["corrupt_pages"])
    return stats


def test_chaos_soak_short(tmp_path):
    s = _soak(steps=120, seed=5, rates=RATES, kill_at=(60,),
              tmp_path=tmp_path)
    # invariant 2: nothing wrong was ever served
    assert s["wrong_bytes"] == 0
    assert s["restores"] == 1
    # the schedule really exercised the ladder: faults fired and the
    # poisoned pages were detected (not served)
    assert s["poisoned"] == 1
    assert s["corrupt_detected"] > 0, "poisoned rows were never probed"


@pytest.mark.slow
def test_chaos_soak_long(tmp_path):
    rates = {k: v * 2 for k, v in RATES.items()}
    s = _soak(steps=600, seed=9, rates=rates, kill_at=(200, 420),
              tmp_path=tmp_path)
    assert s["wrong_bytes"] == 0
    assert s["restores"] == 2
    assert s["corrupt_detected"] > 0
    # chaos actually landed: at least some faults of several kinds fired
    fired = sum(v for k, v in s["chaos"].items()
                if k.endswith("_frames") and k != "forwarded_frames")
    assert fired > 0


def test_chaos_extent_verbs_degrade_to_drop_conn():
    """The extent verbs (`MSG_INSEXT`/`MSG_GETEXT`) ride the same CRC
    rung as the page verbs: a bit-flipped frame is counted (`bad_frames`)
    and dropped — the server never parses a garbage registration, the
    client degrades to the legal result (uncovered / miss), and the
    connection recovers."""
    kv = KV(CFG)
    # warm the extent programs OFF the wire: a first-compile stall must
    # not masquerade as a chaos-induced timeout in the assertions below
    kv.insert_extent(np.array([1, 1], np.uint32),
                     np.array([0, 4096], np.uint32), 4)
    kv.get_extent(np.stack([np.full(8, 1, np.uint32),
                            np.arange(1, 9, dtype=np.uint32)], -1))
    srv = _start_server(kv)
    with srv, ChaosProxy("127.0.0.1", srv.port, seed=21) as px:
        def factory():
            return TcpBackend("127.0.0.1", px.port, page_words=W,
                              keepalive_s=None, op_timeout_s=10.0)

        rc = ReconnectingClient(factory, page_words=W,
                                retry_delay_s=0.005,
                                max_retry_delay_s=0.1, seed=21)
        probe = np.stack([np.full(8, 7, np.uint32),
                          np.arange(512, 520, dtype=np.uint32)], -1)
        # connect + one clean op FIRST (ReconnectingClient dials lazily)
        # so the armed flip lands on the INSEXT frame itself, not the
        # handshake — this test exists to prove the server's INSEXT
        # path, specifically, never parses a corrupted registration
        vals, found = rc.get_extent(probe[:1])
        assert rc.connected and not found.any()
        # a corrupted INSEXT frame: the server must not register ANY
        # extent from it; the client reports the whole run uncovered
        px.flip_next(1)
        uncovered = rc.insert_extent([7, 512], [3, 1 << 20], 40)
        assert uncovered == 40  # legal degraded result, never raises
        assert srv.stats["bad_frames"] >= 1
        deadline = time.time() + 5
        while not rc.connected and time.time() < deadline:
            rc.get_extent(probe[:1])
            time.sleep(0.02)
        vals, found = rc.get_extent(probe)
        assert not found.any(), "a torn INSEXT frame registered an extent"
        # now a clean registration, then a flipped GETEXT: degrade to
        # miss (never garbage values), then recover and resolve
        assert rc.insert_extent([7, 512], [3, 1 << 20], 40) == 0
        px.flip_next(1)
        vals, found = rc.get_extent(probe)
        assert not found.any() and (vals == 0).all()
        deadline = time.time() + 5
        ok = False
        while time.time() < deadline:
            vals, found = rc.get_extent(probe)
            if found.all():
                ok = True
                break
            time.sleep(0.02)
        assert ok, "extent path never recovered after the flipped frame"
        want = (3 << 32 | 1 << 20) + (probe[:, 1].astype(np.int64)
                                      - 512) * 4096
        got = (vals[:, 0].astype(np.int64) << 32) | vals[:, 1]
        assert (got == want).all()
        assert px.stats["flipped_frames"] == 2
        rc.close()


def test_chaos_stats_verb_degrades_to_drop_conn():
    """`MSG_STATS` under chaos: a flipped frame (either direction) must
    surface as a dropped connection (`ConnectionError`/`ProtocolError`)
    — never a parse of a garbage JSON snapshot — and the counter rung
    records it; a fresh op channel then serves the real snapshot."""
    kv = KV(CFG)
    srv = _start_server(kv)
    with srv, ChaosProxy("127.0.0.1", srv.port, seed=22) as px:
        be = TcpBackend("127.0.0.1", px.port, page_words=W,
                        keepalive_s=None, op_timeout_s=1.0)
        snap = be.stats()  # the unified stats() surface = server pull
        assert "puts" in snap and "corrupt_pages" in snap
        px.flip_next(1)  # lands on the STATS request frame
        with pytest.raises((ConnectionError, OSError)):
            be.stats()
        assert srv.stats["bad_frames"] >= 1
        be.close()
        # the server survived: a fresh channel pulls a clean snapshot
        be2 = TcpBackend("127.0.0.1", px.port, page_words=W,
                         keepalive_s=None, op_timeout_s=1.0)
        snap2 = be2.server_stats()  # the explicit-roundtrip alias
        assert "puts" in snap2 and "corrupt_pages" in snap2
        be2.close()


def test_chaos_soak_deterministic_schedule(tmp_path):
    """Same seed ⇒ same op schedule and same fault schedule: two runs
    agree on every deterministic counter (the soak is reproducible, so a
    failure in CI replays locally)."""
    a = _soak(steps=60, seed=13, rates={}, kill_at=(), tmp_path=tmp_path)
    b = _soak(steps=60, seed=13, rates={}, kill_at=(), tmp_path=tmp_path)
    assert a["found_gets"] == b["found_gets"]
    assert a["wrong_bytes"] == b["wrong_bytes"] == 0


# --- pipelined (windowed) connection under chaos (netpipe tier) ---------


@pytest.mark.netpipe
def test_chaos_soak_short_pipelined(tmp_path):
    """The acceptance soak on a WINDOWED connection: the full seeded
    fault schedule (flips, truncations, duplications, delays, reorders)
    plus a kill/restore cycle over a pipelined `TcpBackend` — zero
    wrong-bytes deliveries, zero protocol violations (every fault
    degrades to a legal miss/drop; the soak finishing IS the
    no-exception invariant)."""
    s = _soak(steps=120, seed=5, rates=RATES, kill_at=(60,),
              tmp_path=tmp_path, window=8)
    assert s["wrong_bytes"] == 0
    assert s["restores"] == 1
    assert s["poisoned"] == 1
    assert s["corrupt_detected"] > 0


@pytest.mark.netpipe
def test_chaos_pipelined_replies_match_seq_or_drop():
    """Reordered/duplicated/truncated frames on a windowed connection
    must either match by sequence id or degrade to drop-conn: with 4
    threads keeping the window full through a ChaosProxy, every served
    page content-verifies against its own key (no mis-delivered
    wrong-verb bytes) and every thread finishes (no stuck waiter)."""
    from pmdfc_tpu.client.backends import LocalBackend
    from pmdfc_tpu.runtime.net import NetServer

    shared = LocalBackend(page_words=W, capacity=1 << 13)
    srv = NetServer(lambda: shared).start()
    with srv, ChaosProxy("127.0.0.1", srv.port, seed=31) as px:
        def factory():
            return TcpBackend("127.0.0.1", px.port, page_words=W,
                              keepalive_s=None, op_timeout_s=1.0,
                              window=8)

        rc = ReconnectingClient(factory, page_words=W,
                                retry_delay_s=0.005,
                                max_retry_delay_s=0.1, seed=31)
        # connect BEFORE the storm: a worker that races the lazy
        # connect degrades its whole quota in microseconds (the same
        # unpaced-degraded-loop class the trace soak hit), and on a
        # fast host the one connected thread then finishes before the
        # first fault arms — fired=0, a host-speed flake
        deadline = time.time() + 5
        while not rc.connected and time.time() < deadline:
            rc.get(_keys(1, seed=999))
            time.sleep(0.01)
        assert rc.connected, "could not establish the windowed conn"
        wrong = []
        errs = []
        stop = [False]

        def worker(i):
            try:
                keys = _keys(32, seed=300 + i)
                pages = _pages(keys)
                r = 0
                # run until the barrage landed (stop flag), bounded so
                # a wedged proxy can't hang the drill
                while not stop[0] and r < 4000:
                    r += 1
                    rc.put(keys, pages)
                    out, found = rc.get(keys)
                    bad = (out[found] != pages[found]).any(axis=1)
                    if bad.any():
                        wrong.append((i, int(bad.sum())))
            except Exception as e:  # noqa: BLE001 — invariant 1: no
                errs.append((i, repr(e)))  # exception escapes a page op

        ts = [__import__("threading").Thread(target=worker, args=(i,))
              for i in range(4)]
        for t in ts:
            t.start()
        # seed a deterministic fault barrage while the window is full,
        # then keep the workers running until it actually LANDED
        def _fired():
            return sum(v for k, v in px.stats.items()
                       if k.endswith("_frames")
                       and k != "forwarded_frames")

        for fault in ("duplicate", "reorder", "flip", "duplicate",
                      "truncate", "reorder", "flip"):
            time.sleep(0.05)
            px.arm(fault, 1)
        deadline = time.time() + 20
        while _fired() == 0 and time.time() < deadline \
                and any(t.is_alive() for t in ts):
            time.sleep(0.02)
        stop[0] = True
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts), "stuck waiter"
        assert not errs, errs
        assert not wrong, f"mis-delivered pages: {wrong}"
        fired = sum(v for k, v in px.stats.items()
                    if k.endswith("_frames") and k != "forwarded_frames")
        assert fired > 0, "no fault actually landed"
        rc.close()


@pytest.mark.slow
@pytest.mark.netpipe
def test_chaos_soak_long_pipelined(tmp_path):
    """Long windowed soak at doubled fault rates with two kill/restore
    cycles — the slow-tier twin of the pipelined acceptance soak."""
    rates = {k: v * 2 for k, v in RATES.items()}
    s = _soak(steps=600, seed=9, rates=rates, kill_at=(200, 420),
              tmp_path=tmp_path, window=8)
    assert s["wrong_bytes"] == 0
    assert s["restores"] == 2
    assert s["corrupt_detected"] > 0
    fired = sum(v for k, v in s["chaos"].items()
                if k.endswith("_frames") and k != "forwarded_frames")
    assert fired > 0


@pytest.mark.telemetry
def test_soak_leaves_attributable_trace(tmp_path):
    """ISSUE 5 satellite: the seeded soak must leave an attributable
    trace behind — every verb the client COMPLETED through the chaos has
    a server span carrying the same 32-bit trace id, verbs that died
    with the connection are recorded as failed spans (with the error
    class), and the wire rung (`bad_frame`) counted the CRC/desync drops
    the server actually saw."""
    from pmdfc_tpu.config import TelemetryConfig
    from pmdfc_tpu.runtime import telemetry as tele

    reg = tele.configure(TelemetryConfig(ring_capacity=1 << 15))
    try:
        s = _soak(steps=120, seed=5, rates=RATES, kill_at=(),
                  tmp_path=tmp_path, window=8)
        assert s["wrong_bytes"] == 0
        spans = [r for r in reg.ring if r.get("kind") == "span"]
        client = [r for r in spans if r["src"] == "client"]
        server_traces = {r["trace"] for r in spans
                         if r["src"] == "server"}
        completed = [r for r in client if r["ok"]]
        failed = [r for r in client if not r["ok"]]
        assert len(completed) >= 10, "soak barely completed any verbs"
        missing = [r for r in completed
                   if r["trace"] not in server_traces]
        assert not missing, \
            f"{len(missing)} completed verbs lack a server span"
        # the seeded schedule really dropped connections: those verbs
        # are failed spans naming the failure, not silent gaps
        assert s["client"]["disconnects"] > 0
        assert failed and all(r.get("err") for r in failed)
        # server-side CRC/desync drops are rung-counted with the conn
        if s["chaos"]["flipped_frames"] > 0:
            assert reg._rungs["bad_frame"] > 0
    finally:
        tele.configure()


@pytest.mark.slow
@pytest.mark.containment
def test_reconnect_storm_after_phase_failures_is_backoff_bounded():
    """PR 18 satellite: a server whose EVERY phase fails against an
    un-negotiated client keeps the rung-3 conn-drop contract — and once
    the server goes away entirely, the client's reconnect attempts are
    spaced by exponential backoff, NOT a tight livelock loop: hundreds
    of degraded ops in the dead window cost only a handful of dial
    attempts. No exception ever escapes a page op."""
    import pytest as _pytest

    from pmdfc_tpu.config import NetConfig
    from pmdfc_tpu.runtime.failure import FaultPlan, FaultyBackend

    monkey = _pytest.MonkeyPatch()
    monkey.setenv("PMDFC_CONTAINMENT", "off")  # rung-3 semantics
    try:
        plan = FaultPlan()
        shared = FaultyBackend(
            DirectBackend(KV(CFG)), plan)
        srv = NetServer(lambda: shared,
                        net=NetConfig(flush_timeout_us=20_000,
                                      settle_us=2_000)).start()
        keys = _keys(8, seed=31)
        plan.poison_keys(keys)

        def factory():
            return TcpBackend("127.0.0.1", srv.port, page_words=W,
                              keepalive_s=None, op_timeout_s=5.0)

        rc = ReconnectingClient(factory, page_words=W,
                                retry_delay_s=0.02,
                                max_retry_delay_s=0.3, backoff=2.0,
                                seed=31)
        # phase-failure storm: every op kills the conn (old contract);
        # the client degrades each op to a legal miss/drop and redials
        for _ in range(6):
            _, found = rc.get(keys)
            assert not found.any()
            deadline = time.time() + 5
            while not rc.connected and time.time() < deadline:
                rc.get(keys[:1])
                time.sleep(0.01)
        s = rc.stats()
        assert s["disconnects"] >= 3, s
        # dead-server window: hammer ops far faster than the backoff
        # schedule permits dial attempts — bounded, not a livelock
        srv.stop()
        rc.get(keys)  # burn the attached (now dead) backend
        backoffs0 = rc.stats()["reconnect_backoffs"]
        t_end = time.monotonic() + 0.7
        ops = 0
        while time.monotonic() < t_end:
            _, found = rc.get(keys)
            assert not found.any()
            ops += 1
        attempts = rc.stats()["reconnect_backoffs"] - backoffs0
        assert ops > 50, f"degraded ops were not cheap ({ops})"
        # 0.02 + 0.04 + 0.08 + 0.16 + 0.3 + ... -> <= ~8 dials in 0.7 s
        # even before jitter; a livelock would dial once per op
        assert attempts <= 10, \
            f"{attempts} dial attempts in 0.7s ({ops} ops) — livelock"
        assert attempts >= 2, "backoff never even attempted a redial"
        rc.close()
    finally:
        monkey.undo()


@pytest.mark.slow
@pytest.mark.containment
def test_nacked_ops_close_spans_as_failed_v2_records():
    """PR 18 satellite: an op answered with `MSG_NACK` closes its spans
    as FAILED v2 records on BOTH sides — the server flush span and the
    client op span carry `ok=False` with the cause-bearing
    `err="nack:<cause>"` — so a NACKed op is attributable in the flight
    recorder, never a silent gap."""
    from pmdfc_tpu.config import NetConfig, TelemetryConfig
    from pmdfc_tpu.runtime import telemetry as tele
    from pmdfc_tpu.runtime.failure import FaultPlan, FaultyBackend

    reg = tele.configure(TelemetryConfig(ring_capacity=1 << 15))
    try:
        plan = FaultPlan()
        shared = FaultyBackend(DirectBackend(KV(CFG)), plan)
        srv = NetServer(lambda: shared,
                        net=NetConfig(flush_timeout_us=20_000,
                                      settle_us=2_000)).start()
        keys = _keys(8, seed=33)
        with srv, TcpBackend("127.0.0.1", srv.port, page_words=W,
                             keepalive_s=None) as be:
            assert be.nack
            # warm the GET program off the poison path (first-compile
            # stalls must not blur the assertion window)
            be.get(_keys(4, seed=34))
            plan.poison_keys(keys)
            _, found = be.get(keys)  # isolated -> NACK_POISON
            assert not found.any()
        nacked = [r for r in reg.ring
                  if r.get("kind") == "span" and not r.get("ok", True)
                  and str(r.get("err", "")).startswith("nack:")]
        assert nacked, "no FAILED span carries the nack cause"
        srcs = {r["src"] for r in nacked}
        assert "client" in srcs, f"client span missing ({srcs})"
        assert "server" in srcs, f"server span missing ({srcs})"
        # v2 shape: tree fields + flat fields on the same record
        full = [r for r in nacked if "span" in r and "trace" in r]
        assert full, "nack spans lack v2 span/trace fields"
    finally:
        tele.configure()
