"""TCP messenger tests — tcp_style transport parity over a real socket.

Ref: the tcp_style client's o2net-derived messenger (`client/tcp_style/
tcp.c`), message vocabulary (`tcp.h:36-44`), keepalive/idle-timeout
machinery (`tcp.h:30-34`), and the server's periodic BF push
(`server/rdma_svr.cpp:157-251`). These tests put an actual process/socket
boundary under the client stack — including a subprocess client, the
multi-node analog of the reference's VM-driven runs (SURVEY §4.6).
"""

import gc
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pmdfc_tpu.client.backends import LocalBackend
from pmdfc_tpu.client.cleancache import CleanCacheClient
from pmdfc_tpu.runtime.net import NetServer, ProtocolError, TcpBackend
from pmdfc_tpu.utils.hashing_np import query_packed_np

W = 16  # page words — tiny pages keep socket traffic fast


def _keys(n, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(1 << 22, size=n, replace=False)
    return np.stack([flat >> 11, flat & 0x7FF], -1).astype(np.uint32)


def _pages(keys):
    return (keys[:, 0] * 7 + keys[:, 1])[:, None] + np.arange(
        W, dtype=np.uint32
    )


def _local_server(**kw):
    shared = LocalBackend(page_words=W, capacity=1 << 12)
    return NetServer(lambda: shared, **kw).start(), shared


def _kv_server(kv_cls=None, capacity=1 << 12, **kw):
    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.config import BloomConfig, IndexConfig, KVConfig
    from pmdfc_tpu.kv import KV

    cfg = KVConfig(index=IndexConfig(capacity=capacity),
                   bloom=BloomConfig(num_bits=1 << 13),
                   paged=True, page_words=W)
    kv = (kv_cls or KV)(cfg)
    shared = DirectBackend(kv)
    return NetServer(lambda: shared, **kw).start(), kv


def test_roundtrip_put_get_invalidate():
    srv, _ = _local_server()
    with srv, TcpBackend("127.0.0.1", srv.port, page_words=W) as be:
        keys = _keys(64)
        pages = _pages(keys)
        be.put(keys, pages)
        out, found = be.get(keys)
        assert found.all()
        assert np.array_equal(out, pages)
        # misses are legal, NOTEXIST path when nothing is found
        other = _keys(16, seed=9)
        out2, found2 = be.get(other)
        assert not found2.any()
        assert (out2 == 0).all()
        # mixed hit/miss compaction
        mix = np.concatenate([keys[:3], other[:3], keys[3:6]])
        out3, found3 = be.get(mix)
        assert found3.tolist() == [True] * 3 + [False] * 3 + [True] * 3
        assert np.array_equal(out3[found3], _pages(mix[found3]))
        hit = be.invalidate(keys[:8])
        assert hit.all()
        _, found4 = be.get(keys[:8])
        assert not found4.any()


def test_first_server_start_freezes_the_warm_heap(monkeypatch):
    """What the process holds when its first server starts leaves the
    cyclic collector's reach (a full collection then walks only what
    serving allocates); a later start freezes nothing more."""
    from pmdfc_tpu.runtime import net

    monkeypatch.setattr(net, "_HEAP_FROZEN", False)
    gc.unfreeze()
    warm = [[i] for i in range(1000)]
    srv, _ = _local_server()
    with srv:
        frozen = gc.get_freeze_count()
        assert frozen >= len(warm)
    later = [[i] for i in range(1000)]
    srv, _ = _local_server()
    with srv:
        # frozen objects still die by refcount; none of `later` is frozen
        assert gc.get_freeze_count() < frozen + len(later)
    assert len(later) == len(warm)


@pytest.mark.slow  # fast-tier 300 s contract: extent verbs stay covered
# fast by tests/test_runtime.py::test_extent_verbs_through_transport_storm;
# the TCP-socket variant (~6.5 s of real-socket handshakes) rides slow
def test_extent_verbs_over_tcp():
    """Range registration + cover resolution ride the messenger (round 4):
    insert_extent/get_extent against a real-KV NetServer over a socket,
    verifying the reference's address arithmetic (value + diff*4096,
    `KV.cpp:170-173`) and the miss boundary."""
    srv, kv = _kv_server(capacity=1 << 13)
    with srv, TcpBackend("127.0.0.1", srv.port, page_words=W) as be:
        uncovered = be.insert_extent([7, 512], [3, 1 << 20], 40)
        assert uncovered == 0
        ds = np.array([0, 13, 39, 40], np.uint32)
        probe = np.stack([np.full(4, 7, np.uint32), 512 + ds], -1)
        vals, found = be.get_extent(probe)
        assert found.tolist() == [True, True, True, False]
        np.testing.assert_array_equal(
            vals[:3, 1], (1 << 20) + ds[:3] * 4096)
        np.testing.assert_array_equal(vals[:3, 0], np.full(3, 3))
        # page ops keep working on the same channel afterwards
        keys = _keys(16)
        be.put(keys, _pages(keys))
        out, pfound = be.get(keys)
        assert pfound.all() and np.array_equal(out, _pages(keys))
        assert kv.stats()["extent_puts"] == 1


def test_client_bounds_oversized_server_frame():
    """The CLIENT side of the frame bound (VERDICT-r3 weak 5): a server
    announcing a payload beyond max_frame_bytes must fail the read before
    allocating it, not pre-allocate the 1 GiB default."""
    import socket as socket_mod

    from pmdfc_tpu.runtime.net import (
        MAGIC, MSG_HOLASI, MSG_SENDPAGE, _send_msg, _HDR)

    held = []

    def evil_server(port_box, ready):
        lsock = socket_mod.socket()
        lsock.bind(("127.0.0.1", 0))
        lsock.listen(1)
        port_box.append(lsock.getsockname()[1])
        ready.set()
        conn, _ = lsock.accept()
        held.append(conn)  # keep alive past the test body
        conn.recv(1 << 16)  # swallow HOLA
        # legit handshake, pipelined channel acked
        _send_msg(conn, MSG_HOLASI, words=W, count=1)
        conn.recv(1 << 16)  # swallow the GET
        # reply header claims a 256 MiB payload (over the 1 MiB bound)
        conn.sendall(_HDR.pack(MAGIC, MSG_SENDPAGE, 0, 0, W, 0,
                               256 << 20, 0))
        lsock.close()

    port_box, ready = [], threading.Event()
    th = threading.Thread(target=evil_server, args=(port_box, ready),
                          daemon=True)
    th.start()
    ready.wait(5)
    be = TcpBackend("127.0.0.1", port_box[0], page_words=W,
                    keepalive_s=None, max_frame_bytes=1 << 20)
    with pytest.raises((ProtocolError, ConnectionError, ValueError)):
        be.get(_keys(4))
    th.join(timeout=5)


def test_handshake_word_mismatch_rejected():
    srv, _ = _local_server()
    with srv:
        with pytest.raises(ProtocolError):
            TcpBackend("127.0.0.1", srv.port, page_words=W * 2)


def test_cleancache_client_over_tcp():
    srv, kv = _kv_server()
    with srv:
        be = TcpBackend("127.0.0.1", srv.port, page_words=W)
        cc = CleanCacheClient(be)
        oids = np.full(32, 7, np.uint32)
        idxs = np.arange(32, dtype=np.uint32)
        pages = np.arange(32, dtype=np.uint32)[:, None] + np.zeros(
            (32, W), np.uint32
        )
        cc.put_pages(oids, idxs, pages)
        out, found = cc.get_pages(oids, idxs)
        assert found.all()
        assert np.array_equal(out, pages)
        assert cc.get_page(7, 999) is None
        # client-initiated pull fetches the real packed filter over the wire
        cc.refresh_bloom()
        assert cc._bloom is not None
        assert np.array_equal(cc._bloom, np.asarray(kv.packed_bloom()))
        cc.close()
        be.close()


@pytest.mark.slow
def test_bf_push_full_then_delta():
    srv, kv = _kv_server(bf_block_bytes=64)
    with srv:
        received = []

        class Sink:
            def receive_bloom_full(self, packed, t_snap=None):
                received.append(("full", packed.copy(), t_snap))

            def receive_bloom_blocks(self, idx, blocks, wpb, t_snap=None):
                received.append(("delta", idx.copy(), blocks.copy(), wpb))

        be = TcpBackend("127.0.0.1", srv.port, page_words=W,
                        bloom_sink=Sink())
        keys = _keys(32)
        be.put(keys, _pages(keys))
        deadline = time.time() + 5
        while not any(
            d["push"] for d in srv._clients.values()
        ) and time.time() < deadline:
            time.sleep(0.01)
        srv.push_bloom_now()
        deadline = time.time() + 5
        while not received and time.time() < deadline:
            time.sleep(0.01)
        assert received and received[0][0] == "full"
        assert np.array_equal(received[0][1], np.asarray(kv.packed_bloom()))
        # second cycle with no changes: nothing travels
        n0 = len(received)
        srv.push_bloom_now()
        time.sleep(0.2)
        assert len(received) == n0
        # new puts dirty a few blocks: only those travel
        more = _keys(8, seed=5)
        be.put(more, _pages(more))
        srv.push_bloom_now()
        deadline = time.time() + 5
        while len(received) == n0 and time.time() < deadline:
            time.sleep(0.01)
        kind, idx, blocks, wpb = received[-1]
        assert kind == "delta"
        full = np.asarray(kv.packed_bloom())
        assert np.array_equal(blocks, full.reshape(-1, wpb)[idx])
        assert len(idx) < len(full) // wpb  # strictly partial
        be.close()


@pytest.mark.slow
def test_push_race_no_false_negative():
    """Puts racing the push loop must never yield a mirror false negative —
    the stamp-echo discipline's contract across the process boundary."""
    srv, kv = _kv_server(bf_block_bytes=64)
    with srv:
        be = TcpBackend("127.0.0.1", srv.port, page_words=W)
        cc = CleanCacheClient(be)
        # push channel shares the op channel's client id so the server's
        # stamp echo refers to THIS client's puts
        push_be = TcpBackend("127.0.0.1", srv.port, page_words=W,
                             bloom_sink=cc, client_id=be.client_id)
        deadline = time.time() + 5
        while not any(
            d["push"] for d in srv._clients.values()
        ) and time.time() < deadline:
            time.sleep(0.01)
        all_keys = _keys(512, seed=3)
        stop = threading.Event()

        def pusher():
            while not stop.is_set():
                srv.push_bloom_now()
                time.sleep(0.002)

        t = threading.Thread(target=pusher)
        t.start()
        try:
            for lo in range(0, len(all_keys), 16):
                chunk = all_keys[lo : lo + 16]
                oids, idxs = chunk[:, 0], chunk[:, 1]
                pages = _pages(chunk)
                cc.put_pages(oids, idxs, pages)
        finally:
            stop.set()
            t.join()
        srv.push_bloom_now()
        time.sleep(0.1)
        # every completed put must still pass the client's bloom gate
        with cc._bloom_lock:
            bloom = cc._bloom
            overlay = dict(cc._overlay)
        assert bloom is not None
        in_bloom = query_packed_np(bloom, all_keys, cc.num_hashes)
        in_overlay = np.array(
            [(int(k[0]), int(k[1])) in overlay for k in all_keys]
        )
        assert (in_bloom | in_overlay).all(), "mirror false negative"
        cc.close()
        push_be.close()
        be.close()


@pytest.mark.slow
def test_idle_timeout_kills_and_keepalive_survives():
    srv, _ = _local_server(idle_timeout_s=0.3)
    with srv:
        # no keepalive: connection dies after idling past the timeout
        be = TcpBackend("127.0.0.1", srv.port, page_words=W,
                        keepalive_s=None)
        keys = _keys(4)
        be.put(keys, _pages(keys))
        time.sleep(0.8)
        with pytest.raises(ConnectionError):
            be.put(keys, _pages(keys))
        assert srv.stats["idle_kills"] >= 1
        # keepalive faster than the timeout: connection survives the idle
        be2 = TcpBackend("127.0.0.1", srv.port, page_words=W,
                         keepalive_s=0.1)
        be2.put(keys, _pages(keys))
        time.sleep(0.8)
        be2.put(keys, _pages(keys))  # still alive
        be2.close()


@pytest.mark.slow
def test_reconnecting_client_over_tcp_restart():
    """Kill the server, degrade to legal results, restart on the same port,
    reconnect + invalidation-journal replay — the o2net reconnect drill
    across a real socket."""
    from pmdfc_tpu.runtime.failure import ReconnectingClient

    srv, shared = _local_server()
    port = srv.port

    def factory():
        return TcpBackend("127.0.0.1", port, page_words=W,
                          keepalive_s=None)

    rc = ReconnectingClient(factory, page_words=W, retry_delay_s=0.01)
    keys = _keys(32, seed=11)
    pages = _pages(keys)
    rc.put(keys, pages)
    out, found = rc.get(keys)
    assert found.all() and np.array_equal(out, pages)

    srv.stop()
    # ops degrade, no exception escapes
    out, found = rc.get(keys)
    assert not found.any()
    rc.put(keys, pages)  # dropped put is legal
    rc.invalidate(keys[:4])  # journaled for replay
    assert rc.stats()["disconnects"] >= 1

    # restart on the same port with the SAME store (snapshot-restore analog:
    # the invalidated keys are resurrected until the journal replays)
    srv2 = NetServer(lambda: shared, port=port).start()
    try:
        deadline = time.time() + 5
        ok = False
        while time.time() < deadline:
            out, found = rc.get(keys[4:])
            if found.all():
                ok = True
                break
            time.sleep(0.05)
        assert ok, "client never reconnected"
        # journal replayed: the 4 invalidated keys are gone again
        _, found = rc.get(keys[:4])
        assert not found.any()
        assert rc.stats()["reconnects"] >= 1
        assert rc.stats()["replayed_invalidates"] >= 4
    finally:
        rc.close()
        srv2.stop()


_CHILD = r"""
import sys
import numpy as np
from pmdfc_tpu.runtime.net import TcpBackend

port, W, seed = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
rng = np.random.default_rng(seed)
flat = rng.choice(1 << 22, size=128, replace=False)
keys = np.stack([flat >> 11, flat & 0x7FF], -1).astype(np.uint32)
pages = (keys[:, 0] * 7 + keys[:, 1])[:, None] + np.arange(W, dtype=np.uint32)
with TcpBackend("127.0.0.1", port, page_words=W) as be:
    be.put(keys, pages)
    out, found = be.get(keys)
    assert found.all(), found.sum()
    assert np.array_equal(out, pages)
print("CHILD_OK")
"""


@pytest.mark.slow
def test_multiprocess_clients():
    """Three concurrent client PROCESSES against one server — the 3-VM
    orchestration analog (`script.sh:3-41`) at test scale."""
    srv, _ = _local_server()
    with srv:
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _CHILD, str(srv.port), str(W),
                 str(seed)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            for seed in (1, 2, 3)
        ]
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err
            assert "CHILD_OK" in out
        assert srv.stats["connects"] >= 3


@pytest.mark.slow
def test_multinode_harness_small():
    """The orchestration driver end-to-end at test scale (2 processes)."""
    proc = subprocess.run(
        [sys.executable, "-m", "pmdfc_tpu.bench.multinode",
         "--clients", "2", "--ops", "400", "--file-pages", "128",
         "--ram-pages", "32", "--page-words", "32", "--capacity", "2048"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    agg = __import__("json").loads(proc.stdout.strip().splitlines()[-1])
    assert agg["ok"] == 2
    assert agg["verify_failures"] == 0


def test_server_survives_garbage_and_truncation():
    """Malformed frames must kill only the offending connection — the
    accept loop and other clients keep serving (TEST_Z / BUG_ON tier:
    `server/rdma_svr.h:41-42` dies, a userspace server must not)."""
    import socket as socklib

    from pmdfc_tpu.runtime import net as net_mod
    from pmdfc_tpu.runtime.net import _HDR, _send_msg

    srv, _ = _local_server()
    with srv:
        good = TcpBackend("127.0.0.1", srv.port, page_words=W)
        keys = _keys(8)
        good.put(keys, _pages(keys))

        socks = []
        try:
            # bad magic
            s1 = socklib.create_connection(("127.0.0.1", srv.port))
            socks.append(s1)
            s1.sendall(b"\xde\xad\xbe\xef" * 9)
            # truncated header then close
            s2 = socklib.create_connection(("127.0.0.1", srv.port))
            s2.sendall(b"\x13\xfc")
            s2.close()
            # oversized declared payload
            s3 = socklib.create_connection(("127.0.0.1", srv.port))
            socks.append(s3)
            s3.sendall(_HDR.pack(0xFC13, 0, 0, 0, 0, 0, 1 << 40, 0))
            # valid HOLA then garbage op (valid frame, unknown verb)
            s4 = socklib.create_connection(("127.0.0.1", srv.port))
            socks.append(s4)
            s4.settimeout(5)  # a silent server must FAIL, not hang CI
            _send_msg(s4, net_mod.MSG_HOLA, count=77, words=W)
            s4.recv(4096)  # HOLASI
            _send_msg(s4, 99)
            # valid HOLA then a frame whose payload was bit-flipped in
            # flight: the CRC must catch it (bad_frames), never parse it
            s5 = socklib.create_connection(("127.0.0.1", srv.port))
            socks.append(s5)
            s5.settimeout(5)
            _send_msg(s5, net_mod.MSG_HOLA, count=78, words=W)
            s5.recv(4096)  # HOLASI
            kk = _keys(4)
            body = (np.ascontiguousarray(kk, np.uint32).tobytes()
                    + _pages(kk).tobytes())
            hdr0 = _HDR.pack(0xFC13, net_mod.MSG_PUTPAGE, 0, 4, W, 0,
                             len(body), 0)
            import zlib

            crc = zlib.crc32(body, zlib.crc32(hdr0))
            frame = bytearray(hdr0[:-4] + crc.to_bytes(4, "little") + body)
            frame[_HDR.size + 10] ^= 0x40  # the in-flight bit flip
            s5.sendall(bytes(frame))

            time.sleep(0.2)
            # the healthy client still works
            out, found = good.get(keys)
            assert found.all()
            assert np.array_equal(out, _pages(keys))
            deadline = time.time() + 5
            while srv.stats["bad_frames"] < 2 and time.time() < deadline:
                time.sleep(0.02)
            # s4 (unknown op) and s5 (crc mismatch) both counted
            assert srv.stats["bad_frames"] >= 2
            # and the flipped put must NOT have landed
            _, f5 = good.get(kk)
            assert not f5.any(), "a corrupted frame's payload was applied"
        finally:
            for s in socks:
                s.close()
            good.close()


@pytest.mark.slow
def test_tcp_over_sharded_mesh_server():
    """The full stack at once: client process boundary (TCP messenger) →
    shared backend → 8-way mesh-sharded KV (`ShardedKV`, the NUMA_KV
    analog). The reference's closest shape is N kernel clients against the
    NUMA-dispatch server (`NuMA_KV.cpp` behind `rdma_svr.cpp`)."""
    from pmdfc_tpu.parallel import ShardedKV

    srv, skv = _kv_server(kv_cls=ShardedKV, capacity=1 << 10)
    with srv:
        with TcpBackend("127.0.0.1", srv.port, page_words=W) as be:
            cc = CleanCacheClient(be)
            keys = _keys(96, seed=31)
            oids, idxs = keys[:, 0], keys[:, 1]
            pages = _pages(keys)
            cc.put_pages(oids, idxs, pages)
            out, found = cc.get_pages(oids, idxs)
            assert found.all()
            assert np.array_equal(out, pages)
            # the keys really spread across the mesh
            rep = skv.shard_report()
            assert sum(1 for o in rep["occupancy"] if o > 0) >= 4
            # misses + invalidates flow through the same wire
            assert cc.get_page(12345, 67) is None
            hit = cc.invalidate_pages(oids[:5], idxs[:5])
            assert hit.all()
            _, found2 = cc.get_pages(oids[:5], idxs[:5])
            assert not found2.any()
            # mirror ⊇ server filter: the overlay re-adds bits of its own
            # (even invalidated) puts — false positives are legal, a
            # missing server bit never is
            cc.refresh_bloom()
            server_bits = skv.packed_bloom()
            assert np.array_equal(cc._bloom | server_bits, cc._bloom)
            cc.close()


def test_engine_backend_factory_over_tcp():
    """An EngineBackend in front of a running KVServer behind the TCP
    boundary: two interleaved connections share the one backend the
    server's flush loop serves, and neither sees the other's pages —
    request coalescing and the TCP boundary composed."""
    from pmdfc_tpu.client.backends import EngineBackend
    from pmdfc_tpu.config import BloomConfig, IndexConfig, KVConfig
    from pmdfc_tpu.runtime.engine import Engine
    from pmdfc_tpu.runtime.server import KVServer

    cfg = KVConfig(index=IndexConfig(capacity=1 << 12),
                   bloom=BloomConfig(num_bits=1 << 13),
                   paged=True, page_words=W)
    eng = Engine(num_queues=2, queue_cap=1 << 10, batch=256, timeout_us=200,
                 arena_pages=512, page_bytes=W * 4)
    with KVServer(cfg, engine=eng).start() as ksrv:
        srv = NetServer(lambda: EngineBackend(ksrv)).start()
        with srv:
            b1 = TcpBackend("127.0.0.1", srv.port, page_words=W)
            b2 = TcpBackend("127.0.0.1", srv.port, page_words=W)
            k1, k2 = _keys(32, seed=41), _keys(32, seed=42)
            p1, p2 = _pages(k1), _pages(k2)
            # interleaved clients: one connection's pages must never
            # bleed into the other's replies
            b1.put(k1, p1)
            b2.put(k2, p2)
            out1, f1 = b1.get(k1)
            out2, f2 = b2.get(k2)
            assert f1.all() and np.array_equal(out1, p1)
            assert f2.all() and np.array_equal(out2, p2)
            _, fx = b1.get(_keys(8, seed=43))
            assert not fx.any()
            b1.close()
            b2.close()


@pytest.mark.slow
def test_pull_then_push_stamp_domains_coherent():
    """ADVICE r2 (medium): a client-initiated BFPULL must not freeze the
    push path. The pull snapshot's stamp comes from the SERVER's applied-put
    stamp (one clock domain with push frames); stamping it with local 'now'
    made every later push look stale until a newer put out-stamped it."""
    srv, kv = _kv_server(bf_block_bytes=64)
    with srv:
        be = TcpBackend("127.0.0.1", srv.port, page_words=W)
        cc = CleanCacheClient(be)  # __init__ pulls via refresh_bloom()
        push_be = TcpBackend("127.0.0.1", srv.port, page_words=W,
                             bloom_sink=cc, client_id=be.client_id)
        deadline = time.time() + 5
        while not any(
            d["push"] for d in srv._clients.values()
        ) and time.time() < deadline:
            time.sleep(0.01)
        # put through THIS client, then pull again: the echoed stamp is the
        # put's send stamp, not local now
        ks = _keys(4, seed=11)
        cc.put_pages(ks[:, 0], ks[:, 1], _pages(ks))
        cc.refresh_bloom()
        # another client's put dirties the filter; the subsequent PUSH
        # must be APPLIED (not stale-rejected)
        other = TcpBackend("127.0.0.1", srv.port, page_words=W)
        more = _keys(8, seed=12)
        other.put(more, _pages(more))
        n0 = cc.counters["bf_pushes"]
        srv.push_bloom_now()
        deadline = time.time() + 5
        while cc.counters["bf_pushes"] == n0 and time.time() < deadline:
            time.sleep(0.01)
        assert cc.counters["bf_pushes"] > n0, (
            "push after pull was stale-rejected: stamp domains diverged"
        )
        # and the other client's keys are visible through the mirror gate
        with cc._bloom_lock:
            assert query_packed_np(cc._bloom, more, cc.num_hashes).all()
        other.close()
        push_be.close()
        be.close()


def test_stale_delta_or_merges_instead_of_dropping():
    """A delta frame that lost the race to a newer snapshot must still
    contribute its SET bits (the server's delta baseline already moved past
    it, so a dropped frame's adds would never be resent)."""
    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.config import BloomConfig, IndexConfig, KVConfig
    from pmdfc_tpu.kv import KV

    cfg = KVConfig(index=IndexConfig(capacity=1 << 12),
                   bloom=BloomConfig(num_bits=1 << 13),
                   paged=True, page_words=W)
    kv = KV(cfg)
    cc = CleanCacheClient(DirectBackend(kv))
    full0 = kv.packed_bloom()
    cc.receive_bloom_full(full0, t_snap=time.monotonic())
    t_stale = time.monotonic()
    ks = _keys(6, seed=21)
    kv.insert(ks, _pages(ks))
    packed = kv.packed_bloom()
    wpb = 16
    diff = (full0 ^ packed).reshape(-1, wpb)
    idx = np.flatnonzero((diff != 0).any(axis=1))
    blocks = packed.reshape(-1, wpb)[idx]
    # a fresh snapshot arrives first...
    cc.receive_bloom_full(packed, t_snap=time.monotonic())
    # ...then the delta computed EARLIER lands (stale stamp): its set bits
    # must merge, not vanish
    before = cc._bloom.copy()
    cc.receive_bloom_blocks(idx, blocks, wpb, t_snap=t_stale)
    with cc._bloom_lock:
        assert (cc._bloom & before == before).all(), "stale delta cleared bits"
        assert query_packed_np(cc._bloom, ks, cc.num_hashes).all()


# --- pipelined wire protocol + cross-connection coalescer (netpipe) -----


def test_pipeline_negotiation_and_env_killswitch():
    """Every connection to a default `NetServer` (no `net=`) negotiates
    the pipelined channel (seq-echo ack, bit 0 of the HOLASI count
    field) and its put, get and invalidate answer as on any channel; a
    client refuses a server that withholds the ack."""
    import socket as socklib

    from pmdfc_tpu.runtime import net as net_mod

    srv, _ = _local_server()
    with srv:
        for flags in (net_mod.PIPE_FLAG, 0):
            with socklib.create_connection(("127.0.0.1", srv.port)) as c:
                net_mod._send_msg(c, net_mod.MSG_HOLA,
                                  status=net_mod.CHAN_OP | flags, words=W)
                mt, status, count, *_ = net_mod._recv_msg(c)
                assert (mt, status, count & 1) == (net_mod.MSG_HOLASI, 0, 1)
        for i in range(2):
            with TcpBackend("127.0.0.1", srv.port, page_words=W) as be:
                keys = _keys(16, seed=i)
                be.put(keys, _pages(keys))
                out, found = be.get(keys)
                assert found.all() and np.array_equal(out, _pages(keys))
                assert be.invalidate(keys[:4]).all()
                _, found = be.get(keys)
                assert not found[:4].any() and found[4:].all()
        assert srv.stats["flushes"] >= 1

    lsock = socklib.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)

    def no_ack_server():
        conn, _ = lsock.accept()
        with conn:
            net_mod._recv_msg(conn)
            net_mod._send_msg(conn, net_mod.MSG_HOLASI, words=W)

    th = threading.Thread(target=no_ack_server, daemon=True)
    th.start()
    with pytest.raises(ConnectionError, match="pipelined"):
        TcpBackend("127.0.0.1", lsock.getsockname()[1], page_words=W,
                    keepalive_s=None)
    th.join(5)
    lsock.close()


def test_default_server_builds_one_backend_and_fuses_connections():
    """`NetServer(factory)` with no `net=` serves through the flush
    loop: the factory runs exactly once, in `start()`, and two
    connections' GETs staged together are served in ONE flush."""
    calls = []
    gate, entered = threading.Event(), threading.Event()

    class Gated(LocalBackend):
        blocking = False

        def get(self, keys):
            if self.blocking:
                self.blocking = False
                entered.set()
                gate.wait(10)
            return super().get(keys)

    def factory():
        calls.append(1)
        return Gated(page_words=W, capacity=1 << 10)

    srv = NetServer(factory)
    assert calls == []
    srv.start()
    with srv:
        assert len(calls) == 1
        bes = [TcpBackend("127.0.0.1", srv.port, page_words=W,
                          keepalive_s=None) for _ in range(3)]
        keys = [_keys(8, seed=30 + i) for i in range(3)]
        for be, k in zip(bes, keys):
            be.put(k, _pages(k))
        # park the flush thread inside one GET, stage two more behind it
        srv._co_backend.blocking = True
        out: dict = {}

        def get(i):
            out[i] = bes[i].get(keys[i])

        ts = [threading.Thread(target=get, args=(0,))]
        ts[0].start()
        assert entered.wait(10)
        f0, c0 = srv.stats["flushes"], srv.stats["coalesced_ops"]
        ts += [threading.Thread(target=get, args=(i,)) for i in (1, 2)]
        for t in ts[1:]:
            t.start()
        deadline = time.time() + 10
        while len(srv._staged) < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert len(srv._staged) == 2
        gate.set()
        for t in ts:
            t.join(10)
        assert srv.stats["flushes"] - f0 == 1
        assert srv.stats["coalesced_ops"] - c0 == 2
        for i in range(3):
            found_pages, found = out[i]
            assert found.all()
            assert np.array_equal(found_pages, _pages(keys[i]))
        assert len(calls) == 1
        for be in bes:
            be.close()


@pytest.mark.netpipe
def test_coalesced_server_fuses_across_connections():
    """The tentpole invariant: N connections' verbs land in shared fused
    flushes (flush_max > 1), results route back per connection with no
    cross-connection bleed."""
    from pmdfc_tpu.config import NetConfig

    # long dwell + generous settle so the barrier-released ops coalesce
    # deterministically
    srv, _ = _local_server(net=NetConfig(flush_timeout_us=200_000,
                                         settle_us=30_000))
    with srv:
        n_conns = 6
        bes = [TcpBackend("127.0.0.1", srv.port, page_words=W,
                          keepalive_s=None) for _ in range(n_conns)]
        all_keys = [_keys(24, seed=60 + i) for i in range(n_conns)]
        barrier = threading.Barrier(n_conns)
        errs: list = []

        def worker(i):
            try:
                barrier.wait()
                bes[i].put(all_keys[i], _pages(all_keys[i]))
                out, found = bes[i].get(all_keys[i])
                assert found.all(), i
                assert np.array_equal(out, _pages(all_keys[i])), i
                # a miss probe stays a miss (padding rows match nothing)
                _, f2 = bes[i].get(_keys(8, seed=90 + i))
                assert not f2.any(), i
            except Exception as e:  # noqa: BLE001
                errs.append((i, repr(e)))

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(n_conns)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs, errs
        assert srv.stats["flushes"] >= 1
        assert srv.stats["flush_max"] > 1, (
            "no cross-connection coalescing happened")
        for b in bes:
            b.close()


@pytest.mark.netpipe
def test_pipelined_storm_shared_backend():
    """8 threads share ONE pipelined TcpBackend: replies must match by
    sequence id under full-window concurrency — every page content-
    verifies against its own key, no waiter ever wedges."""
    from pmdfc_tpu.config import NetConfig

    shared = LocalBackend(page_words=W, capacity=1 << 13)
    srv = NetServer(lambda: shared, net=NetConfig()).start()
    with srv:
        be = TcpBackend("127.0.0.1", srv.port, page_words=W,
                        keepalive_s=None, window=16)
        errs: list = []

        def storm(i):
            try:
                keys = _keys(48, seed=200 + i)
                pages = _pages(keys)
                for _ in range(6):
                    be.put(keys, pages)
                    out, found = be.get(keys)
                    assert found.all(), i
                    assert np.array_equal(out, pages), i
                hit = be.invalidate(keys[:8])
                assert hit.all(), i
                _, f2 = be.get(keys[:8])
                assert not f2.any(), i
            except Exception as e:  # noqa: BLE001
                errs.append((i, repr(e)))

        ts = [threading.Thread(target=storm, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts), "stuck waiter"
        assert not errs, errs
        be.close()


@pytest.mark.netpipe
def test_coalesced_vs_lockstep_conformance():
    """The served flush loop against an independent reference: a seeded
    120-verb mixed workload answered over the wire by a `NetServer` is
    verb-for-verb IDENTICAL to the same workload applied in-process to a
    twin `DirectBackend(KV)` with the same config."""
    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.config import BloomConfig, IndexConfig, KVConfig, NetConfig
    from pmdfc_tpu.kv import KV

    def run(be):
        results = []
        rng = np.random.default_rng(77)
        universe = _keys(256, seed=77)
        for _ in range(120):
            op = int(rng.integers(4))
            lo = int(rng.integers(0, 240))
            n = int(rng.integers(1, 16))
            sel = universe[lo:lo + n]
            if op == 0:
                be.put(sel, _pages(sel))
                results.append(("put", n))
            elif op in (1, 2):
                out, found = be.get(sel)
                found = np.asarray(found, bool)
                results.append(("get", found.tolist(),
                                np.asarray(out)[found].tolist()))
            else:
                hit = np.asarray(be.invalidate(sel), bool)
                results.append(("inval", hit.tolist()))
        return results

    srv, _ = _kv_server(
        capacity=1 << 12,
        net=NetConfig(flush_timeout_us=5000, settle_us=200))
    with srv, TcpBackend("127.0.0.1", srv.port, page_words=W,
                         keepalive_s=None) as be:
        served = run(be)
    cfg = KVConfig(index=IndexConfig(capacity=1 << 12),
                   bloom=BloomConfig(num_bits=1 << 13),
                   paged=True, page_words=W)
    want = run(DirectBackend(KV(cfg)))
    assert served == want, "the served flush loop diverged from the twin"


# --- net-level chaos drills (ChaosProxy, deterministic armed faults) ----


def _proxied_client(srv, proxy, **kw):
    """ReconnectingClient whose factory dials the server THROUGH the
    chaos proxy — the full rung-2/rung-3 client stack."""
    from pmdfc_tpu.runtime.failure import ReconnectingClient

    kw.setdefault("op_timeout_s", 2.0)

    def factory():
        return TcpBackend("127.0.0.1", proxy.port, page_words=W,
                          keepalive_s=None, **kw)

    return ReconnectingClient(factory, page_words=W, retry_delay_s=0.01,
                              max_retry_delay_s=0.2, seed=3)


def test_chaos_bitflip_is_dropped_frame_then_reconnect():
    """A bit-flipped frame must fail the CRC (bad_frames), kill only that
    connection, degrade the op legally, and the client must re-attach and
    verify content afterwards."""
    from pmdfc_tpu.runtime.failure import ChaosProxy

    srv, _ = _local_server()
    with srv, ChaosProxy("127.0.0.1", srv.port, seed=11) as px:
        rc = _proxied_client(srv, px)
        keys = _keys(32, seed=51)
        pages = _pages(keys)
        rc.put(keys, pages)
        px.flip_next(1)
        out, found = rc.get(keys)  # flipped request: legal degraded result
        assert not found.any() and (out == 0).all()
        deadline = time.time() + 5
        ok = False
        while time.time() < deadline:
            out, found = rc.get(keys)
            if found.all():
                ok = True
                break
            time.sleep(0.02)
        assert ok, "client never recovered after the flipped frame"
        np.testing.assert_array_equal(out, pages)
        assert srv.stats["bad_frames"] >= 1
        assert px.stats["flipped_frames"] == 1
        assert rc.stats()["disconnects"] >= 1
        rc.close()


def test_chaos_duplicate_frame_desync_is_detected():
    """A duplicated request frame desynchronizes the reply stream; the
    client's reply validation must detect it (drop + reconnect), never
    return another op's payload."""
    from pmdfc_tpu.runtime.failure import ChaosProxy

    srv, _ = _local_server()
    with srv, ChaosProxy("127.0.0.1", srv.port, seed=12) as px:
        rc = _proxied_client(srv, px)
        keys = _keys(16, seed=52)
        pages = _pages(keys)
        rc.put(keys, pages)
        px.dup_next(1)
        out, found = rc.get(keys[:8])  # duplicated GETPAGE: 2 replies queued
        # this op's own reply is fine; the NEXT op reads the stale
        # duplicate and must fail the stream, not misparse it
        assert np.array_equal(out[found], _pages(keys[:8])[found])
        rc.put(keys[:4], pages[:4])  # desync detected here (legal drop)
        deadline = time.time() + 5
        ok = False
        while time.time() < deadline:
            out, found = rc.get(keys)
            if found.all():
                ok = True
                break
            time.sleep(0.02)
        assert ok
        np.testing.assert_array_equal(out, pages)
        assert px.stats["duplicated_frames"] == 1
        rc.close()


def test_chaos_truncated_frame_and_half_open_are_bounded():
    """A truncated frame (torn write) kills the connection; a half-open
    proxy (peer vanished, socket alive) must cost at most the op timeout
    — both degrade to legal results in bounded time."""
    from pmdfc_tpu.runtime.failure import ChaosProxy

    srv, _ = _local_server()
    with srv, ChaosProxy("127.0.0.1", srv.port, seed=13) as px:
        rc = _proxied_client(srv, px, op_timeout_s=1.0)
        keys = _keys(8, seed=53)
        pages = _pages(keys)
        rc.put(keys, pages)
        px.truncate_next(1)
        out, found = rc.get(keys)
        assert not found.any()
        deadline = time.time() + 5
        while not rc.connected and time.time() < deadline:
            rc.get(keys[:1])
            time.sleep(0.02)
        assert rc.connected
        px.half_open_next(1)
        t0 = time.monotonic()
        out, found = rc.get(keys)  # swallowed: recv times out
        dt = time.monotonic() - t0
        assert not found.any()
        assert dt < 4.0, f"half-open hang not bounded ({dt:.1f}s)"
        assert px.stats["truncated_frames"] == 1
        assert px.stats["half_open_drops"] >= 1
        rc.close()


def test_kill_op_conn_is_idempotent():
    """Two phases deciding to kill the SAME connection (a fused flush
    racing the reader's own teardown, or two phases sharing a sick
    conn's ops) must drop it exactly once: the second `_kill_op_conn`
    is a no-op — never a re-shutdown/re-notify against a possibly
    already-reused fd."""
    import socket as socket_mod

    from pmdfc_tpu.runtime.net import _ConnState, _StagedOp

    srv, _ = _local_server()
    with srv:
        a, b = socket_mod.socketpair()
        cs = _ConnState(a, {"addr": "drill"})
        op1 = _StagedOp(cs, 0, 1, 0, 0)
        op2 = _StagedOp(cs, 0, 2, 0, 0)  # second phase, same conn
        drops: list = []
        orig = srv._drop_conn
        srv._drop_conn = lambda conn: drops.append(conn)
        try:
            srv._kill_op_conn(op1)
            assert not cs.alive and len(drops) == 1
            srv._kill_op_conn(op2)
            assert len(drops) == 1, "second kill re-dropped the conn"
            assert not cs.alive
        finally:
            srv._drop_conn = orig
        a.close()
        b.close()
