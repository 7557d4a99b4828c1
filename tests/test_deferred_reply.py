"""GET replies framed by the connection writers (`NetServer._frame_gets`).

- Wire identity: through a real coalesced `NetServer` and two
  `TcpBackend` connections, one flush of PUTs and all-hit, interior-miss,
  edge-miss and all-miss GETs on a 1-shard plane, a 2-shard plane (whose
  router reorders lanes), `DirectBackend`, and a 1-shard plane behind a
  wrapper whose answers offer only `found` and `hit_rows` (as a
  forwarding proxy's do). The answers equal a
  host-dict reference, each connection's replies come in its op order
  (puts first, the phase order), every GET frame is byte for byte the
  frame `_frame_views` builds eagerly from `PlaneGets.hit_rows` (or the
  dense pages), each unframed item's size is its frames' size, and
  `reply_view_frames` counts exactly the verbs whose hit rows are one
  ascending run of the fetched buffer. The sliced CRC of `_frame_views`
  is the protocol's whole-frame CRC at every size.
- Back-pressure: a peer that stops reading is dropped at the backlog cap,
  counted from the unframed items' sizes, while another connection keeps
  being served; a connection whose writer lags holds one fetched buffer
  queued, every later item a copy of its own rows, framed the same.
- Lifetime: a connection dropped with items queued, or whose item fails
  to build, costs that connection only and no frame.
"""

import collections
import socket
import threading
import time

import numpy as np
import pytest

from pmdfc_tpu.client.backends import LocalBackend
from pmdfc_tpu.config import BloomConfig, IndexConfig, KVConfig, NetConfig
from pmdfc_tpu.runtime import net
from pmdfc_tpu.runtime.net import NetServer, TcpBackend

W = 16
N_VERBS = 9  # verbs in every flush of the identity test (`flush_ops`)
REPLIES = (net.MSG_SUCCESS, net.MSG_SENDPAGE, net.MSG_NOTEXIST)


def _keys(n, seed):
    flat = np.random.default_rng(seed).choice(1 << 20, size=n,
                                              replace=False)
    return np.stack([flat >> 10, flat & 0x3FF], -1).astype(np.uint32)


def _pages(keys, w=W):
    return ((keys[:, 0] * np.uint32(31) + keys[:, 1])[:, None]
            + np.arange(1, w + 1, dtype=np.uint32)[None, :])


def _backend(kind):
    cfg = KVConfig(index=IndexConfig(capacity=1 << 12),
                   bloom=BloomConfig(num_bits=1 << 14),
                   paged=True, page_words=W)
    if kind == "direct":
        from pmdfc_tpu.client.backends import DirectBackend
        from pmdfc_tpu.kv import KV

        return DirectBackend(KV(cfg))
    from pmdfc_tpu.config import MeshConfig
    from pmdfc_tpu.parallel.plane import make_serving_backend

    be = make_serving_backend(
        cfg, MeshConfig(n_shards=2 if kind == "plane2" else 1))
    if kind == "wrapped":
        inner = be.get_fused
        be.get_fused = lambda keys: _Bare(inner(keys))
    return be


class _Bare:
    """A GET answer with `PlaneGets`' `found` and `hit_rows` only."""

    def __init__(self, res):
        self.found, self.hit_rows = res.found, res.hit_rows
        self.inner = res


def _capture(be) -> list:
    """Record each GET phase's keys and fetched result: a `PlaneGets`,
    or `(pages, found)` from a dense backend."""
    seen: list = []
    if getattr(be, "get_fused", None) is not None:
        inner = be.get_fused

        def get_fused(keys):
            res = inner(keys)
            seen.append((np.array(keys), res))
            return res

        be.get_fused = get_fused
    else:
        inner = be.get

        def get(keys):
            pages, found = inner(keys)
            seen.append((np.array(keys),
                         (np.asarray(pages), np.asarray(found, bool))))
            return pages, found

        be.get = get
    return seen


def _eager_frame(res, lo, hi, seq) -> bytes:
    """The frame the flush thread used to build for one GET verb."""
    if isinstance(res, tuple):
        pages, found = res
        f = found[lo:hi]
        rows = np.ascontiguousarray(pages[lo:hi][f], np.uint32)
    else:
        f = np.asarray(res.found, bool)[lo:hi]
        rows = res.hit_rows(lo, hi)
        dense = getattr(res, "inner", res).dense()  # no slice, no gather
        np.testing.assert_array_equal(rows, dense[lo:hi][f])
    views = net._frame_views(
        net.MSG_SENDPAGE if f.any() else net.MSG_NOTEXIST,
        (f.astype(np.uint8), rows), status=seq, count=hi - lo, words=W)
    return b"".join(bytes(v) for v in views)


def _one_run(res, lo, hi) -> bool:
    if isinstance(res, _Bare):  # a wrapper hands on the plane's rows
        res = res.inner
    if isinstance(res, tuple):
        pos, found = np.arange(len(res[1])), res[1]
    else:
        pos, found = res._rb.pos, np.asarray(res.found, bool)
    sel = pos[lo:hi][found[lo:hi]]
    return len(sel) == 0 or np.array_equal(
        sel, np.arange(sel[0], sel[0] + len(sel)))


def _frames(stream) -> list:
    """(type, seq, keys or None, raw bytes) of each frame in a stream."""
    out, i = [], 0
    while i < len(stream):
        _, mt, seq, count, _, _, dlen, _ = net._HDR.unpack_from(stream, i)
        n = net._HDR.size + dlen
        keys = None
        if mt in (net.MSG_GETPAGE, net.MSG_PUTPAGE):
            keys = np.frombuffer(bytes(stream[i + net._HDR.size:
                                              i + net._HDR.size + 8 * count]),
                                 np.uint32).reshape(count, 2)
        out.append((mt, seq, keys, bytes(stream[i:i + n])))
        i += n
    return out


@pytest.fixture()
def wire(monkeypatch):
    """Every byte each socket receives, keyed by (own, peer) address."""
    streams: dict = collections.defaultdict(bytearray)
    recv = net._recv_into

    def recording(sock, view):
        recv(sock, view)
        streams[(sock.getsockname(), sock.getpeername())] += view

    monkeypatch.setattr(net, "_recv_into", recording)
    return streams


def _concurrently(calls):
    """Run every `fn()` at once (each a pipelined verb); their results."""
    out = [None] * len(calls)
    go = threading.Barrier(len(calls))

    def run(i, fn):
        go.wait()
        out[i] = fn()

    ts = [threading.Thread(target=run, args=(i, fn))
          for i, fn in enumerate(calls)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    return out


@pytest.mark.parametrize("nbytes", [0, 1, 4095, 4096, 4097, (128 << 10) + 3])
def test_sliced_crc_is_the_protocol_crc(nbytes):
    """`_frame_views` CRCs in 4 KiB slices: the frame carries the CRC of
    the whole frame with its CRC field zeroed, which `_recv_msg` checks."""
    body = np.random.default_rng(nbytes).integers(0, 256, nbytes, np.uint8)
    mask = np.ones(3, np.uint8)
    frame = b"".join(bytes(v) for v in net._frame_views(
        net.MSG_SENDPAGE, (mask, body), status=9, count=3, words=W))
    zeroed = frame[:net._CRC_OFF] + bytes(4) + frame[net._CRC_OFF + 4:]
    crc, = net.struct.unpack_from("<I", frame, net._CRC_OFF)
    assert crc == net.zlib.crc32(zeroed)
    assert frame[net._HDR.size:] == mask.tobytes() + body.tobytes()


@pytest.mark.parametrize("kind", ["plane1", "plane2", "direct", "wrapped"])
def test_writer_frames_equal_eager_frames(kind, wire):
    be = _backend(kind)
    seen = _capture(be)
    # every flush closes at exactly N_VERBS staged verbs
    srv = NetServer(lambda: be, net=NetConfig(
        flush_ops=N_VERBS, flush_timeout_us=20_000_000,
        settle_us=20_000_000))
    sizes: list = []
    build = srv._frame_gets

    def checked(it):
        frames, n_view = build(it)
        sizes.append((it.nbytes, sum(v.nbytes for fr in frames
                                     for v in fr)))
        return frames, n_view

    srv._frame_gets = checked
    stored = _keys(72, 1)
    new = _keys(16, 2)
    new = new[~(new[:, None] == stored[None]).all(-1).any(1)][:8]
    gone = _keys(400, 3)
    gone = gone[~(gone[:, None] == np.concatenate([stored, new])[None])
                .all(-1).any(1)][:7]
    ref = {}
    with srv.start(), \
            TcpBackend("127.0.0.1", srv.port, page_words=W) as c1, \
            TcpBackend("127.0.0.1", srv.port, page_words=W) as c2:
        chunks = np.split(stored, N_VERBS)
        _concurrently([lambda k=k, c=c: c.put(k, _pages(k))
                       for k, c in zip(chunks, [c1, c2] * 5)])
        ref.update((tuple(k), p) for k, p in zip(stored, _pages(stored)))
        mark = {k: len(v) for k, v in wire.items()}
        s0 = dict(srv.stats)
        gets = [
            (c1, stored[0:8]),                                # all hit
            (c1, np.stack([stored[8], stored[9], gone[0],
                           stored[10], stored[11]])),         # interior
            (c1, np.stack([gone[1], stored[12], stored[13],
                           gone[2]])),                        # edges
            (c1, gone[3:6]),                                  # all miss
            (c2, stored[16:24]),                              # all hit
            (c2, np.stack([stored[24], gone[6], stored[25]])),
            (c2, new[4:8]),                          # its own put, hit
        ]
        calls = [lambda: c1.put(new[:4], _pages(new[:4])),
                 lambda: c2.put(new[4:], _pages(new[4:]))]
        calls += [lambda c=c, k=k: c.get(k) for c, k in gets]
        got = _concurrently(calls)
        ref.update((tuple(k), p) for k, p in zip(new, _pages(new)))
        s1 = dict(srv.stats)
        streams = {k: v[mark.get(k, 0):] for k, v in wire.items()}
        conns = [(c._sock.getsockname(), c._sock.getpeername())
                 for c in (c1, c2)]

    assert s1["flushes"] - s0["flushes"] == 1
    # answers: the host-dict reference
    for (_, keys), (out, found) in zip(gets, got[2:]):
        want = np.array([tuple(k) in ref for k in keys])
        np.testing.assert_array_equal(found, want)
        for k, row, f in zip(keys, out, found):
            if f:
                np.testing.assert_array_equal(row, ref[tuple(k)])
    flush_keys, res = seen[-1]
    if kind == "plane2":
        assert not np.array_equal(res._rb.pos, np.arange(len(res.found)))
    n_view = 0
    for me, peer in conns:
        asked = [f for f in _frames(streams[(peer, me)])
                 if f[0] in (net.MSG_PUTPAGE, net.MSG_GETPAGE)]
        replies = [f for f in _frames(streams[(me, peer)])
                   if f[0] in REPLIES]
        # per connection: puts (their phase runs first), then gets, each
        # in the order the connection sent them
        want = ([s for mt, s, _, _ in asked if mt == net.MSG_PUTPAGE]
                + [s for mt, s, _, _ in asked if mt == net.MSG_GETPAGE])
        assert [s for _, s, _, _ in replies] == want
        raw = {s: r for _, s, _, r in replies}
        for mt, seq, keys, _ in asked:
            if mt != net.MSG_GETPAGE:
                continue
            lo = int(np.flatnonzero((flush_keys == keys[0]).all(1))[0])
            hi = lo + len(keys)
            np.testing.assert_array_equal(flush_keys[lo:hi], keys)
            assert raw[seq] == _eager_frame(res, lo, hi, seq)
            n_view += _one_run(res, lo, hi)
    d = {k: s1[k] - s0[k] for k in ("reply_deferred_frames",
                                    "reply_view_frames",
                                    "reply_build_errors")}
    assert d == {"reply_deferred_frames": len(gets),
                 "reply_view_frames": n_view, "reply_build_errors": 0}
    if kind != "plane2":
        # request order kept: only the two interior-miss verbs gather
        assert n_view == len(gets) - 2
    assert sizes and all(a == b for a, b in sizes)
    assert s1["reply_build_us"] > s0["reply_build_us"]


def test_stalled_reader_dropped_at_cap_while_others_served():
    """A peer that stops reading: its unframed GET replies pile up past
    the cap (2 x max_frame_bytes + 1 MiB) on their computed sizes, and
    the connection is dropped; the other connection is served."""
    w = 1024
    shared = LocalBackend(page_words=w, capacity=1 << 12)
    srv = NetServer(lambda: shared, net=NetConfig(),
                    max_frame_bytes=1 << 18).start()
    keys = _keys(32, 5)
    pages = _pages(keys, w)
    sent = 256  # replies of 128 KiB each: far past any socket buffers
    with srv, TcpBackend("127.0.0.1", srv.port, page_words=w) as good:
        good.put(keys, pages)
        with srv._lock:
            n_conns = len(srv._conns)
        a = socket.socket()
        a.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        a.connect(("127.0.0.1", srv.port))
        net._send_msg(a, net.MSG_HOLA, status=net.CHAN_OP | net.PIPE_FLAG,
                      count=77, words=w, stamp=77)
        assert net._recv_msg(a)[0] == net.MSG_HOLASI
        try:
            for seq in range(1, sent + 1):
                net._send_frame(a, net.MSG_GETPAGE,
                                (np.ascontiguousarray(keys),),
                                status=seq, count=len(keys))
        except OSError:
            pass  # dropped while still asking: what the cap is for
        deadline = time.monotonic() + 30
        while True:
            out, found = good.get(keys)
            assert found.all()
            np.testing.assert_array_equal(out, pages)
            with srv._lock:
                if len(srv._conns) == n_conns:
                    break
            assert time.monotonic() < deadline, "stalled peer never dropped"
            time.sleep(0.05)
        # the peer got fewer replies than it asked for, and then the end
        a.settimeout(10)
        got = 0
        try:
            while chunk := a.recv(1 << 20):
                got += len(chunk)
        except OSError:
            pass
        a.close()
        assert got < sent * (net._HDR.size + 32 + 32 * w * 4)
        assert srv.stats["serve_errors"] == 0
        assert srv.stats["reply_build_errors"] == 0


@pytest.mark.parametrize("how", ["dropped", "build_fails"])
def test_lost_connection_costs_only_itself(how):
    """`dropped`: the connection is dropped while a flush's unframed GET
    replies sit in its queue; its writer exits without framing or
    sending. `build_fails`: the item cannot be framed; the writer counts
    it in `reply_build_errors` and drops that connection only. Either
    way the peer reads no byte and the flush loop serves on."""
    shared = LocalBackend(page_words=W, capacity=1 << 12)
    srv = NetServer(lambda: shared, net=NetConfig()).start()
    keys = _keys(8, 7)
    with srv, TcpBackend("127.0.0.1", srv.port, page_words=W) as good:
        good.put(keys, _pages(keys))
        a, b = socket.socketpair()
        cs = net._ConnState(a, srv._client(99))
        s0 = dict(srv.stats)
        ops = [net._StagedOp(cs, net.MSG_GETPAGE, seq, len(k), 0, keys=k)
               for seq, k in ((1, keys[:4]), (2, keys[4:]))]
        for o in ops:
            o.t_ns = time.monotonic_ns()
        with srv._flush_cv:  # staged as its reader would
            srv._staged.extend(ops)
            srv._flush_cv.notify()
        deadline = time.monotonic() + 10
        while not cs.outq:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        with cs.out_cv:
            (item, nbytes), = cs.outq  # one item for the two verbs
        assert isinstance(item, net._GetReplies) and len(item.verbs) == 2
        assert nbytes == cs.out_bytes == 2 * (net._HDR.size + 4 + 4 * W * 4)
        if how == "dropped":
            srv._kill_op_conn(ops[0])
        else:
            item.src = None  # framing it raises
        wt = threading.Thread(target=srv._conn_writer, args=(cs,))
        wt.start()
        wt.join(10)
        assert not wt.is_alive()
        b.settimeout(10)
        assert b.recv(1 << 16) == b""  # closed, and nothing was sent
        b.close()
        s1 = dict(srv.stats)
        out, found = good.get(keys)
        assert found.all()
        np.testing.assert_array_equal(out, _pages(keys))
    assert s1["serve_errors"] == s0["serve_errors"]
    assert s1["reply_deferred_frames"] == s0["reply_deferred_frames"]
    assert s1["reply_build_errors"] - s0["reply_build_errors"] == \
        (how == "build_fails")
    assert not cs.alive


@pytest.mark.parametrize("kind", ["plane1", "direct"])
def test_lagging_writer_holds_one_fetched_buffer(kind):
    """A connection whose writer has not yet taken its last GET item:
    every later flush's item is detached (a copy of its own hit rows,
    its size charged with the copy's position map), so the connection
    holds one fetched buffer queued however many flushes it lags. When
    the writer runs, it sends the frames built eagerly, in order."""
    be = _backend(kind)
    seen = _capture(be)
    srv = NetServer(lambda: be, net=NetConfig()).start()
    stored = _keys(24, 11)
    gone = _keys(64, 12)
    gone = gone[~(gone[:, None] == stored[None]).all(-1).any(1)][:8]
    flushes = [
        [stored[0:4], np.stack([stored[4], gone[0], stored[5]])],
        [gone[1:3]],
        [stored[6:14], stored[14:16]],
        [np.stack([gone[3], stored[16], stored[17], gone[4]]), gone[5:6]],
    ]
    with srv, TcpBackend("127.0.0.1", srv.port, page_words=W) as good:
        good.put(stored, _pages(stored))
        a, b = socket.socketpair()
        cs = net._ConnState(a, srv._client(98))
        s0 = dict(srv.stats)
        want, seq, ops = [], 0, []
        for i, verbs in enumerate(flushes):
            n0 = len(seen)
            staged = []
            for k in verbs:
                seq += 1
                staged.append(net._StagedOp(cs, net.MSG_GETPAGE, seq,
                                            len(k), 0, keys=k))
                staged[-1].t_ns = time.monotonic_ns()
            with srv._flush_cv:  # staged as its reader would, no writer
                srv._staged.extend(staged)
                srv._flush_cv.notify()
            deadline = time.monotonic() + 30
            while len(cs.outq) <= i:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            assert len(seen) == n0 + 1  # one GET phase a flush
            _, res = seen[-1]
            lo = 0
            for o in staged:
                want.append(_eager_frame(res, lo, lo + o.count, o.seq))
                lo += o.count
            ops += staged
        with cs.out_cv:
            items = list(cs.outq)
            assert cs.out_held == 1
        assert [it.held for it, _ in items] == [True] + [False] * 3
        for (it, nbytes), verbs in zip(items, flushes):
            wire_size = sum(net._HDR.size + len(k) for k in verbs) + sum(
                int(np.asarray(it.src.found[lo:hi]).sum()) * W * 4
                for _, lo, hi in it.verbs)
            if it.held:
                assert nbytes == wire_size
            else:  # holds only what it is charged for
                assert nbytes == wire_size + it.src.pos.nbytes
                assert (it.src.rows.nbytes + it.src.found.nbytes
                        + it.src.pos.nbytes) <= nbytes
                assert it.src.rows.base is None
        wt = threading.Thread(target=srv._conn_writer, args=(cs,))
        wt.start()
        expect = b"".join(want)
        got = bytearray()
        b.settimeout(10)
        while len(got) < len(expect):
            chunk = b.recv(1 << 16)
            assert chunk
            got += chunk
        srv._kill_op_conn(ops[0])
        wt.join(10)
        assert not wt.is_alive()
        b.close()
        s1 = dict(srv.stats)
    assert bytes(got) == expect
    assert s1["reply_deferred_frames"] - s0["reply_deferred_frames"] == \
        len(want)
    assert s1["reply_build_errors"] == s0["reply_build_errors"]
    assert s1["reply_build_cpu_us"] >= s0["reply_build_cpu_us"]
