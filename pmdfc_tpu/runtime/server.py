"""KVServer — the driver loop turning coalesced batches into device programs.

This is the role of `server/rdma_svr.cpp`'s per-queue poller threads
(`server_recv_poll_cq` :755 → `process_write_twosided` :319 /
`process_read_odp` :659) redesigned for a TPU: instead of 32 pinned threads
each handling one 4-page verb, ONE driver thread drains every submission
queue into a deep batch and launches one fused device program per op kind.
Within a batch, puts land before deletes before gets, so a client that
pipelines put→get against the same key sees its own write (the reference
client gets the same guarantee from its synchronous per-queue verbs).

Batch shapes are padded up a power-of-two ladder (bounded compile cache —
one program per pow2 width per op kind, NOT one fixed max width: padding a
64-request flush to the 128k ceiling made every flush pay the ceiling's full
compute and transfer, ~100x the useful work at light load). Results fan back
out through the engine's completion slots and, for gets, the page lands in
the request's arena destination slot — the analog of the server RDMA-writing
the page straight into the faulting page's DMA address
(`server/rdma_svr.cpp:706-719`). Page returns are hit-compacted on device
(`kv.get_compact`) so only found rows cross the link, the way the reference
writes only the hit page.

The driver is double-buffered: flush N+1 is launched (JAX async dispatch)
before flush N's results are fetched, overlapping host<->device transfer
with compute — the reference gets the same overlap from per-queue poller
threads with verbs in flight.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from pmdfc_tpu.config import KVConfig
from pmdfc_tpu.kv import KV, _pad_pow2
from pmdfc_tpu.ops.bloom import dirty_blocks as _dirty_blocks
from pmdfc_tpu.runtime import profiler
from pmdfc_tpu.runtime import sanitizer as san
from pmdfc_tpu.runtime.engine import (
    Engine, OP_DEL, OP_GET, OP_GET_EXT, OP_INS_EXT, OP_PUT)
from pmdfc_tpu.utils.timers import Reporter, Timers


class KVServer:
    def __init__(self, config: KVConfig | None = None,
                 engine: Engine | None = None, kv: KV | None = None,
                 report_every_s: float = 0.0, pad_to: int | None = None,
                 bf_push_s: float = 0.0, bf_block_bytes: int = 8192,
                 fault_injector=None, mesh=None):
        self.config = config or KVConfig()
        # mesh= mode: the driver's phases become shard_map programs over
        # a named mesh — pass a jax Mesh, an int shard count, or True
        # (all local devices). `PMDFC_MESH=off` ignores the request and
        # serves the single-device path (the conformance kill switch);
        # an explicit kv= always wins over mesh=.
        if mesh is not None and kv is None:
            kv = self._build_mesh_kv(mesh, pad_to)
        self.kv = kv or KV(self.config)
        # duck-typed plane surface (ShardedKV serving verbs): phases
        # launch PlaneHandles instead of the KV async programs
        self._plane = self.kv if hasattr(self.kv, "plane_insert") else None
        self.engine = engine or Engine(
            page_bytes=self.config.page_words * 4
        )
        # pad_floor: ladder lower bound — batches pad to
        # max(pad_floor, next_pow2(n)), keeping the compiled-shape set small
        # under load jitter without inflating deep flushes to one fixed max
        # width. Legacy `pad_to` callers meant "bound the shape set", not
        # "inflate every flush", so it maps onto the floor (clamped: a huge
        # pad_to as floor would reintroduce the pad-to-max fetch defect).
        self.pad_floor = min(pad_to, 1024) if pad_to else 16
        # optional FaultInjector (runtime/failure.py): batch-granular
        # dropped-completion / stall injection for the failure test tier
        self.fault = fault_injector
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.timers = Timers()
        self._reporter: Reporter | None = None
        if report_every_s > 0:
            # the rdpma_indicator analog (`server/rdma_svr.cpp:145-150`)
            self._reporter = Reporter(
                report_every_s,
                sinks=[
                    lambda: f"kv {self.kv.stats()}",
                    lambda: f"engine {self.engine.stats()}",
                    lambda: f"phases {self.timers.report()}",
                ],
            )
        # -- server→client bloom push (the rdpma_bf_sender analog,
        # `server/rdma_svr.cpp:157-251,1361-1363`, with the 8 KB dirty-block
        # delta machinery of `counting_bloom_filter.h:101-107` actually
        # wired in: after the first full push, only changed blocks travel).
        self.bf_push_s = bf_push_s
        self.bf_block_bytes = bf_block_bytes
        self._bf_clients: list = []
        self._bf_last_sent: list[np.ndarray | None] = []
        # guarded-by: _bf_clients, _bf_last_sent
        self._bf_lock = san.lock("KVServer._bf_lock")
        self._bf_thread: threading.Thread | None = None
        self.bf_push_stats = {"cycles": 0, "full_pushes": 0,
                              "delta_pushes": 0, "blocks_pushed": 0}

    def _build_mesh_kv(self, mesh, pad_to=None):
        """Resolve a mesh= request (jax Mesh, int shard count, True =
        all local devices, or a MeshConfig) into a ShardedKV — or None
        = single device when `PMDFC_MESH=off`. One resolution rule,
        shared with the NetServer path (`plane.build_plane_kv`). A
        legacy `pad_to` (bound-the-shape-set) carries onto the plane
        router's ladder floor unless an explicit MeshConfig wins."""
        from pmdfc_tpu.config import MeshConfig
        from pmdfc_tpu.parallel.plane import build_plane_kv

        knobs = None
        if pad_to and not isinstance(mesh, MeshConfig):
            # largest pow2 <= the (clamped) legacy floor — the router
            # floor must be a power of two
            f = min(pad_to, 1024)
            knobs = MeshConfig(pad_floor=1 << (f.bit_length() - 1))
        return build_plane_kv(self.config, mesh, knobs=knobs)

    # -- lifecycle --
    def start(self) -> "KVServer":
        # Start-once — `with KVServer(...).start()` would otherwise spawn a
        # SECOND driver loop via __enter__: two loops race the KV state's
        # read-modify-write (silently losing inserts), and stop() would
        # join only the newest thread, leaving a stray driver alive on a
        # freed engine. One server = one driver, ever (restart after stop
        # is not supported: _stop is never cleared).
        if self._thread is not None:
            return self
        from pmdfc_tpu.runtime import timeseries

        # same windowed-series contract as the NetServer: an engine-
        # transport server's MSG-less monitors (health pollers, flight
        # dumps) still get the rate trajectory. Unconditional like the
        # NetServer's: tick() honors the kill switch, and a live
        # re-enable must find the sampler armed.
        timeseries.ensure_collector()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="pmdfc-driver")
        self._thread.start()
        if self._reporter:
            self._reporter.start()
        if self.bf_push_s > 0:
            self._bf_thread = threading.Thread(
                target=self._bf_push_loop, daemon=True, name="bf-sender"
            )
            self._bf_thread.start()
        return self

    def warmup(self, max_width: int | None = None,
               kinds: tuple = ("put", "get", "del")) -> int:
        """Pre-compile every ladder shape up to `max_width` (default: the
        engine's flush cap) so no flush pays a fresh XLA compile inside its
        latency budget — the guarantee the old fixed-pad design bought with
        a 100x fetch tax, restored here as an explicit warmup step.

        Uses all-INVALID key batches: they compile and execute the real
        programs but match nothing, place nothing, and touch no pool row.
        Call before serving latency-sensitive traffic; skip it when compile
        time is dearer than the first-flush blip (e.g. short tests).
        Returns the
        number of (kind, width) programs warmed.
        """
        from pmdfc_tpu.utils.keys import INVALID_WORD

        cap = max_width or self.engine.batch
        if self._plane is not None:
            # mesh plane: ONE shared warm loop (walks the router's own
            # pad-floor ladder; see plane.warm_plane for the
            # INVALID-keys-hash-to-one-shard width rule)
            from pmdfc_tpu.parallel.plane import warm_plane

            return warm_plane(self._plane, cap, kinds)
        w, n = self.pad_floor, 0
        widths = []
        while w <= cap:
            widths.append(w)
            w <<= 1
        for w in widths:
            keys = np.full((w, 2), INVALID_WORD, np.uint32)
            if "put" in kinds:
                vw = (self.config.page_words if self.config.paged else 2)
                self.kv.insert_async(keys, np.zeros((w, vw), np.uint32),
                                     pad_floor=self.pad_floor)
                n += 1
            if "del" in kinds:
                self.kv.delete_async(keys, pad_floor=self.pad_floor)
                n += 1
            if "get" in kinds:
                if self.config.paged:
                    _, _, _, nf, _ = self.kv.get_compact_async(
                        keys, pad_floor=self.pad_floor)
                    int(nf)
                else:
                    _, found, _ = self.kv.get_async(
                        keys, pad_floor=self.pad_floor)
                    np.asarray(found)
                n += 1
        return n

    def checkpoint(self, path: str, delta: bool = False) -> dict:
        """Crash-safe snapshot of the live KV under ITS lock.

        `checkpoint.save(server.kv.state, ...)` from another thread races
        the driver's donating dispatches — the snapshot would read donated
        (freed) buffers. `KV.snapshot` serializes against the dispatch
        path, so the saved state is always a consistent op boundary.
        With ``delta=True`` only rows dirtied since the previous link of
        the chain are written (full fallback when no chain is armed)."""
        return self.kv.snapshot(path, delta=delta)

    def health(self) -> dict:
        """One integrity/degradation surface for monitors and drills:
        KV stats (incl. `corrupt_pages`), engine stats, tier counters
        (hot/cold placement + ballooning, when the tiered pool is on),
        and driver-level serve errors — the counters the chaos tier
        asserts on."""
        # tier counters ride the "kv" block (KV.stats() merges them when
        # the tiered pool is active) — ONE authoritative snapshot, not a
        # second fetch that could disagree mid-serving
        out = {
            "kv": self.kv.stats(),
            "engine": self.engine.stats(),
            "serve_errors": getattr(self, "errors", 0),
        }
        info = getattr(self.kv, "recovery_info", None)
        if info is not None:
            out["recovery"] = info()
        return out

    def stop(self) -> None:
        self._stop.set()
        if self._reporter:
            self._reporter.stop()
        if self._bf_thread:
            self._bf_thread.join(timeout=10)
        if self._thread:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                # Driver thread wedged (device hang?): freeing the native
                # queues under it would be a use-after-free. Leak instead.
                raise RuntimeError(
                    "driver thread did not exit; leaking engine")
        self.engine.close()

    # -- bloom push --

    def register_bf_client(self, client) -> None:
        """Attach a client mirror (anything with `receive_bloom_full` /
        `receive_bloom_blocks`) — the MR-exchange analog for the filter."""
        with self._bf_lock:
            self._bf_clients.append(client)
            self._bf_last_sent.append(None)

    def push_bloom_now(self) -> dict:
        """One push cycle: full filter to new clients, dirty blocks to the
        rest. Returns this cycle's counters.

        `t_snap` is sampled BEFORE the filter is read: every put whose
        completion a client observed before `t_snap` is provably contained
        in this snapshot, so the client may retire its overlay entry — the
        stamp that closes the push-races-put false-negative window.
        """
        import time as _time

        t_snap = _time.monotonic()
        packed = self.kv.packed_bloom()
        if packed is None:
            return {"blocks": 0}
        wpb = self.bf_block_bytes // 4
        can_delta = len(packed) % wpb == 0
        pushed_blocks = 0
        with self._bf_lock:
            clients = list(zip(range(len(self._bf_clients)),
                               self._bf_clients, self._bf_last_sent))
        sent: list[int] = []
        for i, client, last in clients:
            try:
                if last is None or not can_delta:
                    client.receive_bloom_full(packed, t_snap=t_snap)
                    self.bf_push_stats["full_pushes"] += 1
                else:
                    dirty = np.asarray(_dirty_blocks(
                        last, packed, block_bytes=self.bf_block_bytes
                    ))
                    idx = np.nonzero(dirty)[0]
                    if len(idx):
                        blocks = packed.reshape(-1, wpb)[idx]
                        client.receive_bloom_blocks(idx, blocks, wpb,
                                                    t_snap=t_snap)
                        pushed_blocks += len(idx)
                    self.bf_push_stats["delta_pushes"] += 1
                sent.append(i)
            except Exception as e:  # noqa: BLE001 — one bad sink must not
                # kill the sender thread for every other client
                self.bf_push_stats["errors"] = (
                    self.bf_push_stats.get("errors", 0) + 1)
                print(f"[kv-server] bf push to client {i} failed: {e!r}")
        with self._bf_lock:
            for i in sent:
                # `packed` is freshly allocated each cycle and never
                # mutated after this point; sinks copy what they keep, and
                # last_sent is only read for XOR diffing — share it.
                self._bf_last_sent[i] = packed
        self.bf_push_stats["cycles"] += 1
        self.bf_push_stats["blocks_pushed"] += pushed_blocks
        return {"blocks": pushed_blocks, "clients": len(clients)}

    def _bf_push_loop(self) -> None:
        while not self._stop.wait(self.bf_push_s):
            self.push_bloom_now()

    def __enter__(self) -> "KVServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- driver --
    def _loop(self) -> None:
        pending: tuple | None = None  # (reqs, launch handles) in flight
        while not self._stop.is_set():
            # With a flush in flight, don't dwell in the coalescer spin:
            # grab whatever is queued (timeout 0) and launch it, THEN go
            # block on the in-flight results — that is the overlap.
            reqs = self.engine.pop_batch(
                timeout_us=0 if pending is not None else None
            )
            nxt = None
            if len(reqs):
                try:
                    nxt = (reqs, self._launch(reqs))
                except Exception as e:  # noqa: BLE001
                    self._fail_batch(reqs, e)
            if pending is not None:
                preqs, handles = pending
                try:
                    self._finalize(preqs, handles)
                except Exception as e:  # noqa: BLE001
                    self._fail_batch(preqs, e)
            pending = nxt
        if pending is not None:
            preqs, handles = pending
            try:
                self._finalize(preqs, handles)
            except Exception as e:  # noqa: BLE001
                self._fail_batch(preqs, e)

    def _fail_batch(self, reqs: np.ndarray, e: Exception) -> None:
        # A batch must never kill the driver silently: fail ITS requests
        # (clients see -2, not a hang) and keep serving.
        import traceback

        from pmdfc_tpu.runtime import telemetry as tele

        traceback.print_exc()
        print(f"[kv-server] serve failed: {e!r}; "
              f"failing {len(reqs)} requests")
        self.errors = getattr(self, "errors", 0) + 1
        tele.rung("phase_failure", tier="engine", requests=len(reqs),
                  error=repr(e))
        self.engine.complete(
            reqs["req_id"], np.full(len(reqs), -2, np.int32)
        )

    def serve_batch(self, reqs: np.ndarray) -> None:
        """Run one coalesced batch synchronously (launch + finalize)."""
        handles = self._launch(reqs)
        self._finalize(reqs, handles)

    def _launch(self, reqs: np.ndarray):
        """Dispatch one coalesced batch: puts, then deletes, then gets.

        Returns opaque handles holding device arrays; nothing blocks on the
        device here. Phase timers mirror the reference's `-DTIME_CHECK`
        accumulators (write/read/poll µs, `server/rdma_svr.cpp:64-76`).
        """
        if self.fault is not None and self.fault.on_batch(reqs) == "drop":
            return None  # completions vanish; clients must time out, not hang

        keys = np.stack([reqs["khi"], reqs["klo"]], axis=-1)
        handles: dict = {}
        floor = self.pad_floor

        puts = reqs["op"] == OP_PUT
        if puts.any():
            if self.config.paged:
                vals = self.engine.arena[reqs["page_off"][puts]]
            else:
                nk = int(puts.sum())
                vals = np.stack(
                    [np.zeros(nk, np.uint32), reqs["page_off"][puts]],
                    axis=-1,
                )
            if self._plane is not None:
                # mesh phase: host-routed shard_map program; results
                # come back request-ordered from the handle's fetch
                handles["puts"] = (
                    puts, self._plane.plane_insert(keys[puts], vals),
                    None)
            else:
                res, nb = self.kv.insert_async(keys[puts], vals,
                                               pad_floor=floor)
                handles["puts"] = (puts, res, nb)

        # Extent inserts land after puts, before deletes/gets, so a client
        # pipelining ins_ext -> get_ext within one flush sees its covers.
        # One dispatch per record (the façade op is single-extent, ref
        # `KV.cpp:129-185`); extents register page RANGES and are orders
        # rarer than page ops, so the serialization is not on the hot path.
        iext = reqs["op"] == OP_INS_EXT
        if iext.any():
            st = np.empty(int(iext.sum()), np.int32)
            for j, r in enumerate(reqs[iext]):
                staged = self.engine.arena[r["page_off"]]
                try:
                    _, uncovered = self.kv.insert_extent(
                        np.array([r["khi"], r["klo"]], np.uint32),
                        np.asarray(staged[:2], np.uint32),
                        int(staged[2]),
                    )
                    # status >= 0 reports the uncovered tail (0 = fully
                    # indexed) — the façade's partial-coverage surface,
                    # carried through the transport
                    st[j] = uncovered
                except Exception:  # noqa: BLE001 — fail THIS record only
                    st[j] = -2
            handles["ins_ext"] = (iext, st)

        dels = reqs["op"] == OP_DEL
        if dels.any():
            if self._plane is not None:
                handles["dels"] = (
                    dels, self._plane.plane_delete(keys[dels]), None)
            else:
                hit, nb = self.kv.delete_async(keys[dels],
                                               pad_floor=floor)
                handles["dels"] = (dels, hit, nb)

        gext = reqs["op"] == OP_GET_EXT
        if gext.any():
            # batched cover resolution, async like the page-get path: the
            # fetch + arena write happen in _finalize so a GET_EXT in the
            # flush does not collapse the launch/finalize overlap
            fn = getattr(self.kv, "get_extent_async", None)
            if self._plane is not None:
                handles["get_ext"] = (
                    gext, self._plane.plane_get_extent(keys[gext]),
                    None, None)
            elif fn is not None:
                out, found, nb = fn(keys[gext], pad_floor=floor)
                handles["get_ext"] = (gext, out, found, nb)
            else:  # sharded KV exposes only the blocking surface
                out_h, found_h = self.kv.get_extent(keys[gext])
                handles["get_ext"] = (gext, out_h, found_h, len(out_h))

        gets = reqs["op"] == OP_GET
        if gets.any():
            if self._plane is not None:
                handles["gets"] = (
                    gets, self._plane.plane_get(keys[gets]), None)
            elif self.config.paged:
                out, order, found, nfound, nb = \
                    self.kv.get_compact_async(keys[gets], pad_floor=floor)
                handles["gets"] = (gets, (out, order, found, nfound), nb)
            else:
                out, found, nb = self.kv.get_async(keys[gets],
                                                   pad_floor=floor)
                handles["gets"] = (gets, (out, None, found, None), nb)
        # launch stamp for the dispatch-vs-device split: _finalize
        # charges the launch-to-first-fetch gap as dispatch_us
        handles["t_ns"] = time.monotonic_ns()
        return handles

    def _finalize(self, reqs: np.ndarray, handles) -> None:
        """Fetch one launched batch's results and publish completions."""
        if handles is None:
            return  # fault-injected drop
        status = np.zeros(len(reqs), np.int32)
        # The blocking fetches below are where device compute + transfer
        # time is actually paid (dispatch in _launch is async), so the
        # reference's TIME_CHECK-style write/read accumulators
        # (`server/rdma_svr.cpp:64-76`) live here — and the device-time
        # profiler's timed-fetch seam with them. `t_l` (the launch
        # stamp) charges the dispatch gap to the FIRST blocking phase;
        # plane handles carry their own per-launch stamps.
        t_l = handles.pop("t_ns", 0)
        n_sh = self._plane.n_shards if self._plane is not None else 0
        if "puts" in handles:
            with self.timers.phase("write"):
                puts, res, nb = handles["puts"]
                if nb is None:  # mesh plane handle
                    h = res
                    res = profiler.fetch(
                        "plane.put", "put", h.fetch, n_ops=h.b,
                        counts=h.counts, n_shards=n_sh,
                        t_launch_ns=h.t_launch_ns, ring=True)
                    dropped = np.asarray(res.dropped)
                else:
                    dropped = profiler.fetch(
                        "kv.insert", "put",
                        lambda: np.asarray(res.dropped)[:nb],
                        n_ops=nb, t_launch_ns=t_l, ring=True)
                t_l = 0
                status[puts] = np.where(dropped, -1, 0)
        if "ins_ext" in handles:
            iext, st = handles["ins_ext"]
            status[iext] = st
        if "get_ext" in handles:
            with self.timers.phase("read"):
                gext, out, found, nb = handles["get_ext"]
                if found is None:  # mesh plane handle
                    h = out
                    out_h, found_h = profiler.fetch(
                        "plane.get_ext", "get_ext", h.fetch, n_ops=h.b,
                        counts=h.counts, n_shards=n_sh,
                        t_launch_ns=h.t_launch_ns, ring=True)
                else:
                    out_h, found_h = profiler.fetch(
                        "kv.get_extent", "get_ext",
                        lambda: (np.asarray(out)[:nb],
                                 np.asarray(found)[:nb]),
                        n_ops=nb, t_launch_ns=t_l, ring=True)
                t_l = 0
                dst = reqs["page_off"][gext]
                self.engine.arena[dst, :2] = out_h
                status[gext] = np.where(found_h, 0, -1)
        if "dels" in handles:
            with self.timers.phase("delete"):
                dels, hit, nb = handles["dels"]
                if nb is None:
                    h = hit
                    hit_h = profiler.fetch(
                        "plane.del", "del", h.fetch, n_ops=h.b,
                        counts=h.counts, n_shards=n_sh,
                        t_launch_ns=h.t_launch_ns, ring=True)
                else:
                    hit_h = profiler.fetch(
                        "kv.delete", "del",
                        lambda: np.asarray(hit)[:nb],
                        n_ops=nb, t_launch_ns=t_l, ring=True)
                t_l = 0
                status[dels] = np.where(hit_h, 0, -1)
        if "gets" in handles:
            with self.timers.phase("read"):
                gets, got, nb = handles["gets"]
                if nb is None:  # mesh plane: request-ordered PlaneGets
                    pg = profiler.fetch(
                        "plane.get", "get", got.fetch, n_ops=got.b,
                        counts=got.counts, n_shards=n_sh,
                        t_launch_ns=got.t_launch_ns, ring=True)
                    found_h = np.asarray(pg.found, bool)
                    if self.config.paged and found_h.any():
                        # hit rows gather straight out of the routed
                        # buffer into their arena destinations
                        dst = reqs["page_off"][gets][found_h]
                        self.engine.arena[dst] = pg.hit_rows()
                    status[gets] = np.where(found_h, 0, -1)
                else:
                    (out, order, found, nfound) = got

                    def _fetch_gets():
                        found_h = np.asarray(found)[:nb]
                        if self.config.paged:
                            # fetch ONLY the hit rows (device-compacted),
                            # padded up the pow2 ladder so slice shapes
                            # stay bounded
                            nf = int(nfound)
                            if nf:
                                w = min(_pad_pow2(nf), out.shape[0])
                                pages = np.asarray(out[:w])[:nf]
                                src = np.asarray(order)[:nf]
                                dst = reqs["page_off"][gets][src]
                                self.engine.arena[dst] = pages
                        return found_h

                    found_h = profiler.fetch("kv.get", "get", _fetch_gets,
                                             n_ops=nb, t_launch_ns=t_l,
                                             ring=True)
                    # (non-paged mode returns hit/miss status only, like
                    # the reference's TX_READ_COMMITTED/ABORTED imm — the
                    # value payload exists only in paged mode)
                    status[gets] = np.where(found_h, 0, -1)
        with self.timers.phase("poll"):
            self.engine.complete(reqs["req_id"], status)
