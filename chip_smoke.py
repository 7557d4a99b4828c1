"""Chip smoke: serve real-size 4 KiB pages on a TPU through the normal entry
points, and check every answer.

    python chip_smoke.py             # one chip: KV behind the coalesced NetServer
    python chip_smoke.py --chips 4   # the sharded serving plane, 1-D then 2-D

One process holds the chip; wire clients are threads of that process. There
is no CPU path: without a TPU the script exits non-zero and prints no result.
Every phase prints one JSON line; the last line is the device verdict
`{"ok": true, "device": {...}}`, printed only when every phase passed.

One-chip phases: device check, build (linear index, capacity 2^21, 4 KiB
pages, 2^28-bit bloom, `fused_get` auto — the `serving.fused_get` gauge must
read 1), load 2^20 pages through `KV.insert`, serve PUTs and GETs from wire
client threads against a host-dict reference, and compare the fused GET
kernel with the composed program on the loaded state.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import threading
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The deployment and its traffic. The defaults are the real sizes:
    an 8 GiB pool of 4 KiB pages (the reference's 10 GB buffer, SURVEY §6,
    cut to what one 16 GB chip holds beside its bloom and table)."""

    capacity: int = 1 << 21       # pool rows == index slots (per shard)
    page_words: int = 1024        # 4 KiB pages
    bloom_bits: int = 1 << 28
    load: int = 1 << 20           # pages loaded per shard before serving
    load_batch: int = 8192
    clients: int = 4
    puts_per_client: int = 1024
    gets_per_client: int = 2048
    wire_batch: int = 256
    parity_keys: int = 4096
    fused_get: str = "auto"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileClock:
    """Seconds XLA spent compiling, summed from JAX's own compile events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


# -- data made from the seed: keys by index, pages by key --------------------

def key_ids(idx: np.ndarray, seed: int) -> np.ndarray:
    """uint64 key per index: an odd multiply plus a xorshift, both
    bijections of uint64, so distinct indices give distinct keys."""
    x = np.asarray(idx, np.uint64) * np.uint64(0x9E3779B97F4A7C15) \
        + np.uint64(seed * 0x632BE59BD9B4E019 + 1)
    x ^= x >> np.uint64(29)
    assert not (x == np.uint64(2**64 - 1)).any()  # the INVALID sentinel
    return x


def split(ids: np.ndarray) -> np.ndarray:
    return np.stack([(ids >> np.uint64(32)).astype(np.uint32),
                     (ids & np.uint64(0xFFFFFFFF)).astype(np.uint32)], -1)


def pages_of(keys: np.ndarray, seed: int, page_words: int) -> np.ndarray:
    base = (keys[:, 0] * np.uint32(0x9E3779B1)) \
        ^ (keys[:, 1] * np.uint32(0x85EBCA77)) ^ np.uint32(seed)
    step = np.arange(page_words, dtype=np.uint32) * np.uint32(0x27D4EB2F)
    return base[:, None] + step[None, :]


class Traffic:
    """Index ranges of the three key classes (loaded, PUT over the wire,
    never written) and the host reference: the set of written keys. Pages
    are recomputed from the key."""

    def __init__(self, sz: Sizes, seed: int, n_load: int):
        self.sz, self.seed, self.n_load = sz, seed, n_load
        self.put_base = n_load
        self.n_put = sz.clients * sz.puts_per_client
        self.never_base = 1 << 40
        self.written: set[int] = set()

    def write(self, ids: np.ndarray) -> None:
        self.written.update(ids.tolist())

    def client_puts(self, c: int) -> np.ndarray:
        lo = self.put_base + c * self.sz.puts_per_client
        return key_ids(np.arange(lo, lo + self.sz.puts_per_client), self.seed)

    def client_gets(self, c: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, c])
        g = self.sz.gets_per_client
        n_put = n_never = g // 4
        idx = np.concatenate([
            rng.integers(0, self.n_load, g - n_put - n_never),
            self.put_base + rng.integers(0, self.n_put, n_put),
            self.never_base + rng.integers(0, 1 << 40, n_never),
        ])
        return key_ids(rng.permutation(idx), self.seed)


def load(put, traffic: Traffic) -> dict:
    """Insert `traffic.n_load` pages in fixed batches through `put`."""
    sz = traffic.sz
    t0 = time.perf_counter()
    for lo in range(0, traffic.n_load, sz.load_batch):
        ids = key_ids(np.arange(lo, min(lo + sz.load_batch, traffic.n_load)),
                      traffic.seed)
        keys = split(ids)
        put(keys, pages_of(keys, traffic.seed, sz.page_words))
        traffic.write(ids)
    return {"pages_loaded": traffic.n_load,
            "load_bytes": traffic.n_load * sz.page_words * 4,
            "load_s": time.perf_counter() - t0}


# -- the wire phase ---------------------------------------------------------

def serve(backend, traffic: Traffic) -> dict:
    """PUT then GET from `clients` TcpBackend threads through a coalesced
    NetServer over `backend`; check every answer against the reference and
    the server's and backend's own counters. Raises on any violation."""
    from pmdfc_tpu.config import NetConfig
    from pmdfc_tpu.kv import MISS_CAUSE_NAMES
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    sz = traffic.sz
    before = backend.stats()
    srv = NetServer(lambda: backend, net=NetConfig(),
                    idle_timeout_s=900.0).start()
    barrier = threading.Barrier(sz.clients)
    answers: list = [None] * sz.clients
    errors: list = []

    def client(c: int) -> None:
        try:
            with TcpBackend("127.0.0.1", srv.port, page_words=sz.page_words,
                            op_timeout_s=900.0, keepalive_s=None) as be:
                ids = traffic.client_puts(c)
                for lo in range(0, len(ids), sz.wire_batch):
                    keys = split(ids[lo:lo + sz.wire_batch])
                    be.put(keys, pages_of(keys, traffic.seed, sz.page_words))
                barrier.wait()
                got = []
                ids = traffic.client_gets(c)
                for lo in range(0, len(ids), sz.wire_batch):
                    out, found = be.get(split(ids[lo:lo + sz.wire_batch]))
                    got.append((ids[lo:lo + sz.wire_batch],
                                np.asarray(out), np.asarray(found, bool)))
                answers[c] = got
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(f"client {c}: {type(e).__name__}: {e}")
            barrier.abort()

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(sz.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    net = dict(srv.stats)
    srv.stop()
    if errors:
        raise RuntimeError("; ".join(errors))
    for c in range(sz.clients):
        traffic.write(traffic.client_puts(c))

    n = {"gets": 0, "hits": 0, "wrong_bytes": 0, "never_hits": 0,
         "written_misses": 0, "never": 0}
    for got in answers:
        for ids, out, found in got:
            written = np.array([k in traffic.written for k in ids.tolist()])
            want = pages_of(split(ids), traffic.seed, sz.page_words)
            n["gets"] += len(ids)
            n["hits"] += int(found.sum())
            n["never"] += int((~written).sum())
            n["never_hits"] += int((found & ~written).sum())
            n["written_misses"] += int((~found & written).sum())
            n["wrong_bytes"] += int(
                (out[found].view(np.uint8) != want[found].view(np.uint8))
                .sum())
    after = backend.stats()
    d = {k: after[k] - before[k] for k in
         ("gets", "hits", "misses", "corrupt_pages", "drops",
          *MISS_CAUSE_NAMES)}
    report = {**n, "serve_s": wall, "evictions": after["evictions"],
              "stats_delta": d,
              "net": {k: net[k] for k in
                      ("serve_errors", "bisect_failures", "bisect_launches",
                       "nacks_sent", "poison_refused", "poison_ops",
                       "deadline_shed", "shed_ops", "flushes",
                       "coalesced_ops")}}
    bad = []
    if n["wrong_bytes"] or n["never_hits"]:
        bad.append("wrong bytes or a never-written key hit")
    if d["gets"] != n["gets"] or d["hits"] != n["hits"]:
        bad.append("server counters disagree with the client answers")
    if d["misses"] != d["miss_cold"] + d["miss_evicted"]:
        bad.append("a miss cause other than cold/evicted")
    if n["written_misses"] > min(d["miss_evicted"], after["evictions"]):
        bad.append("a written key missed without an eviction to explain it")
    if d["corrupt_pages"] or after["drops"]:
        bad.append("corrupt pages or dropped puts")
    if any(report["net"][k] for k in
           ("serve_errors", "bisect_failures", "nacks_sent",
            "poison_refused", "deadline_shed", "shed_ops")):
        bad.append("the server failed, bisected, NACKed or shed ops")
    if bad:
        raise AssertionError(f"{'; '.join(bad)}: {report}")
    return report


# -- fused kernel vs composed program --------------------------------------

def parity(kv, traffic: Traffic) -> dict:
    """Run the fused GET and the composed `_get_core` on the same state and
    keys (read-only programs: no state output) and require identical pages,
    found masks and stats deltas."""
    import jax

    from pmdfc_tpu import kv as kv_mod
    from pmdfc_tpu.ops import fused as fused_ops

    cfg = kv.config

    def program(core):
        def f(state, keys):
            st2, out, found = core(state, cfg, keys)
            return out, found, st2.stats - state.stats
        return jax.jit(f)

    rng = np.random.default_rng([traffic.seed, 99])
    q = traffic.sz.parity_keys
    idx = np.concatenate([
        rng.integers(0, traffic.n_load, q // 2),
        traffic.put_base + rng.integers(0, traffic.n_put, q // 4),
        traffic.never_base + rng.integers(0, 1 << 40, q - q // 2 - q // 4)])
    keys = split(key_ids(rng.permutation(idx), traffic.seed))
    res = {}
    for name, core in (("fused", fused_ops.get_core),
                       ("composed", kv_mod._get_core)):
        fn = program(core)
        t0 = time.perf_counter()
        res[name] = jax.tree.map(np.asarray, fn(kv.state, keys))
        res[name + "_s"] = time.perf_counter() - t0
        # warm calls, each closed by fetching its found mask
        laps = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(fn(kv.state, keys)[1])
            laps.append(time.perf_counter() - t0)
        res[name + "_warm_s"] = sorted(laps)[len(laps) // 2]
    (fo, ff, fd), (co, cf, cd) = res["fused"], res["composed"]
    report = {"keys": q, "found": int(ff.sum()),
              "pages_equal": bool(np.array_equal(fo, co)),
              "found_equal": bool(np.array_equal(ff, cf)),
              "stats_equal": bool(np.array_equal(fd, cd)),
              "fused_first_call_s": res["fused_s"],
              "composed_first_call_s": res["composed_s"],
              "fused_warm_median_s": res["fused_warm_s"],
              "composed_warm_median_s": res["composed_warm_s"]}
    if not (report["pages_equal"] and report["found_equal"]
            and report["stats_equal"]):
        raise AssertionError(f"fused GET diverged from composed: {report}")
    return report


def memory(devices) -> list:
    out = []
    for d in devices:
        m = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": m.get("bytes_in_use"),
                    "peak_bytes_in_use": m.get("peak_bytes_in_use")})
    return out


def kv_config(sz: Sizes):
    from pmdfc_tpu.config import BloomConfig, IndexConfig, IndexKind, KVConfig

    return KVConfig(
        index=IndexConfig(kind=IndexKind.LINEAR, capacity=sz.capacity),
        bloom=BloomConfig(num_bits=sz.bloom_bits, num_hashes=4),
        paged=True, page_words=sz.page_words, fused_get=sz.fused_get)


def one_chip(sz: Sizes, seed: int) -> None:
    import jax

    from pmdfc_tpu.client.backends import DirectBackend
    from pmdfc_tpu.kv import KV
    from pmdfc_tpu.runtime import telemetry as tele
    from pmdfc_tpu.utils.keys import INVALID_WORD

    clock = CompileClock()
    t0 = time.perf_counter()
    kv = KV(kv_config(sz))
    # one GET at the wire width resolves (and compiles) the GET program
    kv.get(np.full((sz.wire_batch, 2), INVALID_WORD, np.uint32))
    gauge = tele.get().scope("serving", unique=False).gauge("fused_get")
    pool = kv.state.pool.pages
    emit(phase="build", index="linear", capacity=sz.capacity,
         pool_rows=int(pool.shape[0]), pool_bytes=int(pool.nbytes),
         page_bytes=sz.page_words * 4, fused_get=gauge.value,
         build_s=time.perf_counter() - t0, compile_s=clock.seconds)
    if gauge.value != 1:
        raise AssertionError("serving.fused_get is not 1: the fused GET "
                             "kernel is not serving")

    traffic = Traffic(sz, seed, sz.load)
    c0 = clock.seconds
    emit(phase="load", **load(kv.insert, traffic),
         evictions=kv.stats()["evictions"], compile_s=clock.seconds - c0)

    c0 = clock.seconds
    emit(phase="serve", **serve(DirectBackend(kv), traffic),
         compile_s=clock.seconds - c0)

    c0 = clock.seconds
    emit(phase="parity", **parity(kv, traffic), compile_s=clock.seconds - c0)
    emit(phase="report", device_kind=jax.devices()[0].device_kind,
         compile_s_total=clock.seconds, memory=memory(jax.devices()[:1]))


def four_chips(sz: Sizes, seed: int) -> None:
    """The sharded plane behind the same NetServer: `n_shards=4` (32 GiB
    of pages, no single chip holds it), then the 2-D plane (2 shards x 2
    replica lanes). Each device must hold its share of the state."""
    import gc

    import jax

    from pmdfc_tpu.config import MeshConfig
    from pmdfc_tpu.parallel.plane import make_serving_backend

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, JAX reports "
                           f"{len(jax.devices())}")
    devices = jax.devices()[:4]
    clock = CompileClock()
    for name, mc in (("plane_1d", MeshConfig(n_shards=4)),
                     ("plane_2d", MeshConfig(n_shards=2, replica_axis=2))):
        c0 = clock.seconds
        backend = make_serving_backend(kv_config(sz), mc)
        shards = mc.n_shards
        traffic = Traffic(sz, seed, sz.load * shards)
        loaded = load(backend.put, traffic)
        mem = memory(devices)
        emit(phase=f"{name}_load", n_shards=shards,
             replicas=mc.replica_axis, pool_rows_per_shard=sz.capacity,
             **loaded, evictions=backend.stats()["evictions"],
             memory=mem, compile_s=clock.seconds - c0)
        # each device holds one shard's (or one replica lane's) state:
        # the pool alone is capacity x page bytes on every device
        share = sz.capacity * sz.page_words * 4
        if any((m["bytes_in_use"] or 0) < share for m in mem):
            raise AssertionError(f"{name}: state not spread over the "
                                 f"devices: {mem}")
        c0 = clock.seconds
        emit(phase=f"{name}_serve", **serve(backend, traffic),
             compile_s=clock.seconds - c0)
        # free this plane's state before the next one allocates its own
        for leaf in jax.tree.leaves(backend.skv.state):
            leaf.delete()
        del backend
        gc.collect()
    emit(phase="report", device_kind=devices[0].device_kind,
         compile_s_total=clock.seconds, memory=memory(devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX reports platform "
              f"{devs[0].platform!r}; there is no CPU path",
              file=sys.stderr)
        return 2
    phase = "setup"
    try:
        run = four_chips if args.chips == 4 else one_chip
        phase = run.__name__
        run(Sizes(), args.seed)
    except BaseException as e:  # noqa: BLE001 - any failure fails the run
        emit(phase=phase, ok=False, error=f"{type(e).__name__}: {e}")
        return 1
    emit(ok=True, device={"platform": devs[0].platform,
                          "kind": devs[0].device_kind,
                          "count": len(devs)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
