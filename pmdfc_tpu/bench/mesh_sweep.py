"""Mesh serving-plane sweep — aggregate GET throughput vs shard count.

Measures the partitioned serving plane (`parallel/plane.py` behind the
coalesced `NetServer`) at 1/2/4/8 shards on a forced multi-device host
mesh (`--xla_force_host_platform_device_count`, the multihost_bench
trick), against the `PMDFC_MESH=off` single-device serving path at the
same serving shape. All configs serve the same preloaded key set with
total table capacity held CONSTANT across shard counts (per-shard
capacity = total / n), 8 pipelined connections by default, content
verified in round 0, min-of-rounds interleaved like net_sweep.

Two ratios come out:

- ``ratio_plane_vs_off`` — the mesh plane (best shard count) over the
  single-device serving path. The plane's read-only GET phase returns
  no state, so non-donating platforms skip the whole-table
  materialization the off path pays per flush — the ratio that shows
  on CPU.
- ``ratio_{n}shard_vs_1shard`` — the chip-scaling proxy. NOTE: forced
  host devices on the CPU jaxlib execute SEQUENTIALLY (measured: N
  concurrent per-device programs take N× one program's wall time), so
  shard-count scaling physically cannot show on a CPU host — these
  ratios are recorded honestly (≈1/overhead-bound on CPU) and the real
  curve needs chips (`MULTICHIP_*.json` / the multihost drill). On a
  TPU mesh each shard is a real device and the phases run in parallel.

Rows land in BENCH_mesh.json and `--history` lanes stamped
``transport=tcp_coalesced_mesh`` (off-path rows: ``tcp_coalesced``).
Run: `python -m pmdfc_tpu.bench.mesh_sweep --smoke` (CI hook, agenda
step `mesh_smoke`) or full.

``--replica R1,R2`` adds the 2-D grid (kv shards × replica lanes): for
every lane count > 1 it prices REPLICATED PUTS both ways at equal
device budget and equal durability —

- **fused** (``transport=tcp_coalesced_mesh2d``): ONE NetServer over a
  ``(kv=s, replica=r)`` plane; a put is one wire verb and one device
  launch that writes all r lanes.
- **host** (``transport=tcp_replica_host``): r separate 1-D NetServers
  behind a `ReplicaGroup` with rf=r; a put is r wire round-trips and r
  server flushes — today's host replication path.

``ratio_put_fused_vs_host_{s}x{r}`` lands in the summary. CPU-proxy
caveat: forced host devices run SEQUENTIALLY, so the fused lane's r
per-lane device programs serialize here (``sequential_host_devices``
stamped true) — the wire/flush savings is what shows on CPU; on a real
mesh the lanes run in parallel on top of it (on-chip curve owed via
the agenda's TPU `mesh_sweep` run).
"""

from __future__ import annotations

import argparse
import json
import os


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--device", default="cpu")
    p.add_argument("--shards", default="1,2,4,8")
    p.add_argument("--devices", type=int, default=8,
                   help="forced host device count (CPU only)")
    p.add_argument("--connections", type=int, default=8)
    p.add_argument("--window", type=int, default=8)
    p.add_argument("--verb", type=int, default=64,
                   help="keys per GET verb")
    p.add_argument("--gets", type=int, default=30,
                   help="GET verbs per worker per round")
    p.add_argument("--replica", default="1",
                   help="replica-lane grid; counts > 1 add the fused-"
                        "vs-host replicated-PUT comparison")
    p.add_argument("--puts", type=int, default=20,
                   help="PUT verbs per worker per round (replica grid)")
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--page-words", type=int, default=64)
    p.add_argument("--capacity", type=int, default=1 << 14,
                   help="TOTAL table capacity (split across shards)")
    p.add_argument("--preload", type=int, default=6144)
    p.add_argument("--flush-timeout-us", type=int, default=2000)
    p.add_argument("--settle-us", type=int, default=200)
    p.add_argument("--out", default=None)
    p.add_argument("--history", default=None)
    p.add_argument("--smoke", action="store_true",
                   help="tiny grid, asserts the machinery, fast exit")
    args = p.parse_args()

    if args.smoke:
        args.shards = "1,2"
        args.connections, args.window = 4, 4
        args.gets, args.rounds, args.verb = 10, 2, 32
        args.preload, args.capacity = 2048, 1 << 13
        args.puts = 8
        if args.replica != "1":
            args.replica = "2"

    # forced host devices BEFORE any jax import (multihost_bench.py:203)
    if args.device == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import numpy as np

    from pmdfc_tpu.bench.common import (
        append_history, enable_compile_cache, stamp_live_device)
    from pmdfc_tpu.bench.net_sweep import _fill_pages, _key_pool, \
        _run_config
    from pmdfc_tpu.config import (KVConfig, IndexConfig, BloomConfig,
                                  MeshConfig, NetConfig, mesh_enabled)
    from pmdfc_tpu.parallel.plane import make_serving_backend
    from pmdfc_tpu.runtime.net import NetServer

    enable_compile_cache()
    if not mesh_enabled():
        print("[mesh_sweep] PMDFC_MESH=off — nothing to sweep")
        return 2

    shard_grid = [int(x) for x in args.shards.split(",") if x]
    n_dev = len(jax.devices())
    shard_grid = [s for s in shard_grid if s <= n_dev]
    sequential_cpu = jax.devices()[0].platform == "cpu"

    def cfg_for(n_shards: int) -> KVConfig:
        return KVConfig(
            index=IndexConfig(capacity=max(256, args.capacity // n_shards)),
            bloom=BloomConfig(num_bits=1 << 20),
            paged=True, page_words=args.page_words)

    pool = _key_pool(args.preload)
    pages = _fill_pages(pool, args.page_words)

    def build(kind, n_shards=1):
        """(backend, server) for one grid point; preloaded + warmed."""
        if kind == "off":
            prev = os.environ.get("PMDFC_MESH")
            os.environ["PMDFC_MESH"] = "off"
            try:
                be = make_serving_backend(cfg_for(1))
            finally:
                if prev is None:
                    del os.environ["PMDFC_MESH"]
                else:
                    os.environ["PMDFC_MESH"] = prev
        else:
            be = make_serving_backend(cfg_for(n_shards),
                                      MeshConfig(n_shards=n_shards))
            be.warmup(2048 if not args.smoke else 512, kinds=("get",))
        be.put(pool, pages)
        _, landed = be.get(pool)
        live = pool[np.asarray(landed, bool)]
        srv = NetServer(
            lambda: be,
            net=NetConfig(flush_timeout_us=args.flush_timeout_us,
                          settle_us=args.settle_us)).start()
        return be, srv, live

    points = [("off", 1)] + [("mesh", s) for s in shard_grid]
    built = {pt: build(*pt) for pt in points}
    best: dict = {}
    try:
        for rnd in range(args.rounds + 1):  # round 0 = warmup + verify
            for pt in points:
                be, srv, live = built[pt]
                res = _run_config(
                    "127.0.0.1", srv.port, conns=args.connections,
                    window=args.window, verb=args.verb,
                    gets=max(4, args.gets // (2 if rnd == 0 else 1)),
                    page_words=args.page_words, pool=live,
                    verify=rnd == 0)
                if res["misses"]:
                    raise RuntimeError(
                        f"{pt}: {res['misses']} preloaded keys missed")
                if rnd == 0:
                    continue
                if pt not in best \
                        or res["pages_per_s"] > best[pt]["pages_per_s"]:
                    best[pt] = res
                kind, s = pt
                print(f"[mesh_sweep] r{rnd} {kind} shards={s}: "
                      f"{res['pages_per_s'] / 1e3:.1f} Kpages/s")
    finally:
        for be, srv, _ in built.values():
            srv.stop()

    rows = []
    for (kind, s), res in sorted(best.items()):
        row = {
            "metric": "mesh_get_throughput",
            "value": round(res["pages_per_s"] / 1e6, 4),
            "unit": "Mpages/s",
            "transport": ("tcp_coalesced_mesh" if kind == "mesh"
                          else "tcp_coalesced"),
            "n_shards": s if kind == "mesh" else 0,
            "connections": args.connections,
            "window": args.window,
            "verb_keys": args.verb,
            "page_words": args.page_words,
            "capacity_total": args.capacity,
            "rounds": args.rounds,
            "best_wall_s": round(res["wall_s"], 4),
            "sequential_host_devices": sequential_cpu,
            "host_evidence": True,
        }
        stamp_live_device(row, backend="direct")
        rows.append(row)
        append_history(args.history, row)

    def rate(pt):
        r = best.get(pt)
        return r["pages_per_s"] if r else None

    summary: dict = {"rows": rows,
                     "sequential_host_devices": sequential_cpu}
    off, one = rate(("off", 1)), rate(("mesh", 1))
    best_mesh = max((rate(("mesh", s)) for s in shard_grid
                     if rate(("mesh", s))), default=None)
    if off and best_mesh:
        summary["ratio_plane_vs_off"] = round(best_mesh / off, 2)
    if one:
        for s in shard_grid[1:]:
            r = rate(("mesh", s))
            if r:
                summary[f"ratio_{s}shard_vs_1shard"] = round(r / one, 2)

    # --- 2-D grid: replicated PUTs, fused plane vs host ReplicaGroup ---
    rep_grid = sorted({int(x) for x in args.replica.split(",") if x
                       and int(x) > 1})
    rep_points = [(s, r) for s in shard_grid for r in rep_grid
                  if s * r <= n_dev]
    rep_best: dict = {}
    if rep_points:
        import threading
        import time

        from pmdfc_tpu.client.replica import ReplicaGroup
        from pmdfc_tpu.config import ReplicaConfig
        from pmdfc_tpu.runtime.net import TcpBackend

        put_workers = max(2, args.connections)

        def put_round(group, verify: bool) -> dict:
            """One measured round: `put_workers` threads each issuing
            `args.puts` replicated PUT verbs of `args.verb` keys."""
            barrier = threading.Barrier(put_workers + 1)
            errs: list = []

            def worker(wi: int) -> None:
                rng = np.random.default_rng(500 + 31 * wi)
                try:
                    barrier.wait()
                    for _ in range(args.puts):
                        lo = int(rng.integers(0, len(pool) - args.verb))
                        group.put(pool[lo:lo + args.verb],
                                  pages[lo:lo + args.verb])
                except Exception as e:  # noqa: BLE001 — re-raised below
                    errs.append(e)

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(put_workers)]
            for t in ts:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in ts:
                t.join()
            wall = time.perf_counter() - t0
            if errs:
                raise errs[0]
            if verify:
                out, found = group.get(pool[:64])
                wrongv = int((out[found]
                              != pages[:64][found]).any(axis=1).sum())
                if not found.all() or wrongv:
                    raise RuntimeError(
                        f"replicated-put verify failed: found "
                        f"{int(found.sum())}/64, wrong_pages={wrongv}")
            return {"pages_per_s": put_workers * args.puts * args.verb
                    / wall, "wall_s": wall}

        ncfg = NetConfig(flush_timeout_us=args.flush_timeout_us,
                         settle_us=args.settle_us)
        rcfg = lambda n, rf: ReplicaConfig(  # noqa: E731
            n_replicas=n, rf=rf, repair_interval_s=0, hedge_ms=0)
        warm_w = 1024 if not args.smoke else 256
        for s, r in rep_points:
            # fused: ONE server over a (kv=s, replica=r) plane — a put
            # is one wire verb + one device launch writing all r lanes
            fb = make_serving_backend(
                cfg_for(s), MeshConfig(n_shards=s, replica_axis=r))
            fb.warmup(warm_w, kinds=("put", "get"))
            fsrv = NetServer(lambda be=fb: be, net=ncfg).start()
            fgrp = ReplicaGroup(
                [TcpBackend("127.0.0.1", fsrv.port,
                            page_words=args.page_words,
                            keepalive_s=None, op_timeout_s=120.0)],
                page_words=args.page_words, cfg=rcfg(1, 1))
            # host: r separate 1-D servers + rf=r group fan-out — a put
            # is r wire round-trips and r server flushes
            hbs = [make_serving_backend(cfg_for(s),
                                        MeshConfig(n_shards=s))
                   for _ in range(r)]
            for hb in hbs:
                hb.warmup(warm_w, kinds=("put", "get"))
            hsrvs = [NetServer(lambda be=hb: be, net=ncfg).start()
                     for hb in hbs]
            hgrp = ReplicaGroup(
                [TcpBackend("127.0.0.1", sv.port,
                            page_words=args.page_words,
                            keepalive_s=None, op_timeout_s=120.0)
                 for sv in hsrvs],
                page_words=args.page_words, cfg=rcfg(r, r))
            try:
                # preload once so the round-0 verify reads known bytes
                # (the storm itself puts random slices). Chunked to the
                # WARMED pad-ladder width: one whole-pool put would
                # compile an unwarmed multi-device program mid-flush
                # and stall the verb behind the build.
                for lo in range(0, len(pool), warm_w // 2):
                    sel = slice(lo, lo + warm_w // 2)
                    fgrp.put(pool[sel], pages[sel])
                    hgrp.put(pool[sel], pages[sel])
                for rnd in range(args.rounds + 1):  # round 0 = verify
                    for name, grp in (("fused", fgrp), ("host", hgrp)):
                        res = put_round(grp, verify=rnd == 0)
                        if rnd == 0:
                            continue
                        key = (s, r, name)
                        if key not in rep_best or res["pages_per_s"] \
                                > rep_best[key]["pages_per_s"]:
                            rep_best[key] = res
                        print(f"[mesh_sweep] r{rnd} put {name} "
                              f"kv={s} lanes={r}: "
                              f"{res['pages_per_s'] / 1e3:.1f} Kpages/s")
            finally:
                fgrp.close()
                hgrp.close()
                fsrv.stop()
                for sv in hsrvs:
                    sv.stop()
        for (s, r, name), res in sorted(rep_best.items()):
            row = {
                "metric": "mesh2d_put_throughput",
                "value": round(res["pages_per_s"] / 1e6, 4),
                "unit": "Mpages/s",
                "transport": ("tcp_coalesced_mesh2d" if name == "fused"
                              else "tcp_replica_host"),
                "n_shards": s,
                "replica_lanes": r,
                "rf": r,
                "connections": put_workers,
                "window": args.window,
                "verb_keys": args.verb,
                "page_words": args.page_words,
                "capacity_total": args.capacity,
                "rounds": args.rounds,
                "best_wall_s": round(res["wall_s"], 4),
                "sequential_host_devices": sequential_cpu,
                "host_evidence": True,
            }
            stamp_live_device(row, backend="direct")
            rows.append(row)
            append_history(args.history, row)
        for s, r in rep_points:
            f = rep_best.get((s, r, "fused"))
            h = rep_best.get((s, r, "host"))
            if f and h:
                summary[f"ratio_put_fused_vs_host_{s}x{r}"] = round(
                    f["pages_per_s"] / h["pages_per_s"], 2)

    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    if args.smoke:
        # machinery gates: verified bytes through every plane, per-shard
        # attribution alive, and the plane not slower than half the
        # single-device path at the serving shape (the copy-elimination
        # win should make it FASTER; 0.5 is the regression tripwire)
        be2 = built[("mesh", shard_grid[-1])][0]
        ops = sum(
            be2._tele.get(f"shard{i}_ops", 0)
            for i in range(shard_grid[-1]))
        ok = bool(best) and off and best_mesh and ops > 0 \
            and best_mesh >= 0.5 * off
        if rep_points:
            # replica-lane machinery gates: both lanes measured,
            # content verified (round 0 raised otherwise), and the
            # fused plane within the regression tripwire of the host
            # fan-out (the recorded full run is where the win lands)
            for s, r in rep_points:
                f = rep_best.get((s, r, "fused"))
                h = rep_best.get((s, r, "host"))
                ratio = (f["pages_per_s"] / h["pages_per_s"]
                         if f and h else 0)
                print(f"[mesh_sweep] smoke put fused/host {s}x{r} = "
                      f"{ratio:.2f}")
                ok = ok and f and h and ratio >= 0.5
        print(f"[mesh_sweep] smoke {'OK' if ok else 'FAIL'} "
              f"(plane/off={best_mesh / off if off else 0:.2f}, "
              f"routed_ops={ops})")
        return 0 if ok else 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
