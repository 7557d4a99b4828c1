"""Paired fused-vs-composed GET sweep (batch × zipf × family).

Prices the tentpole claim of `ops/fused.py`: the whole GET verb — index
probe, row gather, digest verify, tier/generation fold, miss-cause
classify — as ONE Pallas kernel with row data pinned in VMEM, against
the composed XLA chain that materializes an HBM intermediate between
every stage. Successor to `bench/pallas_gather.py`, whose verdict stands
and bounds the claim honestly: XLA's gather lowering beats a per-row DMA
pipeline ~2x on the PURE gather (39 vs 21.5 Mrows/s), so the fused
kernel's case is never the gather itself — it is everything the
composed chain does AROUND the gather (probe + verify + classify
round-trips) that fusion deletes. The paired lanes record whether that
trade wins on the serving shapes.

Every (family × zipf × batch) combo emits TWO history rows differing
only in the `kernel` lane knob — `pallas_fused` vs `xla_composed` —
plus identity knobs (`tile`, `batch`, `zipf`, `family`, ...), so
`tools/check_bench.py` tracks them as separate lanes that can never
collapse into one. When the tracing tier is live the combo also emits
a paired `device_us` lane per kernel side: mean on-chip µs per GET
verb from the device-time profiler's timed-fetch attribution
(`runtime/profiler.py`) — the split of each wall row the host timer
cannot see.

Honesty rules (the acceptance bar's "no fake speedup rows"):
- off-chip, the fused side runs in Pallas INTERPRET mode — a
  correctness vehicle, not a measurement. The run degrades to the
  parity check (bit-identical pages / stats / cause lanes) and the
  shared evidence logger refuses the non-TPU rows anyway.
- both sides are always parity-checked against each other before any
  timing is reported; a mismatch fails the run.

Run: `python -m pmdfc_tpu.bench.fused_get --smoke` (agenda step
`fused_smoke`: tiny shapes, parity only) or full (`fused_sweep`);
`--history` appends the on-chip lanes to BENCH_HISTORY.jsonl.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from pmdfc_tpu.bench.tier_sweep import _keys, _pages, _zipf_stream


def _mk_kv(kind, cap, page_words, fused: str):
    from pmdfc_tpu.config import IndexConfig, KVConfig
    from pmdfc_tpu.kv import KV

    return KV(KVConfig(index=IndexConfig(kind=kind, capacity=cap),
                       bloom=None, paged=True, page_words=page_words,
                       fused_get=fused))


def _stream_pair(kv_f, kv_c, skeys, batch, check: bool, h_dev=None):
    """Drive the SAME stream through both KVs, batch-interleaved so the
    two sides see the same machine weather. Returns (sec_fused,
    sec_composed, hits, device_us_fused, device_us_composed) and asserts
    bit-identical serving when `check`. `h_dev` is the profiler's
    `prof.kv.get.device_us` histogram: both sides attribute into the
    SAME program name, so the per-side split comes from deltaing its
    cumulative sum around each side's call (the loop is single-threaded
    — nothing else observes into it between the reads)."""
    t_f = t_c = 0.0
    d_f = d_c = 0.0
    hits = 0
    dev_sum = ((lambda: h_dev.snapshot()["sum"]) if h_dev is not None
               else (lambda: 0.0))
    for i in range(0, len(skeys), batch):
        kb = skeys[i:i + batch]
        s0 = dev_sum()
        t0 = time.perf_counter()
        out_f, found_f = kv_f.get(kb)
        t_f += time.perf_counter() - t0
        s1 = dev_sum()
        d_f += s1 - s0
        t0 = time.perf_counter()
        out_c, found_c = kv_c.get(kb)
        t_c += time.perf_counter() - t0
        d_c += dev_sum() - s1
        hits += int(found_c.sum())
        if check:
            assert np.array_equal(found_f, found_c), "found mask drift"
            assert np.array_equal(out_f, out_c), "page bytes drift"
    return t_f, t_c, hits, d_f, d_c


def _stats_parity(kv_f, kv_c) -> dict:
    """Cumulative device stats must match lane-for-lane (uptime is host
    wall clock, excluded). Returns the diff dict (empty == parity)."""
    a, b = kv_f.stats(), kv_c.stats()
    return {k: (a.get(k), b.get(k)) for k in set(a) | set(b)
            if k != "uptime_s" and a.get(k) != b.get(k)}


def run(args) -> dict:
    from pmdfc_tpu.bench.common import (
        append_history, enable_compile_cache, pin_cpu, stamp_live_device)

    if args.device == "cpu":
        pin_cpu()
    enable_compile_cache()

    import jax

    from pmdfc_tpu.config import IndexKind
    from pmdfc_tpu.ops import fused as fused_ops
    from pmdfc_tpu.runtime import profiler
    from pmdfc_tpu.runtime import telemetry as tele

    # device-time lanes: the profiler attributes each GET's blocking
    # fetch (kv.py's timed-fetch seam) into `prof.kv.get.device_us`;
    # the paired rows below split that by kernel side
    profiler.install()
    h_dev = (tele.get().scope("prof", unique=False).hist("kv.get.device_us")
             if tele.enabled() else None)

    on_chip = jax.default_backend() == "tpu"
    cap, W = args.capacity, args.page_words
    n_keys = cap // 2  # half-full: no index evictions pollute the sweep
    all_keys = _keys(np.arange(1, n_keys + 1))
    all_pages = _pages(all_keys, W)
    rng = np.random.default_rng(args.seed)

    sweeps = []
    worst = 1.0
    for fam in args.families:
        kind = IndexKind(fam)
        for a in args.zipfs:
            for batch in args.batches:
                # fused_get='on' forces the kernel (interpret off-chip);
                # 'off' is today's composed chain — the paired baseline
                kv_f = _mk_kv(kind, cap, W, "on")
                kv_c = _mk_kv(kind, cap, W, "off")
                for i in range(0, n_keys, max(args.batches)):
                    sl = slice(i, i + max(args.batches))
                    kv_f.insert(all_keys[sl], all_pages[sl])
                    kv_c.insert(all_keys[sl], all_pages[sl])
                stream = _zipf_stream(rng, n_keys, args.gets, a)
                skeys = all_keys[stream]
                # warm both programs (compile outside the timed region)
                _stream_pair(kv_f, kv_c, skeys[:batch * 2], batch, False)
                t_f, t_c, hits, d_f, d_c = _stream_pair(
                    kv_f, kv_c, skeys, batch,
                    check=args.smoke or not on_chip, h_dev=h_dev)
                drift = _stats_parity(kv_f, kv_c)
                assert not drift, f"stats lanes drifted: {drift}"
                tile = fused_ops.tile_for(batch)
                base = {
                    "metric": "fused_get", "family": fam, "zipf": a,
                    "batch": batch, "tile": tile, "capacity": cap,
                    "page_words": W, "gets": args.gets, "hits": hits,
                }
                # `value`/`unit` make the rows gateable lanes in
                # tools/check_bench.py; `kernel` + `tile` are identity
                # knobs there, `hits` a measured-int exception
                row_f = {**base, "kernel": "pallas_fused",
                         "unit": "Mops/s",
                         "value": round(args.gets / t_f / 1e6, 4),
                         "wall_s": round(t_f, 4)}
                row_c = {**base, "kernel": "xla_composed",
                         "unit": "Mops/s",
                         "value": round(args.gets / t_c / 1e6, 4),
                         "wall_s": round(t_c, 4)}
                speedup = round(t_c / t_f, 3)
                worst = min(worst, speedup)
                rows = [row_f, row_c]
                calls = -(-args.gets // batch)
                if h_dev is not None and (d_f > 0 or d_c > 0):
                    # paired device-time lanes: mean on-chip µs per GET
                    # verb from the profiler's timed-fetch attribution —
                    # `device_us` is a latency unit in check_bench, so
                    # these gate lower-is-better alongside the Mops/s
                    # throughput lanes
                    rows.append({**base, "kernel": "pallas_fused",
                                 "unit": "device_us",
                                 "value": round(d_f / calls, 2)})
                    rows.append({**base, "kernel": "xla_composed",
                                 "unit": "device_us",
                                 "value": round(d_c / calls, 2)})
                for row in rows:
                    stamp_live_device(row, "direct")
                    # the shared logger refuses non-TPU rows: interpret-
                    # mode timings must never look like chip evidence
                    append_history(args.history, row)
                sweeps.append({**base, "speedup_fused_vs_composed": speedup,
                               "mops_fused": row_f["value"],
                               "mops_composed": row_c["value"],
                               "device_us_fused": round(d_f / calls, 2),
                               "device_us_composed": round(d_c / calls, 2),
                               "parity": "ok"})

    out = {"metric": "fused_get_sweep", "on_chip": on_chip,
           "interpret_fused": not on_chip, "sweeps": sweeps,
           "worst_speedup": worst}
    stamp_live_device(out, "direct")
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--capacity", type=int, default=1 << 17)
    p.add_argument("--page-words", type=int, default=512)
    p.add_argument("--batches", type=lambda s: [int(x) for x in
                                                s.split(",")],
                   default=[1 << 9, 1 << 11])
    p.add_argument("--gets", type=int, default=1 << 16)
    p.add_argument("--zipfs", type=lambda s: [float(x) for x in
                                              s.split(",")],
                   default=[0.6, 0.99])
    p.add_argument("--families", type=lambda s: s.split(","),
                   default=["linear", "cceh"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--device", default="cpu")
    p.add_argument("--out", default=None, help="write the JSON artifact")
    p.add_argument("--history", default=None,
                   help="BENCH_HISTORY.jsonl path (on-chip lanes only)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes, every batch parity-checked — the "
                        "agenda `fused_smoke` step; correctness, not a "
                        "perf claim (off-chip the fused side is "
                        "interpret-mode)")
    args = p.parse_args()
    if args.smoke:
        args.capacity = 1 << 11
        args.page_words = 64
        args.batches = [128]
        args.gets = 1 << 10
        args.zipfs = [0.99]
    out = run(args)
    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    if args.smoke:
        ok = all(sw["parity"] == "ok" for sw in out["sweeps"])
        print(f"[fused_get] smoke {'OK' if ok else 'FAIL'}")
        return 0 if ok else 1
    if out["on_chip"] and out["worst_speedup"] < 1.0:
        print(f"[fused_get] fused slower than composed on-chip "
              f"(worst {out['worst_speedup']}x) — the lanes above are "
              f"the honest record")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
