"""Tiered vs flat page-store GET sweep across zipf skews.

The tentpole claim to price: with a skewed GET stream (RDMAbox's
observation that remote-paging working sets are small and hot), a small
HOT region serves repeat GETs from a tier the machine can keep close,
while the flat pool strides the whole region on every batch. Two
measurements per skew:

- `hot_gather` — the device gather serving a GET batch drawn from the
  PROMOTED working set, timed on each store's LIVE row distribution for
  the SAME keys: the tiered store resolves them inside its compact hot
  region (≤ 1/8 of capacity), the flat store scatters them across the
  whole pool. This is the structural difference the tier buys, isolated
  from host-side fetch and from the CPU backend's no-donation state-copy
  tax (donation is off on CPU jaxlib — see `kv._donate` — which taxes
  every op in proportion to TOTAL state size and identically hides any
  row-placement effect; on TPU, where serving runs donated, the gather
  IS the page-path cost).
- `stream_mops` — end-to-end throughput of the full zipf stream on both
  stores (includes every promotion/migration the tiered store pays), so
  the artifact records the overhead side of the trade too.

Run: `python -m pmdfc_tpu.bench.tier_sweep --smoke` (CI smoke) or with
real sizes; `--out` writes the JSON artifact, and on-chip runs append to
BENCH_HISTORY.jsonl through the shared evidence logger.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _zipf_stream(rng, n_keys: int, n: int, a: float) -> np.ndarray:
    """Zipf ranks over [0, n_keys) — rank r picked w.p. ∝ (r+1)^-a.

    Finite-universe inverse-CDF sampler (numpy's `rng.zipf` needs a > 1;
    the interesting cache skews live at a <= 1)."""
    if a <= 0:
        return rng.integers(0, n_keys, n).astype(np.uint32)
    w = np.power(np.arange(1, n_keys + 1, dtype=np.float64), -a)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random(n), side="right")
    ranks = np.minimum(ranks, n_keys - 1)
    # rank-shuffled so hot keys are scattered across the key space (the
    # hash-routed reality), not clustered at low ids
    perm = rng.permutation(n_keys).astype(np.uint32)
    return perm[ranks]


def _keys(los: np.ndarray) -> np.ndarray:
    los = np.asarray(los, np.uint32)
    return np.stack([los >> 16, los], axis=-1).astype(np.uint32)


def _pages(keys: np.ndarray, page_words: int) -> np.ndarray:
    lo = np.asarray(keys, np.uint32)[:, 1]
    return (lo[:, None] * np.uint32(2654435761)
            + np.arange(page_words, dtype=np.uint32)[None, :])


def _timed_gets(kv, keys: np.ndarray, batch: int, verify_against=None):
    """Drive GET batches; returns (seconds, hits). Results are fetched
    (np.asarray) so the measurement includes the full serve cost."""
    t0 = time.perf_counter()
    hits = 0
    for i in range(0, len(keys), batch):
        out, found = kv.get(keys[i:i + batch])
        hits += int(found.sum())
        if verify_against is not None:
            assert (out[found]
                    == verify_against(keys[i:i + batch])[found]).all()
    return time.perf_counter() - t0, hits


def _resolve_rows(kv, keys: np.ndarray) -> np.ndarray:
    """Row id per key via the façade's full-scan lookup (chunked so the
    [B, N] compare stays bounded); -1 where absent."""
    rows = np.full(len(keys), -1, np.int64)
    for lo in range(0, len(keys), 512):
        vals, found, _ = kv.find_anyway(keys[lo:lo + 512])
        rows[lo:lo + 512] = np.where(found, vals[:, 1].astype(np.int64),
                                     -1)
    return rows


def _timed_gather_pair(gather, pages_a, rows_a: np.ndarray,
                       pages_b, rows_b: np.ndarray,
                       reps: int = 10, rounds: int = 8):
    """(µs_a, µs_b): min-of-rounds, A/B interleaved per round — the two
    sides see the same machine weather, and the min filters the shared-
    container noise that makes single measurements swing 2-3x."""
    import jax.numpy as jnp

    ra = jnp.asarray(rows_a.astype(np.int32))
    rb = jnp.asarray(rows_b.astype(np.int32))
    np.asarray(gather(pages_a, ra))  # compile + warm
    np.asarray(gather(pages_b, rb))
    best_a = best_b = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = gather(pages_a, ra)
        out.block_until_ready()
        best_a = min(best_a, (time.perf_counter() - t0) / reps * 1e6)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = gather(pages_b, rb)
        out.block_until_ready()
        best_b = min(best_b, (time.perf_counter() - t0) / reps * 1e6)
    return best_a, best_b


def run(args) -> dict:
    from pmdfc_tpu.bench.common import (
        append_history, enable_compile_cache, pin_cpu, stamp_live_device)

    if args.device == "cpu":
        pin_cpu()
    enable_compile_cache()

    from pmdfc_tpu.config import IndexConfig, KVConfig, TierConfig
    from pmdfc_tpu.kv import KV

    W = args.page_words
    cap = args.capacity
    flat_cfg = KVConfig(index=IndexConfig(capacity=cap), bloom=None,
                        paged=True, page_words=W)
    tier_cfg = KVConfig(
        index=IndexConfig(capacity=cap), bloom=None, paged=True,
        page_words=W,
        tier=TierConfig(hot_fraction=args.hot_fraction,
                        promote_touches=2,
                        max_promotes_per_batch=args.batch),
    )
    n_keys = cap // 2  # half-full: no index evictions pollute the sweep
    all_keys = _keys(np.arange(1, n_keys + 1))
    all_pages = _pages(all_keys, W)
    rng = np.random.default_rng(args.seed)

    sweeps = []
    for a in args.zipfs:
        flat = KV(flat_cfg)
        tier = KV(tier_cfg)
        for i in range(0, n_keys, args.batch):
            flat.insert(all_keys[i:i + args.batch],
                        all_pages[i:i + args.batch])
            tier.insert(all_keys[i:i + args.batch],
                        all_pages[i:i + args.batch])
        stream = _zipf_stream(rng, n_keys, args.gets, a)
        skeys = all_keys[stream]
        verify = (lambda k: _pages(k, W)) if args.smoke else None

        # warm: one pass drives promotions (and compiles every program)
        _timed_gets(tier, skeys[: args.batch * 4], args.batch)
        _timed_gets(flat, skeys[: args.batch * 4], args.batch)

        t_tier, hits_t = _timed_gets(tier, skeys, args.batch, verify)
        t_flat, hits_f = _timed_gets(flat, skeys, args.batch, verify)

        # hot-resident batches: keys currently promoted into the hot tier,
        # gather-timed on each store's OWN row distribution (see module
        # docstring for why this isolates the structural difference)
        import jax
        import jax.numpy as jnp

        ts = tier.tier_stats()
        pool = tier.state.pool
        h_rows = pool.hfree.shape[0]
        hk = np.asarray(pool.hot_keys)
        from pmdfc_tpu.utils.keys import INVALID_WORD

        occ = ~np.all(hk == INVALID_WORD, axis=-1)
        hot_keys = hk[occ]
        hot_us = flat_us = hot_frac = None
        if len(hot_keys) >= max(256, args.batch // 4):
            hb = hot_keys[rng.integers(0, len(hot_keys), args.batch)]
            rows_t = _resolve_rows(tier, hb)
            rows_f = _resolve_rows(flat, hb)
            ok = (rows_t >= 0) & (rows_f >= 0)
            hot_frac = round(float((rows_t[ok] < h_rows).mean()), 4)
            gather = jax.jit(lambda p, r: p[r])
            hot_us, flat_us = _timed_gather_pair(
                gather, pool.pages, rows_t[ok],
                flat.state.pool.pages, rows_f[ok])
        sweeps.append({
            "zipf": a,
            "stream_mops_tier": round(args.gets / t_tier / 1e6, 4),
            "stream_mops_flat": round(args.gets / t_flat / 1e6, 4),
            "hits_tier": hits_t, "hits_flat": hits_f,
            "hot_gather_us_tier": round(hot_us, 1) if hot_us else None,
            "hot_gather_us_flat": round(flat_us, 1) if flat_us else None,
            "hot_gather_speedup": (round(flat_us / hot_us, 3)
                                   if hot_us and flat_us else None),
            "hot_batch_frac_in_hot_tier": hot_frac,
            "tier": ts,
        })

    out = {
        "metric": "tier_sweep",
        "capacity": cap, "page_words": W, "batch": args.batch,
        "gets": args.gets, "hot_fraction": args.hot_fraction,
        "sweeps": sweeps,
    }
    stamp_live_device(out, "direct")
    append_history(args.history, out)
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--capacity", type=int, default=1 << 17)
    p.add_argument("--page-words", type=int, default=512)
    p.add_argument("--batch", type=int, default=1 << 11)
    p.add_argument("--gets", type=int, default=1 << 16)
    p.add_argument("--hot-fraction", type=int, default=16,
                   help="hot rows = capacity // this (>= 8 keeps the "
                        "acceptance bound: hot <= 1/8 of capacity)")
    p.add_argument("--zipfs", type=lambda s: [float(x) for x in
                                              s.split(",")],
                   default=[0.6, 0.99, 1.2])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--device", default="cpu")
    p.add_argument("--out", default=None, help="write the JSON artifact")
    p.add_argument("--history", default=None,
                   help="BENCH_HISTORY.jsonl path (on-chip runs only)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes + content verification — the CI/"
                        "tools hook; exercises promote/demote/balloon "
                        "machinery, not a perf claim")
    args = p.parse_args()
    if args.smoke:
        args.capacity = 1 << 11
        args.page_words = 256
        args.batch = 128
        args.gets = 1 << 12
        args.zipfs = [0.99]
    out = run(args)
    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    if args.smoke:
        sw = out["sweeps"][0]
        ok = (sw["tier"]["promotions"] > 0
              and sw["hits_tier"] == sw["hits_flat"])
        print(f"[tier_sweep] smoke {'OK' if ok else 'FAIL'}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
