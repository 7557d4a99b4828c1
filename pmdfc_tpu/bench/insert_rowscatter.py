"""Experiment: whole-row-rebuild insert vs element-scatter insert.

PERF.md's measured cost model says element scatters run ~8-11 ns/element
(insert writes 4-5 elements/key ⇒ ~40-55 ns/key floor) while FULL-row
scatters run ~54 Mrows/s (~18.5 ns per 256 B row, ~0.3 ns/word). The
current `linear.insert_batch` takes the element path. Hypothesis: rebuild
each touched cluster row once (gather base row → apply every batch write
as lane-masked overlays → segment-combine per cluster → ONE row scatter)
and insert drops to ~gather + a few elementwise passes + row scatter.

This experiment (a) proves the row-rebuild plan equivalent to
`insert_batch` on randomized batches, (b) times both on the target device.
Decision + numbers land in PERF.md; if the row path wins on the chip it
becomes `linear.insert_batch`.

Run: python -m pmdfc_tpu.bench.insert_rowscatter --device tpu --n 8388608
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def build(config):
    """The row-rebuild insert is production code now
    (`models/linear.insert_batch_row`, selectable via PMDFC_INSERT_PATH=row);
    this experiment keeps the equivalence proof and the device timing that
    decide the default."""
    from pmdfc_tpu.models.linear import insert_batch_row

    return insert_batch_row


def check_equivalence(seed: int = 0, trials: int = 40) -> int:
    """Randomized equivalence: same state + same batch through both insert
    implementations must produce identical tables, heads, and results."""
    import jax.numpy as jnp

    from pmdfc_tpu.config import IndexConfig
    from pmdfc_tpu.models import linear as L
    from pmdfc_tpu.utils.keys import INVALID_WORD

    ins2 = build(None)
    rng = np.random.default_rng(seed)
    cfg = IndexConfig(capacity=1 << 9, cluster_slots=16)
    state_a = L.init(cfg)
    state_b = L.LinearState(table=state_a.table, head=state_a.head)
    for t in range(trials):
        bsz = int(rng.integers(8, 65))
        # tiny keyspace: repeats across trials force updates, evictions,
        # and update-vs-evicting-insert lane collisions
        keys = rng.integers(0, 24, (bsz, 2), dtype=np.uint32)
        # sprinkle duplicates and padding
        if bsz > 4:
            keys[rng.integers(bsz)] = keys[rng.integers(bsz)]
            keys[rng.integers(bsz)] = INVALID_WORD
        vals = rng.integers(0, 1 << 30, (bsz, 2), dtype=np.uint32)
        kj, vj = jnp.asarray(keys), jnp.asarray(vals)
        state_a, res_a = L.insert_batch_element(state_a, kj, vj)
        state_b, res_b = ins2(state_b, kj, vj)
        assert np.array_equal(np.asarray(state_a.table),
                              np.asarray(state_b.table)), f"table @ {t}"
        assert np.array_equal(np.asarray(state_a.head),
                              np.asarray(state_b.head)), f"head @ {t}"
        for f in ("slots", "evicted", "dropped", "fresh", "evicted_vals"):
            assert np.array_equal(
                np.asarray(getattr(res_a, f)), np.asarray(getattr(res_b, f))
            ), f"{f} @ {t}"
    return trials


def timeit(fn, state, keys, vals, reps: int) -> float:
    import jax

    # warmup + compile
    s2, r = fn(state, keys, vals)
    jax.block_until_ready(s2.table)
    t0 = time.perf_counter()
    s = state
    for _ in range(reps):
        s, r = fn(s, keys, vals)
    # fetch-closed: a dependent host fetch, not just block_until_ready
    float(np.asarray(s.head[:1])[0])
    return (time.perf_counter() - t0) / reps


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--device", default="cpu", choices=("cpu", "tpu"))
    p.add_argument("--n", type=int, default=1 << 20)
    p.add_argument("--capacity", type=int, default=1 << 22)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--skip-check", action="store_true")
    args = p.parse_args()

    if args.device == "cpu":
        from pmdfc_tpu.bench.common import pin_cpu

        pin_cpu()
    from pmdfc_tpu.bench.common import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from pmdfc_tpu.config import IndexConfig
    from pmdfc_tpu.models import linear as L

    if not args.skip_check:
        trials = check_equivalence()
        print(f"equivalence: {trials} randomized batches OK")

    cfg = IndexConfig(capacity=args.capacity, cluster_slots=16)
    state = L.init(cfg)
    ins2 = build(None)
    # distinct keys (bijective counter spread) — all-fresh steady state
    n = args.n
    flat = (np.arange(n, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15))
    keys = jnp.asarray(
        np.stack([(flat >> np.uint64(32)).astype(np.uint32),
                  flat.astype(np.uint32)], -1)
    )
    vals = jnp.asarray(
        np.stack([np.arange(n, dtype=np.uint32),
                  np.arange(n, dtype=np.uint32) + 1], -1)
    )
    dev = jax.devices()[0]
    t_elem = timeit(L.insert_batch_element, state, keys, vals, args.reps)
    t_row = timeit(ins2, state, keys, vals, args.reps)
    out = {
        "metric": "insert_rowscatter_vs_element",
        "device": dev.platform,
        "n": n,
        "element_ns_per_key": round(t_elem / n * 1e9, 2),
        "row_ns_per_key": round(t_row / n * 1e9, 2),
        "element_mops": round(n / t_elem / 1e6, 2),
        "row_mops": round(n / t_row / 1e6, 2),
        "row_speedup": round(t_elem / t_row, 3),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
