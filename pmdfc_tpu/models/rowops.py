"""Shared fused-row primitives for all hash-index families.

A "row" is one probe window stored as `uint32[4*S]`: four S-lane groups
`[khi | klo | vhi | vlo]` (S = 32 by default, so a row is exactly one 128-lane
TPU vreg row). Every index gathers rows with a single `table[row_ids]` and then
works purely on VPU lanes — this layout measured ~40× faster than the naive
`[C, S, 2]` struct-of-pairs form, whose 2-wide minor axis tile-pads 64×.

Reference probe geometry being mirrored: 4 pairs/cacheline × 8 cachelines =
32-slot window (`server/CCEH_hybrid.h:14-19`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pmdfc_tpu.utils.keys import INVALID_WORD, is_invalid


def match_mask(rows: jnp.ndarray, keys: jnp.ndarray, s: int) -> jnp.ndarray:
    """eq[B, S]: key-equality one-hot with INVALID queries masked off —
    the single definition of "this lane holds this key"."""
    eq = (rows[:, 0:s] == keys[:, 0:1]) & (rows[:, s : 2 * s] == keys[:, 1:2])
    return eq & ~is_invalid(keys)[:, None]


def match_rows(rows: jnp.ndarray, keys: jnp.ndarray, s: int):
    """rows[B, 4S] vs keys[B, 2] -> (eq[B, S] one-hot, slot[B] or -1)."""
    eq = match_mask(rows, keys, s)
    slot = jnp.argmax(eq, axis=1).astype(jnp.int32)
    return eq, jnp.where(eq.any(axis=1), slot, jnp.int32(-1))


def lane_pick(rows: jnp.ndarray, onehot: jnp.ndarray, lo: int, s: int):
    """Masked-sum extraction of ONE lane per row (≤1 hot lane per row).
    Summed as int32 (same bits: one nonzero term, wrapping adds) because
    Mosaic cannot reduce unsigned integers."""
    grp = jax.lax.bitcast_convert_type(rows[:, lo : lo + s], jnp.int32)
    picked = jnp.where(onehot, grp, jnp.int32(0)).sum(axis=1, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(picked, jnp.uint32)


def pick_kv(rows: jnp.ndarray, onehot: jnp.ndarray, s: int):
    """(keys[B, 2], vals[B, 2]) at the hot lane of each row."""
    k = jnp.stack(
        [lane_pick(rows, onehot, 0, s), lane_pick(rows, onehot, s, s)], axis=-1
    )
    v = jnp.stack(
        [lane_pick(rows, onehot, 2 * s, s), lane_pick(rows, onehot, 3 * s, s)],
        axis=-1,
    )
    return k, v


def free_lanes(rows: jnp.ndarray, s: int) -> jnp.ndarray:
    """bool[B, S]: lanes whose key is INVALID (empty slots)."""
    return (rows[:, 0:s] == jnp.uint32(0xFFFFFFFF)) & (
        rows[:, s : 2 * s] == jnp.uint32(0xFFFFFFFF)
    )


def nth_lane(mask: jnp.ndarray, rank: jnp.ndarray) -> jnp.ndarray:
    """One-hot[B, S] of the rank-th True lane per row (all-False if rank
    exceeds the population count)."""
    pos = jnp.cumsum(mask, axis=1) - 1
    return mask & (pos == rank[:, None])


def place_free_phase(table: jnp.ndarray, prot: jnp.ndarray, r: jnp.ndarray,
                     keys: jnp.ndarray, vals: jnp.ndarray,
                     active: jnp.ndarray, s: int,
                     rank: jnp.ndarray | None = None):
    """Place active keys into free lanes of row r, rank-deconflicted.

    `prot` is a per-row uint32 lane bitmask of same-batch placements (kept so
    later displacement phases never touch them). Returns
    (table, prot, placed[B], slot[B] or -1). Callers sequence phases and
    re-gather between them, so cross-phase conflicts resolve by occupancy.

    `rank` lets callers that already built an insert sort plan
    (`base.plan_insert`) pass per-row ranks of `active` instead of paying
    this helper's own sort (sorts are the second-largest insert cost after
    scatters on the target chip).
    """
    c = table.shape[0]
    rows = table[r]
    if rank is None:
        from pmdfc_tpu.models.base import batch_rank_by_segment

        rank = batch_rank_by_segment(r.astype(jnp.uint32), active)
    free = free_lanes(rows, s)
    can = active & (rank < free.sum(axis=1))
    hot = nth_lane(free, rank)
    lane = jnp.argmax(hot, axis=1).astype(jnp.int32)
    table = scatter_entry(table, r, lane, keys, vals, s, can)
    bit = jnp.uint32(1) << lane.astype(jnp.uint32)
    prot = prot.at[jnp.where(can, r, jnp.int32(c))].add(bit, mode="drop")
    return table, prot, can, jnp.where(can, r * s + lane, jnp.int32(-1))


def scatter_entry(table: jnp.ndarray, rows: jnp.ndarray, lanes: jnp.ndarray,
                  keys: jnp.ndarray, values: jnp.ndarray, s: int,
                  mask: jnp.ndarray) -> jnp.ndarray:
    """Write (key, value) at (row, lane) where mask; masked-off rows drop.

    (row, lane) pairs must be unique among masked elements.
    """
    n = table.shape[0]
    r = jnp.where(mask, rows, jnp.int32(n))
    lane = jnp.maximum(lanes, 0)
    table = table.at[r, lane].set(keys[:, 0], mode="drop")
    table = table.at[r, s + lane].set(keys[:, 1], mode="drop")
    table = table.at[r, 2 * s + lane].set(values[:, 0], mode="drop")
    table = table.at[r, 3 * s + lane].set(values[:, 1], mode="drop")
    return table


def no_evict_stub(b: int):
    """False branch for the guarded-eviction lax.cond shared by the
    families that skip eviction work on non-overflowing batches (hotring
    overflow, level bottom-tier displacement): table unchanged, no
    evicted pair, no placements. Kept HERE so the cond's output pytree
    has one definition — the true branches differ per policy, the no-op
    must not drift."""

    def stub(tb):
        inv2 = jnp.full((b, 2), INVALID_WORD, jnp.uint32)
        return (tb, inv2, inv2, jnp.zeros((b,), bool),
                jnp.zeros((b,), jnp.int32))

    return stub


def lean_miss_tail(keys: jnp.ndarray, missed: jnp.ndarray,
                   base_values: jnp.ndarray, base_found: jnp.ndarray,
                   probe, width: int | None = None):
    """Shared lean-GET miss tail: probe ONLY the `missed` lanes at a
    compacted narrow width, falling back to a full-width probe under
    `lax.cond` when the miss set overflows the buffer (absent-key
    storms stay exact). One definition for level's bottom tier and
    path's bank 1 — the compaction/scatter-back/fallback machinery must
    not drift per family (code-review r5).

    `probe(ks) -> (values[B', 2], found[B'])` must treat INVALID keys as
    guaranteed misses (every match helper here does). Returns the merged
    `(values[B, 2], found[B])`.
    """

    b = keys.shape[0]
    W = width if width is not None else min(b, max(1024, b // 8))

    def full(_):
        v, f = probe(keys)
        m = missed & f
        return jnp.where(m[:, None], v, base_values), base_found | m

    if W >= b:
        return full(None)

    def narrow(_):
        from pmdfc_tpu.models.base import compact_mask

        idx, in_w, safe, _over = compact_mask(missed, W)
        ks = jnp.where(in_w[:, None], keys[safe], jnp.uint32(INVALID_WORD))
        v, f = probe(ks)
        pos = jnp.where(f, idx, jnp.int32(b))
        fb = jnp.zeros((b,), bool).at[pos].set(True, mode="drop")
        out = jnp.zeros((b, 2), jnp.uint32).at[pos].set(v, mode="drop")
        return jnp.where(fb[:, None], out, base_values), base_found | fb

    ms = missed.sum()  # one reduction feeds both branch decisions

    def tail(_):
        return jax.lax.cond(ms > W, full, narrow, None)

    # zero-miss batches (every key resolved in the primary windows — the
    # fill-phase GET common case) pay one predicate, not a padded narrow
    # probe over W INVALID keys
    return jax.lax.cond(
        ms > 0, tail, lambda _: (base_values, base_found), None
    )


def lean_two_window(table: jnp.ndarray, r1: jnp.ndarray, r2: jnp.ndarray,
                    keys: jnp.ndarray, s: int):
    """Lean GET over two hashed windows: (values[B,2] zero-on-miss,
    found[B]). Requires the one-location invariant (a key occupies exactly
    one lane across both windows). The two hashes can collide (r1 == r2):
    the windows are then the SAME row and a raw sum would double the
    value — window 2 is masked out in that case."""
    rows1, rows2 = table[r1], table[r2]
    eq1 = match_mask(rows1, keys, s)
    eq2 = match_mask(rows2, keys, s) & (r1 != r2)[:, None]
    values = jnp.stack(
        [
            lane_pick(rows1, eq1, 2 * s, s) + lane_pick(rows2, eq2, 2 * s, s),
            lane_pick(rows1, eq1, 3 * s, s) + lane_pick(rows2, eq2, 3 * s, s),
        ],
        axis=-1,
    )
    return values, eq1.any(axis=1) | eq2.any(axis=1)
