"""Shared index-op result types and the index registry.

Reference interface being mirrored: `IHash` (`server/IHash.h:10-24`) —
`Insert(key, value) -> evicted_key_or_-1`, `Get(key) -> value_or_NONE`,
`Delete(key)`, `Capacity()`, `Utilization()` — lifted to fixed-shape batches.
Batches may contain INVALID (padding) keys, which are no-ops.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax.numpy as jnp

from pmdfc_tpu.config import IndexConfig, IndexKind


class GetResult(NamedTuple):
    values: jnp.ndarray  # uint32[B, 2]; undefined where not found
    found: jnp.ndarray   # bool[B]
    slots: jnp.ndarray   # int32[B] global slot id (for the page pool); -1 if miss


class InsertResult(NamedTuple):
    slots: jnp.ndarray    # int32[B] global slot the key landed in; -1 if not placed
    evicted: jnp.ndarray  # uint32[B, 2] keys evicted to make room (INVALID if none)
    dropped: jnp.ndarray  # bool[B] True when the key itself was dropped
                          # (clean-cache overflow: a legal outcome)
    fresh: jnp.ndarray    # bool[B] True when the key landed in a NEW slot
                          # (False for in-place updates and drops). Lets the
                          # page pool scatter updates before fresh inserts so
                          # a same-slot (update, evicting-insert) pair within
                          # one batch resolves the same way the index did.
    evicted_vals: jnp.ndarray  # uint32[B, 2] values of evicted entries
                          # (INVALID where none). The KV façade reclaims pool
                          # rows from these — the analog of the reference
                          # reusing the evicted entry's page slot.
    # int32[] work counts of a family whose insert loops (CCEH): segments
    # split and attempt rounds run. None = one pass, nothing split. The
    # KV façade folds them into its per-batch work vector
    # (`kv.insert_counted`) and strips them from the result it returns.
    splits: jnp.ndarray | None = None
    rounds: jnp.ndarray | None = None


@dataclasses.dataclass(frozen=True)
class IndexOps:
    """Vtable for one index family."""

    init: Callable[[IndexConfig], Any]
    get_batch: Callable[..., GetResult]
    insert_batch: Callable[..., tuple]
    delete_batch: Callable[..., tuple]  # -> (state, hit[B], old_vals[B, 2])
    num_slots: Callable[[IndexConfig], int]  # static global-slot-space size
    # (state, slots[B], values[B, 2]) -> state: overwrite value lanes at the
    # given global slots (slot -1 = no-op). Lets the KV façade patch pool row
    # ids into freshly placed entries after batched allocation.
    set_values: Callable[..., Any] | None = None
    # (flat_keys[N, 2], flat_vals[N, 2]) view of every slot, N == num_slots.
    # Powers FindAnyway (`server/IKV.h:18`) and Utilization as full scans.
    scan: Callable[[Any], tuple] | None = None
    # Post-restart repair (ref `CCEH::Recovery` `server/CCEH_hybrid.cpp:391`).
    # state -> state; indexes without recovery needs leave it None.
    recovery: Callable[[Any], Any] | None = None
    # (state, hit_slots[B]) -> state: access-heat bookkeeping on GET
    # (hotring's per-access counter bump). The KV façade calls it when set.
    touch: Callable[..., Any] | None = None
    # state -> state: periodic heat drain (hotring counter halving). The KV
    # host wrapper applies it every `IndexConfig.decay_every_gets` keys.
    decay: Callable[[Any], Any] | None = None
    # Roofline shape of the lean GET: gathered units per probed key, and
    # the unit's width in slots (None = the index's cluster_slots). The
    # bench divides GET ops/s by these against a measured gather wall;
    # keeping them here means a family changing its probe pattern (e.g.
    # level's window count) cannot silently desynchronize the artifact's
    # gather_bytes_per_s / gather_wall_frac from the code.
    rows_per_get: int = 1
    gather_row_slots: int | None = None
    # Lean probe: (state, keys) -> (values[B, 2], found[B]) with values
    # already zeroed on miss. Skips slot/argmax bookkeeping — the KV façade
    # uses it on the GET hot path when no pool row or touch hook needs the
    # slot (the probe gather runs at the chip's fixed ~79 Mrows/s issue rate,
    # so every non-gather op directly costs headline throughput).
    get_values: Callable[..., tuple] | None = None
    # True where a batch's `dropped` keys (more new keys for a bucket than
    # it has slots) are what a serial order inserting them first would
    # have evicted: the KV façade then counts and sketches them as
    # capacity evictions, not drops (linear's FIFO clusters).
    overflow_evicts: bool = False


_REGISTRY: dict[IndexKind, IndexOps] = {}


def register_index(kind: IndexKind, ops: IndexOps) -> None:
    _REGISTRY[kind] = ops


def get_index_ops(kind: IndexKind) -> IndexOps:
    # Import lazily so each family registers on first use.
    if kind not in _REGISTRY:
        import importlib

        mod = {
            IndexKind.LINEAR: "pmdfc_tpu.models.linear",
            IndexKind.CCEH: "pmdfc_tpu.models.cceh",
            IndexKind.CUCKOO: "pmdfc_tpu.models.cuckoo",
            IndexKind.CUCKOO_PROBING: "pmdfc_tpu.models.cuckoo_probing",
            IndexKind.LEVEL: "pmdfc_tpu.models.level",
            IndexKind.PATH: "pmdfc_tpu.models.path",
            IndexKind.EXTENDIBLE: "pmdfc_tpu.models.extendible",
            IndexKind.STATIC: "pmdfc_tpu.models.static",
            IndexKind.HOTRING: "pmdfc_tpu.models.hotring",
        }[kind]
        importlib.import_module(mod)
    return _REGISTRY[kind]


def compact_mask(mask: jnp.ndarray, width: int):
    """Gather plan for compacting the True lanes of `mask[B]` into a
    width-W buffer (the straggler-round idiom shared by cuckoo's kick
    loop and path's claim stages — one definition, or the fill-value/
    drop-mode details drift per family).

    Returns `(idx, in_w, safe, overflow)`:
    - `idx[W]` — original positions of the first W True lanes (B pads);
    - `in_w[W]` — which buffer lanes are real;
    - `safe[W]` — `idx` clamped for gathering (`x[safe]` then mask);
    - `overflow[B]` — True lanes that did not fit (callers either report
      them as drops or `lax.cond` to a full-width fallback).
    """
    b = mask.shape[0]
    idx = jnp.nonzero(mask, size=width, fill_value=b)[0]
    in_w = idx < b
    safe = jnp.minimum(idx, b - 1)
    sel = jnp.zeros((b,), bool).at[idx].set(True, mode="drop")
    return idx, in_w, safe, mask & ~sel


def batch_rank_by_segment(segment_ids: jnp.ndarray, mask: jnp.ndarray):
    """Rank of each masked element among batch elements with the same segment id.

    The core primitive for conflict-free batched inserts: where the reference
    serializes same-bucket inserts behind a cluster lock
    (`server/src/linear_probing.cpp:26-65`), we sort the batch by bucket and
    assign each key its offset within its bucket's run — every (bucket, rank)
    pair is then a unique target slot and the whole batch scatters at once.

    Returns int32 ranks (0-based within segment; masked-off elements get
    arbitrary large ranks).
    """
    b = segment_ids.shape[0]
    sort_key = jnp.where(mask, segment_ids.astype(jnp.uint32), jnp.uint32(0xFFFFFFFF))
    order = jnp.argsort(sort_key, stable=True)
    sorted_ids = sort_key[order]
    idx = jnp.arange(b, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    start_idx = jax_cummax(jnp.where(is_start, idx, jnp.int32(0)))
    rank_sorted = idx - start_idx
    return jnp.zeros((b,), jnp.int32).at[order].set(rank_sorted)


def jax_cummax(x: jnp.ndarray) -> jnp.ndarray:
    import jax

    return jax.lax.cummax(x)


class InsertPlan(NamedTuple):
    """Products of ONE fused sort serving both dedupe and segment ranking.

    Sorting by (segment-with-invalid-top-bit, khi, klo) makes duplicate
    keys adjacent (same key ⇒ same segment) AND groups segments
    contiguously, so `dedupe_last_wins` and `batch_rank_by_segment` — two
    separate sorts on the insert hot path — collapse into one lexsort
    plus segmented scans. There is NO explicit original-index operand:
    the sort MUST stay stable (jnp.lexsort is), because ties keeping
    batch order is what makes "last occurrence wins" and plan-order
    ranks deterministic. Invalids ride bit 31 of the segment word
    (row counts never reach 2^31), so validity is not a separate operand
    either. Three operands, not five — sort cost grows with operand
    count and the sort is the insert path's biggest single piece
    (bench/insert_profile.py).
    """

    order: jnp.ndarray      # int32[B]: sorted positions (original indices)
    seg_start: jnp.ndarray  # bool[B] in SORTED space: first row of a run
    winner: jnp.ndarray     # bool[B] in ORIGINAL space: last dup occurrence


def plan_insert(keys: jnp.ndarray, seg: jnp.ndarray,
                valid: jnp.ndarray,
                num_segments: int | None = None) -> InsertPlan:
    # The invalid flag rides bit 31 of the segment word below; a segment
    # id at or above 2^31 would silently corrupt the valid/invalid sort
    # order and the dedupe winners. Row counts are trace-time constants,
    # so callers pass theirs and the bound is enforced statically
    # (ADVICE r4 item 2 — a comment-level invariant is not a check).
    if num_segments is not None and num_segments >= (1 << 31):
        # raise, not assert: python -O strips asserts, which would revert
        # this to the comment-level invariant the check exists to replace
        raise ValueError(
            f"plan_insert: {num_segments} segments >= 2^31 would collide "
            "with the packed invalid bit"
        )
    b = keys.shape[0]
    inv = (~valid).astype(jnp.uint32)
    hi, lo = keys[..., 0], keys[..., 1]
    # THREE sort operands, not five: invalids ride the top bit of the
    # segment word (cluster/bucket ids are table-row counts and can never
    # reach 2^31), and jnp.lexsort's stability replaces the explicit
    # original-index tiebreaker — ties keep batch order, so "last
    # occurrence wins" and plan_rank's plan-order ranks are unchanged.
    # The sort is the insert hot path's biggest single piece
    # (bench/insert_profile.py), and sort cost grows with operand count.
    segp = seg.astype(jnp.uint32) | (inv << jnp.uint32(31))
    order = jnp.lexsort((lo, hi, segp))
    s_hi, s_lo = hi[order], lo[order]
    s_segp = segp[order]
    s_inv = s_segp >> jnp.uint32(31)
    same_next = jnp.concatenate(
        [
            (s_hi[:-1] == s_hi[1:]) & (s_lo[:-1] == s_lo[1:])
            & (s_segp[:-1] == s_segp[1:]),
            jnp.zeros((1,), bool),
        ]
    )
    winner_sorted = ~same_next & (s_inv == 0)
    winner = jnp.zeros((b,), bool).at[order].set(winner_sorted)
    seg_start = jnp.concatenate(
        [
            jnp.ones((1,), bool),
            s_segp[1:] != s_segp[:-1],
        ]
    )
    return InsertPlan(order=order.astype(jnp.int32), seg_start=seg_start,
                      winner=winner)


def plan_rank(plan: InsertPlan, mask: jnp.ndarray) -> jnp.ndarray:
    """int32[B]: 0-based rank of each masked row among masked rows of its
    segment (ordered by the plan's sort); unmasked rows get a huge rank
    (same contract as `batch_rank_by_segment`)."""
    import jax

    m = mask[plan.order].astype(jnp.int32)
    c = jnp.cumsum(m)
    base = jax.lax.cummax(jnp.where(plan.seg_start, c - m, jnp.int32(0)))
    rank_sorted = c - m - base
    rank = jnp.zeros_like(rank_sorted).at[plan.order].set(rank_sorted)
    # same contract as batch_rank_by_segment: unmasked rows get a huge rank,
    # so a consumer's `rank < capacity` test stays inert without re-gating
    return jnp.where(mask, rank, jnp.int32(0x7FFFFFFF))


def dedupe_last_wins(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Mask selecting, for each distinct key in the batch, its LAST occurrence.

    Batched puts must be deterministic: two puts of the same key in one batch
    resolve to the later one (matching the serialized order the reference's
    per-queue lock would impose, `client/rdpma.c:307-320`).
    """
    b = keys.shape[0]
    idx = jnp.arange(b, dtype=jnp.uint32)
    # Leading invalid flag keeps padding rows strictly after — and never
    # equal to — any valid key (a valid key may legitimately have
    # hi == 0xFFFFFFFF, so hi/lo alone cannot disambiguate).
    inv = (~valid).astype(jnp.uint32)
    hi, lo = keys[..., 0], keys[..., 1]
    order = jnp.lexsort((idx, lo, hi, inv))  # (inv, hi, lo), stable by position
    s_hi, s_lo, s_inv = hi[order], lo[order], inv[order]
    same_as_next = jnp.concatenate(
        [
            (s_hi[:-1] == s_hi[1:])
            & (s_lo[:-1] == s_lo[1:])
            & (s_inv[:-1] == s_inv[1:]),
            jnp.zeros((1,), bool),
        ]
    )
    winner_sorted = ~same_as_next
    winner = jnp.zeros((b,), bool).at[order].set(winner_sorted)
    return winner & valid
