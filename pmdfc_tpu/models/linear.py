"""Linear-probing index with FIFO cluster eviction — the default index.

Reference: `server/src/linear_probing.{h,cpp}` — fixed 16-slot lock-striped
clusters; when a cluster is full the oldest entry is FIFO-evicted and returned
so the KV façade can delete it from the bloom filter
(`server/src/linear_probing.cpp:26-65`). That eviction-on-overflow behavior IS
the clean-cache semantics: the store may drop entries, a miss is legal.

TPU-native redesign (not a translation):
- **Fused-row layout**: one cluster = ONE `uint32[4*S]` row holding four
  S-lane groups `[khi | klo | vhi | vlo]` (S = 32 slots by default → a
  128-lane row, exactly one TPU vreg row and exactly the reference CCEH's
  32-slot probe window, `server/CCEH_hybrid.h:18-19`). A batched GET is a
  single row gather `table[c] -> [B, 128]` followed by pure VPU lane
  compares — measured ~40× faster on TPU than the naive `[C, S, 2]`
  struct-of-pairs layout, whose 2-wide minor axis tile-pads 64× and whose
  value fetch needs extra element gathers.
- Values are extracted from the matched lane with a one-hot masked sum (keys
  are unique within a cluster), not a second gather.
- Per-cluster monotone FIFO cursor `head[C]`: eviction is a pure overwrite
  at `(head + rank) % S`, so a batched insert is a handful of elementwise
  scatters — no shift-left, no locks.
- Same-cluster conflicts inside a batch are resolved by ONE fused sort
  (`plan_insert`/`plan_rank`: dedupe-last-wins + per-cluster ranks from a
  single lexsort) rather than locks: every (cluster, rank) pair is a unique
  target lane. If a single batch carries more than S new keys for one
  cluster the overflow keys are not placed (`InsertResult.dropped`): the
  cluster ends as a serial insert that took them first would leave it
  (each placed, then evicted by the S keys after it), so the KV façade
  counts them as evictions (`IndexOps.overflow_evicts`).
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from pmdfc_tpu.config import IndexConfig, IndexKind
from pmdfc_tpu.models.base import (
    GetResult,
    IndexOps,
    InsertResult,
    plan_insert,
    plan_rank,
    register_index,
)
from pmdfc_tpu.models.rowops import lane_pick as _lane_pick
from pmdfc_tpu.models.rowops import match_mask, match_rows as _match
from pmdfc_tpu.utils.hashing import hash_u64
from pmdfc_tpu.utils.keys import INVALID_WORD, is_invalid


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LinearState:
    table: jnp.ndarray  # uint32[C, 4*S]: lane groups [khi | klo | vhi | vlo]
    head: jnp.ndarray   # uint32[C] monotone FIFO cursor


def _num_clusters(config: IndexConfig) -> int:
    c = max(1, config.capacity // config.cluster_slots)
    # power of two so bucket selection is a mask, not a modulo
    return 1 << (c - 1).bit_length() if c & (c - 1) else c


def num_slots(config: IndexConfig) -> int:
    return _num_clusters(config) * config.cluster_slots


def init(config: IndexConfig) -> LinearState:
    c, s = _num_clusters(config), config.cluster_slots
    table = jnp.concatenate(
        [
            jnp.full((c, 2 * s), INVALID_WORD, jnp.uint32),  # khi | klo
            jnp.zeros((c, 2 * s), jnp.uint32),               # vhi | vlo
        ],
        axis=1,
    )
    return LinearState(table=table, head=jnp.zeros((c,), jnp.uint32))


def _cluster_of(keys: jnp.ndarray, num_clusters: int) -> jnp.ndarray:
    h = hash_u64(keys[..., 0], keys[..., 1])
    return h & jnp.uint32(num_clusters - 1)


@jax.jit
def get_batch(state: LinearState, keys: jnp.ndarray) -> GetResult:
    c_count = state.table.shape[0]
    s = state.table.shape[1] // 4
    c = _cluster_of(keys, c_count)
    rows = state.table[c]  # [B, 4S] — the one gather
    eq, slot = _match(rows, keys, s)
    found = slot >= 0
    values = jnp.stack(
        [_lane_pick(rows, eq, 2 * s, s), _lane_pick(rows, eq, 3 * s, s)],
        axis=-1,
    )
    gslot = jnp.where(
        found, c.astype(jnp.int32) * s + jnp.maximum(slot, 0), jnp.int32(-1)
    )
    return GetResult(values=values, found=found, slots=gslot)


@jax.jit
def get_values(state: LinearState, keys: jnp.ndarray):
    """Lean GET: (values[B, 2] zero-on-miss, found[B]) — no slot math.

    The masked sums already yield 0 for miss rows (all-false one-hot), so no
    extra `where` pass is needed downstream. This is the benched hot path:
    gather + 2 lane-group compares + 3 reductions, nothing else.
    """
    c_count = state.table.shape[0]
    s = state.table.shape[1] // 4
    c = _cluster_of(keys, c_count)
    rows = state.table[c]
    eq = match_mask(rows, keys, s)
    found = eq.any(axis=1)
    values = jnp.stack(
        [_lane_pick(rows, eq, 2 * s, s), _lane_pick(rows, eq, 3 * s, s)],
        axis=-1,
    )
    return values, found


def _insert_plan(state: LinearState, keys: jnp.ndarray):
    """Shared insert prologue — classification both insert paths must agree
    on bit-for-bit: batch plan (dedupe/ranks), update-vs-fresh split, FIFO
    target lanes, drops, and the evicted pair pulled from the ORIGINAL row
    (BF-delete needs the pre-overwrite occupant). Only the scatter strategy
    may differ between the element and row paths.

    Returns (c, s, rows, plan, upd, ins, drop, mslot, pos, pos_hot,
    evicted, evicted_vals).
    """
    c_count = state.table.shape[0]
    s = state.table.shape[1] // 4
    valid = ~is_invalid(keys)
    c = _cluster_of(keys, c_count)
    plan = plan_insert(keys, c, valid, num_segments=c_count)  # one sort
    winner = plan.winner

    rows = state.table[c]
    _, mslot = _match(rows, keys, s)
    upd = winner & (mslot >= 0)
    new = winner & (mslot < 0)

    # fresh inserts: unique (cluster, rank) targets via segment ranking
    rank = plan_rank(plan, new)
    drop = new & (rank >= s)
    ins = new & ~drop
    pos = (state.head[c] + rank.astype(jnp.uint32)) & jnp.uint32(s - 1)
    pos_hot = (
        jnp.arange(s, dtype=jnp.uint32)[None, :] == pos[:, None]
    ) & ins[:, None]
    old = jnp.stack(
        [_lane_pick(rows, pos_hot, 0, s), _lane_pick(rows, pos_hot, s, s)],
        axis=-1,
    )
    old_v = jnp.stack(
        [_lane_pick(rows, pos_hot, 2 * s, s),
         _lane_pick(rows, pos_hot, 3 * s, s)],
        axis=-1,
    )
    # non-ins rows sum to (0, 0) which is not INVALID, but `ins` masks them
    evicted_mask = ins & ~is_invalid(old)
    evicted = jnp.where(
        evicted_mask[:, None], old, jnp.full_like(old, INVALID_WORD)
    )
    evicted_vals = jnp.where(
        evicted_mask[:, None], old_v, jnp.full_like(old_v, INVALID_WORD)
    )
    return (c, s, rows, plan, upd, ins, drop, mslot, pos, pos_hot,
            evicted, evicted_vals)


def _insert_result(c, s, upd, ins, drop, mslot, pos, evicted, evicted_vals):
    """Shared insert epilogue: global slot ids + InsertResult."""
    su = jnp.maximum(mslot, 0)
    gslot = jnp.where(
        upd,
        c.astype(jnp.int32) * s + su,
        jnp.where(ins, c.astype(jnp.int32) * s + pos.astype(jnp.int32),
                  jnp.int32(-1)),
    )
    return InsertResult(
        slots=gslot, evicted=evicted, dropped=drop, fresh=ins,
        evicted_vals=evicted_vals,
    )


@jax.jit
def insert_batch_element(state: LinearState, keys: jnp.ndarray,
                         values: jnp.ndarray):
    c_count = state.table.shape[0]
    (c, s, rows, plan, upd, ins, drop, mslot, pos, pos_hot,
     evicted, evicted_vals) = _insert_plan(state, keys)

    # --- elementwise lane scatters; rows can repeat but (row, lane) targets
    # are unique within each phase. Updates land first so a same-slot
    # (update, evicting-insert) pair resolves in the insert's favor —
    # matching the serialized order a lock would impose.
    table = state.table
    pos_i = pos.astype(jnp.int32)
    su = jnp.maximum(mslot, 0)
    cu = jnp.where(upd, c, jnp.uint32(c_count))  # OOB => dropped by scatter
    ci = jnp.where(ins, c, jnp.uint32(c_count))
    vhi, vlo = values[:, 0], values[:, 1]

    # scatter cost scales with ELEMENTS PROCESSED, not scatter count
    # (~8-11 ns/elem on the target chip even for fully-masked rows), so the
    # update phase is skipped at runtime when the batch carries no updates —
    # the common case for a cleancache fill, worth ~2 passes per batch.
    def with_updates(t):
        t = t.at[cu, 2 * s + su].set(vhi, mode="drop")
        return t.at[cu, 3 * s + su].set(vlo, mode="drop")

    table = jax.lax.cond(upd.any(), with_updates, lambda t: t, table)
    table = table.at[ci, pos_i].set(keys[:, 0], mode="drop")
    table = table.at[ci, s + pos_i].set(keys[:, 1], mode="drop")
    table = table.at[ci, 2 * s + pos_i].set(vhi, mode="drop")
    table = table.at[ci, 3 * s + pos_i].set(vlo, mode="drop")
    head2 = state.head.at[ci].add(jnp.uint32(1), mode="drop")

    res = _insert_result(c, s, upd, ins, drop, mslot, pos,
                         evicted, evicted_vals)
    return LinearState(table=table, head=head2), res


@jax.jit
def insert_batch_row(state: LinearState, keys: jnp.ndarray,
                     values: jnp.ndarray):
    """Whole-row-rebuild insert — the alternative to the element-scatter
    path (`insert_batch_element`): gather each touched cluster row once,
    merge every batch write as lane-masked overlays combined per cluster
    (segment sums in plan order), then ONE full-row scatter.

    Exactly equivalent to the element path (shared `_insert_plan`
    classification; randomized-equivalence proven in
    `tests/test_linear.py`); which one is faster is device-dependent —
    PERF.md's cost model says elements cost ~8-11 ns each (4-5/key) while
    full 256 B rows scatter at ~18.5 ns/row, so the row path should win
    on-chip once a batch writes >2-3 elements/key. Select with
    PMDFC_INSERT_PATH=row until the on-chip decision flips the default.
    """
    c_count = state.table.shape[0]
    b = keys.shape[0]
    valid = ~is_invalid(keys)
    (c, s, rows, plan, upd, ins, drop, mslot, pos, ins_hot,
     evicted, evicted_vals) = _insert_plan(state, keys)
    lane = jnp.arange(s, dtype=jnp.uint32)[None, :]
    upd_hot = (lane == jnp.maximum(mslot, 0).astype(jnp.uint32)[:, None]
               ) & upd[:, None]

    khi, klo = keys[:, 0], keys[:, 1]
    vhi, vlo = values[:, 0], values[:, 1]
    zero = jnp.uint32(0)
    # two write planes: inserts and updates can legally target the SAME
    # lane (a fresh insert evicting the very slot another batch element
    # is updating); the element path's scatter order makes the insert
    # win, so the planes combine separately and insert takes priority
    ins4 = jnp.concatenate(
        [
            jnp.where(ins_hot, khi[:, None], zero),
            jnp.where(ins_hot, klo[:, None], zero),
            jnp.where(ins_hot, vhi[:, None], zero),
            jnp.where(ins_hot, vlo[:, None], zero),
        ],
        axis=1,
    )
    ins_m4 = jnp.tile(ins_hot, (1, 4))
    upd4 = jnp.concatenate(
        [
            jnp.zeros_like(upd_hot, jnp.uint32),
            jnp.zeros_like(upd_hot, jnp.uint32),
            jnp.where(upd_hot, vhi[:, None], zero),
            jnp.where(upd_hot, vlo[:, None], zero),
        ],
        axis=1,
    )
    upd_m4 = jnp.concatenate(
        [jnp.zeros_like(upd_hot), jnp.zeros_like(upd_hot),
         upd_hot, upd_hot], axis=1,
    )

    # combine all writes of one cluster: within a plane the
    # (cluster, lane) targets are unique, so a per-segment SUM in plan
    # order is an exact merge
    order = plan.order
    seg_id = jnp.cumsum(plan.seg_start.astype(jnp.int32)) - 1
    ci_m = jax.ops.segment_sum(ins_m4[order].astype(jnp.uint32), seg_id,
                               num_segments=b)
    ci_v = jax.ops.segment_sum(ins4[order], seg_id, num_segments=b)
    cu_m = jax.ops.segment_sum(upd_m4[order].astype(jnp.uint32), seg_id,
                               num_segments=b)
    cu_v = jax.ops.segment_sum(upd4[order], seg_id, num_segments=b)

    rows_s = rows[order]
    merged = jnp.where(
        ci_m[seg_id] > 0,
        ci_v[seg_id],
        jnp.where(cu_m[seg_id] > 0, cu_v[seg_id], rows_s),
    )
    c_s = c[order]
    valid_s = valid[order]
    first = plan.seg_start & valid_s  # invalid runs never scatter
    target = jnp.where(first, c_s, jnp.uint32(c_count))
    table = state.table.at[target].set(merged, mode="drop")
    head2 = state.head.at[
        jnp.where(ins, c, jnp.uint32(c_count))
    ].add(jnp.uint32(1), mode="drop")

    res = _insert_result(c, s, upd, ins, drop, mslot, pos,
                         evicted, evicted_vals)
    return LinearState(table=table, head=head2), res


# Insert-path selection: the element path is the measured default; set
# PMDFC_INSERT_PATH=row to run the whole stack (KV facade, engine, bench)
# through the row-rebuild path — the on-chip comparison that decides the
# permanent default (PERF.md "Pending on-chip experiments").
insert_batch = (
    insert_batch_row
    if os.environ.get("PMDFC_INSERT_PATH") == "row"
    else insert_batch_element
)


@jax.jit
def delete_batch(state: LinearState, keys: jnp.ndarray):
    c_count = state.table.shape[0]
    s = state.table.shape[1] // 4
    c = _cluster_of(keys, c_count)
    rows = state.table[c]
    eq, slot = _match(rows, keys, s)
    hit = slot >= 0
    old_vals = jnp.stack(
        [_lane_pick(rows, eq, 2 * s, s), _lane_pick(rows, eq, 3 * s, s)],
        axis=-1,
    )
    old_vals = jnp.where(
        hit[:, None], old_vals, jnp.full_like(old_vals, INVALID_WORD)
    )
    cd = jnp.where(hit, c, jnp.uint32(c_count))
    sd = jnp.maximum(slot, 0)
    inval = jnp.full((keys.shape[0],), INVALID_WORD, jnp.uint32)
    table = state.table.at[cd, sd].set(inval, mode="drop")
    table = table.at[cd, s + sd].set(inval, mode="drop")
    return dataclasses.replace(state, table=table), hit, old_vals


@jax.jit
def set_values(state: LinearState, slots: jnp.ndarray, values: jnp.ndarray):
    """Overwrite value lanes at global slots (slot -1 ⇒ no-op)."""
    c_count = state.table.shape[0]
    s = state.table.shape[1] // 4
    ok = slots >= 0
    c = jnp.where(ok, slots // s, jnp.int32(c_count)).astype(jnp.uint32)
    lane = jnp.maximum(slots, 0) % s
    table = state.table.at[c, 2 * s + lane].set(values[:, 0], mode="drop")
    table = table.at[c, 3 * s + lane].set(values[:, 1], mode="drop")
    return dataclasses.replace(state, table=table)


def scan(state: LinearState):
    s = state.table.shape[1] // 4
    keys = jnp.stack(
        [state.table[:, 0:s].reshape(-1), state.table[:, s : 2 * s].reshape(-1)],
        axis=-1,
    )
    vals = jnp.stack(
        [
            state.table[:, 2 * s : 3 * s].reshape(-1),
            state.table[:, 3 * s : 4 * s].reshape(-1),
        ],
        axis=-1,
    )
    return keys, vals


register_index(
    IndexKind.LINEAR,
    IndexOps(
        init=init,
        get_batch=get_batch,
        insert_batch=insert_batch,
        delete_batch=delete_batch,
        num_slots=num_slots,
        scan=scan,
        set_values=set_values,
        get_values=get_values,
        overflow_evicts=True,
    ),
)
