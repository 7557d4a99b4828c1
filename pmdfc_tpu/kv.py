"""KV façade — the L2 layer: one index + bloom filter + page pool + extents.

Reference: `server/KV.{h,cpp}` / `server/IKV.h:10-23` — `Insert` updates the
counting bloom filter and propagates index evictions into BF deletes
(`KV.cpp:100-127`); `InsertExtent/GetExtent` decompose page runs into aligned
power-of-two covers sharing one extent record (`KV.cpp:129-185`,
`CCEH::Insert_extent` `CCEH_hybrid.cpp:90-105`, `Get_extent` :330-341);
plus `Delete, FindAnyway, Recovery, Utilization, Capacity, PrintStats`.

TPU-native redesign:
- All mutation is functional: `KVState -> KVState` under `jit`, one fused
  program per op (index scatter + BF scatter-add + pool scatter in a single
  XLA computation — the reference needs three locked data structures).
- Miss-is-legal everywhere (clean-cache semantics): `get` returns a `found`
  mask, eviction and batch-overflow drops are reported, never raised.
- Extents: covers are index entries whose value carries an *extent-record id*
  (tag bit 63 of the value, same bit the reference's cuckoo-probing steals for
  its `cuckooBit`, `server/src/cuckoo_probing.h:13`). Records live in a
  fixed-size SoA ring (clean-cache: old extents may be overwritten). A
  `get_extent` probes ALL heights of ALL keys as ONE batched index get of
  shape [B*H] — the reference's ascending-height loop (`CCEH_hybrid.cpp:
  330-341`) becomes a single gather + first-hit selection, and unlike the
  reference we validate `key < base + len` so a stale cover cannot return a
  wrong page.
- Stats are a device int32 vector bumped inside the same jitted op (the
  reference's `kv_putcnt/kv_getcnt` + KV_DEBUG timers, `KV.cpp:100-127`).

The host-facing `KV` class pads arbitrary host batches to power-of-two shapes
(bounded set of compiled programs) and exposes the reference's method names.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pmdfc_tpu import tier as tier_mod
from pmdfc_tpu.config import KVConfig, TierConfig
from pmdfc_tpu.models.base import dedupe_last_wins, get_index_ops
from pmdfc_tpu.ops import bloom as bloom_ops
from pmdfc_tpu.ops import pagepool
from pmdfc_tpu.utils.hashing import shard_of
from pmdfc_tpu.utils.keys import INVALID_WORD, is_invalid

# stats vector layout. The trailing miss_* lanes are the MISS-CAUSE
# TAXONOMY: every recorded miss carries exactly one cause, and
# `misses == Σ miss_*` holds on every stats surface (KV.stats,
# shard_report sums, KVServer.health, the MSG_STATS wire snapshot) —
# the same one-source-of-truth rule PR 5 pinned for tier counters.
(PUTS, GETS, HITS, MISSES, EVICTIONS, DROPS, EXTENT_PUTS, DELETES,
 CORRUPT_PAGES, MISS_COLD, MISS_EVICTED, MISS_PARKED, MISS_STALE,
 MISS_DIGEST, MISS_ROUTED, MISS_RECOVERING, MISS_SHED,
 MISS_QUARANTINED, MISS_DEADLINE) = range(19)
STAT_NAMES = [
    "puts", "gets", "hits", "misses", "evictions", "drops",
    "extent_puts", "deletes", "corrupt_pages",
    # miss causes, in taxonomy order:
    "miss_cold",     # never inserted (or inserted only as an extent cover)
    "miss_evicted",  # capacity-evicted (FIFO cluster eviction, cuckoo
                     # displacement-to-death, ...) — attributed via the
                     # evicted-key sketch below
    "miss_parked",   # balloon-shrunk/parked: NOPAGE placement, or a
                     # current-generation row ballooned out of circulation
    "miss_stale",    # generation mismatch after a forced balloon shrink
    "miss_digest",   # bytes failed their at-rest digest (rides with
                     # corrupt_pages; the page is never returned)
    "miss_routed",   # a2a bucket-overflow shed (host-routed plane is
                     # loss-free; only the a2a dispatch can manufacture it)
    "miss_recovering",  # would-be miss_cold during a warm restart's
                        # recovering window: the key may simply not have
                        # caught up yet (ring migration / anti-entropy
                        # still draining) — reattributed batch-local so
                        # misses == Σ causes stays exact mid-recovery
    "miss_shed",  # QoS overload shed at the serving edge (token-bucket
                  # admission or staged-queue shed ladder, runtime/qos):
                  # the op was answered all-miss/ack-and-drop WITHOUT a
                  # device dispatch. Host-side only — no device program
                  # ever bumps this lane; accounted via `account_shed`
                  # into the host overlay so the sum invariant holds.
    "miss_quarantined",  # the key's owning shard sits behind an OPEN
                         # shard-scoped breaker (failure.ShardQuarantine):
                         # the GET degrades to a legal miss host-side
                         # before any device dispatch; accounted via
                         # `account_quarantined` (host overlay only).
    "miss_deadline",  # the op's end-to-end deadline budget expired while
                      # staged: shed before device dispatch (an expired
                      # op never burns a flush slot); accounted via
                      # `account_deadline` (host overlay only).
]
NSTATS = len(STAT_NAMES)
MISS_CAUSE_NAMES = tuple(STAT_NAMES[MISS_COLD:MISS_DEADLINE + 1])

EXTENT_TAG = 0x80000000  # bit 63 of the u64 value marks an extent-record ref
NOPAGE_TAG = 0xC0000000  # tiered pool: entry placed but no row allocated
                         # (balloon exhaustion — the entry is a legal miss)
EXTENT_REC_WORDS = 6     # khi, klo, vhi, vlo, len, valid


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ExtentState:
    recs: jnp.ndarray    # uint32[N, 6]
    cursor: jnp.ndarray  # uint32[] bump/ring cursor


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVState:
    index: Any
    bloom: bloom_ops.BloomState | None
    # page store when paged: flat PoolState, or tier.TierState (hot/cold
    # pools + migration planes) when the tier subsystem is enabled. All
    # device ops dispatch on the pytree type at trace time, so the two
    # layouts never share compiled programs.
    pool: pagepool.PoolState | tier_mod.TierState | None
    extents: ExtentState
    stats: jnp.ndarray           # int32[NSTATS]
    # evicted-key sketch: a plain (non-counting) bloom of keys the index
    # capacity-evicted, written inside the same insert program that
    # evicts. GET-time misses split on it: sketch hit ⇒ `miss_evicted`,
    # else `miss_cold`. Approximate BY DESIGN (bits never clear: a key
    # evicted, re-inserted, deleted, then missed again still reads
    # "evicted") — attribution may drift toward `evicted` at saturation,
    # but Σ causes == misses holds exactly and no miss is double-counted.
    evicted_filter: jnp.ndarray  # bool[KVConfig.evicted_sketch_bits]


def _init_extents(capacity: int) -> ExtentState:
    return ExtentState(
        recs=jnp.zeros((capacity, EXTENT_REC_WORDS), jnp.uint32),
        cursor=jnp.zeros((), jnp.uint32),
    )


def _admit_cfg_at_init(tcfg: TierConfig) -> TierConfig:
    """Apply the `PMDFC_ADMIT` escape hatch to an effective tier config
    (init-time only, the `PMDFC_TIER` discipline: after init the
    STATE's pytree structure — admit leaves present or not — carries
    the decision, so a mid-process env flip never mixes programs).
    `off` strips the gate (the TierState never grows the sketch leaves
    and the serving tree is bit-identical to an admission-less config);
    `on` installs `AdmitConfig()` defaults on a tiered config that
    carries none."""
    import os

    from pmdfc_tpu.config import AdmitConfig

    env = os.environ.get("PMDFC_ADMIT", "")
    if env not in ("", "on", "off"):
        # a typo'd flag must not silently run the other promotion policy
        raise ValueError(
            f"PMDFC_ADMIT={env!r}: expected 'on', 'off', or unset")
    if env == "off" and tcfg.admit is not None:
        return dataclasses.replace(tcfg, admit=None)
    if env == "on" and tcfg.admit is None:
        return dataclasses.replace(tcfg, admit=AdmitConfig())
    return tcfg


def _tier_cfg_at_init(config: KVConfig) -> TierConfig | None:
    """Effective tier config, env escape hatches applied (init-time
    only: after init the pool's pytree TYPE carries the decision, so a
    mid-process env flip never mixes programs). `PMDFC_ADMIT` rides
    the same resolution (see `_admit_cfg_at_init`)."""
    if not config.paged:
        return None
    import os

    env = os.environ.get("PMDFC_TIER", "")
    if env not in ("", "on", "off"):
        # a typo'd flag must not silently run the other pool layout
        raise ValueError(
            f"PMDFC_TIER={env!r}: expected 'on', 'off', or unset")
    if env == "off":
        return None
    if config.tier is not None:
        return _admit_cfg_at_init(config.tier)
    return _admit_cfg_at_init(TierConfig()) if env == "on" else None


def _tcfg(config: KVConfig) -> TierConfig:
    """Tier knobs for an already-tiered state (config.tier, or the
    defaults when the tier came from PMDFC_TIER=on)."""
    return config.tier if config.tier is not None else TierConfig()


def init(config: KVConfig) -> KVState:
    ops = get_index_ops(config.index.kind)
    n = ops.num_slots(config.index)
    pool = None
    if config.paged:
        tcfg = _tier_cfg_at_init(config)
        pool = (tier_mod.init(n, config.page_words, tcfg)
                if tcfg is not None
                else pagepool.init(n, config.page_words))
    return KVState(
        index=ops.init(config.index),
        bloom=bloom_ops.init(config.bloom) if config.bloom else None,
        pool=pool,
        extents=_init_extents(config.extent_capacity),
        stats=jnp.zeros((NSTATS,), jnp.int32),
        evicted_filter=jnp.zeros((config.evicted_sketch_bits,), bool),
    )


# ---------------------------------------------------------------------------
# core batched ops (functional; `config` is static)
# ---------------------------------------------------------------------------

# evicted-key sketch (see KVState.evicted_filter): 2 independent hash
# family members, seeds salted away from every index/bloom/shard seed
_SKETCH_SEEDS = (0x0E51C7ED, 0x0E51C7ED ^ 0x9E3779B9)


def _sketch_slots(config: KVConfig, keys: jnp.ndarray) -> jnp.ndarray:
    """int32[len(_SKETCH_SEEDS), B] sketch bit positions per key."""
    from pmdfc_tpu.utils.hashing import hash_u64

    nb = jnp.uint32(config.evicted_sketch_bits)
    return jnp.stack([
        (hash_u64(keys[..., 0], keys[..., 1], seed=s) % nb)
        .astype(jnp.int32)
        for s in _SKETCH_SEEDS
    ])


def _sketch_mark(state: KVState, config: KVConfig, keys: jnp.ndarray,
                 mask: jnp.ndarray) -> KVState:
    """Record capacity-evicted keys in the sketch. Cond-gated like
    `_bf_delete`: eviction-free batches (the fill phase) pay nothing."""

    def go(f):
        idx = _sketch_slots(config, keys)
        idx = jnp.where(mask[None, :], idx,
                        jnp.int32(config.evicted_sketch_bits))
        return f.at[idx.reshape(-1)].set(True, mode="drop")

    f = jax.lax.cond(mask.any(), go, lambda f: f, state.evicted_filter)
    return dataclasses.replace(state, evicted_filter=f)


def _sketch_query(state: KVState, config: KVConfig,
                  keys: jnp.ndarray) -> jnp.ndarray:
    """bool[B] — all sketch bits set (the key was capacity-evicted at
    some point; see the approximation note on `KVState.evicted_filter`)."""
    idx = _sketch_slots(config, keys)
    hit = state.evicted_filter[idx[0]]
    for i in range(1, len(_SKETCH_SEEDS)):
        hit = hit & state.evicted_filter[idx[i]]
    return hit


def _index_miss_causes(bumps: jnp.ndarray, state: KVState,
                       config: KVConfig, keys: jnp.ndarray,
                       idx_miss: jnp.ndarray) -> jnp.ndarray:
    """Split index-level misses (no entry for the key) into
    `miss_evicted` (evicted-key sketch hit) vs `miss_cold`."""
    ev = idx_miss & _sketch_query(state, config, keys)
    bumps = bumps.at[MISS_EVICTED].add(ev.sum(dtype=jnp.int32))
    bumps = bumps.at[MISS_COLD].add((idx_miss & ~ev).sum(dtype=jnp.int32))
    return bumps


def _bf_insert(state: KVState, config: KVConfig, keys, mask) -> KVState:
    if state.bloom is None:
        return state
    b = bloom_ops.insert_batch(
        state.bloom, keys, mask, num_hashes=config.bloom.num_hashes
    )
    return dataclasses.replace(state, bloom=b)


def _bf_delete(state: KVState, config: KVConfig, keys, mask) -> KVState:
    if state.bloom is None:
        return state
    # a fully-masked scatter still pays per-ELEMENT cost on the target
    # device (~8-11 ns/elem × num_hashes, see PERF.md), so eviction-free
    # batches — the common cleancache fill — skip the whole pass
    b = jax.lax.cond(
        mask.any(),
        lambda bl: bloom_ops.delete_batch(
            bl, keys, mask, num_hashes=config.bloom.num_hashes
        ),
        lambda bl: bl,
        state.bloom,
    )
    return dataclasses.replace(state, bloom=b)


def _is_tagged(vals: jnp.ndarray) -> jnp.ndarray:
    return vals[..., 0] == jnp.uint32(EXTENT_TAG)


def _is_special(vals: jnp.ndarray) -> jnp.ndarray:
    """Paged-mode: a set top-2-bit hi word = NOT a page-row value
    (EXTENT_TAG = 0b10..., NOPAGE = 0b11...). Page entries store
    [generation, row] — flat pools always write gen 0, the tiered pool
    uses the low 30 hi-word bits for the cold row's generation
    (`tier.entry_current`), so the tag space and the gen space never
    collide."""
    return (vals[..., 0] >> 30) != jnp.uint32(0)


def _reclaim_evicted(res) -> tuple:
    """(freed_mask, freed_rows) — pool rows released by index evictions.

    Extent-cover and NOPAGE entries carry no pool row; their eviction
    frees nothing.
    """
    evicted_mask = ~is_invalid(res.evicted)
    freed = evicted_mask & ~_is_special(res.evicted_vals)
    rows = jnp.where(freed, res.evicted_vals[:, 1].astype(jnp.int32), -1)
    return freed, rows


@partial(jax.jit, static_argnames=("config",))
def insert(state: KVState, config: KVConfig, keys: jnp.ndarray,
           values: jnp.ndarray):
    """Batched Insert (ref `KV::Insert` `server/KV.cpp:100-127`).

    `values` is pages[B, page_words] when paged else u64 values[B, 2].
    Index insert + BF insert of landed keys + BF delete of evicted keys +
    pool-row recycle/alloc + page scatter — one fused program.

    Paged mode stores each entry's pool row id as its index value (the
    reference stores the page's buffer address the same way), so index
    mutations that MOVE entries (CCEH splits, cuckoo kicks) never copy pages.
    """
    ops = get_index_ops(config.index.kind)
    valid = ~is_invalid(keys)

    if state.pool is not None:
        # Existing entries keep their row; fresh ones get a 0 placeholder
        # patched after allocation.
        pre = ops.get_batch(state.index, keys)
        keep = pre.found & ~_is_special(pre.values)
        if isinstance(state.pool, tier_mod.TierState):
            # a stale entry (generation mismatch after a forced balloon
            # shrink recirculated its row) must NOT keep "its" row — the
            # row may belong to another key now; the put converts instead
            keep = keep & tier_mod.entry_current(state.pool, pre.values)
        index_vals = jnp.where(keep[:, None], pre.values, jnp.uint32(0))
    else:
        index_vals = values

    new_index, res = ops.insert_batch(state.index, keys, index_vals)
    state = dataclasses.replace(state, index=new_index)

    placed = valid & ~res.dropped
    state = _bf_insert(state, config, keys, placed)
    evicted_mask = ~is_invalid(res.evicted)
    state = _bf_delete(state, config, res.evicted, evicted_mask)
    # capacity evictions enter the evicted-key sketch HERE — the one
    # program that knows a key died of capacity, so a later GET's miss
    # can name the cause (`miss_evicted`, never a silent "cold")
    state = _sketch_mark(state, config, res.evicted, evicted_mask)

    if state.pool is not None:
        tiered = isinstance(state.pool, tier_mod.TierState)
        wrote = res.slots >= 0
        # A plain put over an extent-cover, NOPAGE, or stale entry
        # converts it to a (fresh-rowed) page entry — anything `keep`
        # rejected that still landed.
        conv = wrote & ~res.fresh & pre.found & ~keep
        want = res.fresh | conv
        freed, freed_rows = _reclaim_evicted(res)
        if tiered:
            # never free a row off a STALE evicted value (the row was
            # recirculated by the balloon; it belongs to someone else)
            freed = freed & tier_mod.entry_current(state.pool,
                                                   res.evicted_vals)
            pool, new_rows = tier_mod.recycle_and_alloc(
                state.pool, _tcfg(config), freed, freed_rows, want
            )
            row_vals = tier_mod.row_values(pool, new_rows)
        else:
            pool, new_rows = pagepool.recycle_and_alloc(
                state.pool, freed, freed_rows, want
            )
            row_vals = jnp.stack(
                [jnp.zeros_like(new_rows), jnp.maximum(new_rows, 0)],
                axis=-1,
            ).astype(jnp.uint32)
        # Post-verify every row-consuming placement: an entry placed
        # mid-batch can lose its slot to a LATER same-batch eviction (a conv
        # entry FIFO-evicted by a subsequent insert into the same cluster;
        # CCEH fresh entries are safe — prot_bits shields all same-batch
        # placements from the overflow fallback). Writing its row id anyway
        # would be a duplicate-slot scatter with an undefined winner, and
        # would leak or alias the row. One extra row gather buys
        # determinism — and ONLY an eviction can take a placement away, so
        # an eviction-free batch (fill phase, the cleancache common case)
        # skips the gather under lax.cond: lost ⊆ same-batch evictions.
        probe = jnp.where(want[:, None], keys, jnp.uint32(INVALID_WORD))

        def post_verify(idx):
            return want & ~ops.get_batch(idx, probe).found

        lost = jax.lax.cond(
            evicted_mask.any(), post_verify,
            lambda idx: jnp.zeros_like(want), state.index,
        )
        # (new_rows >= 0) is defense-in-depth in flat mode (unreachable
        # when the index conserves slots); under the tier it is REAL — a
        # ballooned-down cold pool can run out of circulating rows.
        good = want & ~lost & (new_rows >= 0)
        if tiered:
            # A placed entry that got no row must not keep its placeholder
            # (it would alias global row 0): stamp the NOPAGE sentinel —
            # the entry reads as a legal first-class miss.
            shortfall = want & ~lost & (new_rows < 0)
            nopage = jnp.broadcast_to(
                jnp.asarray([NOPAGE_TAG, 0], jnp.uint32), row_vals.shape)
            state = dataclasses.replace(
                state,
                index=ops.set_values(
                    state.index,
                    jnp.where(good | shortfall, res.slots, jnp.int32(-1)),
                    jnp.where(good[:, None], row_vals, nopage),
                ),
            )
        else:
            shortfall = jnp.zeros_like(want)
            state = dataclasses.replace(
                state,
                index=ops.set_values(
                    state.index, jnp.where(good, res.slots, jnp.int32(-1)),
                    row_vals,
                ),
            )
        if tiered:
            pool, _ = tier_mod.recycle_and_alloc(
                pool, _tcfg(config), lost, new_rows,
                jnp.zeros_like(lost), balloon=False,
            )
        else:
            pool, _ = pagepool.recycle_and_alloc(
                pool, lost, new_rows, jnp.zeros_like(lost)
            )
        # Ordered page scatters: in-place updates first, newly allocated rows
        # second — a same-row (update, evicting-insert) pair inside one batch
        # then resolves in the insert's favor, matching the index. The
        # integrity sidecar (per-row digest) rides the same two scatters so
        # page bytes and their digest can never publish separately.
        upd_rows = jnp.where(
            wrote & ~want & keep, pre.values[:, 1].astype(jnp.int32), -1
        )
        alloc_rows = jnp.where(good, new_rows, jnp.int32(-1))
        digs = pagepool.page_digest(values)
        if tiered:
            pool = tier_mod.write_rows(pool, upd_rows, values, digs)
            pool = tier_mod.write_rows(pool, alloc_rows, values, digs)
            acfg = tier_mod.admit_cfg(pool, _tcfg(config))
            if acfg is not None:
                # a put is a touch: written keys accrue admission
                # evidence too (the other consult site is the GET
                # program's fold in `tier.on_get`) — a page the client
                # keeps re-writing earns its hot slot the same way one
                # it keeps re-reading does
                pool = tier_mod.admit_observe(
                    pool, acfg, keys, dedupe_last_wins(keys, valid))
            state = dataclasses.replace(state, pool=pool)
        else:
            pages = pagepool.write_batch(pool.pages, upd_rows, values)
            pages = pagepool.write_batch(pages, alloc_rows, values)
            sums = pagepool.write_sums(pool.sums, upd_rows, digs)
            sums = pagepool.write_sums(sums, alloc_rows, digs)
            state = dataclasses.replace(
                state, pool=dataclasses.replace(pool, pages=pages, sums=sums)
            )
    else:
        shortfall = None

    bumps = jnp.zeros((NSTATS,), jnp.int32)
    bumps = bumps.at[PUTS].add(valid.sum(dtype=jnp.int32))
    bumps = bumps.at[EVICTIONS].add(evicted_mask.sum(dtype=jnp.int32))
    bumps = bumps.at[DROPS].add((valid & res.dropped).sum(dtype=jnp.int32))
    if shortfall is not None:
        # tiered pool-exhaustion drops (flat: structurally zero)
        bumps = bumps.at[DROPS].add(shortfall.sum(dtype=jnp.int32))
    state = dataclasses.replace(state, stats=state.stats + bumps)
    return state, res


def _reattribute_recovering(bumps: jnp.ndarray) -> jnp.ndarray:
    """Recovering serving state: a would-be `miss_cold` cannot be
    distinguished from a key that simply hasn't caught up yet (snapshot
    chain + journal tail restored, ring migration / anti-entropy still
    draining), so the whole cold lane of THIS batch moves to
    `miss_recovering`. Batch-local on the bumps vector, so
    `misses == Σ causes` stays bit-exact through the window; every other
    cause (stale, parked, digest, evicted) keeps its honest label."""
    cold = bumps[MISS_COLD]
    return bumps.at[MISS_RECOVERING].add(cold).at[MISS_COLD].add(-cold)


def _get_core(state: KVState, config: KVConfig, keys: jnp.ndarray,
              lean: bool = False, recovering: bool = False):
    """Shared body of `get` / `get_compact` (ref `KV::Get` `KV.cpp:148`).

    `lean=True` skips hotness bookkeeping (touch) and allows the no-slot
    fast probe even for counter-tracking indexes — the sampled-statistics
    path (`IndexConfig.touch_sample_every`). `recovering=True` is the
    warm-restart serving state: cold misses reattribute to
    `miss_recovering` (see `_reattribute_recovering`).
    """
    ops = get_index_ops(config.index.kind)
    valid = ~is_invalid(keys)
    if ops.get_values is not None and state.pool is None and (
            ops.touch is None or lean):
        # lean probe: no slot bookkeeping, values pre-zeroed on miss
        out, found = ops.get_values(state.index, keys)
        found = found & valid
        bumps = jnp.zeros((NSTATS,), jnp.int32)
        bumps = bumps.at[GETS].add(valid.sum(dtype=jnp.int32))
        bumps = bumps.at[HITS].add(found.sum(dtype=jnp.int32))
        bumps = bumps.at[MISSES].add((valid & ~found).sum(dtype=jnp.int32))
        bumps = _index_miss_causes(bumps, state, config, keys,
                                   valid & ~found)
        if recovering:
            bumps = _reattribute_recovering(bumps)
        return dataclasses.replace(
            state, stats=state.stats + bumps
        ), out, found
    res = ops.get_batch(state.index, keys)
    found = res.found & valid
    # miss-cause planes (disjoint; their sum reconciles with MISSES below)
    idx_miss = valid & ~res.found
    ext_m = jnp.zeros_like(found)     # extent-cover entry: not a page
    nopage_m = jnp.zeros_like(found)  # NOPAGE placement (balloon parked)
    stale_m = jnp.zeros_like(found)   # generation mismatch
    dead_m = jnp.zeros_like(found)    # current gen, row out of circulation
    if ops.touch is not None and not lean:
        # hotness bookkeeping (hotring access counters)
        state = dataclasses.replace(
            state, index=ops.touch(state.index, res.slots)
        )
    corrupt = jnp.zeros_like(found)
    if isinstance(state.pool, tier_mod.TierState):
        # Tiered path: resolve through the global row id (hot rows < H,
        # cold rows >= H), verify against whichever tier's sidecar owns
        # the row, then run the fused hotness/migration epilogue —
        # repeat-touched cold rows promote, victims demote, all inside
        # this same program (`tier.on_get`).
        tag = res.values[:, 0] >> 30  # 0 = page entry, 2 = extent, 3 = NOPAGE
        nopage_m = found & (tag == jnp.uint32(3))
        # every other special tag is "not a page" ⇒ cold for a page GET
        ext_m = found & _is_special(res.values) & ~nopage_m
        found = found & ~_is_special(res.values)
        # stale entries (generation mismatch) are legal misses, never
        # reads of the row's NEW owner
        cur = tier_mod.entry_current(state.pool, res.values)
        stale_m = found & ~cur
        found = found & cur
        rows = jnp.where(found, res.values[:, 1].astype(jnp.int32), -1)
        out = tier_mod.read_batch(state.pool, rows)
        live = tier_mod.row_live(state.pool, rows)
        sums_ok = (pagepool.page_digest(out)
                   == tier_mod.stored_sums(state.pool, rows))
        # a ballooned-out row is a legal MISS, not corruption; only live
        # rows whose bytes fail their digest count as corrupt
        dead_m = found & ~live
        corrupt = found & live & ~sums_ok
        found = found & live & sums_ok
        out = jnp.where(found[:, None], out, jnp.uint32(0))
        if not lean:
            # hotness bookkeeping + fused migration ride the SAMPLED
            # (non-lean) path, same cadence contract as ops.touch — the
            # host wrappers' _touch_due counts tiered pools as
            # touch-tracking so the sampling knob governs tier placement
            # too (and lean batches stay pure reads)
            new_index, pool = tier_mod.on_get(
                ops, state.index, state.pool, _tcfg(config), keys,
                res.slots, rows, out, found,
            )
            state = dataclasses.replace(state, index=new_index, pool=pool)
    elif state.pool is not None:
        # Page gets resolve through the stored pool row id; extent-cover
        # entries (tagged values) are not pages — report them as misses here
        # (get_extent is the op that resolves covers).
        ext_m = found & _is_tagged(res.values)
        found = found & ~ext_m
        rows = jnp.where(found, res.values[:, 1].astype(jnp.int32), -1)
        out = pagepool.read_batch(state.pool.pages, rows)
        # Integrity gate: recompute the digest of the gathered bytes and
        # compare to the row's sidecar sum. A mismatched page is NEVER
        # returned — it degrades to a first-class miss (clean-cache: lose
        # anything, serve nothing wrong) and bumps `corrupt_pages`.
        ok = pagepool.verify_batch(state.pool, rows, out)
        corrupt = found & ~ok
        found = found & ok
        out = jnp.where(found[:, None], out, jnp.uint32(0))
    else:
        out = jnp.where(found[:, None], res.values, jnp.uint32(0))
    bumps = jnp.zeros((NSTATS,), jnp.int32)
    bumps = bumps.at[GETS].add(valid.sum(dtype=jnp.int32))
    bumps = bumps.at[HITS].add(found.sum(dtype=jnp.int32))
    bumps = bumps.at[MISSES].add((valid & ~found).sum(dtype=jnp.int32))
    bumps = bumps.at[CORRUPT_PAGES].add(corrupt.sum(dtype=jnp.int32))
    # miss causes: the planes above are pairwise disjoint and their
    # union is exactly `valid & ~found`, so Σ miss_* == misses holds
    # bit-exactly on every batch. An extent-cover entry is "cold" for a
    # page GET (the key was never inserted AS a page).
    bumps = _index_miss_causes(bumps, state, config, keys, idx_miss)
    bumps = bumps.at[MISS_COLD].add(ext_m.sum(dtype=jnp.int32))
    bumps = bumps.at[MISS_PARKED].add(
        (nopage_m | dead_m).sum(dtype=jnp.int32))
    bumps = bumps.at[MISS_STALE].add(stale_m.sum(dtype=jnp.int32))
    bumps = bumps.at[MISS_DIGEST].add(corrupt.sum(dtype=jnp.int32))
    if recovering:
        bumps = _reattribute_recovering(bumps)
    state = dataclasses.replace(state, stats=state.stats + bumps)
    return state, out, found


def _get_core_dispatch(state: KVState, config: KVConfig, keys: jnp.ndarray,
                       lean: bool = False, recovering: bool = False,
                       fused: bool = False):
    """Static fused/composed fork of the GET body. `fused=True` routes
    through the Pallas device-fused program (`ops/fused.py`) — same
    signature, same returns, bit-identical results/stats/cause lanes; it
    falls back to `_get_core` itself for configs the kernel does not
    support, so callers can thread the flag unconditionally. The import
    is function-local: kv is the module everything else imports, and
    ops/fused imports kv lazily for the shared constants."""
    if fused:
        from pmdfc_tpu.ops import fused as fused_ops

        return fused_ops.get_core(state, config, keys, lean=lean,
                                  recovering=recovering)
    return _get_core(state, config, keys, lean=lean, recovering=recovering)


@partial(jax.jit, static_argnames=("config",))
def get(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Batched Get -> (values_or_pages, found) (ref `KV::Get` `KV.cpp:148`)."""
    return _get_core(state, config, keys)


@partial(jax.jit, static_argnames=("config",))
def get_lean(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Sampled-statistics GET: no hotness bookkeeping (see _get_core)."""
    return _get_core(state, config, keys, lean=True)


@partial(jax.jit, static_argnames=("config",))
def get_recovering(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """GET in the warm-restart serving state (miss_recovering lane)."""
    return _get_core(state, config, keys, recovering=True)


@partial(jax.jit, static_argnames=("config",))
def get_lean_recovering(state: KVState, config: KVConfig,
                        keys: jnp.ndarray):
    """Sampled GET in the warm-restart serving state."""
    return _get_core(state, config, keys, lean=True, recovering=True)


def _get_compact_core(state: KVState, config: KVConfig, keys: jnp.ndarray,
                      lean: bool = False, recovering: bool = False,
                      fused: bool = False):
    """Shared compaction epilogue: stable argsort on ~found keeps the
    found-compressed wire contract identical for both sampling paths."""
    state, out, found = _get_core_dispatch(state, config, keys, lean=lean,
                                           recovering=recovering,
                                           fused=fused)
    order = jnp.argsort(~found, stable=True)
    return (state, out[order], order.astype(jnp.int32), found,
            found.sum(dtype=jnp.int32))


@partial(jax.jit, static_argnames=("config",))
def get_compact(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Get with hit rows compacted to the front -> (state, out_sorted,
    order, found, nfound).

    The serving path must not ship a miss-shaped page row over the link:
    the reference writes ONLY the hit page, straight to the requester
    (`server/rdma_svr.cpp:706-719`). A stable sort on `~found` moves every
    hit row to the front (original request order preserved among hits), so
    the host fetches just `nfound` rows — the found-compressed return —
    while `order[:nfound]` maps them back to request positions.
    """
    return _get_compact_core(state, config, keys)


@partial(jax.jit, static_argnames=("config",))
def get_compact_lean(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Hit-compacted GET without hotness bookkeeping (sampled path)."""
    return _get_compact_core(state, config, keys, lean=True)


@partial(jax.jit, static_argnames=("config",))
def get_compact_recovering(state: KVState, config: KVConfig,
                           keys: jnp.ndarray):
    """Hit-compacted GET in the warm-restart serving state."""
    return _get_compact_core(state, config, keys, recovering=True)


@partial(jax.jit, static_argnames=("config",))
def get_compact_lean_recovering(state: KVState, config: KVConfig,
                                keys: jnp.ndarray):
    """Sampled hit-compacted GET in the warm-restart serving state."""
    return _get_compact_core(state, config, keys, lean=True,
                             recovering=True)


# -- device-fused GET twins (`ops/fused.py`) ---------------------------
# Same signatures and returns as the composed programs above, with the
# probe→gather→verify→classify chain lowered as one Pallas kernel. The
# host wrappers select these names when `fused.resolve(config)` says the
# kernel serves this config (PMDFC_FUSED / KVConfig.fused_get); distinct
# jitted callables keep the kernel-bearing traces out of the composed
# programs' caches, and unsupported configs degrade to the composed body
# INSIDE the fused program (see `_get_core_dispatch`), so selection can
# stay unconditional.


@partial(jax.jit, static_argnames=("config",))
def get_fused(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Device-fused batched Get (counting path)."""
    return _get_core_dispatch(state, config, keys, fused=True)


@partial(jax.jit, static_argnames=("config",))
def get_fused_lean(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Device-fused sampled-statistics GET (no hotness bookkeeping)."""
    return _get_core_dispatch(state, config, keys, lean=True, fused=True)


@partial(jax.jit, static_argnames=("config",))
def get_fused_recovering(state: KVState, config: KVConfig,
                         keys: jnp.ndarray):
    """Device-fused GET in the warm-restart serving state."""
    return _get_core_dispatch(state, config, keys, recovering=True,
                              fused=True)


@partial(jax.jit, static_argnames=("config",))
def get_fused_lean_recovering(state: KVState, config: KVConfig,
                              keys: jnp.ndarray):
    """Device-fused sampled GET in the warm-restart serving state."""
    return _get_core_dispatch(state, config, keys, lean=True,
                              recovering=True, fused=True)


@partial(jax.jit, static_argnames=("config",))
def get_fused_compact(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Device-fused hit-compacted GET (see `get_compact`)."""
    return _get_compact_core(state, config, keys, fused=True)


@partial(jax.jit, static_argnames=("config",))
def get_fused_compact_lean(state: KVState, config: KVConfig,
                           keys: jnp.ndarray):
    """Device-fused sampled hit-compacted GET."""
    return _get_compact_core(state, config, keys, lean=True, fused=True)


@partial(jax.jit, static_argnames=("config",))
def get_fused_compact_recovering(state: KVState, config: KVConfig,
                                 keys: jnp.ndarray):
    """Device-fused hit-compacted GET, warm-restart serving state."""
    return _get_compact_core(state, config, keys, recovering=True,
                             fused=True)


@partial(jax.jit, static_argnames=("config",))
def get_fused_compact_lean_recovering(state: KVState, config: KVConfig,
                                      keys: jnp.ndarray):
    """Device-fused sampled hit-compacted GET, warm-restart state."""
    return _get_compact_core(state, config, keys, lean=True,
                             recovering=True, fused=True)


@partial(jax.jit, static_argnames=("config",))
def delete(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Batched Delete; removes from index and BF, frees the pool row
    (ref `KV::Delete`)."""
    ops = get_index_ops(config.index.kind)
    new_index, hit, old_vals = ops.delete_batch(state.index, keys)
    state = dataclasses.replace(state, index=new_index)
    state = _bf_delete(state, config, keys, hit)
    if state.pool is not None:
        # Dedupe: the same key twice in one batch reports hit twice but must
        # free its row once.
        freed = hit & ~_is_special(old_vals) & dedupe_last_wins(keys, hit)
        rows = jnp.where(freed, old_vals[:, 1].astype(jnp.int32), -1)
        if isinstance(state.pool, tier_mod.TierState):
            # a stale entry's delete removes the entry but must not free
            # the (recirculated) row under its new owner
            freed = freed & tier_mod.entry_current(state.pool, old_vals)
            rows = jnp.where(freed, rows, -1)
            pool, _ = tier_mod.recycle_and_alloc(
                state.pool, _tcfg(config), freed, rows,
                jnp.zeros_like(freed), balloon=False,
            )
        else:
            pool, _ = pagepool.recycle_and_alloc(
                state.pool, freed, rows, jnp.zeros_like(freed)
            )
        state = dataclasses.replace(state, pool=pool)
    bumps = jnp.zeros((NSTATS,), jnp.int32).at[DELETES].add(
        hit.sum(dtype=jnp.int32))
    return dataclasses.replace(state, stats=state.stats + bumps), hit


# --- extents ---------------------------------------------------------------

def _covers(lo: jnp.ndarray, length: jnp.ndarray, max_covers: int,
            max_height: int):
    """Aligned power-of-two cover decomposition of [lo, lo+length).

    Mirrors the recursion of `CCEH::Insert_extent` (`CCEH_hybrid.cpp:90-105`):
    each cover starts at the current head with size = largest power of two
    that divides the head (or fits the remainder), as a fixed-length
    `lax.scan` producing up to `max_covers` (INVALID-padded) cover bases.

    Cover size is capped at `2**(max_height-1)` so every emitted cover is
    reachable by `get_extent`'s height probes. Returns (bases, remaining):
    `remaining > 0` means the run needed more than `max_covers` covers and
    the tail was NOT indexed — callers must surface that (clean-cache makes
    partial coverage legal, silent loss is not).
    """
    cap = jnp.uint32(1) << (max_height - 1)

    def step(carry, _):
        head, remaining = carry
        low_bit = head & (~head + jnp.uint32(1))  # 2**ffs; 0 -> cap
        size = jnp.minimum(jnp.where(head == 0, cap, low_bit), cap)
        # shrink to fit remainder: size = 2**floor(log2(remaining)) cap
        def shrink(s):
            for _i in range(32):
                s = jnp.where(s > remaining, s >> 1, s)
            return s
        size = jnp.where(remaining > 0, shrink(size), jnp.uint32(0))
        emit = remaining > 0
        out = (jnp.where(emit, head, jnp.uint32(INVALID_WORD)))
        head2 = head + size
        remaining2 = remaining - jnp.minimum(size, remaining)
        return (head2, remaining2), out

    (_, remaining), bases = jax.lax.scan(
        step, (lo, length), None, length=max_covers
    )
    return bases, remaining  # uint32[max_covers], uint32[]


def _insert_extent_impl(state: KVState, config: KVConfig, key: jnp.ndarray,
                        value: jnp.ndarray, length: jnp.ndarray,
                        shard: tuple | None = None):
    """Shared body of InsertExtent; `shard=(n_shards, me)` for SPMD mode.

    Sharded semantics (ref NUMA analog, `server/NuMA_KV.cpp:136-151`): every
    shard appends the IDENTICAL record at the identical ring cursor (the ring
    is deterministically replicated), but inserts only the covers whose cover
    key routes to it — a cover's owner differs from the base key's owner, so
    records must be resolvable from any shard.
    """
    ext = state.extents
    n = ext.recs.shape[0]
    rid = ext.cursor % jnp.uint32(n)
    rec = jnp.stack([
        key[0], key[1], value[0], value[1],
        length.astype(jnp.uint32), jnp.uint32(1),
    ])
    ext = ExtentState(recs=ext.recs.at[rid].set(rec), cursor=ext.cursor + 1)
    state = dataclasses.replace(state, extents=ext)

    max_covers = config.extent_max_covers
    bases, uncovered = _covers(
        key[1], length.astype(jnp.uint32), max_covers,
        config.extent_max_height,
    )
    cover_keys = jnp.stack(
        [jnp.broadcast_to(key[0], bases.shape), bases], axis=-1
    )
    cover_keys = jnp.where(
        (bases == jnp.uint32(INVALID_WORD))[:, None],
        jnp.uint32(INVALID_WORD), cover_keys,
    )
    bump = jnp.int32(1)
    if shard is not None:
        n_shards, me = shard
        mine = shard_of(cover_keys, n_shards) == me.astype(jnp.uint32)
        cover_keys = jnp.where(
            mine[:, None], cover_keys, jnp.uint32(INVALID_WORD)
        )
        bump = jnp.where(me == 0, 1, 0).astype(jnp.int32)
    tagged = jnp.broadcast_to(
        jnp.stack([jnp.uint32(EXTENT_TAG), rid]), (max_covers, 2)
    )
    ops = get_index_ops(config.index.kind)
    if state.pool is not None:
        # A cover overwriting an existing page entry releases its pool row.
        pre = ops.get_batch(state.index, cover_keys)
        conv = pre.found & ~_is_special(pre.values)
        if isinstance(state.pool, tier_mod.TierState):
            conv = conv & tier_mod.entry_current(state.pool, pre.values)
    new_index, res = ops.insert_batch(state.index, cover_keys, tagged)
    state = dataclasses.replace(state, index=new_index)
    live = ~is_invalid(cover_keys)
    state = _bf_insert(state, config, cover_keys, live & ~res.dropped)
    state = _bf_delete(state, config, res.evicted, ~is_invalid(res.evicted))
    state = _sketch_mark(state, config, res.evicted,
                         ~is_invalid(res.evicted))
    if state.pool is not None:
        freed_e, rows_e = _reclaim_evicted(res)
        freed_c = conv & (res.slots >= 0) & ~res.fresh
        rows_c = jnp.where(freed_c, pre.values[:, 1].astype(jnp.int32), -1)
        # A conv'd cover entry can ALSO be reported evicted (its slot taken
        # by another cover's fresh insert in this batch, whose evicted_vals
        # were gathered pre-batch and so still show the page row). Keep only
        # the conv-side free. max_covers is small, so pairwise compare is ok.
        dup = (
            (res.evicted[:, None, 0] == cover_keys[None, :, 0])
            & (res.evicted[:, None, 1] == cover_keys[None, :, 1])
            & freed_e[:, None]
            & freed_c[None, :]
        )
        freed_e = freed_e & ~dup.any(axis=1)
        nothing = jnp.zeros_like(freed_e)
        if isinstance(state.pool, tier_mod.TierState):
            freed_e = freed_e & tier_mod.entry_current(state.pool,
                                                       res.evicted_vals)
            tc = _tcfg(config)
            pool, _ = tier_mod.recycle_and_alloc(
                state.pool, tc, freed_e, rows_e, nothing, balloon=False
            )
            pool, _ = tier_mod.recycle_and_alloc(
                pool, tc, freed_c, rows_c, nothing, balloon=False
            )
        else:
            pool, _ = pagepool.recycle_and_alloc(
                state.pool, freed_e, rows_e, nothing
            )
            pool, _ = pagepool.recycle_and_alloc(
                pool, freed_c, rows_c, nothing)
        state = dataclasses.replace(state, pool=pool)
    bumps = jnp.zeros((NSTATS,), jnp.int32).at[EXTENT_PUTS].add(bump)
    return dataclasses.replace(state, stats=state.stats + bumps), res, uncovered


@partial(jax.jit, static_argnames=("config",))
def insert_extent(state: KVState, config: KVConfig, key: jnp.ndarray,
                  value: jnp.ndarray, length: jnp.ndarray):
    """InsertExtent(key[2], value[2], len) (ref `KV::InsertExtent`).

    Allocates one record in the extent ring; inserts one index entry per
    power-of-two cover whose value is the tagged record id. O(log len)
    entries for a contiguous page run.
    """
    return _insert_extent_impl(state, config, key, value, length)


def insert_extent_sharded(state: KVState, config: KVConfig, key: jnp.ndarray,
                          value: jnp.ndarray, length: jnp.ndarray,
                          n_shards: int, me: jnp.ndarray):
    """SPMD variant (called inside `shard_map`, so not jitted here)."""
    return _insert_extent_impl(
        state, config, key, value, length, shard=(n_shards, me)
    )


def _build_extent_probe(keys: jnp.ndarray, hmax: int) -> jnp.ndarray:
    """[B*H, 2] height-masked cover probe keys (INVALID rows propagate)."""
    b = keys.shape[0]
    hs = jnp.arange(hmax, dtype=jnp.uint32)
    masks = ~((jnp.uint32(1) << hs) - jnp.uint32(1))           # [H]
    lo_t = keys[:, None, 1] & masks[None, :]                   # [B, H]
    hi_t = jnp.broadcast_to(keys[:, None, 0], lo_t.shape)
    probe = jnp.stack([hi_t, lo_t], axis=-1).reshape(b * hmax, 2)
    return jnp.where(
        jnp.broadcast_to(is_invalid(keys)[:, None, None],
                         (b, hmax, 2)).reshape(b * hmax, 2),
        jnp.uint32(INVALID_WORD), probe,
    )


def _resolve_covers(recs: jnp.ndarray, keys: jnp.ndarray, vals: jnp.ndarray,
                    hit: jnp.ndarray, hmax: int):
    """Pick the winning cover per key from [B, H] probe results.

    `recs` is the extent-record ring; `vals`/`hit` are the raw index results
    of `_build_extent_probe`'s keys reshaped to [B, H(, 2)]. Returns
    (out[B, 2], found[B], height[B]) — see `_get_extent_impl`.
    """
    b = keys.shape[0]
    is_ext = hit & (vals[..., 0] == jnp.uint32(EXTENT_TAG))

    rid = jnp.where(is_ext, vals[..., 1], jnp.uint32(0))
    recs_g = recs[rid]                                          # [B, H, 6]
    spans = (
        is_ext
        & (recs_g[..., 5] > 0)
        & (recs_g[..., 0] == keys[:, None, 0])
        & (keys[:, None, 1] >= recs_g[..., 1])
        & (keys[:, None, 1] - recs_g[..., 1] < recs_g[..., 4])
    )
    first = jnp.argmax(spans, axis=1)
    found = spans.any(axis=1)
    rec = recs_g[jnp.arange(b), first]                          # [B, 6]

    # value64 = record.value + key_diff * 4096  (u64 add on u32 lanes)
    diff = (keys[:, 1] - rec[:, 1]) * jnp.uint32(4096)
    lo = rec[:, 3] + diff
    carry = (lo < rec[:, 3]).astype(jnp.uint32)
    hi = rec[:, 2] + carry
    out = jnp.where(found[:, None], jnp.stack([hi, lo], axis=-1),
                    jnp.uint32(0))
    height = jnp.where(found, first.astype(jnp.int32), jnp.int32(hmax))
    return out, found, height


def _get_extent_impl(state: KVState, config: KVConfig, keys: jnp.ndarray,
                     bump_causes: bool = True):
    """Batched GetExtent -> (state, values[B, 2], found[B], height[B],
    evicted_flag[B]).

    All `B × H` height-masked probes run as ONE index get; per key the
    lowest-height hit that (a) carries the extent tag and (b) actually spans
    the key wins, and the returned value is `record.value + 4096 * (key -
    record.base)` — the reference's address arithmetic (`KV.cpp:170-173`)
    on u64 lanes. `height` (the winning probe height, H if miss) is exposed
    for the sharded path: different shards can span the same key via covers
    at different heights, and the cross-shard merge must arbitrate by global
    min height to reproduce this op's argmax (`parallel/shard.py`).
    """
    b = keys.shape[0]
    hmax = config.extent_max_height
    probe = _build_extent_probe(keys, hmax)
    ops = get_index_ops(config.index.kind)
    res = ops.get_batch(state.index, probe)
    out, found, height = _resolve_covers(
        state.extents.recs, keys, res.values.reshape(b, hmax, 2),
        res.found.reshape(b, hmax), hmax,
    )
    bumps = jnp.zeros((NSTATS,), jnp.int32)
    valid = ~is_invalid(keys)
    bumps = bumps.at[GETS].add(valid.sum(dtype=jnp.int32))
    bumps = bumps.at[HITS].add(found.sum(dtype=jnp.int32))
    bumps = bumps.at[MISSES].add((valid & ~found).sum(dtype=jnp.int32))
    # evicted-key sketch flag on the BASE key: a missed extent probe whose
    # key the sketch remembers was capacity-evicted classifies
    # `miss_evicted`, else `miss_cold`. Returned raw so the sharded
    # broadcast body can arbitrate causes globally (`bump_causes=False`
    # there — every shard probes the full batch, and per-shard cause
    # bumps would multiply by n_shards).
    ev = (valid & ~found) & _sketch_query(state, config, keys)
    if bump_causes:
        bumps = bumps.at[MISS_EVICTED].add(ev.sum(dtype=jnp.int32))
        bumps = bumps.at[MISS_COLD].add(
            (valid & ~found & ~ev).sum(dtype=jnp.int32))
    state = dataclasses.replace(state, stats=state.stats + bumps)
    return state, out, found, height, ev


@partial(jax.jit, static_argnames=("config",))
def get_extent(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Batched GetExtent -> (values[B, 2], found[B]) (ref `KV::GetExtent`)."""
    state, out, found, _, _ = _get_extent_impl(state, config, keys)
    return state, out, found


# --- scans -----------------------------------------------------------------

@partial(jax.jit, static_argnames=("config",))
def find_anyway(state: KVState, config: KVConfig, keys: jnp.ndarray):
    """Full-table scan for keys the hashed probe lost (ref `FindAnyway`,
    `server/IKV.h:18`, used by test_KV's lost-key postmortem
    `server/test_KV.cpp:305-327`)."""
    ops = get_index_ops(config.index.kind)
    flat_keys, flat_vals = ops.scan(state.index)
    eq = (flat_keys[None, :, 0] == keys[:, None, 0]) & (
        flat_keys[None, :, 1] == keys[:, None, 1]
    )
    eq &= ~is_invalid(keys)[:, None]
    found = eq.any(axis=1)
    slot = jnp.argmax(eq, axis=1)
    return flat_vals[slot], found, jnp.where(found, slot, -1)


@partial(jax.jit, static_argnames=("config",))
def utilization(state: KVState, config: KVConfig) -> jnp.ndarray:
    """Fraction of occupied slots (ref `Utilization`, `server/IKV.h:19`)."""
    ops = get_index_ops(config.index.kind)
    flat_keys, _ = ops.scan(state.index)
    occ = (~is_invalid(flat_keys)).sum(dtype=jnp.float32)
    return occ / jnp.float32(flat_keys.shape[0])


def live_entries(state: KVState, config: KVConfig):
    """Host-side scan of one (single-shard) state: the live
    (key, payload) set a reshard/migration replay must re-insert.

    Returns `(keys[L, 2], payload)` where payload is the page rows
    `[L, page_words]` in paged mode, else the stored u64 value words
    `[L, 2]`. The classes a replay must NOT carry ride out implicitly:
    extent-cover refs (tagged values) re-register from the extent ring,
    NOPAGE placements and stale-generation tiered entries are legal
    misses, and pages whose bytes fail their at-rest digest are dropped
    here (re-inserting them would re-checksum corrupt bytes as good —
    the one move the degradation ladder must never make).
    """
    ops = get_index_ops(config.index.kind)
    if ops.scan is None:
        raise ValueError(
            f"index kind {config.index.kind} has no scan op; "
            "reshard replay needs one")
    flat_keys, flat_vals = ops.scan(state.index)
    keys = np.asarray(flat_keys, np.uint32).reshape(-1, 2)
    vals = np.asarray(flat_vals, np.uint32).reshape(-1, 2)
    live = ~np.all(keys == np.uint32(INVALID_WORD), axis=-1)
    if not config.paged:
        # extent-cover refs are tagged by the EXACT hi-word sentinel in
        # unpaged mode (arbitrary user hi-words are legal, so no >>30
        # class test here); replaying one as a plain value would
        # resurrect a stale ref pointing into the REBUILT ring
        live &= vals[:, 0] != np.uint32(EXTENT_TAG)
        return keys[live], vals[live]
    keys, rows, pages, _ = _live_paged(state, config, keys, vals, live)
    return keys, pages


def _live_paged(state: KVState, config: KVConfig, keys: np.ndarray,
                vals: np.ndarray, live: np.ndarray):
    """Shared paged-mode live filter: (keys[L,2], rows[L], pages[L,W],
    sums[L]) for entries whose bytes currently verify — the common tail
    of `live_entries` (reshard replay) and `directory_entries` (the
    one-sided fast-path directory)."""
    live = live & ((vals[:, 0] >> 30) == 0)  # drop EXTENT_TAG / NOPAGE
    if isinstance(state.pool, tier_mod.TierState):
        live &= np.asarray(
            tier_mod.entry_current(state.pool, jnp.asarray(vals)))
    keys, vals = keys[live], vals[live]
    rows = vals[:, 1].astype(np.int64)
    if isinstance(state.pool, tier_mod.TierState):
        # ballooned-out (parked) rows are legal misses, not servable rows
        held = np.asarray(
            tier_mod.row_live(state.pool, jnp.asarray(rows, jnp.int32)))
        keys, rows = keys[held], rows[held]
    pages = np.asarray(state.pool.pages)[rows]
    sums = np.asarray(state.pool.sums)[rows]
    ok = np.asarray(pagepool.page_digest_np(pages)) == sums
    return keys[ok], rows[ok], pages[ok], sums[ok]


def directory_entries(state: KVState, config: KVConfig):
    """Host-side scan for the fast-path directory: the live, currently
    verifying (key → row) set with each row's at-rest digest —
    `(keys[L, 2], rows[L], digs[L])`. The digest is the VALIDATION TOKEN
    of the one-sided read: a client presents `(row, dig)` and the server
    serves the row only while its current `sums[row]` still equals
    `dig`, so a recycled or re-written row can never serve bytes for the
    wrong key (same 2^-32 collision class as the integrity layer).
    Paged configs only (unpaged values have no row to read)."""
    if not config.paged:
        return None
    ops = get_index_ops(config.index.kind)
    if ops.scan is None:
        return None
    flat_keys, flat_vals = ops.scan(state.index)
    keys = np.asarray(flat_keys, np.uint32).reshape(-1, 2)
    vals = np.asarray(flat_vals, np.uint32).reshape(-1, 2)
    live = ~np.all(keys == np.uint32(INVALID_WORD), axis=-1)
    keys, rows, _, sums = _live_paged(state, config, keys, vals, live)
    return keys, rows.astype(np.uint32), sums.astype(np.uint32)


class FastView:
    """Immutable host mirror of one pool's (pages, sums, row liveness)
    at a single mutation sequence point — the server half of the
    one-sided fast path. `pages` is `[R, W]` (one shard) or `[S, R, W]`
    (stacked sharded state); `sums`/`live` match with the page axis
    dropped. `live` is None for flat pools (every row's bytes change
    when it is recycled, so the digest alone suffices); tiered pools
    need it because a free-row PROMOTION vacates the cold row WITHOUT
    scrubbing its pages/sums — the vacated row still carries the old
    digest while the key's current value lives (and mutates) in the hot
    tier, and only the liveness bit distinguishes the two.

    On the CPU backend (donation off) the arrays are zero-copy views of
    the live functional state — a mutating dispatch builds NEW buffers,
    so a view taken before it keeps serving the old consistent bytes
    and the next `fast_view()` call (seq changed) re-mirrors. Where
    donation is on the buffers are owned copies (a donated program
    scribbles on its inputs)."""

    __slots__ = ("epoch", "seq", "pages", "sums", "live")

    def __init__(self, epoch: int, seq: int, pages: np.ndarray,
                 sums: np.ndarray, live: np.ndarray | None = None):
        self.epoch = epoch
        self.seq = seq
        self.pages = pages
        self.sums = sums
        self.live = live

    def validate(self, epoch: int, shards: np.ndarray, rows: np.ndarray,
                 digs: np.ndarray) -> np.ndarray:
        """ok[N]: the (shard, row) is in range, LIVE (tiered: not
        vacated/parked), AND the row's current at-rest digest still
        equals the client's directory digest. A stale epoch fails every
        lane (structural change: reshard, balloon, restore)."""
        n = len(rows)
        if epoch != self.epoch:
            return np.zeros(n, bool)
        if self.pages.ndim == 3:
            ns, nr = self.pages.shape[:2]
            ok = (shards < ns) & (rows < nr)
            s = np.where(ok, shards, 0).astype(np.int64)
            r = np.where(ok, rows, 0).astype(np.int64)
            ok &= self.sums[s, r] == digs
            if self.live is not None:
                ok &= self.live[s, r]
            return ok
        nr = self.pages.shape[0]
        ok = (shards == 0) & (rows < nr)
        r = np.where(ok, rows, 0).astype(np.int64)
        ok &= self.sums[r] == digs
        if self.live is not None:
            ok &= self.live[r]
        return ok

    def gather(self, shards: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Validated-lane page gather (pure numpy, zero device work)."""
        if self.pages.ndim == 3:
            return self.pages[shards.astype(np.int64),
                              rows.astype(np.int64)]
        return self.pages[rows.astype(np.int64)]


# ---------------------------------------------------------------------------
# host-facing class (the `IKV` surface, `server/IKV.h:10-23`)
# ---------------------------------------------------------------------------

# Donated variants — the KV wrapper's dispatch path. The wrapper always
# replaces `self.state` with the returned state, so the input buffers can
# be donated; WITHOUT donation XLA materializes a fresh copy of every
# pass-through table buffer on each call (measured ~160 ms per 256 MB of
# table on this host — at serving flush rates that, not the probe gather,
# was the entire cost of the engine path). Module-level `insert`/`get`/...
# stay un-donated for callers that keep their input state alive.
#
# CPU exception (same defect family as `parallel/shard._wrap`): on the
# jaxlib 0.4.x CPU backend, donated programs can SCRIBBLE on pass-through
# buffers — observed deterministically as the donated hit-compacted GET
# corrupting the pool's digest sidecar (every data row failing its
# checksum after one call), and as wandering full-suite segfaults. Real
# serving runs on TPU where donation is sound, so donation keys off the
# platform; PMDFC_KV_DONATE=1/0 forces it either way.
_jit_don = partial(jax.jit, static_argnames=("config",), donate_argnums=(0,))
_insert_don = _jit_don(insert.__wrapped__)
_get_don = _jit_don(get.__wrapped__)
_get_lean_don = _jit_don(get_lean.__wrapped__)
_get_compact_don = _jit_don(get_compact.__wrapped__)
_get_compact_lean_don = _jit_don(get_compact_lean.__wrapped__)
_delete_don = _jit_don(delete.__wrapped__)
_insert_extent_don = _jit_don(insert_extent.__wrapped__)
_get_extent_don = _jit_don(get_extent.__wrapped__)
_get_rec_don = _jit_don(get_recovering.__wrapped__)
_get_lean_rec_don = _jit_don(get_lean_recovering.__wrapped__)
_get_compact_rec_don = _jit_don(get_compact_recovering.__wrapped__)
_get_compact_lean_rec_don = _jit_don(get_compact_lean_recovering.__wrapped__)
_get_fused_don = _jit_don(get_fused.__wrapped__)
_get_fused_lean_don = _jit_don(get_fused_lean.__wrapped__)
_get_fused_rec_don = _jit_don(get_fused_recovering.__wrapped__)
_get_fused_lean_rec_don = _jit_don(get_fused_lean_recovering.__wrapped__)
_get_fused_compact_don = _jit_don(get_fused_compact.__wrapped__)
_get_fused_compact_lean_don = _jit_don(get_fused_compact_lean.__wrapped__)
_get_fused_compact_rec_don = _jit_don(get_fused_compact_recovering.__wrapped__)
_get_fused_compact_lean_rec_don = _jit_don(
    get_fused_compact_lean_recovering.__wrapped__)

_DONATE: bool | None = None


def _donate() -> bool:
    """Lazy platform check (lazy so importing kv never forces backend
    init: importing the package must never touch a device)."""
    global _DONATE
    if _DONATE is None:
        import os

        env = os.environ.get("PMDFC_KV_DONATE")
        if env in ("0", "1"):
            _DONATE = env == "1"
        else:
            _DONATE = jax.default_backend() != "cpu"
    return _DONATE


_DON_FNS = {
    "insert": _insert_don, "get": _get_don, "get_lean": _get_lean_don,
    "get_compact": _get_compact_don,
    "get_compact_lean": _get_compact_lean_don, "delete": _delete_don,
    "insert_extent": _insert_extent_don, "get_extent": _get_extent_don,
    "get_recovering": _get_rec_don,
    "get_lean_recovering": _get_lean_rec_don,
    "get_compact_recovering": _get_compact_rec_don,
    "get_compact_lean_recovering": _get_compact_lean_rec_don,
    "get_fused": _get_fused_don, "get_fused_lean": _get_fused_lean_don,
    "get_fused_recovering": _get_fused_rec_don,
    "get_fused_lean_recovering": _get_fused_lean_rec_don,
    "get_fused_compact": _get_fused_compact_don,
    "get_fused_compact_lean": _get_fused_compact_lean_don,
    "get_fused_compact_recovering": _get_fused_compact_rec_don,
    "get_fused_compact_lean_recovering": _get_fused_compact_lean_rec_don,
}
_PLAIN_FNS = {
    "insert": insert, "get": get, "get_lean": get_lean,
    "get_compact": get_compact, "get_compact_lean": get_compact_lean,
    "delete": delete, "insert_extent": insert_extent,
    "get_extent": get_extent,
    "get_recovering": get_recovering,
    "get_lean_recovering": get_lean_recovering,
    "get_compact_recovering": get_compact_recovering,
    "get_compact_lean_recovering": get_compact_lean_recovering,
    "get_fused": get_fused, "get_fused_lean": get_fused_lean,
    "get_fused_recovering": get_fused_recovering,
    "get_fused_lean_recovering": get_fused_lean_recovering,
    "get_fused_compact": get_fused_compact,
    "get_fused_compact_lean": get_fused_compact_lean,
    "get_fused_compact_recovering": get_fused_compact_recovering,
    "get_fused_compact_lean_recovering": get_fused_compact_lean_recovering,
}


def _fn(name: str):
    """Dispatch-path op: donated where donation is sound, plain jit where
    it is not (see the CPU exception above)."""
    return (_DON_FNS if _donate() else _PLAIN_FNS)[name]


def _pad_pow2(n: int, lo: int = 16) -> int:
    p = lo
    while p < n:
        p <<= 1
    return p


def _locked(fn):
    """Serialize a method on the instance `_lock` (used by KV and
    ShardedKV: donating dispatches must not interleave with state
    readers; see the KV class docstring)."""
    import functools

    @functools.wraps(fn)
    def wrapper(self, *a, **k):
        with self._lock:
            return fn(self, *a, **k)
    return wrapper


class KV:
    """Host wrapper: numpy in/out, fixed-shape padded device batches.

    Takes OWNERSHIP of `state`: mutating ops donate the current state's
    buffers to the device program, so a caller-held reference to a state
    passed in here (or read off `.state`) is invalidated by the next op.
    Pass `jax.tree.map(jnp.copy, state)` to keep an outside copy live.
    (On the CPU backend donation is disabled — see `_donate()` — but the
    ownership contract is the same everywhere: never rely on a state
    reference surviving the next op.)

    Thread safety: every public method serializes on an internal lock —
    donation means a reader (bloom push, stats reporter, checkpoint) that
    raced a mutating op would touch a deleted buffer, so reads of
    `self.state` and donated dispatches must not interleave. Outputs of a
    dispatch are fresh buffers and are safely fetched outside the lock.
    """

    def __init__(self, config: KVConfig | None = None, state: KVState | None = None,
                 journal=None):
        self.config = config or KVConfig()
        self.state = state if state is not None else init(self.config)
        self._ops = get_index_ops(self.config.index.kind)
        self._t0 = time.monotonic()
        self._gets_since_decay = 0
        self._batches_since_touch = 0
        # Bounded-RPO durability (runtime/journal.py, duck-typed so kv
        # never imports the runtime package at module level): when
        # attached, every mutation appends its CRC-framed record BEFORE
        # the device dispatch — the WAL covers everything the device
        # acknowledges. `_chain` is the incremental-snapshot cursor
        # (chain id/seq/prev_crc + the base digest sidecar the next
        # delta diffs against); `_recovering` is the warm-restart
        # serving state (GET misses land in `miss_recovering`).
        self._journal = journal
        self._chain: dict | None = None
        self._recovering = False
        self._recover_t0 = 0.0
        # fused-GET selection (ops/fused.py), resolved lazily so KV
        # construction never forces backend init (resolve() consults
        # jax.default_backend() in 'auto' mode — see _donate())
        self._fused: bool | None = None
        # function-local import: runtime/__init__ imports server -> kv,
        # so a module-level sanitizer import would be circular (same
        # reason stats() imports telemetry locally)
        from pmdfc_tpu.runtime import sanitizer as san

        # serializes state swaps (donating dispatch) against state readers
        # guarded-by: state, _gets_since_decay, _batches_since_touch,
        # guarded-by: dir_epoch, _mut_seq, _fastview, _host_stats
        self._lock = san.rlock("KV._lock")
        # host-side stats overlay: lanes the DEVICE never bumps (today
        # only the QoS shed accounting, `account_shed`) accumulate here
        # and fold into every stats() snapshot, so `misses == Σ causes`
        # stays bit-exact without a device round-trip per shed op
        self._host_stats = np.zeros(NSTATS, np.int64)
        # One-sided fast-path surface. `dir_epoch` names a STRUCTURAL
        # generation of the key→row mapping: it bumps on changes that
        # invalidate every outstanding directory entry at once (delete,
        # balloon shrink/grow, recovery/restore) and clients fall back
        # to the verb path on mismatch. Randomized start so a restored
        # or swapped instance can never collide with a client's cached
        # epoch (digest validation is the byte-level backstop either
        # way). `_mut_seq` counts EVERY mutating dispatch and keys the
        # cached host mirror (`fast_view`) — per-put row recycling is
        # caught by the per-row digest, not by the epoch.
        import os as _os

        self.dir_epoch = int.from_bytes(_os.urandom(4), "little") | 1
        self._mut_seq = 0
        self._fastview: FastView | None = None
        # telemetry mirror (runtime/telemetry.py): the device stats
        # vector stays the source of truth; stats() publishes each
        # snapshot into a per-instance registry scope so the exporter /
        # teledump see the KV counters alongside everything else.
        # Lazy: a KV that is never snapshotted registers nothing.
        self._tele_scope = None

    # -- helpers --
    def _pad_keys(self, keys: np.ndarray, width: int) -> np.ndarray:
        out = np.full((width, 2), INVALID_WORD, np.uint32)
        out[: len(keys)] = keys
        return out

    def _fn_t(self, name: str, w: int, vw: int = 0, extra: tuple = ()):
        """`_fn` + recompile tracking: a (program, padded width, value
        width, config) signature the telemetry registry hasn't seen yet
        is a jit compile this process is about to pay — report it so a
        cold pad-ladder rung or a drifting batch shape shows up as a
        named `recompile.kv.*` counter, not a mystery latency spike.
        `vw` is the value-row width for programs that trace a values
        operand (insert: pages vs u64 values at the same padded w are
        two distinct compiles). One flag test when the tracing tier is
        off (function-local import for the same circularity reason as
        stats()). `extra` appends signature parts beyond (w, vw, config)
        — the fused GET programs key on (family, tile) too, since a new
        tile rung is a new Pallas kernel compile."""
        from pmdfc_tpu.runtime import telemetry as tele

        first = tele.track_program(f"kv.{name}", (w, vw, *extra, self.config),
                                   detail=f"w={w}" + (f",vw={vw}" if vw else "")
                                   + "".join(f",{k}={v}" for k, v in extra))
        fn = _fn(name)
        if first:
            # static cost capture rides the recompile-tracker seam: the
            # first dispatch of a fresh signature lowers once for the
            # `cost.*` FLOPs/bytes gauges (runtime/profiler.py; no-op
            # unless a profiler is attached)
            from pmdfc_tpu.runtime import profiler

            fn = profiler.cost_probe(f"kv.{name}", fn)
        return fn

    @_locked
    def insert(self, keys: np.ndarray, values: np.ndarray):
        """keys[B, 2] uint32; values = pages[B, page_words] or u64 vals[B, 2]."""
        keys = np.asarray(keys, np.uint32)
        if self._journal is not None:
            # WAL before dispatch: the record must be durable-bound
            # before the device flush can acknowledge these pages
            self._journal.append_put(keys, np.asarray(values, np.uint32))
        b = len(keys)
        w = _pad_pow2(b)
        vwidth = values.shape[-1]
        vpad = np.zeros((w, vwidth), np.uint32)
        vpad[:b] = values
        self.state, res = self._fn_t("insert", w, vwidth)(
            self.state, self.config, self._pad_keys(keys, w), jnp.asarray(vpad)
        )
        self._mut_seq += 1
        from pmdfc_tpu.runtime import profiler

        # the host transfer is where device compute is actually paid
        # (async dispatch): the profiler's sanctioned timed-fetch seam
        return profiler.fetch(
            "kv.insert", "put",
            lambda: jax.tree.map(lambda x: np.asarray(x)[:b], res),
            n_ops=b, ring=True)

    # caller-holds: _lock
    def _touch_due(self) -> bool:
        """Sampled hotness accounting: one batch in `touch_sample_every`
        pays the counting path; the rest take the lean probe. A tiered
        pool counts as touch-tracking (its migration program rides the
        counting path), so the sampling knob governs tier placement the
        same way it governs hotring counters. Callers hold the instance
        lock."""
        every = self.config.index.touch_sample_every
        if self._ops.touch is None and not isinstance(
                self.state.pool, tier_mod.TierState):
            return False  # lean selection is automatic inside _get_core
        if every <= 1:
            return True
        self._batches_since_touch += 1
        if self._batches_since_touch >= every:
            self._batches_since_touch = 0
            return True
        return False

    # caller-holds: _lock
    def _fused_on(self) -> bool:
        """Lazy fused/composed decision for this instance's GET programs
        (`ops/fused.py`): PMDFC_FUSED over `KVConfig.fused_get`, 'auto'
        = TPU only, and never fused for configs the kernel does not
        support. Resolved once — flipping the env mid-process needs a
        fresh KV, same contract as `_donate()`."""
        if self._fused is None:
            from pmdfc_tpu.ops import fused as fused_ops

            self._fused = fused_ops.resolve(self.config)
        return self._fused

    # caller-holds: _lock
    def _get_fn(self, base: str, w: int):
        """Serving-path GET program selection: sampled (lean) vs
        counting, crossed with the warm-restart `recovering` state (a
        distinct jitted program — the reattribution is a static branch,
        so steady-state serving never pays for it), crossed with the
        device-fused kernel when `_fused_on()` (fused names carry the
        (family, tile, value width) signature so a cold tile rung shows
        up as exactly one `recompile.kv.get_fused*` counter)."""
        name = base if self._touch_due() else base + "_lean"
        if self._recovering:
            name += "_recovering"
        if self._fused_on():
            from pmdfc_tpu.ops import fused as fused_ops

            return self._fn_t(
                name.replace("get", "get_fused", 1), w,
                vw=self.config.page_words,
                extra=(("family", self.config.index.kind.value),
                       ("tile", fused_ops.tile_for(w))),
            )
        return self._fn_t(name, w)

    @_locked
    def get(self, keys: np.ndarray):
        keys = np.asarray(keys, np.uint32)
        b = len(keys)
        w = _pad_pow2(b)
        fn = self._get_fn("get", w)
        self.state, out, found = fn(
            self.state, self.config, self._pad_keys(keys, w)
        )
        self._maybe_decay(b)
        from pmdfc_tpu.runtime import profiler

        return profiler.fetch(
            "kv.get", "get",
            lambda: (np.asarray(out)[:b], np.asarray(found)[:b]),
            n_ops=b, ring=True)

    @_locked
    def _maybe_decay(self, gets: int) -> None:
        # periodic heat drain for hotness-aware indexes (hotring)
        every = self.config.index.decay_every_gets
        if self._ops.decay is not None and every:
            self._gets_since_decay += gets
            if self._gets_since_decay >= every:
                self._gets_since_decay = 0
                self.state = dataclasses.replace(
                    self.state, index=self._ops.decay(self.state.index)
                )

    # -- async variants (serving path) --
    # These return DEVICE arrays without forcing a host transfer, so a
    # driver can launch batch N+1 while batch N's results are still in
    # flight (JAX async dispatch = the double-buffered flush the reference
    # gets from overlapping verbs with poller threads). `self.state` is
    # updated immediately — functional chaining keeps ordering correct.

    @_locked
    def insert_async(self, keys: np.ndarray, values: np.ndarray,
                     pad_floor: int = 16):
        """Like insert() but returns (device InsertResult, b)."""
        keys = np.asarray(keys, np.uint32)
        if self._journal is not None:
            self._journal.append_put(keys, np.asarray(values, np.uint32))
        b = len(keys)
        w = _pad_pow2(b, lo=pad_floor)
        vpad = np.zeros((w, values.shape[-1]), np.uint32)
        vpad[:b] = values
        self.state, res = self._fn_t("insert", w, vpad.shape[-1])(
            self.state, self.config, self._pad_keys(keys, w),
            jnp.asarray(vpad)
        )
        self._mut_seq += 1
        return res, b

    @_locked
    def get_async(self, keys: np.ndarray, pad_floor: int = 16):
        """Like get() but returns (device out, device found, b)."""
        keys = np.asarray(keys, np.uint32)
        b = len(keys)
        w = _pad_pow2(b, lo=pad_floor)
        fn = self._get_fn("get", w)
        self.state, out, found = fn(
            self.state, self.config, self._pad_keys(keys, w)
        )
        self._maybe_decay(b)
        return out, found, b

    @_locked
    def get_extent_async(self, keys: np.ndarray, pad_floor: int = 16):
        """Like get_extent() but returns (device vals, device found, b) —
        the driver's launch/finalize split must not block on the device
        inside launch (see KVServer._launch's contract)."""
        keys = np.asarray(keys, np.uint32)
        b = len(keys)
        w = _pad_pow2(b, lo=pad_floor)
        self.state, out, found = self._fn_t("get_extent", w)(
            self.state, self.config, self._pad_keys(keys, w)
        )
        return out, found, b

    @_locked
    def get_compact_async(self, keys: np.ndarray, pad_floor: int = 16):
        """Hit-compacted get: (device out_sorted, order, found, nfound, b).

        `out_sorted[:nfound]` are the hit rows in request order;
        `order[:nfound]` are their original request indices. The caller
        fetches only a power-of-two prefix of the hits — the
        found-compressed page return (`server/rdma_svr.cpp:706-719`).
        """
        keys = np.asarray(keys, np.uint32)
        b = len(keys)
        w = _pad_pow2(b, lo=pad_floor)
        fn = self._get_fn("get_compact", w)
        self.state, out, order, found, nfound = fn(
            self.state, self.config, self._pad_keys(keys, w)
        )
        self._maybe_decay(b)
        return out, order, found, nfound, b

    @_locked
    def delete_async(self, keys: np.ndarray, pad_floor: int = 16):
        """Like delete() but returns (device hit mask, b)."""
        keys = np.asarray(keys, np.uint32)
        if self._journal is not None:
            self._journal.append_delete(keys)
        b = len(keys)
        w = _pad_pow2(b, lo=pad_floor)
        self.state, hit = self._fn_t("delete", w)(
            self.state, self.config, self._pad_keys(keys, w)
        )
        self._mut_seq += 1
        self.dir_epoch += 1
        return hit, b

    @_locked
    def delete(self, keys: np.ndarray):
        keys = np.asarray(keys, np.uint32)
        if self._journal is not None:
            self._journal.append_delete(keys)
        b = len(keys)
        w = _pad_pow2(b)
        self.state, hit = self._fn_t("delete", w)(
            self.state, self.config, self._pad_keys(keys, w)
        )
        self._mut_seq += 1
        self.dir_epoch += 1
        from pmdfc_tpu.runtime import profiler

        return profiler.fetch("kv.delete", "del",
                              lambda: np.asarray(hit)[:b],
                              n_ops=b, ring=True)

    @_locked
    def insert_extent(self, key, value, length: int):
        """Returns (index InsertResult over the covers, uncovered tail pages).

        `uncovered > 0` means the run needed more than
        `config.extent_max_covers` covers and the tail pages were not
        indexed (legal under clean-cache, surfaced so callers can re-insert
        the tail as a new extent).
        """
        if self._journal is not None:
            self._journal.append_extent(key, value, length)
        self.state, res, uncovered = self._fn_t("insert_extent", 1)(
            self.state, self.config,
            jnp.asarray(np.asarray(key, np.uint32)),
            jnp.asarray(np.asarray(value, np.uint32)),
            jnp.uint32(length),
        )
        self._mut_seq += 1
        return res, int(uncovered)

    @_locked
    def get_extent(self, keys: np.ndarray):
        keys = np.asarray(keys, np.uint32)
        b = len(keys)
        w = _pad_pow2(b)
        self.state, out, found = self._fn_t("get_extent", w)(
            self.state, self.config, self._pad_keys(keys, w)
        )
        from pmdfc_tpu.runtime import profiler

        return profiler.fetch(
            "kv.get_extent", "get_ext",
            lambda: (np.asarray(out)[:b], np.asarray(found)[:b]),
            n_ops=b, ring=True)

    @_locked
    def find_anyway(self, keys: np.ndarray):
        keys = np.asarray(keys, np.uint32)
        b = len(keys)
        w = _pad_pow2(b)
        vals, found, slot = find_anyway(
            self.state, self.config, self._pad_keys(keys, w)
        )
        return np.asarray(vals)[:b], np.asarray(found)[:b], np.asarray(slot)[:b]

    def capacity(self) -> int:
        return self._ops.num_slots(self.config.index)

    @_locked
    def utilization(self) -> float:
        return float(utilization(self.state, self.config))

    @_locked
    def recovery(self) -> bool:
        """Post-restart repair hook (ref `KV::Recovery`)."""
        if self._ops.recovery is None:
            return True
        self.state = dataclasses.replace(
            self.state, index=self._ops.recovery(self.state.index)
        )
        self._mut_seq += 1
        self.dir_epoch += 1
        return True

    @_locked
    def snapshot(self, path: str, delta: bool = False) -> dict:
        """Crash-safe checkpoint of the live state (temp + fsync + atomic
        rename + integrity digest, see `checkpoint.save`).

        `delta=True` writes an INCREMENTAL chain member: only the pool
        rows whose digest sidecar (or tier liveness) changed since the
        previous member of this instance's chain, under the same
        CRC-manifest discipline (`checkpoint.save_delta`) — restore goes
        through `checkpoint.load_chain`. Falls back to a FULL (which
        starts a new chain) when there is no chain yet, the config is
        unpaged, or the row space drifted; a full always starts a new
        chain. When a journal is attached the save also appends a
        durable MARK record, so `journal.replay(after_mark=True)`
        replays exactly the tail past this snapshot.

        Runs under the instance lock: `self.state` read by an UNLOCKED
        external `checkpoint.save(kv.state, ...)` can race a donating
        dispatch and snapshot freed buffers — servers must checkpoint
        through this method (`KVServer.checkpoint`). Returns a report
        (`kind`, `chain_id`, `seq`, `crc`, `dirty_rows`, ...).
        """
        from pmdfc_tpu import checkpoint as _ckpt  # lazy: ckpt imports kv

        sums, live = self._dirty_basis()
        report, self._chain = _ckpt.chain_step(
            self.state, path, self._chain, sums, live, delta)
        if self._journal is not None:
            self._journal.mark({"chain_id": report["chain_id"],
                                "seq": report["seq"],
                                "crc": report["crc"], "path": path,
                                "kind": report["kind"]})
        return report

    # caller-holds: _lock
    def _dirty_basis(self):
        """Host copies of `(sums, live)` — the delta-dirty basis. The
        digest sidecar is maintained by exactly the mutation paths
        (insert / delete-recycle / balloon rewrite), so a sidecar diff
        IS the dirty-row set; tier liveness rides along to catch rows
        vacated WITHOUT a rewrite (a promotion vacates its cold row and
        only the live bit records it). None for unpaged configs."""
        pool = self.state.pool
        if pool is None:
            return None, None
        sums = np.array(np.asarray(pool.sums)).reshape(-1)
        live = None
        if isinstance(pool, tier_mod.TierState):
            live = tier_mod.live_mask(pool)
        return sums, live

    def attach_journal(self, journal) -> None:
        """Arm the write-ahead journal (runtime/journal.py): from now on
        every mutation appends its record before the device dispatch."""
        with self._lock:
            self._journal = journal

    @_locked
    def resume_chain(self, chain: dict) -> None:
        """Re-arm the snapshot-chain cursor after a restore (`chain` is
        `materialize_chain`'s resume card): the next `snapshot(delta=
        True)` extends the restored chain instead of starting a new one,
        with the dirty basis re-anchored at the restored state."""
        sums, live = self._dirty_basis()
        self._chain = {"id": chain["id"], "seq": int(chain["seq"]),
                       "prev_crc": int(chain["crc"]),
                       "base_sums": sums, "base_live": live}

    @_locked
    def begin_recovering(self) -> None:
        """Enter the warm-restart serving state: GETs answer from
        restored rows immediately; misses that would read `miss_cold`
        attribute to `miss_recovering` until `mark_recovered()` (the
        catch-up — ring migration + anti-entropy — may simply not have
        landed the key yet)."""
        from pmdfc_tpu.runtime import telemetry as tele

        if not self._recovering:
            self._recovering = True
            self._recover_t0 = time.monotonic()
            sc = tele.scope("recovery", {"warm_restarts": 0,
                                         "completed": 0}, unique=False)
            sc.inc("warm_restarts")
            sc.set("recovering", 1)

    @_locked
    def mark_recovered(self) -> bool:
        """Leave the recovering state (idempotent — the replica tier's
        repair drain and an operator can both call it). Returns whether
        the flag was set."""
        from pmdfc_tpu.runtime import telemetry as tele

        was = self._recovering
        self._recovering = False
        if was:
            sc = tele.scope("recovery", unique=False)
            sc.inc("completed")
            sc.set("recovering", 0)
            sc.set("last_recovery_s",
                   round(time.monotonic() - self._recover_t0, 3))
        return was

    @_locked
    def recovery_info(self) -> dict:
        """Warm-restart status for health surfaces and the
        MSG_RECOVERY wire verb."""
        info: dict = {"recovering": self._recovering}
        if self._recovering:
            info["recovering_s"] = round(
                time.monotonic() - self._recover_t0, 3)
        if self._chain is not None:
            info["chain"] = {"id": self._chain["id"],
                             "seq": self._chain["seq"]}
        return info

    @_locked
    def packed_bloom(self) -> np.ndarray | None:
        """Packed bit form for the client mirror (ref `send_bf`,
        `server/rdma_svr.cpp:157-251`)."""
        if self.state.bloom is None:
            return None
        return np.asarray(bloom_ops.to_packed_bits(self.state.bloom))

    # -- one-sided fast-path surface (`runtime/net.py` MSG_DIRPULL /
    # MSG_FASTREAD): a client-cached directory + direct validated row
    # reads that never enter the serving dispatch path --

    @_locked
    def fast_view(self) -> FastView | None:
        """Current host mirror of (pool pages, digest sidecar), cached
        per mutation seq. None for unpaged configs (no rows to read).
        Cheap on CPU (zero-copy views of the functional state); where
        donation is on the mirror owns copies, so the fast path there
        trades put-side copy cost for read-side bypass — exactly the
        knob `PMDFC_FASTPATH` exists to keep honest."""
        if not self.config.paged:
            return None
        fv = self._fastview
        if fv is not None and fv.seq == self._mut_seq \
                and fv.epoch == self.dir_epoch:
            return fv
        pool = self.state.pool
        pages, sums = np.asarray(pool.pages), np.asarray(pool.sums)
        if _donate():
            # donated dispatches scribble on their input buffers — the
            # mirror must own its bytes on donating platforms
            pages, sums = np.array(pages), np.array(sums)
        live = None
        if isinstance(pool, tier_mod.TierState):
            # row liveness (tier.row_live's rule): hot rows always, cold
            # rows only while live — a free-row promotion vacates its
            # cold row without scrubbing pages/sums, and the stale-bytes
            # guard for that row IS this bit (the digest can't see it).
            # The fancy assignment copies, so `live` owns its bytes
            # regardless of donation.
            h = pool.hfree.shape[0]
            live = np.ones(pages.shape[0], bool)
            live[h:] = np.asarray(pool.live)
        fv = FastView(self.dir_epoch, self._mut_seq, pages, sums, live)
        self._fastview = fv
        return fv

    @_locked
    def directory_snapshot(self, max_entries: int = 1 << 20) -> dict | None:
        """Compact key→(shard, row, digest) directory for the client
        mirror: `{"epoch", "keys"[L,2], "shards"[L], "rows"[L],
        "digs"[L]}` (shard column all-zero on a single-device KV).
        Bounded by `max_entries` (oldest-scan-order tail dropped — a
        missing entry only costs the verb path, never correctness).
        None when the config is unpaged or the index kind has no scan."""
        ents = directory_entries(self.state, self.config)
        if ents is None:
            return None
        keys, rows, digs = ents
        if len(keys) > max_entries:
            keys, rows, digs = (keys[:max_entries], rows[:max_entries],
                                digs[:max_entries])
        return {"epoch": self.dir_epoch, "keys": keys,
                "shards": np.zeros(len(rows), np.uint32),
                "rows": rows, "digs": digs}

    @_locked
    def bump_dir_epoch(self) -> int:
        """Structural invalidation requested from ABOVE the KV — the
        membership tier's `MSG_RINGNOTE` lands here: a ring transition
        re-owns key ranges fleet-wide, so every outstanding directory
        entry must stop validating at once (clients fall back to the
        verb path until their next refresh). Returns the new epoch."""
        self._mut_seq += 1
        self.dir_epoch += 1
        return self.dir_epoch

    # -- tier surface (no-ops on a flat pool) --

    @_locked
    def tier_stats(self) -> dict | None:
        """Per-tier counters (`hot_hits`, `promotions`, `demotions`,
        `balloon_*`, `migrated_bytes`, occupancy) — None when flat."""
        if not isinstance(self.state.pool, tier_mod.TierState):
            return None
        return tier_mod.stats_dict(self.state.pool,
                                   self.config.page_words * 4)

    def _balloon_rows(self, rows: int) -> int:
        """Round a balloon request UP to whole extents and clamp to the
        cold pool: `rows` is a static jit argument, so an un-rounded
        pressure-daemon value would compile a fresh program (argsort over
        the whole cold array included) per distinct size — extent
        granularity bounds the compiled set to C/balloon_step programs."""
        step = _tcfg(self.config).balloon_step
        c = self.state.pool.cfree.shape[0]
        return min(-(-int(rows) // step) * step, c)

    @_locked
    def balloon_state(self) -> dict | None:
        """Cold-pool circulation snapshot for the balloon controller
        (`runtime/autotune.py`): circulating/parked/free rows plus the
        extent step one knob move covers. None on a flat pool — the
        controller's probe for \"is ballooning even available here\"."""
        if not isinstance(self.state.pool, tier_mod.TierState):
            return None
        return tier_mod.balloon_state(self.state.pool,
                                      _tcfg(self.config).balloon_step)

    @_locked
    def balloon_grow(self, rows: int) -> bool:
        """Ensure at least `rows` free cold rows are circulating (parked
        capacity returns first; rounded up to whole extents). False on a
        flat pool."""
        if not isinstance(self.state.pool, tier_mod.TierState):
            return False
        self.state = dataclasses.replace(
            self.state,
            pool=tier_mod.grow(self.state.pool, self._balloon_rows(rows)),
        )
        self._mut_seq += 1
        self.dir_epoch += 1
        return True

    @_locked
    def balloon_shrink(self, rows: int) -> bool:
        """Balloon the cold pool down by up to `rows` rows now (rounded
        up to whole extents). Free rows park first; under load the
        coldest live rows are evicted — their pages degrade to legal
        misses (never wrong bytes). False on a flat pool."""
        if not isinstance(self.state.pool, tier_mod.TierState):
            return False
        self.state = dataclasses.replace(
            self.state,
            pool=tier_mod.shrink(self.state.pool,
                                 self._balloon_rows(rows)),
        )
        self._mut_seq += 1
        self.dir_epoch += 1
        return True

    # -- admission surface (no-ops when flat or the gate is off) --

    @_locked
    def admit_state(self) -> dict | None:
        """TinyLFU admission-gate snapshot (live threshold, epoch
        progress, counter lanes — `tier.admit_state`). None when the
        pool is flat or the gate is off — the controller's probe for
        "is an admission knob even available here", the
        `balloon_state` discipline."""
        pool = self.state.pool
        if not isinstance(pool, tier_mod.TierState) \
                or pool.admit_cm is None:
            return None
        return tier_mod.admit_state(
            pool, tier_mod.admit_cfg(pool, _tcfg(self.config)))

    @_locked
    def set_admit_threshold(self, value: int) -> bool:
        """Live admission-threshold write (the autotune knob's KV-side
        half; clamped to >= 0). Pages and digests are untouched, so the
        one-sided directory stays valid — no epoch bump. False when no
        gate is installed."""
        pool = self.state.pool
        if not isinstance(pool, tier_mod.TierState) \
                or pool.admit_cm is None:
            return False
        self.state = dataclasses.replace(
            self.state, pool=tier_mod.set_admit_threshold(pool, value))
        return True

    @_locked
    def account_shed(self, gets: int, puts: int = 0) -> None:
        """Attribute QoS-shed ops (runtime/qos.py) into the stats vector
        WITHOUT a device dispatch: a shed GET is a served all-miss with
        cause `miss_shed`; a shed PUT is an acked drop. Bumps the host
        overlay only — the device vector stays untouched — so the sum
        invariant `misses == Σ causes` holds on every snapshot."""
        if gets:
            self._host_stats[GETS] += int(gets)
            self._host_stats[MISSES] += int(gets)
            self._host_stats[MISS_SHED] += int(gets)
        if puts:
            self._host_stats[PUTS] += int(puts)
            self._host_stats[DROPS] += int(puts)

    @_locked
    def account_quarantined(self, gets: int, puts: int = 0) -> None:
        """Attribute shard-quarantine degradations (failure.ShardQuarantine
        via parallel/plane.py) without a device dispatch: a quarantined
        GET is a served all-miss with cause `miss_quarantined`; a
        quarantined PUT is an acked drop. Host overlay only, like
        `account_shed`, so `misses == Σ causes` holds on every snapshot."""
        if gets:
            self._host_stats[GETS] += int(gets)
            self._host_stats[MISSES] += int(gets)
            self._host_stats[MISS_QUARANTINED] += int(gets)
        if puts:
            self._host_stats[PUTS] += int(puts)
            self._host_stats[DROPS] += int(puts)

    @_locked
    def account_deadline(self, gets: int, puts: int = 0) -> None:
        """Attribute deadline-expired staged ops (runtime/net.py flush
        shed) without a device dispatch: an expired GET is a served
        all-miss with cause `miss_deadline`; an expired PUT is an acked
        drop. Host overlay only, the `account_shed` discipline."""
        if gets:
            self._host_stats[GETS] += int(gets)
            self._host_stats[MISSES] += int(gets)
            self._host_stats[MISS_DEADLINE] += int(gets)
        if puts:
            self._host_stats[PUTS] += int(puts)
            self._host_stats[DROPS] += int(puts)

    @_locked
    def stats(self) -> dict:
        vec = np.asarray(self.state.stats).astype(np.int64) \
            + self._host_stats
        d = dict(zip(STAT_NAMES, (int(x) for x in vec)))
        t = self.tier_stats()
        if t is not None:
            d.update(t)
        d["uptime_s"] = time.monotonic() - self._t0
        from pmdfc_tpu.runtime import telemetry as tele

        if tele.enabled():
            if self._tele_scope is None:
                self._tele_scope = tele.scope("kv")
            for k, v in d.items():
                if isinstance(v, (int, float)):
                    self._tele_scope.set(k, v)
        return d

    def print_stats(self) -> str:
        """Human stats dump (ref `PrintStats`, `rdpma_print_stats`
        `server/rdma_svr.cpp:107-140`)."""
        s = self.stats()
        line = ", ".join(f"{k}={v}" for k, v in s.items())
        print(f"[kv] {line}")
        return line
