"""CCEH — cacheline-conscious extendible hashing, TPU-native.

Reference: `server/CCEH_hybrid.{h,cpp}` and the DRAM variant
`server/src/cceh.{h,cpp}`: 16 KB segments probed 4 pairs × 8 cachelines
(32-slot window, `server/CCEH_hybrid.h:14-19`), MSB directory indexing,
segment split (`Segment::Split` `CCEH_hybrid.cpp:30-67`), directory doubling
+ stride updates (`:198-295`), and `Recovery` walking the directory to repair
buddy pointers (`:391-410`). The DRAM CCEH evicts on unsplittable overflow
and returns the victim (`server/src/cceh.h:169`) — the clean-cache contract.

TPU-native redesign (not a translation):
- **Fused-row probe window**: a segment is `W = segment_slots/32` rows of the
  shared `[khi|klo|vhi|vlo]` 128-lane layout (`models/rowops.py`); the hashed
  window IS the reference's 8-cacheline probe region, and a batched GET is
  directory-gather → row-gather → VPU lane compare. Two gathers total.
- **Replicated preallocated directory**: `dir[Smax]` always holds the entry
  for every top-`Gmax`-bit prefix, where `Gmax = log2(initial segments) +
  split_headroom`. A logical directory of depth g < Gmax is stored with each
  entry replicated `2**(Gmax-g)` times, so lookups never depend on the
  current depth and *doubling is a no-op on the array* (a scalar depth bump):
  the reference's stop-the-world directory realloc + stride pointer fix-up
  (`CCEH_hybrid.cpp:198-295`) disappears.
- **In-jit vectorized multi-split**: inserts run a `lax.while_loop` of
  (attempt placement → split every overflowing segment, up to
  `max_splits_per_round` at once). A split gathers the segment's `[W, 4*32]`
  block, moves entries whose next MSB hash bit is 1 to the buddy segment
  (same window, same lane — lanes are preserved, which keeps result slots
  recomputable), and rewrites the directory range with one vector `where`.
  The reference suspends the segment and rehashes pair-by-pair
  (`CCEH_hybrid.cpp:143-233`); here the whole thing is three scatters.
- **Eviction fallback**: when headroom is exhausted (local depth == Gmax) a
  full window evicts an occupant not touched by this batch and reports it,
  so the store keeps absorbing puts — the DRAM CCEH's behavior, and what the
  KV façade needs to propagate bloom deletes.

Mutation is eager (split rehashes entries now), so the reference's
lazy-deletion pattern-mismatch reuse (`CCEH_hybrid.cpp:143-168`) is
unnecessary: a slot is free iff its key is INVALID.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from pmdfc_tpu.config import IndexConfig, IndexKind
from pmdfc_tpu.models.base import (
    GetResult,
    IndexOps,
    InsertResult,
    batch_rank_by_segment,
    dedupe_last_wins,
    register_index,
)
from pmdfc_tpu.models.rowops import (
    free_lanes,
    lane_pick,
    match_mask,
    match_rows,
    no_evict_stub,
    nth_lane,
    pick_kv,
    scatter_entry,
)
from pmdfc_tpu.utils.hashing import hash_u64
from pmdfc_tpu.utils.keys import INVALID_WORD, is_invalid

WINDOW_SEED = 0x77AA55EE  # window hash family, independent of directory bits


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CCEHState:
    table: jnp.ndarray   # uint32[R, 4*P] fused rows; R = Smax * W
    ld: jnp.ndarray      # uint32[Smax] local depth per segment
    dirr: jnp.ndarray    # int32[Smax] replicated directory (MSB prefix -> seg)
    gdepth: jnp.ndarray  # uint32[] global depth (stats/recovery)
    nseg: jnp.ndarray    # int32[] allocated segment count
    # static knobs (part of the treedef, not traced)
    k_splits: int = dataclasses.field(metadata=dict(static=True), default=64)
    rounds: int = dataclasses.field(metadata=dict(static=True), default=3)
    # MSB directory indexing (CCEH, `CCEH_hybrid.cpp` uses high bits) vs LSB
    # (classic extendible hashing, `server/src/extendible_hash.h:27-33`).
    # Same machinery; only the prefix/bit arithmetic differs.
    msb: bool = dataclasses.field(metadata=dict(static=True), default=True)


@dataclasses.dataclass(frozen=True)
class _Geom:
    P: int      # probe window lanes per row
    W: int      # rows (windows) per segment
    Gmax: int   # max depth
    Smax: int   # max segments = 2**Gmax
    R: int      # total rows
    K: int      # max splits per round
    rounds: int
    msb: bool


def _geom(state: CCEHState) -> _Geom:
    r, lanes = state.table.shape
    smax = state.ld.shape[0]
    return _Geom(
        P=lanes // 4, W=r // smax, Gmax=smax.bit_length() - 1, Smax=smax,
        R=r, K=state.k_splits, rounds=state.rounds, msb=state.msb,
    )


def _init_geom(config: IndexConfig):
    p = config.probe_window
    w = max(1, config.segment_slots // p)
    s0 = max(1, config.capacity // (w * p))
    if s0 & (s0 - 1):
        s0 = 1 << (s0 - 1).bit_length()
    g0 = s0.bit_length() - 1
    gmax = max(1, g0 + config.split_headroom)
    return p, w, s0, g0, gmax, 1 << gmax


def num_slots(config: IndexConfig) -> int:
    p, w, _, _, _, smax = _init_geom(config)
    return smax * w * p


def init(config: IndexConfig, msb: bool = True) -> CCEHState:
    p, w, s0, g0, gmax, smax = _init_geom(config)
    r = smax * w
    table = jnp.concatenate(
        [
            jnp.full((r, 2 * p), INVALID_WORD, jnp.uint32),
            jnp.zeros((r, 2 * p), jnp.uint32),
        ],
        axis=1,
    )
    ld = jnp.where(jnp.arange(smax) < s0, jnp.uint32(g0), jnp.uint32(0))
    i = jnp.arange(smax, dtype=jnp.int32)
    # prefix i's g0 directory bits (top for MSB, low for LSB) name its segment
    dirr = (i >> (gmax - g0)) if msb else (i & (s0 - 1))
    return CCEHState(
        table=table, ld=ld, dirr=dirr.astype(jnp.int32),
        gdepth=jnp.asarray(g0, jnp.uint32),
        nseg=jnp.asarray(s0, jnp.int32),
        k_splits=min(config.max_splits_per_round, smax),
        rounds=config.split_headroom + 2,
        msb=msb,
    )


def _locate(g: _Geom, dirr: jnp.ndarray, hdir: jnp.ndarray,
            hwin: jnp.ndarray) -> jnp.ndarray:
    if g.msb:
        idx = (hdir >> (32 - g.Gmax)).astype(jnp.int32)
    else:
        idx = (hdir & jnp.uint32(g.Smax - 1)).astype(jnp.int32)
    seg = dirr[idx]
    return seg * g.W + hwin


def _hashes(g: _Geom, keys: jnp.ndarray):
    hdir = hash_u64(keys[..., 0], keys[..., 1])
    hwin = (
        hash_u64(keys[..., 0], keys[..., 1], seed=WINDOW_SEED)
        & jnp.uint32(g.W - 1)
    ).astype(jnp.int32)
    return hdir, hwin


@jax.jit
def get_batch(state: CCEHState, keys: jnp.ndarray) -> GetResult:
    g = _geom(state)
    hdir, hwin = _hashes(g, keys)
    row = _locate(g, state.dirr, hdir, hwin)
    rows = state.table[row]
    eq, lane = match_rows(rows, keys, g.P)
    found = lane >= 0
    values = jnp.stack(
        [lane_pick(rows, eq, 2 * g.P, g.P), lane_pick(rows, eq, 3 * g.P, g.P)],
        axis=-1,
    )
    gslot = jnp.where(found, row * g.P + jnp.maximum(lane, 0), jnp.int32(-1))
    return GetResult(values=values, found=found, slots=gslot)


@jax.jit
def get_values(state: CCEHState, keys: jnp.ndarray):
    """Lean GET (see `linear.get_values`): (values zero-on-miss, found),
    no slot/argmax bookkeeping — the probe gather runs at a fixed rows/s,
    so every non-gather op on this path costs headline throughput."""
    g = _geom(state)
    hdir, hwin = _hashes(g, keys)
    row = _locate(g, state.dirr, hdir, hwin)
    rows = state.table[row]
    eq = match_mask(rows, keys, g.P)
    found = eq.any(axis=1)
    values = jnp.stack(
        [lane_pick(rows, eq, 2 * g.P, g.P), lane_pick(rows, eq, 3 * g.P, g.P)],
        axis=-1,
    )
    return values, found


def _split_round(g: _Geom, table, ld, dirr, gdepth, nseg, want):
    """Split every flagged segment (≤K, capacity permitting) at once.

    `want: bool[Smax]`. Returns updated (table, ld, dirr, gdepth, nseg).
    """
    can = want & (ld < jnp.uint32(g.Gmax))
    srank = jnp.cumsum(can.astype(jnp.int32)) - 1
    avail = jnp.minimum(jnp.int32(g.K), jnp.int32(g.Smax) - nseg)
    doit = can & (srank < avail)
    ndo = doit.sum(dtype=jnp.int32)

    # compact the ≤K splitting segment ids
    seg_ids = jnp.arange(g.Smax, dtype=jnp.int32)
    seg_list = jnp.full((g.K,), -1, jnp.int32).at[
        jnp.where(doit, srank, jnp.int32(g.K))
    ].set(seg_ids, mode="drop")
    ok = seg_list >= 0
    ld_old = ld  # pre-split depths (directory math needs these)
    ld_old_k = ld_old[jnp.maximum(seg_list, 0)]

    # move entries whose next MSB bit is 1 into the buddy segment
    warange = jnp.arange(g.W, dtype=jnp.int32)
    src_rows = jnp.maximum(seg_list, 0)[:, None] * g.W + warange[None, :]
    blocks = table[src_rows]                                  # [K, W, 4P]
    khi, klo = blocks[..., 0 : g.P], blocks[..., g.P : 2 * g.P]
    occupied = ~((khi == jnp.uint32(INVALID_WORD))
                 & (klo == jnp.uint32(INVALID_WORD)))
    hb = hash_u64(khi, klo)
    if g.msb:
        shift_e = jnp.uint32(31) - ld_old_k[:, None, None]
    else:
        shift_e = ld_old_k[:, None, None]
    bit = (hb >> shift_e) & jnp.uint32(1)
    move = occupied & (bit == 1) & ok[:, None, None]

    inv = jnp.uint32(INVALID_WORD)
    move4 = jnp.concatenate([move, move, move, move], axis=-1)
    keymask4 = jnp.concatenate(
        [jnp.ones_like(move), jnp.ones_like(move),
         jnp.zeros_like(move), jnp.zeros_like(move)], axis=-1
    )
    # buddy gets moved entries, INVALID keys elsewhere (values don't matter)
    tgt_blocks = jnp.where(move4, blocks, jnp.where(keymask4, inv, blocks))
    # source keeps non-moved entries, moved keys cleared
    src_after = jnp.where(move4 & keymask4, inv, blocks)

    new_ids = nseg + jnp.arange(g.K, dtype=jnp.int32)          # [K]
    tgt_rows = jnp.where(
        ok[:, None], new_ids[:, None] * g.W + warange[None, :], jnp.int32(g.R)
    )
    table = table.at[tgt_rows].set(tgt_blocks, mode="drop")
    table = table.at[jnp.where(ok[:, None], src_rows, jnp.int32(g.R))].set(
        src_after, mode="drop"
    )

    # depths: split seg and buddy both deepen to ld_old+1
    ld = jnp.where(doit, ld_old + 1, ld_old)
    ld = ld.at[jnp.where(ok, new_ids, jnp.int32(g.Smax))].set(
        ld_old_k + 1, mode="drop"
    )
    gdepth = jnp.maximum(gdepth, jnp.where(doit, ld, 0).max())
    new_of_seg = jnp.zeros((g.Smax,), jnp.int32).at[
        jnp.where(ok, seg_list, jnp.int32(g.Smax))
    ].set(new_ids, mode="drop")

    # directory: prefixes owned by s whose bit at ld_old[s] is 1 -> buddy
    i = jnp.arange(g.Smax, dtype=jnp.int32)
    s_i = dirr[i]
    # clamp: shift is only meaningful where doit (ld_old < Gmax); elsewhere
    # ld_old may equal Gmax and the raw MSB shift would be negative
    if g.msb:
        shift = jnp.maximum(
            jnp.int32(g.Gmax - 1) - ld_old[s_i].astype(jnp.int32), 0
        )
    else:
        shift = ld_old[s_i].astype(jnp.int32)
    bit_i = (i >> shift) & 1
    dirr = jnp.where(doit[s_i] & (bit_i == 1), new_of_seg[s_i], dirr)
    return table, ld, dirr, gdepth, nseg + ndo


@jax.jit
def insert_batch(state: CCEHState, keys: jnp.ndarray, values: jnp.ndarray):
    g = _geom(state)
    b = keys.shape[0]
    valid = ~is_invalid(keys)
    winner = dedupe_last_wins(keys, valid)
    hdir, hwin = _hashes(g, keys)
    vhi, vlo = values[:, 0], values[:, 1]

    def attempt(table, dirr, slots, fresh, pending):
        """Place pending keys into free lanes; returns overflow mask too."""
        row = _locate(g, dirr, hdir, hwin)
        rows = table[row]
        mk = jnp.where(pending[:, None], keys, jnp.uint32(INVALID_WORD))
        eq, lane = match_rows(rows, mk, g.P)
        upd = pending & (lane >= 0)
        r_u = jnp.where(upd, row, jnp.int32(g.R))
        l_u = jnp.maximum(lane, 0)
        table = table.at[r_u, 2 * g.P + l_u].set(vhi, mode="drop")
        table = table.at[r_u, 3 * g.P + l_u].set(vlo, mode="drop")
        slots = jnp.where(upd, row * g.P + l_u, slots)

        new = pending & ~upd
        rank = batch_rank_by_segment(row.astype(jnp.uint32), new)
        free = free_lanes(rows, g.P)
        can = new & (rank < free.sum(axis=1))
        hot = nth_lane(free, rank)
        lane_t = jnp.argmax(hot, axis=1).astype(jnp.int32)
        table = scatter_entry(table, row, lane_t, keys, values, g.P, can)
        slots = jnp.where(can, row * g.P + lane_t, slots)
        fresh = fresh | can
        return table, slots, fresh, new & ~can, row

    def cond(carry):
        table, ld, dirr, gdepth, nseg, slots, fresh, rnd = carry
        return (rnd < g.rounds) & (winner & (slots < 0)).any()

    def body(carry):
        table, ld, dirr, gdepth, nseg, slots, fresh, rnd = carry
        pending = winner & (slots < 0)
        table, slots, fresh, overflow, row = attempt(
            table, dirr, slots, fresh, pending
        )

        # split + relocation only when something actually overflowed: a
        # round whose attempt placed every pending key would otherwise
        # still pay _split_round's fixed K-segment gathers and a full
        # directory relocate for an empty `want` (the common last round).
        def do_split(op):
            table, ld, dirr, gdepth, nseg, slots = op
            seg = row // g.W
            want = jnp.zeros((g.Smax,), bool).at[
                jnp.where(overflow, seg, jnp.int32(g.Smax))
            ].set(True, mode="drop")
            with jax.named_scope("cceh_split_round"):
                table, ld, dirr, gdepth, nseg = _split_round(
                    g, table, ld, dirr, gdepth, nseg, want
                )
            # placed entries may have moved (lane is split-invariant;
            # row is not)
            row2 = _locate(g, dirr, hdir, hwin)
            slots = jnp.where(slots >= 0, row2 * g.P + slots % g.P, slots)
            return table, ld, dirr, gdepth, nseg, slots

        table, ld, dirr, gdepth, nseg, slots = jax.lax.cond(
            overflow.any(), do_split, lambda op: op,
            (table, ld, dirr, gdepth, nseg, slots),
        )
        return table, ld, dirr, gdepth, nseg, slots, fresh, rnd + 1

    slots0 = jnp.full((b,), -1, jnp.int32)
    fresh0 = jnp.zeros((b,), bool)
    table, ld, dirr, gdepth, nseg, slots, fresh, rounds = jax.lax.while_loop(
        cond, body,
        (state.table, state.ld, state.dirr, state.gdepth, state.nseg,
         slots0, fresh0, jnp.int32(0)),
    )

    # final pass: fill any space the last split opened, then evict — but
    # only when the loop left keys unplaced. In the common fill batch the
    # while_loop exits with nothing pending, and the whole tail (another
    # attempt gather+rank+scatters, the protection scatter, the eviction
    # gather+rank+extraction) is a no-op not worth its passes.
    inv2 = jnp.full((b, 2), INVALID_WORD, jnp.uint32)

    @jax.named_scope("cceh_evict_tail")
    def tail_evict(op):
        table, slots, fresh = op
        pending = winner & (slots < 0)
        table, slots, fresh, still, row = attempt(
            table, dirr, slots, fresh, pending
        )
        # eviction fallback — never evict a lane placed/updated in THIS
        # batch
        prot_bits = jnp.zeros((g.R,), jnp.uint32).at[
            jnp.where(slots >= 0, slots // g.P, jnp.int32(g.R))
        ].add(
            jnp.uint32(1)
            << (jnp.maximum(slots, 0) % g.P).astype(jnp.uint32),
            mode="drop",
        )
        rows2 = table[row]
        lanes = jnp.arange(g.P, dtype=jnp.uint32)[None, :]
        prot = ((prot_bits[row][:, None] >> lanes) & 1).astype(bool)
        cand = ~free_lanes(rows2, g.P) & ~prot
        erank = batch_rank_by_segment(row.astype(jnp.uint32), still)
        place = still & (erank < cand.sum(axis=1))
        hot = nth_lane(cand, erank) & place[:, None]
        lane_e = jnp.argmax(hot, axis=1).astype(jnp.int32)
        ek, ev = pick_kv(rows2, hot, g.P)
        evicted_ = jnp.where(place[:, None], ek, inv2)
        evicted_vals_ = jnp.where(place[:, None], ev, inv2)
        table = scatter_entry(table, row, lane_e, keys, values, g.P, place)
        slots = jnp.where(place, row * g.P + lane_e, slots)
        fresh = fresh | place
        dropped_ = still & ~place
        return table, slots, fresh, evicted_, evicted_vals_, dropped_

    def tail_skip(op):
        table, slots, fresh = op
        # no-evict payload single-sourced from rowops (lane_e unused here:
        # cceh's tail computes its own placement lanes in the true branch)
        tb, no_ek, no_ev, no_drop, _ = no_evict_stub(b)(table)
        return tb, slots, fresh, no_ek, no_ev, no_drop

    table, slots, fresh, evicted, evicted_vals, dropped = jax.lax.cond(
        (winner & (slots < 0)).any(), tail_evict, tail_skip,
        (table, slots, fresh),
    )

    new_state = dataclasses.replace(
        state, table=table, ld=ld, dirr=dirr, gdepth=gdepth, nseg=nseg
    )
    # every split allocates one segment, so the count is nseg's growth
    res = InsertResult(
        slots=slots, evicted=evicted, dropped=dropped, fresh=fresh,
        evicted_vals=evicted_vals, splits=nseg - state.nseg, rounds=rounds,
    )
    return new_state, res


@jax.jit
def delete_batch(state: CCEHState, keys: jnp.ndarray):
    g = _geom(state)
    hdir, hwin = _hashes(g, keys)
    row = _locate(g, state.dirr, hdir, hwin)
    rows = state.table[row]
    eq, lane = match_rows(rows, keys, g.P)
    hit = lane >= 0
    _, old_vals = pick_kv(rows, eq, g.P)
    old_vals = jnp.where(hit[:, None], old_vals, jnp.uint32(INVALID_WORD))
    r_d = jnp.where(hit, row, jnp.int32(g.R))
    l_d = jnp.maximum(lane, 0)
    inv = jnp.full((keys.shape[0],), INVALID_WORD, jnp.uint32)
    table = state.table.at[r_d, l_d].set(inv, mode="drop")
    table = table.at[r_d, g.P + l_d].set(inv, mode="drop")
    return dataclasses.replace(state, table=table), hit, old_vals


@jax.jit
def set_values(state: CCEHState, slots: jnp.ndarray, values: jnp.ndarray):
    g = _geom(state)
    okr = jnp.where(slots >= 0, slots // g.P, jnp.int32(g.R))
    lane = jnp.maximum(slots, 0) % g.P
    table = state.table.at[okr, 2 * g.P + lane].set(values[:, 0], mode="drop")
    table = table.at[okr, 3 * g.P + lane].set(values[:, 1], mode="drop")
    return dataclasses.replace(state, table=table)


def scan(state: CCEHState):
    p = state.table.shape[1] // 4
    t = state.table
    keys = jnp.stack(
        [t[:, 0:p].reshape(-1), t[:, p : 2 * p].reshape(-1)], axis=-1
    )
    vals = jnp.stack(
        [t[:, 2 * p : 3 * p].reshape(-1), t[:, 3 * p : 4 * p].reshape(-1)],
        axis=-1,
    )
    return keys, vals


@jax.jit
def recovery(state: CCEHState) -> CCEHState:
    """Directory repair after restore (ref `CCEH::Recovery`
    `server/CCEH_hybrid.cpp:391-410`).

    In the replicated representation every segment's 2**(Gmax-ld) directory
    entries must agree; the canonical entry is the block start (the buddy
    walk of the reference collapses to one vectorized re-read).
    """
    g = _geom(state)
    i = jnp.arange(g.Smax, dtype=jnp.int32)
    s = state.dirr[i]
    if g.msb:
        # MSB replication blocks are contiguous; canonical = block start
        block = jnp.int32(1) << (
            jnp.int32(g.Gmax) - state.ld[s].astype(jnp.int32)
        )
        start = i & ~(block - 1)
    else:
        # LSB replication classes are strided (i ≡ canonical mod 2**ld)
        start = i & ((jnp.int32(1) << state.ld[s].astype(jnp.int32)) - 1)
    dirr = state.dirr[start]
    gdepth = state.ld[dirr].max()
    return dataclasses.replace(state, dirr=dirr, gdepth=gdepth)


register_index(
    IndexKind.CCEH,
    IndexOps(
        init=init,
        get_batch=get_batch,
        insert_batch=insert_batch,
        delete_batch=delete_batch,
        num_slots=num_slots,
        scan=scan,
        set_values=set_values,
        recovery=recovery,
        get_values=get_values,
    ),
)
