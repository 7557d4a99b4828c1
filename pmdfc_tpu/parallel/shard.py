"""KV state sharded across a TPU mesh — the NUMA_KV analog, done as SPMD.

Reference: `server/NuMA_KV.cpp` routes each request to a per-NUMA-node
lock-free circular queue picked by `GetNodeID(key)` (`NuMA_KV.cpp:136-151`),
with worker/receiver/poller thread pools per node (`NuMA_KV.h:94-100`).

TPU-native redesign (collectives instead of queues):
- The whole `KVState` pytree gains a leading `[n_shards]` axis sharded over a
  1-D `Mesh` axis ``"kv"`` — every shard owns an independent index + bloom +
  page pool + extent ring covering the key-space slice
  ``shard_of(key) = murmur3(key, SHARD_SEED) % n_shards``.

Two dispatch strategies, selected by ``ShardedKV(dispatch=...)``:

- ``"a2a"`` (default): the request batch arrives SHARDED (each shard holds a
  contiguous B/n slice). Each shard bins its slice by owner
  (`batch_rank_by_segment` gives conflict-free bucket lanes), ships the
  buckets with ONE `lax.all_to_all`, runs the same fused local program the
  single-chip path uses on what it received, and a reverse `all_to_all`
  returns per-request results to the requesting shard. Per-shard probe work
  is O(B/n · capacity_factor) — the ragged exchange the reference's per-node
  queues approximate with worker threads (SURVEY §5.8/§7.5). The bucket
  capacity is `min(Bl, max(16, 2·ceil(Bl/n)))` per (src, dst) pair: exact
  for small batches, 2× the uniform-hash expectation for large ones;
  overflow (astronomically rare under murmur3 routing, and impossible when
  the pair capacity is Bl) is reported as a drop/miss — legal clean-cache
  outcomes, never silent corruption. Request order is preserved end-to-end
  (source-major receive order + stable in-source ranks), so batched
  dedupe-last-wins semantics match the single-chip ground truth exactly.
- ``"broadcast"``: the round-1 owner-computes form — the batch is replicated,
  each shard masks non-owned keys to INVALID and runs the local program, and
  results merge with one `psum`/`pmax` (each key lands on exactly one shard).
  O(B) per-shard work; kept as the semantic reference and for tiny batches.

Extent records are deterministically replicated (every shard appends the same
record at the same ring cursor), because an extent's power-of-two covers hash
to *different* shards; replication makes any cover resolvable locally on
whichever shard owns it. `get_extent` always uses the broadcast body — its
cover probes are maximally skewed (nearby keys share cover keys), so a
loss-free exchange degenerates to broadcast work plus two collectives.

Stats: per-shard `stats` vectors sum to the global truth; overflow drops are
accounted on the requesting shard. `ShardedKV.stats()` sums host-side.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pmdfc_tpu import checkpoint as ckpt_mod
from pmdfc_tpu import kv as kv_mod
from pmdfc_tpu import tier as tier_mod
from pmdfc_tpu.models.base import (
    InsertResult,
    batch_rank_by_segment,
    get_index_ops,
)
from pmdfc_tpu.config import KVConfig
from pmdfc_tpu.kv import (
    GETS, HITS, MISSES, MISS_COLD, MISS_DEADLINE, MISS_DIGEST,
    MISS_EVICTED, MISS_QUARANTINED, MISS_ROUTED, MISS_SHED, NSTATS,
    PUTS, DROPS, KVState)
from pmdfc_tpu.ops import pagepool
from pmdfc_tpu.ops import bloom as bloom_ops
from pmdfc_tpu.parallel import partitioning as pt
from pmdfc_tpu.runtime import profiler
from pmdfc_tpu.utils.hashing import shard_of
from pmdfc_tpu.utils.keys import INVALID_WORD, is_invalid

AXIS = pt.MESH_AXIS
# second mesh axis of a 2-D serving mesh: replica lanes (state is
# replicated along it; GET arbitration / repair collectives run over it)
RAXIS = pt.REPLICA_MESH_AXIS


def _shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication check off (bodies use
    collectives whose replication the checker cannot prove)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def shard_donate() -> bool:
    """ONE copy of the sharded-dispatch donation predicate (see the
    CPU-segfault note in `ShardedKV._wrap`): `_wrap`'s donate_argnums
    AND `fast_view`'s own-your-bytes rule both key off it — a drift
    between the two would let a donating dispatch scribble on buffers
    the fast lane still aliases."""
    return (jax.devices()[0].platform != "cpu"
            or os.environ.get("PMDFC_SHARD_DONATE") == "1")


def make_mesh(devices=None, axis: str = AXIS) -> Mesh:
    """1-D mesh over all (or given) devices; axis name ``"kv"``.

    After `connect_multihost`, `jax.devices()` spans every host, so the
    same mesh (and the same `shard_map` programs) scales from one chip to
    a multi-host pod with no code change: XLA routes the `all_to_all`
    exchange over ICI within a slice and DCN across slices.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devices.reshape(-1), (axis,))


def make_mesh2d(n_shards: int, n_replicas: int, devices=None) -> Mesh:
    """2-D mesh `(kv=n_shards, replica=n_replicas)` — the fused serving
    plane's topology: the kv axis partitions the key space exactly like
    the 1-D mesh, the replica axis carries `n_replicas` full copies of
    each shard's state, so one device launch replaces the host
    ReplicaGroup's rf TCP fan-out loops (PAPER.md §2.4/§5.8: many lanes,
    one logical op stream, minimum boundary crossings)."""
    need = n_shards * n_replicas
    devices = np.asarray(devices if devices is not None
                         else jax.devices()[:need])
    if devices.size != need:
        raise ValueError(
            f"mesh2d needs {n_shards}x{n_replicas}={need} devices, "
            f"got {devices.size}")
    return Mesh(devices.reshape(n_shards, n_replicas), (AXIS, RAXIS))


def connect_multihost(coordinator: str, num_processes: int,
                      process_id: int, timeout_s: int | None = None) -> int:
    """Join a multi-host JAX runtime — the DCN-scale analog of the
    reference's multi-node RDMA fabric (SURVEY §5.8; the reference scales
    out with one RDMA server and N kernel clients, this framework scales
    the SERVER across hosts and keeps clients on the TCP messenger).

    Wraps `jax.distributed.initialize`; afterwards `jax.devices()` lists
    every host's chips and `make_mesh()` builds the global mesh. Returns
    the global device count. Single-host callers never need this.

    Must run before ANY jax computation or device query in the process
    (`jax.distributed.initialize` refuses once a backend exists) — in
    particular before constructing a `ShardedKV`.
    """
    kw = {}
    if timeout_s is not None:
        # bound the join so a worker chasing a coordinator that moved its
        # port (bind-retry ladder, `bench/multihost_bench.py`) fails fast
        # enough to re-read the published port instead of eating the
        # 300 s default
        kw["initialization_timeout"] = timeout_s
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
            **kw,
        )
    except TypeError:
        # older jax without initialization_timeout
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    return len(jax.devices())


def _mask_to_owner(keys: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    me = jax.lax.axis_index(AXIS).astype(jnp.uint32)
    mine = shard_of(keys, n_shards) == me
    return jnp.where(mine[:, None], keys, jnp.uint32(INVALID_WORD))


def _unstack(state):
    return jax.tree.map(lambda x: x[0], state)


def _restack(state):
    return jax.tree.map(lambda x: x[None], state)


def _combine_values(values: jnp.ndarray, found: jnp.ndarray):
    """Merge per-shard (values, found): each key found on ≤1 shard."""
    v = jnp.where(found[:, None], values, jnp.zeros_like(values))
    return jax.lax.psum(v, AXIS), jax.lax.pmax(found, AXIS)


def _bump_stats(st, **by_name):
    names = {"puts": PUTS, "gets": GETS, "hits": HITS, "misses": MISSES,
             "drops": DROPS, "miss_routed": MISS_ROUTED}
    fix = jnp.zeros((NSTATS,), jnp.int32)
    for k, v in by_name.items():
        fix = fix.at[names[k]].add(v)
    return dataclasses.replace(st, stats=st.stats + fix)


# ---------------------------------------------------------------------------
# a2a dispatch primitives (run per shard inside shard_map)
# ---------------------------------------------------------------------------

def pair_capacity(bl: int, n: int) -> int:
    """Static per-(src, dst) bucket size: exact for small batches, 2× the
    uniform expectation for large ones."""
    return min(bl, max(16, -(-2 * bl // n)))


def _route(keys: jnp.ndarray, n: int, c_pair: int):
    """(ok[Bl], flat[Bl]): bucket lane assignment for each local request.

    `flat = dest * c_pair + rank`; rows beyond the pair capacity (or INVALID)
    get the dump slot `n * c_pair`. Ranks are stable in batch order, which is
    what makes cross-shard dedupe-last-wins match the single-chip order.
    """
    valid = ~is_invalid(keys)
    dest = jnp.where(valid, shard_of(keys, n), jnp.uint32(0)).astype(jnp.int32)
    rank = batch_rank_by_segment(dest.astype(jnp.uint32), valid)
    ok = valid & (rank < c_pair)
    flat = jnp.where(ok, dest * c_pair + rank, jnp.int32(n * c_pair))
    return ok, flat


def _to_owner(x: jnp.ndarray, flat: jnp.ndarray, n: int, c_pair: int,
              fill) -> jnp.ndarray:
    """Scatter rows into [n, c_pair] buckets and all_to_all them to owners.

    Returns the received [n*c_pair, ...] buffer in source-major order."""
    buf = jnp.full((n * c_pair + 1, *x.shape[1:]), fill, x.dtype)
    buf = buf.at[flat].set(x)  # (dest, rank) lanes are unique; dump row junk
    out = jax.lax.all_to_all(
        buf[: n * c_pair].reshape(n, c_pair, *x.shape[1:]), AXIS, 0, 0
    )
    return out.reshape(n * c_pair, *x.shape[1:])


def _to_source(r: jnp.ndarray, flat: jnp.ndarray, ok: jnp.ndarray,
               n: int, c_pair: int, miss) -> jnp.ndarray:
    """Reverse exchange of per-request results + gather back to batch order."""
    back = jax.lax.all_to_all(
        r.reshape(n, c_pair, *r.shape[1:]), AXIS, 0, 0
    ).reshape(n * c_pair, *r.shape[1:])
    got = back[jnp.minimum(flat, n * c_pair - 1)]
    if got.ndim > ok.ndim:
        sel = ok.reshape(ok.shape + (1,) * (got.ndim - ok.ndim))
    else:
        sel = ok
    return jnp.where(sel, got, miss)


def _a2a_insert_body(config: KVConfig, n: int, c_pair: int, state, keys,
                     values):
    st = _unstack(state)
    ok, flat = _route(keys, n, c_pair)
    k_go = _to_owner(keys, flat, n, c_pair, jnp.uint32(INVALID_WORD))
    v_go = _to_owner(values, flat, n, c_pair, jnp.uint32(0))
    st2, res = kv_mod.insert(st, config, k_go, v_go)
    inval2 = jnp.full((1, 2), INVALID_WORD, jnp.uint32)
    out = InsertResult(
        slots=_to_source(res.slots, flat, ok, n, c_pair, jnp.int32(-1)),
        evicted=_to_source(res.evicted, flat, ok, n, c_pair, inval2),
        dropped=_to_source(res.dropped, flat, ok, n, c_pair,
                           ~is_invalid(keys)),  # overflow ⇒ dropped
        fresh=_to_source(res.fresh, flat, ok, n, c_pair, False),
        evicted_vals=_to_source(res.evicted_vals, flat, ok, n, c_pair,
                                inval2),
    )
    # bucket-overflow rows never reached an owner: account them here
    lost = (~is_invalid(keys) & ~ok).sum(dtype=jnp.int32)
    st2 = _bump_stats(st2, puts=lost, drops=lost)
    return _restack(st2), out


def _a2a_get_impl(config: KVConfig, n: int, c_pair: int, state, keys,
                  lean: bool):
    st = _unstack(state)
    ok, flat = _route(keys, n, c_pair)
    k_go = _to_owner(keys, flat, n, c_pair, jnp.uint32(INVALID_WORD))
    st2, out, found = kv_mod._get_core(st, config, k_go, lean=lean)
    vals = _to_source(out, flat, ok, n, c_pair, jnp.zeros_like(out[:1]))
    got = _to_source(found, flat, ok, n, c_pair, False)
    # bucket-overflow rows never reached an owner: a routed shed, the
    # one miss cause only the a2a dispatch can manufacture
    lost = (~is_invalid(keys) & ~ok).sum(dtype=jnp.int32)
    st2 = _bump_stats(st2, gets=lost, misses=lost, miss_routed=lost)
    return _restack(st2), vals, got


def _a2a_get_body(config: KVConfig, n: int, c_pair: int, state, keys):
    return _a2a_get_impl(config, n, c_pair, state, keys, lean=False)


def _a2a_get_lean_body(config: KVConfig, n: int, c_pair: int, state, keys):
    return _a2a_get_impl(config, n, c_pair, state, keys, lean=True)


def _a2a_delete_body(config: KVConfig, n: int, c_pair: int, state, keys):
    st = _unstack(state)
    ok, flat = _route(keys, n, c_pair)
    k_go = _to_owner(keys, flat, n, c_pair, jnp.uint32(INVALID_WORD))
    st2, hit = kv_mod.delete(st, config, k_go)
    got = _to_source(hit, flat, ok, n, c_pair, False)
    return _restack(st2), got


# (No a2a body for get_extent: its cover probes are maximally skewed —
# every nearby key's height-h probe collapses onto the same cover key — so a
# loss-free exchange needs exact per-pair buckets of the full local width,
# which makes each shard probe the same B·H rows as broadcast PLUS two full
# all_to_alls and a routing sort. The broadcast body is strictly cheaper;
# both dispatch modes use it.)


# ---------------------------------------------------------------------------
# broadcast (owner-computes) bodies — the semantic reference path
# ---------------------------------------------------------------------------

def _combine_insert_result(res: InsertResult) -> InsertResult:
    return InsertResult(
        slots=jax.lax.pmax(res.slots, AXIS),
        evicted=jax.lax.pmin(res.evicted, AXIS),  # non-owners hold all-ones
        dropped=jax.lax.pmax(res.dropped, AXIS),
        fresh=jax.lax.pmax(res.fresh, AXIS),
        evicted_vals=jax.lax.pmin(res.evicted_vals, AXIS),
    )


def _insert_body(config: KVConfig, n: int, state, keys, values):
    st = _unstack(state)
    st2, res = kv_mod.insert(st, config, _mask_to_owner(keys, n), values)
    return _restack(st2), _combine_insert_result(res)


def _get_body(config: KVConfig, n: int, state, keys):
    st = _unstack(state)
    st2, out, found = kv_mod.get(st, config, _mask_to_owner(keys, n))
    out, found = _combine_values(out, found)
    return _restack(st2), out, found


def _get_lean_body(config: KVConfig, n: int, state, keys):
    st = _unstack(state)
    st2, out, found = kv_mod._get_core(
        st, config, _mask_to_owner(keys, n), lean=True
    )
    out, found = _combine_values(out, found)
    return _restack(st2), out, found


def _delete_body(config: KVConfig, n: int, state, keys):
    st = _unstack(state)
    st2, hit = kv_mod.delete(st, config, _mask_to_owner(keys, n))
    return _restack(st2), jax.lax.pmax(hit, AXIS)


def _insert_extent_body(config: KVConfig, n: int, state, key, value, length):
    # Cover keys only exist inside the op, so owner masking happens there
    # (`kv._insert_extent_impl` shard branch), not here. Tiny batches
    # (≤ extent_max_covers rows) — broadcast is the right dispatch in both
    # modes.
    st = _unstack(state)
    st2, res, uncovered = kv_mod.insert_extent_sharded(
        st, config, key, value, length, n, jax.lax.axis_index(AXIS)
    )
    return _restack(st2), _combine_insert_result(res), uncovered


def _get_extent_body(config: KVConfig, n: int, state, keys):
    st = _unstack(state)
    # bump_causes=False: every shard probes the FULL batch, so per-shard
    # cause bumps would multiply by n_shards; causes are arbitrated
    # globally below and land on shard 0 with the gets/misses rewrite
    st2, out, found_local, height, ev = kv_mod._get_extent_impl(
        st, config, keys, bump_causes=False)
    # A key can be spanned by covers at DIFFERENT heights living on DIFFERENT
    # shards (e.g. covers [136,137) and [128,136) both span page 136). The
    # single-chip op resolves that with a lowest-height argmax; here the
    # arbitration is a pmin over hit heights — only the shard holding the
    # globally lowest hit contributes its value (heights are distinct across
    # shards: a given probe key has exactly one owner).
    best = jax.lax.pmin(height, AXIS)
    wins = found_local & (height == best)
    out, found = _combine_values(out, wins)
    # Stats correction: every shard bumped GETS/MISSES for the full batch and
    # HITS for its local hits. Rewrite so per-shard stats SUM to the truth:
    # shard 0 carries gets/misses, hits stay where they WON the arbitration.
    me = jax.lax.axis_index(AXIS)
    n_valid = (~is_invalid(keys)).sum(dtype=jnp.int32)
    local_hits = found_local.sum(dtype=jnp.int32)
    win_hits = wins.sum(dtype=jnp.int32)
    global_hits = found.sum(dtype=jnp.int32)
    fix = jnp.zeros((NSTATS,), jnp.int32)
    fix = fix.at[GETS].add(jnp.where(me == 0, 0, -n_valid))
    fix = fix.at[HITS].add(win_hits - local_hits)
    fix = fix.at[MISSES].add(
        jnp.where(me == 0, local_hits - global_hits, local_hits - n_valid)
    )
    # miss causes for the GLOBAL misses, on shard 0 (where the rewritten
    # gets/misses live): `evicted` if ANY shard's evicted-key sketch
    # remembers the base key (covers evict per-shard; pmax is the union)
    miss_glob = (~is_invalid(keys)) & ~found
    ev_glob = jax.lax.pmax(ev, AXIS) & miss_glob
    n_ev = ev_glob.sum(dtype=jnp.int32)
    n_miss = miss_glob.sum(dtype=jnp.int32)
    fix = fix.at[MISS_EVICTED].add(jnp.where(me == 0, n_ev, 0))
    fix = fix.at[MISS_COLD].add(jnp.where(me == 0, n_miss - n_ev, 0))
    st2 = dataclasses.replace(st2, stats=st2.stats + fix)
    return _restack(st2), out, found


# ---------------------------------------------------------------------------
# whole-state bodies (scans, repair, bloom export) — shared by both modes
# ---------------------------------------------------------------------------

def _find_anyway_body(config: KVConfig, n: int, state, keys):
    st = _unstack(state)
    vals, found, slot = kv_mod.find_anyway(st, config, keys)
    vals = jnp.where(found[:, None], vals, jnp.zeros_like(vals))
    me = jax.lax.axis_index(AXIS).astype(jnp.int32)
    shard = jnp.where(found, me, jnp.int32(-1))
    return (
        _restack(st),
        jax.lax.psum(vals, AXIS),
        jax.lax.pmax(found, AXIS),
        jax.lax.pmax(slot, AXIS),
        jax.lax.pmax(shard, AXIS),
    )


def _occupancy_body(config: KVConfig, n: int, state):
    st = _unstack(state)
    ops = get_index_ops(config.index.kind)
    flat_keys, _ = ops.scan(st.index)
    occ = (~is_invalid(flat_keys)).sum(dtype=jnp.int32)
    return _restack(st), occ[None]


def _recovery_body(config: KVConfig, n: int, state):
    st = _unstack(state)
    ops = get_index_ops(config.index.kind)
    if ops.recovery is not None:
        st = dataclasses.replace(st, index=ops.recovery(st.index))
    return _restack(st)


def _balloon_shrink_body(config: KVConfig, n: int, k: int, state):
    """Per-shard forced balloon-down (`tier.shrink` semantics: free rows
    park first, then the coldest live rows evict to legal misses whose
    entries go provably stale — the `miss_stale` taxonomy rung)."""
    st = _unstack(state)
    st = dataclasses.replace(st, pool=tier_mod.shrink(st.pool, k))
    return _restack(st)


def _balloon_grow_body(config: KVConfig, n: int, k: int, state):
    st = _unstack(state)
    st = dataclasses.replace(st, pool=tier_mod.grow(st.pool, k))
    return _restack(st)


def _packed_bloom_body(config: KVConfig, n: int, state):
    st = _unstack(state)
    packed = bloom_ops.to_packed_bits(st.bloom)
    return _restack(st), packed[None]


# ---------------------------------------------------------------------------
# serving-plane bodies (host-routed: batches arrive SHARD-MAJOR, already
# binned to their owners by `partitioning.ShardRouter`, so the per-shard
# program is exactly the single-chip program — no collectives at all).
# This is the dispatch the wire tier uses: routing is a pure host hash
# the messenger pays while it is already touching every request, pads
# are per-shard up the pow2 ladder, and results gather back to host
# once per phase (out_specs P(kv) → one device→host fetch per phase).
# ---------------------------------------------------------------------------


def _plane_insert_body(config: KVConfig, n: int, state, keys, values):
    st = _unstack(state)
    st2, res = kv_mod.insert(st, config, keys, values)
    return _restack(st2), res


def _plane_get_body(config: KVConfig, n: int, fused: bool, state, keys):
    # `fused` (static) selects the device-fused Pallas GET program
    # (ops/fused.py) per shard; False is today's composed chain,
    # bit-identical either way (the PMDFC_FUSED=off conformance bar)
    st = _unstack(state)
    st2, out, found = kv_mod._get_core_dispatch(st, config, keys,
                                                fused=fused)
    return _restack(st2), out, found


def _plane_get_ro_body(config: KVConfig, n: int, fused: bool, state, keys):
    """READ-ONLY lean GET: the state is an input only — no state output
    means XLA materializes no fresh copy of the per-shard table on
    platforms where donation is off (the jax 0.4.37 CPU rule), so the
    serving hot path pays O(batch) instead of O(table) per flush. The
    stats bumps the state-returning path would carry ride out as one
    per-shard int32[NSTATS] DELTA vector instead (folded into
    `ShardedKV._plane_stats` at fetch): with the miss-cause taxonomy the
    found mask alone can no longer reconstruct the cause split, and the
    device program is the one place every cause is already classified."""
    st = _unstack(state)
    st2, out, found = kv_mod._get_core_dispatch(st, config, keys,
                                                lean=True, fused=fused)
    return out, found, (st2.stats - st.stats)[None]


def _plane_delete_body(config: KVConfig, n: int, state, keys):
    st = _unstack(state)
    st2, hit = kv_mod.delete(st, config, keys)
    return _restack(st2), hit


# ---------------------------------------------------------------------------
# 2-D serving-plane bodies (replica lanes fused into the phase programs).
#
# Every lane holds a full copy of its shard's state, and every mutation
# (insert/delete/extent/balloon) applies identically on all lanes — so
# the ONLY way lanes can diverge is page-byte damage (a seeded corrupt
# drill, a real bit-flip): insert's control flow digests the INCOMING
# values, never stored pages, and the flat pool's GET reads are pure.
# The 2-D plane refuses tiered pools at construction to keep that
# invariant (tier promotion keys off the per-lane `found` mask, which
# would let a corrupt lane's placement drift for good).
#
# That invariant is what makes the hedged-read arbitration's cause
# accounting exact: a key one lane missed that ANOTHER lane served can
# only be a digest refusal on the missing lane — all index/placement
# metadata is lane-identical, so anything except the digest gate misses
# on every lane at once.
#
# The legacy host verbs (ShardedKV.get / a2a dispatch) stay SAFE on a
# 2-D mesh but are not lane-arbitrated: each lane's digest gate zeroes
# its own refusals (never wrong bytes), and the host fetch reads one
# lane's buffer — a damaged lane answers a legal miss where the plane
# verbs would have hedged to a sibling. The serving path is the plane. The canonical per-shard stats delta is lane 0's
# with each rescued key converted miss_digest -> hit (psum'd so every
# lane agrees bit-for-bit), keeping `misses == Σ causes` exact on every
# surface while per-lane served/refused counts ride out separately for
# the `mesh.replica{r}_*` attribution families.
# ---------------------------------------------------------------------------


def _replica_pick0(x: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Lane-0's value, agreed on every lane (bool via pmax, else psum)."""
    if x.dtype == jnp.bool_:
        return jax.lax.pmax(x & (r == 0), RAXIS)
    return jax.lax.psum(jnp.where(r == 0, x, jnp.zeros_like(x)), RAXIS)


def _replica_merge(out: jnp.ndarray, found: jnp.ndarray, nrep: int):
    """First-validated-lane-wins arbitration over the replica axis:
    (out_g, found_g, wins, r) — `wins` marks the rows THIS lane served
    (lowest lane index among the lanes whose digest-gated row answered)."""
    r = jax.lax.axis_index(RAXIS).astype(jnp.int32)
    winner = jax.lax.pmin(jnp.where(found, r, jnp.int32(nrep)), RAXIS)
    wins = found & (r == winner)
    out_g = jax.lax.psum(
        jnp.where(wins[:, None], out, jnp.zeros_like(out)), RAXIS)
    found_g = jax.lax.pmax(found, RAXIS)
    return out_g, found_g, wins, r


def _replica_canon_delta(delta: jnp.ndarray, found: jnp.ndarray,
                         found_g: jnp.ndarray, r: jnp.ndarray):
    """Canonical per-shard stats delta: lane 0's, with every rescued key
    (missed here, served by another lane — always a digest refusal, see
    the module-section note) converted miss_digest -> hit. psum'd so
    all lanes return the identical vector."""
    rescued = (found_g & ~found).sum(dtype=jnp.int32)
    fix = jnp.zeros((NSTATS,), jnp.int32)
    fix = fix.at[HITS].add(rescued)
    fix = fix.at[MISSES].add(-rescued)
    fix = fix.at[MISS_DIGEST].add(-rescued)
    return _replica_pick0(delta + fix, r)


def _plane_insert2_body(config: KVConfig, n: int, nrep: int, state, keys,
                        values):
    # each lane applies the same inserts to its copy: ONE launch
    # replicates nrep ways (vs nrep host TCP loops). Results are
    # lane-identical by the control-purity invariant; lane-0 arbitration
    # is belt-and-braces so a damaged lane can never speak for the plane.
    st = _unstack(state)
    st2, res = kv_mod.insert(st, config, keys, values)
    r = jax.lax.axis_index(RAXIS).astype(jnp.int32)
    res = jax.tree.map(lambda x: _replica_pick0(x, r), res)
    return _restack(st2), res


def _plane_get_ro2_body(config: KVConfig, n: int, nrep: int, fused: bool,
                        state, keys):
    """Read-only hedged replica-shard GET: every lane probes its copy,
    the first lane whose digest-validated row answers wins, and the
    canonical stats delta rides out like the 1-D read-only path. The
    extra [1, 1, 2] output is this lane's (served, digest_refused)
    attribution pair, sharded P(kv, replica) -> [S, R, 2] host-side."""
    st = _unstack(state)
    st2, out, found = kv_mod._get_core_dispatch(st, config, keys,
                                                lean=True, fused=fused)
    delta = st2.stats - st.stats
    out_g, found_g, wins, r = _replica_merge(out, found, nrep)
    canon = _replica_canon_delta(delta, found, found_g, r)
    lane = jnp.stack([wins.sum(dtype=jnp.int32),
                      delta[MISS_DIGEST]])[None, None]
    return out_g, found_g, canon[None], lane


def _plane_get2_body(config: KVConfig, n: int, nrep: int, fused: bool,
                     state, keys):
    """Counting-path twin of `_plane_get_ro2_body` (hotness bookkeeping
    on): the canonical delta REPLACES each lane's own stats bump so the
    stats leaf stays lane-identical (any lane's copy is the truth)."""
    st = _unstack(state)
    st2, out, found = kv_mod._get_core_dispatch(st, config, keys,
                                                lean=False, fused=fused)
    delta = st2.stats - st.stats
    out_g, found_g, wins, r = _replica_merge(out, found, nrep)
    canon = _replica_canon_delta(delta, found, found_g, r)
    st2 = dataclasses.replace(st2, stats=st.stats + canon)
    lane = jnp.stack([wins.sum(dtype=jnp.int32),
                      delta[MISS_DIGEST]])[None, None]
    return _restack(st2), out_g, found_g, lane


def _plane_delete2_body(config: KVConfig, n: int, nrep: int, state, keys):
    st = _unstack(state)
    st2, hit = kv_mod.delete(st, config, keys)
    return _restack(st2), jax.lax.pmax(hit, RAXIS)


def _replica_repair_body(config: KVConfig, n: int, nrep: int, state):
    """Device-side anti-entropy compare-and-copy over the replica axis:
    each lane digests its own pool rows against the (lane-identical)
    digest sidecar; a row whose bytes fail on THIS lane but validate on
    another copies the lowest validating lane's bytes — one collective
    pass replaces the host repair loop's per-key fetch/verify/re-put.
    Returns this lane's repaired-row count ([1, 1] -> [S, R])."""
    st = _unstack(state)
    pool = st.pool
    r = jax.lax.axis_index(RAXIS).astype(jnp.int32)
    digs = pagepool.page_digest(pool.pages)
    ok = digs == pool.sums
    donor = jax.lax.pmin(jnp.where(ok, r, jnp.int32(nrep)), RAXIS)
    need = ~ok & (donor < nrep)
    donor_pages = jax.lax.psum(
        jnp.where((r == donor)[:, None], pool.pages,
                  jnp.zeros_like(pool.pages)), RAXIS)
    pages = jnp.where(need[:, None], donor_pages, pool.pages)
    st = dataclasses.replace(
        st, pool=dataclasses.replace(pool, pages=pages))
    return _restack(st), need.sum(dtype=jnp.int32)[None, None]


def _corrupt_lane_body(config: KVConfig, n: int, nrep: int, lane: int,
                       state):
    """Seeded fault injection for the replica-hedged drills: XOR every
    pool page word on ONE lane (digest sidecars untouched, so the lane's
    rows stop validating). Control state never diverges — exactly the
    damage class the arbitration and repair programs own."""
    st = _unstack(state)
    r = jax.lax.axis_index(RAXIS).astype(jnp.int32)
    flip = jnp.where(r == lane, jnp.uint32(0x5A5A5A5A), jnp.uint32(0))
    st = dataclasses.replace(
        st, pool=dataclasses.replace(st.pool,
                                     pages=st.pool.pages ^ flip))
    return _restack(st)


class PlaneHandle:
    """One launched mesh phase: device futures plus the host-side read-
    back that reorders results to request order.

    `fetch()` blocks on the device program (JAX async dispatch pays
    compute+transfer here, not at launch) — the launch/finalize split
    the serving drivers use to overlap flush N+1's dispatch with flush
    N's results. `counts` is the per-shard routed-op vector (telemetry
    attribution: which shards this phase actually touched).
    `t_launch_ns` stamps the dispatch so the device-time profiler can
    split launch-to-fetch dispatch gap from time blocked in the fetch
    (`runtime/profiler.py`)."""

    __slots__ = ("_fetch", "b", "counts", "t_launch_ns")

    def __init__(self, fetch, b: int, counts=None):
        self._fetch = fetch
        self.b = b
        self.counts = counts
        self.t_launch_ns = time.monotonic_ns()

    def fetch(self):
        return self._fetch()


class PlaneGets:
    """One fetched GET phase: request-ordered found mask over ROUTED-LANE
    page storage.

    The full request-order page matrix is never materialized unless a
    caller asks (`dense()`): the wire tier only ever ships HIT rows per
    connection slice, so `hit_rows(lo, hi)` gathers exactly those rows
    straight out of the routed buffer — one fancy-index per reply frame
    instead of an O(batch × page) scatter per flush plus a second gather
    per frame."""

    __slots__ = ("found", "_rb", "_routed", "lane_served", "lane_refused")

    def __init__(self, rb: pt.RoutedBatch, routed_pages, found,
                 lane_served=None, lane_refused=None):
        self.found = found          # bool[b], request order
        self._rb = rb
        self._routed = routed_pages  # [n*wl, W] routed-lane order
        # per-replica-lane attribution for THIS phase (2-D planes only):
        # rows served per lane / digest refusals per lane, summed over
        # shards — the `mesh.replica{r}_*` telemetry families' source
        self.lane_served = lane_served    # int64[R] | None
        self.lane_refused = lane_refused  # int64[R] | None

    def hit_rows(self, lo: int = 0, hi: int | None = None) -> np.ndarray:
        """Contiguous page rows for the HIT requests in [lo, hi)."""
        hi = len(self.found) if hi is None else hi
        sel = self._rb.pos[lo:hi][self.found[lo:hi]]
        return np.ascontiguousarray(np.asarray(self._routed)[sel],
                                    np.uint32)

    def dense(self) -> np.ndarray:
        """Full request-order [b, W] matrix (`kv.KV.get` out semantics:
        read the found mask before trusting a row)."""
        return self._rb.scatter(np.asarray(self._routed))


# ---------------------------------------------------------------------------
# host-facing wrapper
# ---------------------------------------------------------------------------

# serializes donating dispatches against state readers — shared with kv.KV
_locked = kv_mod._locked


class ShardedKV:
    """`kv.KV`-shaped host API over mesh-sharded state.

    State layout: every `KVState` leaf gets a leading `[n_shards]` axis with
    sharding `P("kv")`. Request batches are sharded `P("kv")` on the batch
    axis under ``dispatch="a2a"`` (each shard routes its slice), replicated
    `P()` under ``dispatch="broadcast"``.
    """

    def __init__(self, config: KVConfig | None = None,
                 mesh: Mesh | None = None, dispatch: str = "a2a",
                 lrfu_stats: bool = False, plane_pad_floor: int = 8,
                 axis_rules=None):
        if dispatch not in ("a2a", "broadcast"):
            raise ValueError(f"unknown dispatch {dispatch!r}")
        self.config = config or KVConfig()
        self.mesh = mesh or make_mesh()
        if AXIS not in self.mesh.axis_names:
            raise ValueError(
                f"mesh axes {tuple(self.mesh.axis_names)} lack the "
                f"{AXIS!r} axis")
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.n_shards = shape[AXIS]
        # replica lanes (2-D mesh): state replicated along RAXIS, GET
        # arbitration + repair collectives over it. Tiered pools are
        # refused — tier placement keys off the per-lane found mask, so
        # a damaged lane's hot/cold layout would drift for good and the
        # rescued-implies-digest cause accounting would stop being exact
        # (see the 2-D bodies' section note).
        self.n_replicas = shape.get(RAXIS, 1)
        if self.n_replicas > 1 and \
                kv_mod._tier_cfg_at_init(self.config) is not None:
            raise ValueError(
                "the 2-D replica plane does not compose with the tiered "
                "pool yet — run the tier on a 1-D mesh (host ReplicaGroup "
                "replication) or drop tier= from the KVConfig")
        self.dispatch = dispatch
        self._batches_since_touch = 0
        # device-fused GET selection (ops/fused.py), resolved lazily per
        # instance exactly like kv.KV._fused_on — every plane GET body
        # threads it as a static arg, so fused and composed traces get
        # distinct `_wrap` cache entries and recompile counters
        self._fused: bool | None = None
        # logical-axis rules -> specs/shardings (partitioning.py): ONE
        # vocabulary for init/restore placement and every shard_map's
        # in/out specs, validated against the live mesh up front so a
        # rule naming a missing mesh axis fails construction, not
        # silently replicates. 2-D meshes pick up the grown
        # MESH2D_AXIS_RULES table (the replica_lane rule the per-lane
        # attribution outputs shard over).
        self._rules = pt.rules_for_mesh(self.mesh, axis_rules)
        pt.validate_rules(self._rules, self.mesh)
        self._specs = pt.state_specs(self.config, self._rules)
        # serving-plane host router (the NUMA-queue dispatch analog) +
        # the host-side stats plane for READ-ONLY get programs (those
        # return no state, so their gets/hits/misses/corrupt bumps are
        # reconstructed here; every stats surface merges this in)
        self._router = pt.ShardRouter(self.n_shards,
                                      pad_floor=plane_pad_floor)
        self._plane_stats = np.zeros((self.n_shards, NSTATS), np.int64)
        # per-replica-lane totals (served / digest_refused / repaired):
        # the host accumulation behind `replica_report()` and the
        # `mesh.replica{r}_*` telemetry families (2-D planes only)
        self._lane_stats = np.zeros((self.n_replicas, 3), np.int64)
        # Optional per-shard LRFU load plane — the `Metric{atime, crf}` /
        # `freq` / `segments_in_node` stats of the reference's NUMA path
        # (`server/CCEH_hybrid.h:202-206`, gated by -DLRFU there and by
        # this flag here; the reference leaves them stubs). Granularity is
        # the shard (the NUMA-node analog): atime = last batch tick that
        # routed work to the shard, crf = exponentially-decayed combined
        # recency-frequency (F(x) = 0.5^(lambda*x), the LRFU paper's
        # weighting the reference's Metric comment cites), freq = total
        # requests routed. Host-side bookkeeping off the routing hash —
        # zero cost on the device path, like the reference's CPU-side
        # stats.
        self.lrfu_stats = lrfu_stats
        self.lrfu_lambda = 0.1
        self._lrfu = np.zeros((self.n_shards, 2))  # [atime, crf]
        self._freq = np.zeros((self.n_shards,), np.int64)
        self._lrfu_tick = 0
        self.state = self._init_sharded()
        from pmdfc_tpu.runtime import sanitizer as san

        # serializes donating dispatches against state readers (stats,
        # save, bloom pack) — a reader racing a donation touches deleted
        # buffers; same discipline as kv.KV
        # guarded-by: state, _jits, _lrfu, _freq, _lrfu_tick,
        # guarded-by: _batches_since_touch, _plane_stats, _lane_stats,
        # guarded-by: dir_epoch, _mut_seq, _fastview
        self._lock = san.rlock("ShardedKV._lock")
        self._jits: dict = {}
        # one-sided fast-path surface (same contract as kv.KV): the
        # directory epoch bumps on STRUCTURAL invalidation (delete,
        # balloon, restore/reshard, recovery), the mutation seq keys the
        # cached host mirror; randomized start so a restored/swapped
        # instance never collides with a client's cached epoch
        import os as _os

        self.dir_epoch = int.from_bytes(_os.urandom(4), "little") | 1
        self._mut_seq = 0
        self._fastview = None
        # incremental-snapshot chain cursor (same contract as kv.KV:
        # id/seq/prev_crc + the base dirty basis the next delta diffs
        # against, over the FLAT row space — shard axis folded in)
        self._chain: dict | None = None

    def _eval_struct(self):
        return jax.eval_shape(lambda: kv_mod.init(self.config))

    def _init_sharded(self) -> KVState:
        n = self.n_shards

        def stacked_init():
            st = kv_mod.init(self.config)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n, *x.shape)), st
            )

        out_shardings = pt.state_shardings(self.config, self.mesh,
                                           self._rules)
        return jax.jit(stacked_init, out_shardings=out_shardings)()

    # caller-holds: _lock
    def _wrap(self, name, body, n_in, n_out, *, data_spec=None, static=(),
              cache_key=(), out_data_specs=None, state_out=True):
        """shard_map + jit a body; cache per (name, static args, cache key).

        `state_out=False` wraps a READ-ONLY body (no state in the
        outputs): the state is a plain input, never donated — the
        serving plane's lean-GET form, which skips the whole-table copy
        non-donating platforms otherwise pay per dispatch."""
        key = (name, *static, *cache_key)
        if key in self._jits:
            return self._jits[key]
        # recompile tracker (runtime/telemetry.py): a miss here IS a
        # program build the process pays — a cold pad-ladder rung or a
        # drifting shape surfaces as a named `recompile.plane.*` storm
        from pmdfc_tpu.runtime import telemetry as tele

        first = tele.track_program(f"plane.{name}", key, detail=key)
        ds = data_spec if data_spec is not None else P()
        # partitioning rules -> specs: the same vocabulary init/restore
        # placement uses, so a 2-D-mesh rules change reshapes every
        # program here with no rewrite
        spec_state = self._specs
        in_specs = (spec_state,) + tuple(ds for _ in range(n_in))
        if out_data_specs is None:
            out_data_specs = tuple(ds for _ in range(n_out))
        if not state_out:
            fn = jax.jit(
                _shard_map(
                    partial(body, self.config, self.n_shards, *static),
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=tuple(out_data_specs),
                ),
            )
            self._jits[key] = fn
            # static cost capture rides the recompile-tracker seam: the
            # first call of a fresh signature lowers once for FLOPs /
            # bytes gauges; the cached entry stays the bare jit fn
            return profiler.cost_probe(f"plane.{name}", fn) if first else fn
        # bare state out (no tuple) when the body returns only state
        out_specs = (
            spec_state if n_out == 0 and not out_data_specs
            else (spec_state,) + tuple(out_data_specs)
        )
        # Donate the sharded state: every body passes it through (or
        # replaces it) and every call site reassigns self.state, so the
        # input buffers are dead after the call — without donation XLA
        # materializes a fresh copy of the whole sharded table per op
        # (measured ~160 ms per 256 MB on the host path; same defect the
        # KV wrapper had). External references to .state are invalidated
        # by the next op — snapshot via save()/stats() accessors instead.
        #
        # CPU exception: donated shard_map programs on the forced-N-device
        # CPU platform intermittently SEGFAULT jaxlib 0.9's compiler deep
        # into large test runs (five full-suite crashes, onset exactly at
        # this change, never reproducible standalone). The copy tax is a
        # test-environment cost only — real meshes are TPU — so donation
        # keys off the platform. PMDFC_SHARD_DONATE=1 forces it anywhere.
        donate = shard_donate()
        fn = jax.jit(
            _shard_map(
                partial(body, self.config, self.n_shards, *static),
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
            ),
            donate_argnums=(0,) if donate else (),
        )
        self._jits[key] = fn
        return profiler.cost_probe(f"plane.{name}", fn) if first else fn

    def _data_call(self, name, body_a2a, body_bcast, n_in, n_out, w):
        """Pick the dispatch mode's body + specs for a data batch of width w."""
        if self.dispatch == "a2a":
            bl = w // self.n_shards
            c_pair = pair_capacity(bl, self.n_shards)
            return self._wrap(
                name + "_a2a", body_a2a, n_in, n_out,
                data_spec=P(AXIS), static=(c_pair,), cache_key=(w,),
            )
        return self._wrap(name, body_bcast, n_in, n_out)

    # caller-holds: _lock
    def _lrfu_touch(self, keys: np.ndarray) -> None:
        """Fold one routed batch into the per-shard LRFU plane (no-op
        unless `lrfu_stats`): decay each touched shard's crf by the time
        since its own atime, add this batch's request count, stamp
        atime."""
        if not self.lrfu_stats:
            return
        self._lrfu_tick += 1
        counts = np.bincount(self.node_of(keys), minlength=self.n_shards)
        touched = counts > 0
        dt = self._lrfu_tick - self._lrfu[:, 0]
        decay = np.power(0.5, self.lrfu_lambda * dt)
        self._lrfu[:, 1] = np.where(
            touched, self._lrfu[:, 1] * decay + counts, self._lrfu[:, 1]
        )
        self._lrfu[:, 0] = np.where(touched, self._lrfu_tick,
                                    self._lrfu[:, 0])
        self._freq += counts

    # -- ops (numpy in/out, like kv.KV) --

    @_locked
    def insert(self, keys: np.ndarray, values: np.ndarray):
        self._lrfu_touch(keys)
        keys, values, b, w = self._pad(keys, values)
        fn = self._data_call("insert", _a2a_insert_body, _insert_body,
                             2, 1, w)
        self.state, res = fn(self.state, keys, values)
        self._mut_seq += 1
        return jax.tree.map(lambda x: self._fetch(x)[:b], res)

    # caller-holds: _lock
    def _touch_due(self) -> bool:
        """Sampled hotness cadence, same contract as `kv.KV._touch_due`:
        one batch in `touch_sample_every` pays the counting path (tiered
        pools count as touch-tracking — migration rides that path)."""
        from pmdfc_tpu.models.base import get_index_ops

        every = self.config.index.touch_sample_every
        if get_index_ops(self.config.index.kind).touch is None \
                and not isinstance(self.state.pool, tier_mod.TierState):
            return False
        if every <= 1:
            return True
        self._batches_since_touch += 1
        if self._batches_since_touch >= every:
            self._batches_since_touch = 0
            return True
        return False

    def _fused_on(self) -> bool:
        """Lazy fused/composed GET decision, same contract as
        `kv.KV._fused_on` (PMDFC_FUSED / KVConfig.fused_get; 'auto' =
        TPU only; unsupported configs never fuse)."""
        if self._fused is None:
            from pmdfc_tpu.ops import fused as fused_ops

            self._fused = fused_ops.resolve(self.config)
        return self._fused

    @_locked
    def get(self, keys: np.ndarray):
        self._lrfu_touch(keys)
        keys, _, b, w = self._pad(keys)
        if self._touch_due():
            fn = self._data_call("get", _a2a_get_body, _get_body, 1, 2, w)
        else:
            fn = self._data_call("get_lean", _a2a_get_lean_body,
                                 _get_lean_body, 1, 2, w)
        self.state, out, found = fn(self.state, keys)
        return self._fetch(out)[:b], self._fetch(found)[:b]

    @_locked
    def delete(self, keys: np.ndarray):
        self._lrfu_touch(keys)
        keys, _, b, w = self._pad(keys)
        if self.dispatch == "a2a":
            # Deletes use EXACT per-pair buckets (c_pair = full local width):
            # a bucket-overflow drop is legal for puts/gets (miss-is-legal)
            # but a silently failed delete would leave a stale value that
            # later gets serve as a hit — invalidation must be loss-free.
            bl = w // self.n_shards
            fn = self._wrap("delete_a2a", _a2a_delete_body, 1, 1,
                            data_spec=P(AXIS), static=(bl,), cache_key=(w,))
        else:
            fn = self._wrap("delete", _delete_body, 1, 1)
        self.state, hit = fn(self.state, keys)
        self._mut_seq += 1
        self.dir_epoch += 1
        return self._fetch(hit)[:b]

    @_locked
    def insert_extent(self, key, value, length: int):
        fn = self._wrap("insert_extent", _insert_extent_body, 3, 2)
        # plain numpy inputs, NOT jnp.asarray: the body's in_specs are
        # replicated (P()), and an uncommitted host array satisfies that
        # on a multi-process mesh too, where a locally-committed device
        # array would be rejected (code-review r5 finding)
        self.state, res, uncovered = fn(
            self.state,
            np.asarray(key, np.uint32),
            np.asarray(value, np.uint32),
            np.uint32(length),
        )
        self._mut_seq += 1
        return (jax.tree.map(lambda x: self._fetch(x), res),
                int(self._fetch(uncovered)))

    @_locked
    def get_extent(self, keys: np.ndarray):
        keys, _, b, w = self._pad(keys)
        fn = self._wrap("get_extent", _get_extent_body, 1, 2)
        self.state, out, found = fn(self.state, keys)
        return self._fetch(out)[:b], self._fetch(found)[:b]

    # -- serving-plane verbs (host-routed shard-major dispatch) --
    #
    # The wire tier's phase programs: `partitioning.ShardRouter` bins the
    # fused batch by owning shard (stable order, loss-free — unlike the
    # a2a buckets there is no overflow class), pads PER SHARD up the pow2
    # ladder, and each launch returns a `PlaneHandle` whose fetch()
    # blocks on the device (JAX async dispatch: compute+transfer are
    # paid at fetch, not launch — the overlap the serving drivers use).

    @_locked
    def plane_insert(self, keys: np.ndarray,
                     values: np.ndarray) -> PlaneHandle:
        self._lrfu_touch(keys)
        rb = self._router.build(keys, values)
        if rb.b == 0:
            return PlaneHandle(lambda: None, 0, rb.counts)
        if self.n_replicas > 1:
            # one launch writes every replica lane (vs rf host loops)
            fn = self._wrap("plane_insert2", _plane_insert2_body, 2, 1,
                            data_spec=P(AXIS), static=(self.n_replicas,))
        else:
            fn = self._wrap("plane_insert", _plane_insert_body, 2, 1,
                            data_spec=P(AXIS))
        self.state, res = fn(self.state, rb.keys, rb.values)
        self._mut_seq += 1

        def fetch():
            return jax.tree.map(lambda x: rb.scatter(self._fetch(x)), res)

        return PlaneHandle(fetch, rb.b, rb.counts)

    @_locked
    def plane_get(self, keys: np.ndarray) -> PlaneHandle:
        self._lrfu_touch(keys)
        rb = self._router.build(keys)
        if rb.b == 0:
            vw = (self.config.page_words if self.config.paged else 2)
            empty = PlaneGets(rb, np.zeros((0, vw), np.uint32),
                              np.zeros(0, bool))
            return PlaneHandle(lambda: empty, 0, rb.counts)
        lane = None
        if self.n_replicas > 1:
            # hedged replica-shard read: every lane probes its copy, the
            # first digest-validated lane wins, per-lane attribution
            # rides out as a [S, R, 2] (served, refused) matrix
            nrep = self.n_replicas
            if self._touch_due():
                fn = self._wrap(
                    "plane_get2", _plane_get2_body, 1, 3,
                    data_spec=P(AXIS), static=(nrep, self._fused_on()),
                    out_data_specs=(P(AXIS), P(AXIS), self._lane_spec()))
                self.state, out, found, lane = fn(self.state, rb.keys)
                delta = None
            else:
                fn = self._wrap(
                    "plane_get_ro2", _plane_get_ro2_body, 1, 4,
                    data_spec=P(AXIS), static=(nrep, self._fused_on()),
                    state_out=False,
                    out_data_specs=(P(AXIS), P(AXIS), P(AXIS),
                                    self._lane_spec()))
                out, found, delta, lane = fn(self.state, rb.keys)
        elif self._touch_due():
            # counting path (tier migration / hotring heat): state
            # mutates, stats ride the device vector as usual
            fn = self._wrap("plane_get", _plane_get_body, 1, 2,
                            data_spec=P(AXIS),
                            static=(self._fused_on(),))
            self.state, out, found = fn(self.state, rb.keys)
            delta = None
        else:
            # read-only path: no state output, no donation, no table
            # copy — the per-shard stats delta (causes included) rides
            # out as a small vector and folds into the host plane
            fn = self._wrap("plane_get_ro", _plane_get_ro_body, 1, 3,
                            data_spec=P(AXIS), state_out=False,
                            static=(self._fused_on(),))
            out, found, delta = fn(self.state, rb.keys)

        def fetch():
            f_routed = self._fetch(found)
            if delta is not None:
                self._plane_note_get(self._fetch(delta))
            ls = lr = None
            if lane is not None:
                lanes = np.asarray(self._fetch(lane), np.int64)
                ls = lanes[..., 0].sum(axis=0)  # served per lane
                lr = lanes[..., 1].sum(axis=0)  # digest refusals per lane
                self._note_lanes(ls, lr)
            return PlaneGets(rb, self._fetch(out), rb.scatter(f_routed),
                             ls, lr)

        return PlaneHandle(fetch, rb.b, rb.counts)

    @_locked
    def plane_warm_get(self, keys: np.ndarray) -> None:
        """Warm BOTH get-phase programs (read-only AND counting) at this
        batch's routed width. `plane_get` picks one per call by the
        sampled touch cadence, so a warmup loop riding it would leave
        the other program to compile mid-flush at serve time; this
        traces each explicitly WITHOUT advancing `_batches_since_touch`
        (warmup must not shift the serving cadence)."""
        rb = self._router.build(keys)
        if self.n_replicas > 1:
            fn_ro = self._wrap(
                "plane_get_ro2", _plane_get_ro2_body, 1, 4,
                data_spec=P(AXIS),
                static=(self.n_replicas, self._fused_on()),
                state_out=False,
                out_data_specs=(P(AXIS), P(AXIS), P(AXIS),
                                self._lane_spec()))
        else:
            fn_ro = self._wrap("plane_get_ro", _plane_get_ro_body, 1, 3,
                               data_spec=P(AXIS), state_out=False,
                               static=(self._fused_on(),))
        out = fn_ro(self.state, rb.keys)
        profiler.block_ready(out)  # warmup sync: sanctioned, unattributed
        if get_index_ops(self.config.index.kind).touch is not None \
                or isinstance(self.state.pool, tier_mod.TierState):
            if self.n_replicas > 1:
                fn = self._wrap(
                    "plane_get2", _plane_get2_body, 1, 3,
                    data_spec=P(AXIS),
                    static=(self.n_replicas, self._fused_on()),
                    out_data_specs=(P(AXIS), P(AXIS), self._lane_spec()))
                self.state, out, found, _lane = fn(self.state, rb.keys)
            else:
                fn = self._wrap("plane_get", _plane_get_body, 1, 2,
                                data_spec=P(AXIS),
                                static=(self._fused_on(),))
                self.state, out, found = fn(self.state, rb.keys)
            profiler.block_ready(found)

    @_locked
    def plane_delete(self, keys: np.ndarray) -> PlaneHandle:
        self._lrfu_touch(keys)
        rb = self._router.build(keys)
        if rb.b == 0:
            return PlaneHandle(lambda: np.zeros(0, bool), 0, rb.counts)
        if self.n_replicas > 1:
            # one launch deletes on every replica lane (loss-free: no
            # lane can keep a value the tombstone missed)
            fn = self._wrap("plane_delete2", _plane_delete2_body, 1, 1,
                            data_spec=P(AXIS), static=(self.n_replicas,))
        else:
            fn = self._wrap("plane_delete", _plane_delete_body, 1, 1,
                            data_spec=P(AXIS))
        self.state, hit = fn(self.state, rb.keys)
        self._mut_seq += 1
        self.dir_epoch += 1

        def fetch():
            return rb.scatter(self._fetch(hit))

        return PlaneHandle(fetch, rb.b, rb.counts)

    @_locked
    def plane_get_extent(self, keys: np.ndarray) -> PlaneHandle:
        """Extent covers are deterministically replicated, so this phase
        is the broadcast body launched async (counts=None: every shard
        probes the full batch — there is no per-shard attribution)."""
        keys_p, _, b, w = self._pad(keys)
        fn = self._wrap("get_extent", _get_extent_body, 1, 2)
        self.state, out, found = fn(self.state, keys_p)

        def fetch():
            return self._fetch(out)[:b], self._fetch(found)[:b]

        return PlaneHandle(fetch, b, None)

    def _plane_note_get(self, delta: np.ndarray) -> None:
        """Fold one read-only GET's device-computed per-shard stats
        delta ([n, NSTATS]: gets/hits/misses + the full miss-cause
        split + corrupt_pages) into `_plane_stats`. INVALID keys —
        client sentinels and pad lanes — counted nothing on device (the
        single-device stat contract), so the delta IS the truth; no
        host-side reconstruction that could drift from the device
        classification."""
        with self._lock:
            self._plane_stats += np.asarray(delta, np.int64)

    # caller-holds: <none> (takes _lock itself — fetch closures and the
    # repair verb both land here; _lock is reentrant)
    def _lane_spec(self):
        """PartitionSpec for per-replica-lane outputs — derived from the
        MESH2D rules' `replica_lane` line, the one-rules-line promise."""
        return pt.spec_for((pt.SHARD, pt.REPLICA_LANE), self._rules)

    def _note_lanes(self, served, refused, repaired=None) -> None:
        """Fold one phase's per-lane attribution into the cumulative
        plane (`replica_report()` / `mesh.replica{r}_*` source)."""
        with self._lock:
            self._lane_stats[:, 0] += np.asarray(served, np.int64)
            self._lane_stats[:, 1] += np.asarray(refused, np.int64)
            if repaired is not None:
                self._lane_stats[:, 2] += np.asarray(repaired, np.int64)

    def replica_report(self) -> dict | None:
        """Per-replica-lane attribution totals (None on 1-D meshes):
        rows each lane served (won the hedged-read arbitration), rows
        each lane's digest gate refused, rows repaired onto each lane by
        the device-side anti-entropy pass."""
        if self.n_replicas <= 1:
            return None
        with self._lock:
            ls = self._lane_stats.copy()
        return {
            "n_replicas": self.n_replicas,
            "served": [int(x) for x in ls[:, 0]],
            "digest_refused": [int(x) for x in ls[:, 1]],
            "repaired": [int(x) for x in ls[:, 2]],
        }

    @_locked
    def replica_repair(self) -> int:
        """Device-side anti-entropy pass over the replica axis: one
        collective compare-and-copy program re-syncs every pool row
        whose bytes fail their digest on some lane but validate on
        another (see `_replica_repair_body`). Returns total rows
        repaired across all lanes; 0 on 1-D meshes and unpaged state
        (nothing to compare)."""
        if self.n_replicas <= 1 or not self.config.paged:
            return 0
        fn = self._wrap("replica_repair", _replica_repair_body, 0, 1,
                        static=(self.n_replicas,),
                        out_data_specs=(self._lane_spec(),))
        self.state, rep = fn(self.state)
        per = np.asarray(self._fetch(rep), np.int64).sum(axis=0)  # [R]
        zero = np.zeros_like(per)
        self._note_lanes(zero, zero, per)
        self._mut_seq += 1
        return int(per.sum())

    @_locked
    def corrupt_replica_lane(self, lane: int) -> None:
        """Seeded fault injection for drills/chaos ONLY: XOR every pool
        page word on one replica lane (digests untouched, so the lane's
        rows stop validating and the hedged read must route around it).
        The damage class the plane owns — control state stays
        lane-identical."""
        if self.n_replicas <= 1 or not self.config.paged:
            raise ValueError(
                "corrupt_replica_lane needs a paged 2-D replica plane")
        if not 0 <= lane < self.n_replicas:
            raise ValueError(f"lane {lane} not in [0, {self.n_replicas})")
        fn = self._wrap("corrupt_lane", _corrupt_lane_body, 0, 0,
                        static=(self.n_replicas, lane))
        self.state = fn(self.state)
        self._mut_seq += 1

    # -- scans / maintenance (full `IKV` surface parity) --

    @_locked
    def find_anyway(self, keys: np.ndarray):
        """Full-table scan across every shard (ref `FindAnyway`,
        `server/IKV.h:18`). Returns (vals, found, slot, shard)."""
        keys, _, b, w = self._pad(keys)
        fn = self._wrap("find_anyway", _find_anyway_body, 1, 4)
        self.state, vals, found, slot, shard = fn(self.state, keys)
        return (self._fetch(vals)[:b], self._fetch(found)[:b],
                self._fetch(slot)[:b], self._fetch(shard)[:b])

    @_locked
    def utilization(self) -> float:
        fn = self._wrap("occupancy", _occupancy_body, 0, 1,
                        out_data_specs=(P(AXIS),))
        self.state, occ = fn(self.state)
        return float(self._fetch(occ).sum() / self.capacity())

    @_locked
    def recovery(self) -> bool:
        """Per-shard post-restart repair (ref `CCEH::Recovery`)."""
        fn = self._wrap("recovery", _recovery_body, 0, 0)
        out = fn(self.state)
        self.state = out
        self._mut_seq += 1
        self.dir_epoch += 1
        return True

    # -- one-sided fast-path surface (`kv.KV` contract at mesh scale) --

    @_locked
    def fast_view(self):
        """Stacked host mirror of every shard's (pages, sums) —
        `FastView` with a leading shard axis, cached per mutation seq.
        On the forced-host CPU mesh the global arrays are addressable
        and the mirror is a plain fetch; re-mirroring happens only when
        a mutating dispatch landed since the last fast read."""
        if not self.config.paged or self.n_replicas > 1:
            # 2-D planes refuse the one-sided mirror: a host fetch of a
            # replicated-over-lanes array reads SOME lane's buffer, and
            # a corrupted lane's pages with intact sidecar sums would
            # VALIDATE — the exact wrong-bytes class the hedged verb
            # path exists to prevent. The server then withholds the
            # FAST_FLAG ack and clients keep the (lane-arbitrated) verbs.
            return None
        fv = self._fastview
        if fv is not None and fv.seq == self._mut_seq \
                and fv.epoch == self.dir_epoch:
            return fv
        pool = self.state.pool
        pages = self._fetch(pool.pages)
        sums = self._fetch(pool.sums)
        if shard_donate():
            # donated shard_map dispatches scribble on input buffers —
            # the mirror must own its bytes (same predicate as _wrap,
            # by construction: `shard_donate` is the one copy)
            pages, sums = np.array(pages), np.array(sums)
        live = None
        if isinstance(pool, tier_mod.TierState):
            # per-shard row liveness (see kv.KV.fast_view): the guard
            # against vacated-by-promotion cold rows whose pages/sums
            # were never scrubbed. Fancy assignment copies, so `live`
            # owns its bytes regardless of donation.
            h = pool.hfree.shape[-1]
            live = np.ones(pages.shape[:2], bool)
            live[:, h:] = self._fetch(pool.live)
        fv = kv_mod.FastView(self.dir_epoch, self._mut_seq, pages, sums,
                             live)
        self._fastview = fv
        return fv

    @_locked
    def directory_snapshot(self, max_entries: int = 1 << 20) -> dict | None:
        """Compact key→(shard, row, digest) directory across every
        shard: each shard's index is scanned host-side
        (`kv.directory_entries` over the per-shard state slice, the
        reshard-replay fetch path) and the shard id rides each entry so
        a client addresses the OWNING shard's pool region directly.
        None when unpaged or the index kind has no scan."""
        if not self.config.paged or self.n_replicas > 1 or \
                get_index_ops(self.config.index.kind).scan is None:
            # 2-D planes: no one-sided directory (see fast_view)
            return None
        # fetch ONLY the subtrees the scan reads (index + pool): on a
        # real device mesh a directory pull must not drag bloom
        # counters, ghost rings, stats and free stacks device-to-host
        # per refresh. `directory_entries` touches `.index`/`.pool`
        # alone, so a 2-field shim stands in for the full KVState (the
        # pool keeps its TierState identity through tree.map, which the
        # tiered liveness/generation checks key off).
        import types

        host_index = jax.tree.map(self._fetch, self.state.index)
        host_pool = jax.tree.map(self._fetch, self.state.pool)
        out_k, out_s, out_r, out_d = [], [], [], []
        for i in range(self.n_shards):
            st_i = types.SimpleNamespace(
                index=jax.tree.map(lambda x: x[i], host_index),
                pool=jax.tree.map(lambda x: x[i], host_pool))
            ents = kv_mod.directory_entries(st_i, self.config)
            if ents is None:
                return None
            keys, rows, digs = ents
            out_k.append(keys)
            out_s.append(np.full(len(rows), i, np.uint32))
            out_r.append(rows)
            out_d.append(digs)
        keys = np.concatenate(out_k) if out_k else np.zeros((0, 2), np.uint32)
        shards = np.concatenate(out_s) if out_s else np.zeros(0, np.uint32)
        rows = np.concatenate(out_r) if out_r else np.zeros(0, np.uint32)
        digs = np.concatenate(out_d) if out_d else np.zeros(0, np.uint32)
        if len(keys) > max_entries:
            keys, shards, rows, digs = (
                keys[:max_entries], shards[:max_entries],
                rows[:max_entries], digs[:max_entries])
        return {"epoch": self.dir_epoch, "keys": keys, "shards": shards,
                "rows": rows, "digs": digs}

    @_locked
    def bump_dir_epoch(self) -> int:
        """Structural invalidation from the membership tier (see
        `kv.KV.bump_dir_epoch`): a ring transition re-owns key ranges
        fleet-wide, so every outstanding directory entry must stop
        validating at once. Returns the new epoch."""
        self._mut_seq += 1
        self.dir_epoch += 1
        return self.dir_epoch

    @_locked
    def packed_bloom(self) -> np.ndarray | None:
        """Packed bit form for the client mirror (ref `send_bf`,
        `server/rdma_svr.cpp:157-251`).

        Each shard's filter covers only its owned keys, so the OR of the
        per-shard packed forms equals the single-chip filter bit-for-bit
        (counters are non-negative and each key lives on exactly one shard)
        — clients keep using one flat mirror, sharding-oblivious.
        """
        per = self.packed_bloom_per_shard()
        return None if per is None else np.bitwise_or.reduce(per, axis=0)

    @_locked
    def packed_bloom_per_shard(self) -> np.ndarray | None:
        """[n_shards, words] per-shard packed filters (for shard-aware
        clients that route first and mirror per shard)."""
        if self.config.bloom is None:
            return None
        fn = self._wrap("packed_bloom", _packed_bloom_body, 0, 1,
                        out_data_specs=(P(AXIS),))
        self.state, per_shard = fn(self.state)
        return self._fetch(per_shard)

    # -- persistence (checkpoint/restore of sharded state) --

    @_locked
    def save(self, path: str, delta: bool = False) -> dict:
        """Atomic snapshot of the full sharded pytree (leading [n] axis).

        The host-side `_plane_stats` plane (read-only GET accounting) is
        folded into the written stats leaf, so a snapshot carries the
        same totals `stats()` reports and a restore starts from them.

        `delta=True` writes an incremental chain member over the FLAT
        row space (shard axis folded into rows, `checkpoint.save_delta`'s
        `[-1, W]` view) — restore a chain with `restore_chain`. Falls
        back to a full (starting a new chain) exactly like
        `kv.KV.snapshot`."""
        folded = np.clip(
            self._fetch(self.state.stats).astype(np.int64)
            + self._plane_stats,
            np.iinfo(np.int32).min, np.iinfo(np.int32).max)
        st = dataclasses.replace(
            self.state, stats=jnp.asarray(folded.astype(np.int32)))
        sums, live = self._dirty_basis()
        report, self._chain = ckpt_mod.chain_step(
            st, path, self._chain, sums, live, delta)
        return report

    # caller-holds: _lock
    def _dirty_basis(self):
        """Host `(sums, live)` over the flat row space (shard-stacked
        sidecars flattened) — see `kv.KV._dirty_basis`; tier liveness
        expands per shard (hot rows always live)."""
        pool = self.state.pool
        if pool is None:
            return None, None
        sums = self._fetch(pool.sums).reshape(-1)
        live = None
        if isinstance(pool, tier_mod.TierState):
            lv = self._fetch(pool.live)          # [n, C]
            h = pool.hfree.shape[-1]
            full = np.ones((lv.shape[0], h + lv.shape[1]), bool)
            full[:, h:] = lv
            live = full.reshape(-1)
        return sums, live

    def snapshot(self, path: str, delta: bool = False) -> dict:
        """`kv.KV.snapshot` name parity (the KVServer checkpoint hook)."""
        return self.save(path, delta=delta)

    @_locked
    def restore_chain(self, paths: list, run_recovery: bool = True) -> None:
        """Warm restart: materialize a full+delta chain (any order of
        paths; `checkpoint.materialize_chain` sorts, verifies linkage,
        and refuses gaps/torn members) and restore it like one full
        snapshot — including onto a DIFFERENT shard count, which rides
        the same plane-router replay as `restore`."""
        folded = ckpt_mod.materialize_chain(list(paths))
        label = paths[-1] if paths else "<chain>"
        self._restore_from_leaves(folded["leaves"], label, run_recovery)
        # resume the chain where it left off — but ONLY when the shard
        # count matches: a resharded restore rewrites the row space, so
        # the restored chain's dirty basis no longer describes it and
        # the next snapshot must start a fresh chain (full)
        n_loaded = int(np.asarray(folded["leaves"][0]).shape[0])
        if n_loaded == self.n_shards:
            sums, live = self._dirty_basis()
            self._chain = {"id": folded["chain"]["id"],
                           "seq": int(folded["chain"]["seq"]),
                           "prev_crc": int(folded["chain"]["crc"]),
                           "base_sums": sums, "base_live": live}
        else:
            self._chain = None

    @_locked
    def restore(self, path: str, run_recovery: bool = True) -> None:
        """Load a snapshot taken by `save` onto this mesh.

        Same shard count: leaves map straight onto this mesh's
        shardings. DIFFERENT shard count (an N-shard snapshot onto an
        M-shard mesh): the snapshot's live entries are re-routed — every
        old shard's index is scanned host-side (`kv.live_entries`), live
        pages re-inserted through the normal sharded path (landing on
        their new owners), extent records replayed in ring order from
        shard 0's (deterministically replicated) ring, and the
        snapshot's counter totals carried onto shard 0. Stale-generation
        and NOPAGE entries degrade to legal misses, never wrong bytes.
        Requires the same per-shard KVConfig on both sides (trailing
        leaf shapes must match).

        The admission gate starts EMPTY on the restored plane either
        way (the `checkpoint.strip_admission` contract: snapshots never
        carry the sketch, the reshard target's fresh init supplies it)."""
        loaded = ckpt_mod.load_leaves(path, None)
        self._restore_from_leaves(loaded, path, run_recovery)

    # caller-holds: _lock
    def _restore_from_leaves(self, loaded: list, path: str,
                             run_recovery: bool) -> None:
        skeleton = ckpt_mod.strip_admission(self._eval_struct())
        leaves = jax.tree.leaves(skeleton)
        treedef = jax.tree.structure(skeleton)
        n = self.n_shards
        expected = [(n, *leaf.shape) for leaf in leaves]
        loaded = [np.asarray(x) for x in loaded]
        if [tuple(x.shape) for x in loaded] == expected:
            shardings = jax.tree.leaves(
                ckpt_mod.strip_admission(
                    pt.state_shardings(self.config, self.mesh,
                                       self._rules)),
                is_leaf=lambda x: isinstance(x, NamedSharding))
            put = [jax.device_put(x, s)
                   for x, s in zip(loaded, shardings)]
            self.state = self._transplant_admission(
                jax.tree.unflatten(treedef, put))
        else:
            self._restore_resharded(loaded, leaves, treedef, path)
        # reset the host stats plane only once a restore SUCCEEDED: a
        # rejected snapshot (shape/config mismatch raises above) must
        # not wipe the live plane's read-only-GET accounting
        self._plane_stats[:] = 0
        self._mut_seq += 1
        self.dir_epoch += 1
        if run_recovery:
            self.recovery()

    # caller-holds: _lock
    def _transplant_admission(self, st):
        """Fresh stacked admission-gate leaves onto a restored state
        whose gate the snapshot never carried (the restart-empty
        contract, `checkpoint.strip_admission`). Placement flows from
        the axis rules like every other leaf. No-op when the live
        config carries no gate."""
        tcfg = kv_mod._tier_cfg_at_init(self.config)
        acfg = tcfg.admit if tcfg is not None else None
        if acfg is None or not isinstance(st.pool, tier_mod.TierState):
            return st
        fresh = tier_mod.init_admission(acfg)
        sh = pt.state_shardings(self.config, self.mesh,
                                self._rules).pool
        stacked = {
            k: jax.device_put(
                np.ascontiguousarray(np.broadcast_to(
                    np.asarray(v),
                    (self.n_shards,) + np.asarray(v).shape)),
                getattr(sh, k))
            for k, v in fresh.items()}
        return dataclasses.replace(
            st, pool=dataclasses.replace(st.pool, **stacked))

    # caller-holds: _lock
    def _restore_resharded(self, loaded: list, sk_leaves: list, treedef,
                           path: str) -> None:
        if len(loaded) != len(sk_leaves):
            raise ValueError(
                f"snapshot {path!r} has {len(loaded)} leaves, this "
                f"config expects {len(sk_leaves)} — reshard-restore "
                "needs the same per-shard KVConfig on both sides")
        n_olds = set()
        for x, sk in zip(loaded, sk_leaves):
            if x.ndim != sk.ndim + 1 or \
                    tuple(x.shape[1:]) != tuple(sk.shape):
                raise ValueError(
                    f"snapshot {path!r} leaf {tuple(x.shape)} does not "
                    f"stack per-shard shape {tuple(sk.shape)} — "
                    "reshard-restore needs the same per-shard KVConfig "
                    "on both sides")
            n_olds.add(int(x.shape[0]))
        if len(n_olds) != 1:
            raise ValueError(
                f"snapshot {path!r} leaves disagree on the shard axis "
                f"({sorted(n_olds)})")
        n_old = n_olds.pop()
        # every replay precondition must fail BEFORE the live state is
        # replaced — a rejected snapshot must leave the instance serving
        if get_index_ops(self.config.index.kind).scan is None:
            raise ValueError(
                f"index kind {self.config.index.kind} has no scan op; "
                "reshard replay needs one")
        self.state = self._init_sharded()
        totals = np.zeros((NSTATS,), np.int64)
        for s in range(n_old):
            st_s = jax.tree.unflatten(
                treedef, [jnp.asarray(x[s]) for x in loaded])
            totals += np.asarray(st_s.stats, np.int64)
            keys, payload = kv_mod.live_entries(st_s, self.config)
            for lo in range(0, len(keys), 4096):
                # replay through the PLANE router, not a2a dispatch:
                # when M divides N an old shard's whole key set lands on
                # ONE new shard, which overflows the a2a per-pair bucket
                # capacity (silent drops); host routing is loss-free, so
                # the only drop classes left are real capacity pressure
                # (index drops AND tiered pool-exhaustion shortfalls) —
                # read off the replay-era device stats below, never
                # silent
                self.plane_insert(keys[lo:lo + 4096],
                                  payload[lo:lo + 4096]).fetch()
        # extent rings are replicated (every shard appended every
        # record); replay shard 0's in ring order so newest-wins
        # arbitration sees the same sequence the snapshot did
        st0 = jax.tree.unflatten(
            treedef, [jnp.asarray(x[0]) for x in loaded])
        recs = np.asarray(st0.extents.recs)
        if len(recs):
            cur = int(np.asarray(st0.extents.cursor)) % len(recs)
            for i in np.r_[cur:len(recs), 0:cur]:
                khi, klo, vhi, vlo, length, valid = (
                    int(v) for v in recs[i])
                if not valid:
                    continue
                self.insert_extent(np.array([khi, klo], np.uint32),
                                   np.array([vhi, vlo], np.uint32),
                                   length)
        # the replay itself bumped puts/extent_puts; overwrite with the
        # snapshot's totals (on shard 0) so counters survive the
        # reshard. Capacity-pressure drops during the replay (a smaller
        # target mesh) are legal clean-cache outcomes but must never be
        # SILENT: the state was fresh-initialized above, so the device
        # DROPS total at this point IS the replay's loss (index-level
        # drops and tiered NOPAGE shortfalls both land there) — carry
        # it onto the restored drops counter and warn.
        n_dropped = int(self._fetch(self.state.stats)
                        .astype(np.int64)[:, DROPS].sum())
        if n_dropped:
            print(f"[sharded-kv] reshard replay dropped {n_dropped} "
                  "pages (target mesh capacity pressure; legal misses)")
        totals[DROPS] += n_dropped
        stacked = np.zeros((self.n_shards, NSTATS), np.int32)
        stacked[0] = np.clip(totals, np.iinfo(np.int32).min,
                             np.iinfo(np.int32).max).astype(np.int32)
        # placement flows from the axis rules like every other leaf — a
        # literal P(kv) here would desync from remapped 'stat' rules
        stats_sh = pt.state_shardings(self.config, self.mesh,
                                      self._rules).stats
        self.state = dataclasses.replace(
            self.state, stats=jax.device_put(stacked, stats_sh))

    def node_of(self, keys: np.ndarray) -> np.ndarray:
        """Owning shard per key — the `GetNodeID(key)` analog
        (`server/NuMA_KV.cpp:136-151`, `CCEH::GetNodeID`). Host-side, no
        device work: routing is a pure hash."""
        keys = np.asarray(keys, np.uint32).reshape(-1, 2)
        return np.asarray(shard_of(jnp.asarray(keys), self.n_shards))

    @_locked
    def shard_report(self) -> dict:
        """Per-shard load report — the `segments_in_node` / per-node freq
        stats analog (`server/CCEH_hybrid.h:202-206`): occupancy and the
        full stats vector PER shard (sums equal `stats()`), for spotting
        key-space skew the way the reference eyeballs NUMA imbalance."""
        fn = self._wrap("occupancy", _occupancy_body, 0, 1,
                        out_data_specs=(P(AXIS),))
        self.state, occ = fn(self.state)
        # device vector + the host plane (read-only GET accounting)
        per_stats = (self._fetch(self.state.stats).astype(np.int64)
                     + self._plane_stats)  # [n, NSTATS]
        occ = self._fetch(occ).reshape(-1)
        cap = self.capacity() // self.n_shards
        return {
            "n_shards": self.n_shards,
            "occupancy": [int(x) for x in occ],
            "utilization": [round(float(x) / cap, 4) for x in occ],
            "stats": {
                name: [int(x) for x in per_stats[:, i]]
                for i, name in enumerate(kv_mod.STAT_NAMES)
            },
            # per-shard LRFU plane (present when lrfu_stats=True): the
            # reference's Metric{atime, crf} + freq per node. Stored crf is
            # lazily decayed (only when a shard is touched), so the report
            # decays every shard to the CURRENT tick — idle shards would
            # otherwise expose stale crf and cross-shard comparisons would
            # mix values decayed to different ticks (ADVICE r5).
            **({
                "freq": [int(x) for x in self._freq],
                "atime": [int(x) for x in self._lrfu[:, 0]],
                "crf": [
                    round(float(x), 3)
                    for x in self._lrfu[:, 1] * np.power(
                        0.5,
                        self.lrfu_lambda
                        * (self._lrfu_tick - self._lrfu[:, 0]),
                    )
                ],
            } if self.lrfu_stats else {}),
            # per-shard tier counters + hot-plane heat, both normalized to
            # the CURRENT tick (the r5 decay-at-report rule: stored hot
            # metrics are stamped lazily, so cross-shard comparisons must
            # not mix values aged to different moments)
            **self._tier_report(),
            # per-replica-lane attribution (2-D planes): which lane won
            # the hedged reads, which lane's digest gate refused
            **({"replica": self.replica_report()}
               if self.n_replicas > 1 else {}),
        }

    def _tier_report(self) -> dict:
        """shard_report's tier block (empty when the pool is flat)."""
        pool = self.state.pool
        if not isinstance(pool, tier_mod.TierState):
            return {}
        per = self._fetch(pool.tstats)            # [n, NTSTATS]
        hk = self._fetch(pool.hot_keys)           # [n, H, 2]
        met = self._fetch(pool.metric)            # [n, H]
        tick = self._fetch(pool.tick)             # [n]
        occ = ~np.all(hk == INVALID_WORD, axis=-1)  # [n, H]
        heat = [
            round(tier_mod.hot_heat_arrays(
                hk[s], met[s], int(tick[s]), self.lrfu_lambda), 3)
            for s in range(self.n_shards)
        ]
        admit = {}
        if pool.admit_stats is not None:
            # per-shard admission lanes (the shard_report discipline:
            # sums must equal the tier_stats() fold)
            ast = self._fetch(pool.admit_stats)  # [n, NASTATS]
            admit = {name: [int(x) for x in ast[:, i]]
                     for i, name in enumerate(tier_mod.ADMIT_STAT_NAMES)}
        return {
            "tier": {
                **{name: [int(x) for x in per[:, i]]
                   for i, name in enumerate(tier_mod.TIER_STAT_NAMES)},
                "hot_occupied": [int(x) for x in occ.sum(axis=1)],
                **admit,
            },
            "hot_heat": heat,
        }

    # caller-holds: _lock
    def _balloon_rows(self, rows: int) -> int:
        """PER-SHARD balloon amount, `kv.KV._balloon_rows` rule (round
        up to whole extents, clamp to the per-shard cold pool — `rows`
        is a static jit arg, so rounding bounds the compiled set)."""
        step = kv_mod._tcfg(self.config).balloon_step
        c = self.state.pool.cfree.shape[-1]
        return min(-(-int(rows) // step) * step, c)

    @_locked
    def balloon_state(self) -> dict | None:
        """Cold-pool circulation snapshot summed across shards (the
        `kv.KV.balloon_state` surface at mesh scale — the balloon
        controller's probe). None on a flat pool. `step` stays the
        PER-SHARD extent: one knob move balloons every shard by one
        extent, matching `balloon_grow`/`balloon_shrink` semantics."""
        pool = self.state.pool
        if not isinstance(pool, tier_mod.TierState):
            return None
        hwm = self._fetch(pool.hwm).astype(np.int64)
        ptop = self._fetch(pool.ptop).astype(np.int64)
        ctop = self._fetch(pool.ctop).astype(np.int64)
        return {
            "cold_rows": self.n_shards * pool.cfree.shape[-1],
            "circulating": int((hwm - ptop).sum()),
            "parked": int(ptop.sum()),
            "free": int(ctop.sum()),
            "step": int(kv_mod._tcfg(self.config).balloon_step),
        }

    @_locked
    def balloon_shrink(self, rows: int) -> bool:
        """Balloon every shard's cold pool down by up to `rows` rows
        PER SHARD (the `kv.KV.balloon_shrink` surface at mesh scale:
        free rows park first, then the coldest live rows evict to legal
        misses). False on a flat pool."""
        if not isinstance(self.state.pool, tier_mod.TierState):
            return False
        k = self._balloon_rows(rows)
        fn = self._wrap("balloon_shrink", _balloon_shrink_body, 0, 0,
                        static=(k,))
        self.state = fn(self.state)
        self._mut_seq += 1
        self.dir_epoch += 1
        return True

    @_locked
    def balloon_grow(self, rows: int) -> bool:
        """Ensure at least `rows` free cold rows circulate per shard
        (parked capacity returns first). False on a flat pool."""
        if not isinstance(self.state.pool, tier_mod.TierState):
            return False
        k = self._balloon_rows(rows)
        fn = self._wrap("balloon_grow", _balloon_grow_body, 0, 0,
                        static=(k,))
        self.state = fn(self.state)
        self._mut_seq += 1
        self.dir_epoch += 1
        return True

    @_locked
    def tier_stats(self) -> dict | None:
        """Summed per-tier counters across every shard (None when flat) —
        the `kv.KV.tier_stats` surface at mesh scale."""
        pool = self.state.pool
        if not isinstance(pool, tier_mod.TierState):
            return None
        per = self._fetch(pool.tstats)
        # ONE derivation (tier.counters_dict): the mesh sum must use the
        # exact naming/derived-field rule the single-chip surface uses —
        # the two used to fork migrated_bytes and could drift
        d = tier_mod.counters_dict(per.sum(axis=0),
                                   self.config.page_words * 4)
        if pool.admit_stats is not None:
            # admission lanes (same one-derivation rule:
            # tier.admit_counters_dict); threshold is one knob written
            # identically to every shard, reported as the max so a torn
            # read mid-set still reports a value that was live
            ast = self._fetch(pool.admit_stats)  # [n, NASTATS]
            d.update(tier_mod.admit_counters_dict(ast.sum(axis=0)))
            d["admit_threshold"] = int(
                self._fetch(pool.admit_thresh).max())
        return d

    @_locked
    def admit_state(self) -> dict | None:
        """Admission-gate snapshot summed across shards (the
        `kv.KV.admit_state` surface at mesh scale — same key set, so
        the controller's probe is shape-oblivious). `threshold` and
        `reset_ops` stay PER-SHARD values (one knob written identically
        everywhere); counter lanes and epoch progress sum. None when
        flat or the gate is off."""
        pool = self.state.pool
        if not isinstance(pool, tier_mod.TierState) \
                or pool.admit_cm is None:
            return None
        acfg = tier_mod.admit_cfg(pool, kv_mod._tcfg(self.config))
        d = tier_mod.admit_counters_dict(
            self._fetch(pool.admit_stats).sum(axis=0))
        d.update({
            "threshold": int(self._fetch(pool.admit_thresh).max()),
            "ops": int(self._fetch(pool.admit_ops).sum()),
            "reset_ops": int(acfg.reset_ops),
            "epochs": d["admit_age_epochs"],
        })
        return d

    @_locked
    def set_admit_threshold(self, value: int) -> bool:
        """Write the live admission threshold on EVERY shard (one knob,
        one value — the `kv.KV.set_admit_threshold` surface at mesh
        scale). Placement flows from the axis rules like every other
        leaf. False when flat or the gate is off."""
        pool = self.state.pool
        if not isinstance(pool, tier_mod.TierState) \
                or pool.admit_cm is None:
            return False
        v = max(0, int(value))
        sh = pt.state_shardings(self.config, self.mesh,
                                self._rules).pool.admit_thresh
        arr = jax.device_put(
            np.full((self.n_shards,), v, np.uint32), sh)
        self.state = dataclasses.replace(
            self.state,
            pool=dataclasses.replace(pool, admit_thresh=arr))
        return True

    @_locked
    def account_shed(self, gets: int, puts: int = 0) -> None:
        """QoS shed attribution at mesh scale (the `kv.KV.account_shed`
        surface): bumps land in shard 0's host stats plane — a shed op
        never routed, so no shard ever touched it; parking the lanes on
        one plane row keeps `misses == Σ causes` exact on both stats()
        and the shard_report sum without inventing a phantom shard."""
        if gets:
            self._plane_stats[0, GETS] += int(gets)
            self._plane_stats[0, MISSES] += int(gets)
            self._plane_stats[0, MISS_SHED] += int(gets)
        if puts:
            self._plane_stats[0, PUTS] += int(puts)
            self._plane_stats[0, DROPS] += int(puts)

    @_locked
    def account_quarantined(self, gets: int, puts: int = 0,
                            shard: int = 0) -> None:
        """Shard-quarantine attribution at mesh scale (the
        `kv.KV.account_quarantined` surface): bumps land on the
        QUARANTINED shard's own host stats row — the op was routed to
        that shard and degraded there, so shard_report shows exactly
        which failure domain is eating the misses, and `misses == Σ
        causes` stays exact on stats() and the per-shard sums."""
        s = int(shard) % self.n_shards
        if gets:
            self._plane_stats[s, GETS] += int(gets)
            self._plane_stats[s, MISSES] += int(gets)
            self._plane_stats[s, MISS_QUARANTINED] += int(gets)
        if puts:
            self._plane_stats[s, PUTS] += int(puts)
            self._plane_stats[s, DROPS] += int(puts)

    @_locked
    def account_deadline(self, gets: int, puts: int = 0) -> None:
        """Deadline-shed attribution at mesh scale (the
        `kv.KV.account_deadline` surface): an expired op was never
        routed, so the bumps park on shard 0's host plane row — the
        `account_shed` convention."""
        if gets:
            self._plane_stats[0, GETS] += int(gets)
            self._plane_stats[0, MISSES] += int(gets)
            self._plane_stats[0, MISS_DEADLINE] += int(gets)
        if puts:
            self._plane_stats[0, PUTS] += int(puts)
            self._plane_stats[0, DROPS] += int(puts)

    @_locked
    def stats(self) -> dict:
        per_shard = (self._fetch(self.state.stats).astype(np.int64)
                     + self._plane_stats)  # [n, NSTATS]
        vec = per_shard.sum(axis=0)
        d = dict(zip(kv_mod.STAT_NAMES, (int(x) for x in vec)))
        t = self.tier_stats()
        if t is not None:
            d.update(t)
        return d

    def print_stats(self) -> str:
        s = self.stats()
        line = ", ".join(f"{k}={v}" for k, v in s.items())
        print(f"[sharded-kv n={self.n_shards} {self.dispatch}] {line}")
        return line

    def capacity(self) -> int:
        from pmdfc_tpu.models.base import get_index_ops

        return get_index_ops(self.config.index.kind).num_slots(
            self.config.index
        ) * self.n_shards

    def _dspec(self):
        """Data-batch partition spec for the active dispatch mode."""
        return P(AXIS) if self.dispatch == "a2a" else P()

    def _to_global(self, arr: np.ndarray):
        """Host batch -> device array. Single-process: plain transfer
        (XLA shards at the jit boundary). Multi-process (after
        `connect_multihost`): every process passes the IDENTICAL full
        batch and serves its addressable shards from its local copy —
        the host-replicated-input convention of multi-host JAX."""
        if jax.process_count() == 1:
            return jnp.asarray(arr)
        sh = NamedSharding(self.mesh, self._dspec())
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx: arr[idx]
        )

    @staticmethod
    def _fetch(x) -> np.ndarray:
        """Device output -> host numpy. Multi-process outputs are only
        partially addressable here; allgather assembles the global value
        on every process (each host API call returns the full result on
        all hosts, like the single-process path)."""
        if getattr(x, "is_fully_addressable", True):
            return np.asarray(x)
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(x, tiled=True)

    def _pad(self, keys: np.ndarray, values: np.ndarray | None = None):
        """Pad to a power-of-two width, rounded up to a multiple of
        n_shards (meshes need not be powers of two)."""
        keys = np.asarray(keys, np.uint32)
        b = len(keys)
        w = 16
        while w < b:
            w <<= 1
        w += -w % self.n_shards
        kpad = np.full((w, 2), INVALID_WORD, np.uint32)
        kpad[:b] = keys
        if values is None:
            return self._to_global(kpad), None, b, w
        values = np.asarray(values, np.uint32)
        vpad = np.zeros((w, values.shape[-1]), np.uint32)
        vpad[:b] = values
        return self._to_global(kpad), self._to_global(vpad), b, w
