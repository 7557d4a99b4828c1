"""Logical-axis partitioning for mesh-sharded KV state — rules → specs.

The t5x/fmengine discipline (SNIPPETS §1-§3) applied to the serving
plane: every leaf of the `KVState` pytree is named by LOGICAL axes
(`shard`, `pool_row`, `page_word`, `bloom_counter`, ...), a small rules
table maps logical axes onto MESH axes, and the mapped rules produce the
`PartitionSpec`/`NamedSharding` pytrees every mesh program uses. One
vocabulary, three consumers:

- `ShardedKV` builds its `shard_map` in/out specs and its init/restore
  `NamedSharding`s from `state_specs`/`state_shardings` instead of a
  blanket `P("kv")` tree-map, so a future 2-D mesh (e.g. page words
  split over a `model` axis) is a RULES change, not a rewrite.
- The serving plane (`runtime/server.py` mesh mode, `runtime/net.py`
  overlapped mesh flushes) routes request batches host-side with
  `ShardRouter` — the NUMA-queue analog (`server/NuMA_KV.cpp:136-151`:
  requests dispatch to the node that owns the page). Routing uses the
  numpy mirror of the device hash, so the wire tier never pays a device
  dispatch just to pick a queue.
- `describe()` renders the axis table (leaf → logical axes → spec) for
  docs/telemetry, and `validate_rules` fails loudly on a rule naming a
  mesh axis the mesh doesn't have — a silent typo would quietly
  replicate state that was meant to shard.

Why the default rules map ONLY `shard`: KV state is an independent
table per shard (index + bloom + pool + extents each cover the shard's
key-space slice), so the leading stacked axis is the one that
partitions; everything trailing is shard-local. The rules table still
names every trailing axis so the day a leaf SHOULD split further (page
words over a second mesh dim, bloom counters over a wide mesh), the
change is one rule line validated against the live mesh.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pmdfc_tpu.config import KVConfig
from pmdfc_tpu.utils.hashing import SHARD_SEED
from pmdfc_tpu.utils.hashing_np import hash_u64_np
from pmdfc_tpu.utils.keys import INVALID_WORD

# the mesh axis every default rule maps the leading state axis onto;
# `parallel.shard.AXIS` aliases this name
MESH_AXIS = "kv"

# the SECOND mesh axis of a 2-D serving mesh: replica lanes. State is
# REPLICATED along it (every lane holds a full copy of its shard's
# tables — see `_PATH_REPLICATED`), while per-lane OUTPUTS (attribution
# scalars) shard over it via the `replica_lane` logical axis below.
# `parallel.shard.RAXIS` aliases this name.
REPLICA_MESH_AXIS = "replica"

# logical name of the leading stacked axis (one slice per shard)
SHARD = "shard"

# logical axis for values laid out one-per-replica-lane (the per-lane
# served/refused/repaired attribution outputs of the 2-D plane bodies —
# no persistent KVState leaf uses it: state replicates along the lane)
REPLICA_LANE = "replica_lane"

# logical-axis → mesh-axis (None = replicated along that dim). The
# LogicalAxisRules shape of t5x: first match wins, every logical axis a
# state leaf uses MUST appear here (resolve_spec raises otherwise).
DEFAULT_AXIS_RULES: tuple[tuple[str, str | None], ...] = (
    (SHARD, MESH_AXIS),
    # index tables (kind-specific row/col planes — shard-local)
    ("index_row", None),
    ("index_col", None),
    ("index_plane", None),
    # page pools (flat and tiered share the row/word vocabulary)
    ("pool_row", None),
    ("page_word", None),
    ("hot_row", None),
    ("cold_row", None),
    ("ghost_slot", None),
    ("key_word", None),
    # bloom counters, extent ring, counters
    ("bloom_counter", None),
    ("extent_slot", None),
    ("extent_word", None),
    ("stat", None),
    ("tier_stat", None),
    # evicted-key sketch bits (miss-cause taxonomy; shard-local like the
    # bloom counters — each shard remembers only its own evictions)
    ("sketch_bit", None),
    # TinyLFU admission gate (tiered pool; shard-local like the bloom —
    # each shard's sketch sees only its own key traffic): count-min rows
    # × counters, doorkeeper bits, and the admission stats vector
    ("cm_row", None),
    ("cm_counter", None),
    ("door_bit", None),
    ("admit_stat", None),
)

# The 2-D serving mesh's table: DEFAULT_AXIS_RULES grown by the second
# axis — the one-rules-line promise of the original design. Selected by
# `rules_for_mesh` whenever the live mesh carries the `replica` axis;
# on a 1-D mesh `validate_rules` REFUSES this table (the replica rule
# names a mesh axis a 1-D mesh doesn't have), which is exactly the
# silent-replicate guard the 1-D path keeps.
MESH2D_AXIS_RULES: tuple[tuple[str, str | None], ...] = (
    (REPLICA_LANE, REPLICA_MESH_AXIS),
) + DEFAULT_AXIS_RULES

# Explicit replicated-along markers for the 2-D mesh: every KVState
# leaf family must either shard over the replica axis via a rule above
# or appear HERE, naming the mesh axes it intentionally replicates
# along. All state replicates (each lane is a full copy — that IS the
# replication scheme); the table is per-family, not a catch-all, so a
# NEW leaf must be classified before it can ride a 2-D mesh (the same
# coverage discipline `_PATH_AXES` enforces for logical axes).
_PATH_REPLICATED: tuple[tuple[str, tuple[str, ...]], ...] = (
    (r"\.stats$", (REPLICA_MESH_AXIS,)),
    (r"\.evicted_filter$", (REPLICA_MESH_AXIS,)),
    (r"\.bloom\.", (REPLICA_MESH_AXIS,)),
    (r"\.extents\.", (REPLICA_MESH_AXIS,)),
    (r"\.pool\.", (REPLICA_MESH_AXIS,)),
    (r"\.index\.", (REPLICA_MESH_AXIS,)),
)

# leaf-path regex → trailing logical axis names (leading `shard` is
# prepended by `stacked_axes`). First match wins; names beyond a leaf's
# rank are ignored so one rule covers e.g. both [C] and [C, W] planes.
_PATH_AXES: tuple[tuple[str, tuple[str, ...]], ...] = (
    (r"\.stats$", ("stat",)),
    (r"\.evicted_filter$", ("sketch_bit",)),
    (r"\.bloom\.", ("bloom_counter",)),
    (r"\.extents\.recs$", ("extent_slot", "extent_word")),
    (r"\.extents\.", ()),  # cursor scalar
    # tiered pool planes (hot/cold split, ghost ring, generations)
    (r"\.pool\.(hot_keys)$", ("hot_row", "key_word")),
    (r"\.pool\.(hfree|metric)$", ("hot_row",)),
    (r"\.pool\.(cfree|touch|live|pmask|parked|cgen)$", ("cold_row",)),
    (r"\.pool\.ghost$", ("ghost_slot", "key_word")),
    (r"\.pool\.tstats$", ("tier_stat",)),
    # TinyLFU admission gate (leaves exist IFF the effective TierConfig
    # carries an AdmitConfig; admit_ops/admit_thresh scalars ride the
    # pool catch-all below)
    (r"\.pool\.admit_cm$", ("cm_row", "cm_counter")),
    (r"\.pool\.admit_door$", ("door_bit",)),
    (r"\.pool\.admit_stats$", ("admit_stat",)),
    # flat + tiered backing arrays ([rows, page_words] / [rows])
    (r"\.pool\.(pages|sums|free)$", ("pool_row", "page_word")),
    (r"\.pool\.", ()),  # top/htop/ctop/ptop/hwm/tick/gcur scalars
    # index internals: kind-specific, named by position (row-major)
    (r"\.index\.", ("index_row", "index_col", "index_plane")),
)


def _path_str(path) -> str:
    """KeyPath → dotted string (``.index.table``, ``.pool.pages``)."""
    out = []
    for k in path:
        name = getattr(k, "name", None)
        if name is None:
            name = str(getattr(k, "key", getattr(k, "idx", k)))
        out.append(str(name))
    return "." + ".".join(out)


def leaf_axes(path: str, ndim: int) -> tuple[str, ...]:
    """Trailing logical axes for one single-shard leaf of `ndim` dims."""
    for pat, names in _PATH_AXES:
        if re.search(pat, path):
            if ndim > len(names):
                raise ValueError(
                    f"state leaf {path} has {ndim} dims but the axis "
                    f"table names only {names} — name the new axis in "
                    "partitioning._PATH_AXES")
            return names[:ndim]
    raise ValueError(
        f"state leaf {path} matches no axis rule — name it in "
        "partitioning._PATH_AXES")


def replicated_along(path: str) -> tuple[str, ...]:
    """Mesh axes the leaf at `path` is explicitly marked replicated
    along on a 2-D mesh. A leaf matching no marker raises — a new state
    family must be classified before it can ride the replica axis."""
    for pat, axes in _PATH_REPLICATED:
        if re.search(pat, path):
            return axes
    raise ValueError(
        f"state leaf {path} has no replicated-along marker — classify "
        "it in partitioning._PATH_REPLICATED (or give it a 2-D rule)")


def resolve_rules(extra=None) -> tuple[tuple[str, str | None], ...]:
    """Rules table with caller overrides PREPENDED (first match wins)."""
    return tuple(extra or ()) + DEFAULT_AXIS_RULES


def rules_for_mesh(mesh: Mesh, extra=None):
    """The axis-rule table matching the live mesh's dimensionality:
    `MESH2D_AXIS_RULES` when the mesh carries the `replica` axis, the
    1-D `DEFAULT_AXIS_RULES` otherwise — so a 1-D construction never
    sees (and `validate_rules` never has to tolerate) a rule naming a
    mesh axis it doesn't have. Caller overrides still prepend."""
    base = (MESH2D_AXIS_RULES if REPLICA_MESH_AXIS in mesh.axis_names
            else DEFAULT_AXIS_RULES)
    return tuple(extra or ()) + base


def validate_rules(rules, mesh: Mesh) -> None:
    """A rule mapping onto a mesh axis the mesh doesn't have is a silent
    replicate-instead-of-shard bug; fail construction instead."""
    for logical, mesh_axis in rules:
        if mesh_axis is not None and mesh_axis not in mesh.axis_names:
            raise ValueError(
                f"axis rule ({logical!r} -> {mesh_axis!r}) names a mesh "
                f"axis not in {tuple(mesh.axis_names)}")


def spec_for(axes: tuple[str, ...], rules) -> P:
    """Logical axis names → PartitionSpec via the first matching rule."""
    mapped = []
    for a in axes:
        for logical, mesh_axis in rules:
            if logical == a:
                mapped.append(mesh_axis)
                break
        else:
            raise ValueError(
                f"logical axis {a!r} has no entry in the axis rules")
    while mapped and mapped[-1] is None:  # trailing Nones are noise
        mapped.pop()
    return P(*mapped)


def _eval_struct(config: KVConfig):
    from pmdfc_tpu import kv as kv_mod

    return jax.eval_shape(lambda: kv_mod.init(config))


def stacked_axes(config: KVConfig):
    """Pytree (matching `kv.init(config)`'s structure) of logical axis
    names per leaf, with the leading `shard` axis prepended."""
    struct = _eval_struct(config)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(struct)
    named = [
        (SHARD,) + leaf_axes(_path_str(path), leaf.ndim)
        for path, leaf in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, named)


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(a, str) for a in x)


def state_specs(config: KVConfig, rules=None):
    """Pytree of PartitionSpec for the STACKED state ([n_shards] leading
    axis) — the shard_map in/out specs and jit sharding vocabulary."""
    rules = rules if rules is not None else DEFAULT_AXIS_RULES
    return jax.tree.map(lambda axes: spec_for(axes, rules),
                        stacked_axes(config), is_leaf=_is_axes)


def state_shardings(config: KVConfig, mesh: Mesh, rules=None):
    """Pytree of NamedSharding for the stacked state on `mesh`."""
    rules = rules if rules is not None else DEFAULT_AXIS_RULES
    validate_rules(rules, mesh)
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        state_specs(config, rules),
                        is_leaf=lambda x: isinstance(x, P))


def describe(config: KVConfig, rules=None) -> list[dict]:
    """Axis-rule table rows (leaf, shape, logical axes, spec) — the
    README table's source and a debugging surface."""
    rules = rules if rules is not None else DEFAULT_AXIS_RULES
    struct = _eval_struct(config)
    leaves, _ = jax.tree_util.tree_flatten_with_path(struct)
    rows = []
    for path, leaf in leaves:
        p = _path_str(path)
        axes = (SHARD,) + leaf_axes(p, leaf.ndim)
        rows.append({
            "leaf": p,
            "shape": ("n_shards",) + tuple(leaf.shape),
            "axes": axes,
            "spec": str(spec_for(axes, rules)),
            "replicated_along": replicated_along(p),
        })
    return rows


# ---------------------------------------------------------------------------
# host-side request routing (the per-NUMA-node dispatch queue analog)
# ---------------------------------------------------------------------------


def shard_of_np(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Numpy mirror of `utils.hashing.shard_of` — same murmur3 family
    member, bit-identical owners, zero device work. The serving plane
    routes with this (a device dispatch per routing decision would put
    the router itself on the device's critical path)."""
    keys = np.asarray(keys, np.uint32).reshape(-1, 2)
    h = hash_u64_np(keys[:, 0], keys[:, 1], seed=SHARD_SEED)
    return (h % np.uint32(n_shards)).astype(np.uint32)


@dataclasses.dataclass
class RoutedBatch:
    """One host-routed batch: shard-major padded lanes + the scatter
    map back to request order. `values` is a prefix view of the
    router's held page buffer when `held` (hand it back with
    `ShardRouter.release` once no launch reads it), else an array of
    its own; `fresh` says the pages needed a newly allocated array."""

    keys: np.ndarray          # uint32[n*wl, 2] shard-major, INVALID pads
    values: np.ndarray | None  # uint32[n*wl, V] aligned with keys, 0 pads
    pos: np.ndarray           # int64[b] routed lane of request i
    counts: np.ndarray        # int64[n] requests routed per shard
    wl: int                   # per-shard padded width (pow2)
    b: int                    # live request count
    held: bool = False        # values is the router's held buffer
    fresh: bool = False       # values needed a new allocation

    def scatter(self, routed: np.ndarray) -> np.ndarray:
        """Routed-lane result array → request order ([b, ...]). Each
        request reads back its OWN lane, so pad lanes (INVALID keys:
        match nothing, place nothing) never leak into results."""
        return np.asarray(routed)[self.pos]


class ShardRouter:
    """Bins host batches by owning shard and pads PER SHARD up the pow2
    ladder — `GetNodeID(key)` queue dispatch fused with the serving
    tier's pad discipline.

    Per-shard padding (vs. the global pow2 pad the single-device path
    uses) keeps each shard's program width independent of how many
    OTHER shards' requests rode the same flush, so the compiled-shape
    set stays one ladder per shard count, and a skewed flush pays only
    its own shard's pad waste. Requests keep their in-batch order
    within each shard (stable binning), which is what makes cross-shard
    dedupe-last-wins match the single-device ground truth. One shard
    is the identity route: no owner hash, no binning, contiguous
    copies.

    Routed pages go into ONE page buffer the router holds and reuses
    from batch to batch, grown (never shrunk) to the widest rung seen.
    A fresh array per PUT batch would be, at the served width (8,192
    rows of 4 KiB: 32 MiB, glibc's mmap threshold ceiling), a new
    anonymous mapping that the copy page-faults in and the launch's end
    unmaps, every flush. `build` marks the buffer busy; `release` frees
    it once the launch that reads it has been fetched. A `build` that
    finds it busy allocates an array of its own instead of waiting.
    Callers serialize `build` (`ShardedKV`'s lock); `release` is one
    store by the batch's holder, so it may run on any thread.
    """

    def __init__(self, n_shards: int, pad_floor: int = 8):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if pad_floor < 1 or (pad_floor & (pad_floor - 1)):
            raise ValueError("pad_floor must be a positive power of two")
        self.n = n_shards
        self.pad_floor = pad_floor
        self._buf: np.ndarray | None = None  # uint32[rows, V], held
        self._busy = False

    def owners(self, keys: np.ndarray) -> np.ndarray:
        return shard_of_np(keys, self.n)

    def width(self, max_count: int) -> int:
        w = self.pad_floor
        while w < max_count:
            w <<= 1
        return w

    def build(self, keys: np.ndarray,
              values: np.ndarray | None = None) -> RoutedBatch:
        keys = np.asarray(keys, np.uint32).reshape(-1, 2)
        b = len(keys)
        if self.n == 1:
            counts = np.array([b], np.int64)
            pos = np.arange(b, dtype=np.int64)
            wl = self.width(b)
            lanes = slice(0, b)
        else:
            own = self.owners(keys)
            order = np.argsort(own, kind="stable")
            counts = np.bincount(own, minlength=self.n).astype(np.int64)
            wl = self.width(int(counts.max()) if b else 0)
            starts = np.zeros(self.n, np.int64)
            np.cumsum(counts[:-1], out=starts[1:])
            own_sorted = own[order]
            rank = np.arange(b, dtype=np.int64) - starts[own_sorted]
            pos_sorted = own_sorted.astype(np.int64) * wl + rank
            pos = np.empty(b, np.int64)
            pos[order] = pos_sorted
            lanes = pos
        kp = np.full((self.n * wl, 2), INVALID_WORD, np.uint32)
        kp[lanes] = keys
        rb = RoutedBatch(keys=kp, values=None, pos=pos, counts=counts,
                         wl=wl, b=b)
        if values is not None:
            values = np.asarray(values, np.uint32)
            vp = rb.values = self._page_buffer(rb, values.shape[-1])
            vp[lanes] = values
            for s in range(self.n):  # the pad lanes read 0
                vp[s * wl + int(counts[s]):(s + 1) * wl] = 0
        return rb

    def _page_buffer(self, rb: RoutedBatch, width: int) -> np.ndarray:
        """`rb`'s page rows: a prefix of the held buffer (grown to fit),
        marked busy, or a new array where a launch still reads it."""
        rows = self.n * rb.wl
        buf = self._buf
        if self._busy:
            rb.fresh = True
            return np.empty((rows, width), np.uint32)
        if buf is None or buf.shape[0] < rows or buf.shape[1] != width:
            rb.fresh = True
            buf = self._buf = np.empty((rows, width), np.uint32)
        self._busy = rb.held = True
        return buf[:rows]

    def release(self, rb: RoutedBatch) -> None:
        """Hand `rb`'s page rows back: no launch reads them any more."""
        if rb.held:
            rb.held = self._busy = False
