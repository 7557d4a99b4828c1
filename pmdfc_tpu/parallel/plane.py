"""Mesh-sharded serving plane — partitioned KV state behind the NetServer.

The reference JULEE server is NUMA-aware by construction: each request
dispatches to a per-node queue picked by `GetNodeID(key)`
(`server/NuMA_KV.cpp:136-151`), so batching and data placement are
co-designed rather than bolted together (the HiStore/RDMAbox argument,
arxiv 2208.12987 / 2104.12197). This module is the TPU analog: ONE
coalesced `NetServer` flush loop drives a `ShardedKV` whose state is
partitioned over a named mesh by `partitioning.py`'s axis rules, through
the plane verbs (`ShardedKV.plane_*`):

- **Routing is host-side and loss-free** (`partitioning.ShardRouter`):
  the messenger bins each fused batch by owning shard while it is
  already touching every request — no device dispatch just to pick a
  queue, and no a2a bucket-overflow class.
- **Pads are per-shard** up the pow2 ladder, so a skewed flush pays only
  its own shard's pad waste and the compiled-shape set stays one ladder
  per shard count (`routes_per_shard` tells the NetServer to skip its
  global pad — the fused-pad/routing co-design).
- **Lean GETs are read-only programs**: no state output means no
  whole-table materialization on non-donating platforms (the jax 0.4.37
  CPU rule keeps donation off there) — the serving hot path pays
  O(batch), not O(table), per flush. Donating state-mutating phases
  stay platform-keyed in `shard._wrap`.
- **Results gather back to host once per phase**, and GET replies ship
  straight out of the routed buffer (`PlaneGets.hit_rows`), framed by
  the NetServer's connection writers: only hit rows are ever copied.

Telemetry stays per-shard attributable: `shard{i}_ops` counters and
`phase_*_us_s{i}` histogram families on the shared `mesh` scope, and a
phase failure fires a flight-recorder rung naming the shards whose
routed ops were in the failed program.

`make_serving_backend` is the kill-switch seam: `PMDFC_MESH=off` (or
`mesh_enabled()` false) returns the current single-device path
(`DirectBackend` over `kv.KV`) — conformance-tested bit-identical to
the sharded plane on a seeded workload served through `NetServer`.

2-D planes (`MeshConfig.replica_axis > 1`, `PMDFC_MESH2D` kill switch):
the mesh grows a `replica` axis carrying full per-shard state copies —
every mutating phase replicates all lanes in its ONE launch, GETs are
hedged replica-shard reads (first digest-validated lane wins, per-lane
`mesh.replica{r}_served/digest_refused/repaired` attribution), and
`replica_repair()` runs the device-side anti-entropy compare-and-copy
the wire exposes as `MSG_RREPAIR`. `replica_lanes` is the capability
the NetServer advertises in the HOLA exchange so a host `ReplicaGroup`
can delegate its fan-out to the fused plane.
"""

from __future__ import annotations

import numpy as np

from pmdfc_tpu import kv as kv_mod
from pmdfc_tpu.config import (ContainmentConfig, KVConfig, MeshConfig,
                              containment_enabled, mesh_enabled)
from pmdfc_tpu.runtime import profiler
from pmdfc_tpu.runtime import telemetry as tele
from pmdfc_tpu.runtime.failure import ShardFault, ShardQuarantine
from pmdfc_tpu.utils.keys import INVALID_WORD

_PHASES = ("put", "get", "del", "ins_ext", "get_ext")


class PlaneBackend:
    """Backend surface (`put/get/invalidate/...`) over a `ShardedKV`'s
    plane verbs — what the coalesced `NetServer` fronts in mesh mode.

    The flush loop calls one verb per phase; each verb launches the
    routed shard_map program and blocks on its `PlaneHandle` (JAX async
    dispatch pays compute+transfer at the fetch). No per-shard locks
    anywhere: the router is pure host math and `ShardedKV._lock` is the
    single dispatch serializer, exactly like the single-device path.
    """

    # the NetServer reads this: routing pads per shard, so the wire
    # tier's global pow2 pad would only inflate the routed width
    routes_per_shard = True

    def __init__(self, skv, containment: ContainmentConfig | None = None,
                 fault_plan=None):
        self.skv = skv
        self.n_shards = skv.n_shards
        # rung-8 failure domains: one shard-scoped breaker per shard,
        # fed by ShardFaults out of the launch path. `fault_plan` is the
        # deterministic device-fault seam drills arm (failure.FaultPlan)
        cc = (containment if containment is not None
              else ContainmentConfig(enabled=containment_enabled()))
        self.containment = cc
        self.fault_plan = fault_plan
        self.quarantine = (ShardQuarantine(
            skv.n_shards,
            failures_to_open=cc.quarantine_failures,
            cooldown_s=cc.quarantine_cooldown_s,
            max_cooldown_s=cc.quarantine_max_cooldown_s,
            backoff=cc.quarantine_backoff)
            if cc.enabled else None)
        # device-side replica lanes (2-D mesh; 1 = plain 1-D plane) —
        # the capability the wire tier advertises so a host ReplicaGroup
        # can delegate its fan-out to the fused plane
        self.replica_lanes = getattr(skv, "n_replicas", 1)
        self.page_words = skv.config.page_words
        # shared process scope (sweeps build many planes; per-instance
        # scopes would explode the namespace): per-shard routed-op
        # counters + per-shard per-phase latency histogram families
        self._tele = tele.scope("mesh", unique=False)
        self._h_phase = {
            ph: self._tele.hist_family(f"phase_{ph}_us", self.n_shards)
            for ph in _PHASES
        }
        # counters pre-resolved like the histograms: the hot path
        # indexes a tuple instead of paying the name->metric lookup
        # (f-string + scope lock) per shard per phase
        self._c_shard = tuple(self._tele.counter(f"shard{i}_ops")
                              for i in range(self.n_shards))
        # per-replica-lane attribution families (2-D planes): which lane
        # won each hedged read, which lane's digest gate refused, rows
        # the device repair pass re-synced onto each lane
        self._c_lane = tuple(
            (self._tele.counter(f"replica{r}_served"),
             self._tele.counter(f"replica{r}_digest_refused"),
             self._tele.counter(f"replica{r}_repaired"))
            for r in range(self.replica_lanes)
        ) if self.replica_lanes > 1 else ()

    # -- per-shard attribution helpers --

    def _note(self, phase: str, counts, dur_us: float,
              t0_ns: int, t1_ns: int) -> None:
        if counts is None:
            # broadcast phase (extents): every shard ran the program
            counts = np.ones(self.n_shards, np.int64)
        hists = self._h_phase[phase]
        on = tele.enabled()
        for s in np.flatnonzero(np.asarray(counts)):
            s = int(s)
            self._c_shard[s].inc(int(counts[s]))
            if on and s < len(hists):
                hists[s].observe(dur_us)
            if on:
                # one shard-program tree node per involved shard: the
                # fetch window, attributed with the shard's routed op
                # count. Parent comes off the calling thread's ambient
                # stack — the NetServer's open flush-phase span when
                # serving the wire, root when driven directly.
                sp = tele.span_begin("server", "shard_program",
                                     t0_ns=t0_ns, shard=s, phase=phase,
                                     ops=int(counts[s]))
                tele.span_end(sp, t1_ns=t1_ns)

    def _run(self, phase: str, handle):
        """Fetch one launched phase under its telemetry envelope; a
        failure rung names the shards whose routed ops were aboard.
        The fetch is a stage of its own (`telemetry.stage`): its two
        readings time the shard spans, the `phase_*_us_s{i}` families
        and the profiler, and the caller's reply assembly starts at the
        fetch's end."""
        prof = profiler.active() if tele.enabled() else None
        t0 = tele.stage(phase, "fetch")
        try:
            out = handle.fetch()
        except Exception as e:  # noqa: BLE001 — attribution, then re-raise
            shards = ([int(s) for s in
                       np.flatnonzero(np.asarray(handle.counts))]
                      if handle.counts is not None
                      else list(range(self.n_shards)))
            tele.rung("phase_failure", tier="mesh", phase=phase,
                      shards=shards, ops=handle.b, error=repr(e))
            raise
        t1 = tele.stage(phase, "reply")
        fetch_us = (t1 - t0) / 1e3
        self._note(phase, handle.counts, fetch_us, t0, t1)
        if handle.work is not None:
            # an insert's index work (splits, rounds, evictions, calls)
            # onto the serving flush's counters
            for name, n in zip(kv_mod.INDEX_WORK_NAMES, handle.work):
                tele.stage_count(name, int(n))
        if prof is not None:
            # the routed counts vector splits the fetch across shards in
            # the SAME proportions that fed shard{i}_ops; the launch
            # stage's reading gives the launch-to-fetch gap
            t_l = handle.t_launch_ns
            prof.note_launch(
                f"plane.{phase}", phase, fetch_us,
                launch_gap_us=(t0 - t_l) / 1e3 if t_l else 0.0,
                n_ops=handle.b, counts=handle.counts,
                n_shards=self.n_shards)
        return out

    # -- containment front door (rung 8) --

    def _contained(self, phase: str, keys: np.ndarray, launch):
        """Run one routed launch through the containment front door:
        rows owned by quarantined shards are masked to INVALID
        HOST-SIDE (they match nothing on device and pad nothing extra —
        request-order alignment with `PlaneGets` is preserved), the
        deterministic fault seam (`FaultPlan.check`) runs over what
        remains, and the launch outcome feeds the shard breakers.

        `launch(masked_keys) -> PlaneHandle`. Returns
        `(out, blocked, shards)` where `blocked` is None when every row
        flowed. A half-open probe that fails re-opens the breaker, so a
        net-tier bisection relaunch of the same ops immediately finds
        the sick shard's rows masked — the probe costs the fused batch
        at most one extra launch."""
        if self.quarantine is None and self.fault_plan is None:
            return self._run(phase, launch(keys)), None, None
        keys = np.asarray(keys, np.uint32).reshape(-1, 2)
        shards = self.skv.node_of(keys)
        blocked, probing = (self.quarantine.gate(shards)
                            if self.quarantine is not None
                            else (np.zeros(len(keys), bool), []))
        if blocked.any():
            keys = keys.copy()
            keys[blocked] = INVALID_WORD
        try:
            if self.fault_plan is not None:
                self.fault_plan.check(
                    phase, keys=keys,
                    shards=np.unique(shards[~blocked]))
            out = self._run(phase, launch(keys))
        except ShardFault as e:
            if self.quarantine is not None:
                self.quarantine.note_failure(int(e.shard) % self.n_shards)
            raise
        for s in probing:
            if self.quarantine.note_success(s):
                self._replay_journal(s)
        return out, (blocked if blocked.any() else None), shards

    def _account_blocked(self, blocked: np.ndarray, shards: np.ndarray,
                         gets: bool = False) -> None:
        """Attribute quarantine-masked rows on the OWNING shard's stats
        row: GETs are `miss_quarantined` misses, PUTs acked drops —
        `misses == Σ causes` stays bit-exact on every surface."""
        for s in np.unique(shards[blocked]):
            n = int(np.count_nonzero(blocked & (shards == s)))
            self.skv.account_quarantined(n if gets else 0,
                                         0 if gets else n, shard=int(s))
        self.quarantine.stats.inc(
            "quarantined_gets" if gets else "dropped_puts",
            int(np.count_nonzero(blocked)))

    def _replay_journal(self, shard: int) -> None:
        """Re-admission barrier: replay the invalidations a shard
        missed while quarantined BEFORE it serves again (a failed
        replay re-journals the remainder and re-charges the breaker)."""
        ks, overflowed = self.quarantine.drain_journal(shard)
        if overflowed:
            # the journal dropped entries while quarantined: replay is
            # PARTIAL and the shard may hold pages it was told to
            # forget — operator-visible, never silent
            tele.rung("shard_quarantine", shard=int(shard),
                      event="journal_overflow", replay=len(ks))
        for lo in range(0, len(ks), 1024):
            try:
                self.skv.plane_delete(ks[lo:lo + 1024]).fetch()
            except Exception:  # noqa: BLE001 — requeue, re-quarantine
                self.quarantine.journal_invalidations(shard, ks[lo:])
                self.quarantine.note_failure(shard)
                return

    # -- Backend surface --

    def put(self, keys: np.ndarray, pages: np.ndarray) -> None:
        _, blocked, shards = self._contained(
            "put", keys, lambda k: self.skv.plane_insert(k, pages))
        if blocked is not None:
            self._account_blocked(blocked, shards, gets=False)

    def _note_lanes(self, res) -> None:
        """Fold one GET phase's per-lane attribution into the
        `mesh.replica{r}_*` families (no-op on 1-D planes)."""
        if not self._c_lane or res.lane_served is None:
            return
        for r, (cs, cr, _) in enumerate(self._c_lane):
            cs.inc(int(res.lane_served[r]))
            cr.inc(int(res.lane_refused[r]))

    def get(self, keys: np.ndarray):
        """(pages[B, W], found[B]) — the portable Backend contract (the
        NetServer's hot path uses `get_fused` and never densifies)."""
        res = self.get_fused(keys)
        return res.dense(), res.found

    def get_fused(self, keys: np.ndarray):
        """`PlaneGets` for the wire tier: request-order found mask +
        per-reply-slice hit-row gathers out of the routed buffer.
        Quarantine-masked rows come back found=False (INVALID rows
        match nothing), attributed to `miss_quarantined`.

        ("fused" here is the host-side batching fusion — one routed
        launch for the whole coalesced batch. The DEVICE-fused Pallas
        GET kernel, `ops/fused.py`, is orthogonal: `plane_get` selects
        it per shard via `ShardedKV._fused_on()`/PMDFC_FUSED, so this
        verb rides it automatically on TPU.)"""
        res, blocked, shards = self._contained("get", keys,
                                               self.skv.plane_get)
        if blocked is not None:
            self._account_blocked(blocked, shards, gets=True)
        self._note_lanes(res)
        return res

    def replica_repair(self) -> int:
        """Device-side anti-entropy compare-and-copy over the replica
        axis (`ShardedKV.replica_repair`); rows repaired land on the
        per-lane `replica{r}_repaired` counters. 0 on 1-D planes."""
        if self.replica_lanes <= 1:
            return 0
        before = self.skv.replica_report()["repaired"]
        total = self.skv.replica_repair()
        after = self.skv.replica_report()["repaired"]
        for r, (_, _, cp) in enumerate(self._c_lane):
            cp.inc(int(after[r]) - int(before[r]))
        return total

    def invalidate(self, keys: np.ndarray) -> np.ndarray:
        out, blocked, shards = self._contained("del", keys,
                                               self.skv.plane_delete)
        if blocked is not None:
            # a quarantined shard must never resurrect a page it was
            # told to forget: journal the blocked invalidations for
            # replay at re-admission (rows answer found=False now)
            kk = np.asarray(keys, np.uint32).reshape(-1, 2)
            for s in np.unique(shards[blocked]):
                self.quarantine.journal_invalidations(
                    int(s), kk[blocked & (shards == s)])
        return out

    def insert_extent(self, key, value, length: int) -> int:
        prof = profiler.active() if tele.enabled() else None
        t0 = tele.stage("ins_ext", "launch")
        _, uncovered = self.skv.insert_extent(key, value, length)
        t1 = tele.stage("ins_ext", "reply")
        dur_us = (t1 - t0) / 1e3
        self._note("ins_ext", None, dur_us, t0, t1)
        if prof is not None:
            # broadcast phase: every shard ran the program — the same
            # ones-vector `_note` uses, so per-shard op attribution
            # reconciles with `mesh.shard{i}_ops` across ALL phases
            prof.note_launch("plane.ins_ext", "ins_ext", dur_us, n_ops=1,
                             counts=np.ones(self.n_shards, np.int64),
                             n_shards=self.n_shards)
        return uncovered

    def get_extent(self, keys: np.ndarray):
        return self._run("get_ext", self.skv.plane_get_extent(keys))

    def packed_bloom(self) -> np.ndarray | None:
        return self.skv.packed_bloom()

    # -- one-sided fast-path surface: the NetServer reader lane reads
    # the stacked per-shard pool mirror directly (zero plane dispatch;
    # the directory's shard column addresses the owning shard) --

    def fast_view(self):
        return self.skv.fast_view()

    def directory_snapshot(self, max_entries: int = 1 << 20):
        return self.skv.directory_snapshot(max_entries=max_entries)

    def bump_dir_epoch(self) -> int:
        return self.skv.bump_dir_epoch()

    # balloon surface (autotune walks cold capacity through the serving
    # backend — per-shard stepping, the ShardedKV contract)
    def balloon_state(self) -> dict | None:
        return self.skv.balloon_state()

    def balloon_grow(self, rows: int) -> bool:
        return self.skv.balloon_grow(rows)

    def balloon_shrink(self, rows: int) -> bool:
        return self.skv.balloon_shrink(rows)

    # admission surface (same contract as the balloon forwards above)
    def admit_state(self) -> dict | None:
        return self.skv.admit_state()

    def set_admit_threshold(self, value: int) -> bool:
        return self.skv.set_admit_threshold(value)

    # host-overlay miss-cause accounting forwards (the NetServer calls
    # these for ops it answered WITHOUT device dispatch — QoS sheds,
    # deadline sheds — so `misses == Σ causes` holds on the mesh path
    # exactly as on the single-device one)
    def account_shed(self, gets: int, puts: int = 0) -> None:
        self.skv.account_shed(gets, puts)

    def account_deadline(self, gets: int, puts: int = 0) -> None:
        self.skv.account_deadline(gets, puts)

    def account_quarantined(self, gets: int, puts: int = 0,
                            shard: int = 0) -> None:
        self.skv.account_quarantined(gets, puts, shard=shard)

    def stats(self) -> dict:
        """Summed KV counters plus the per-shard report — the MSG_STATS
        payload, so one wire pull shows key-space skew per shard."""
        out = dict(self.skv.stats())
        out["capacity"] = self.skv.capacity()
        out["shard_report"] = self.skv.shard_report()
        if self.quarantine is not None:
            # rung-8 visibility: breaker states + invalidation-journal
            # depths per shard ride the same wire pull
            out["quarantine"] = self.quarantine.report()
        rep = self.skv.replica_report()
        if rep is not None:
            # per-lane hedged-read attribution — one wire pull shows
            # which replica lane served and which lane's digest refused
            out["replica"] = rep
        return out

    def warmup(self, max_width: int, kinds=("put", "get", "del")) -> int:
        return warm_plane(self.skv, max_width, kinds)

    def shard_report(self) -> dict:
        return self.skv.shard_report()


def warm_plane(skv, max_width: int, kinds=("put", "get", "del")) -> int:
    """Pre-compile a plane's per-shard pow2 ladder up to `max_width`
    PER SHARD using all-INVALID batches (compile + run the real
    programs; match nothing, place nothing, count nothing). The one
    warm loop both serving drivers share (`PlaneBackend.warmup`,
    `KVServer.warmup` mesh branch). Returns programs warmed.

    w-row batches, NOT w*n_shards: identical INVALID keys all hash to
    ONE shard, so a w-row batch produces per-shard width pow2(w) —
    exactly one rung of the per-shard ladder (a w*n batch would compile
    only n×-oversized widths and leave the real ladder cold)."""
    from pmdfc_tpu.utils.keys import INVALID_WORD

    vw = skv.config.page_words if skv.config.paged else 2
    w = skv._router.pad_floor
    n = 0
    while w <= max_width:
        keys = np.full((w, 2), INVALID_WORD, np.uint32)
        if "put" in kinds:
            skv.plane_insert(keys, np.zeros((w, vw), np.uint32)).fetch()
            n += 1
        if "del" in kinds:
            skv.plane_delete(keys).fetch()
            n += 1
        if "get" in kinds:
            # BOTH get-phase programs (read-only + counting) per rung
            skv.plane_warm_get(keys)
            n += 1
        w <<= 1
    return n


def build_plane_kv(config: KVConfig, mesh=None,
                   knobs: MeshConfig | None = None):
    """Resolve one mesh request into a `ShardedKV` — the single
    resolution rule both serving drivers share (`make_serving_backend`
    and `KVServer(mesh=...)`).

    `mesh` may be a `MeshConfig`, a jax `Mesh`, an int shard count,
    True (all local devices), or None (= `MeshConfig()` defaults);
    `knobs` supplies pad_floor/dispatch when `mesh` is a bare Mesh.
    `MeshConfig.replica_axis > 1` builds the 2-D `(kv, replica)` mesh —
    replication fused into the plane — unless `PMDFC_MESH2D=off`
    forces the lane count back to 1 (the conformance escape hatch:
    same factory, a plain 1-D mesh, zero 2-D programs).
    Returns None when `PMDFC_MESH=off` — the caller falls back to its
    single-device path."""
    if not mesh_enabled():
        return None
    import jax

    from pmdfc_tpu.config import mesh2d_enabled
    from pmdfc_tpu.parallel.shard import ShardedKV, make_mesh, make_mesh2d

    mc = (knobs if knobs is not None
          else mesh if isinstance(mesh, MeshConfig) else MeshConfig())
    rep = mc.replica_axis if mesh2d_enabled() else 1
    if mesh is None or isinstance(mesh, MeshConfig):
        mesh = mc.n_shards if mc.n_shards is not None else True
    if mesh is True:
        if rep > 1:
            n_dev = len(jax.devices())
            if n_dev // rep < 1:
                raise ValueError(
                    f"replica_axis={rep} exceeds the {n_dev} "
                    "available devices")
            mesh = make_mesh2d(n_dev // rep, rep)
        else:
            mesh = make_mesh()
    elif isinstance(mesh, int):
        devs = jax.devices()
        if mesh * rep > len(devs):
            raise ValueError(
                f"mesh n_shards={mesh} x replica_axis={rep} exceeds "
                f"the {len(devs)} available devices")
        mesh = (make_mesh2d(mesh, rep, np.array(devs[:mesh * rep]))
                if rep > 1 else make_mesh(np.array(devs[:mesh])))
    return ShardedKV(config, mesh=mesh, dispatch=mc.dispatch,
                     plane_pad_floor=mc.pad_floor)


def make_serving_backend(config: KVConfig | None = None,
                         mesh_config: MeshConfig | None = None,
                         mesh=None,
                         containment: ContainmentConfig | None = None,
                         fault_plan=None):
    """The serving plane's kill-switch seam.

    Mesh path (default): a `ShardedKV` over `mesh` (or a fresh 1-D mesh
    spanning `mesh_config.n_shards` local devices — a 1-device host
    gets a 1-shard plane, which still buys the read-only GET phase)
    behind a `PlaneBackend`. `PMDFC_MESH=off` falls back to the current
    single-device serving path (`DirectBackend` over `kv.KV`),
    conformance-tested verb-for-verb bit-identical in
    `tests/test_mesh.py`.
    """
    config = config or KVConfig()
    skv = build_plane_kv(
        config, mesh if mesh is not None else mesh_config,
        knobs=mesh_config)
    if skv is None:
        from pmdfc_tpu.client.backends import DirectBackend
        from pmdfc_tpu.kv import KV

        return DirectBackend(KV(config))
    return PlaneBackend(skv, containment=containment,
                        fault_plan=fault_plan)
