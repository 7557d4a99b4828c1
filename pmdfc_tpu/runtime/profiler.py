"""Fetch-time attribution: per-program time blocked on device results.

The host-side telemetry stack (spans, SLO watchdog, series rings)
stops at the dispatch boundary: JAX launches are async, so on the
host clock a program's cost shows only at the FETCH — the first host
access that blocks on the result. This module owns that seam:

- `fetch(program, phase, thunk, ...)` is the ONE sanctioned sync
  point. It times the blocking fetch on the host clock (`fetch_us`:
  the wait for device compute plus the copy back — not device busy
  time, which only a profiler trace gives), takes the gap from the
  launch when the caller has its stamp (`launch_gap_us`), and feeds
  the per-program `fetch_us`/`launch_gap_us` histogram families, the
  per-shard fetch-time lanes, and the windowed `shard_imbalance`
  gauge (max/mean fetch time across shards per window). The serving
  plane times its fetch with the `fetch`/`launch` stage readings
  (`PlaneBackend._run`) and calls `note_launch` itself. Serving
  modules must not call `block_until_ready` themselves (the
  `profiler-seam` analyze rule); warmup paths use `block_ready`.
- `Profiler.start_capture(ms)` runs one bounded `jax.profiler` trace
  under the flight recorder's dump dir with the recorder's own
  cooldown + rotation discipline — the server half of `MSG_PROFILE`.
  It runs without the Python tracer, so the host runs at its real
  speed; the flush loop's stages show as `pmdfc.*` host events.

The profiler is opt-in (`PMDFC_PROF=on` or an explicit `install()`);
when nothing attaches, every seam is a passthrough and telemetry
snapshots stay byte-identical to the v2 schema. When attached, the
registry snapshot gains a `profile` block (schema `pmdfc-telemetry-v3`)
carrying the phase x program x shard attribution table that
`tools/proftool.py` rolls into breakdown tables and Perfetto lanes.
Recording rides the TRACING tier: `PMDFC_TELEMETRY=off` silences the
fetch lanes too, so overhead has exactly two states.
"""
from __future__ import annotations

import os
import shutil
import threading
import time

import numpy as np

from pmdfc_tpu.config import ProfilerConfig, profiler_enabled
from pmdfc_tpu.runtime import telemetry as tele


class Profiler:
    """Device-time accounting attached to ONE telemetry registry as its
    `profile_sink` (mirrors the series collector's attachment pattern).

    All mutable state is guarded by `_lock`; the metric objects
    themselves (histograms/counters/gauges) carry their own locks, so
    `note_launch` holds `_lock` only for the attribution table and the
    imbalance window."""

    def __init__(self, config: ProfilerConfig | None = None,
                 registry=None):
        self.config = config or ProfilerConfig()
        self._reg = registry if registry is not None else tele.get()
        self._sc = self._reg.scope("prof", unique=False)
        # guarded-by: _launches, _n_shards, _shard_us, _shard_ops,
        # guarded-by: _win_us, _win_n, _imbalance, _h_shard, _g_shard,
        # guarded-by: _table, _rows_dropped
        self._lock = threading.Lock()
        # program -> (fetch_us hist, launch_gap_us hist): the per-launch
        # path runs on the serving tier's serialized reply drain, so it
        # indexes a plain dict instead of paying the scope name->metric
        # lookup (registry lock + f-string) twice per launch. Benign
        # race: scope lookups are idempotent, a lost insert just repeats
        # the lookup once.
        self._h_prog: dict = {}
        self._launches = 0
        self._n_shards = 0
        self._shard_us: list[float] = []   # cumulative fetch µs
        self._shard_ops: list[int] = []    # ops attributed (== mesh lanes)
        self._win_us: list[float] = []     # current imbalance window
        self._win_n = 0
        self._imbalance = 0.0              # 0 until one window completes
        self._h_shard: tuple = ()          # fetch_us_s{i} hist family
        self._g_shard: tuple = ()          # shard{i}_fetch_us gauges
        # (phase, program, shard) -> [ops, fetch_us]; shard -1 = host
        # path with no per-shard routing (engine/kv transports)
        self._table: dict = {}
        self._rows_dropped = 0
        self._g_imb = self._sc.gauge("shard_imbalance")
        # guarded-by: _trace_active, _last_trace_t, _trace_seq
        self._trace_lock = threading.Lock()
        self._trace_active = False
        self._last_trace_t = -1e18
        self._trace_seq = 0

    # -- per-launch attribution ------------------------------------

    # caller-holds: _lock
    def _grow(self, n: int) -> None:
        # the shard axis is learned from the first routed launch and
        # only ever widens (elastic resize adds shards)
        while len(self._shard_us) < n:
            self._shard_us.append(0.0)
            self._shard_ops.append(0)
            self._win_us.append(0.0)
        if n > self._n_shards:
            self._n_shards = n
            self._h_shard = self._sc.hist_family("fetch_us", n)
            self._g_shard = tuple(
                self._sc.gauge(f"shard{i}_fetch_us") for i in range(n))

    # caller-holds: _lock
    def _bump_row(self, phase: str, program: str, shard: int,
                  ops: int, us: float) -> None:
        key = (phase, program, shard)
        row = self._table.get(key)
        if row is None:
            if len(self._table) >= self.config.table_max_rows:
                self._rows_dropped += 1
                return
            row = self._table[key] = [0, 0.0]
        row[0] += ops
        row[1] += us

    def note_launch(self, program: str, phase: str, fetch_us: float,
                    launch_gap_us: float = 0.0, n_ops: int = 0,
                    counts=None, n_shards: int = 0) -> None:
        """Attribute one blocking fetch: `fetch_us` is the wall time
        the host spent blocked in the fetch (compute + transfer),
        `launch_gap_us` the launch-to-fetch-begin gap when the caller
        stamped the launch. `counts` (the plane's per-shard routed-op
        vector) splits the fetch time across shards proportionally —
        the SAME vector that feeds `mesh.shard{i}_ops`, so per-shard
        sums reconcile with the span attribution by construction."""
        if not tele.enabled():
            return
        hp = self._h_prog.get(program)
        if hp is None:
            hp = (self._sc.hist(f"{program}.fetch_us"),
                  self._sc.hist(f"{program}.launch_gap_us"))
            self._h_prog[program] = hp
        hp[0].observe(fetch_us)
        if launch_gap_us:
            # only launches with a real stamp feed the gap family — a
            # sync verb's structural 0.0 would just bury the signal
            hp[1].observe(launch_gap_us)
        c = None
        if counts is not None:
            c = np.asarray(counts)
            if not int(c.sum()):
                c = None
        with self._lock:
            self._launches += 1
            if c is None:
                self._bump_row(phase, program, -1, int(n_ops),
                               float(fetch_us))
                return
            self._grow(max(len(c), int(n_shards)))
            total = int(c.sum())
            hot = np.flatnonzero(c)
            for s in hot:
                s = int(s)
                share = float(fetch_us) * (int(c[s]) / total)
                self._shard_us[s] += share
                self._shard_ops[s] += int(c[s])
                self._win_us[s] += share
                self._h_shard[s].observe(share)
                self._bump_row(phase, program, s, int(c[s]), share)
            self._win_n += 1
            if self._win_n >= self.config.imbalance_window:
                # window boundary: the cumulative lane gauges refresh
                # HERE (not per launch) — this path rides the reply
                # drain, and a gauge set per hot shard per launch is
                # lock traffic the snapshot can batch 1/window
                tot = sum(self._win_us)
                if tot > 0:
                    mean = tot / self._n_shards
                    self._imbalance = max(self._win_us) / mean
                    self._g_imb.set(round(self._imbalance, 3))
                for i in range(self._n_shards):
                    self._g_shard[i].set(round(self._shard_us[i], 1))
                self._win_n = 0
                for i in range(len(self._win_us)):
                    self._win_us[i] = 0.0

    # -- bounded on-demand trace (MSG_PROFILE server half) ---------

    def start_capture(self, duration_ms: int) -> dict | None:
        """Start one bounded `jax.profiler` trace under the flight
        recorder's dump dir. Returns `{"path", "duration_ms"}` or None
        when refused: no dump dir configured, a capture is already
        live, or the cooldown has not elapsed — the recorder's "a rung
        firing in a tight loop must not write a dump per op"
        discipline, applied to traces. A daemon timer stops the trace;
        the caller never blocks for the capture window. The Python
        tracer stays off (`python_tracer_level=0`): it traces every
        Python call and would slow the very host path being captured."""
        dump_dir = getattr(self._reg, "dump_dir", None)
        if not dump_dir:
            return None
        now = time.monotonic()
        with self._trace_lock:
            if self._trace_active:
                return None
            if now - self._last_trace_t < self.config.trace_min_interval_s:
                return None
            self._trace_active = True
            self._last_trace_t = now
            self._trace_seq += 1
            seq = self._trace_seq
        dur = max(1, min(int(duration_ms), self.config.trace_max_ms))
        path = os.path.join(dump_dir, f"prof_{seq:05d}")
        try:
            os.makedirs(path, exist_ok=True)
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(path, profiler_options=opts)
        except Exception:  # noqa: BLE001 — capture is advisory
            with self._trace_lock:
                self._trace_active = False
            shutil.rmtree(path, ignore_errors=True)
            return None
        t = threading.Timer(dur / 1e3, self._stop_capture)
        t.daemon = True
        t.start()
        self._rotate_captures(dump_dir)
        return {"path": path, "duration_ms": dur}

    def _stop_capture(self) -> None:
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — device backend may be gone
            pass
        with self._trace_lock:
            self._trace_active = False

    def _rotate_captures(self, dump_dir: str) -> None:
        cap = self.config.trace_max_files
        if not cap:
            return
        try:
            dirs = sorted(
                (e for e in os.scandir(dump_dir)
                 if e.name.startswith("prof_") and e.is_dir()),
                key=lambda e: e.stat().st_mtime)
        except OSError:
            return
        for e in dirs[:-cap]:
            shutil.rmtree(e.path, ignore_errors=True)

    # -- snapshot (the teledump `profile` block) -------------------

    def snapshot(self) -> dict:
        with self._lock:
            # gauges refresh on window boundaries; sync them here so a
            # teledump's gauge view agrees with the profile block even
            # mid-window
            for i in range(self._n_shards):
                self._g_shard[i].set(round(self._shard_us[i], 1))
            rows = [
                {"phase": ph, "program": pr, "shard": s,
                 "ops": row[0], "fetch_us": round(row[1], 1)}
                for (ph, pr, s), row in sorted(self._table.items())
            ]
            doc = {
                "schema": "pmdfc-prof-v2",
                "launches": self._launches,
                "n_shards": self._n_shards,
                "rows": rows,
                "rows_dropped": self._rows_dropped,
                "shard_fetch_us": [round(v, 1) for v in self._shard_us],
                "shard_ops": list(self._shard_ops),
                "imbalance": round(self._imbalance, 3),
            }
        return doc


# -- module plumbing (mirrors telemetry's _STATE discipline) -------

class _ModState:
    __slots__ = ("registry", "prof")

    def __init__(self):
        self.registry = None
        self.prof = None


_S = _ModState()


def install(config: ProfilerConfig | None = None, registry=None) -> Profiler:
    """Attach a profiler to the registry (idempotent) and return it —
    the explicit form of the `PMDFC_PROF=on` lazy attach."""
    reg = registry if registry is not None else tele.get()
    p = getattr(reg, "profile_sink", None)
    if p is None:
        p = Profiler(config=config, registry=reg)
        reg.profile_sink = p
    _S.registry = reg
    _S.prof = p
    return p


def active() -> Profiler | None:
    """The registry's attached profiler, or None (every seam's cheap
    gate). `PMDFC_PROF` is resolved once per registry at first use — a
    `telemetry.configure()` swap re-resolves, matching the kill-switch
    discipline of the other opt-in tiers."""
    reg = tele.get()
    if _S.registry is not reg:
        p = getattr(reg, "profile_sink", None)
        if p is None and profiler_enabled():
            p = Profiler(registry=reg)
            reg.profile_sink = p
        _S.registry = reg
        _S.prof = p
    return _S.prof


def fetch(program: str, phase: str, thunk, *, n_ops: int = 0,
          counts=None, n_shards: int = 0, t_launch_ns: int = 0,
          ring: bool = False):
    """THE sanctioned sync point: run `thunk` (the blocking fetch),
    time it as fetch_us, and attribute. Passthrough when no profiler
    is attached or the tracing tier is off. `ring=True` additionally
    rings a `device` span record (src=prof) so SLO stage attribution
    and tracetool timelines see the device window — plane launches skip
    it (their `shard_program` spans already cover the same window)."""
    p = active()
    if p is None or not tele.enabled():
        return thunk()
    t0 = time.monotonic_ns()
    out = thunk()
    t1 = time.monotonic_ns()
    p.note_launch(program, phase, (t1 - t0) / 1e3,
                  launch_gap_us=max(0.0, (t0 - t_launch_ns) / 1e3)
                  if t_launch_ns else 0.0,
                  n_ops=n_ops, counts=counts, n_shards=n_shards)
    if ring:
        tele.record_tree_span("prof", "device", 0, 0, t0, t1,
                              program=program, phase=phase,
                              ops=int(n_ops))
    return out


def block_ready(x):
    """The ONE sanctioned `block_until_ready` outside `fetch` thunks:
    warmup/teardown sync with nothing worth attributing. Serving
    modules call this instead of `jax.block_until_ready` directly —
    the `profiler-seam` analyze rule flags stray sync points."""
    import jax
    return jax.block_until_ready(x)


def capture(duration_ms: int) -> dict | None:
    p = active()
    return p.start_capture(duration_ms) if p is not None else None
