"""The flush loop's stage clock (`telemetry.stage`) and the serving
programs' names.

- A `NetServer` over a 1-shard plane counts every stage of its flush
  thread into `stats` (`stage_<phase>_<stage>_us` / `_n`, plus the flush
  loop's `stage_idle`/`stage_dwell` and the ops' `stage_queue_wait`).
- The stages are back to back: their sum is the flush thread's life.
- They count with the tracing tier off (plain counters).
- A thread nobody bound counts nothing (direct `ShardedKV` use).
- Each stage also counts the thread's CPU time (`_cpu_us`), which leaves
  the wall-clock counts as they were; a wait for the GIL counts as time
  off the CPU.
- Each plane program's XLA module is named after its `_wrap` name,
  which is what trace reductions match (`plane_get`, `plane_insert`).
"""

import sys
import threading
import time
import types

import numpy as np
import pytest

from pmdfc_tpu.config import (BloomConfig, IndexConfig, KVConfig, MeshConfig,
                              NetConfig, TelemetryConfig)
from pmdfc_tpu.runtime import telemetry as tele

W = 16
PHASE_STAGES = ("prep", "route", "launch", "fetch", "reply")


def _cfg():
    return KVConfig(index=IndexConfig(capacity=1 << 10),
                    bloom=BloomConfig(num_bits=1 << 14),
                    paged=True, page_words=W)


def _keys(n, seed):
    flat = np.random.default_rng(seed).choice(1 << 20, size=n,
                                              replace=False)
    return np.stack([flat >> 10, flat & 0x3FF], -1).astype(np.uint32)


def _pages(keys):
    return ((keys[:, 0] * np.uint32(31) + keys[:, 1])[:, None]
            + np.arange(1, W + 1, dtype=np.uint32)[None, :])


@pytest.fixture()
def fresh_registry():
    reg = tele.configure(TelemetryConfig(ring_capacity=1 << 14))
    yield reg
    tele.configure()


def _mixed_flushes(c, rounds, seed):
    for i in range(rounds):
        keys = _keys(48, seed + i)
        c.put(keys, _pages(keys))
        out, found = c.get(keys)
        assert found.all() and (out == _pages(keys)).all()
        c.invalidate(keys[:8])


def _stages(stats) -> dict:
    return {k: v for k, v in dict(stats).items() if k.startswith("stage_")}


def _names():
    out = ["stage_idle", "stage_dwell", "stage_queue_wait"]
    for ph in ("put", "get", "del"):
        out += [f"stage_{ph}_{st}" for st in PHASE_STAGES]
    return out


def test_stages_count_and_cover_the_flush_thread(fresh_registry):
    from pmdfc_tpu.parallel.plane import make_serving_backend
    from pmdfc_tpu.runtime import net as net_mod
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    be = make_serving_backend(_cfg(), MeshConfig(n_shards=1))
    srv = NetServer(lambda: be, net=NetConfig())
    # the first start() in a process collects and freezes the heap before
    # the flush thread exists (up to ~1 s in a worker that ran other
    # tests first): do that outside the bracket of the thread's life
    net_mod._freeze_warm_heap()
    t_start = time.monotonic_ns()
    srv.start()
    try:
        with TcpBackend("127.0.0.1", srv.port, page_words=W) as c:
            _mixed_flushes(c, 2, seed=1)   # compiles happen here
            s0 = _stages(srv.stats)
            _mixed_flushes(c, 3, seed=10)
            s1 = _stages(srv.stats)
            # with the tracing tier off the stages still count
            tele.set_enabled(False)
            try:
                _mixed_flushes(c, 2, seed=20)
                s2 = _stages(srv.stats)
            finally:
                tele.set_enabled(True)
        time.sleep(2.0)   # idle time the clock must see too
    finally:
        t_stop = time.monotonic_ns()
        srv.stop()
    # the flush thread lives from start() until shortly after the stop
    # flag; stop() itself may wait longer on the connection threads
    life_us = (t_stop - t_start) / 1e3
    wall_us = (time.monotonic_ns() - t_start) / 1e3
    for name in _names():
        for snap, prev in ((s1, s0), (s2, s1)):
            assert snap[f"{name}_n"] > prev.get(f"{name}_n", 0), name
            assert snap[f"{name}_us"] >= prev.get(f"{name}_us", 0), name
    # back to back: after stop() the sum of the thread's stages is its
    # whole life, which the test's own clock brackets
    final = _stages(srv.stats)
    # every op's queue wait is counted once
    assert final["stage_queue_wait_n"] == srv.stats["coalesced_ops"]
    walls = [n for n in _names() if n != "stage_queue_wait"] + [
        k[:-2] for k in final
        if k.endswith("_n") and k[:-2] not in _names()]
    covered = sum(final[f"{n}_us"] for n in walls)
    assert covered == sum(v for k, v in final.items()
                          if k.endswith("_us") and k != "stage_queue_wait_us"
                          and not k.endswith("_cpu_us"))
    assert 0.95 * life_us <= covered <= wall_us, (covered, life_us, wall_us)
    # each stage's CPU time lies within its wall time, give or take the
    # skew of two clocks read one after the other (up to ~3 µs a close
    # seen on a CPU)
    for n in walls:
        assert 0 <= final[f"{n}_cpu_us"] <= final[f"{n}_us"] \
            + 5 * final[f"{n}_n"], n


def test_unbound_threads_count_nothing(fresh_registry):
    import jax

    from pmdfc_tpu.parallel.plane import PlaneBackend
    from pmdfc_tpu.parallel.shard import ShardedKV, make_mesh

    skv = ShardedKV(_cfg(), mesh=make_mesh(np.array(jax.devices()[:1])))
    be = PlaneBackend(skv)
    keys = _keys(32, seed=3)
    be.put(keys, _pages(keys))
    _, found = be.get(keys)
    assert found.all()
    skv.plane_delete(keys[:4]).fetch()
    counters = tele.get().snapshot()["counters"]
    assert not [k for k in counters if ".stage_" in k]
    # and the test thread keeps no open stage
    assert tele._SPAN_TLS.stage is None
    # a bound thread does: one stage, closed on unbind into its scope
    sc = tele.scope("stagetest")
    tele.bind_stages(sc)
    try:
        t0 = tele.stage("get", "prep")
        assert tele.stage("get", "prep") == t0   # re-entering is a no-op
        t1 = tele.stage(None, "idle")
        assert t1 >= t0
    finally:
        tele.bind_stages(None)
    assert sc["stage_get_prep_n"] == 1 and sc["stage_idle_n"] == 1
    assert sc["stage_get_prep_us"] == (t1 - t0 + 500) // 1000


def test_plane_programs_are_named_after_their_wrap_name(fresh_registry):
    import jax

    from pmdfc_tpu.parallel.plane import warm_plane
    from pmdfc_tpu.parallel.shard import ShardedKV, make_mesh
    from pmdfc_tpu.utils.keys import INVALID_WORD

    skv = ShardedKV(_cfg(), mesh=make_mesh(np.array(jax.devices()[:1])))
    warm_plane(skv, 8)
    rb = skv._router.build(np.full((8, 2), INVALID_WORD, np.uint32),
                           np.zeros((8, W), np.uint32))
    names = {}
    for key, fn in skv._jits.items():
        name = key[0]
        args = (skv.state, rb.keys, rb.values) \
            if name.startswith("plane_insert") else (skv.state, rb.keys)
        names[name] = fn.lower(*args).as_text().split("\n", 1)[0]
    assert {"plane_insert", "plane_get_ro", "plane_delete"} <= set(names)
    for name, head in names.items():
        assert head.startswith(f"module @jit_{name} "), (name, head)


# -- work and waiting: the thread CPU clock at each boundary ---------------

SUFFIXES = ("_us", "_n", "_cpu_us")


@pytest.fixture()
def bound(fresh_registry):
    """A scope bound as this thread's stage counters, unbound after."""
    sc = tele.scope("cpuclock")
    tele.bind_stages(sc)
    yield sc
    tele.bind_stages(None)


def _spin(seconds):
    t = time.monotonic()
    while time.monotonic() - t < seconds:
        pass


def test_a_spinning_stage_counts_cpu(bound):
    """A thread that spins holding the GIL is on a CPU or waiting for
    one: on an idle machine its CPU time is (nearly) all of its wall
    time. One `sum` over a range runs in C and never lets the GIL go, so
    no other thread of the process can make it wait."""
    t = time.perf_counter()
    sum(range(100_000))
    n = int(100_000 * 0.025 / max(time.perf_counter() - t, 1e-5))
    tele.stage("get", "fetch")
    sum(range(n))
    tele.stage(None, "idle")
    wall = bound["stage_get_fetch_us"]
    cpu = bound["stage_get_fetch_cpu_us"]
    assert wall >= 5_000
    assert cpu >= 0.8 * wall, dict(bound)


def test_a_sleeping_stage_counts_little_cpu(bound):
    tele.stage("put", "fetch")
    time.sleep(0.02)
    tele.stage(None, "idle")
    assert bound["stage_put_fetch_us"] >= 20_000
    assert bound["stage_put_fetch_cpu_us"] <= 0.2 * bound["stage_put_fetch_us"]


def test_a_gil_wait_counts_off_the_cpu(bound):
    """A stage that lets go of the GIL while another thread runs Python
    waits to get it back, off its CPU: the flush thread's blocked time.
    Each `time.sleep(0)` hands the GIL to the spinner, which keeps it
    for a switch interval (5 ms here) before it must give it back."""
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    prev = sys.getswitchinterval()
    sys.setswitchinterval(0.005)
    th = threading.Thread(target=spin)
    th.start()
    try:
        time.sleep(0.01)
        tele.stage("put", "prep")
        for _ in range(20):
            time.sleep(0)
        tele.stage(None, "idle")
    finally:
        stop.set()
        th.join()
        sys.setswitchinterval(prev)
    wall = bound["stage_put_prep_us"]
    assert wall >= 50_000, dict(bound)
    assert bound["stage_put_prep_cpu_us"] <= 0.3 * wall, dict(bound)


def test_the_readings_count_with_tracing_off(bound):
    tele.set_enabled(False)
    try:
        tele.stage("get", "prep")
        _spin(0.005)
        tele.stage(None, "idle")
    finally:
        tele.set_enabled(True)
    assert bound["stage_get_prep_n"] == 1
    assert bound["stage_get_prep_cpu_us"] > 0


def test_an_unbound_thread_counts_no_readings(fresh_registry):
    def work():
        tele.stage("get", "fetch")
        _spin(0.005)
        tele.stage(None, "idle")

    th = threading.Thread(target=work)
    th.start()
    th.join()
    work()   # and the test thread, which nobody bound either
    counters = tele.get().snapshot()["counters"]
    assert not [k for k in counters if ".stage_" in k]


def test_the_wall_clock_counts_are_unchanged(fresh_registry, monkeypatch):
    """The CPU clock adds a counter beside `_us`/`_n` and nothing else: on
    a fixed sequence of clock readings those two read as the clock alone
    says, and the same stages exist."""
    ticks = iter([1_000, 5_400, 9_000, 9_499, 1_010_000, 1_012_345,
                  1_020_000])
    fake = types.SimpleNamespace(**{k: getattr(time, k) for k in dir(time)
                                    if not k.startswith("_")})
    fake.monotonic_ns = lambda: next(ticks)
    monkeypatch.setattr(tele, "time", fake)
    sc = tele.scope("fixedclock")
    tele.bind_stages(sc)
    try:
        tele.stage(None, "idle")           # 1_000
        tele.stage(None, "dwell")          # 5_400
        tele.stage("get", "prep")          # 9_000
        tele.stage("get", "prep")          # re-entering reads no clock
        tele.stage("get", "route")         # 9_499
        tele.stage("put", "prep")          # 1_010_000
        tele.stage(None, "idle")           # 1_012_345
    finally:
        tele.bind_stages(None)             # 1_020_000
    walls = {k: v for k, v in sc.items() if k.endswith(("_us", "_n"))
             and not k.endswith("_cpu_us")}
    assert walls == {
        "stage_idle_us": (5_400 - 1_000 + 500) // 1000
        + (1_020_000 - 1_012_345 + 500) // 1000, "stage_idle_n": 2,
        "stage_dwell_us": (9_000 - 5_400 + 500) // 1000, "stage_dwell_n": 1,
        "stage_get_prep_us": (9_499 - 9_000 + 500) // 1000,
        "stage_get_prep_n": 1,
        "stage_get_route_us": (1_010_000 - 9_499 + 500) // 1000,
        "stage_get_route_n": 1,
        "stage_put_prep_us": (1_012_345 - 1_010_000 + 500) // 1000,
        "stage_put_prep_n": 1}
    bases = {k[:-2] for k in walls if k.endswith("_n")}
    for k in sc:
        assert any(k == b + s for b in bases for s in SUFFIXES), k
