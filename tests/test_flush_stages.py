"""The flush loop's stage clock (`telemetry.stage`) and the serving
programs' names.

- A `NetServer` over a 1-shard plane counts every stage of its flush
  thread into `stats` (`stage_<phase>_<stage>_us` / `_n`, plus the flush
  loop's `stage_idle`/`stage_dwell` and the ops' `stage_queue_wait`).
- The stages are back to back: their sum is the flush thread's life.
- They count with the tracing tier off (plain counters).
- A thread nobody bound counts nothing (direct `ShardedKV` use).
- Each plane program's XLA module is named after its `_wrap` name,
  which is what trace reductions match (`plane_get`, `plane_insert`).
"""

import time

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
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    be = make_serving_backend(_cfg(), MeshConfig(n_shards=1))
    srv = NetServer(lambda: be, net=NetConfig())
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
    covered = sum(v for k, v in final.items()
                  if k.endswith("_us") and k != "stage_queue_wait_us")
    assert 0.95 * life_us <= covered <= wall_us, (covered, life_us, wall_us)


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
