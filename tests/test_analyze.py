"""Concurrency & JAX-discipline suite drills (marker: analyze).

Three layers, mirroring the tooling itself:

1. **The tree gate** — `run_analysis()` over `pmdfc_tpu/` with the
   checked-in allowlist must be empty (the same invariant
   `python -m tools.analyze` enforces in the agenda).
2. **Seeded fixtures** — known-bad modules (AB/BA inversion, unguarded
   write, platform-unkeyed donation) must each produce their expected
   finding; the clean twins must pass. This is the suite testing the
   SUITE: a rule that silently stopped firing would otherwise look like
   a clean tree.
3. **The runtime sanitizer** — instrumented locks must catch order
   inversions against the declared hierarchy, refuse self-deadlocks,
   and time long holds (condition waits excluded); and a chaos-proxied
   net soak under `PMDFC_SAN` semantics must finish with ZERO reports.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

from tools.analyze import Allowlist, build_model, run_analysis
from tools.analyze import guarded, jaxrules, lockorder
from tools.analyze.resolve import analyze_functions

pytestmark = pytest.mark.analyze

_FIXTURES = os.path.join(os.path.dirname(__file__), "data",
                         "analyze_fixtures")


def _run_all(*names):
    files = [(os.path.join(_FIXTURES, n), n) for n in names]
    model = build_model(files)
    facts = analyze_functions(model)
    allow = Allowlist({})
    return (guarded.run(model, facts, allow)
            + lockorder.run(model, facts, allow)
            + jaxrules.run(model, allow))


# --- 1. the tree gate ------------------------------------------------------


def test_tree_is_clean_under_checked_in_allowlist():
    findings, stale = run_analysis()
    assert not findings, "\n".join(str(f) for f in findings)
    assert not stale, f"stale allowlist entries: {stale}"


def test_lock_hierarchy_covers_every_ranked_module_lock():
    # every lock the model finds in the instrumented serving modules must
    # have a rank — a new lock without one silently opts out of both the
    # static rank rule and the runtime order check. The module set is the
    # analyzer's own (lockorder.RANKED_MODULES drives the unranked-lock
    # rule inside `python -m tools.analyze`); this drill re-checks it
    # directly so the rule and the table can't drift apart, and pins that
    # the mesh serving plane's modules are covered.
    from pmdfc_tpu.runtime.sanitizer import HIERARCHY

    findings, _ = run_analysis()
    assert not findings  # precondition: tree parses + passes
    from tools.analyze import DEFAULT_ROOTS
    from tools.analyze.model import collect_files

    assert {"parallel/shard.py", "parallel/partitioning.py",
            "parallel/plane.py",
            "runtime/slo.py"} <= lockorder.RANKED_MODULES
    model = build_model(collect_files(DEFAULT_ROOTS))
    missing = []
    for decl in model.all_locks():
        mod = decl.module.path.split("pmdfc_tpu/", 1)[-1]
        if mod in lockorder.RANKED_MODULES \
                and decl.lock_id not in HIERARCHY:
            missing.append(decl.lock_id)
    assert not missing, f"locks without a declared rank: {missing}"


def test_unranked_serving_lock_is_a_finding(monkeypatch):
    # the coverage gate itself: strip a serving-plane lock's rank and the
    # unranked-lock rule must fire with a stable id
    from pmdfc_tpu.runtime import sanitizer

    stripped = {k: v for k, v in sanitizer.HIERARCHY.items()
                if k != "ShardedKV._lock"}
    monkeypatch.setattr(sanitizer, "HIERARCHY", stripped)
    from tools.analyze import DEFAULT_ROOTS
    from tools.analyze.model import collect_files

    model = build_model(collect_files(DEFAULT_ROOTS))
    facts = analyze_functions(model)
    found = lockorder.run(model, facts, Allowlist({}))
    unranked = [f for f in found if f.rule == "unranked-lock"]
    assert any(f.ident == "unranked-lock:ShardedKV._lock"
               for f in unranked), found


def test_unranked_slo_lock_is_a_finding(monkeypatch):
    # ISSUE 9 satellite: runtime/slo.py is a RANKED module — a lock the
    # SLO watchdog grows WITHOUT a HIERARCHY rank must be a finding in
    # `python -m tools.analyze`, not a silent opt-out (same drill shape
    # as the mesh-plane coverage gate above)
    from pmdfc_tpu.runtime import sanitizer

    stripped = {k: v for k, v in sanitizer.HIERARCHY.items()
                if k != "SloWatchdog._lock"}
    monkeypatch.setattr(sanitizer, "HIERARCHY", stripped)
    from tools.analyze import DEFAULT_ROOTS
    from tools.analyze.model import collect_files

    model = build_model(collect_files(DEFAULT_ROOTS))
    facts = analyze_functions(model)
    found = lockorder.run(model, facts, Allowlist({}))
    assert any(f.ident == "unranked-lock:SloWatchdog._lock"
               and f.rule == "unranked-lock" for f in found), found


# --- 2. seeded fixtures ----------------------------------------------------


def test_bad_inversion_fixture_yields_lock_order_cycle():
    found = _run_all("bad_inversion.py")
    cycles = [f for f in found if f.rule == "lock-order"]
    assert cycles, found
    assert any("Pair.lock_a" in f.message and "Pair.lock_b" in f.message
               for f in cycles)


def test_bad_unguarded_fixture_yields_guarded_write():
    found = _run_all("bad_unguarded.py")
    writes = [f for f in found if f.rule == "guarded-write"]
    assert len(writes) == 1, found
    assert "closed" in writes[0].message
    assert writes[0].ident == \
        "guarded-write:bad_unguarded.py:Box.drop:closed"


def test_bad_donation_fixture_yields_jax_donation():
    found = _run_all("bad_donation.py")
    dons = [f for f in found if f.rule == "jax-donation"]
    assert len(dons) == 1, found
    assert dons[0].ident == "jax-donation:bad_donation.py:scatter"


def test_bad_shardmap_donation_fixture_yields_jax_donation():
    # the mesh-plane shape of the donation class: a shard_map-wrapped
    # program donated without platform keying must fire the same rule
    found = _run_all("bad_donation_shardmap.py")
    dons = [f for f in found if f.rule == "jax-donation"]
    assert len(dons) == 1, found
    assert dons[0].ident == "jax-donation:bad_donation_shardmap.py:build"


def test_bad_pallas_gate_fixture_yields_finding():
    # an unconditional Mosaic lowering (no interpret= fallback, no
    # platform guard anywhere in the module) is the TPU-only-path bug
    found = _run_all("bad_pallas_gate.py")
    gates = [f for f in found if f.rule == "pallas-platform-gate"]
    assert len(gates) == 1, found
    assert gates[0].ident == "pallas-platform-gate:bad_pallas_gate.py:launch"


def test_interpret_false_literal_is_still_unconditional(tmp_path):
    # `interpret=False` is the same as omitting the kwarg — the call is
    # Mosaic-only on every backend, so it must NOT satisfy the gate
    src = tmp_path / "lit.py"
    src.write_text(
        "from jax.experimental import pallas as pl\n"
        "def go(x, k, s):\n"
        "    return pl.pallas_call(k, out_shape=s, interpret=False)(x)\n")
    model = build_model([(str(src), "lit.py")])
    found = jaxrules.run(model, Allowlist({}))
    assert [f.rule for f in found] == ["pallas-platform-gate"], found


def test_bad_profiler_seam_fixture_yields_findings():
    # a raw device sync outside runtime/profiler.py is unattributable
    # device time — both the `jax.block_until_ready(...)` form and the
    # `.block_until_ready()` method form must fire, with def-stable ids
    found = _run_all("bad_profiler_seam.py")
    seams = [f for f in found if f.rule == "profiler-seam"]
    assert len(seams) == 2, found
    assert {f.ident for f in seams} == {
        "profiler-seam:bad_profiler_seam.py:fetch_result",
        "profiler-seam:bad_profiler_seam.py:drain",
    }


def test_profiler_seam_exempts_bench_and_the_seam_itself(tmp_path):
    # the same leaky source under a bench/ path or as the profiler
    # module itself is the sanctioned raw boundary — no finding
    src = ("import jax\n"
           "def measure(x):\n"
           "    return jax.block_until_ready(x)\n")
    bench = tmp_path / "bench"
    bench.mkdir()
    (bench / "lat.py").write_text(src)
    prof = tmp_path / "profiler.py"
    prof.write_text(src)
    model = build_model([
        (str(bench / "lat.py"), "pmdfc_tpu/bench/lat.py"),
        (str(prof), "pmdfc_tpu/runtime/profiler.py"),
    ])
    found = jaxrules.run(model, Allowlist({}))
    assert [f for f in found if f.rule == "profiler-seam"] == [], found


def test_clean_fixtures_pass():
    assert _run_all("clean_locks.py") == []
    assert _run_all("clean_donation.py") == []
    # the canonical shared helper (`from pmdfc_tpu.kv import _donate`,
    # the onesided.py pattern) also counts as platform keying
    assert _run_all("clean_donation_shared.py") == []
    # platform-keyed shard_map donation (the parallel/shard._wrap shape)
    assert _run_all("clean_donation_shardmap.py") == []
    # platform-keyed pallas launches (interpret= fallback / backend
    # branch, the ops/fused.py idiom)
    assert _run_all("clean_pallas_gate.py") == []
    # device syncs routed through the profiler seam (fetch thunks +
    # block_ready warmups, the runtime/profiler.py discipline)
    assert _run_all("clean_profiler_seam.py") == []


def test_local_donate_spoof_does_not_count_as_guard():
    # a module-local `def _donate()` (arbitrary policy) must NOT satisfy
    # the rule — only the canonical kv import does
    found = _run_all("bad_donation_spoof.py")
    assert [f.rule for f in found] == ["jax-donation"], found


def test_allowlist_suppresses_and_reports_stale():
    files = [(os.path.join(_FIXTURES, "bad_unguarded.py"),
              "bad_unguarded.py")]
    model = build_model(files)
    facts = analyze_functions(model)
    allow = Allowlist({
        "guarded-write:bad_unguarded.py:Box.drop:closed": "drill",
        "guarded-write:bad_unguarded.py:Box.gone:items": "stale entry",
    })
    assert guarded.run(model, facts, allow) == []
    assert allow.unused() == \
        ["guarded-write:bad_unguarded.py:Box.gone:items"]


def test_lambda_body_does_not_fabricate_lock_order_edges(tmp_path):
    # a lambda CONSTRUCTED under a lock is deferred work: nothing in its
    # body runs under that lock, so no edge may come from it (a phantom
    # edge here could report a fake AB/BA cycle on correct code)
    src = '''
import threading

class A:
    def __init__(self):
        # guarded-by: <none>  (fixture)
        self.lock_a = threading.Lock()
        # guarded-by: <none>  (fixture)
        self.lock_b = threading.Lock()

    def inner(self):
        with self.lock_a:
            pass

    def defer(self):
        with self.lock_b:
            cb = lambda: self.inner()   # noqa: E731
        return cb

    def order(self):
        with self.lock_a:
            with self.lock_b:
                pass
'''
    p = tmp_path / "lam.py"
    p.write_text(src)
    model = build_model([(str(p), "lam.py")])
    facts = analyze_functions(model)
    found = lockorder.run(model, facts, Allowlist({}))
    assert found == [], found


def test_lexical_self_reacquire_is_flagged(tmp_path):
    # `with L: with L:` on a non-reentrant Lock is a certain deadlock —
    # the static side must see the lexical form, not just call summaries
    src = '''
import threading

class B:
    def __init__(self):
        # guarded-by: <none>  (fixture)
        self._lock = threading.Lock()
        # guarded-by: <none>  (fixture)
        self._rlock = threading.RLock()

    def bad(self):
        with self._lock:
            with self._lock:
                pass

    def fine(self):
        with self._rlock:
            with self._rlock:
                pass
'''
    p = tmp_path / "self.py"
    p.write_text(src)
    model = build_model([(str(p), "self.py")])
    facts = analyze_functions(model)
    found = lockorder.run(model, facts, Allowlist({}))
    assert [f.ident for f in found] == \
        ["lock-order:B._lock->B._lock"], found


def test_wire_drift_rule_catches_constant_divergence(tmp_path):
    twin = tmp_path / "runtime"
    twin.mkdir()
    (twin / "net.py").write_text("MSG_PUTPAGE = 3\nPIPE_FLAG = 0x100\n")
    drifted = tmp_path / "peer.py"
    drifted.write_text("MSG_PUTPAGE = 4\nTRACE_FLAG = 0x10\n")
    model = build_model([(str(twin / "net.py"), "runtime/net.py"),
                         (str(drifted), "peer.py")])
    found = jaxrules.run(model, Allowlist({}))
    idents = {f.ident for f in found}
    assert "wire-drift:peer.py:MSG_PUTPAGE" in idents   # value drift
    assert "wire-drift:peer.py:TRACE_FLAG" in idents    # flag in chan byte


# --- 3. the runtime sanitizer ---------------------------------------------


@pytest.fixture
def san_on():
    from pmdfc_tpu.runtime import sanitizer

    sanitizer.configure(on=True, strict=False, hold_ms=200.0)
    sanitizer.reset()
    yield sanitizer
    sanitizer.reset()
    sanitizer.configure(on=False)


def test_sanitizer_off_returns_plain_primitives():
    from pmdfc_tpu.runtime import sanitizer

    sanitizer.configure(on=False)
    assert type(sanitizer.lock("x")) is type(threading.Lock())
    assert isinstance(sanitizer.condition("y"),
                      type(threading.Condition()))


def test_sanitizer_detects_ab_ba_inversion(san_on):
    a = san_on.lock("NetServer._push_cycle_lock")  # rank 32
    b = san_on.lock("KV._lock")                # rank 65 (inner)
    with a:
        with b:
            pass
    assert san_on.violations() == []           # declared order: clean
    with b:
        with a:                                # against the hierarchy
            pass
    v = san_on.violations()
    assert len(v) == 1 and v[0]["kind"] == "inversion"
    assert v[0]["acquired"] == "NetServer._push_cycle_lock"
    assert v[0]["while_holding"] == "KV._lock"


def test_sanitizer_refuses_self_deadlock(san_on):
    lk = san_on.lock("NetServer._push_cycle_lock")
    with lk:
        with pytest.raises(RuntimeError, match="re-acquired"):
            lk.acquire()
    assert [v["kind"] for v in san_on.violations()] == ["reacquire"]
    # and the lock still works after the refusal
    with lk:
        pass


def test_sanitizer_rlock_reentry_is_legal(san_on):
    rl = san_on.rlock("KV._lock")
    with rl:
        with rl:
            pass
    assert san_on.violations() == []


def test_sanitizer_times_long_holds_on_watched_locks(san_on):
    san_on.configure(hold_ms=20.0)
    cv = san_on.condition("NetServer._flush_cv")   # in HOLD_WATCH
    with cv:
        time.sleep(0.06)
    v = san_on.violations()
    assert len(v) == 1 and v[0]["kind"] == "long_hold"
    assert v[0]["held_ms"] >= 20.0
    san_on.reset()
    # an UNwatched lock may hold long (device dispatch under KV._lock)
    lk = san_on.rlock("KV._lock")
    with lk:
        time.sleep(0.06)
    assert san_on.violations() == []


def test_sanitizer_condition_wait_does_not_count_as_holding(san_on):
    san_on.configure(hold_ms=20.0)
    cv = san_on.condition("NetServer._flush_cv")
    with cv:
        cv.wait(0.06)      # parked, not holding
    assert san_on.violations() == []


def test_sanitizer_condition_is_reentrant_like_the_primitive(san_on):
    # threading.Condition()'s default lock is an RLock: nested
    # `with cv:` is legal and must not be reported — and a wait from
    # the nested depth must fully release and restore it (Condition
    # releases ALL recursion levels via _release_save)
    cv = san_on.condition("NetServer._flush_cv")
    with cv:
        with cv:
            cv.wait(0.01)
        cv.notify_all()    # still held after the nested exit
    assert san_on.violations() == []
    # and the condition is actually free afterwards: another thread
    # can take it (a leaked recursion level would hang here)
    got = []
    t = threading.Thread(target=lambda: (cv.acquire(), got.append(1),
                                         cv.release()))
    t.start(); t.join(2.0)
    assert got == [1]


def test_none_guard_with_justification_declares_no_fields(tmp_path):
    # `# guarded-by: <none>  (reason...)` is the convention's dominant
    # form; the justification must not be comma-split into phantom
    # guarded fields (a phantom matching a real attribute elsewhere
    # would fabricate guarded-write findings on unrelated classes)
    p = tmp_path / "none_guard.py"
    p.write_text(
        "import threading\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        # guarded-by: <none>  (pure section, alive, stats)\n"
        "        self._lock = threading.Lock()\n")
    model = build_model([(str(p), "none_guard.py")])
    c = model.modules["none_guard.py"].classes["C"]
    assert model.find_lock(c, "_lock").guards == []
    assert dict(c.guarded) == {}


def test_sanitizer_nonblocking_self_probe_returns_false(san_on):
    # acquire(blocking=False) on a self-held lock cannot deadlock:
    # plain threading.Lock returns False there, so must the wrapper
    lk = san_on.lock("NetServer._push_cycle_lock")
    with lk:
        assert lk.acquire(blocking=False) is False
    assert san_on.violations() == []
    with lk:       # still usable, no leaked state
        pass


def test_sanitizer_flush_runs_after_the_physical_release(san_on):
    # the deferred telemetry/rung half (which can write a flight dump)
    # must run AFTER the wrapped primitive is dropped, not merely after
    # the held-set empties — otherwise the dump IO runs inside the very
    # critical section being timed and convoys its waiters
    from pmdfc_tpu.runtime import sanitizer as san_mod
    san_on.configure(hold_ms=5.0)
    lk = san_on.lock("NetServer._flush_cv")  # in HOLD_WATCH
    seen = []
    orig = san_mod._flush_pending

    def spy():
        seen.append(lk._inner.locked())
        orig()

    san_mod._flush_pending = spy
    try:
        with lk:
            time.sleep(0.02)               # trips the long-hold report
    finally:
        san_mod._flush_pending = orig
    assert [v["kind"] for v in san_on.violations()] == ["long_hold"]
    assert seen == [False]                 # inner lock already released


def test_sanitizer_violations_reach_telemetry(san_on):
    from pmdfc_tpu.runtime import telemetry as tele

    tele.configure()
    b = san_on.lock("KV._lock")
    a = san_on.lock("NetServer._push_cycle_lock")
    with b, a:
        # the violation is RECORDED immediately but its telemetry/rung
        # half (which can write a flight dump) must be deferred until
        # this thread has dropped every lock — dump IO inside the very
        # critical section being timed would convoy the serving path
        assert len(san_on.violations()) == 1
        mid = tele.snapshot()["counters"]
        assert not any(k == "rung.sanitizer_violation" and v
                       for k, v in mid.items())
    snap = tele.snapshot()
    assert snap["counters"].get("sanitizer0.inversions", 0) >= 1 or any(
        k.endswith(".inversions") and v >= 1
        for k, v in snap["counters"].items())
    assert snap["counters"].get("rung.sanitizer_violation", 0) >= 1 or any(
        k == "rung.sanitizer_violation" and v >= 1
        for k, v in snap["counters"].items())


# --- 3b. instrumented serving plane under chaos ---------------------------


W = 16


def _keys(n, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(1 << 22, size=n, replace=False)
    return np.stack([flat >> 11, flat & 0x7FF], -1).astype(np.uint32)


def _pages(keys):
    return (keys[:, 0] * 7 + keys[:, 1])[:, None] + np.arange(
        W, dtype=np.uint32)


@pytest.mark.slow
def test_chaos_soak_under_sanitizer_reports_nothing(san_on):
    """The acceptance drill: coalesced server + pipelined clients +
    seeded net chaos, every lock instrumented — the soak must complete
    with zero wrong bytes AND zero sanitizer reports."""
    from pmdfc_tpu.client.backends import LocalBackend
    from pmdfc_tpu.config import NetConfig
    from pmdfc_tpu.runtime.failure import ChaosProxy, ReconnectingClient
    from pmdfc_tpu.runtime.net import NetServer, TcpBackend

    shared = LocalBackend(page_words=W, capacity=1 << 12)
    srv = NetServer(lambda: shared, net=NetConfig(
        flush_ops=64, flush_timeout_us=500, settle_us=100)).start()
    proxy = ChaosProxy("127.0.0.1", srv.port, seed=7,
                       rates={"flip": 0.01, "duplicate": 0.005,
                              "delay": 0.01}, delay_s=0.002)
    stop = threading.Event()
    errors: list[BaseException] = []

    def worker(t):
        rc = ReconnectingClient(
            lambda: TcpBackend("127.0.0.1", proxy.port, page_words=W,
                               op_timeout_s=2.0, keepalive_s=None),
            page_words=W, retry_delay_s=0.01, seed=t)
        rng = np.random.default_rng(100 + t)
        try:
            while not stop.is_set():
                keys = _keys(int(rng.integers(1, 32)),
                             seed=int(rng.integers(1 << 16)))
                rc.put(keys, _pages(keys))
                out, found = rc.get(keys)
                # zero wrong bytes: served rows must match their content
                if found.any():
                    assert np.array_equal(out[found], _pages(keys)[found])
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
        finally:
            rc.close()

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(4)]
    with srv, proxy:
        for t in threads:
            t.start()
        time.sleep(3.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors, errors
    assert san_on.violations() == [], san_on.violations()
