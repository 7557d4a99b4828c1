"""CPU tests of the flush-stage readers (`benchmark/metrics/_stage.py` and
the eight metrics on it): hand-built counter snapshots, a program that
lacks the counters, and a tiny traced run of the harness that must report
all eight."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from benchmark import run, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STAGE_METRICS = ("flush_idle_share", "flush_dwell_share", "queue_wait_ms",
                 "host_prep_share", "route_share", "launch_share",
                 "fetch_share", "reply_share")
SHARES = [m for m in STAGE_METRICS if m != "queue_wait_ms"]


def _read(name, ctx):
    return run.load_reader(os.path.join(REPO, "benchmark"), name)(ctx)


def _ctx():
    """A 10 s window: idle 2 s, dwell 1 s, and two phases whose stages
    add to 6.5 s; 4,000 ops waited 8 s in all."""
    net0 = {"coalesced_ops": 1000, "stage_idle_us": 5_000_000,
            "stage_put_prep_us": 7, "stage_queue_wait_us": 1_000_000}
    net1 = {"coalesced_ops": 5000,
            "stage_idle_us": 7_000_000, "stage_dwell_us": 1_000_000,
            "stage_put_prep_us": 500_007, "stage_get_prep_us": 500_000,
            "stage_put_route_us": 250_000, "stage_get_route_us": 250_000,
            "stage_put_launch_us": 1_000_000,
            "stage_get_launch_us": 500_000,
            "stage_put_fetch_us": 1_500_000,
            "stage_get_fetch_us": 1_000_000,
            "stage_put_reply_us": 500_000, "stage_get_reply_us": 500_000,
            "stage_queue_wait_us": 9_000_000}
    return {"window_s": 10.0, "net0": net0, "net1": net1}


def test_stage_readers_on_hand_built_counters():
    ctx = _ctx()
    got = {m: _read(m, ctx) for m in STAGE_METRICS}
    assert got == pytest.approx({
        "flush_idle_share": 20.0, "flush_dwell_share": 10.0,
        "host_prep_share": 10.0, "route_share": 5.0, "launch_share": 15.0,
        "fetch_share": 25.0, "reply_share": 10.0,
        "queue_wait_ms": 8e6 / 4000 / 1000})
    # the stages cover the window
    assert sum(got[m] for m in SHARES) == pytest.approx(95.0)


def test_stage_readers_read_nothing_without_the_counters():
    """The parent program keeps no stage counters: no reading, no error."""
    ctx = {"window_s": 10.0, "net0": {"coalesced_ops": 0},
           "net1": {"coalesced_ops": 100}}
    assert {m: _read(m, ctx) for m in STAGE_METRICS} == \
        dict.fromkeys(STAGE_METRICS)
    # no op in the window, no queue wait
    ctx = _ctx()
    ctx["net1"]["coalesced_ops"] = ctx["net0"]["coalesced_ops"]
    assert _read("queue_wait_ms", ctx) is None


@pytest.fixture(scope="module")
def stage_root(tmp_path_factory):
    """A checkout with a tiny configuration and mix whose one cell lists
    the eight stage metrics and `backend_busy_share` beside them."""
    root = tmp_path_factory.mktemp("stage_root")
    bench = root / "benchmark"
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    bench / "metrics")
    (bench / "traffic").mkdir()
    (root / "configs").mkdir()
    mix = traffic.Mix(hosts=2, jobs_per_host=2, iodepth=2,
                      pattern="sequential", get_pages=32,
                      working_set_share=0.75, client_cache_pages=256,
                      evict_put_pages=64, ramp_s=0.5)
    (bench / "traffic" / "tiny-seq.json").write_text(json.dumps(
        mix.__dict__))
    with open(os.path.join(REPO, "benchmark", "configs",
                           "linear-8g.json")) as f:
        cfg = json.load(f)
    cfg.update(capacity=4096, n_shards=1, mesh={"n_shards": 1})
    cfg["kv"]["index"]["capacity"] = 4096
    cfg["kv"]["bloom"]["num_bits"] = 1 << 16
    (root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    e2e = [{"name": n, "unit": u, "better": "lower", "bound": 0.25,
            "source": "host_clock"} for n, u in
           (("get_pages_per_s", "pages/s"), ("get_p99_ms", "ms"),
            ("get_hit_ratio", "ratio"), ("setup_s", "s"))]
    layer = [{"name": n, "unit": "%", "better": "lower",
              "source": "program_counter", "layer": "test",
              "moves": "get_pages_per_s"}
             for n in STAGE_METRICS + ("backend_busy_share",)]
    (root / "BENCHMARK.json").write_text(json.dumps(
        {"command": ["python3", "-m", "benchmark.run"],
         "paths": ["benchmark"], "run_seconds": 2,
         "configs": [{"name": "tiny", "source": "synthetic",
                      "file": "configs/tiny.json", "reduced": [],
                      "why": "test"}],
         "workloads": [{"name": "tiny.tiny-seq", "config": "tiny",
                        "traffic": "tiny-seq", "chips": 1,
                        "why": "test"}],
         "end_to_end": e2e, "per_layer": layer}))
    return str(root)


def test_traced_run_reports_the_stage_metrics(stage_root, capsys):
    rc = run.run(["--workload", "tiny.tiny-seq", "--seed", str(2**31 + 7),
                  "--seconds", "2", "--trace", "1"],
                 root=stage_root, allow_cpu=True)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    res = json.loads(out[-1])
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(STAGE_METRICS) <= set(m)
    assert all(m[k] >= 0 for k in STAGE_METRICS)
    assert m["queue_wait_ms"] > 0 and m["fetch_share"] > 0
    # back to back: the flush thread's stages cover the window, give or
    # take the stages open at its two edges (a flush or so each, which a
    # 2 s window on the CPU feels far more than the cell's 51 s)
    assert 60.0 <= sum(m[k] for k in SHARES) <= 140.0
    # the backend's own stages lie inside the proxy's timing of its calls
    assert m["route_share"] + m["launch_share"] + m["fetch_share"] <= \
        m["backend_busy_share"] + 1.0
