"""CPU tests of the readers of the flush thread's work and waiting
(`flush_cpu_share`, `flush_offcpu_share`): hand-built counter snapshots,
a program that lacks the counters, and a tiny traced run of the harness
that must report them."""

from __future__ import annotations

import json
import os

import pytest

from benchmark import run
from benchmark.tests.test_stage_metrics import stage_root  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = ("flush_cpu_share", "flush_offcpu_share")
STAGE_SHARES = ("host_prep_share", "route_share", "launch_share",
                "fetch_share", "reply_share")


def _read(name, ctx):
    return run.load_reader(os.path.join(REPO, "benchmark"), name)(ctx)


def _ctx():
    """A 10 s window of 100 flushes: PUT prep 2 s of wall (1 s on a CPU),
    GET fetch 3 s (0.5 s) whose counters first appear inside the window;
    idle and dwell, which are no serve stages, 4 s of CPU that no reader
    may count."""
    net0 = {"flushes": 100, "stage_put_prep_us": 1_000,
            "stage_put_prep_n": 100, "stage_put_prep_cpu_us": 500,
            "stage_idle_us": 7, "stage_idle_cpu_us": 7,
            "stage_dwell_cpu_us": 0}
    net1 = dict(net0, flushes=200, stage_put_prep_us=2_001_000,
                stage_put_prep_n=200, stage_put_prep_cpu_us=1_000_500,
                stage_get_fetch_us=3_000_000, stage_get_fetch_n=100,
                stage_get_fetch_cpu_us=500_000,
                stage_idle_us=2_000_007, stage_idle_cpu_us=2_000_007,
                stage_dwell_cpu_us=2_000_000)
    return {"window_s": 10.0, "net0": net0, "net1": net1}


@pytest.mark.parametrize("name, want", [
    ("flush_cpu_share", 15.0),         # (1 + 0.5) s of 10
    ("flush_offcpu_share", 35.0)])     # (2 + 3 - 1.5) s of 10
def test_readers_on_hand_built_counters(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


def test_work_and_waiting_add_up_to_the_serve_stages():
    ctx = _ctx()
    stages = sum(_read(m, ctx) or 0.0 for m in STAGE_SHARES)
    assert stages == pytest.approx(50.0)
    assert _read("flush_cpu_share", ctx) + _read("flush_offcpu_share", ctx) \
        == pytest.approx(stages)


@pytest.mark.parametrize("name", METRICS)
def test_readers_read_nothing_without_the_counters(name):
    """The parent program keeps the wall-clock stage counters and none of
    these: no reading, no error."""
    wall = {"flushes": 10, "coalesced_ops": 10, "stage_put_prep_us": 10,
            "stage_put_prep_n": 1}
    ctx = {"window_s": 10.0, "net0": wall,
           "net1": dict(wall, flushes=20, stage_put_prep_us=900)}
    assert _read(name, ctx) is None


def test_traced_run_reports_the_flush_cpu_metrics(stage_root,  # noqa: F811
                                                  capsys):
    path = os.path.join(stage_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    have = {m["name"] for m in spec["per_layer"]}
    spec["per_layer"] += [
        {"name": n, "unit": "%", "better": "lower",
         "source": "program_counter", "layer": "test",
         "moves": "get_pages_per_s"}
        for n in METRICS if n not in have]
    with open(path, "w") as f:
        json.dump(spec, f)
    rc = run.run(["--workload", "tiny.tiny-seq", "--seed", str(2**31 + 13),
                  "--seconds", "2", "--trace", "1"],
                 root=stage_root, allow_cpu=True)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(METRICS) <= set(m)
    assert m["flush_cpu_share"] > 0.0 and m["flush_offcpu_share"] >= 0.0
    stages = sum(m[k] for k in STAGE_SHARES)
    assert m["flush_cpu_share"] + m["flush_offcpu_share"] == \
        pytest.approx(stages, abs=0.5)
