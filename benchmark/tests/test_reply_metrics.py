"""CPU tests of the GET reply readers (`reply_view_share`, `reply_build_us`,
`reply_build_cpu_us`):
hand-built counter snapshots, a program that lacks the counters, and a
tiny traced run of the harness that must report both."""

from __future__ import annotations

import json
import os

import pytest

from benchmark import run
from benchmark.tests.test_stage_metrics import stage_root  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REPLY_METRICS = {"reply_view_share": "%", "reply_build_us": "us",
                 "reply_build_cpu_us": "us"}


def _read(name, ctx):
    return run.load_reader(os.path.join(REPO, "benchmark"), name)(ctx)


def _ctx(net0, net1):
    return {"window_s": 10.0, "net0": net0, "net1": net1}


@pytest.mark.parametrize("name, want", [("reply_view_share", 75.0),
                                        ("reply_build_us", 50.0),
                                        ("reply_build_cpu_us", 20.0)])
def test_reply_readers_on_hand_built_counters(name, want):
    """4,000 frames built in the window, 3,000 of them views, in 0.2 s
    of wall time and 0.08 s of the writers' CPU."""
    ctx = _ctx({"reply_deferred_frames": 1000, "reply_view_frames": 900,
                "reply_build_us": 100_000, "reply_build_cpu_us": 40_000},
               {"reply_deferred_frames": 5000, "reply_view_frames": 3900,
                "reply_build_us": 300_000, "reply_build_cpu_us": 120_000})
    assert _read(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(REPLY_METRICS))
def test_reply_readers_read_nothing_without_frames(name):
    """The parent program keeps no such counters; a window with no GET
    frame built has nothing to average."""
    assert _read(name, _ctx({"coalesced_ops": 0},
                            {"coalesced_ops": 100})) is None
    still = {"reply_deferred_frames": 7, "reply_view_frames": 7,
             "reply_build_us": 70, "reply_build_cpu_us": 30}
    assert _read(name, _ctx(still, dict(still))) is None


def test_traced_run_reports_the_reply_metrics(stage_root, capsys):  # noqa: F811
    path = os.path.join(stage_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"] += [
        {"name": n, "unit": u, "better": "higher", "source": "program_counter",
         "layer": "test", "moves": "get_pages_per_s"}
        for n, u in REPLY_METRICS.items()]
    with open(path, "w") as f:
        json.dump(spec, f)
    rc = run.run(["--workload", "tiny.tiny-seq", "--seed", str(2**31 + 11),
                  "--seconds", "2", "--trace", "1"],
                 root=stage_root, allow_cpu=True)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # one shard keeps request order: views are most frames, never more
    assert 0.0 < m["reply_view_share"] <= 100.0
    assert m["reply_build_us"] > 0.0
    assert 0.0 <= m["reply_build_cpu_us"]
