"""CPU tests of the readers of the CCEH cell: `get_kernel_hbm_roofline` on a
hand-built `Trace`, `index_evict_share` and `cceh_insert_rounds` on
hand-built counter snapshots, nothing read where the program lacks what
they read, and a tiny traced run of a CCEH configuration (the fused GET in
interpret mode) that must report the two counter metrics."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from benchmark import roofline, run, traffic
from benchmark.trace import DeviceTrace, Trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000
KERNEL = ("%pmdfc_fused_get.1 = (u32[8192,1024]{1,0:T(8,128)}, "
          "s32[1,8192]{1,0}) custom-call(%copy.1, %table), "
          "custom_call_target=\"tpu_custom_call\"")


def _read(name, ctx):
    return run.load_reader(os.path.join(REPO, "benchmark"), name)(ctx)


def _trace(kernel=True):
    """Two devices over a 10 ms slice: the kernel runs 2 ms on the first
    and 4 ms on the second (1 ms of it past the slice); beside it ops that
    read its output or belong to other programs."""
    k = [(KERNEL, 1 * MS, 2 * MS)] if kernel else []
    d0 = DeviceTrace("/device:TPU:0", ops=k + [
        ("%get-tuple-element.5 = u32[8192,1024] "
         "get-tuple-element(%pmdfc_fused_get.1), index=0", 3 * MS, 1 * MS),
        ("%fusion.3 = u32[8192,1024] fusion(%get-tuple-element.5)",
         4 * MS, 1 * MS)],
        modules=[("jit_plane_get_ro(3)", 1 * MS, 5 * MS)])
    k1 = [("pmdfc_fused_get.1", 7 * MS, 4 * MS)] if kernel else []
    d1 = DeviceTrace("/device:TPU:1", ops=k1 + [("while.37", 0, 5 * MS)],
                     modules=[("jit_plane_insert(9)", 0, 5 * MS)])
    return Trace([d0, d1], [], (0, 10 * MS))


def _ctx(tr):
    return {"trace": tr, "cfg": {"index_row_bytes": 512,
                                 "kv": {"page_words": 1024}},
            "device_kind": "TPU v5 lite",
            "trace_prx0": {"get_keys": 1000}, "trace_prx1": {"get_keys": 9000}}


def test_kernel_roofline_reads_the_kernel_ops_alone():
    got = _read("get_kernel_hbm_roofline", _ctx(_trace()))
    per_key = roofline.get_bytes_per_key(512, 4096)
    assert per_key == 8704
    # kernel time: 2 ms and 3 ms (clipped), mean 2.5 ms over two devices
    least = 8000 * per_key / (819e9 * 2)
    assert got == pytest.approx(100 * least / 0.0025)


def test_kernel_roofline_reads_nothing_without_the_kernel():
    assert _read("get_kernel_hbm_roofline", _ctx(_trace(kernel=False))) \
        is None
    assert _read("get_kernel_hbm_roofline", _ctx(None)) is None
    ctx = _ctx(_trace())
    ctx["trace_prx1"] = {"get_keys": 1000}  # no GET key in the slice
    assert _read("get_kernel_hbm_roofline", ctx) is None


def _counters():
    net0 = {"index_splits": 1024, "index_insert_rounds": 300,
            "index_evictions": 9000, "index_insert_calls": 100}
    net1 = {"index_splits": 1024, "index_insert_rounds": 540,
            "index_evictions": 9500, "index_insert_calls": 180}
    return {"net0": net0, "net1": net1, "prx0": {"put_keys": 10_000},
            "prx1": {"put_keys": 110_000}}


def test_counter_readers_on_hand_built_counters():
    ctx = _counters()
    assert _read("index_evict_share", ctx) == pytest.approx(0.5)
    assert _read("cceh_insert_rounds", ctx) == pytest.approx(3.0)


def test_counter_readers_read_nothing_without_the_counters():
    """The parent program keeps no index counters: no reading, no
    error; nor is there one without a PUT or an insert call."""
    bare = {"net0": {"flushes": 1}, "net1": {"flushes": 9},
            "prx0": {"put_keys": 0}, "prx1": {"put_keys": 100}}
    for name in ("index_evict_share", "cceh_insert_rounds"):
        assert _read(name, bare) is None
    ctx = _counters()
    ctx["prx1"] = dict(ctx["prx0"])
    ctx["net1"]["index_insert_calls"] = ctx["net0"]["index_insert_calls"]
    for name in ("index_evict_share", "cceh_insert_rounds"):
        assert _read(name, ctx) is None


@pytest.fixture(scope="module")
def cceh_root(tmp_path_factory):
    """A checkout whose one cell runs `cceh-8g.json` cut to 4,096 pool rows
    (16 segments of 128 slots, one doubling), the fused GET forced on."""
    root = tmp_path_factory.mktemp("cceh_root")
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
                           "cceh-8g.json")) as f:
        cfg = json.load(f)
    cfg["capacity"] = 4096
    cfg["kv"]["index"].update(capacity=2048, segment_slots=128)
    cfg["kv"]["bloom"]["num_bits"] = 1 << 16
    cfg["kv"]["fused_get"] = "on"
    (root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    layer = [m for m in bench_json["per_layer"]
             if "cceh-8g.seqread-128k" in m.get("workloads", ())]
    for m in layer:
        m["workloads"] = ["tiny.tiny-seq"]
    (root / "BENCHMARK.json").write_text(json.dumps(
        {**bench_json, "run_seconds": 2,
         "configs": [{"name": "tiny", "source": "synthetic",
                      "file": "configs/tiny.json", "reduced": [],
                      "why": "test"}],
         "workloads": [{"name": "tiny.tiny-seq", "config": "tiny",
                        "traffic": "tiny-seq", "chips": 1, "why": "test"}],
         "per_layer": layer}))
    return str(root)


def test_traced_run_reports_the_index_metrics(cceh_root, capsys):
    rc = run.run(["--workload", "tiny.tiny-seq", "--seed", str(2**31 + 25),
                  "--seconds", "2", "--trace", "1"],
                 root=cceh_root, allow_cpu=True)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    res = json.loads(out[-1])
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # no device trace on the CPU: the kernel's share is not read there
    assert set(m) == {"index_evict_share", "cceh_insert_rounds"}
    assert 1.0 <= m["cceh_insert_rounds"] <= 3.0
    assert 0.0 <= m["index_evict_share"] < 100.0
