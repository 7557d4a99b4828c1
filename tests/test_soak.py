"""Smoke coverage for the committed soak + insert-profile harnesses.

The soak (`bench/soak.py`) is the reproducible form of the round-3/4
serving-path soak claim in PERF.md; the profiler (`bench/insert_profile.py`)
is the decomposition the insert optimizations were driven by. A harness
that only works on the day it was written is a lost chip run, so CI pins
their contracts at toy sizes.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", *args], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=timeout,
    )


@pytest.mark.slow  # fast-tier 300 s contract (VERDICT r4 item 8): the
# subprocess soak costs ~13 s; fast-tier serving-path coverage lives in
# tests/test_runtime.py's engine storms, the full soak runs in slow
def test_soak_smoke_clean_run():
    """A short soak must serve verified traffic, hold the clean-cache
    invariant, and exit 0 (no --history: CPU is a legal device)."""
    p = _run(["pmdfc_tpu.bench.soak", "--minutes", "0.08", "--threads", "2",
              "--verb", "64", "--capacity", "16384", "--keyspace", "512",
              "--page-words", "16", "--engine-batch", "1024"])
    assert p.returncode == 0, p.stderr.decode()[-2000:]
    out = json.loads(p.stdout.decode().strip().splitlines()[-1])
    assert out["metric"] == "soak_verified_pages_per_sec"
    assert out["verified_pages"] > 0
    assert out["mismatches"] == 0
    assert out["deleted_hits"] == 0
    assert out["clean_cache_invariant_ok"] is True
    # the headline counts deliveries, not requests
    assert out["value"] <= out["requests_per_sec"]


@pytest.mark.slow
def test_soak_history_offchip_exits_3(tmp_path):
    """--history off-chip must exit 3 and append nothing (the resumable
    agenda's done-marker discipline)."""
    hist = tmp_path / "h.jsonl"
    p = _run(["pmdfc_tpu.bench.soak", "--minutes", "0.03", "--threads", "1",
              "--verb", "32", "--capacity", "8192", "--keyspace", "256",
              "--page-words", "16", "--engine-batch", "256",
              "--history", str(hist)])
    assert p.returncode == 3, p.stderr.decode()[-2000:]
    assert not hist.exists() or not hist.read_text().strip()


@pytest.mark.slow
def test_insert_profile_smoke():
    """The profiler's pieces must sum near its fused ground truth and the
    JSON record must carry every phase."""
    p = _run(["pmdfc_tpu.bench.insert_profile", "--device", "cpu",
              "--n", "16384", "--capacity", "32768", "--reps", "1"])
    assert p.returncode == 0, p.stderr.decode()[-2000:]
    out = json.loads(p.stdout.decode().strip().splitlines()[-1])
    ns = out["ns_per_key"]
    assert set(ns) == {"hash", "plan", "rank", "gather", "scatter", "index"}
    assert all(v > 0 for v in ns.values())
    assert out["insert_mops_equiv"] > 0
