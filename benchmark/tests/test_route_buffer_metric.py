"""CPU tests of `route_buffer_reuse_share` on hand-built counter snapshots:
the share of the window's routed PUT batches that reused the router's
held page buffer, and nothing read where the program lacks the counters
or the window routed no PUT."""

from __future__ import annotations

import os

import pytest

from benchmark import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read(ctx):
    return run.load_reader(os.path.join(REPO, "benchmark"),
                           "route_buffer_reuse_share")(ctx)


def test_reads_the_window_share_of_reused_batches():
    # 11 allocations while the ladder warmed, before the window; in it
    # 297 batches reused the buffer and 3 needed an array of their own
    ctx = {"net0": {"route_buf_reused": 100, "route_buf_alloc": 11},
           "net1": {"route_buf_reused": 397, "route_buf_alloc": 14}}
    assert _read(ctx) == pytest.approx(99.0)
    # a counter first seen inside the window counts from 0
    ctx = {"net0": {"flushes": 4},
           "net1": {"route_buf_reused": 50, "flushes": 9}}
    assert _read(ctx) == pytest.approx(100.0)
    ctx = {"net0": {}, "net1": {"route_buf_alloc": 5}}
    assert _read(ctx) == 0.0


def test_reads_nothing_without_the_counters_or_a_put():
    """The parent program keeps no such counters: no reading, no error;
    nor is there one in a window that routed no PUT."""
    assert _read({"net0": {"flushes": 1}, "net1": {"flushes": 9}}) is None
    same = {"route_buf_reused": 40, "route_buf_alloc": 2}
    assert _read({"net0": dict(same), "net1": dict(same)}) is None
