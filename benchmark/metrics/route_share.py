"""Serving backend: percent of the window the plane spent routing each
phase's batch on the host: binning by shard, per-shard pads, copies
into the routed buffers (`stage_<phase>_route_us`)."""

from benchmark.metrics import _stage


def read(ctx):
    return _stage.share(ctx, "route")
