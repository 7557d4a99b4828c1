"""Serving backend: percent of the lanes the plane launched over the window
that were pads, not keys: 100 x (route_lanes - route_keys) / route_lanes,
window deltas of the counters every launched route bumps on
`NetServer.stats` (`ShardedKV._count_route`). Each shard pads its part of
a batch up to the pow2 rung of the fullest shard, so this reads the rung
waste and, across shards, the imbalance. None where the counters are
missing or nothing was routed."""

NAMES = ("route_keys", "route_lanes")


def read(ctx):
    n0, n1 = ctx["net0"], ctx["net1"]
    if not all(k in n1 for k in NAMES):
        return None
    keys, lanes = (n1[k] - n0.get(k, 0) for k in NAMES)
    return 100.0 * (lanes - keys) / lanes if lanes > 0 else None
