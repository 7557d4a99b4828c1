"""Serving backend: how unevenly the plane's router spread the window's
keys over its shards: n_shards x route_shard_max_keys / route_keys, the
fullest shard's keys summed over routes against an even split (1.0 =
even; n_shards = every route's keys on one shard). Window deltas of the
counters every launched route bumps on `NetServer.stats`
(`ShardedKV._count_route`). None where the counters are missing or
nothing was routed."""

NAMES = ("route_keys", "route_shard_max_keys")


def read(ctx):
    n0, n1 = ctx["net0"], ctx["net1"]
    if not all(k in n1 for k in NAMES):
        return None
    keys, max_keys = (n1[k] - n0.get(k, 0) for k in NAMES)
    if keys <= 0:
        return None
    return int(ctx["cfg"]["n_shards"]) * max_keys / keys
