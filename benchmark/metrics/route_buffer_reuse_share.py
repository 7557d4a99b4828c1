"""Serving backend: percent of the window's routed PUT batches whose pages
the plane's router wrote into the page buffer it holds and reuses, rather
than a newly allocated array. The window deltas of `route_buf_reused` and
`route_buf_alloc`, counted by `ShardedKV.plane_insert` on
`NetServer.stats`. A program without the counters, or a window that routed
no PUT, reads nothing (None)."""

NAMES = ("route_buf_reused", "route_buf_alloc")


def read(ctx):
    n0, n1 = ctx["net0"], ctx["net1"]
    if not any(k in n1 for k in NAMES):
        return None
    reused, alloc = (n1.get(k, 0) - n0.get(k, 0) for k in NAMES)
    if reused + alloc <= 0:
        return None
    return 100.0 * reused / (reused + alloc)
