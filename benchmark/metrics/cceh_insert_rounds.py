"""Device programs + kernel: attempt rounds a CCEH insert call ran in the
window (place, then split every overflowing segment; at most
`split_headroom + 2`). The window delta of `index_insert_rounds` over
`index_insert_calls` (insert programs that held a key), both counted by
`PlaneBackend` on `NetServer.stats`. A family that places in one pass
reads 1. A program without the counters reads nothing (None)."""


def read(ctx):
    n0, n1 = ctx["net0"], ctx["net1"]
    if "index_insert_calls" not in n1:
        return None
    calls = n1["index_insert_calls"] - n0.get("index_insert_calls", 0)
    if calls <= 0:
        return None
    return (n1["index_insert_rounds"] - n0.get("index_insert_rounds", 0)) \
        / calls
