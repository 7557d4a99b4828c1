"""Device programs + kernel: percent of the window's PUT pages whose insert
evicted a resident key. The window delta of `index_evictions` (the insert
programs' evicted keys, counted by `PlaneBackend` on `NetServer.stats`)
over the PUT keys the flush loop handed the backend (`put_keys`). A
program without the counter reads nothing (None)."""


def read(ctx):
    n0, n1 = ctx["net0"], ctx["net1"]
    if "index_evictions" not in n1:
        return None
    puts = ctx["prx1"]["put_keys"] - ctx["prx0"]["put_keys"]
    if puts <= 0:
        return None
    return 100.0 * (n1["index_evictions"] - n0.get("index_evictions", 0)) \
        / puts
