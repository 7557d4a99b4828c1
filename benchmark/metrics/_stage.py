"""Shared by the flush-stage readers: window deltas of the stage counters
the serving program's flush loop keeps on `NetServer.stats`
(`stage_<phase>_<stage>_us`, `stage_idle_us`, `stage_dwell_us`,
`stage_queue_wait_us`), read at the window's edges (`net0`, `net1`). A
program without them reads nothing (None)."""

PHASES = ("put", "ins_ext", "del", "get_ext", "get", "aux")


def delta_us(ctx, names):
    """Summed window increments of the counters `names` that the window's
    last snapshot holds, in µs; None when it holds none of them."""
    n0, n1 = ctx["net0"], ctx["net1"]
    have = [k for k in names if k in n1]
    if not have:
        return None
    return float(sum(n1[k] - n0.get(k, 0) for k in have))


def share(ctx, stage: str):
    """Percent of the window the flush thread spent in `stage`: a stage of
    the flush loop itself (`idle`, `dwell`) or of every phase (`prep`,
    `route`, `launch`, `fetch`, `reply`), summed over the phases."""
    if stage in ("idle", "dwell"):
        names = [f"stage_{stage}_us"]
    else:
        names = [f"stage_{ph}_{stage}_us" for ph in PHASES]
    us = delta_us(ctx, names)
    return None if us is None else 100.0 * us / 1e6 / ctx["window_s"]
