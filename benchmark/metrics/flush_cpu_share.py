"""Wire + coalescer: percent of the window the flush thread was on a CPU
in its serve stages (`prep`, `route`, `launch`, `fetch`, `reply` of every
phase; `stage_<phase>_<stage>_cpu_us`, the thread's user + system time,
page faults included). Beside the serve stages' wall time it says how
much of the pace is the flush thread's own work."""

from benchmark.metrics import _serve_cpu


def read(ctx):
    return _serve_cpu.share(_serve_cpu.serve_delta(ctx, "_cpu_us"), ctx)
