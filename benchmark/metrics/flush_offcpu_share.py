"""Wire + coalescer: percent of the window the flush thread spent in its
serve stages off a CPU: their wall time (`stage_<phase>_<stage>_us`) less
their CPU time (`_cpu_us`). It is waiting: for the GIL, a lock, the
device, a DMA or a core. With `flush_cpu_share` it adds up to the five
serve-stage shares."""

from benchmark.metrics import _serve_cpu


def read(ctx):
    cpu = _serve_cpu.serve_delta(ctx, "_cpu_us")
    if cpu is None:
        return None
    return _serve_cpu.share(_serve_cpu.serve_delta(ctx, "_us") - cpu, ctx)
