"""Wire + coalescer: percent of the window the flush loop waited with
nothing staged (`stage_idle_us`). At a fixed closed-loop load it rises
only when the server's own work per round shrank."""

from benchmark.metrics import _stage


def read(ctx):
    return _stage.share(ctx, "idle")
