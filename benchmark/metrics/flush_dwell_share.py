"""Wire + coalescer: percent of the window the flush loop held a batch
open for more ops after its first drain (`stage_dwell_us`)."""

from benchmark.metrics import _stage


def read(ctx):
    return _stage.share(ctx, "dwell")
