"""Wire + coalescer: percent of the window the flush loop spent preparing
phases before the backend call: deadline shed, counters, workload
sketch, key and page concatenation, padding (`stage_<phase>_prep_us`)."""

from benchmark.metrics import _stage


def read(ctx):
    return _stage.share(ctx, "prep")
