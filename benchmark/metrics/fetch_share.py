"""Device programs + kernel: percent of the window the flush loop waited
on results: the device's work, the device-to-host copy, the scatter
back to request order (`stage_<phase>_fetch_us`)."""

from benchmark.metrics import _stage


def read(ctx):
    return _stage.share(ctx, "fetch")
