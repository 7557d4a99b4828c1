"""KV host wrapper: percent of the window inside the jitted calls until
they return futures: dispatch and the host-to-device copy
(`stage_<phase>_launch_us`)."""

from benchmark.metrics import _stage


def read(ctx):
    return _stage.share(ctx, "launch")
