"""Wire + coalescer: percent of the window from the backend's answer to
replies enqueued: hit-row gathers, frames, stamps, span bookkeeping
(`stage_<phase>_reply_us`)."""

from benchmark.metrics import _stage


def read(ctx):
    return _stage.share(ctx, "reply")
