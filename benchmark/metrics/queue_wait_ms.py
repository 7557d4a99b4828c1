"""Wire + coalescer: mean wait of an op in the staging queue, from its
staging to the start of its phase, over the window
(`stage_queue_wait_us` over `coalesced_ops`), in ms."""

from benchmark.metrics import _stage


def read(ctx):
    us = _stage.delta_us(ctx, ["stage_queue_wait_us"])
    ops = ctx["net1"]["coalesced_ops"] - ctx["net0"]["coalesced_ops"]
    if us is None or ops <= 0:
        return None
    return us / ops / 1000.0
