"""Device: the busiest device's busy time in the traced slice over the mean
over the cell's devices (1.0 = every device as busy; the flush waits for
the slowest). It moves the rate only where the devices' work sets the
flush's fetch wait, i.e. where they are busy for much of a flush; a host-
bound cell whose devices idle ~95% reads ~1.0 whatever the host does.
None without a trace, or where no device was busy."""

from benchmark import trace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    busy = [trace.busy_s(d, tr.window) for d in tr.devices]
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean > 0 else None
