"""Shared by the readers of the flush thread's work and waiting: window
deltas of the thread CPU time the stage clock counts beside each serve
stage's wall time (`stage_<phase>_<stage>_cpu_us` beside `_us`, for
stages `prep`, `route`, `launch`, `fetch`, `reply` of every phase), on
`NetServer.stats` at the window's edges (`net0`, `net1`). A program
without them reads nothing (None)."""

from benchmark.metrics import _stage

SERVE_STAGES = ("prep", "route", "launch", "fetch", "reply")


def serve_delta(ctx, suffix: str):
    """Summed window increments of `stage_<phase>_<stage><suffix>` over
    every phase and serve stage; None where the window's last snapshot
    holds none of them."""
    return _stage.delta_us(ctx, [f"stage_{ph}_{st}{suffix}"
                                 for ph in _stage.PHASES
                                 for st in SERVE_STAGES])


def share(us, ctx):
    """`us` µs as a percent of the window; None stays None."""
    return None if us is None else 100.0 * us / 1e6 / ctx["window_s"]
