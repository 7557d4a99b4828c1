"""Wire + coalescer: mean CPU time of a connection writer's own thread
spent building one GET reply frame in the window, in µs
(`reply_build_cpu_us` over `reply_deferred_frames`). Beside
`reply_build_us`, the same span on the wall clock, it splits a frame's
build into work and waiting (for the GIL, or a core). A program that
keeps no such counter reads nothing (None)."""


def read(ctx):
    n0, n1 = ctx["net0"], ctx["net1"]
    if "reply_build_cpu_us" not in n1:
        return None
    frames = n1["reply_deferred_frames"] - n0.get("reply_deferred_frames", 0)
    if frames <= 0:
        return None
    return (n1["reply_build_cpu_us"] - n0.get("reply_build_cpu_us", 0)) \
        / frames
