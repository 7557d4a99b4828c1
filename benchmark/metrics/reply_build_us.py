"""Wire + coalescer: mean time a connection writer spent building one GET
reply frame in the window (row slice or gather, mask, CRC), in µs
(`reply_build_us` over `reply_deferred_frames`). A program whose flush
thread frames its own replies keeps neither counter and reads nothing
(None)."""


def read(ctx):
    n0, n1 = ctx["net0"], ctx["net1"]
    if "reply_deferred_frames" not in n1:
        return None
    frames = n1["reply_deferred_frames"] - n0.get("reply_deferred_frames", 0)
    if frames <= 0:
        return None
    return (n1["reply_build_us"] - n0.get("reply_build_us", 0)) / frames
