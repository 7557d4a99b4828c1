"""Wire + coalescer: percent of the GET reply frames the connection writers
built in the window that went out as a zero-copy slice of the fetched
buffer, or with no rows (`reply_view_frames` over `reply_deferred_frames`);
the rest cost one gather each. A program whose flush thread frames its
own replies keeps neither counter and reads nothing (None)."""


def read(ctx):
    n0, n1 = ctx["net0"], ctx["net1"]
    if "reply_deferred_frames" not in n1:
        return None
    frames = n1["reply_deferred_frames"] - n0.get("reply_deferred_frames", 0)
    if frames <= 0:
        return None
    views = n1["reply_view_frames"] - n0.get("reply_view_frames", 0)
    return 100.0 * views / frames
