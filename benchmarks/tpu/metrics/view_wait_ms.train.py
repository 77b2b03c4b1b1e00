"""Time the training loop waited for its next staged view, per step of
the window: the program's ``train.view_wait`` spans (the loop's
``next()`` on the prefetch pipeline), summed, over the steps of the
window's ``train.fit``."""
import spans


def read(ctx):
    got = spans.window(ctx, "train")
    if got is None:
        return None
    recs, off, s = got
    steps = spans.named(recs, "train.fit")[0].attrs.get("steps")
    waits = spans.in_window(recs, "train.view_wait", off, s)
    if not steps or not waits:
        return None
    return sum(spans.duration_ns(r) for r in waits) / steps / 1e6
