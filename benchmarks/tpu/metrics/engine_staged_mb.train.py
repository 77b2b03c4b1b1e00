"""Bytes the engine ``Trainer`` puts on the device per step of the
window, in MB: the ``staged_bytes`` of the program's ``engine.stage``
spans in the window (the sharded view masks it stages), summed, over
the steps of the window's ``train.fit``."""
import spans


def read(ctx):
    got = spans.window(ctx, "train")
    if got is None:
        return None
    recs, off, s = got
    steps = spans.named(recs, "train.fit")[0].attrs.get("steps")
    stages = spans.in_window(recs, "engine.stage", off, s)
    if not steps or not stages or any(
            "staged_bytes" not in r.attrs for r in stages):
        return None
    return sum(r.attrs["staged_bytes"] for r in stages) / steps / 1e6
