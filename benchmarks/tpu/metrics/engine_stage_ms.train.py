"""Host time the engine ``Trainer`` spends putting views on the device,
per step of the window: the program's ``engine.shard`` (masks mapped onto
the partition plan) and ``engine.stage`` (their ``device_put``) spans,
summed, over the steps of the window's ``train.fit``. The prefetch
workers run both, inside ``prefetch.build``."""
import spans


def read(ctx):
    got = spans.window(ctx, "train")
    if got is None:
        return None
    recs, off, s = got
    steps = spans.named(recs, "train.fit")[0].attrs.get("steps")
    stages = spans.in_window(recs, "engine.stage", off, s)
    if not steps or not stages:
        return None
    work = stages + spans.in_window(recs, "engine.shard", off, s)
    return sum(spans.duration_ns(r) for r in work) / steps / 1e6
