"""Host time to build one training view: the mean of the program's
``prefetch.build`` spans in the window (a prefetch worker's sample, the
wait for the stage lock and the staging, one span a view)."""
import spans


def read(ctx):
    got = spans.window(ctx, "train")
    if got is None:
        return None
    builds = spans.in_window(got[0], "prefetch.build", *got[1:])
    if not builds:
        return None
    return sum(spans.duration_ns(r) for r in builds) / len(builds) / 1e6
