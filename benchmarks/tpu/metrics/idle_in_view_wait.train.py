"""Share of the traced training window in which the device was idle
while the training loop waited for a view: device idle time inside the
program's ``train.view_wait`` spans, over the window."""
import spans


def read(ctx):
    got = spans.window(ctx, "train")
    if got is None:
        return None
    waits = spans.in_window(got[0], "train.view_wait", *got[1:])
    return spans.idle_share(waits, *got[1:]) if waits else None
