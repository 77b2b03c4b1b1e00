"""Share of the traced serving window in which the device was idle
while a batch was being served: device idle time inside the program's
``serve.batch`` spans, over the window (the rest of the device's idle
share falls between batches)."""
import spans


def read(ctx):
    got = spans.window(ctx, "serve")
    if got is None:
        return None
    batches = spans.in_window(got[0], "serve.batch", *got[1:])
    return spans.idle_share(batches, *got[1:]) if batches else None
