"""Host time a served batch spends outside the device step: the mean,
over the program's ``serve.batch`` spans of the window, of the span's
length less its ``serve.device`` spans (lock wait, coverage split, view
build and staging, cache write-back, gather and responses)."""
import spans


def read(ctx):
    got = spans.window(ctx, "serve")
    if got is None:
        return None
    recs = got[0]
    batches = spans.in_window(recs, "serve.batch", *got[1:])
    if not batches:
        return None
    host = sum(spans.duration_ns(b)
               - spans.descendant_ns(recs, b, "serve.device")
               for b in batches)
    return host / len(batches) / 1e6
