"""Share of served batches that took the K-hop miss path: the program's
``serve.batch`` spans of the window whose ``misses`` counter (targets
the cache did not cover) is above zero."""
import spans


def read(ctx):
    got = spans.window(ctx, "serve")
    if got is None:
        return None
    batches = [b for b in spans.in_window(got[0], "serve.batch", *got[1:])
               if "misses" in b.attrs]
    if not batches:
        return None
    return 100.0 * sum(b.attrs["misses"] > 0 for b in batches) / len(batches)
