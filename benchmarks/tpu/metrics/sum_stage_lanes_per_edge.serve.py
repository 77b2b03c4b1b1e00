"""Plan lanes the Sum-stage kernels walk a pass per live edge, in
serving: over the program's ``view.stage`` spans of the window, both
paths (the K-hop miss view and the one-hop hit view), the sum of
``plan_lanes`` over the sum of ``live_edges``."""
import spans


def read(ctx):
    got = spans.window(ctx, "serve")
    if got is None:
        return None
    return spans.lanes_per_edge(
        spans.in_window(got[0], "view.stage", *got[1:]))
