"""Plan lanes the Sum-stage kernels walk a pass per live edge, in
training: over the program's ``view.stage`` spans of the window, the
sum of ``plan_lanes`` (num_blocks x l_pad of the CSCPlan built there)
over the sum of ``live_edges`` (the view's edges)."""
import spans


def read(ctx):
    got = spans.window(ctx, "train")
    if got is None:
        return None
    return spans.lanes_per_edge(
        spans.in_window(got[0], "view.stage", *got[1:]))
