"""Model FLOP utilisation of training: the forward and backward FLOPs of
every window step over its live nodes and edges (costs/<model>.py), over
the window's wall time, the chips and the chip's peak."""


def read(ctx):
    d = ctx["driver"]
    if not hasattr(d, "work"):
        return None
    model, fdim = ctx["config"]["model"], d.g["x"].shape[1]
    flops = sum(ctx["costs"].train_flops(w, model, fdim) for w in d.work())
    return 100.0 * flops / d.window_s / (ctx["chips"] * ctx["peaks"]["flops"])
