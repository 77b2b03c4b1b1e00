"""Model FLOP utilisation of serving: for every request answered in the
window, the forward FLOPs of the top layer over the node's in-edges and
the decoder (the least any answer needs: a cache hit's work), over the
window, the chips and the chip's peak."""
import numpy as np


def read(ctx):
    d = ctx["driver"]
    if not hasattr(d, "answers"):
        return None
    model, fdim = ctx["config"]["model"], d.g["x"].shape[1]
    K = model["num_layers"]
    ok = np.isfinite(d.done)
    indeg = np.bincount(d.g["dst"], minlength=len(d.g["y"]))[d.nodes[ok]]
    e = float(indeg.sum())
    n = float(ok.sum())
    work = {"layers": [{"n_src": n + e, "n_dst": n, "edges": e}],
            "targets": n}
    flops = ctx["costs"].forward_flops(work, model, fdim, first=K - 1)
    return 100.0 * flops / d.window_s / (ctx["chips"] * ctx["peaks"]["flops"])
