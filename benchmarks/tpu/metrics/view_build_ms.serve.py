"""Host time per served batch spent building and staging its compact
view (the server's own ``ServeStats.view_build_s``, window delta)."""


def read(ctx):
    d = ctx["driver"]
    if not hasattr(d, "delta") or d.delta("batches") <= 0:
        return None
    return 1e3 * d.delta("view_build_s") / d.delta("batches")
