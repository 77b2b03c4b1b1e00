"""Mean time a request waited in the server's batching queue (the
server's own ``ServeStats.queue_wait_s`` over its requests, window
delta)."""


def read(ctx):
    d = ctx["driver"]
    if not hasattr(d, "delta") or d.delta("requests") <= 0:
        return None
    return 1e3 * d.delta("queue_wait_s") / d.delta("requests")
