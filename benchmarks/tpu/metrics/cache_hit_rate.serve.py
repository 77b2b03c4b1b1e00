"""Share of served targets answered from the embedding cache (the
cache's own hit and miss counters, window delta)."""


def read(ctx):
    d = ctx["driver"]
    if not hasattr(d, "delta"):
        return None
    hits, misses = d.delta("hits"), d.delta("misses")
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
