"""Share of the traced serving window in which no op ran on the device
(averaged over the chips): 100 x (1 - busy / window)."""


def read(ctx):
    s = ctx["trace"]
    if not s or s["devices"] == 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
