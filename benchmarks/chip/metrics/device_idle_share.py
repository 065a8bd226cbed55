"""Device idle share of the traced stretch, in %: 1 - busy / window, with
busy the union of the device's op intervals, averaged over the cell's
chips. Layer: the host loop (the harness's loop and `StoreEngine.step`'s
dispatch and fetch)."""


def read(ctx):
    t = ctx["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
