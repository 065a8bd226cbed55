"""Share of the window's FIND lanes that the hot hash tier served, in %:
the obs plane's `hot_hits` over `ops_find`, both as deltas over the window
(`store/tiers.py` records them; the `obs:` backend carries them in state)."""


def read(ctx):
    c = ctx["counters"]
    if not c or c.get("ops_find", 0) <= 0:
        return None
    return 100.0 * c["hot_hits"] / c["ops_find"]
