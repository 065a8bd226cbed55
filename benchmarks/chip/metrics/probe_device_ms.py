"""Device time per step of the probe, in ms: ops under a `find` scope
(`store/exec.py`'s `skiplist_find`, `tier_find`, and the FIND phase around
them), on the slowest chip."""


def read(ctx):
    per = ctx["trace"]["per_chip_step_ns"].values()
    v = max(c["probe"] for c in per)
    return v * 1e-6 if v > 0 else None
