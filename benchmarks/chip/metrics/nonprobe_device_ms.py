"""Device time per step outside the probe and outside collectives, in ms:
the write path (`core/det_skiplist.py` merge, delete, compaction and level
rebuild; `store/tiers.py` movement) and bookkeeping, on the slowest chip."""


def read(ctx):
    per = ctx["trace"]["per_chip_step_ns"].values()
    v = max(c["other"] for c in per)
    return v * 1e-6 if v > 0 else None
