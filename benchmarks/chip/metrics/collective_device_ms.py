"""Device time per step of the collectives, in ms, on the slowest chip: the
ops `bench_trace.py` classifies as collectives by HLO name, which in a
sharded engine step are the all-to-all hops of both routing directions
(`core/routing.py`, under `obs.exchange`) and the reduction of the drop
count.

On a TPU a chip that reaches an all-to-all first waits inside it for the
others, so the number holds the skew between the shards' work before each
hop as well as the transfer. A trace that holds no collective reads
nothing."""


def read(ctx):
    per = ctx["trace"]["per_chip_step_ns"].values()
    v = max(c["collective"] for c in per)
    return v * 1e-6 if v > 0 else None
