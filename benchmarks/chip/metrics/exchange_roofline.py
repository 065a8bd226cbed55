"""The exchange's share of its roofline, in %: the least time the exchange
could take over the time it took (`collective_device_ms`).

The least time is the bytes the `OpPlan` contract must send off the busiest
chip each step, at the chip's interchip interconnect (ICI) bandwidth. Out go
the chip's lanes owned elsewhere: key 8 B + op 1 B each (an INSERT's 8-byte
value too, but every write lane is counted at a DELETE's bytes, so that the
time stays a lower bound). Back go the results of the lanes it owns for
other chips: value 8 B + ok 1 B for a FIND, ok 1 B for a write. The share
of lanes owned elsewhere is (chips - 1) / chips, which uniform keys over the
full range give under a top-bits partition; the FIND count is the traced
plans' (`find_lanes_per_step`). Both come from the cell and its traffic, so
no implementation can make them stale.
"""

# Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of interchip
# interconnect per chip. An unknown device kind is an error, never a default.
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}

KEY_OP_BYTES = 8 + 1           # a lane out: key and op code
FIND_RESULT_BYTES = 8 + 1      # a FIND's result back: value and ok flag
WRITE_RESULT_BYTES = 1         # a write's result back: ok flag


def ici_bytes_per_s(kind: str) -> float:
    if kind not in ICI_BYTES_PER_S:
        raise KeyError(f"device kind {kind!r} has no ICI bandwidth here: "
                       f"{sorted(ICI_BYTES_PER_S)}")
    return ICI_BYTES_PER_S[kind]


def least_bytes(ctx) -> float:
    """Bytes off the busiest chip per step, under the contract."""
    chips = ctx["cell"]["chips"]
    lanes = ctx["config"]["lanes_per_plan"]         # per chip
    finds = ctx["find_lanes_per_step"] / chips      # per chip
    writes = lanes - finds
    away = (chips - 1) / chips
    out = away * lanes * KEY_OP_BYTES
    back = away * (finds * FIND_RESULT_BYTES + writes * WRITE_RESULT_BYTES)
    return out + back


def read(ctx):
    import jax
    per = ctx["trace"]["per_chip_step_ns"].values()
    took_s = max(c["collective"] for c in per) * 1e-9
    if took_s <= 0:
        return None
    bw = ici_bytes_per_s(jax.devices()[0].device_kind)
    return 100.0 * least_bytes(ctx) / bw / took_s
