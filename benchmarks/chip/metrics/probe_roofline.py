"""The probe's share of its roofline, in %: the least time the probe could
take over the time it took (`probe_device_ms`).

The least time counts what any implementation must move for a FIND lane,
32 bytes (the 8-byte key in, the entry's 8-byte key and 8-byte value read,
the 8-byte result out), at the chip's HBM bandwidth from the peak table.
The count depends on the lanes alone, so a later kernel cannot make it
stale; the bytes bound it, since a probe does no arithmetic to speak of."""

BYTES_PER_FIND = 32


def read(ctx):
    per = ctx["trace"]["per_chip_step_ns"].values()
    n_chips = len(ctx["trace"]["per_chip_step_ns"])
    probe_s = max(c["probe"] for c in per) * 1e-9
    if probe_s <= 0:
        return None
    lanes = ctx["find_lanes_per_step"] / n_chips   # the busiest chip's share
    least_s = lanes * BYTES_PER_FIND / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / probe_s
