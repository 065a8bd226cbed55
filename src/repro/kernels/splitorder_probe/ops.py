"""Wrapper: TwoLevelSplitOrder state -> (hi, lo) planes -> batched Pallas
per-table searchsorted probe.

`twolevel_splitorder_probe` is the unjitted entry the `repro.store.exec`
dispatch layer calls from inside already-jitted store steps — the same
contract as `core.splitorder.twolevel_splitorder_find`: (found bool[K],
vals u64[K]). The bit-reversed-hash sort keys and the table routing both
compute on the u64 host path (TPU lanes have no u64); the kernel sees u32
planes and int32 table ids only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bits import KEY_INF, bitrev64, hash64
from repro.core.layout import split_u64
from repro.kernels.rows import query_tiles
from repro.kernels.splitorder_probe.kernel import splitorder_probe_tiles


def _table_of(h, keys):
    # mirror of core.splitorder._table_of: route by the TOP hash bits
    t_bits = h.num_tables.bit_length() - 1
    if not t_bits:
        return jnp.zeros(keys.shape, jnp.int32)
    return (hash64(keys) >> jnp.uint64(64 - t_bits)).astype(jnp.int32)


def twolevel_splitorder_probe(h, keys, *, tile: int | None = None,
                              interpret: bool = True):
    """Batched probe of a TwoLevelSplitOrder via the Pallas kernel — same
    contract as core.splitorder.twolevel_splitorder_find. Not jitted:
    callable from inside jitted/shard_mapped store steps."""
    t = keys.shape[0]
    tp, tile = query_tiles(t, tile)
    kp = jnp.pad(keys, (0, tp - t), constant_values=KEY_INF)
    rkq = bitrev64(hash64(kp))
    tbl = _table_of(h, kp)
    qrh, qrl = split_u64(rkq)
    qkh, qkl = split_u64(kp)
    rh, rl = split_u64(h.rk)
    kh, kl = split_u64(h.keys)
    # named scope: visible as obs.kernel.splitorder_probe in jax.profiler
    # timelines / lowered HLO (span taxonomy in store/obs.py)
    with jax.named_scope("obs.kernel.splitorder_probe"):
        found, at = splitorder_probe_tiles(qrh, qrl, qkh, qkl, tbl, rh, rl,
                                           kh, kl, tile=tile,
                                           interpret=interpret)
    found = found[:t].astype(bool) & (keys != KEY_INF)
    at = at[:t]
    vals = jnp.where(found, h.vals[tbl[:t], at], jnp.uint64(0))
    return found, vals
