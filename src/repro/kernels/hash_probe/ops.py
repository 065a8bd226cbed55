"""Wrapper: FixedHash state -> shared bucket layout
(`repro.core.layout.bucket_layout`) -> batched Pallas probe.

`fixed_hash_find` is the unjitted entry the `repro.store.exec` dispatch
layer calls from inside already-jitted store steps; `hash_probe` keeps a
standalone jitted form with the contract of `core.hashtable.fixed_find`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.bits import EMPTY
from repro.core.layout import bucket_layout, hash_slot, split_u64
from repro.kernels.hash_probe.kernel import hash_probe_tiles
from repro.kernels.rows import query_tiles


def fixed_hash_find_cols(h, keys, *, tile: int | None = None,
                         interpret: bool = True):
    """Batched probe of a FixedHash via the Pallas kernel — same contract as
    core.hashtable.fixed_find_cols: (found bool[K], vals u64[K], col i32[K]).
    The kernel already emits the hit column (argmax over the bucket row, the
    same first-match rule as the jnp reference), so surfacing it for the tier
    stack's eviction-policy metadata refresh costs nothing. Not jitted:
    callable from inside jitted/shard_mapped store steps."""
    t = keys.shape[0]
    tp, tile = query_tiles(t, tile)
    kp = jnp.pad(keys, (0, tp - t), constant_values=EMPTY)
    slots = hash_slot(kp, h.num_slots)
    qh, ql = split_u64(kp)
    lay = bucket_layout(h.keys)
    # named scope: the kernel shows up as obs.kernel.hash_probe in
    # jax.profiler timelines / lowered HLO (span taxonomy in store/obs.py)
    with jax.named_scope("obs.kernel.hash_probe"):
        found, col = hash_probe_tiles(qh, ql, slots, lay.key_hi, lay.key_lo,
                                      tile=tile, interpret=interpret)
    found = found[:t].astype(bool) & (keys != EMPTY)
    col = col[:t]
    vals = jnp.where(found, h.vals[slots[:t], col], jnp.uint64(0))
    return found, vals, col


def fixed_hash_find(h, keys, *, tile: int | None = None,
                    interpret: bool = True):
    """(found, vals) form of `fixed_hash_find_cols` — the contract of
    core.hashtable.fixed_find."""
    return fixed_hash_find_cols(h, keys, tile=tile, interpret=interpret)[:2]


@partial(jax.jit, static_argnames=("tile", "interpret"))
def hash_probe(h, keys, *, tile: int | None = None, interpret: bool = True):
    """Jitted standalone form of `fixed_hash_find`."""
    return fixed_hash_find(h, keys, tile=tile, interpret=interpret)
