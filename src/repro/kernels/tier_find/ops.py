"""Wrapper: tier-stack state -> shared layouts (`core.layout.bucket_layout`
/ `skiplist_layout` / `spill_layout`) -> ONE fused Pallas dispatch.

`tier_find_fused` is the unjitted entry the `repro.store.exec` dispatch
layer calls from inside already-jitted store steps. Like every kernel
wrapper, the u64 value gathers happen out here (TPU lanes have no u64);
the kernel returns per-tier hit flags and gather indices only. Raw per-tier
results — the fall-through masking lives in `store.exec.tier_find`, shared
with the jnp reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bits import KEY_INF
from repro.core.layout import (bskiplist_layout, bucket_layout, hash_slot,
                               skiplist_layout, spill_layout, split_u64)
from repro.kernels.rows import query_tiles
from repro.kernels.tier_find.kernel import tier_find_tiles


def tier_find_fused(hot, cold, spill, queries, *, warm_layout: str = "level",
                    tile: int | None = None, interpret: bool = True):
    """One dispatch over the whole tier stack. `hot` is a FixedHash,
    `cold` a DetSkiplist, `spill` a SpillTier or None (2-tier stacks).
    Returns ((found, vals, col), (found, vals), (found, vals)) — the same
    raw per-tier contract as `kernels.tier_find.ref.tier_find_ref`.
    `warm_layout="block"` walks the warm tier through the block-major
    B-skiplist planes (`core.layout.bskiplist_layout`) instead of the
    level-major stack — same found/vals, fewer walk steps."""
    t = queries.shape[0]
    tp, tile = query_tiles(t, tile)
    qp = jnp.pad(queries, (0, tp - t), constant_values=KEY_INF)
    qh, ql = split_u64(qp)
    slots = hash_slot(qp, hot.num_slots)
    blay = bucket_layout(hot.keys)
    if warm_layout == "block":
        wlay = bskiplist_layout(cold)
        warm_planes = (wlay.blk_hi, wlay.blk_lo, None,
                       wlay.term_hi, wlay.term_lo, wlay.term_mark)
    else:
        slay = skiplist_layout(cold)
        warm_planes = (slay.lvl_hi, slay.lvl_lo, slay.lvl_child,
                       slay.term_hi, slay.term_lo, slay.term_mark)
    args = (qh, ql, slots, blay.key_hi, blay.key_lo) + warm_planes
    if spill is not None:
        sp = spill_layout(spill.keys, spill.dead, spill.run_start, spill.n)
        args += (sp.key_hi, sp.key_lo, sp.dead, sp.run_off)
    # named scope: visible as obs.kernel.tier_find in jax.profiler
    # timelines / lowered HLO (span taxonomy in store/obs.py)
    with jax.named_scope("obs.kernel.tier_find"):
        out = tier_find_tiles(*args, tile=tile, interpret=interpret)

    valid = queries != KEY_INF
    f_hot = out[0][:t].astype(bool) & valid
    c_hot = out[1][:t]
    v_hot = jnp.where(f_hot, hot.vals[slots[:t], c_hot], jnp.uint64(0))
    f_warm = out[2][:t].astype(bool) & valid
    i_warm = jnp.clip(out[3][:t], 0, cold.capacity - 1)
    v_warm = jnp.where(f_warm, cold.term_vals[i_warm], jnp.uint64(0))
    if spill is not None:
        f_sp = out[4][:t].astype(bool) & valid
        i_sp = jnp.clip(out[5][:t], 0, spill.keys.shape[0] - 1)
        v_sp = jnp.where(f_sp, spill.vals[i_sp], jnp.uint64(0))
    else:
        f_sp = jnp.zeros((t,), bool)
        v_sp = jnp.zeros((t,), jnp.uint64)
    return (f_hot, v_hot, c_hot), (f_warm, v_warm), (f_sp, v_sp)
