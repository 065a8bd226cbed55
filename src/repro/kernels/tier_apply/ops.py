"""Wrapper: tier-stack state -> shared layouts -> ONE fused apply dispatch.

`tier_apply_fused` is the unjitted entry `store.exec.tier_apply` calls
from inside already-jitted store steps. The host side owns everything u64
and everything sort-shaped: the (slot, key) lane sort and its run-start
planes (mask-INDEPENDENT — `core.hashtable._batch_plan` sorts unmasked
keys, which is what lets them be precomputed before the kernel decides the
membership mask), the u64 victim gathers, and the key/value/metadata
scatters. The kernel returns flags and columns only. The scatter formulas
are copied term for term from `kernels.tier_apply.ref.hot_insert_evict` /
`core.hashtable.fixed_insert`, so the fused path's state updates are
bit-identical to the unfused references by construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hashtable as ht
from repro.core.bits import EMPTY
from repro.core.layout import (bskiplist_layout, bucket_layout, hash_slot,
                               skiplist_layout, spill_layout, split_u64,
                               val_weight)
from repro.kernels.rows import query_tiles
from repro.kernels.tier_apply.kernel import FLAGS, tier_apply_tiles


def tier_apply_fused(hot, meta, clock, cold, spill, keys, vals, mask,
                     policy: str, max_evict, *, warm_layout: str = "level",
                     tile: int | None = None, interpret: bool = True):
    """One dispatch over the whole apply prologue. `hot` is a FixedHash
    (+ its [M, B] i32 `meta` plane and the batch `clock`), `cold` a
    DetSkiplist, `spill` a SpillTier or None. Returns the same 9-tuple as
    `kernels.tier_apply.ref.tier_apply_ref`. `warm_layout="block"` runs
    the in-kernel warm membership walk over the block-major B-skiplist
    planes — same flags, fewer walk steps."""
    K = keys.shape[0]
    M, B = hot.num_slots, hot.bucket
    if mask is None:
        mask = jnp.ones((K,), bool)
    if K == 0:   # empty batch: same contract as the jnp reference
        z64 = jnp.zeros((0,), jnp.uint64)
        zb = jnp.zeros((0,), bool)
        return hot, meta, zb, zb, zb, zb, z64, z64, zb

    m_eff = mask & (keys != EMPTY)
    slots = hash_slot(keys, M)
    order = ht._lex_sort_slots_keys(slots, keys)
    ss, sk, sv, sm = slots[order], keys[order], vals[order], m_eff[order]
    idx = jnp.arange(K, dtype=jnp.int32)
    inv = jnp.zeros((K,), jnp.int32).at[order].set(idx)
    same = jnp.concatenate([jnp.zeros((1,), bool),
                            (sk[1:] == sk[:-1]) & (ss[1:] == ss[:-1])])
    krs = jax.lax.associative_scan(jnp.maximum, jnp.where(~same, idx, -1))
    srs = jnp.searchsorted(ss, ss, side="left").astype(jnp.int32)

    # pad the lane planes to whole tiles: padded lanes are masked off and
    # each opens its own key and slot run, after every real lane
    kp, tile = query_tiles(K, tile)
    pad = lambda x, v: jnp.pad(x, (0, kp - K), constant_values=v)
    tail = jnp.arange(K, kp, dtype=jnp.int32)
    skh, skl = split_u64(pad(sk, EMPTY))
    blay = bucket_layout(hot.keys)
    if warm_layout == "block":
        wlay = bskiplist_layout(cold)
        warm_planes = (wlay.blk_hi, wlay.blk_lo, None,
                       wlay.term_hi, wlay.term_lo, wlay.term_mark)
    else:
        slay = skiplist_layout(cold)
        warm_planes = (slay.lvl_hi, slay.lvl_lo, slay.lvl_child,
                       slay.term_hi, slay.term_lo, slay.term_mark)
    args = (skh, skl, pad(ss, 0), pad(sm.astype(jnp.int32), 0),
            jnp.concatenate([krs.astype(jnp.int32), tail]),
            jnp.concatenate([srs, tail]),
            blay.key_hi, blay.key_lo, meta) + warm_planes + (
            jnp.asarray(max_evict, jnp.int32).reshape(1),)
    kw = {}
    if spill is not None:
        splay = spill_layout(spill.keys, spill.dead, spill.run_start,
                             spill.n)
        kw = dict(sp_hi=splay.key_hi, sp_lo=splay.key_lo,
                  sp_dead=splay.dead, run_off=splay.run_off)
    # named scope: visible as obs.kernel.tier_apply in jax.profiler
    # timelines / lowered HLO (span taxonomy in store/obs.py)
    with jax.named_scope("obs.kernel.tier_apply"):
        word, col, vcol, ecol = (o[:K] for o in tier_apply_tiles(
            *args, **kw, policy=policy, tile=tile, interpret=interpret))
    (in_warm, in_spill, placed, exists, dup, need_ev) = (
        ((word >> bit) & 1) == 1 for bit in range(len(FLAGS)))

    # u64 victim gathers from the PRE-batch rows (a key placed this batch
    # is never its own batch's victim)
    if policy == "none":
        ev_key = jnp.zeros((K,), jnp.uint64)
        ev_val = jnp.zeros((K,), jnp.uint64)
    else:
        ev_key = hot.keys[ss, vcol]
        ev_val = hot.vals[ss, vcol]

    flat = jnp.where(placed, ss * B + col, M * B)
    nk = hot.keys.reshape(-1).at[flat].set(sk, mode="drop").reshape(M, B)
    nv = hot.vals.reshape(-1).at[flat].set(sv, mode="drop").reshape(M, B)
    nm = meta
    if policy != "none":
        stamp = (jnp.broadcast_to(clock, (K,)).astype(jnp.int32)
                 if policy == "lru" else val_weight(sv))
        nm = meta.reshape(-1).at[flat].set(stamp, mode="drop").reshape(M, B)
        if policy == "lru":
            # upsert traffic refreshes the resident cell's stamp
            eflat = jnp.where(exists, ss * B + ecol, M * B)
            nm = nm.reshape(-1).at[eflat].set(stamp,
                                              mode="drop").reshape(M, B)
    hot2 = ht.FixedHash(
        keys=nk, vals=nv,
        count=hot.count + jnp.sum(placed & ~need_ev).astype(jnp.int64))
    return (hot2, nm, in_warm[inv], in_spill[inv], placed[inv],
            (exists | dup)[inv], ev_key[inv], ev_val[inv], need_ev[inv])
