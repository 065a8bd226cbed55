"""Batched fixed-hash bucket probe — Pallas TPU kernel.

The hot-tier fast path of the §IX tier stack: a fixed-slot table whose
buckets are contiguous [B]-wide rows (`repro.core.layout.BucketLayout`), the
whole table VMEM-resident via whole-array BlockSpecs. One probe = one
bucket-row load + one vector compare across the bucket — the "constant
cost per key" the paper wants, with the bucket row as the VMEM tile.

TPU mapping (the per-query row walk of `kernels.rows`):
  * queries tile [T] per grid step in SMEM; 64-bit keys travel as (hi, lo)
    u32 planes compared per-plane (equality, so no lexicographic carry).
  * slot ids arrive precomputed as int32 (the splitmix64 scramble runs on
    the u64 host path — TPU lanes have no u64; see `core.layout.hash_slot`).
  * one probe = one bucket-row load at the query's slot (a dynamic sublane
    offset into the VMEM-resident [M, B] planes) + one vector compare.
  * outputs are (found i32[T], col i32[T]); the value gather happens
    outside the kernel where u64 lanes exist.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import rows as R


def bucket_probe(qh, ql, slot, kh_ref, kl_ref):
    """The in-kernel bucket probe body for ONE query: one bucket-row load +
    one vector compare. Shared with the fused tier kernels
    (`kernels/tier_find`, `kernels/tier_apply`), so the hot-tier compare
    rule has exactly one implementation. Returns (hit bool, col i32); col
    of a miss is the argmax convention (first column)."""
    m, b = kh_ref.shape
    s = jnp.clip(slot, 0, m - 1)
    hit = (kh_ref[pl.ds(s, 1), :] == qh) & (kl_ref[pl.ds(s, 1), :] == ql)
    col = R.first_lane(hit, jax.lax.broadcasted_iota(jnp.int32, (1, b), 1),
                       b)
    return col < b, jnp.where(col < b, col, 0)


def _hp_kernel(qh_ref, ql_ref, slot_ref, kh_ref, kl_ref, found_ref, col_ref):
    def one(i):
        hit, col = bucket_probe(qh_ref[i], ql_ref[i], slot_ref[i], kh_ref,
                                kl_ref)
        found_ref[i] = hit.astype(jnp.int32)
        col_ref[i] = col
    R.for_each_query(qh_ref.shape[0], one)


def hash_probe_tiles(q_hi, q_lo, slots, key_hi, key_lo, *, tile: int,
                     interpret: bool = True):
    """q_*: [T] u32; slots: [T] i32 (T a multiple of `tile`, see
    `rows.query_tiles`); key_*: [M, B] u32 (the bucket layout). Returns
    (found i32[T], col i32[T])."""
    t = q_hi.shape[0]
    if t == 0:   # empty batch: same contract as the jnp reference
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    assert t % tile == 0
    qspec = R.smem_tile(tile)
    return R.pallas_call(
        _hp_kernel, q_hi, q_lo, slots, key_hi, key_lo,
        grid=(t // tile,),
        in_specs=[qspec, qspec, qspec, R.vmem_whole(key_hi),
                  R.vmem_whole(key_lo)],
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((t,), jnp.int32)] * 2,
        interpret=interpret,
    )
