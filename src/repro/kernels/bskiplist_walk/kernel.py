"""Batched B-skiplist search — Pallas TPU kernel.

The level-major walk (`kernels/skiplist_search`) touches 4 keys per step —
correct, but it uses 4 of 128 VPU lanes. The B-skiplist walk loads one
lane-width fat node (BSKIP_BLOCK = 128 sorted keys) per step and computes
the searchsorted-left position as ONE vector compare + sum-reduction, so
the descent is `ceil(log_128(..))+1` full-tile steps instead of
`num_levels+1` fan-out-4 steps (e.g. C=8192: 2 blocked vs 12 level-major).

TPU mapping (the per-query row walk of `kernels.rows`):
  * block-major layout (`core.layout.bskiplist_layout`): index levels are a
    [L, W] rectangle, terminal a flat [NB*128] plane — handed over as
    [L, W/128, 128] and [NB, 128] rows, so a fat node IS one 128-lane row;
    whole-array BlockSpecs keep both VMEM-resident.
  * queries tile [T] per grid step in SMEM; keys travel as (hi, lo) u32
    pairs with the shared `key_lt` compare (searchsorted-left needs
    strict <).
  * each step loads one node row at a data-dependent row index and counts
    its entries below the query (one vector compare + one reduction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.layout import BSKIP_BLOCK, key_lt as _lt
from repro.kernels import rows as R
from repro.kernels.skiplist_search.kernel import term_match



def block_walk(qh, ql, bh_ref, bl_ref, th_ref, tl_ref, tm_ref, *,
               levels: int):
    """The in-kernel block-major descent body for ONE query: exactly
    `levels` + 1 whole-node compares (index rows top-down, then the
    terminal block). Shared with the fused tier kernels
    (`kernels/tier_find`, `kernels/tier_apply`), so the blocked warm-tier
    walk has exactly one implementation. bh/bl are [L, W/128, 128] node
    rows, th/tl/tm the [NB, 128] terminal blocks. Returns (found bool,
    term idx i32)."""
    B = R.LANES
    nodes = bh_ref.shape[1]
    nb = th_ref.shape[0]
    i = jnp.int32(0)                            # root: node 0 of row L-1
    for r in range(levels - 1, -1, -1):
        node = jnp.clip(i, 0, nodes - 1)
        sel = R.count(_lt(bh_ref[r, pl.ds(node, 1), :],
                          bl_ref[r, pl.ds(node, 1), :], qh, ql))
        i = node * B + sel                      # child node / block id
    blk = jnp.clip(i, 0, nb - 1)
    sel = R.count(_lt(th_ref[pl.ds(blk, 1), :], tl_ref[pl.ds(blk, 1), :], qh,
                      ql))
    i = jnp.clip(blk * B + sel, 0, nb * B - 1)
    return term_match(qh, ql, i, th_ref, tl_ref, tm_ref), i


def block_rows(blk_hi, blk_lo, term_hi, term_lo, term_mark,
               block: int = BSKIP_BLOCK):
    """Block-major layout planes -> the row forms `block_walk` reads. A
    node must be one 128-lane row."""
    if block != R.LANES:
        raise ValueError(f"block_walk needs {R.LANES}-key nodes, got "
                         f"block={block}")
    L, W = blk_hi.shape
    return (blk_hi.reshape(L, W // block, block),
            blk_lo.reshape(L, W // block, block),
            term_hi.reshape(-1, block), term_lo.reshape(-1, block),
            term_mark.astype(jnp.int32).reshape(-1, block))


def _bw_kernel(qh_ref, ql_ref, bh_ref, bl_ref, th_ref, tl_ref, tm_ref,
               found_ref, idx_ref, *, levels: int):
    def one(q):
        found, i = block_walk(qh_ref[q], ql_ref[q], bh_ref, bl_ref, th_ref,
                              tl_ref, tm_ref, levels=levels)
        found_ref[q] = found.astype(jnp.int32)
        idx_ref[q] = i
    R.for_each_query(qh_ref.shape[0], one)


def bskiplist_walk_tiles(q_hi, q_lo, blk_hi, blk_lo, term_hi, term_lo,
                         term_mark, *, block: int = BSKIP_BLOCK, tile: int,
                         interpret: bool = True):
    """q_*: [T] (T a multiple of `tile`); blk_*: [L, W]; term_*: [NB*B].
    Returns (found i32[T], idx i32[T])."""
    t = q_hi.shape[0]
    L = blk_hi.shape[0]
    if t == 0:   # empty batch: same contract as the jnp reference
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    assert t % tile == 0
    planes = block_rows(blk_hi, blk_lo, term_hi, term_lo, term_mark, block)
    qspec = R.smem_tile(tile)
    kernel = functools.partial(_bw_kernel, levels=L)
    return R.pallas_call(
        kernel, q_hi, q_lo, *planes,
        grid=(t // tile,),
        in_specs=[qspec, qspec] + [R.vmem_whole(a) for a in planes],
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((t,), jnp.int32)] * 2,
        interpret=interpret,
    )
