"""Batched deterministic-skiplist search — Pallas TPU kernel.

Why this kernelizes well (and the randomized skiplist would not): the
1-2-3-4 criterion guarantees EXACTLY L descent steps with a fan-out-4 probe
each — a static loop with fixed-shape 4-wide gathers. Determinism = static
shapes = full lane occupancy (DESIGN.md §2's inversion of the paper's CPU
conclusion).

TPU mapping (the per-query row walk of `kernels.rows`):
  * level-major layout: every level is one contiguous row — the whole index
    stack ([L, C1] u32 x3, handed over as [L, R, 128] rows) and the
    terminal planes are VMEM-resident via whole-array BlockSpecs (the
    skiplist path through HBM pointer-land on CPU becomes L VMEM hops).
  * queries tile [T] per grid step in SMEM; 64-bit keys travel as (hi, lo)
    u32 pairs compared lexicographically (TPU has no native u64 lanes —
    this is the hardware adaptation of the paper's 128-bit key|next words).
  * a descent step reads the child start (one row load + lane select) and
    loads the two rows that hold the fan-out-4 window below it; one vector
    compare + min-reduction picks the child.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.layout import key_leq as _le
from repro.kernels import rows as R

_INF32 = 0xFFFFFFFF


def level_walk(qh, ql, lh_ref, ll_ref, lc_ref, th_ref, tl_ref, tm_ref, *,
               levels: int, fanout: int, c1: int, cap: int):
    """The in-kernel level-major descent body for ONE query: exactly
    `levels` steps of fan-out-`fanout` probes down to the terminal level.
    Shared with the fused tier kernels and the pop kernel, so the warm-tier
    walk has exactly one implementation. lh/ll/lc are the [L, R, 128] row
    forms of the [L, c1] level planes, th/tl/tm the [R, 128] row forms of
    the [cap] terminal planes. qh/ql are scalars or [1, 1] vectors.
    Returns (found bool, term idx i32).

    Window rule (the reference clips window positions to the row length):
    a clipped position repeats the row's last key, which an earlier window
    position already compared, so the first hit is the first hit among the
    in-range positions, and a window with none picks position 0."""
    win = R.lane_iota(2)
    top = R.lane_iota(1)
    ok = (_le(qh, ql, lh_ref[levels - 1, 0:1, :], ll_ref[levels - 1, 0:1, :])
          & (top < min(fanout, c1)))
    i = R.first_lane(ok, top, fanout)
    i = jnp.where(i == fanout, 0, i)

    def step(r, i, bh, bl, hi, lead):
        """Child of node i of level r: the fan-out window below it."""
        start = R.read_i32(lc_ref, jnp.clip(i, 0, c1 - 1), (r,))
        kh, base = R.load_rows(bh, start, 2, lead)
        kl, _ = R.load_rows(bl, start, 2, lead)
        pos = base + win
        cand = ((pos >= start) & (pos < start + fanout) & (pos < hi)
                & _le(qh, ql, kh, kl))
        sel = R.first_lane(cand, pos - start, fanout)
        return start + jnp.where(sel == fanout, 0, sel)

    i = jax.lax.fori_loop(      # index levels top-down, then the terminal
        1, levels, lambda j, i: step(levels - j, i, lh_ref, ll_ref, c1,
                                     (levels - j - 1,)), i)
    i = jnp.clip(step(0, i, th_ref, tl_ref, cap, ()), 0, cap - 1)
    return term_match(qh, ql, i, th_ref, tl_ref, tm_ref), i


def term_match(qh, ql, i, th_ref, tl_ref, tm_ref):
    """True iff terminal position i holds the query key, unmarked."""
    kh, base = R.load_rows(th_ref, i)
    kl, _ = R.load_rows(tl_ref, i)
    km, _ = R.load_rows(tm_ref, i)
    return R.any_lane((R.lane_iota() + base == i) & (kh == qh) & (kl == ql)
                      & (km == 0))


def level_rows(lvl_hi, lvl_lo, lvl_child, term_hi, term_lo, term_mark):
    """Level-major layout planes -> the row forms `level_walk` reads."""
    return (R.as_rows(lvl_hi, _INF32), R.as_rows(lvl_lo, _INF32),
            R.as_rows(lvl_child, 0), R.as_rows(term_hi, _INF32),
            R.as_rows(term_lo, _INF32),
            R.as_rows(term_mark.astype(jnp.int32), 1))


def _sk_kernel(qh_ref, ql_ref, lh_ref, ll_ref, lc_ref, th_ref, tl_ref,
               tm_ref, found_ref, idx_ref, *, levels: int, fanout: int,
               c1: int, cap: int):
    def one(q):
        found, i = level_walk(qh_ref[q], ql_ref[q], lh_ref, ll_ref, lc_ref,
                              th_ref, tl_ref, tm_ref, levels=levels,
                              fanout=fanout, c1=c1, cap=cap)
        found_ref[q] = found.astype(jnp.int32)
        idx_ref[q] = i
    R.for_each_query(qh_ref.shape[0], one)


def skiplist_search_tiles(q_hi, q_lo, lvl_hi, lvl_lo, lvl_child,
                          term_hi, term_lo, term_mark, *, tile: int,
                          interpret: bool = True):
    """q_*: [T] (T a multiple of `tile`); lvl_*: [L, C1]; term_*: [C].
    Returns (found i32[T], idx i32[T])."""
    t = q_hi.shape[0]
    L, c1 = lvl_hi.shape
    cap = term_hi.shape[0]
    if t == 0:   # empty batch: same contract as the jnp reference
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    assert t % tile == 0
    planes = level_rows(lvl_hi, lvl_lo, lvl_child, term_hi, term_lo,
                        term_mark)
    qspec = R.smem_tile(tile)
    kernel = functools.partial(_sk_kernel, levels=L, fanout=4, c1=c1,
                               cap=cap)
    return R.pallas_call(
        kernel, q_hi, q_lo, *planes,
        grid=(t // tile,),
        in_specs=[qspec, qspec] + [R.vmem_whole(a) for a in planes],
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((t,), jnp.int32)] * 2,
        interpret=interpret,
    )
