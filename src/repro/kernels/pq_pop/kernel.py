"""Batched priority-queue pop (rank-select + descent) — Pallas TPU kernel.

Pop-min on the deterministic skiplist is rank selection over the live
terminal prefix: the j-th pop lane of a plan extracts the j-th smallest
live key. The live-prefix count (the SAME live = unmarked & non-padding
formula as `core.det_skiplist.range_query`) is a cumsum, which Mosaic does
not lower, so it is computed on the host side of the call and handed to
the kernel as one more plane. Per lane the kernel binary-searches that
monotone prefix for the first position reaching the rank (searchsorted-
left), then feeds the selected key through the shared
`skiplist_search.level_walk` descent so the key -> terminal-index mapping
has exactly one implementation across FIND and POP.

Same layout contract as `kernels/skiplist_search`: level-major index stack
([L, C1] u32 x3) + flat terminal planes ([C] u32 hi/lo + marks), all
VMEM-resident as `kernels.rows` row forms; ranks tile [T] per grid step in
SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import rows as R
from repro.kernels.skiplist_search.kernel import level_rows, level_walk

# numpy scalar, not a jnp array: pallas_call rejects closure-captured
# jax-array constants, while numpy scalars inline as literals at trace time
_INF32 = np.uint32(0xFFFFFFFF)


def rank_select(rank, mask, pre_ref, th_ref, tl_ref, *, cap: int):
    """The in-kernel rank-select body for ONE lane: the first terminal
    position whose live-prefix count reaches rank + 1. Returns (found bool,
    key_hi, key_lo) — the key as [1, 1] u32 vectors; a lane whose rank
    exceeds the live population comes back found=False with KEY_INF, so
    the downstream level walk cannot match it against a live entry."""
    want = rank + 1
    total = R.read_i32(pre_ref, cap - 1)
    found = (mask != 0) & (want >= 1) & (want <= total)

    def step(c):
        lo, hi = c
        mid = (lo + hi) // 2
        reached = R.read_i32(pre_ref, mid) >= want
        return jnp.where(reached, lo, mid + 1), jnp.where(reached, mid, hi)

    lo, _ = jax.lax.while_loop(lambda c: c[0] < c[1], step,
                               (jnp.int32(0), jnp.int32(cap)))
    i = jnp.minimum(lo, cap - 1)
    kh = jnp.where(found, R.read_u32(th_ref, i), _INF32)
    kl = jnp.where(found, R.read_u32(tl_ref, i), _INF32)
    return found, kh, kl


def live_prefix(term_hi, term_lo, term_mark):
    """Inclusive count of live (unmarked, non-padding) terminal entries."""
    live = (term_mark == 0) & ~((term_hi == _INF32) & (term_lo == _INF32))
    return jnp.cumsum(live.astype(jnp.int32)).astype(jnp.int32)


def _pq_kernel(rk_ref, mk_ref, pre_ref, lh_ref, ll_ref, lc_ref, th_ref,
               tl_ref, tm_ref, found_ref, idx_ref, *, levels: int,
               fanout: int, c1: int, cap: int):
    def one(q):
        sel, kh, kl = rank_select(rk_ref[q], mk_ref[q], pre_ref, th_ref,
                                  tl_ref, cap=cap)
        walked, i = level_walk(kh, kl, lh_ref, ll_ref, lc_ref, th_ref,
                               tl_ref, tm_ref, levels=levels, fanout=fanout,
                               c1=c1, cap=cap)
        hit = sel & walked
        found_ref[q] = hit.astype(jnp.int32)
        idx_ref[q] = jnp.where(hit, i, 0)
    R.for_each_query(rk_ref.shape[0], one)


def pq_pop_tiles(ranks, mask, lvl_hi, lvl_lo, lvl_child, term_hi, term_lo,
                 term_mark, *, tile: int, interpret: bool = True):
    """ranks i32[T], mask i32[T] (T a multiple of `tile`); lvl_*: [L, C1];
    term_*: [C]. Returns (found i32[T], term idx i32[T])."""
    t = ranks.shape[0]
    L, c1 = lvl_hi.shape
    cap = term_hi.shape[0]
    if t == 0:   # empty batch: same contract as the jnp reference
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    assert t % tile == 0
    planes = (R.as_rows(live_prefix(term_hi, term_lo, term_mark), 0),
              *level_rows(lvl_hi, lvl_lo, lvl_child, term_hi, term_lo,
                          term_mark))
    qspec = R.smem_tile(tile)
    kernel = functools.partial(_pq_kernel, levels=L, fanout=4, c1=c1,
                               cap=cap)
    return R.pallas_call(
        kernel, ranks, mask, *planes,
        grid=(t // tile,),
        in_specs=[qspec, qspec] + [R.vmem_whole(a) for a in planes],
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((t,), jnp.int32)] * 2,
        interpret=interpret,
    )
