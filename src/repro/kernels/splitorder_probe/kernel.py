"""Two-level split-order probe — Pallas TPU kernel.

The split-order FIND is a searchsorted over keys ordered by bit-reversed
hash (`core.splitorder`). The ONE-level variant binary-searches one global
[C] array — too large for VMEM at production capacity, so it stays a jnp
probe in every exec mode (the same scattered-gather pathology the paper
measured in its one-level table VI). The TWO-level variant routes by the
top hash bits to one of T small tables first (the paper's NUMA
partitioning), so each probe touches ONE [C2] row — the whole [T, C2]
plane stack fits VMEM via whole-array BlockSpecs, and this kernel is the
per-table searchsorted over it.

TPU mapping (the per-query row walk of `kernels.rows`):
  * queries tile [T] per grid step in SMEM; the bit-reversed-hash sort key
    and the original key both travel as (hi, lo) u32 planes
    (`core.layout.split_u64`); table ids arrive precomputed as int32 (the
    splitmix64 scramble runs on the u64 host path).
  * the binary search is log2(C2) steps over the flattened planes (flat
    index = table * C2 + mid, each step one row load + lane select),
    `key_lt` compares — `searchsorted(..., side="left")` semantics,
    bit-identical to the jnp reference by construction.
  * the rk-collision window scan (WINDOW entries from the insertion point,
    matching `core.splitorder._window_match`) is one two-row load + vector
    compare; outputs are (found i32[T], at i32[T]) and the u64 value
    gather happens outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.layout import key_lt as _lt
from repro.kernels import rows as R

WINDOW = 4  # rk-collision scan width — MUST match core.splitorder._WINDOW
_INF32 = 0xFFFFFFFF


def table_search(qrh, qrl, qkh, qkl, tbl, rh_ref, rl_ref, kh_ref, kl_ref, *,
                 n_tables: int, c2: int, window: int = WINDOW):
    """The in-kernel per-table searchsorted + window match body for ONE
    query. rh/rl/kh/kl are row forms of the flattened [n_tables * c2]
    planes; returns (found bool, at i32) with the reference's clipping
    conventions. Window rule: a position the reference clips to c2 - 1
    repeats an entry an earlier window position already compared, so the
    first match among the in-table positions is the reference's."""
    base = jnp.clip(tbl, 0, n_tables - 1) * c2
    lane = R.lane_iota()

    def step(c):
        lo, hi = c
        mid = (lo + hi) // 2
        p = base + jnp.clip(mid, 0, c2 - 1)
        kh, b = R.load_rows(rh_ref, p)
        kl, _ = R.load_rows(rl_ref, p)
        less = R.any_lane((lane + b == p) & _lt(kh, kl, qrh, qrl))
        return jnp.where(less, mid + 1, lo), jnp.where(less, hi, mid)

    pos, _ = jax.lax.while_loop(lambda c: c[0] < c[1], step,
                                (jnp.int32(0), jnp.int32(c2)))
    start = base + pos
    rh, b = R.load_rows(rh_ref, start, 2)
    rl, _ = R.load_rows(rl_ref, start, 2)
    kh, _ = R.load_rows(kh_ref, start, 2)
    kl, _ = R.load_rows(kl_ref, start, 2)
    flat = b + R.lane_iota(2)
    hit = ((flat >= start) & (flat < start + window) & (flat < base + c2)
           & (rh == qrh) & (rl == qrl) & (kh == qkh) & (kl == qkl))
    off = R.first_lane(hit, flat - start, window)
    found = off < window
    return found, jnp.clip(pos + jnp.where(found, off, 0), 0, c2 - 1)


def _so_kernel(qrh_ref, qrl_ref, qkh_ref, qkl_ref, tbl_ref, rh_ref, rl_ref,
               kh_ref, kl_ref, found_ref, at_ref, *, n_tables: int, c2: int,
               window: int):
    def one(i):
        found, at = table_search(qrh_ref[i], qrl_ref[i], qkh_ref[i],
                                 qkl_ref[i], tbl_ref[i], rh_ref, rl_ref,
                                 kh_ref, kl_ref, n_tables=n_tables, c2=c2,
                                 window=window)
        found_ref[i] = found.astype(jnp.int32)
        at_ref[i] = at
    R.for_each_query(qrh_ref.shape[0], one)


def splitorder_probe_tiles(q_rk_hi, q_rk_lo, q_key_hi, q_key_lo, tables,
                           rk_hi, rk_lo, key_hi, key_lo, *, tile: int,
                           interpret: bool = True):
    """q_*: [T] u32 (T a multiple of `tile`); tables: [T] i32;
    rk_*/key_*: [T_tables, C2] u32. Returns (found i32[T], at i32[T])."""
    t = q_rk_hi.shape[0]
    if t == 0:   # empty batch: same contract as the jnp reference
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    assert t % tile == 0
    n_tables, c2 = rk_hi.shape
    planes = [R.as_rows(a.reshape(-1), _INF32)
              for a in (rk_hi, rk_lo, key_hi, key_lo)]
    qspec = R.smem_tile(tile)
    kernel = functools.partial(_so_kernel, n_tables=n_tables, c2=c2,
                               window=WINDOW)
    return R.pallas_call(
        kernel, q_rk_hi, q_rk_lo, q_key_hi, q_key_lo, tables, *planes,
        grid=(t // tile,),
        in_specs=[qspec] * 5 + [R.vmem_whole(a) for a in planes],
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((t,), jnp.int32)] * 2,
        interpret=interpret,
    )
