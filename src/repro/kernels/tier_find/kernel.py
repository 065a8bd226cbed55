"""Fused tier-find — one Pallas dispatch across all three §IX tiers.

The unfused FIND path of the tier stack issues one dispatch per tier per
plan: hot bucket probe, warm skiplist walk, cold spill membership. That is
three memory-system round trips for what is logically ONE lookup whose
later stages only matter on a miss — exactly the repeated-dispatch overhead
the paper's hierarchy exists to avoid (hot keys answered in the fast tier,
cold accesses batched). This kernel fuses the chain: per query tile, ONE
`pallas_call` probes the hot fixed-hash buckets, falls misses through a
level-major walk of the warm skiplist, and finishes with a per-run binary
search over the cold spill tier's `run_offsets` boundaries. The dispatch
count of a FIND plan becomes independent of tier depth.

TPU mapping (the per-query row walk of `kernels.rows`; all three tier
layouts are VMEM-resident via whole-array BlockSpecs; the per-tile VMEM
budget is the sum of the three planes — see docs/tiers.md):
  * hot: `core.layout.bucket_layout` [M, B] u32 planes; the probe body is
    `kernels.hash_probe.kernel.bucket_probe` — shared, not copied.
  * warm: `core.layout.skiplist_layout` [L, C1] u32/i32 level stack + flat
    [C] terminal planes; the walk body is
    `kernels.skiplist_search.kernel.level_walk` — shared, not copied.
    Stacks built with `warm_layout="block"` pass the block-major
    `core.layout.bskiplist_layout` planes instead ([L, W] fat-node rows, no
    child plane — the child id is `node*128 + position`) and the walk body
    is `kernels.bskiplist_walk.kernel.block_walk`: one whole-block compare
    per step instead of a fan-out-4 gather, same found/idx contract.
  * cold: `core.layout.spill_layout` [S] u32 key planes + tombstones + the
    [MAX_SPILL_RUNS + 1] i32 `run_offsets` plane (in SMEM). Each run is
    binary searched with `key_lt` (searchsorted "left" semantics); empty
    runs cost nothing — the run cap is what makes this static-shape.
  * 64-bit keys travel as (hi, lo) u32 planes; all value gathers happen
    outside the kernel where u64 lanes exist (ops.py), and the tier
    fall-through masking (warm only counts on hot miss, spill only on
    hot+warm miss) also lives in the dispatch layer so the jnp reference
    shares it verbatim.

Outputs are per-tier raw probe results: (hot hit, hot col, warm found,
warm terminal idx[, spill found, spill cell]), all i32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.layout import BSKIP_BLOCK, key_lt as _lt
from repro.kernels import rows as R
from repro.kernels.bskiplist_walk.kernel import block_rows, block_walk
from repro.kernels.hash_probe.kernel import bucket_probe
from repro.kernels.skiplist_search.kernel import level_rows, level_walk

_INF32 = 0xFFFFFFFF


def spill_run_probe(qh, ql, sph_ref, spl_ref, spd_ref, off_ref, *,
                    max_runs: int, s: int):
    """The in-kernel cold-tier probe body for ONE query: binary search each
    sorted run [off[r], off[r+1]) for the query, first live match wins (at
    most one exists under the single-tier-residency invariant; the
    tie-break keeps pathological states deterministic) — the same search
    as the jnp reference (`kernels.tier_find.ref.spill_run_cells`). sph/spl
    /spd are [R, 128] row forms of the [s] spill planes, off_ref the SMEM
    run-boundary plane. Shared with the fused apply kernel. Returns
    (found bool, cell i32); the cell of a miss is run 0's insertion
    point."""
    lane = R.lane_iota()

    def at(ref, p):
        row, base = R.load_rows(ref, p)
        return row, lane + base == p

    def less(p):                                   # sp[p] < q
        kh, here = at(sph_ref, p)
        kl, _ = at(spl_ref, p)
        return R.any_lane(here & _lt(kh, kl, qh, ql))

    def step(c):
        lo, hi = c
        mid = jnp.clip((lo + hi) // 2, 0, s - 1)
        lt = less(mid)
        return jnp.where(lt, mid + 1, lo), jnp.where(lt, hi, mid)

    def run(r, c):
        found, cell = c
        end = off_ref[r + 1]
        lo, _ = jax.lax.while_loop(lambda c: c[0] < c[1], step,
                                   (off_ref[r], end))
        pos = jnp.clip(lo, 0, s - 1)
        kh, here = at(sph_ref, pos)
        kl, _ = at(spl_ref, pos)
        kd, _ = at(spd_ref, pos)
        live = (lo < end) & R.any_lane(here & (kh == qh) & (kl == ql)
                                       & (kd == 0))
        cell = jnp.where((r == 0) | (live & ~found), pos, cell)
        return found | live, cell

    return jax.lax.fori_loop(0, max_runs, run,
                             (jnp.bool_(False), jnp.int32(0)))


def spill_rows(sp_hi, sp_lo, sp_dead):
    """Spill layout planes -> the row forms `spill_run_probe` reads."""
    return (R.as_rows(sp_hi, _INF32), R.as_rows(sp_lo, _INF32),
            R.as_rows(sp_dead.astype(jnp.int32), 1))


def warm_rows(lvl_hi, lvl_lo, lvl_child, term_hi, term_lo, term_mark,
              block: int = BSKIP_BLOCK):
    """Warm planes -> row forms: level-major, or block-major when
    `lvl_child` is None."""
    if lvl_child is None:
        return block_rows(lvl_hi, lvl_lo, term_hi, term_lo, term_mark,
                          block)
    return level_rows(lvl_hi, lvl_lo, lvl_child, term_hi, term_lo,
                      term_mark)


def warm_walk(qh, ql, refs, *, levels: int, c1: int, cap: int):
    """The warm-tier walk over `warm_rows` refs: 5 refs = block-major,
    6 = level-major. Returns (found bool, term idx i32)."""
    if len(refs) == 5:
        return block_walk(qh, ql, *refs, levels=levels)
    return level_walk(qh, ql, *refs, levels=levels, fanout=4, c1=c1,
                      cap=cap)


def _tf_kernel(*refs, n_warm: int, levels: int, c1: int, cap: int,
               has_spill: bool, max_runs: int, s: int):
    qh_ref, ql_ref, slot_ref, kh_ref, kl_ref = refs[:5]
    warm = refs[5:5 + n_warm]
    rest = refs[5 + n_warm:]
    if has_spill:
        spill, off_ref = rest[:3], rest[3]
        outs = rest[4:]
    else:
        outs = rest

    def one(i):
        qh, ql = qh_ref[i], ql_ref[i]
        hit, col = bucket_probe(qh, ql, slot_ref[i], kh_ref, kl_ref)
        outs[0][i] = hit.astype(jnp.int32)
        outs[1][i] = col
        found, idx = warm_walk(qh, ql, warm, levels=levels, c1=c1, cap=cap)
        outs[2][i] = found.astype(jnp.int32)
        outs[3][i] = idx
        if has_spill:
            found, cell = spill_run_probe(qh, ql, *spill, off_ref,
                                          max_runs=max_runs, s=s)
            outs[4][i] = found.astype(jnp.int32)
            outs[5][i] = cell
    R.for_each_query(qh_ref.shape[0], one)


def tier_find_tiles(q_hi, q_lo, slots, key_hi, key_lo, lvl_hi, lvl_lo,
                    lvl_child, term_hi, term_lo, term_mark, sp_hi=None,
                    sp_lo=None, sp_dead=None, run_off=None, *,
                    block: int = BSKIP_BLOCK, tile: int,
                    interpret: bool = True):
    """q_*: [T] u32 (T a multiple of `tile`); slots: [T] i32; key_*:
    [M, B]; lvl_*: [L, C1]; term_*: [C]; sp_*: [S] (+ run_off [R+1] i32) or
    None for a 2-tier stack. Returns (hot i32[T], col i32[T], warm i32[T],
    idx i32[T]) plus (spill i32[T], cell i32[T]) when the spill planes are
    given. `lvl_child=None` selects the BLOCKED warm walk: lvl_* then carry
    the `bskiplist_layout` [L, W] fat-node rows and term_* its [NB*block]
    padded terminal planes (warm idx is into that padded plane)."""
    t = q_hi.shape[0]
    L, c1 = lvl_hi.shape
    has_spill = sp_hi is not None
    n_out = 6 if has_spill else 4
    if t == 0:   # empty batch: same contract as the jnp references
        return (jnp.zeros((0,), jnp.int32),) * n_out
    assert t % tile == 0
    warm = warm_rows(lvl_hi, lvl_lo, lvl_child, term_hi, term_lo,
                     term_mark, block)
    qspec = R.smem_tile(tile)
    ins = [q_hi, q_lo, slots, key_hi, key_lo, *warm]
    in_specs = [qspec] * 3 + [R.vmem_whole(a) for a in ins[3:]]
    max_runs = s = 0
    if has_spill:
        spill = spill_rows(sp_hi, sp_lo, sp_dead)
        ins += [*spill, run_off]
        in_specs += [R.vmem_whole(a) for a in spill] + [R.smem_whole(run_off)]
        max_runs = run_off.shape[0] - 1
        s = sp_hi.shape[0]
    kernel = functools.partial(_tf_kernel, n_warm=len(warm), levels=L, c1=c1,
                               cap=term_hi.shape[0], has_spill=has_spill,
                               max_runs=max_runs, s=s)
    return R.pallas_call(
        kernel, *ins,
        grid=(t // tile,),
        in_specs=in_specs,
        out_specs=[qspec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((t,), jnp.int32)] * n_out,
        interpret=interpret,
    )
