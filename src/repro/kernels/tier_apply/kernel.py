"""Fused tier-apply — membership probes + the hot-insert prologue in ONE
Pallas dispatch.

PR 5 fused the tier stack's FIND chain; the write half still ran as
separate phases: a membership probe dispatch, then the
`bucket_insert_plan` sort prologue, victim selection, and scatters as jnp
phases. This kernel folds the whole apply prologue into the fused find's
launch: per plan, ONE `pallas_call` probes all three tiers for residency
(hot bucket probe, warm level walk, per-run spill binary search), applies
the miss fall-through, and — for the lanes that should try the hot tier —
runs the insert linearization (in-batch dup rank, pre-batch existence,
within-slot candidate rank, nth-empty placement column) plus the eviction
policy's victim selection over the metadata plane. The u64 scatters and
victim gathers commit in the glue (`ops.py`) where u64 lanes exist.

Shared bodies, not copies: the hot probe is
`kernels.hash_probe.kernel.bucket_probe`, the warm walk is
`kernels.skiplist_search.kernel.level_walk` (or
`kernels.bskiplist_walk.kernel.block_walk` when the stack selected the
block-major warm layout — no child plane in that case) and the spill probe
is `kernels.tier_find.kernel.spill_run_probe` — the same functions the
fused find uses. The lane math mirrors
`core.hashtable.bucket_insert_plan` / `kernels.tier_apply.ref.
hot_insert_evict` term by term over (hi, lo) u32 planes, so fused/unfused
bit-identity is by construction.

Lanes run in sorted (slot, key) order, one at a time (the per-lane row walk
of `kernels.rows`): every cumsum of the reference is a running count over
that order, carried across lanes — and across grid steps — in three SMEM
counters (lanes of the current key run that try the hot tier, hot-insert
candidates of the current slot run, evicting lanes so far). The run-start
planes say where a run begins, which resets its counter.

Victim selection without an argsort: the reference takes entry
`clip(ev_rank, 0, B-1)` of a stable argsort over the policy score row.
Stable-sort position of column j is `#{k: (score_k, k) <lex (score_j, j)}`
— a counting rank, computed for all B columns at once on a [B, B] tile
(positions are distinct, so exactly one column matches each target rank).
Provably equal to the argsort take. The nth-empty placement column is the
same [B, B] count over the empty mask.

Outputs (all [K], sorted (slot, key) lane order, i32): a flag word (bit 0
in_warm, 1 in_spill, 2 placed, 3 exists, 4 dup, 5 need_ev), col, vcol,
ecol.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layout import BSKIP_BLOCK
from repro.kernels import rows as R
from repro.kernels.hash_probe.kernel import bucket_probe
from repro.kernels.tier_find.kernel import (spill_rows, spill_run_probe,
                                            warm_rows, warm_walk)

# numpy scalar, not a jnp array: pallas_call rejects closure-captured
# jax-array constants, while numpy scalars inline as literals at trace time
_U32MAX = np.uint32(0xFFFFFFFF)
_I32MAX = 2 ** 31 - 1
_I32MIN = -(2 ** 31)
FLAGS = ("in_warm", "in_spill", "placed", "exists", "dup", "need_ev")


def nth_true_col(mask, n):
    """Column of the n-th (1-based) true entry of a [1, B] row, B if the
    row has fewer: `core.hashtable._nth_empty` on one row."""
    b = mask.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    m = jnp.broadcast_to(mask, (b, b))
    upto = jnp.sum((m & (col <= row)).astype(jnp.int32), axis=1,
                   keepdims=True)                     # trues in cols <= j
    here = jnp.max((m & (col == row)).astype(jnp.int32), axis=1,
                   keepdims=True)                     # mask[j]
    return R.first_lane((here > 0) & (upto == n), row[:, :1], b)


def stable_rank_col(score, target):
    """The column whose stable-sort position in the [1, B] score row is
    `target` (0 if none): the argsort take as a counting rank."""
    b = score.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    sc = jnp.broadcast_to(score, (b, b))              # [j, k] = score[k]
    sj = jnp.max(jnp.where(col == row, sc, _I32MIN), axis=1,
                 keepdims=True)                       # score[j]
    before = (sc < sj) | ((sc == sj) & (col < row))
    pos = jnp.sum(before.astype(jnp.int32), axis=1, keepdims=True)
    v = R.first_lane(pos == target, row[:, :1], b)
    return jnp.where(v == b, 0, v)


def _ta_kernel(*refs, n_warm: int, levels: int, c1: int, cap: int,
               policy: str, has_spill: bool, max_runs: int, s: int,
               tile: int):
    (skh_ref, skl_ref, ss_ref, sm_ref, krs_ref, srs_ref, kh_ref, kl_ref,
     meta_ref) = refs[:9]
    warm = refs[9:9 + n_warm]
    i = 9 + n_warm
    if has_spill:
        spill = refs[i:i + 3]
        off_ref = refs[i + 3]
        i += 4
    me_ref = refs[i]
    flag_ref, col_ref, vcol_ref, ecol_ref = refs[i + 1:i + 5]
    cnt = refs[i + 5]            # SMEM running counters, live across tiles
    m_slots, b = kh_ref.shape
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)

    g = pl.program_id(0)

    @pl.when(g == 0)
    def _zero():
        for j in range(3):
            cnt[j] = 0

    def one(q):
        lane = g * tile + q
        kh, kl, slot = skh_ref[q], skl_ref[q], ss_ref[q]
        m = sm_ref[q] != 0
        # membership: masked-off lanes probe with the KEY_INF sentinel, the
        # dispatch layer's `where(mask, keys, KEY_INF)` in u32 planes
        mqh = jnp.where(m, kh, _U32MAX)
        mql = jnp.where(m, kl, _U32MAX)
        f_hot = bucket_probe(mqh, mql, slot, kh_ref, kl_ref)[0] & m
        f_warm = warm_walk(mqh, mql, warm, levels=levels, c1=c1,
                           cap=cap)[0] & m
        if has_spill:
            f_sp = spill_run_probe(mqh, mql, *spill, off_ref,
                                   max_runs=max_runs, s=s)[0] & m
        else:
            f_sp = jnp.bool_(False)
        in_warm = f_warm & ~f_hot
        in_spill = f_sp & ~f_hot & ~f_warm
        ins = m & ~in_warm & ~in_spill          # lanes that try the hot tier

        # in-batch duplicate: an earlier lane of the same (slot, key) run
        # also tries the hot tier
        c_key = jnp.where(krs_ref[q] == lane, 0, cnt[0])
        dup = ins & (c_key > 0)
        cnt[0] = c_key + ins.astype(jnp.int32)

        # pre-batch bucket row: existence, the empty scan, victim metadata
        sc = jnp.clip(slot, 0, m_slots - 1)
        rh = kh_ref[pl.ds(sc, 1), :]
        rl = kl_ref[pl.ds(sc, 1), :]
        hit_e = (rh == kh) & (rl == kl)
        ecol = R.first_lane(hit_e, lane_b, b)
        exists = ins & (ecol < b) & ~dup
        cand = ins & ~dup & ~exists

        # within-slot candidate rank (`core.hashtable._seg_rank`)
        rank = jnp.where(srs_ref[q] == lane, 0, cnt[1])
        cnt[1] = rank + cand.astype(jnp.int32)

        empty = (rh == _U32MAX) & (rl == _U32MAX)
        col_e = nth_true_col(empty, rank + 1)
        fit_e = col_e < b

        if policy != "none":
            n_empty = R.count(empty)
            ev_rank = rank - n_empty
            metar = meta_ref[pl.ds(sc, 1), :]
            score = metar if policy == "lru" else -metar
            score = jnp.where(empty, _I32MAX, score)
            vcol = stable_rank_col(score, jnp.clip(ev_rank, 0, b - 1))
            want_ev = cand & ~fit_e & (ev_rank < b - n_empty)
            n_ev = cnt[2]
            need_ev = want_ev & (n_ev < me_ref[0])
            cnt[2] = n_ev + want_ev.astype(jnp.int32)
        else:
            vcol = jnp.int32(0)
            need_ev = jnp.bool_(False)

        placed = (cand & fit_e) | need_ev
        flags = (in_warm, in_spill, placed, exists, dup, need_ev)
        word = jnp.int32(0)
        for bit, f in enumerate(flags):
            word = word | (f.astype(jnp.int32) << bit)
        flag_ref[q] = word
        col_ref[q] = jnp.where(fit_e, col_e, vcol)
        vcol_ref[q] = vcol
        ecol_ref[q] = jnp.where(ecol < b, ecol, 0)
    R.for_each_query(skh_ref.shape[0], one)


def tier_apply_tiles(sk_hi, sk_lo, slots, sm, krs, srs, key_hi, key_lo,
                     meta, lvl_hi, lvl_lo, lvl_child, term_hi, term_lo,
                     term_mark, max_evict, sp_hi=None, sp_lo=None,
                     sp_dead=None, run_off=None, *, policy: str,
                     block: int = BSKIP_BLOCK, tile: int,
                     interpret: bool = True):
    """sk_*: [K] u32 keys in sorted (slot, key) lane order (K a multiple of
    `tile`); slots/krs/srs: [K] i32 (slot per lane, key-run starts,
    slot-run starts); sm: [K] i32 insert mask; key_*/meta: [M, B];
    lvl_*: [L, C1]; term_*: [C]; max_evict: [1] i32; sp_* [S] + run_off
    [R+1] i32 or None for a 2-tier stack. Returns (flags, col, vcol, ecol)
    as listed in the module docstring. `lvl_child=None` selects the
    BLOCKED warm walk: lvl_* then carry the `bskiplist_layout` [L, W]
    fat-node rows and term_* its [NB*block] padded terminal planes."""
    k = sk_hi.shape[0]
    assert k % tile == 0
    L, c1 = lvl_hi.shape
    has_spill = sp_hi is not None
    warm = warm_rows(lvl_hi, lvl_lo, lvl_child, term_hi, term_lo,
                     term_mark, block)
    qspec = R.smem_tile(tile)
    ins = [sk_hi, sk_lo, slots, sm, krs, srs, key_hi, key_lo, meta, *warm]
    in_specs = [qspec] * 6 + [R.vmem_whole(a) for a in ins[6:]]
    max_runs = s = 0
    if has_spill:
        spill = spill_rows(sp_hi, sp_lo, sp_dead)
        ins += [*spill, run_off]
        in_specs += [R.vmem_whole(a) for a in spill] + [R.smem_whole(run_off)]
        max_runs = run_off.shape[0] - 1
        s = sp_hi.shape[0]
    ins.append(max_evict)
    in_specs.append(R.smem_whole(max_evict))
    kernel = functools.partial(_ta_kernel, n_warm=len(warm), levels=L, c1=c1,
                               cap=term_hi.shape[0], policy=policy,
                               has_spill=has_spill, max_runs=max_runs, s=s,
                               tile=tile)
    return R.pallas_call(
        kernel, *ins,
        grid=(k // tile,),
        in_specs=in_specs,
        out_specs=[qspec] * 4,
        out_shape=[jax.ShapeDtypeStruct((k,), jnp.int32)] * 4,
        scratch_shapes=[pltpu.SMEM((3,), jnp.int32)],
        # the running counters make lane tiles a sequential scan
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )
