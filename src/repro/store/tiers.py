"""Hierarchical tier stack (paper §IX): hot fixed-hash tier with pluggable
eviction policies, warm ordered skiplist tier, and an optional cold
"host spill" tier of append-only sorted runs.

The paper's closing proposal is *hierarchical usage of concurrent data
structures* so hot data stays in the fastest tier and remote/cold accesses
are batched. Related work ("Skiplists with Foresight", NUMA-local skip
graphs) shows the latency win comes from locality-aware PLACEMENT and
EVICTION, not just capacity spill — hence the policy layer here.

Tier layout (every live key resides in EXACTLY ONE tier):

  hot    fixed-slot hash (`core.hashtable.FixedHash`): one VMEM-tile row
         per bucket, the kernelized constant-cost fast path, annotated with
         a per-entry policy-metadata plane (`core.layout.policy_arrays`)
  warm   the deterministic skiplist (ordered, large — the `cold` field,
         named for continuity with the two-tier stack)
  cold   `SpillTier` (depth-3 only): append-only sorted runs next to the
         hot/warm structures (`core.layout.spill_arrays`; device-resident
         like every other state leaf). Cells below the cursor are
         immutable except for tombstones, so the region could live in
         host memory and be DMA'd in bulk; runs are merged on scan, probed
         by a per-run binary search over the `core.layout.run_offsets`
         boundary plane, and `spill_compact` rewrites them (dropping
         tombstones) when dead entries pass 1/4 of the appended total OR
         the live run count nears `core.layout.MAX_SPILL_RUNS` (the static
         cap that keeps the probe's boundary plane fixed-size).

Probe execution (the `fused` knob, default True): the FIND phases issue
ONE `store.exec.tier_find` dispatch per plan — the fused
`kernels/tier_find` pallas_call probes hot buckets, walks the warm
skiplist, and binary-searches the spill runs in a single launch, so the
hot path's dispatch count is independent of tier depth (one for the
insert-phase membership probe + one for the FIND phase = 2 per apply,
down from 5). `fused=False` keeps the original three-dispatch chain —
bit-identical results AND residency by contract (the parity suite
`tests/test_tier_find.py` asserts it across exec modes and shardings).

Eviction policies (the `policy` knob; state carried in `TierState.hot_meta`
plus the `clock` batch counter — all deterministic, jit-able, and
bit-identical across exec modes):

  none   no eviction: bucket-full inserts fall through (spill-only, the
         original two-tier behavior)
  lru    LRU-by-batch: `hot_meta[slot, col]` holds the batch clock of the
         entry's last touch — placement, FIND hit, or an INSERT that found
         the key already resident; a full bucket evicts the oldest stamp
         (ties: lowest column) down to the warm tier and installs the
         incoming key hot — repeated access keeps an entry resident
  size   size-aware: `hot_meta` holds `core.layout.val_weight` (payload
         bytes); a full bucket evicts the LARGEST payload first (ties:
         lowest column), biasing the fast tier toward many small entries

Batched movement between tiers, all inside one `apply` (no host round
trips):
  * spill     — insert lanes the hot tier cannot place (bucket full under
                `none`, or more lanes than bucket width under any policy)
                fall to warm; warm capacity overflow appends to the cold
                spill runs
  * eviction  — policy victims demote hot -> warm (-> spill runs on warm
                overflow), batched with the inserts that displaced them.
                Evictions are capped at the lower tiers' free headroom, so
                a displaced resident ALWAYS lands somewhere: when the
                whole stack is full, the NEW lane fails (the flat
                backend's allocation-failure analogue), never a resident
  * promotion — FIND lanes served by warm or spill are re-installed hot
                (evicting victims under `lru`/`size`; only into free space
                under `none`) and removed from their source tier
  * flush     — explicit bulk demotion of the whole hot tier into warm
                (-> spill on overflow); entries the lower tiers cannot
                absorb stay hot (demotion is lossless here too). Flushed
                cells' policy metadata is cleared WITH the keys, but the
                batch clock and the cumulative eviction/promotion counters
                are preserved — a flush is an event in the policy's
                history, not a history reset.

Linearization matches every flat backend: INSERTS -> DELETES -> FINDS,
first lane wins on duplicates. Eviction and promotion are
membership-neutral (they move keys between tiers, never add or drop one),
so EVERY tier configuration is bit-identical to the flat `det_skiplist`
backend for the same `OpPlan` stream — asserted across all registered tier
configs by `tests/test_store_api.py`, across exec modes by
`tests/test_exec_modes.py`, and for residency itself (the full state, not
just results) by `tests/test_tiers3.py`.

`scan` stays exact: the warm range count/slice merges with in-range
reductions over the hot table and the live spill-run entries; materialized
rows are the sorted union of all tiers, truncated at `max_out`.

Warm probe layout (the `warm_layout` knob, default "level"): "block"
walks the warm tier through the block-major B-skiplist planes
(`core.layout.bskiplist_layout` — lane-width 128-key fat nodes, one
whole-block compare per descent step) instead of the level-major
fan-out-4 stack, on the fused AND unfused paths. Like `fused`, it is an
execution knob: results, the full residency pytree, and the metrics
plane are bit-identical across layouts (`tests/test_bskiplist.py`).

Registered configurations (see `store.api`): `hash+skiplist` (2-tier,
policy `none` — unchanged semantics), `tiered3`, `tiered3/lru`,
`tiered3/size` (3-tier), `tiered3/b128` (3-tier, block-major warm walks).
Any depth/policy combination can be constructed
directly: `TieredBackend(depth=2, policy="lru")`. Capacity sizing: the warm
tier holds `capacity` entries and (depth 3) the spill runs another
`spill_cap` (default `capacity`), so policy-driven demotion always has
somewhere to put a victim until the whole stack is genuinely full.
See docs/tiers.md for the architecture walkthrough and a worked example.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import det_skiplist as dsl
from repro.core import hashtable as ht
from repro.core.bits import EMPTY, KEY_INF, dup_in_run
from repro.core.layout import (SpillLayout, hash_slot, policy_arrays,
                               spill_arrays)
from repro.kernels.tier_apply.ref import hot_insert_evict
from repro.kernels.tier_find.ref import spill_find_runs, spill_run_cells
from repro.store import exec as exec_
from repro.store import obs
from repro.store.api import (OP_DELETE, OP_FIND, OP_INSERT, OpPlan,
                             get_backend, register, uniform_stats)
from repro.store.backends import _pow2, finalize_results

POLICIES = ("none", "lru", "size")


class SpillTier(NamedTuple):
    """Cold host-spill tier: append-only sorted runs (`core.layout.
    spill_arrays`). Each batch that demotes past the warm tier appends ONE
    sorted run; `run_start[i]` marks run boundaries, `dead` tombstones
    entries deleted or promoted away, `n` is the append cursor. Cells below
    `n` are never rewritten — the append-only contract that lets the region
    live off-device."""
    keys: jnp.ndarray       # [S] uint64, KEY_INF pad
    vals: jnp.ndarray       # [S] uint64
    dead: jnp.ndarray       # [S] bool tombstones
    run_start: jnp.ndarray  # [S] bool — True at the first entry of each run
    n: jnp.ndarray          # scalar int32 append cursor
    n_dead: jnp.ndarray     # scalar int32


def spill_init(capacity: int) -> SpillTier:
    keys, vals, dead, run_start = spill_arrays(capacity)
    return SpillTier(keys=keys, vals=vals, dead=dead, run_start=run_start,
                     n=jnp.int32(0), n_dead=jnp.int32(0))


def spill_append(sp: SpillTier, keys, vals, mask):
    """Append the masked lanes as ONE sorted run (in-batch duplicates keep
    the first lane). Lanes past capacity are dropped (appended=False) — the
    whole stack is full at that point, the flat backend's
    allocation-failure analogue. Returns (sp', appended[K])."""
    K = keys.shape[0]
    S = sp.keys.shape[0]
    mask = mask & (keys != KEY_INF)
    order = jnp.argsort(keys, stable=True)
    sk, sv, sm = keys[order], vals[order], mask[order]
    same = jnp.concatenate([jnp.zeros((1,), bool), sk[1:] == sk[:-1]])
    put = sm & ~dup_in_run(same, sm)
    rank = jnp.cumsum(put.astype(jnp.int32)) - 1
    ok = put & (sp.n + rank < S)
    dest = jnp.where(ok, sp.n + rank, S)
    nk = sp.keys.at[dest].set(sk, mode="drop")
    nv = sp.vals.at[dest].set(sv, mode="drop")
    cnt = jnp.sum(ok).astype(jnp.int32)
    rs = sp.run_start.at[jnp.where(cnt > 0, sp.n, S)].set(True, mode="drop")
    inv = jnp.zeros((K,), jnp.int32).at[order].set(
        jnp.arange(K, dtype=jnp.int32))
    obs.record("spill_appends", lambda: jnp.sum(ok))
    return sp._replace(keys=nk, vals=nv, run_start=rs, n=sp.n + cnt), ok[inv]


def spill_find_ref(sp: SpillTier, queries):
    """Membership probe over the live run entries: (found[Q], vals[Q]).
    The jnp reference behind `store.exec.spill_find` — a per-run binary
    search over the `run_offsets` boundaries
    (`kernels.tier_find.ref.spill_find_runs`), O(runs * log run-len)
    instead of the old O(S) masked flat compare, so every exec mode AND
    the fused tier-find kernel share one cold-tier algorithm."""
    return spill_find_runs(sp.keys, sp.vals, sp.dead, sp.run_start, sp.n,
                           queries)


def spill_compact(sp: SpillTier) -> SpillTier:
    """Merge the runs: drop tombstones and rewrite the live entries as ONE
    sorted run (the batched analogue of an LSM run merge). Triggered by
    `apply` when tombstones exceed 1/4 of the appended entries — the same
    threshold discipline as the skiplist's compaction — so churn cannot
    exhaust the spill capacity while live occupancy is low. Between
    compactions the append-only contract holds unchanged."""
    live = ~sp.dead & (sp.keys != KEY_INF)
    skey = jnp.where(live, sp.keys, KEY_INF)
    o = jnp.argsort(skey)
    n_live = jnp.sum(live).astype(jnp.int32)
    return SpillTier(
        keys=skey[o],
        vals=jnp.where(live, sp.vals, jnp.uint64(0))[o],
        dead=jnp.zeros_like(sp.dead),
        run_start=jnp.zeros_like(sp.run_start).at[0].set(n_live > 0),
        n=n_live, n_dead=jnp.zeros_like(sp.n_dead))


def spill_discard(sp: SpillTier, keys, mask):
    """Tombstone live matches (used by DELETE and by promotion). The cell
    lookup is the same per-run binary search as the membership probe
    (`spill_run_cells` — the update path shares the find path's O(runs *
    log run-len) algorithm, not the old flat compare). In-batch duplicate
    lanes for one key dedupe by cell so `n_dead` stays exact.
    Returns (sp', hit[K])."""
    K = keys.shape[0]
    S = sp.keys.shape[0]
    hit, at = spill_run_cells(sp.keys, sp.dead, sp.run_start, sp.n, keys)
    found = hit & mask & (keys != KEY_INF)
    cell = jnp.where(found, at.astype(jnp.int32), S)
    o = jnp.argsort(cell, stable=True)
    cs = cell[o]
    fdup = jnp.concatenate([jnp.zeros((1,), bool),
                            cs[1:] == cs[:-1]]) & found[o]
    inv = jnp.zeros((K,), jnp.int32).at[o].set(jnp.arange(K, dtype=jnp.int32))
    eff = found & ~fdup[inv]
    nd = sp.dead.at[jnp.where(eff, cell, S)].set(True, mode="drop")
    return sp._replace(dead=nd,
                       n_dead=sp.n_dead + jnp.sum(eff).astype(jnp.int32)), eff


def spill_maintain(sp: SpillTier) -> SpillTier:
    """Run-merging maintenance, applied at the end of every `apply`/`flush`
    that carries a spill tier. Compacts when tombstones pass
    1/`SpillLayout.COMPACT_DEAD_FRAC` of the appended total (the churn
    rule) OR when the live run count could exceed `SpillLayout.MAX_RUNS`
    next batch (one apply appends at most `SpillLayout.RUNS_PER_APPLY`
    runs: eviction demotes, insert overflow, promotion demotes). The
    thresholds live on `core.layout.SpillLayout` — the SAME class the
    probe kernels size their boundary plane from — so the compaction
    policy and the layout's static-shape assumptions cannot drift apart.
    The second trigger is what makes the run cap an INVARIANT — and the
    cap is what gives the per-run probe (jnp and the fused kernel alike)
    a static run-boundary plane to binary-search."""
    churn = sp.n_dead * SpillLayout.COMPACT_DEAD_FRAC > sp.n
    runs = jnp.sum(sp.run_start.astype(jnp.int32))
    return jax.lax.cond(
        churn | (runs + SpillLayout.RUNS_PER_APPLY > SpillLayout.MAX_RUNS),
        spill_compact, lambda s: s, sp)


class TierState(NamedTuple):
    hot: ht.FixedHash          # fixed-slot table (the near/fast tier)
    hot_meta: jnp.ndarray      # [M, B] int32 policy metadata (stamp/weight)
    clock: jnp.ndarray         # scalar int32 — the LRU batch clock
    n_evict: jnp.ndarray       # scalar int64 — cumulative policy evictions
    n_promote: jnp.ndarray     # scalar int64 — cumulative promotions
    cold: dsl.DetSkiplist      # warm ordered tier (historic field name)
    spill: Optional[SpillTier]  # cold spill runs; None on 2-tier stacks


# The policy-driven hot insert (`hot_insert_evict`, formerly defined here)
# moved to `kernels.tier_apply.ref` so the fused apply kernel, the unfused
# `store.exec.hot_update` dispatch, and the promotion path below all share
# ONE implementation of the victim-selection lane math.


class TieredBackend:
    """The configurable tier stack behind the registry strings
    `hash+skiplist` (depth 2) and `tiered3[/lru|/size]` (depth 3)."""

    ordered = True
    kernelized = True      # fused tier find / per-tier probes -> kernels

    def __init__(self, promote: bool = True, depth: int = 2,
                 policy: str = "none", fused: bool = True,
                 warm_layout: str = "level"):
        assert depth in (2, 3), "2 (hash->skiplist) or 3 (+ host spill)"
        assert policy in POLICIES, f"policy must be one of {POLICIES}"
        assert warm_layout in ("level", "block")
        self.promote = promote
        self.depth = depth
        self.policy = policy
        self.fused = fused     # one tier_find dispatch per probe phase
        # warm probe layout: level-major fan-out-4 walk, or the block-major
        # B-skiplist (lane-width fat nodes, one whole-block compare per
        # step). An execution knob like `fused` — results, residency, and
        # the metrics plane are bit-identical either way.
        self.warm_layout = warm_layout
        base = "hash+skiplist" if depth == 2 else "tiered3"
        name = base if policy == "none" else f"{base}/{policy}"
        self.name = name + ("/b128" if warm_layout == "block" else "")

    def init(self, capacity: int, hot_bucket: int = 8, hot_frac: int = 8,
             spill_cap: int | None = None, **kw) -> TierState:
        """Warm tier sized at `capacity`; hot tier at ~capacity/hot_frac;
        depth-3 spill runs at `spill_cap` (default `capacity`)."""
        hot_slots = _pow2(max(capacity // (hot_frac * hot_bucket), 1))
        return TierState(
            hot=ht.fixed_init(hot_slots, hot_bucket),
            hot_meta=policy_arrays((hot_slots, hot_bucket)),
            clock=jnp.int32(0),
            n_evict=jnp.int64(0),
            n_promote=jnp.int64(0),
            cold=dsl.skiplist_init(capacity),
            spill=(spill_init(capacity if spill_cap is None else spill_cap)
                   if self.depth == 3 else None))

    # -- tier movement helpers ----------------------------------------------

    def _demote(self, cold, spill, keys, vals, mask):
        """Push lanes down: warm skiplist first; lanes the skiplist cannot
        take (capacity) append to the spill runs (depth 3) or drop (depth 2
        — the flat backend's allocation-failure analogue)."""
        with obs.scope("demote"):
            cold, ok_c, ex_c = dsl.insert_batch(cold, keys, vals, mask)
            ok = ok_c | ex_c
            if spill is not None:
                spill, ok_s = spill_append(spill, keys, vals, mask & ~ok)
                ok = ok | ok_s
            obs.record("demotions", lambda: jnp.sum(ok & mask))
        return cold, spill, ok

    def _record_probe_cost(self, cold, spill, queries):
        """`warm_probe_steps` / `spill_runs_searched` for ONE lower-tier
        probe phase, derived from the probe INPUTS (pre-probe tier state +
        query mask). The deterministic skiplist walk descends every level
        exactly once and the spill probe binary-searches every live run, so
        the counts are exact per probed lane — and identical on the fused
        and unfused paths by construction, since both consume the same
        inputs. The counters use the level-major walk formula for BOTH
        warm layouts: `warm_layout` is an execution knob like `fused`, and
        the metrics plane must stay bit-identical across execution knobs
        (the blocked walk's smaller step count is reported in the bench
        rows, not here)."""
        if not obs.collecting():
            return
        lanes = jnp.sum(queries != KEY_INF).astype(jnp.int64)
        obs.record("warm_probe_steps", lanes * (cold.num_levels + 1))
        if spill is not None:
            runs = jnp.sum(
                spill.run_start
                & (jnp.arange(spill.run_start.shape[0]) < spill.n)
            ).astype(jnp.int64)
            obs.record("spill_runs_searched", lanes * runs)

    def _headroom(self, cold, spill):
        """Free lower-tier slots = the eviction budget: how many hot
        victims the warm tier + spill runs can absorb RIGHT NOW. Capping
        evictions at this keeps demotion lossless — when the stack is
        genuinely full, the NEW key's lane fails (like the flat backend's
        allocation failure), never a resident's."""
        free = (jnp.int32(cold.term_keys.shape[0]) - cold.n_term)
        if spill is not None:
            free = free + (jnp.int32(spill.keys.shape[0]) - spill.n)
        return free

    # -- apply ---------------------------------------------------------------

    def apply(self, state: TierState, plan: OpPlan):
        hot, meta, clock = state.hot, state.hot_meta, state.clock
        cold, spill = state.cold, state.spill
        n_evict, n_promote = state.n_evict, state.n_promote
        ops, keys, vals = plan.ops, plan.keys, plan.vals
        K = keys.shape[0]
        valid = plan.mask & (ops >= 0)
        ins_m = valid & (ops == OP_INSERT)
        del_m = valid & (ops == OP_DELETE)
        qk = jnp.where(valid, keys, KEY_INF)

        # INSERTS: insert-if-absent across ALL tiers; lanes absent
        # everywhere try hot first (under the policy), the rest fall down.
        # Fused: membership + the whole hot-insert prologue (bucket plan,
        # victim selection) is ONE tier_apply dispatch per plan; unfused:
        # one probe dispatch per lower tier, then one hot_update dispatch.
        with obs.scope("insert"):
            ins_k = jnp.where(ins_m, keys, KEY_INF)
            self._record_probe_cost(cold, spill, ins_k)
            if self.fused:
                (hot, meta, in_cold, in_spill, ins_hot, ex_hot,
                 ev_k, ev_v, ev_m) = exec_.tier_apply(
                    hot, meta, clock, cold, spill, keys, vals, ins_m,
                    self.policy, self._headroom(cold, spill),
                    warm_layout=self.warm_layout)
                try_hot = ins_m & ~in_cold & ~in_spill
            else:
                warm_find = (exec_.bskiplist_find
                             if self.warm_layout == "block"
                             else exec_.skiplist_find)
                in_cold, _, _ = warm_find(cold, ins_k)
                if spill is not None:
                    in_spill, _ = exec_.spill_find(spill, ins_k)
                else:
                    in_spill = jnp.zeros((K,), bool)
                try_hot = ins_m & ~in_cold & ~in_spill
                (hot, meta, ins_hot, ex_hot,
                 ev_k, ev_v, ev_m) = exec_.hot_update(
                    hot, meta, clock, keys, vals, try_hot, self.policy,
                    self._headroom(cold, spill))
            if self.policy != "none":
                n_evict = n_evict + jnp.sum(ev_m).astype(jnp.int64)
                obs.record("evictions", lambda: jnp.sum(ev_m))
                # victims demote first — the eviction cap guarantees they
                # fit, so a displaced resident is never the lane that fails
                cold, spill, _ = self._demote(cold, spill, ev_k, ev_v, ev_m)
            down = try_hot & ~ins_hot & ~ex_hot
            cold, spill, down_ok = self._demote(
                cold, spill, jnp.where(down, keys, KEY_INF), vals, down)
            inserted = ins_hot | down_ok
            existed = ex_hot | in_cold | in_spill

        # DELETES: the single-tier invariant means exactly one tier can hit
        with obs.scope("delete"):
            hot, del_hot = ht.fixed_delete(hot, keys, del_m)
            cold, del_cold = dsl.delete_batch(cold, keys, del_m & ~del_hot)
            if spill is not None:
                spill, del_spill = spill_discard(
                    spill, keys, del_m & ~del_hot & ~del_cold)
            else:
                del_spill = jnp.zeros((K,), bool)
            deleted = del_hot | del_cold | del_spill

        # FINDS observe the post-update state of every tier. Fused: the
        # whole hot -> warm -> spill chain is ONE tier_find dispatch per
        # plan (dispatch count independent of tier depth); unfused: one
        # dispatch per tier. Either way the hot probe reports the hit
        # column so the LRU policy can refresh its stamps.
        with obs.scope("find"):
            self._record_probe_cost(cold, spill, qk)
            if self.fused:
                ((f_hot, v_hot, c_hot), (f_cold, v_cold),
                 (f_spill, v_spill)) = exec_.tier_find(
                    hot, cold, spill, qk, warm_layout=self.warm_layout)
            else:
                warm_find = (exec_.bskiplist_find
                             if self.warm_layout == "block"
                             else exec_.skiplist_find)
                f_hot, v_hot, c_hot = exec_.hash_find_cols(hot, qk)
                f_cold, v_cold, _ = warm_find(cold, qk)
                if spill is not None:
                    f_spill, v_spill = exec_.spill_find(spill, qk)
                else:
                    f_spill = jnp.zeros((K,), bool)
                    v_spill = jnp.zeros((K,), jnp.uint64)
            # per-tier FIND attribution + hot probe collisions — all
            # derived from post-branch probe outputs and the post-update
            # hot table, so the fused and unfused paths record identical
            # counters (single-tier residency makes f_* disjoint)
            fnd_m = valid & (ops == OP_FIND)
            obs.record("hot_hits", lambda: jnp.sum(fnd_m & f_hot))
            obs.record("warm_hits", lambda: jnp.sum(fnd_m & f_cold))
            obs.record("spill_hits", lambda: jnp.sum(fnd_m & f_spill))
            obs.record("bucket_collisions",
                       lambda: obs.bucket_collision_count(hot, qk))
            found = f_hot | f_cold | f_spill
            fvals = jnp.where(f_hot, v_hot,
                              jnp.where(f_cold, v_cold, v_spill))
            if self.policy == "lru":
                touch = fnd_m & f_hot
                tslots = hash_slot(qk, hot.num_slots)
                cell = jnp.where(touch, tslots * hot.bucket + c_hot,
                                 hot.keys.size)
                meta = meta.reshape(-1).at[cell].set(
                    jnp.broadcast_to(clock, (K,)).astype(jnp.int32),
                    mode="drop").reshape(meta.shape)

        # PROMOTION (after the linearization point; membership-neutral):
        # warm/spill-served FIND lanes migrate up, displacing policy victims
        if self.promote:
            with obs.scope("promote"):
                prom = valid & (ops == OP_FIND) & found & ~f_hot
                pv = jnp.where(f_cold, v_cold, v_spill)
                if self.policy == "none":
                    hot, prom_ok, _ = ht.fixed_insert(hot, keys, pv, prom)
                else:
                    (hot, meta, prom_ok, _,
                     ev_k, ev_v, ev_m) = hot_insert_evict(
                        hot, meta, clock, keys, pv, prom, self.policy,
                        self._headroom(cold, spill))
                    n_evict = n_evict + jnp.sum(ev_m).astype(jnp.int64)
                    obs.record("evictions", lambda: jnp.sum(ev_m))
                    cold, spill, _ = self._demote(cold, spill,
                                                  ev_k, ev_v, ev_m)
                n_promote = n_promote + jnp.sum(prom_ok).astype(jnp.int64)
                obs.record("promotions", lambda: jnp.sum(prom_ok))
                cold, _ = dsl.delete_batch(cold, keys,
                                           prom & prom_ok & f_cold)
                if spill is not None:
                    spill, _ = spill_discard(spill, keys,
                                             prom & prom_ok & f_spill)

        # spill-run maintenance: merge runs + drop tombstones at the same
        # 25% threshold discipline as the skiplist compaction (so churn
        # cannot exhaust the append cursor while live occupancy stays low)
        # and keep the live run count under the static MAX_SPILL_RUNS cap
        # the per-run probe's boundary plane is sized for
        if spill is not None:
            with obs.scope("compact"):
                pre_dead = spill.n_dead
                spill = spill_maintain(spill)
                obs.record("tombstones_reclaimed",
                           lambda: pre_dead - spill.n_dead)

        state2 = TierState(hot=hot, hot_meta=meta, clock=clock + 1,
                           n_evict=n_evict, n_promote=n_promote,
                           cold=cold, spill=spill)
        return state2, finalize_results(ops, valid, found, fvals, inserted,
                                        existed, deleted)

    # -- ordered scan over all tiers -----------------------------------------

    def scan(self, state: TierState, lo, hi, max_out: int):
        cnt_c, k_c, v_c, val_c = dsl.range_query(state.cold, lo, hi, max_out)

        def tier_rows(tk, tv, live):
            """In-range count + per-query sorted top-max_out of a flat
            (keys, vals, live) tier view."""
            in_r = (tk[None, :] >= lo[:, None]) & (tk[None, :] < hi[:, None]) \
                & live[None, :]
            cnt = jnp.sum(in_r, axis=1).astype(cnt_c.dtype)
            sk = jnp.where(in_r, tk[None, :], KEY_INF)
            o = jnp.argsort(sk, axis=1)[:, :max_out]
            return (cnt, jnp.take_along_axis(sk, o, axis=1),
                    jnp.take_along_axis(
                        jnp.broadcast_to(tv[None, :], sk.shape), o, axis=1))

        hk = state.hot.keys.reshape(-1)
        cnt_h, hkeys, hvals = tier_rows(hk, state.hot.vals.reshape(-1),
                                        hk != EMPTY)
        count = cnt_c + cnt_h
        parts_k = [jnp.where(val_c, k_c, KEY_INF), hkeys]
        parts_v = [jnp.where(val_c, v_c, jnp.uint64(0)), hvals]
        if state.spill is not None:
            sp = state.spill
            cnt_s, skeys, svals = tier_rows(sp.keys, sp.vals,
                                            ~sp.dead & (sp.keys != KEY_INF))
            count = count + cnt_s
            parts_k.append(skeys)
            parts_v.append(svals)

        # materialize the sorted union, truncated at max_out (single-tier
        # residency means the union has no cross-tier duplicates)
        allk = jnp.concatenate(parts_k, axis=1)
        allv = jnp.concatenate(parts_v, axis=1)
        om = jnp.argsort(allk, axis=1)[:, :max_out]
        keys = jnp.take_along_axis(allk, om, axis=1)
        vals = jnp.take_along_axis(allv, om, axis=1)
        return count, keys, vals, keys != KEY_INF

    # -- movement / stats ----------------------------------------------------

    def flush(self, state: TierState) -> TierState:
        """Bulk demotion: move every hot entry into the warm tier (spill
        runs absorb warm overflow on depth 3). Entries the lower tiers
        cannot absorb (stack genuinely full) STAY hot with their metadata —
        demotion is lossless, same invariant as eviction. Flushed cells'
        policy metadata is cleared with the keys; the batch clock and the
        cumulative eviction / promotion counters are PRESERVED — flushing
        the tier must not erase the policy's history (the
        hot-tier-exactly-full audit)."""
        with obs.scope("flush"):
            shape = state.hot.keys.shape
            hk = state.hot.keys.reshape(-1)
            hv = state.hot.vals.reshape(-1)
            cold, spill, ok = self._demote(state.cold, state.spill, hk, hv,
                                           hk != EMPTY)
            if spill is not None:   # keep the run count under the static cap
                with obs.scope("compact"):
                    pre_dead = spill.n_dead
                    spill = spill_maintain(spill)
                    obs.record("tombstones_reclaimed",
                               lambda: pre_dead - spill.n_dead)
            keep = (hk != EMPTY) & ~ok
            hot = state.hot._replace(
                keys=jnp.where(keep, hk, EMPTY).reshape(shape),
                vals=jnp.where(keep, hv, jnp.uint64(0)).reshape(shape),
                count=jnp.sum(keep).astype(jnp.int64))
            meta = jnp.where(keep.reshape(shape), state.hot_meta, 0)
        return state._replace(hot=hot, hot_meta=meta, cold=cold, spill=spill)

    def stats(self, state: TierState):
        hot_size = state.hot.count.astype(jnp.int64)
        cold_size = (state.cold.n_term - state.cold.n_marked).astype(jnp.int64)
        spill_size = jnp.int64(0)
        spill_dead = jnp.int64(0)
        capacity = state.hot.keys.size + state.cold.term_keys.shape[0]
        if state.spill is not None:
            spill_size = (state.spill.n - state.spill.n_dead).astype(jnp.int64)
            spill_dead = state.spill.n_dead.astype(jnp.int64)
            capacity += state.spill.keys.shape[0]
        return uniform_stats(
            size=hot_size + cold_size + spill_size,
            hot_size=hot_size,
            cold_size=cold_size,
            spill_size=spill_size,
            tombstones=state.cold.n_marked + spill_dead,
            evictions=state.n_evict,
            promotions=state.n_promote,
            capacity=capacity)


def unfused_twin(name: str) -> TieredBackend:
    """A `fused=False` twin of a registered tier config — same depth,
    policy, and promotion, probing through the original dispatch-per-tier
    chain. The single source for what the parity suites and the
    fused-vs-unfused bench rows compare the fused path against."""
    be = get_backend(name)
    assert isinstance(be, TieredBackend), f"{name!r} is not a tier stack"
    return TieredBackend(promote=be.promote, depth=be.depth,
                         policy=be.policy, fused=False,
                         warm_layout=be.warm_layout)


HASH_SKIPLIST = register(TieredBackend())
TIERED3 = register(TieredBackend(depth=3))
TIERED3_LRU = register(TieredBackend(depth=3, policy="lru"))
TIERED3_SIZE = register(TieredBackend(depth=3, policy="size"))
TIERED3_B128 = register(TieredBackend(depth=3, warm_layout="block"))
