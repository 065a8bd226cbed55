"""Concurrent deterministic 1-2-3-4 skiplist (paper §II) — TPU-native encoding.

The paper's structure: a terminal sorted linked list of (key, data) nodes plus
log n index levels; every non-terminal node covers 2..4 children ("1-2-3-4"
criterion), node key = max of its children's keys, sentinel tail/bottom nodes,
mark bits for lazy deletion, lock-free Find, and proactive top-down
rebalancing whose total work is linear in the number of operations (the
(a,b)-tree analysis, eqs. 2-4: rebalancing work at height h decays
geometrically).

TPU adaptation (DESIGN.md §4): pointers -> level-major sorted arrays.

  level 0 (terminal):  keys[C], vals[C], mark[C]  — sorted, KEY_INF padding
  level l>=1:          keys_l[C_l] (max-of-group), child_l[C_l] (group start)

* Lock-free Find -> a pure fixed-trip-count walk: exactly L levels, one
  4-wide gather per level (guaranteed arity <= 4 — THIS is why the
  deterministic variant is SIMD-friendly; the randomized skiplist needs
  worst-case probe padding, see rand_skiplist.py).
* Threads -> batch lanes. A batch of K ops linearizes by (key, lane) sort with
  first-lane-wins tie-break: a deterministic linearization, strictly stronger
  than the paper's "some linearization exists".
* Top-down rebalancing -> deterministic level rebuild, grouping threes
  (boundaries b_j = min(3j, n-2)) so every group has arity in {2,3} — always
  1-2-3-4-legal. Rebuild cost at level l is n/3^l: the same geometric decay
  the paper proves for per-op rebalancing, amortized over the batch.
* Lazy deletion -> tombstone marks; non-terminal nodes keep routing through
  marked keys (the paper's lazy non-terminal removal + CheckNodeKey) until a
  compaction at 25% tombstones rebuilds all levels.
* Sentinels -> KEY_INF padding rows with clamped gathers (self-pointing
  sentinels = never out of bounds).

All ops are jit-able; state is a pytree (checkpointable for free).

Device phases: each write pass opens a `jax.named_scope("obs.<name>")`
— "merge", "rebuild", "tombstone", "range_delete", "reclaim" — so a
`jax.profiler` trace can attribute its device time. The names belong to
`repro.store.obs.DEVICE_PHASES`; this module opens the scopes directly
because `core` does not import `repro.store`.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.bits import KEY_INF, dup_in_run
from repro.core.layout import kv_arrays

FANOUT = 4  # 1-2-3-4: arity in [2, 4]


class DetSkiplist(NamedTuple):
    term_keys: jnp.ndarray            # [C] uint64 sorted (marked entries stay)
    term_vals: jnp.ndarray            # [C] uint64
    term_mark: jnp.ndarray            # [C] bool tombstones
    term_stamp: jnp.ndarray           # [C] int32 batch clock at insert/revive
    n_term: jnp.ndarray               # scalar int32 — physical entries
    n_marked: jnp.ndarray             # scalar int32
    clock: jnp.ndarray                # scalar int32 — ticked once per apply
    level_keys: tuple                 # L arrays [C_l] uint64 (max of group)
    level_child: tuple                # L arrays [C_l] int32  (group start)
    level_count: jnp.ndarray          # [L] int32

    @property
    def capacity(self) -> int:
        return self.term_keys.shape[0]

    @property
    def num_levels(self) -> int:
        return len(self.level_keys)

    def size(self) -> jnp.ndarray:
        return self.n_term - self.n_marked


def _level_caps(capacity: int) -> list[int]:
    """Index-level capacities: groups are >=2 wide so counts at least halve."""
    caps, c = [], capacity
    while c > FANOUT:
        c = (c + 1) // 2
        caps.append(max(c, FANOUT))
    return caps or [FANOUT]


def skiplist_init(capacity: int) -> DetSkiplist:
    caps = _level_caps(capacity)
    term_keys, term_vals = kv_arrays(capacity)
    return DetSkiplist(
        term_keys=term_keys,
        term_vals=term_vals,
        term_mark=jnp.zeros((capacity,), bool),
        term_stamp=jnp.zeros((capacity,), jnp.int32),
        n_term=jnp.int32(0),
        n_marked=jnp.int32(0),
        clock=jnp.int32(0),
        level_keys=tuple(jnp.full((c,), KEY_INF) for c in caps),
        level_child=tuple(jnp.zeros((c,), jnp.int32) for c in caps),
        level_count=jnp.zeros((len(caps),), jnp.int32),
    )


# ---------------------------------------------------------------------------
# rebuild (the batched top-down rebalance)
# ---------------------------------------------------------------------------

def _group(n_prev: jnp.ndarray, cap_l: int, prev_keys: jnp.ndarray):
    """Deterministic 1-2-3-4 grouping of a sorted level of n_prev keys.

    boundaries b_j = min(3j, max(n_prev-2, 0)), b_g = n_prev with
    g = (n_prev+2)//3 -> every group arity in {2,3} (single group of 1 only
    when n_prev == 1 — the root edge case, same as the paper's head node).
    """
    j = jnp.arange(cap_l, dtype=jnp.int32)
    g = jnp.where(n_prev > 0, (n_prev + 2) // 3, 0)
    lo = jnp.minimum(3 * j, jnp.maximum(n_prev - 2, 0))
    hi = jnp.where(j + 1 < g, jnp.minimum(3 * (j + 1), jnp.maximum(n_prev - 2, 0)), n_prev)
    live = j < g
    kidx = jnp.clip(hi - 1, 0, prev_keys.shape[0] - 1)
    keys = jnp.where(live, prev_keys[kidx], KEY_INF)   # node key = max of group
    child = jnp.where(live, lo, 0)
    return keys, child, g


@jax.named_scope("obs.rebuild")
def _rebuild_levels(s: DetSkiplist) -> DetSkiplist:
    """Rebuild every index level from the terminal array (work n/3^l at level
    l — the geometric decay of eqs. 2-4, amortized over the batch)."""
    lkeys, lchild, counts = [], [], []
    prev_keys, n_prev = s.term_keys, s.n_term
    for l in range(s.num_levels):
        cap_l = s.level_keys[l].shape[0]
        keys, child, g = _group(n_prev, cap_l, prev_keys)
        lkeys.append(keys)
        lchild.append(child)
        counts.append(g)
        prev_keys, n_prev = keys, g
    return s._replace(level_keys=tuple(lkeys), level_child=tuple(lchild),
                      level_count=jnp.stack(counts).astype(jnp.int32))


# ---------------------------------------------------------------------------
# Find (lock-free walk -> pure fixed-trip-count walk)
# ---------------------------------------------------------------------------

def find_batch(s: DetSkiplist, queries: jnp.ndarray):
    """Batched Find. Returns (found[Q] bool, vals[Q], term_idx[Q] int32).

    Exactly L descent steps; each step gathers <= FANOUT child keys (guaranteed
    by the 1-2-3-4 criterion) and picks the first child with q <= child_key —
    which exists inside the group because node key = max of group, and
    first-true never escapes the group because the next group's keys are
    larger (sorted order = the self-pointing sentinel).
    """
    Q = queries.shape[0]
    top = s.num_levels - 1
    # top level holds <= FANOUT live nodes: one static probe
    topk = s.level_keys[top][:FANOUT]
    ge = queries[:, None] <= topk[None, :]
    i = jnp.argmax(ge, axis=1).astype(jnp.int32)          # first j with q <= key
    for l in range(top, -1, -1):
        child = s.level_child[l]
        start = child[jnp.clip(i, 0, child.shape[0] - 1)]
        below = s.term_keys if l == 0 else s.level_keys[l - 1]
        idx = jnp.clip(start[:, None] + jnp.arange(FANOUT, dtype=jnp.int32)[None, :],
                       0, below.shape[0] - 1)
        ck = below[idx]                                    # [Q, FANOUT]
        sel = jnp.argmax(queries[:, None] <= ck, axis=1).astype(jnp.int32)
        i = start + sel
    i = jnp.clip(i, 0, s.capacity - 1)
    found = (s.term_keys[i] == queries) & ~s.term_mark[i] & (queries != KEY_INF)
    return found, jnp.where(found, s.term_vals[i], jnp.uint64(0)), i


def find_batch_blocked(s: DetSkiplist, queries: jnp.ndarray,
                       block: int | None = None):
    """Batched Find through the block-major B-skiplist view — same contract
    (and bit-identical found/vals) as `find_batch`, with the descent
    restructured into lane-width fat nodes: each step compares a WHOLE
    block of `block` sorted keys (one vector compare + sum-reduction = the
    searchsorted-left position) instead of a fan-out-4 gather, so the walk
    is `ceil(log_block(C/block)) + 1` steps instead of `num_levels + 1`.
    The blocked index is derived from the terminal level at probe time
    (`core.layout.bskiplist_layout`) exactly like `_rebuild_levels` derives
    the level-major index — the layout is a probe-execution knob, state
    never changes shape. Kernel twin: `repro.kernels.bskiplist_walk`.
    """
    from repro.core.layout import BSKIP_BLOCK, bskiplist_layout, key_lt, split_u64

    B = BSKIP_BLOCK if block is None else block
    lay = bskiplist_layout(s, B)
    qh, ql = split_u64(queries)
    L, W = lay.blk_hi.shape
    nb = lay.term_hi.shape[0] // B
    lanes = jnp.arange(B, dtype=jnp.int32)[None, :]
    i = jnp.zeros(queries.shape, jnp.int32)          # root: node 0, row L-1
    for r in range(L - 1, -1, -1):
        base = jnp.clip(i, 0, W // B - 1) * B
        idx = base[:, None] + lanes
        lt = key_lt(lay.blk_hi[r][idx], lay.blk_lo[r][idx],
                    qh[:, None], ql[:, None])
        sel = jnp.sum(lt, axis=1).astype(jnp.int32)  # searchsorted-left
        i = base + sel                               # child node / block id
    blk = jnp.clip(i, 0, nb - 1)
    idx = blk[:, None] * B + lanes
    lt = key_lt(lay.term_hi[idx], lay.term_lo[idx], qh[:, None], ql[:, None])
    sel = jnp.sum(lt, axis=1).astype(jnp.int32)
    i = jnp.clip(blk * B + sel, 0, s.capacity - 1)
    found = (s.term_keys[i] == queries) & ~s.term_mark[i] & (queries != KEY_INF)
    return found, jnp.where(found, s.term_vals[i], jnp.uint64(0)), i


def contains(s: DetSkiplist, key) -> jnp.ndarray:
    return find_batch(s, jnp.asarray([key], jnp.uint64))[0][0]


# ---------------------------------------------------------------------------
# Addition (bulk, deterministic linearization)
# ---------------------------------------------------------------------------

def insert_batch(s: DetSkiplist, keys: jnp.ndarray, vals: jnp.ndarray,
                 mask: jnp.ndarray | None = None):
    """Batched Addition. Returns (s', inserted[K] bool, existed[K] bool).

    Linearization: lanes sort by (key, lane) — stable argsort — duplicates
    within the batch resolve to the lowest lane (first-writer-wins, a fixed
    rule). Duplicate-vs-stored keys return existed (the paper's duplicate
    check); keys matching a *marked* entry revive it in place (lazy-deletion
    composition). Capacity overflow drops the highest-ranked lanes and
    reports inserted=False (the paper's allocation-failure path).

    The merge moves stored entry i to i + #{j : newk[j] < term_keys[i]}.
    No new key equals a stored key (`match` tests every stored entry, marked
    ones included) and the compacted new keys `newk` are sorted, so that
    count is #{j < n_new : p[j] <= i}, where p[j] is newk[j]'s insertion
    point into term_keys (the `pos` of its lane): a prefix count over C of K
    known positions, not C binary searches into newk.
    """
    K = keys.shape[0]
    C = s.capacity
    if mask is None:
        mask = jnp.ones((K,), bool)
    mask = mask & (keys != KEY_INF)

    with jax.named_scope("obs.merge"):
        order = jnp.argsort(keys, stable=True)
        sk = keys[order]
        sv = vals[order]
        sm = mask[order]
        same = jnp.concatenate([jnp.zeros((1,), bool), sk[1:] == sk[:-1]])
        dup = dup_in_run(same, sm)

        pos = jnp.searchsorted(s.term_keys, sk).astype(jnp.int32)
        posc = jnp.clip(pos, 0, C - 1)
        match = sm & (pos < C) & (s.term_keys[posc] == sk)
        revive = match & s.term_mark[posc] & ~dup
        exists = match & ~s.term_mark[posc]

        # revive in place (first lane among in-batch dups wins — dup already
        # false); a revival is a re-insertion, so its snapshot stamp refreshes
        # to the current batch clock (upserts on LIVE entries do not re-stamp)
        rpos = jnp.where(revive, posc, C)
        term_mark = s.term_mark.at[rpos].set(False, mode="drop")
        term_vals = s.term_vals.at[rpos].set(sv, mode="drop")
        term_stamp = s.term_stamp.at[rpos].set(s.clock, mode="drop")
        n_marked = s.n_marked - jnp.sum(revive).astype(jnp.int32)

        new = sm & ~match & ~dup
        rank = jnp.cumsum(new.astype(jnp.int32)) - 1
        new = new & (s.n_term + rank < C)                      # overflow -> fail lanes
        n_new = jnp.sum(new).astype(jnp.int32)

        # compact the new keys into a sorted [K] buffer (pad KEY_INF), with
        # each one's insertion point into term_keys (pad C)
        crank = jnp.where(new, rank, K)
        newk = jnp.full((K,), KEY_INF).at[crank].set(sk, mode="drop")
        newv = jnp.zeros((K,), jnp.uint64).at[crank].set(sv, mode="drop")
        p = jnp.full((K,), C, jnp.int32).at[crank].set(pos, mode="drop")

        # two-way sorted merge by destination scatter: old entry i moves up by
        # cnt[i] = #{j < n_new : p[j] <= i}, one running max over C of j + 1
        # scattered at the last j of each run of equal p (distinct indices).
        # dest_new (= p + j) keeps its own K-wide search: built from p, it
        # leads the v5e's compiler to place half of the merged term_vals in
        # HBM, where its scatter takes 61 ms instead of 36 (PERF.md Findings)
        lane = jnp.arange(K, dtype=jnp.int32)
        p_next = jnp.concatenate([p[1:], jnp.full((1,), C, jnp.int32)])
        last = (lane < n_new) & (p != p_next)
        cnt = jax.lax.cummax(
            jnp.zeros((C,), jnp.int32).at[jnp.where(last, p, C + lane)].set(
                lane + 1, mode="drop", unique_indices=True))
        old_idx = jnp.arange(C, dtype=jnp.int32)
        dest_old = jnp.where(old_idx < s.n_term, old_idx + cnt, C)
        dest_new = jnp.where(lane < n_new, lane + jnp.searchsorted(
            s.term_keys, newk, side="left").astype(jnp.int32), C)

        tk = jnp.full((C,), KEY_INF).at[dest_old].set(s.term_keys, mode="drop")
        tk = tk.at[dest_new].set(newk, mode="drop")
        tv = jnp.zeros((C,), jnp.uint64).at[dest_old].set(term_vals, mode="drop")
        tv = tv.at[dest_new].set(newv, mode="drop")
        tm = jnp.zeros((C,), bool).at[dest_old].set(term_mark, mode="drop")
        # new entries unmarked (already False); their stamp = this batch's clock
        ts = jnp.zeros((C,), jnp.int32).at[dest_old].set(term_stamp, mode="drop")
        ts = ts.at[dest_new].set(s.clock, mode="drop")

        s2 = s._replace(term_keys=tk, term_vals=tv, term_mark=tm, term_stamp=ts,
                        n_term=s.n_term + n_new, n_marked=n_marked)

        inv = jnp.zeros((K,), jnp.int32).at[order].set(jnp.arange(K, dtype=jnp.int32))
        inserted = (new | revive)[inv]
        existed = (exists | dup)[inv]
    return _rebuild_levels(s2), inserted, existed


# ---------------------------------------------------------------------------
# Deletion (lazy marks + threshold compaction)
# ---------------------------------------------------------------------------

def delete_batch(s: DetSkiplist, keys: jnp.ndarray,
                 mask: jnp.ndarray | None = None, compact_num: int = 1,
                 compact_den: int = 4):
    """Batched Deletion: tombstone the terminal nodes (DropKey), leave the
    index levels stale (the paper's lazy non-terminal removal). Compaction
    (merge/borrow analogue, performed wholesale) triggers when tombstones
    exceed compact_num/compact_den of entries. Returns (s', deleted[K])."""
    K = keys.shape[0]
    C = s.capacity
    if mask is None:
        mask = jnp.ones((K,), bool)

    with jax.named_scope("obs.tombstone"):
        order = jnp.argsort(keys, stable=True)
        sk = keys[order]
        sm = mask[order] & (sk != KEY_INF)
        same = jnp.concatenate([jnp.zeros((1,), bool), sk[1:] == sk[:-1]])
        dup = dup_in_run(same, sm)

        pos = jnp.searchsorted(s.term_keys, sk).astype(jnp.int32)
        posc = jnp.clip(pos, 0, C - 1)
        hit = sm & ~dup & (pos < C) & (s.term_keys[posc] == sk) & ~s.term_mark[posc]

        mark = s.term_mark.at[jnp.where(hit, posc, C)].set(True, mode="drop")
        n_marked = s.n_marked + jnp.sum(hit).astype(jnp.int32)
        s2 = s._replace(term_mark=mark, n_marked=n_marked)
        inv = jnp.zeros((K,), jnp.int32).at[order].set(jnp.arange(K, dtype=jnp.int32))
        deleted = hit[inv]
    s2 = jax.lax.cond(n_marked * compact_den > s2.n_term * compact_num,
                      compact, lambda t: t, s2)
    return s2, deleted


@jax.named_scope("obs.reclaim")
def compact(s: DetSkiplist) -> DetSkiplist:
    """Physically remove tombstones and rebuild all levels (the wholesale
    merge/borrow + DecreaseDepth: stale index nodes vanish here)."""
    C = s.capacity
    keep = (~s.term_mark) & (jnp.arange(C) < s.n_term)
    dest = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32)) - 1, C)
    tk = jnp.full((C,), KEY_INF).at[dest].set(s.term_keys, mode="drop")
    tv = jnp.zeros((C,), jnp.uint64).at[dest].set(s.term_vals, mode="drop")
    ts = jnp.zeros((C,), jnp.int32).at[dest].set(s.term_stamp, mode="drop")
    n = jnp.sum(keep).astype(jnp.int32)
    # derive cleared fields from inputs (keeps shard_map varying-axis types
    # identical across lax.cond branches)
    s2 = s._replace(term_keys=tk, term_vals=tv, term_stamp=ts,
                    term_mark=s.term_mark & False, n_term=n,
                    n_marked=s.n_marked * 0)
    return _rebuild_levels(s2)


# ---------------------------------------------------------------------------
# Range search (the skiplist's raison d'être vs hash tables)
# ---------------------------------------------------------------------------

def range_query(s: DetSkiplist, lo: jnp.ndarray, hi: jnp.ndarray, max_out: int,
                as_of_batch=None):
    """Keys in [lo, hi), batched over Q query rows.

    Returns (count[Q], keys[Q, max_out], vals[Q, max_out], valid[Q, max_out]).
    Terminal contiguity makes this a gather — the paper's argument for
    skiplists over BSTs (follow the linked list vs depth-first traversal).

    `as_of_batch`: snapshot scan — additionally exclude entries whose
    insert/revive stamp is LATER than the given batch clock (entries of
    batch b carry stamp b, so `as_of_batch=b` sees batches 0..b). Tombstones
    still hide deleted entries: this is a filter, not time travel — a key
    deleted since its insertion does not reappear. None (the default) skips
    the stamp plane entirely, which keeps this routine shared with states
    that don't carry one (the randomized skiplist).
    """
    i_lo = jnp.searchsorted(s.term_keys, lo, side="left").astype(jnp.int32)
    i_hi = jnp.searchsorted(s.term_keys, hi, side="left").astype(jnp.int32)
    idx = jnp.clip(i_lo[:, None] + jnp.arange(max_out, dtype=jnp.int32)[None, :],
                   0, s.capacity - 1)
    in_range = (i_lo[:, None] + jnp.arange(max_out)[None, :]) < i_hi[:, None]
    valid = in_range & ~s.term_mark[idx]
    # exact count (including beyond max_out): prefix-sum of live entries
    live = (~s.term_mark) & (s.term_keys != KEY_INF)
    if as_of_batch is not None:
        vis = s.term_stamp <= jnp.asarray(as_of_batch, jnp.int32)
        valid = valid & vis[idx]
        live = live & vis
    cs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(live.astype(jnp.int32))])
    count = cs[i_hi] - cs[i_lo]
    return count, s.term_keys[idx], s.term_vals[idx], valid


# ---------------------------------------------------------------------------
# Priority-queue extraction (pop-min as rank-select over the live prefix)
# ---------------------------------------------------------------------------

def pop_rank_select(s: DetSkiplist, ranks: jnp.ndarray, mask: jnp.ndarray):
    """Locate the rank-th smallest live key per lane (rank 0 = minimum).

    Returns (found[K] bool, keys[K] uint64, idx[K] int32). Pure read — the
    caller commits the extraction with `pop_mark`. Built on the same
    live-prefix cumsum as `range_query` (live = unmarked, non-padding), so
    every execution path that reproduces that formula agrees bit-for-bit.
    Lanes whose rank exceeds the live population (or with mask False)
    return found=False, keys=KEY_INF, idx=0.
    """
    live = (~s.term_mark) & (s.term_keys != KEY_INF)
    prefix = jnp.cumsum(live.astype(jnp.int32))            # [C] inclusive
    total = s.n_term - s.n_marked
    want = ranks.astype(jnp.int32) + 1
    found = mask & (want >= 1) & (want <= total)
    idx = jnp.searchsorted(prefix, want, side="left").astype(jnp.int32)
    idx = jnp.where(found, jnp.clip(idx, 0, s.capacity - 1), 0)
    keys = jnp.where(found, s.term_keys[idx], KEY_INF)
    return found, keys, idx


def pop_mark(s: DetSkiplist, idx: jnp.ndarray, hit: jnp.ndarray,
             compact_num: int = 1, compact_den: int = 4) -> DetSkiplist:
    """Commit a batch of pops: tombstone the selected terminal slots (the
    same lazy DropKey path as `delete_batch` — index levels stay stale) and
    run the threshold compaction. `idx` rows with hit=False are ignored.
    Lanes must target distinct slots (guaranteed by distinct ranks)."""
    mark = s.term_mark.at[jnp.where(hit, idx, s.capacity)].set(True, mode="drop")
    n_marked = s.n_marked + jnp.sum(hit).astype(jnp.int32)
    s2 = s._replace(term_mark=mark, n_marked=n_marked)
    return jax.lax.cond(n_marked * compact_den > s2.n_term * compact_num,
                        compact, lambda t: t, s2)


# ---------------------------------------------------------------------------
# Range deletion (bulk DropKey over [lo, hi) intervals)
# ---------------------------------------------------------------------------

@jax.named_scope("obs.range_delete")
def range_delete_batch(s: DetSkiplist, lo: jnp.ndarray, hi: jnp.ndarray,
                       mask: jnp.ndarray | None = None, compact_num: int = 1,
                       compact_den: int = 4):
    """Tombstone every live key in [lo, hi) per lane, batched over K lanes.

    Returns (s', counts[K] int32). When lanes overlap, each deleted entry
    is attributed to the FIRST (lowest-index) covering lane — a fixed rule,
    like first-lane-wins everywhere else — so sum(counts) is exactly the
    number of entries removed. Same threshold compaction as `delete_batch`.
    """
    K = lo.shape[0]
    if mask is None:
        mask = jnp.ones((K,), bool)
    live = (~s.term_mark) & (s.term_keys != KEY_INF)
    cover = (mask[:, None]
             & (s.term_keys[None, :] >= lo[:, None])
             & (s.term_keys[None, :] < hi[:, None])
             & live[None, :])                               # [K, C]
    hitany = jnp.any(cover, axis=0)                         # [C]
    first = jnp.argmax(cover, axis=0).astype(jnp.int32)     # [C] first lane
    counts = jnp.zeros((K,), jnp.int32).at[
        jnp.where(hitany, first, K)].add(1, mode="drop")
    n_marked = s.n_marked + jnp.sum(hitany).astype(jnp.int32)
    s2 = s._replace(term_mark=s.term_mark | hitany, n_marked=n_marked)
    s2 = jax.lax.cond(n_marked * compact_den > s2.n_term * compact_num,
                      compact, lambda t: t, s2)
    return s2, counts


# ---------------------------------------------------------------------------
# invariant checker (tests + the paper's 1-2-3-4 criterion)
# ---------------------------------------------------------------------------

def check_invariants(s: DetSkiplist) -> dict:
    """Host-side structural validation. Returns dict of violation counts."""
    import numpy as np

    out = {}
    tk = np.asarray(s.term_keys)
    n = int(s.n_term)
    out["terminal_sorted"] = int(np.sum(np.diff(tk[:n].astype(np.float64)) < 0)) if n > 1 else 0
    out["padding_inf"] = int(np.sum(tk[n:] != np.uint64(0xFFFFFFFFFFFFFFFF)))
    prev_keys, n_prev = tk, n
    bad_arity = bad_maxkey = bad_subset = 0
    counts = np.asarray(s.level_count)
    for l in range(s.num_levels):
        lk = np.asarray(s.level_keys[l])
        lc = np.asarray(s.level_child[l])
        g = int(counts[l])
        for j in range(g):
            lo = int(lc[j])
            hi = int(lc[j + 1]) if j + 1 < g else n_prev
            arity = hi - lo
            if not (1 <= arity <= FANOUT) or (arity == 1 and n_prev != 1):
                bad_arity += 1
            if hi >= 1 and lk[j] != prev_keys[hi - 1]:
                bad_maxkey += 1
            if lk[j] not in prev_keys[:n_prev]:
                bad_subset += 1
        prev_keys, n_prev = lk, g
    out["bad_arity"] = bad_arity
    out["bad_maxkey"] = bad_maxkey
    out["bad_subset"] = bad_subset
    return out
