"""Three-deep tier stack + eviction-policy determinism.

Covers the depth-3 `tiered3[/lru|/size]` configurations of
`repro.store.tiers`: spill-run overflow into the cold tier, policy victim
selection (LRU-by-batch picks the oldest touch, size-aware picks the
largest payload), policy counters surviving `flush`, and the residency
determinism contract — the same `OpPlan` stream produces BIT-IDENTICAL
tier residency (the full state pytree, not just results) across exec modes
and between the sharded engine and a direct backend instance.
(8-device residency parity runs in tests/multidev/store_prog.py.)
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (enables x64)
from repro.core.layout import hash_slot, val_weight
from repro.store import (OP_DELETE, OP_FIND, OP_INSERT, get_backend,
                         make_plan)
from repro.store import exec as exec_

from invariants import assert_bskiplist_ok

TIERED = ["hash+skiplist", "tiered3", "tiered3/lru", "tiered3/size",
          "tiered3/b128"]
POLICIED = ["tiered3/lru", "tiered3/size"]


def u64(xs):
    return jnp.asarray(np.array(xs, dtype=np.uint64))


def keys_for_slot(num_slots: int, slot: int, n: int, seed=0) -> np.ndarray:
    """n distinct keys hashing into hot-tier slot `slot`."""
    rng = np.random.default_rng(seed)
    out: list[int] = []
    while len(out) < n:
        cand = rng.integers(1, 2**61, 256, dtype=np.uint64)
        sl = np.asarray(hash_slot(jnp.asarray(cand), num_slots))
        for k, s in zip(cand.tolist(), sl.tolist()):
            if s == slot and k not in out:
                out.append(k)
                if len(out) == n:
                    break
    return np.array(out, dtype=np.uint64)


def ins(be, st, keys, vals=None):
    keys = np.asarray(keys, np.uint64)
    vals = keys + 1 if vals is None else np.asarray(vals, np.uint64)
    return be.apply(st, make_plan(np.full(len(keys), OP_INSERT, np.int32),
                                  keys, vals))


def hot_set(st):
    return set(np.asarray(st.hot.keys).reshape(-1).tolist()) - {2**64 - 1}


def spill_live(st):
    ks = np.asarray(st.spill.keys)
    return set(ks[~np.asarray(st.spill.dead) & (ks != np.uint64(2**64 - 1))]
               .tolist())


def _stats(be, st):
    return {k: int(v) for k, v in be.stats(st).items()}


def test_val_weight():
    w = np.asarray(val_weight(u64([0, 1, 255, 256, 2**32, 2**63 - 1, 2**63])))
    assert w.tolist() == [1, 1, 1, 2, 5, 8, 8]


class TestThirdTier:
    def _overflow_setup(self):
        """Warm tier (32) overfilled so inserts land in all THREE tiers."""
        be = get_backend("tiered3")
        st = be.init(32, hot_bucket=4, hot_frac=32)      # hot 1x4, spill 32
        rng = np.random.default_rng(7)
        ks = np.unique(rng.integers(1, 2**62, 80, dtype=np.uint64))[:60]
        st, res = ins(be, st, ks)
        assert res.ok.all()
        return be, st, ks

    def test_overflow_reaches_spill_runs(self):
        be, st, ks = self._overflow_setup()
        s = _stats(be, st)
        assert s["hot_size"] <= 4
        assert s["cold_size"] == 32                       # warm at capacity
        assert s["spill_size"] == len(ks) - s["hot_size"] - 32
        assert s["spill_size"] > 0
        assert s["size"] == len(ks)
        # one batch spilled -> exactly one sorted run
        assert int(np.asarray(st.spill.run_start).sum()) == 1
        n = int(st.spill.n)
        run = np.asarray(st.spill.keys)[:n]
        assert (np.diff(run.astype(np.float64)) > 0).all()

    def test_spill_residents_found_with_values(self):
        be, st, ks = self._overflow_setup()
        st, res = be.apply(st, make_plan(np.full(len(ks), OP_FIND, np.int32),
                                         ks))
        assert res.ok.all()
        assert (np.asarray(res.vals) == ks + 1).all()

    def test_spill_delete_tombstones(self):
        be, st, ks = self._overflow_setup()
        victim = sorted(spill_live(st))[:5]
        st, res = be.apply(st, make_plan(
            np.full(5, OP_DELETE, np.int32), np.array(victim, np.uint64)))
        assert res.ok.all()
        s = _stats(be, st)
        assert s["size"] == len(ks) - 5
        assert s["tombstones"] >= 5                      # spill dead counted
        st, res = be.apply(st, make_plan(
            np.full(5, OP_FIND, np.int32), np.array(victim, np.uint64)))
        assert not res.ok.any()

    def test_scan_merges_all_three_tiers(self):
        be, st, ks = self._overflow_setup()
        flat = get_backend("det_skiplist")
        st_f, _ = ins(flat, flat.init(1024), ks)
        sk = np.sort(ks)
        lo = u64([0, int(sk[5])])
        hi = u64([2**63, int(sk[40])])
        out_t = [np.asarray(a) for a in be.scan(st, lo, hi, len(ks) + 8)]
        out_f = [np.asarray(a) for a in flat.scan(st_f, lo, hi, len(ks) + 8)]
        assert (out_t[0] == out_f[0]).all()              # exact counts
        for q in range(2):
            rows_t = [(int(k), int(v)) for k, v, m in
                      zip(out_t[1][q], out_t[2][q], out_t[3][q]) if m]
            rows_f = [(int(k), int(v)) for k, v, m in
                      zip(out_f[1][q], out_f[2][q], out_f[3][q]) if m]
            assert rows_t == rows_f == sorted(rows_t), q

    def test_promotion_from_spill_marks_dead(self):
        be, st, ks = self._overflow_setup()
        target = sorted(spill_live(st))[0]
        # free the single hot bucket so promotion has space (policy "none")
        hot_res = np.array(sorted(hot_set(st)), np.uint64)
        st, res = be.apply(st, make_plan(
            np.full(len(hot_res), OP_DELETE, np.int32), hot_res))
        assert res.ok.all()
        dead0 = int(st.spill.n_dead)
        st, res = be.apply(st, make_plan(
            np.array([OP_FIND], np.int32), u64([target])))
        assert bool(res.ok[0]) and int(res.vals[0]) == target + 1
        assert target in hot_set(st)                     # promoted up
        assert target not in spill_live(st)              # tombstoned below
        assert int(st.spill.n_dead) == dead0 + 1
        assert _stats(be, st)["size"] == len(ks) - len(hot_res)


class TestEvictionPolicies:
    def _fresh(self, name):
        be = get_backend(name)
        # hot: 8 slots x 2 -> tiny buckets so eviction triggers fast
        return be, be.init(1024, hot_bucket=2, hot_frac=64)

    def test_lru_evicts_oldest_touch(self):
        be, st = self._fresh("tiered3/lru")
        k1, k2, k3 = keys_for_slot(8, 3, 3).tolist()
        st, _ = ins(be, st, [k1])                        # stamp 0
        st, _ = ins(be, st, [k2])                        # stamp 1
        st, res = be.apply(st, make_plan(
            np.array([OP_FIND], np.int32), u64([k1])))   # k1 touched: stamp 2
        assert bool(res.ok[0])
        st, _ = ins(be, st, [k3])                        # bucket full: evict
        assert hot_set(st) == {k1, k3}                   # k2 was LRU
        assert _stats(be, st)["evictions"] == 1
        st, res = be.apply(st, make_plan(                # k2 demoted, intact
            np.array([OP_FIND], np.int32), u64([k2])))
        assert bool(res.ok[0]) and int(res.vals[0]) == k2 + 1

    def test_lru_without_touch_evicts_first_insert(self):
        be, st = self._fresh("tiered3/lru")
        k1, k2, k3 = keys_for_slot(8, 5, 3, seed=1).tolist()
        st, _ = ins(be, st, [k1])
        st, _ = ins(be, st, [k2])
        st, _ = ins(be, st, [k3])
        assert hot_set(st) == {k2, k3}                   # k1 oldest stamp

    def test_size_evicts_largest_payload(self):
        be, st = self._fresh("tiered3/size")
        k1, k2, k3 = keys_for_slot(8, 2, 3, seed=2).tolist()
        st, _ = ins(be, st, [k1, k2], vals=[3, 2**60])   # weights 1 vs 8
        st, _ = ins(be, st, [k3], vals=[17])
        assert hot_set(st) == {k1, k3}                   # big k2 demoted
        st, res = be.apply(st, make_plan(
            np.array([OP_FIND], np.int32), u64([k2])))
        assert bool(res.ok[0]) and int(res.vals[0]) == 2**60

    @pytest.mark.parametrize("name", POLICIED)
    def test_eviction_is_membership_neutral(self, name):
        be, st = self._fresh(name)
        rng = np.random.default_rng(13)
        ks = np.unique(rng.integers(1, 2**62, 200, dtype=np.uint64))
        st, res = ins(be, st, ks)
        assert res.ok.all()
        s = _stats(be, st)
        assert s["size"] == len(ks)
        assert s["hot_size"] + s["cold_size"] + s["spill_size"] == len(ks)
        st, res = be.apply(st, make_plan(
            np.full(len(ks), OP_FIND, np.int32), ks))
        assert res.ok.all()
        assert (np.asarray(res.vals) == ks + 1).all()

    @pytest.mark.parametrize("name", POLICIED)
    def test_full_stack_fails_new_lane_not_residents(self, name):
        """When every tier is full, eviction is suppressed (no headroom):
        the NEW insert reports ok=False — the flat backend's allocation
        failure — and every previously stored key stays findable."""
        be = get_backend(name)
        st = be.init(8, hot_bucket=2, hot_frac=4, spill_cap=8)  # hot 2, 8, 8
        rng = np.random.default_rng(41)
        ks = np.unique(rng.integers(1, 2**62, 64, dtype=np.uint64))
        st, res = ins(be, st, ks)
        stored = ks[np.asarray(res.ok)]
        assert len(stored) == int(be.stats(st)["capacity"])   # brim full
        extra = np.uint64(2**62 + 5)
        st, res = be.apply(st, make_plan(
            np.array([OP_INSERT], np.int32), u64([extra]), u64([extra + 1])))
        assert not bool(res.ok[0])                 # new lane fails honestly
        st, res = be.apply(st, make_plan(
            np.full(len(stored), OP_FIND, np.int32), stored))
        assert res.ok.all()                        # no resident was lost
        assert (np.asarray(res.vals) == stored + 1).all()

    @pytest.mark.parametrize("name", ["tiered3", "tiered3/lru"])
    def test_flush_on_full_stack_keeps_unabsorbed_hot(self, name):
        """flush() demotes what the lower tiers can absorb and KEEPS the
        rest hot — a full stack must not turn flush into key loss."""
        be = get_backend(name)
        st = be.init(8, hot_bucket=2, hot_frac=4, spill_cap=8)
        rng = np.random.default_rng(47)
        ks = np.unique(rng.integers(1, 2**62, 64, dtype=np.uint64))
        st, res = ins(be, st, ks)
        stored = ks[np.asarray(res.ok)]
        s0 = _stats(be, st)
        assert s0["size"] == s0["capacity"] and s0["hot_size"] > 0
        st = be.flush(st)
        s1 = _stats(be, st)
        assert s1["size"] == s0["size"]            # nothing lost
        assert s1["hot_size"] == s0["hot_size"]    # no headroom below
        st, res = be.apply(st, make_plan(
            np.full(len(stored), OP_FIND, np.int32), stored))
        assert res.ok.all()
        assert (np.asarray(res.vals) == stored + 1).all()

    def test_lru_reinsert_of_resident_refreshes_stamp(self):
        """An INSERT that finds its key hot-resident counts as a touch:
        upsert-style traffic must keep the entry warm."""
        be = get_backend("tiered3/lru")
        st = be.init(1024, hot_bucket=2, hot_frac=64)
        k1, k2, k3 = keys_for_slot(8, 4, 3, seed=5).tolist()
        st, _ = ins(be, st, [k1])                  # stamp 0
        st, _ = ins(be, st, [k2])                  # stamp 1
        st, res = ins(be, st, [k1])                # existed -> stamp 2
        assert not bool(res.ok[0]) or int(res.vals[0]) == 1  # existed flag
        st, _ = ins(be, st, [k3])                  # evicts k2, not k1
        assert hot_set(st) == {k1, k3}

    def test_spill_compaction_reclaims_tombstones(self):
        """Churn (deletes + promotions against spill residents) triggers
        `spill_compact` at the 25% threshold: the append cursor shrinks
        back to the live count and the runs merge into one sorted run."""
        be = get_backend("tiered3")
        st = be.init(16, hot_bucket=2, hot_frac=8, spill_cap=32)
        rng = np.random.default_rng(43)
        ks = np.unique(rng.integers(1, 2**62, 48, dtype=np.uint64))[:40]
        st, res = ins(be, st, ks)
        assert res.ok.all()
        assert int(st.spill.n) > 8
        doomed = np.array(sorted(spill_live(st)), np.uint64)
        st, res = be.apply(st, make_plan(
            np.full(len(doomed), OP_DELETE, np.int32), doomed))
        assert res.ok.all()
        assert int(st.spill.n_dead) == 0           # compaction fired
        assert int(st.spill.n) == 0                # cursor reclaimed
        live = np.array(sorted(set(ks.tolist()) - set(doomed.tolist())),
                        np.uint64)
        st, res = be.apply(st, make_plan(
            np.full(len(live), OP_FIND, np.int32), live))
        assert res.ok.all()
        assert _stats(be, st)["size"] == len(live)

    def test_policy_counters_survive_flush(self):
        be, st = self._fresh("tiered3/lru")
        ks = keys_for_slot(8, 6, 4, seed=3)
        for k in ks:                                     # 2 evictions
            st, _ = ins(be, st, [k])
        demoted = sorted(set(ks.tolist()) - hot_set(st))
        st, res = be.apply(st, make_plan(                # 2 promotions
            np.full(len(demoted), OP_FIND, np.int32),
            np.array(demoted, np.uint64)))
        assert res.ok.all()
        s0 = _stats(be, st)
        assert s0["evictions"] > 0 and s0["promotions"] > 0
        clock0 = int(st.clock)
        st = be.flush(st)
        s1 = _stats(be, st)
        assert s1["hot_size"] == 0 and s1["size"] == s0["size"]
        # the audit fix: flush clears metadata WITH the keys but must not
        # silently drop the policy's history
        assert s1["evictions"] == s0["evictions"]
        assert s1["promotions"] == s0["promotions"]
        assert int(st.clock) == clock0
        assert not np.asarray(st.hot_meta).any()
        st, res = be.apply(st, make_plan(
            np.full(len(ks), OP_FIND, np.int32), ks))
        assert res.ok.all()


# ---------------------------------------------------------------------------
# residency determinism (the eviction-determinism contract)
# ---------------------------------------------------------------------------

def _churn_plans(seed=21, n_rounds=6, width=48):
    """Mixed workload over a pool small enough to churn every tier."""
    rng = np.random.default_rng(seed)
    pool = rng.integers(1, 2**62, 96, dtype=np.uint64)
    plans = []
    for _ in range(n_rounds):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], width,
                         p=[0.5, 0.35, 0.15]).astype(np.int32)
        keys = rng.choice(pool, width)
        mask = rng.random(width) > 0.05
        plans.append(make_plan(ops, keys, keys + 1, mask))
    return plans


def assert_states_equal(sa, sb, ctx):
    la, lb = jax.tree.leaves(sa), jax.tree.leaves(sb)
    assert len(la) == len(lb), ctx
    for i, (a, b) in enumerate(zip(la, lb)):
        assert (np.asarray(a) == np.asarray(b)).all(), (ctx, i)


@pytest.mark.parametrize("name", TIERED)
def test_residency_bit_identical_across_modes(name):
    """Same plan stream => identical TIER RESIDENCY (full state pytree,
    including policy metadata and spill runs) in every exec mode."""
    be = get_backend(name)
    states = {}
    for mode in exec_.runnable_modes():
        with exec_.exec_mode(mode):
            st = be.init(64, hot_bucket=4, hot_frac=8)   # churn all tiers
            step = jax.jit(be.apply)     # traced in `mode`
            for p in _churn_plans():
                st, _ = step(st, p)
        states[mode] = st
    ref_mode, ref = next(iter(states.items()))
    for mode, st in states.items():
        assert_states_equal(ref, st, (name, ref_mode, mode))
        # the warm tier's derived block layout stays sound in every mode
        assert_bskiplist_ok(st.cold, (name, mode))


@pytest.mark.parametrize("name", POLICIED)
def test_engine_residency_matches_direct_apply(name):
    """Sharding is pure partitioning: the 1-device engine's backend state
    is bit-identical to a direct (engine-less) instance applying the same
    per-round op multisets — placement depends on sorted key order, not
    lane order, so the engine's routing/pooling cannot change residency."""
    from repro.launch.mesh import make_mesh
    from repro.store.engine import StoreEngine
    lanes = 32
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    eng = StoreEngine(mesh, ("data",), lanes, backend=name)
    state = jax.device_put(eng.init(64, hot_bucket=4, hot_frac=8),
                           eng.sharding)
    be = get_backend(name)
    direct = be.init(64, hot_bucket=4, hot_frac=8)

    rng = np.random.default_rng(31)
    pool = rng.integers(1, 2**62, 64, dtype=np.uint64)
    put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
    for _ in range(5):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], lanes,
                         p=[0.5, 0.35, 0.15]).astype(np.int32)
        keys = rng.choice(pool, lanes, replace=False)    # distinct per round
        state, _, _, dropped = eng.step(state, put(ops), put(keys),
                                        put(keys + 7))
        assert int(dropped) == 0
        direct, _ = be.apply(direct, make_plan(ops, keys, keys + 7))
    assert_states_equal(jax.tree.map(lambda x: x[0], state), direct, name)
    assert_bskiplist_ok(direct.cold, name)
