"""Tests for the unified repro.store storage-engine API.

The load-bearing property is backend interchangeability: every registered
backend must produce IDENTICAL per-lane results for the same `OpPlan` under
the deterministic linearization (INSERTS -> DELETES -> FINDS, first lane
wins on duplicates). Plus tier-stack correctness: spill, promotion, flush,
and the exact two-tier ordered scan.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (enables x64)
from repro.core.layout import hash_slot
from repro.store import (OP_DELETE, OP_FIND, OP_INSERT, OP_NONE, STATS_SCHEMA,
                         available_backends, get_backend, make_plan)

ALL_BACKENDS = available_backends()
ORDERED = [n for n in ALL_BACKENDS if get_backend(n).ordered]


def u64(xs):
    return jnp.asarray(np.array(xs, dtype=np.uint64))


def _mixed_plans(seed=0, n_rounds=6, width=48, pool_size=64):
    """Overlapping insert/find/delete workload: keys drawn from a small pool
    so finds and deletes hit, duplicates occur in-batch, and deletes collide
    with same-batch inserts."""
    rng = np.random.default_rng(seed)
    pool = rng.integers(1, 2**62, pool_size, dtype=np.uint64)
    plans = []
    for _ in range(n_rounds):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], width,
                         p=[0.5, 0.35, 0.15]).astype(np.int32)
        keys = rng.choice(pool, width)
        mask = rng.random(width) > 0.05          # a few masked-off lanes
        plans.append(make_plan(ops, keys, keys + 1, mask))
    return plans


# ---------------------------------------------------------------------------
# per-backend semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_BACKENDS)
class TestBackendSemantics:
    def test_roundtrip(self, name):
        be = get_backend(name)
        st = be.init(1024)
        ks = u64([10, 20, 30, 40, 50])
        st, res = be.apply(st, make_plan(np.full(5, OP_INSERT, np.int32),
                                         ks, ks * jnp.uint64(2)))
        assert res.ok.all() and not res.vals.any()   # inserted, none existed
        st, res = be.apply(st, make_plan(np.full(5, OP_FIND, np.int32), ks))
        assert res.ok.all()
        assert (res.vals == ks * jnp.uint64(2)).all()
        st, res = be.apply(st, make_plan(
            np.array([OP_DELETE, OP_FIND], np.int32), u64([20, 20])))
        assert bool(res.ok[0]) and not bool(res.ok[1])  # find after delete
        assert int(be.stats(st)["size"]) == 4

    def test_masked_lanes_are_noops(self, name):
        be = get_backend(name)
        st = be.init(256)
        ks = u64([1, 2, 3, 4])
        mask = jnp.asarray([True, False, True, False])
        st, res = be.apply(st, make_plan(np.full(4, OP_INSERT, np.int32),
                                         ks, ks, mask))
        assert (np.asarray(res.ok) == np.asarray(mask)).all()
        st, res = be.apply(st, make_plan(np.full(4, OP_FIND, np.int32), ks))
        assert (np.asarray(res.ok) == np.asarray(mask)).all()
        assert int(be.stats(st)["size"]) == 2

    def test_idle_lanes(self, name):
        be = get_backend(name)
        st = be.init(256)
        st, res = be.apply(st, make_plan(
            np.array([OP_INSERT, OP_NONE, OP_NONE], np.int32), u64([7, 8, 9]),
            u64([70, 80, 90])))
        assert bool(res.ok[0]) and not res.ok[1:].any()
        assert int(be.stats(st)["size"]) == 1

    def test_stats_contract(self, name):
        """Every backend returns EXACTLY the shared STATS_SCHEMA key set
        (untracked counters are zero), in schema order, as int64."""
        be = get_backend(name)
        st = be.init(512)
        s = be.stats(st)
        assert tuple(s) == STATS_SCHEMA
        assert all(np.asarray(v).dtype == np.int64 for v in s.values())
        assert int(s["size"]) == 0 and int(s["capacity"]) >= 512
        assert all(int(v) >= 0 for v in s.values())
        # schema still uniform (and size live) after a few inserts
        ks = u64([3, 5, 7])
        st, _ = be.apply(st, make_plan(np.full(3, OP_INSERT, np.int32), ks, ks))
        s2 = be.stats(st)
        assert tuple(s2) == STATS_SCHEMA
        assert int(s2["size"]) == 3


# ---------------------------------------------------------------------------
# cross-backend parity (the API's core promise)
# ---------------------------------------------------------------------------

def test_all_backends_identical_results():
    plans = _mixed_plans()
    results = {}
    sizes = {}
    for name in ALL_BACKENDS:
        be = get_backend(name)
        st = be.init(4096)
        step = jax.jit(be.apply)        # the plans share a shape: one compile
        out = []
        for p in plans:
            st, res = step(st, p)
            out.append((np.asarray(res.ok), np.asarray(res.vals)))
        results[name] = out
        sizes[name] = int(be.stats(st)["size"])

    ref = results["det_skiplist"]
    for name, out in results.items():
        for rnd, ((ok_r, v_r), (ok, v)) in enumerate(zip(ref, out)):
            assert (ok_r == ok).all(), (name, rnd, "ok")
            assert (v_r == v).all(), (name, rnd, "vals")
    assert len(set(sizes.values())) == 1, sizes


def test_parity_matches_dict_model():
    plans = _mixed_plans(seed=3)
    be = get_backend("det_skiplist")
    st = be.init(4096)
    model = {}
    for p in plans:
        ops = np.asarray(p.ops)
        keys = np.asarray(p.keys)
        vals = np.asarray(p.vals)
        mask = np.asarray(p.mask)
        st, res = be.apply(st, p)
        ok = np.asarray(res.ok)
        live = [i for i in range(p.width) if mask[i]]
        for i in live:
            if ops[i] == OP_INSERT and int(keys[i]) not in model:
                model[int(keys[i])] = int(vals[i])
        for i in live:
            if ops[i] == OP_DELETE:
                model.pop(int(keys[i]), None)
        for i in range(p.width):
            if mask[i] and ops[i] == OP_FIND:
                assert bool(ok[i]) == (int(keys[i]) in model)
                if ok[i]:
                    assert int(np.asarray(res.vals)[i]) == model[int(keys[i])]
    assert int(be.stats(st)["size"]) == len(model)


def test_ordered_backends_scan_parity():
    rng = np.random.default_rng(5)
    ks = np.unique(rng.integers(1, 2**40, 60, dtype=np.uint64))
    plan = make_plan(np.full(len(ks), OP_INSERT, np.int32), ks, ks + 9)
    lo = u64([0, int(ks[10])])
    hi = u64([2**41, int(ks[40])])
    ref = None
    for name in ORDERED:
        be = get_backend(name)
        st, _ = be.apply(be.init(1024), plan)
        cnt, keys, vals, valid = be.scan(st, lo, hi, 64)
        rows = []
        for q in range(2):
            rows.append([(int(k), int(v)) for k, v, m in
                         zip(np.asarray(keys[q]), np.asarray(vals[q]),
                             np.asarray(valid[q])) if m])
        got = (np.asarray(cnt).tolist(), rows)
        if ref is None:
            ref = (name, got)
        else:
            assert got == ref[1], (name, ref[0])
    assert ref[1][0] == [len(ks), 30]


@pytest.mark.parametrize("name", ["det_skiplist", "pq"])
def test_snapshot_scan_as_of_batch(name):
    """scan(as_of_batch=b) sees exactly the entries inserted by applies
    0..b: later batches are invisible, and the exact count plane agrees
    with the valid plane. Apply #i stamps its inserts with clock i."""
    be = get_backend(name)
    st = be.init(256)
    batches = [u64([10, 20]), u64([30, 40]), u64([50, 60])]
    for ks in batches:
        st, res = be.apply(st, make_plan(
            np.full(2, OP_INSERT, np.int32), ks, ks + 1))
        assert res.ok.all()
    lo, hi = u64([0]), u64([2**63])
    for b in range(3):
        cnt, keys, vals, valid = be.scan(st, lo, hi, 16, as_of_batch=b)
        seen = sorted(int(k) for k, m in
                      zip(np.asarray(keys[0]), np.asarray(valid[0])) if m)
        want = sorted(int(k) for ks in batches[:b + 1] for k in np.asarray(ks))
        assert seen == want, b
        assert int(cnt[0]) == 2 * (b + 1)
    # no as_of: the plain full scan, unchanged
    cnt, _, _, valid = be.scan(st, lo, hi, 16)
    assert int(cnt[0]) == 6 == int(np.asarray(valid[0]).sum())


def test_snapshot_scan_is_a_filter_not_time_travel():
    """Deleting an entry hides it from EVERY as_of (tombstones still
    apply), and re-inserting it re-stamps: the revived entry belongs to
    the reviving batch, not the original one."""
    be = get_backend("det_skiplist")
    st = be.init(256)
    ks = u64([10, 20, 30])
    st, _ = be.apply(st, make_plan(np.full(3, OP_INSERT, np.int32), ks, ks))
    st, res = be.apply(st, make_plan(
        np.array([OP_DELETE], np.int32), u64([20])))          # batch 1
    assert bool(res.ok[0])
    lo, hi = u64([0]), u64([2**63])
    for b in range(2):
        _, keys, _, valid = be.scan(st, lo, hi, 8, as_of_batch=b)
        seen = {int(k) for k, m in
                zip(np.asarray(keys[0]), np.asarray(valid[0])) if m}
        assert seen == {10, 30}, b                 # 20 gone at every as_of
    st, _ = be.apply(st, make_plan(
        np.array([OP_INSERT], np.int32), u64([20]), u64([99])))  # batch 2
    _, keys, _, valid = be.scan(st, lo, hi, 8, as_of_batch=1)
    seen = {int(k) for k, m in
            zip(np.asarray(keys[0]), np.asarray(valid[0])) if m}
    assert seen == {10, 30}                        # revival stamped batch 2
    cnt, keys, _, valid = be.scan(st, lo, hi, 8, as_of_batch=2)
    seen = {int(k) for k, m in
            zip(np.asarray(keys[0]), np.asarray(valid[0])) if m}
    assert seen == {10, 20, 30} and int(cnt[0]) == 3


def test_unordered_backends_refuse_scan():
    for name in ALL_BACKENDS:
        be = get_backend(name)
        if be.ordered:
            continue
        with pytest.raises(NotImplementedError):
            be.scan(be.init(64), u64([0]), u64([1]), 4)


def test_unknown_backend_error():
    with pytest.raises(KeyError, match="unknown store backend"):
        get_backend("btree9000")


# ---------------------------------------------------------------------------
# tier stack (store/tiers.py)
# ---------------------------------------------------------------------------

def _keys_filling_hot(num_slots: int, per_slot: int, seed=17) -> np.ndarray:
    """Distinct keys hashing `per_slot`-deep into every hot-tier slot — fills
    an [num_slots, per_slot] fixed-hash tier EXACTLY."""
    rng = np.random.default_rng(seed)
    buckets: dict[int, list] = {s: [] for s in range(num_slots)}
    while any(len(v) < per_slot for v in buckets.values()):
        cand = rng.integers(1, 2**62, 512, dtype=np.uint64)
        slots = np.asarray(hash_slot(jnp.asarray(cand), num_slots))
        for k, s in zip(cand.tolist(), slots.tolist()):
            if len(buckets[s]) < per_slot and k not in buckets[s]:
                buckets[s].append(k)
    return np.array([k for v in buckets.values() for k in v], dtype=np.uint64)


class TestTieredStore:
    def _setup_split(self):
        """Insert past the hot tier's capacity so spill is guaranteed."""
        be = get_backend("hash+skiplist")
        st = be.init(1024, hot_bucket=4, hot_frac=32)   # hot: 8 slots x 4
        rng = np.random.default_rng(11)
        ks = np.unique(rng.integers(1, 2**62, 64, dtype=np.uint64))
        st, res = be.apply(st, make_plan(
            np.full(len(ks), OP_INSERT, np.int32), ks, ks + 1))
        assert res.ok.all()
        return be, st, ks

    def test_spill_and_size_conservation(self):
        be, st, ks = self._setup_split()
        s = be.stats(st)
        assert int(s["size"]) == len(ks)
        assert int(s["hot_size"]) <= 32
        assert int(s["cold_size"]) > 0          # bucket overflow spilled down
        assert int(s["hot_size"]) + int(s["cold_size"]) == len(ks)

    def test_promotion_moves_cold_hits_up(self):
        be, st, ks = self._setup_split()
        hot_keys = set(np.asarray(st.hot.keys).reshape(-1).tolist())
        hot_resident = np.array([k for k in ks if int(k) in hot_keys],
                                dtype=np.uint64)
        cold_resident = np.array([k for k in ks if int(k) not in hot_keys],
                                 dtype=np.uint64)
        assert len(hot_resident) and len(cold_resident)

        # free the hot tier, then FIND the cold residents -> they promote
        st, res = be.apply(st, make_plan(
            np.full(len(hot_resident), OP_DELETE, np.int32), hot_resident))
        assert res.ok.all()
        st, res = be.apply(st, make_plan(
            np.full(len(cold_resident), OP_FIND, np.int32), cold_resident))
        assert res.ok.all()
        assert (np.asarray(res.vals) == cold_resident + 1).all()
        s = be.stats(st)
        assert int(s["size"]) == len(cold_resident)   # membership-neutral
        assert int(s["hot_size"]) > 0                 # promotion happened
        # promoted keys now serve from the hot tier
        hot_keys2 = set(np.asarray(st.hot.keys).reshape(-1).tolist())
        promoted = [k for k in cold_resident if int(k) in hot_keys2]
        assert len(promoted) == int(s["hot_size"])
        # and still findable with intact values
        st, res = be.apply(st, make_plan(
            np.full(len(cold_resident), OP_FIND, np.int32), cold_resident))
        assert res.ok.all()

    def test_flush_demotes_everything(self):
        be, st, ks = self._setup_split()
        st = be.flush(st)
        s = be.stats(st)
        assert int(s["hot_size"]) == 0
        assert int(s["size"]) == len(ks)
        st, res = be.apply(st, make_plan(
            np.full(len(ks), OP_FIND, np.int32), ks))
        assert res.ok.all()
        assert (np.asarray(res.vals) == ks + 1).all()

    def _setup_exactly_full(self):
        """Hot tier (8 slots x 4) filled to EXACTLY its capacity."""
        be = get_backend("hash+skiplist")
        st = be.init(1024, hot_bucket=4, hot_frac=32)
        fill = _keys_filling_hot(8, 4)
        st, res = be.apply(st, make_plan(
            np.full(len(fill), OP_INSERT, np.int32), fill, fill + 1))
        assert res.ok.all()
        s = be.stats(st)
        assert int(s["hot_size"]) == 32 and int(s["cold_size"]) == 0
        return be, st, fill

    def test_insert_spills_when_hot_exactly_full(self):
        be, st, fill = self._setup_exactly_full()
        extra = np.uint64(2**62 + 11)          # outside the fill key range
        st, res = be.apply(st, make_plan(
            np.array([OP_INSERT], np.int32), u64([extra]), u64([extra + 1])))
        assert bool(res.ok[0])
        s = be.stats(st)
        assert int(s["hot_size"]) == 32        # no hot cell was displaced
        assert int(s["cold_size"]) == 1        # the new key spilled down
        # every hot resident still served, values intact
        st, res = be.apply(st, make_plan(
            np.full(len(fill), OP_FIND, np.int32), fill))
        assert res.ok.all()
        assert (np.asarray(res.vals) == fill + 1).all()

    def test_promotion_noop_when_hot_exactly_full(self):
        be, st, fill = self._setup_exactly_full()
        extra = np.uint64(2**62 + 11)
        st, _ = be.apply(st, make_plan(
            np.array([OP_INSERT], np.int32), u64([extra]), u64([extra + 1])))
        # FIND the cold resident: promotion has no hot space -> key STAYS
        # cold, result still correct, membership conserved
        st, res = be.apply(st, make_plan(
            np.array([OP_FIND], np.int32), u64([extra])))
        assert bool(res.ok[0]) and int(res.vals[0]) == int(extra) + 1
        s = be.stats(st)
        assert int(s["hot_size"]) == 32 and int(s["cold_size"]) == 1
        assert int(s["size"]) == len(fill) + 1

    def test_flush_when_hot_exactly_full(self):
        be, st, fill = self._setup_exactly_full()
        st = be.flush(st)
        s = be.stats(st)
        assert int(s["hot_size"]) == 0
        assert int(s["cold_size"]) == len(fill) == int(s["size"])
        st, res = be.apply(st, make_plan(
            np.full(len(fill), OP_FIND, np.int32), fill))
        assert res.ok.all()
        assert (np.asarray(res.vals) == fill + 1).all()

    def test_scan_sees_both_tiers(self):
        be, st, ks = self._setup_split()
        det = get_backend("det_skiplist")
        st_d, _ = det.apply(det.init(1024), make_plan(
            np.full(len(ks), OP_INSERT, np.int32), ks, ks + 1))
        sk = np.sort(ks)
        lo = u64([0, int(sk[8])])
        hi = u64([2**63, int(sk[40])])
        cnt_t, k_t, v_t, m_t = be.scan(st, lo, hi, len(ks) + 8)
        cnt_d, k_d, v_d, m_d = det.scan(st_d, lo, hi, len(ks) + 8)
        assert (np.asarray(cnt_t) == np.asarray(cnt_d)).all()
        assert int(cnt_t[0]) == len(ks) and int(cnt_t[1]) == 32
        for q in range(2):
            a = [(int(k), int(v)) for k, v, m in zip(
                np.asarray(k_t[q]), np.asarray(v_t[q]), np.asarray(m_t[q])) if m]
            b = [(int(k), int(v)) for k, v, m in zip(
                np.asarray(k_d[q]), np.asarray(v_d[q]), np.asarray(m_d[q])) if m]
            assert a == b, q
            assert a == sorted(a)                    # ordered output
