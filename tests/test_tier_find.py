"""Fused tier-find parity: the one-dispatch FIND path contract.

The fused `store.exec.tier_find` (kernels/tier_find — hot bucket probe +
warm level walk + per-run spill search in ONE pallas_call) must be
BIT-IDENTICAL to the unfused dispatch-per-tier chain, for results AND for
the full residency pytree, in every runnable exec mode — fusion is a
dispatch-count optimization, never a semantics change. Also covered: the
per-run spill searchsorted (now the jnp reference path too), the
`run_offsets` boundary plane, the run-count cap that keeps it static, the
measured dispatch counts (FIND phase = exactly ONE dispatch fused), the
two-level split-order probe kernel, and the pinned-host spill placement
guard. (The 8-device engine analogue runs in
tests/multidev/store_prog.py: FUSED-OK.)
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (enables x64)
from repro.core.bits import KEY_INF
from repro.core.layout import MAX_SPILL_RUNS, run_offsets
from repro.store import (OP_DELETE, OP_FIND, OP_INSERT, get_backend,
                         make_plan)
from repro.store import exec as exec_
from repro.store.tiers import spill_find_ref, spill_init, unfused_twin

MODES = exec_.runnable_modes()
TIERED = ["hash+skiplist", "tiered3", "tiered3/lru", "tiered3/size",
          "tiered3/b128"]
WARM_LAYOUTS = ("level", "block")


def _mixed_plans(seed=21, n_rounds=5, width=48, pool_size=96):
    rng = np.random.default_rng(seed)
    pool = rng.integers(1, 2**62, pool_size, dtype=np.uint64)
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


# ---------------------------------------------------------------------------
# the run-boundary plane + per-run spill probe
# ---------------------------------------------------------------------------

def _spill_with_runs(seed=3, capacity=128, runs=5, run_len=9, kills=7):
    """A spill tier holding `runs` appended sorted runs with tombstones."""
    rng = np.random.default_rng(seed)
    sp = spill_init(capacity)
    from repro.store.tiers import spill_append, spill_discard
    all_keys = []
    for _ in range(runs):
        ks = np.unique(rng.integers(1, 2**62, run_len + 2,
                                    dtype=np.uint64))[:run_len]
        sp, ok = spill_append(sp, jnp.asarray(ks), jnp.asarray(ks + 1),
                              jnp.ones((len(ks),), bool))
        all_keys.extend(ks[np.asarray(ok)].tolist())
    doomed = rng.choice(np.array(all_keys, np.uint64), kills, replace=False)
    sp, eff = spill_discard(sp, jnp.asarray(doomed),
                            jnp.ones((kills,), bool))
    assert bool(np.asarray(eff).all())
    live = sorted(set(all_keys) - set(doomed.tolist()))
    return sp, np.array(live, np.uint64), doomed


def test_run_offsets_boundaries():
    sp, _, _ = _spill_with_runs()
    off = np.asarray(run_offsets(sp.run_start, sp.n))
    assert off.shape == (MAX_SPILL_RUNS + 1,)
    n = int(sp.n)
    starts = np.flatnonzero(np.asarray(sp.run_start)[:n])
    n_runs = len(starts)
    assert off[:n_runs].tolist() == starts.tolist()
    assert (off[n_runs:] == n).all()           # pads + sentinel = cursor
    assert (np.diff(off) >= 0).all()
    # every run slice is sorted (the property the binary search leans on)
    keys = np.asarray(sp.keys)
    for r in range(n_runs):
        run = keys[off[r]:off[r + 1]]
        assert (np.diff(run.astype(np.float64)) > 0).all()


def test_spill_per_run_probe_matches_flat_compare():
    sp, live, doomed = _spill_with_runs()
    queries = np.concatenate([live, doomed,
                              np.array([123456789, KEY_INF], np.uint64)])
    found, vals = spill_find_ref(sp, jnp.asarray(queries))
    # oracle: the pre-fusion masked flat compare
    alive = ~np.asarray(sp.dead) & (np.asarray(sp.keys) != KEY_INF)
    eq = (np.asarray(sp.keys)[None, :] == queries[:, None]) & alive[None, :]
    want = eq.any(axis=1) & (queries != KEY_INF)
    assert (np.asarray(found) == want).all()
    idx = np.argmax(eq, axis=1)
    wvals = np.where(want, np.asarray(sp.vals)[idx], 0)
    assert (np.asarray(vals) == wvals).all()


def test_spill_probe_handles_duplicate_dead_copies():
    """A key whose old copy is tombstoned in an earlier run and live in a
    later one must resolve to the live cell (promote-then-evict churn)."""
    from repro.store.tiers import spill_append, spill_discard
    sp = spill_init(64)
    ks = np.array([10, 20, 30], np.uint64)
    sp, _ = spill_append(sp, jnp.asarray(ks), jnp.asarray(ks + 1),
                         jnp.ones((3,), bool))
    sp, _ = spill_discard(sp, jnp.asarray(np.array([20], np.uint64)),
                          jnp.ones((1,), bool))
    sp, _ = spill_append(sp, jnp.asarray(np.array([20], np.uint64)),
                         jnp.asarray(np.array([99], np.uint64)),
                         jnp.ones((1,), bool))
    found, vals = spill_find_ref(sp, jnp.asarray(np.array([20], np.uint64)))
    assert bool(found[0]) and int(vals[0]) == 99


def test_run_count_stays_under_cap():
    """Appending more batches than MAX_SPILL_RUNS must trigger the
    run-merging maintenance, never exceed the boundary plane."""
    be = get_backend("tiered3")
    st = be.init(8, hot_bucket=2, hot_frac=4, spill_cap=4096)
    rng = np.random.default_rng(11)
    step = jax.jit(be.apply)
    for i in range(MAX_SPILL_RUNS + 8):
        ks = np.unique(rng.integers(1, 2**62, 24, dtype=np.uint64))[:20]
        st, res = step(st, make_plan(
            np.full(len(ks), OP_INSERT, np.int32), ks, ks + 1))
        assert bool(np.asarray(res.ok).all())
        runs = int(np.asarray(st.spill.run_start).sum())
        assert runs <= MAX_SPILL_RUNS, (i, runs)
    # everything is still findable after the forced merges
    assert int(be.stats(st)["spill_size"]) > 0


def test_spill_tier_init_is_device_resident():
    """The spill planes are placed like every other state leaf (on the
    default device); no init path moves them to host memory."""
    st = get_backend("tiered3").init(32, hot_bucket=4, hot_frac=8)
    assert_states_equal(st.spill, spill_init(32), "spill init")
    dev = jax.devices()[0]
    for leaf in jax.tree.leaves(st.spill):
        assert leaf.devices() == {dev}
        assert leaf.sharding.memory_kind in (None, dev.default_memory().kind)


# ---------------------------------------------------------------------------
# fused probe vs unfused chain, probe-level and apply-level
# ---------------------------------------------------------------------------

def _loaded_state(name, seed=7):
    """A tier state with all tiers populated (warm overflowed on depth 3)."""
    be = get_backend(name)
    st = be.init(32, hot_bucket=4, hot_frac=8)
    rng = np.random.default_rng(seed)
    ks = np.unique(rng.integers(1, 2**62, 80, dtype=np.uint64))[:60]
    st, _ = be.apply(st, make_plan(np.full(len(ks), OP_INSERT, np.int32),
                                   ks, ks + 1))
    return be, st, ks


@pytest.mark.parametrize("warm_layout", WARM_LAYOUTS)
@pytest.mark.parametrize("name", ["tiered3", "hash+skiplist"])
def test_tier_find_matches_unfused_probes(name, warm_layout):
    """Probe-level parity: one tier_find call vs the three (or two)
    separate exec probes, same state, every runnable mode — under BOTH
    warm layouts (the unfused warm probe is the matching layout's walk:
    `skiplist_find` or `bskiplist_find`)."""
    _, st, ks = _loaded_state(name)
    rng = np.random.default_rng(5)
    queries = jnp.asarray(np.concatenate(
        [ks[:20], rng.integers(1, 2**62, 12, dtype=np.uint64)]))
    warm_find = (exec_.bskiplist_find if warm_layout == "block"
                 else exec_.skiplist_find)
    for mode in MODES:
        (fh, vh, ch), (fc, vc), (fs, vs) = exec_.tier_find(
            st.hot, st.cold, st.spill, queries, mode,
            warm_layout=warm_layout)
        rh, rvh, rch = exec_.hash_find_cols(st.hot, queries, mode)
        rc, rvc, _ = warm_find(st.cold, queries, mode)
        if st.spill is not None:
            rs, rvs = exec_.spill_find(st.spill, queries, mode)
        else:
            rs = jnp.zeros(queries.shape, bool)
            rvs = jnp.zeros(queries.shape, jnp.uint64)
        # raw parity on the hot tier (col included, it feeds LRU stamps)
        assert (np.asarray(fh) == np.asarray(rh)).all(), mode
        assert (np.asarray(vh) == np.asarray(rvh)).all(), mode
        hot_hit = np.asarray(rh)
        assert (np.asarray(ch)[hot_hit] == np.asarray(rch)[hot_hit]).all()
        # fall-through masking: lower tiers only count on upper-tier miss
        assert (np.asarray(fc) == (np.asarray(rc) & ~hot_hit)).all(), mode
        miss2 = ~hot_hit & ~np.asarray(rc)
        assert (np.asarray(fs) == (np.asarray(rs) & miss2)).all(), mode
        cold_hit = np.asarray(fc)
        assert (np.asarray(vc)[cold_hit]
                == np.asarray(rvc)[cold_hit]).all(), mode
        sp_hit = np.asarray(fs)
        assert (np.asarray(vs)[sp_hit] == np.asarray(rvs)[sp_hit]).all()
        # every preloaded key is found in exactly one tier
        total = (np.asarray(fh) | np.asarray(fc) | np.asarray(fs))
        assert total[:20].all(), mode


@pytest.mark.parametrize("name", TIERED)
def test_fused_apply_bit_identical_to_unfused(name):
    """Apply-level parity: the registered (fused) backend and an unfused
    twin produce identical results AND identical residency (full state
    pytree) for the same plan stream, in every runnable mode."""
    plans = _mixed_plans()
    for mode in MODES:
        fused = get_backend(name)
        unf = unfused_twin(name)
        with exec_.exec_mode(mode):
            sf = fused.init(64, hot_bucket=4, hot_frac=8)
            su = unf.init(64, hot_bucket=4, hot_frac=8)
            step_f = jax.jit(fused.apply)
            step_u = jax.jit(unf.apply)
            for rnd, p in enumerate(plans):
                sf, rf = step_f(sf, p)
                su, ru = step_u(su, p)
                assert (np.asarray(rf.ok) == np.asarray(ru.ok)).all(), \
                    (name, mode, rnd)
                assert (np.asarray(rf.vals) == np.asarray(ru.vals)).all(), \
                    (name, mode, rnd)
                assert_states_equal(sf, su, (name, mode, rnd))


@pytest.mark.parametrize("name", ["tiered3/lru"])
def test_fused_residency_bit_identical_across_modes(name):
    """The fused path keeps the residency-determinism contract across exec
    modes (the unfused analogue lives in test_tiers3)."""
    be = get_backend(name)
    states = {}
    for mode in MODES:
        with exec_.exec_mode(mode):
            st = be.init(64, hot_bucket=4, hot_frac=8)
            step = jax.jit(be.apply)
            for p in _mixed_plans(seed=33):
                st, _ = step(st, p)
        states[mode] = st
    ref = states[MODES[0]]
    for mode, st in states.items():
        assert_states_equal(ref, st, (name, mode))


@pytest.mark.parametrize("name", ["tiered3", "tiered3/b128"])
def test_fused_find_is_one_dispatch(name):
    """The acceptance criterion, measured: in fused mode the FIND chain is
    ONE exec dispatch per plan regardless of tier depth (the unfused chain
    pays one per tier), and a whole fused apply traces 2 dispatches total
    (ONE tier_apply update + ONE FIND-phase probe) against the unfused 6
    (2 insert probes + 1 hot_update + 3 FIND probes). The warm layout is
    an execution knob: `tiered3/b128` has the SAME budgets — the blocked
    walk changes steps per dispatch, never dispatches per plan."""
    be = get_backend(name)
    wl = be.warm_layout
    _, st, _ = _loaded_state(name)
    q = jnp.asarray(np.arange(1, 33, dtype=np.uint64))
    with exec_.measure_dispatches() as m_f:
        exec_.tier_find(st.hot, st.cold, st.spill, q, warm_layout=wl)
    assert (m_f.n, m_f.probe, m_f.update) == (1, 1, 0)
    warm_find = (exec_.bskiplist_find if wl == "block"
                 else exec_.skiplist_find)
    with exec_.measure_dispatches() as m_u:
        exec_.hash_find_cols(st.hot, q)
        warm_find(st.cold, q)
        exec_.spill_find(st.spill, q)
    assert (m_u.n, m_u.probe, m_u.update) == (3, 3, 0)

    plan = make_plan(np.full(32, OP_FIND, np.int32), np.asarray(q))
    fused, unf = get_backend(name), unfused_twin(name)
    with exec_.measure_dispatches() as m_f:
        jax.make_jaxpr(fused.apply)(st, plan)
    assert (m_f.n, m_f.probe, m_f.update) == (2, 1, 1), \
        "fused apply: ONE tier_apply update + ONE FIND-phase probe"
    with exec_.measure_dispatches() as m_u:
        jax.make_jaxpr(unf.apply)(st, plan)
    assert (m_u.n, m_u.probe, m_u.update) == (6, 5, 1), \
        "unfused apply: 2 insert probes + hot_update + 3 FIND probes"


def test_tier_find_empty_batch_all_modes():
    _, st, _ = _loaded_state("tiered3")
    none = jnp.zeros((0,), jnp.uint64)
    for mode in MODES:
        (fh, vh, ch), (fc, vc), (fs, vs) = exec_.tier_find(
            st.hot, st.cold, st.spill, none, mode)
        for a in (fh, vh, ch, fc, vc, fs, vs):
            assert a.shape == (0,), mode


# ---------------------------------------------------------------------------
# the two-level split-order probe kernel
# ---------------------------------------------------------------------------

def test_twolevel_splitorder_probe_matches_reference():
    from repro.core import splitorder as so
    from repro.kernels.splitorder_probe.ops import twolevel_splitorder_probe
    rng = np.random.default_rng(17)
    h = so.twolevel_splitorder_init(8, 64, 2)
    ks = np.unique(rng.integers(1, 2**62, 200, dtype=np.uint64))[:150]
    h, ins, _ = so.twolevel_splitorder_insert(h, jnp.asarray(ks),
                                              jnp.asarray(ks + 1))
    assert bool(np.asarray(ins).all())
    queries = np.concatenate([ks[:64], rng.integers(1, 2**62, 64,
                                                    dtype=np.uint64),
                              np.array([KEY_INF], np.uint64)])
    want_f, want_v = so.twolevel_splitorder_find(h, jnp.asarray(queries))
    got_f, got_v = twolevel_splitorder_probe(h, jnp.asarray(queries),
                                             interpret=True)
    assert (np.asarray(got_f) == np.asarray(want_f)).all()
    assert (np.asarray(got_v) == np.asarray(want_v)).all()


def test_twolevel_splitorder_backend_parity_modes():
    """Backend-level: interpret mode (kernel) == jnp mode (reference) for a
    mixed plan stream, including the post-resize layout."""
    name = "twolevel_splitorder"
    plans = _mixed_plans(seed=9, n_rounds=3)
    outs = {}
    for mode in MODES:
        be = get_backend(name)
        with exec_.exec_mode(mode):
            st = be.init(2048)
            step = jax.jit(be.apply)
            rows = []
            for p in plans:
                st, res = step(st, p)
                rows.append((np.asarray(res.ok), np.asarray(res.vals)))
        outs[mode] = rows
    ref = outs[MODES[0]]
    for mode in MODES[1:]:
        for (ok_r, v_r), (ok, v) in zip(ref, outs[mode]):
            assert (ok_r == ok).all(), mode
            assert (v_r == v).all(), mode
