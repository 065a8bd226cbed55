"""Fused tier-apply parity: the ≤2-dispatch apply-path contract.

The fused `store.exec.tier_apply` (kernels/tier_apply — the tier_find
membership probes + the hot-insert linearization + the eviction policy's
victim selection in ONE pallas_call, lanes scanned in tiles with the
running counts carried across them) must be
BIT-IDENTICAL to the jnp reference and to the unfused dispatch-per-phase
chain, for results AND the full residency pytree, in every runnable exec
mode. Covered here: direct exec-entry parity across modes for every
policy, the measured dispatch budget (a whole fused apply = exactly TWO
dispatches: one tier_apply update + one FIND-phase tier_find probe), a
many-run spill tier scanned in several lane tiles, run-cap
compaction tripping INSIDE a fused apply, and the empty batch. (The
8-device engine analogue runs in tests/multidev/store_prog.py: APPLY-OK.)
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (enables x64)
from repro.core.layout import SpillLayout
from repro.store import (OP_DELETE, OP_FIND, OP_INSERT, get_backend,
                         make_plan)
from repro.store import exec as exec_
from repro.store.tiers import unfused_twin

MODES = exec_.runnable_modes()
TIERED = ["hash+skiplist", "tiered3", "tiered3/lru", "tiered3/size",
          "tiered3/b128"]
POLICY_OF = {"tiered3": "none", "tiered3/lru": "lru",
             "tiered3/size": "size", "tiered3/b128": "none"}


def _warm_layout_of(name):
    return "block" if name.endswith("/b128") else "level"


def assert_states_equal(sa, sb, ctx):
    la, lb = jax.tree.leaves(sa), jax.tree.leaves(sb)
    assert len(la) == len(lb), ctx
    for i, (a, b) in enumerate(zip(la, lb)):
        assert (np.asarray(a) == np.asarray(b)).all(), (ctx, i)


def _loaded_state(name, seed=7, capacity=32):
    """A tier state with all three tiers populated (warm overflowed)."""
    be = get_backend(name)
    st = be.init(capacity, hot_bucket=4, hot_frac=8)
    rng = np.random.default_rng(seed)
    ks = np.unique(rng.integers(1, 2**62, 80, dtype=np.uint64))[:60]
    st, _ = be.apply(st, make_plan(np.full(len(ks), OP_INSERT, np.int32),
                                   ks, ks + 1))
    return be, st, ks


def _apply_batch(ks, seed=9, width=48):
    """Insert lanes mixing resident keys (hot/warm/spill), fresh keys,
    in-batch duplicates, and masked-off lanes — every branch of the apply
    prologue in one batch."""
    rng = np.random.default_rng(seed)
    fresh = rng.integers(2**62, 2**63, width, dtype=np.uint64)
    keys = np.where(rng.random(width) < 0.5, rng.choice(ks, width), fresh)
    keys[width - 3] = keys[0]                       # guaranteed in-batch dup
    mask = rng.random(width) > 0.1
    vals = rng.integers(1, 2**62, width, dtype=np.uint64)
    return jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(mask)


# ---------------------------------------------------------------------------
# exec-entry parity across modes (the kernel vs its jnp oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(POLICY_OF))
def test_tier_apply_exec_matches_ref_across_modes(name):
    """One exec.tier_apply call per mode on the same loaded state: all nine
    outputs (hot', meta', the membership/insert flags, and the victim
    lanes) bit-identical between the jnp reference and the fused kernel."""
    _, st, ks = _loaded_state(name)
    keys, vals, mask = _apply_batch(ks)
    outs = {}
    for mode in MODES:
        outs[mode] = exec_.tier_apply(st.hot, st.hot_meta, st.clock,
                                      st.cold, st.spill, keys, vals, mask,
                                      POLICY_OF[name], 8, mode,
                                      warm_layout=_warm_layout_of(name))
    ref_mode, ref = next(iter(outs.items()))
    for mode, got in outs.items():
        assert_states_equal(ref, got, (name, ref_mode, mode))


def test_tier_apply_two_tier_stack_no_spill():
    """spill=None (hash+skiplist depth): the kernel builds without the
    spill planes and spill lanes are all-miss."""
    _, st, ks = _loaded_state("hash+skiplist")
    assert st.spill is None
    keys, vals, mask = _apply_batch(ks, seed=11)
    outs = {}
    for mode in MODES:
        outs[mode] = exec_.tier_apply(st.hot, st.hot_meta, st.clock,
                                      st.cold, None, keys, vals, mask,
                                      "none", 8, mode)
    ref_mode, ref = next(iter(outs.items()))
    for mode, got in outs.items():
        assert_states_equal(ref, got, (ref_mode, mode))
    assert not np.asarray(ref[3]).any()             # in_spill all-miss


def test_tier_apply_large_spill_across_lane_tiles():
    """A many-run spill tier probed by a plan cut into several lane tiles:
    the per-run spill search and the running counts carried from tile to
    tile must reproduce the reference bit exactly."""
    from repro.kernels.tier_apply.ops import tier_apply_fused
    from repro.kernels.tier_apply.ref import tier_apply_ref
    be = get_backend("tiered3/lru")
    st = be.init(64, hot_bucket=4, hot_frac=8, spill_cap=4096)
    rng = np.random.default_rng(19)
    ks = np.unique(rng.integers(1, 2**62, 900, dtype=np.uint64))[:800]
    for chunk in np.array_split(ks, 4):
        st, _ = be.apply(st, make_plan(
            np.full(len(chunk), OP_INSERT, np.int32), chunk, chunk + 1))
    assert int(st.spill.n) > 256                  # several spill rows
    keys, vals, mask = _apply_batch(ks, seed=23)
    ref = tier_apply_ref(st.hot, st.hot_meta, st.clock, st.cold, st.spill,
                         keys, vals, mask, "lru", 8)
    got = tier_apply_fused(st.hot, st.hot_meta, st.clock, st.cold, st.spill,
                           keys, vals, mask, "lru", 8, tile=16,
                           interpret=True)
    assert_states_equal(ref, got, "tiled-lanes")
    assert np.asarray(ref[3]).any()               # spill residents probed


def test_tier_apply_empty_batch_all_modes():
    _, st, _ = _loaded_state("tiered3")
    none = jnp.zeros((0,), jnp.uint64)
    zb = jnp.zeros((0,), bool)
    for mode in MODES:
        out = exec_.tier_apply(st.hot, st.hot_meta, st.clock, st.cold,
                               st.spill, none, none, zb, "none", 8, mode)
        for a in out[2:]:
            assert a.shape == (0,), mode
        assert_states_equal((out[0], out[1]), (st.hot, st.hot_meta), mode)


# ---------------------------------------------------------------------------
# the dispatch budget (the acceptance criterion, measured)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", TIERED)
def test_fused_apply_is_two_dispatches(name):
    """A whole fused apply traces exactly TWO exec dispatches per plan —
    one tier_apply update (insert prologue) + one tier_find probe (FIND
    phase) — regardless of tier depth or policy; the unfused twin pays one
    probe per tier per phase plus the hot_update."""
    be = get_backend(name)
    st = be.init(32, hot_bucket=4, hot_frac=8)
    plan = make_plan(np.array([OP_INSERT, OP_FIND, OP_DELETE], np.int32),
                     np.array([5, 6, 7], np.uint64))
    with exec_.measure_dispatches() as m_f:
        jax.make_jaxpr(be.apply)(st, plan)
    assert (m_f.n, m_f.probe, m_f.update) == (2, 1, 1), name
    n_tiers = 2 if name == "hash+skiplist" else 3
    with exec_.measure_dispatches() as m_u:
        jax.make_jaxpr(unfused_twin(name).apply)(st, plan)
    # insert phase: probes the LOWER tiers only; FIND phase: every tier
    assert (m_u.n, m_u.probe, m_u.update) == \
        (2 * n_tiers, 2 * n_tiers - 1, 1), name


# ---------------------------------------------------------------------------
# run-cap compaction inside a fused apply (the maintenance interaction)
# ---------------------------------------------------------------------------

def test_run_cap_compaction_inside_fused_apply():
    """Demote-per-apply churn accretes one spill run per batch until the
    static run cap trips `spill_maintain` INSIDE an apply. The fused path
    must ride through the merge bit-identically to the unfused twin in
    every runnable mode, and the residency audit must hold throughout."""
    rng = np.random.default_rng(29)
    preload = np.unique(rng.integers(1, 2**61, 32, dtype=np.uint64))[:20]
    rounds = [np.unique(rng.integers(2**61, 2**62, 4, dtype=np.uint64))[:2]
              for _ in range(SpillLayout.MAX_RUNS - 1)]
    total = len(preload) + sum(len(r) for r in rounds)

    states, runs_seen = {}, []
    for tag, be in (("fused", get_backend("tiered3/lru")),
                    ("unfused", unfused_twin("tiered3/lru"))):
        for mode in MODES:
            with exec_.exec_mode(mode):
                # hot 2x2, warm 16: every post-preload insert demotes
                st = be.init(16, hot_bucket=2, hot_frac=8, spill_cap=64)
                step = jax.jit(be.apply)
                st, res = step(st, make_plan(
                    np.full(len(preload), OP_INSERT, np.int32), preload,
                    preload + 1))
                assert bool(np.asarray(res.ok).all())
                for ks in rounds:
                    st, res = step(st, make_plan(
                        np.full(len(ks), OP_INSERT, np.int32), ks, ks + 1))
                    assert bool(np.asarray(res.ok).all())
                    runs = int(np.asarray(st.spill.run_start)
                               [:int(st.spill.n)].sum())
                    assert runs <= SpillLayout.MAX_RUNS
                    if tag == "fused" and mode == MODES[0]:
                        runs_seen.append(runs)
            states[(tag, mode)] = st

    # the cap genuinely tripped: the run count grew, then a merge shrank it
    assert max(runs_seen) >= SpillLayout.MAX_RUNS - SpillLayout.RUNS_PER_APPLY
    assert any(b < a for a, b in zip(runs_seen, runs_seen[1:])), runs_seen

    ref_key, ref = next(iter(states.items()))
    for key, st in states.items():
        assert_states_equal(ref, st, (ref_key, key))

    # residency audit on the final state: conservation + findability
    be = get_backend("tiered3/lru")
    s = {k: int(v) for k, v in be.stats(ref).items()}
    assert s["size"] == total
    assert s["hot_size"] + s["cold_size"] + s["spill_size"] == total
    every = np.concatenate([preload] + rounds)
    st, res = be.apply(ref, make_plan(
        np.full(len(every), OP_FIND, np.int32), every))
    assert bool(np.asarray(res.ok).all())
    assert (np.asarray(res.vals) == every + 1).all()
