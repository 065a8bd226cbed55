"""Multi-device validation program for the sharded store engine.

Run under XLA_FLAGS=--xla_force_host_platform_device_count=8 by the
`run_store_prog` fixture of tests/conftest.py. Builds a (2, 4)
("pod", "data") mesh — a miniature of the production (2, 16, 16) — and,
for EVERY backend listed in BACKENDS (flat skiplist, hash tables,
split-order, and the tiered hash+skiplist stack), applies random batched
ops through the hierarchical router and checks every result against a
global dict model. The uniform
`repro.store` protocol is what lets one program validate all of them.
`python store_prog.py [group ...]` runs the named groups of `GROUPS`
(every group when none is named). Exits 0 on success.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import repro  # noqa: F401,E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.store import (OP_DELETE, OP_FIND, OP_INSERT, OP_POPK,  # noqa: E402
                         OP_POPMIN)
from repro.store.engine import StoreEngine  # noqa: E402

AXES = ("pod", "data")
LANES = 16
N_SHARDS = 8
ROUNDS = 4
BACKENDS = ("det_skiplist", "twolevel_hash", "splitorder", "hash+skiplist",
            "tiered3/lru", "pq")


def check_backend(mesh, backend: str) -> None:
    eng = StoreEngine(mesh, AXES, LANES, backend=backend, pool_factor=4)
    state = jax.device_put(eng.init(512), eng.sharding)

    rng = np.random.default_rng(42)
    model: dict[int, int] = {}
    total = N_SHARDS * LANES
    for rnd in range(ROUNDS):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], size=total,
                         p=[0.5, 0.4, 0.1]).astype(np.int32)
        keys = rng.integers(1, 2**63, size=total, dtype=np.uint64)
        # force key reuse so finds/deletes hit
        if model:
            reuse = rng.choice(np.fromiter(model.keys(), dtype=np.uint64),
                               size=min(len(model), total // 2))
            keys[: len(reuse)] = reuse
        vals = keys + 1

        ops_d = jax.device_put(jnp.asarray(ops), eng.sharding)
        keys_d = jax.device_put(jnp.asarray(keys), eng.sharding)
        vals_d = jax.device_put(jnp.asarray(vals), eng.sharding)
        state, res, ok, dropped = eng.step(state, ops_d, keys_d, vals_d)
        res, ok = np.asarray(res), np.asarray(ok)
        assert int(dropped) == 0, f"capacity drops: {int(dropped)}"

        # model semantics: batch linearization = inserts, then deletes, then
        # finds; in-batch duplicate inserts: lowest lane wins (vals are a
        # pure function of keys here, so lane order cannot disagree)
        for i in range(total):
            if ops[i] == OP_INSERT and int(keys[i]) not in model:
                model[int(keys[i])] = int(vals[i])
        for i in range(total):
            if ops[i] == OP_DELETE:
                model.pop(int(keys[i]), None)

        for i in range(total):
            k = int(keys[i])
            if ops[i] == OP_FIND:
                want = k in model
                assert bool(ok[i]) == want, (backend, rnd, i, k, "find flag")
                if want:
                    assert int(res[i]) == model[k], (backend, rnd, i, k,
                                                     "find val")

    # uniform stats accessor: global live size must match the model
    sizes = eng.stats(state)["size"]
    assert int(sizes.sum()) == len(model), (backend, sizes, len(model))
    print(f"STORE-OK backend={backend} rounds={ROUNDS} "
          f"model_size={len(model)}")


def check_range(mesh, backend: str) -> None:
    """Cross-shard range counting on an ordered backend (all_gather + psum)."""
    eng = StoreEngine(mesh, AXES, LANES, backend=backend, pool_factor=4)
    state = jax.device_put(eng.init(1024), eng.sharding)
    put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
    rng = np.random.default_rng(9)
    keys = rng.integers(1, 2**63, N_SHARDS * LANES, dtype=np.uint64)
    state, _, ok, dropped = eng.step(
        state, put(np.full(keys.size, OP_INSERT, np.int32)), put(keys),
        put(keys + 1))
    assert np.asarray(ok).all() and int(dropped) == 0
    rstep = eng.range_step(max_out=keys.size)
    ks = np.sort(np.unique(keys))
    los = np.zeros(keys.size, np.uint64)
    his = np.zeros(keys.size, np.uint64)
    valid = np.zeros(keys.size, bool)
    los[0], his[0], valid[0] = 0, np.uint64(2**63), True      # everything
    los[1], his[1], valid[1] = ks[10], ks[50], True           # 40 keys
    cnt = np.asarray(rstep(state, put(los), put(his), put(valid)))
    assert int(cnt[0]) == len(ks), cnt[0]
    assert int(cnt[1]) == 40, cnt[1]
    print(f"RANGE-OK backend={backend} counts={cnt[:2]}")


def check_uneven_occupancy(mesh) -> None:
    """Engine scan/stats when per-shard occupancy is SKEWED (shard s holds
    2s+1 keys: shard 0 nearly empty, shard 7 ~full for its lane budget),
    and exec-layer parity on the same skewed state: the jnp and
    Pallas-interpret engines must agree bit-for-bit."""
    per_shard = [2 * s + 1 for s in range(N_SHARDS)]         # 1,3,...,15
    rng = np.random.default_rng(5)
    keys = []
    for s, n in enumerate(per_shard):
        low = rng.integers(1, 2**61, n, dtype=np.uint64)
        keys.extend((np.uint64(s) << np.uint64(61)) | low)   # owner = top 3b
    keys = np.array(keys, np.uint64)
    total = len(keys)
    assert len(np.unique(keys)) == total
    ops = np.full(N_SHARDS * LANES, -1, np.int32)
    ops[:total] = OP_INSERT
    ks = np.zeros(N_SHARDS * LANES, np.uint64)
    ks[:total] = keys

    outs = {}
    for mode in ("jnp", "interpret"):
        eng = StoreEngine(mesh, AXES, LANES, backend="hash+skiplist",
                          pool_factor=8, exec_mode=mode)
        state = jax.device_put(eng.init(512), eng.sharding)
        put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
        state, res, ok, dropped = eng.step(state, put(ops), put(ks),
                                           put(ks + 1))
        assert int(dropped) == 0
        assert np.asarray(ok)[:total].all()
        outs[mode] = (np.asarray(ok), np.asarray(res))

        # per-shard stats see the skew exactly, under the uniform schema
        stats = eng.stats(state)
        assert stats["size"].tolist() == per_shard, (mode, stats["size"])
        assert (stats["hot_size"] + stats["cold_size"]
                == stats["size"]).all(), mode
        assert (stats["tombstones"] == 0).all()

        # cross-shard range counts on the skewed state
        rstep = eng.range_step(max_out=total)
        sk = np.sort(keys)
        los = np.zeros(N_SHARDS * LANES, np.uint64)
        his = np.zeros(N_SHARDS * LANES, np.uint64)
        valid = np.zeros(N_SHARDS * LANES, bool)
        los[0], his[0], valid[0] = 0, np.uint64(2**64 - 1), True   # all
        los[1], his[1], valid[1] = sk[10], sk[50], True            # 40 keys
        los[2], his[2], valid[2] = sk[0], sk[1], True              # 1 key
        cnt = np.asarray(rstep(state, put(los), put(his), put(valid)))
        assert int(cnt[0]) == total, cnt[0]
        assert int(cnt[1]) == 40, cnt[1]
        assert int(cnt[2]) == 1, cnt[2]
    assert (outs["jnp"][0] == outs["interpret"][0]).all()
    assert (outs["jnp"][1] == outs["interpret"][1]).all()
    print(f"UNEVEN-OK per_shard={per_shard} modes=jnp,interpret")


def check_tier_residency(mesh, backend: str = "tiered3/lru") -> None:
    """Eviction determinism under sharding: after the same global op
    stream, every shard's tier residency — the FULL tier-stack state,
    including hot keys, policy metadata, warm skiplist, and spill runs —
    is bit-identical to a direct (engine-less) backend instance applying
    that shard's per-round sub-plans. Sharding is pure partitioning; the
    mesh, routing, and pooling cannot change what the policies decide.
    Run for both exec modes (the 1-device analogue lives in
    tests/test_tiers3.py)."""
    from repro.store import get_backend, make_plan
    from repro.store import exec as exec_

    total = N_SHARDS * LANES
    rng = np.random.default_rng(77)
    # per-shard key pools, owner = top 3 bits (the router's partition)
    pools = [np.unique((np.uint64(s) << np.uint64(61))
                       | rng.integers(1, 2**61, 24, dtype=np.uint64))
             for s in range(N_SHARDS)]
    rounds = []
    for _ in range(ROUNDS):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], size=total,
                         p=[0.5, 0.4, 0.1]).astype(np.int32)
        keys = np.concatenate([
            rng.choice(pools[s], LANES, replace=False)
            for s in range(N_SHARDS)])
        rng.shuffle(keys)                    # lanes hit arbitrary owners
        rounds.append((ops, keys))

    init_kw = dict(hot_bucket=4, hot_frac=8)
    for mode in ("jnp", "interpret"):
        eng = StoreEngine(mesh, AXES, LANES, backend=backend, pool_factor=8,
                          exec_mode=mode)
        state = jax.device_put(eng.init(64, **init_kw), eng.sharding)
        put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
        for ops, keys in rounds:
            state, _, _, dropped = eng.step(state, put(ops), put(keys),
                                            put(keys + 3))
            assert int(dropped) == 0, mode

        be = get_backend(backend)
        # every shard's sub-plan has LANES lanes: one compile per mode
        # (a fresh function, so the mode is read when it traces)
        step = jax.jit(lambda st, plan: be.apply(st, plan))
        for s in range(N_SHARDS):
            with exec_.exec_mode(mode):
                direct = be.init(64, **init_kw)
                for ops, keys in rounds:
                    owner = (keys >> np.uint64(61)).astype(np.int32)
                    sel = owner == s
                    direct, _ = step(direct, make_plan(
                        ops[sel], keys[sel], keys[sel] + 3))
            sharded = jax.tree.map(lambda x, s=s: x[s], state)
            la, lb = jax.tree.leaves(sharded), jax.tree.leaves(direct)
            assert len(la) == len(lb)
            for i, (a, b) in enumerate(zip(la, lb)):
                assert (np.asarray(a) == np.asarray(b)).all(), \
                    (backend, mode, s, i)
    print(f"RESIDENCY-OK backend={backend} shards={N_SHARDS} "
          f"modes=jnp,interpret")


def check_fused_vs_unfused(mesh, name: str = "tiered3/lru") -> None:
    """Fused-path determinism under sharding: an engine over the registered
    (fused — one `exec.tier_find` dispatch per probe phase) tier backend
    and an engine over an unfused `TieredBackend(fused=False)` twin must
    produce bit-identical results AND bit-identical per-shard residency
    (the full tier-stack state) for the same global op stream, in both
    exec modes. Fusing the FIND chain is a dispatch-count optimization;
    the 8-device mesh must not be able to tell the difference."""
    from repro.store.tiers import unfused_twin

    total = N_SHARDS * LANES
    rng = np.random.default_rng(99)
    pools = [np.unique((np.uint64(s) << np.uint64(61))
                       | rng.integers(1, 2**61, 24, dtype=np.uint64))
             for s in range(N_SHARDS)]
    rounds = []
    for _ in range(ROUNDS):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], size=total,
                         p=[0.5, 0.4, 0.1]).astype(np.int32)
        keys = np.concatenate([
            rng.choice(pools[s], LANES, replace=False)
            for s in range(N_SHARDS)])
        rng.shuffle(keys)
        rounds.append((ops, keys))

    init_kw = dict(hot_bucket=4, hot_frac=8)
    unfused = unfused_twin(name)
    for mode in ("jnp", "interpret"):
        states, results = [], []
        for backend in (name, unfused):
            eng = StoreEngine(mesh, AXES, LANES, backend=backend,
                              pool_factor=8, exec_mode=mode)
            state = jax.device_put(eng.init(64, **init_kw), eng.sharding)
            put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
            outs = []
            for ops, keys in rounds:
                state, res, ok, dropped = eng.step(state, put(ops),
                                                   put(keys), put(keys + 3))
                assert int(dropped) == 0, mode
                outs.append((np.asarray(ok), np.asarray(res)))
            states.append(state)
            results.append(outs)
        for rnd, ((ok_f, v_f), (ok_u, v_u)) in enumerate(zip(*results)):
            assert (ok_f == ok_u).all(), (mode, rnd)
            assert (v_f == v_u).all(), (mode, rnd)
        la, lb = jax.tree.leaves(states[0]), jax.tree.leaves(states[1])
        assert len(la) == len(lb)
        for i, (a, b) in enumerate(zip(la, lb)):
            assert (np.asarray(a) == np.asarray(b)).all(), (mode, i)
    print(f"FUSED-OK backend={name} shards={N_SHARDS} modes=jnp,interpret")


def check_fused_apply(mesh, name: str = "tiered3/lru") -> None:
    """APPLY-OK: the fused-apply budget and its eviction math survive the
    mesh. (a) Tracing one 8-device engine step over the fused tier backend
    records exactly TWO exec dispatches — one `tier_apply` update plus one
    `tier_find` probe — while the unfused twin records the
    dispatch-per-tier chain (2*n_tiers total, 2*n_tiers-1 probes);
    shard_map traces the shard body once, so the per-shard budget is
    visible at trace time. (b) An INSERT-heavy stream over a deliberately
    tiny hot tier forces the policy's victim selection and demote scatter
    through the fused kernel on every shard; the fused engine and its
    `fused=False` twin must stay bit-identical in results AND full sharded
    residency, in both exec modes, with evictions actually recorded."""
    from repro.store import exec as exec_
    from repro.store.tiers import unfused_twin

    total = N_SHARDS * LANES
    init_kw = dict(hot_bucket=2, hot_frac=8)      # tiny hot tier: 8 slots
    unfused = unfused_twin(name)
    n_tiers = 3

    # (a) trace-time dispatch budget of the sharded step, per variant
    budgets = {name: (2, 1, 1),
               unfused: (2 * n_tiers, 2 * n_tiers - 1, 1)}
    for backend, (n, npr, nup) in budgets.items():
        eng = StoreEngine(mesh, AXES, LANES, backend=backend, pool_factor=8,
                          exec_mode="jnp")
        state = jax.device_put(eng.init(64, **init_kw), eng.sharding)
        put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
        args = (state, put(np.full(total, OP_INSERT, np.int32)),
                put(np.arange(1, total + 1, dtype=np.uint64)),
                put(np.arange(2, total + 2, dtype=np.uint64)))
        with exec_.measure_dispatches() as m:
            jax.eval_shape(eng._jit_step, *args)
        assert (m.n, m.probe, m.update) == (n, npr, nup), \
            (backend, m.n, m.probe, m.update)

    # (b) eviction-heavy fused-vs-unfused bit-identity under sharding
    rng = np.random.default_rng(101)
    pools = [np.unique((np.uint64(s) << np.uint64(61))
                       | rng.integers(1, 2**61, 64, dtype=np.uint64))
             for s in range(N_SHARDS)]
    rounds = []
    for _ in range(ROUNDS):
        keys = np.concatenate([
            rng.choice(pools[s], LANES, replace=False)
            for s in range(N_SHARDS)])
        rng.shuffle(keys)
        rounds.append((np.full(total, OP_INSERT, np.int32), keys))

    for mode in ("jnp", "interpret"):
        states, results, evs = [], [], []
        for backend in (name, unfused):
            eng = StoreEngine(mesh, AXES, LANES, backend=backend,
                              pool_factor=8, exec_mode=mode)
            state = jax.device_put(eng.init(64, **init_kw), eng.sharding)
            put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
            outs = []
            for ops, keys in rounds:
                state, res, ok, dropped = eng.step(state, put(ops),
                                                   put(keys), put(keys + 3))
                assert int(dropped) == 0, mode
                outs.append((np.asarray(ok), np.asarray(res)))
            states.append(state)
            results.append(outs)
            evs.append(int(eng.stats(state)["evictions"].sum()))
        assert evs[0] > 0 and evs[0] == evs[1], (mode, evs)
        for rnd, ((ok_f, v_f), (ok_u, v_u)) in enumerate(zip(*results)):
            assert (ok_f == ok_u).all(), (mode, rnd)
            assert (v_f == v_u).all(), (mode, rnd)
        la, lb = jax.tree.leaves(states[0]), jax.tree.leaves(states[1])
        assert len(la) == len(lb)
        for i, (a, b) in enumerate(zip(la, lb)):
            assert (np.asarray(a) == np.asarray(b)).all(), (mode, i)
    print(f"APPLY-OK backend={name} shards={N_SHARDS} "
          f"evictions={evs[0]} modes=jnp,interpret")


def check_bskip(mesh) -> None:
    """BSKIP-OK: the warm tier's block-major probe layout under sharding.
    An engine over `tiered3/b128` (B-skiplist warm walk, fused) and one
    over `tiered3` (level-major walk) must produce bit-identical results
    AND bit-identical per-shard residency for the same global op stream,
    in both exec modes — the layout knob, like fusion, is invisible to
    the 8-device mesh."""
    total = N_SHARDS * LANES
    rng = np.random.default_rng(117)
    pools = [np.unique((np.uint64(s) << np.uint64(61))
                       | rng.integers(1, 2**61, 24, dtype=np.uint64))
             for s in range(N_SHARDS)]
    rounds = []
    for _ in range(ROUNDS):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], size=total,
                         p=[0.5, 0.4, 0.1]).astype(np.int32)
        keys = np.concatenate([
            rng.choice(pools[s], LANES, replace=False)
            for s in range(N_SHARDS)])
        rng.shuffle(keys)
        rounds.append((ops, keys))

    for mode in ("jnp", "interpret"):
        states, results = [], []
        for backend in ("tiered3", "tiered3/b128"):
            eng = StoreEngine(mesh, AXES, LANES, backend=backend,
                              pool_factor=8, exec_mode=mode)
            state = jax.device_put(eng.init(64, hot_bucket=4, hot_frac=8),
                                   eng.sharding)
            put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
            outs = []
            for ops, keys in rounds:
                state, res, ok, dropped = eng.step(state, put(ops),
                                                   put(keys), put(keys + 3))
                assert int(dropped) == 0, mode
                outs.append((np.asarray(ok), np.asarray(res)))
            states.append(state)
            results.append(outs)
        for rnd, ((ok_l, v_l), (ok_b, v_b)) in enumerate(zip(*results)):
            assert (ok_l == ok_b).all(), (mode, rnd)
            assert (v_l == v_b).all(), (mode, rnd)
        la, lb = jax.tree.leaves(states[0]), jax.tree.leaves(states[1])
        assert len(la) == len(lb)
        for i, (a, b) in enumerate(zip(la, lb)):
            assert (np.asarray(a) == np.asarray(b)).all(), (mode, i)
    print(f"BSKIP-OK backend=tiered3/b128 shards={N_SHARDS} "
          f"modes=jnp,interpret")


def check_metrics(mesh, backend: str = "obs:tiered3/lru") -> None:
    """METRICS-OK: the observability plane under sharding. Each shard of an
    `obs:`-wrapped engine carries its own metrics counters (on dim 0, like
    every state leaf); after the same global op stream, every shard's
    counters must be bit-identical to a direct observed instance replaying
    that shard's sub-stream — the same pure-partitioning contract as tier
    residency — and the engine-only routing counters must equal the
    explicitly computed expectation (`routed_ops` = valid lanes the shard
    owns, `routed_bytes` = 24x). Run for both exec modes, so cross-mode AND
    cross-sharding bit-identity is covered in one lane."""
    from repro.store import get_backend, make_plan
    from repro.store import exec as exec_
    from repro.store import obs

    total = N_SHARDS * LANES
    rng = np.random.default_rng(123)
    pools = [np.unique((np.uint64(s) << np.uint64(61))
                       | rng.integers(1, 2**61, 24, dtype=np.uint64))
             for s in range(N_SHARDS)]
    rounds = []
    for _ in range(ROUNDS):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], size=total,
                         p=[0.5, 0.4, 0.1]).astype(np.int32)
        keys = np.concatenate([
            rng.choice(pools[s], LANES, replace=False)
            for s in range(N_SHARDS)])
        rng.shuffle(keys)
        rounds.append((ops, keys))

    init_kw = dict(hot_bucket=4, hot_frac=8)
    pool = 8 * LANES
    ref = None
    for mode in ("jnp", "interpret"):
        eng = StoreEngine(mesh, AXES, LANES, backend=backend, pool_factor=8,
                          exec_mode=mode)
        state = jax.device_put(eng.init(64, **init_kw), eng.sharding)
        put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
        for ops, keys in rounds:
            state, _, _, dropped = eng.step(state, put(ops), put(keys),
                                            put(keys + 3))
            assert int(dropped) == 0, mode
        per_shard = eng.metrics(state)
        assert set(per_shard) == set(obs.METRICS_SCHEMA)

        be = get_backend(backend)
        # sub-plans padded to the pool: one compile per mode (a fresh
        # function, so the mode is read when it traces)
        step = jax.jit(lambda st, plan: be.apply(st, plan))
        for s in range(N_SHARDS):
            with exec_.exec_mode(mode):
                direct = be.init(64, **init_kw)
                expect_routed = 0
                for ops, keys in rounds:
                    owner = (keys >> np.uint64(61)).astype(np.int32)
                    sel = (owner == s) & (ops >= 0)
                    expect_routed += int(np.sum(sel))
                    # the shard executes its sub-stream padded to the
                    # engine's routing pool; pad lanes are masked
                    n = int(np.sum(sel))
                    p_ops = np.full(pool, -1, np.int32)
                    p_keys = np.zeros(pool, np.uint64)
                    p_ops[:n] = ops[sel]
                    p_keys[:n] = keys[sel]
                    direct, _ = step(direct, make_plan(
                        p_ops, p_keys, p_keys + 3,
                        mask=np.arange(pool) < n))
            m_dir = {k: int(v) for k, v in be.metrics(direct).items()}
            for k in obs.METRICS_SCHEMA:
                if k in ("routed_ops", "routed_bytes"):
                    continue
                assert int(per_shard[k][s]) == m_dir[k], (mode, s, k)
            assert int(per_shard["routed_ops"][s]) == expect_routed, (mode, s)
            assert (int(per_shard["routed_bytes"][s])
                    == obs.ROUTED_OP_BYTES * expect_routed), (mode, s)
        if ref is None:
            ref = {k: v.tolist() for k, v in per_shard.items()}
        else:       # cross-mode bit-identity of the whole sharded plane
            assert ref == {k: v.tolist() for k, v in per_shard.items()}, mode
    print(f"METRICS-OK backend={backend} shards={N_SHARDS} "
          f"modes=jnp,interpret")


def check_pq(mesh) -> None:
    """PQ-OK: sharded bulk-pop-k on the `pq` backend. Pop lanes carry a
    shard HINT in their key field (the per-shard relaxed-pq design), so
    each round every shard extracts its LANES smallest live keys in one
    routed plan. Per (shard, round) the popped multiset must equal the
    next block of a per-shard sorted model (POPK answers keys, POPMIN the
    stored values), the store must drain to empty with exact pops /
    pop_empty counters per shard, and the whole run must be bit-identical
    across exec modes."""
    total = N_SHARDS * LANES
    rng = np.random.default_rng(31)
    per_shard = [2 * s + 3 for s in range(N_SHARDS)]          # uneven: 3..17
    shard_keys = []
    for s, n in enumerate(per_shard):
        low = np.unique(rng.integers(1, 2**61, 2 * n, dtype=np.uint64))[:n]
        shard_keys.append(((np.uint64(s) << np.uint64(61)) | low))
    keys = np.zeros(total, np.uint64)
    flat = np.concatenate(shard_keys)
    keys[:len(flat)] = flat
    ins = np.full(total, -1, np.int32)
    ins[:len(flat)] = OP_INSERT
    hints = (np.arange(total, dtype=np.uint64) % N_SHARDS) << np.uint64(61)

    outs_by_mode = {}
    for mode in ("jnp", "interpret"):
        eng = StoreEngine(mesh, AXES, LANES, backend="pq", pool_factor=4,
                          exec_mode=mode)
        state = jax.device_put(eng.init(512), eng.sharding)
        put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
        state, _, ok, dropped = eng.step(state, put(ins), put(keys),
                                         put(keys + 1))
        assert np.asarray(ok)[:len(flat)].all() and int(dropped) == 0, mode

        model = [sorted(int(k) for k in sk) for sk in shard_keys]
        expect_pops = np.zeros(N_SHARDS, np.int64)
        expect_empty = np.zeros(N_SHARDS, np.int64)
        rnd, outs = 0, []
        while True:
            op = OP_POPK if rnd % 2 == 0 else OP_POPMIN
            state, res, ok, _ = eng.step(
                state, put(np.full(total, op, np.int32)), put(hints),
                put(np.zeros(total, np.uint64)))
            ok, res = np.asarray(ok), np.asarray(res)
            outs.append((ok.copy(), res.copy()))
            for s in range(N_SHARDS):
                lanes = (np.arange(total) % N_SHARDS == s) & ok
                got = sorted(int(v) for v in res[lanes])
                if op == OP_POPMIN:                 # value = key + 1
                    got = [v - 1 for v in got]
                k = min(LANES, len(model[s]))
                assert got == model[s][:k], (mode, rnd, s)
                model[s] = model[s][k:]
                expect_pops[s] += k
                expect_empty[s] += LANES - k
            rnd += 1
            if not ok.any():
                break
        stats = eng.stats(state)
        assert int(stats["size"].sum()) == 0, mode   # drained dry
        assert stats["pops"].tolist() == expect_pops.tolist(), mode
        assert stats["pop_empty"].tolist() == expect_empty.tolist(), mode
        outs_by_mode[mode] = (outs, jax.tree.leaves(state))
    (oa, sa), (ob, sb) = outs_by_mode["jnp"], outs_by_mode["interpret"]
    for (ok_a, v_a), (ok_b, v_b) in zip(oa, ob):
        assert (ok_a == ok_b).all() and (v_a == v_b).all()
    for a, b in zip(sa, sb):
        assert (np.asarray(a) == np.asarray(b)).all()
    print(f"PQ-OK backend=pq shards={N_SHARDS} per_shard={per_shard} "
          f"modes=jnp,interpret")


def check_recover(mesh, backend: str = "obs:tiered3/lru") -> None:
    """RECOVER-OK: the resilience layer on the 8-device mesh.

    (a) snapshot + journal `restore` onto a FRESH engine reproduces the
    fault-free run's state digest and full per-shard metrics plane;
    (b) a mid-trace shard drop (shard 3, step 3) recovered in sync mode
    leaves every round's results AND the final state/metrics digests
    bit-identical to the fault-free run;
    (c) degraded mode: healthy shards keep serving (their lanes match the
    fault-free run at every step) while the dead shard rebuilds one journal
    entry per tick; the deferred lanes' true answers land in `completions`
    equal to the fault-free answers, and a post-run FIND sweep over every
    key agrees between the two runs."""
    from repro.store import resilience as R

    total = N_SHARDS * LANES
    n_rounds = 6
    rng = np.random.default_rng(211)
    pools = [np.unique((np.uint64(s) << np.uint64(61))
                       | rng.integers(1, 2**61, 24, dtype=np.uint64))
             for s in range(N_SHARDS)]
    rounds = []
    for _ in range(n_rounds):
        ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], size=total,
                         p=[0.4, 0.5, 0.1]).astype(np.int32)
        keys = np.concatenate([
            rng.choice(pools[s], LANES, replace=False)
            for s in range(N_SHARDS)])
        rng.shuffle(keys)
        rounds.append((ops, keys))

    init_kw = dict(hot_bucket=4, hot_frac=8)

    def fresh():
        eng = StoreEngine(mesh, AXES, LANES, backend=backend, pool_factor=8)
        state = jax.device_put(eng.init(64, **init_kw), eng.sharding)
        return eng, state

    put = lambda eng, x: jax.device_put(jnp.asarray(x), eng.sharding)

    # fault-free reference run
    eng0, state0 = fresh()
    ff_outs = []
    for ops, keys in rounds:
        state0, res, ok, dropped = eng0.step(state0, put(eng0, ops),
                                             put(eng0, keys),
                                             put(eng0, keys + 3))
        assert int(dropped) == 0
        ff_outs.append((np.asarray(ok).copy(), np.asarray(res).copy()))
    ff_digest = R.state_digest(state0)
    ff_metrics = {k: v.tolist() for k, v in eng0.metrics(state0).items()}

    # (a) full restore onto a fresh engine: 8-device snapshot + journal
    eng1, state1 = fresh()
    snap = R.take_snapshot(state1, 0)
    j = R.Journal(base_seq=0)
    for r, (ops, keys) in enumerate(rounds):
        j.append(r, ops, keys, keys + 3)
    assert j.verify()
    eng2, _ = fresh()
    restored, replayed = R.restore(eng2, snap, j.entries)
    assert replayed == sum(e.n_ops for e in j.entries)
    assert R.state_digest(restored) == ff_digest
    assert {k: v.tolist()
            for k, v in eng2.metrics(restored).items()} == ff_metrics

    # (b) mid-trace shard drop, sync recovery: bit-identical throughout
    eng3, state3 = fresh()
    reng = R.ResilientEngine(
        eng3, snapshot_every=2,
        fault_plan=R.FaultPlan(0, [R.Fault("shard_drop", 3, shard=3)]))
    for r, (ops, keys) in enumerate(rounds):
        state3, res, ok, dropped = reng.step(state3, put(eng3, ops),
                                             put(eng3, keys),
                                             put(eng3, keys + 3))
        assert int(dropped) == 0
        ok_f, v_f = ff_outs[r]
        assert (np.asarray(ok) == ok_f).all(), ("sync", r)
        assert (np.asarray(res) == v_f).all(), ("sync", r)
    assert R.state_digest(state3) == ff_digest
    assert {k: v.tolist()
            for k, v in eng3.metrics(state3).items()} == ff_metrics
    assert reng.tally["faults_injected"] == 1
    assert reng.tally["recoveries"] == 1
    assert reng.tally["replayed_ops"] > 0
    assert reng.journal.verify()

    # (c) degraded mode: drop shard 3 at step 3 with the last snapshot at
    # seq 0 and a one-entry-per-tick replay budget -> the rebuild spans
    # steps 3..5 while the healthy shards keep serving
    eng4, state4 = fresh()
    reng4 = R.ResilientEngine(
        eng4, snapshot_every=4, mode="degraded", replay_per_tick=1,
        fault_plan=R.FaultPlan(0, [R.Fault("shard_drop", 3, shard=3)]))
    owner_all = []
    for r, (ops, keys) in enumerate(rounds):
        owner = (keys >> np.uint64(61)).astype(np.int32)
        owner_all.append(owner)
        state4, res, ok, _ = reng4.step(state4, put(eng4, ops),
                                        put(eng4, keys), put(eng4, keys + 3))
        ok_h, v_h = np.asarray(ok), np.asarray(res)
        ok_f, v_f = ff_outs[r]
        deferred = (owner == 3) & (ops >= 0) if r >= 3 else \
            np.zeros(total, bool)
        live = ~deferred
        assert (ok_h[live] == ok_f[live]).all(), ("degraded", r)
        assert (v_h[live] == v_f[live]).all(), ("degraded", r)
        assert not ok_h[deferred].any(), ("degraded", r)   # visibly deferred
    assert reng4.quarantine is None                        # rebuild done
    assert reng4.tally["recoveries"] == 1
    # every deferred lane completed with the fault-free answer
    n_def = 0
    for (seq, lane), (cok, cval) in reng4.completions.items():
        ok_f, v_f = ff_outs[seq]
        assert cok == bool(ok_f[lane]), ("completion", seq, lane)
        assert cval == int(v_f[lane]), ("completion", seq, lane)
        n_def += 1
    assert n_def == sum(int(((o == 3) & (rounds[r][0] >= 0)).sum())
                        for r, o in enumerate(owner_all) if r >= 3)
    # content sweep: FIND every pool key on both final states
    for s in range(N_SHARDS):
        for chunk in np.array_split(pools[s], max(1, len(pools[s]) // LANES)):
            probe = np.zeros(total, np.uint64)
            probe[:len(chunk)] = chunk
            fops = np.full(total, -1, np.int32)
            fops[:len(chunk)] = OP_FIND
            _, v_a, ok_a, _ = eng0.step(state0, put(eng0, fops),
                                        put(eng0, probe),
                                        put(eng0, np.zeros(total, np.uint64)))
            _, v_b, ok_b, _ = eng4.step(state4, put(eng4, fops),
                                        put(eng4, probe),
                                        put(eng4, np.zeros(total, np.uint64)))
            assert (np.asarray(ok_a) == np.asarray(ok_b)).all(), ("sweep", s)
            m = np.asarray(ok_a)
            assert (np.asarray(v_a)[m] == np.asarray(v_b)[m]).all(), \
                ("sweep", s)
    print(f"RECOVER-OK backend={backend} shards={N_SHARDS} "
          f"sync_digest=match degraded_completions={n_def}")


def check_store(mesh) -> None:
    for backend in BACKENDS:
        check_backend(mesh, backend)
    for backend in ("det_skiplist", "hash+skiplist"):
        check_range(mesh, backend)


# groups of checks of a few minutes each: the tests launch each group as
# its own process, so that pytest-xdist runs them on separate workers
GROUPS = {
    "store": (check_store, check_uneven_occupancy, check_pq),
    "residency": (check_tier_residency, check_bskip, check_fused_vs_unfused,
                  check_fused_apply),
    "metrics": (check_metrics, check_recover),
}


def main(groups) -> int:
    """Run the named groups of checks, every group when none is named."""
    mesh = make_mesh((2, 4), AXES)
    for group in groups or GROUPS:
        for check in GROUPS[group]:
            check(mesh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
