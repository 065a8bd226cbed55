"""Fault-tolerance layer tests: journal digest chain, crash-point restore,
seeded fault plans, and ResilientEngine recovery equality.

The headline contracts (docs/resilience.md):

* snapshot + journal replay reproduces the EXACT state digest (and metrics
  plane) of the uninterrupted run — killed after every batch, in every exec
  mode;
* a mid-stream shard drop recovered in sync mode leaves results, state
  digest, and metrics digest bit-identical to a fault-free run;
* degraded mode keeps healthy lanes serving and lands the deferred lanes'
  true results in `completions`, equal to the fault-free answers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.launch.mesh import make_mesh
from repro.store import engine as engine_mod
from repro.store import obs
from repro.store import resilience as R
from repro.store.api import OP_DELETE, OP_FIND, OP_INSERT, OP_NONE

BACKEND = "obs:det_skiplist"
LANES = 8
CAP = 256


def _mk_engine(exec_mode=None, lanes=LANES):
    """A FRESH 1-shard engine (not the lru-cached local engine: these tests
    own the host `seq` counter)."""
    mesh = make_mesh((1,), ("local",), devices=jax.devices()[:1])
    return engine_mod.StoreEngine(mesh, ("local",), lanes, backend=BACKEND,
                                  pool_factor=1, exec_mode=exec_mode)


def _stream(seed, n_steps, lanes=LANES):
    """Deterministic mixed op stream: inserts dominate early, finds and
    deletes of previously inserted keys later."""
    rng = np.random.default_rng(seed)
    inserted = []
    out = []
    for t in range(n_steps):
        ops = np.full(lanes, OP_NONE, np.int32)
        keys = np.zeros(lanes, np.uint64)
        vals = np.zeros(lanes, np.uint64)
        for i in range(lanes):
            r = rng.random()
            if r < 0.55 or not inserted:
                k = np.uint64(rng.integers(1, 1 << 32))
                ops[i], keys[i], vals[i] = OP_INSERT, k, np.uint64(t * 100 + i)
                inserted.append(k)
            elif r < 0.85:
                ops[i] = OP_FIND
                keys[i] = inserted[rng.integers(len(inserted))]
            else:
                ops[i] = OP_DELETE
                keys[i] = inserted[rng.integers(len(inserted))]
        out.append((ops, keys, vals))
    return out, inserted


def _run(eng, state, plans):
    """Apply plans, returning per-step (results, ok) host copies."""
    outs = []
    for ops, keys, vals in plans:
        state, res, ok, _ = eng.step(state, jnp.asarray(ops),
                                     jnp.asarray(keys), jnp.asarray(vals))
        outs.append((np.asarray(res).copy(), np.asarray(ok).copy()))
    return state, outs


class TestJournal:
    def test_chain_append_verify_tail(self):
        plans, _ = _stream(0, 4)
        j = R.Journal(base_seq=0)
        heads = [j.head_digest]
        for s, (ops, keys, vals) in enumerate(plans):
            j.append(s, ops, keys, vals)
            heads.append(j.head_digest)
        assert len(set(heads)) == 5          # every link moves the head
        assert heads[0] == R.GENESIS
        assert j.verify()
        assert len(j.tail(2)) == 2 and j.tail(2)[0].seq == 2
        assert j.next_seq == 4

    def test_seq_gap_rejected(self):
        plans, _ = _stream(1, 2)
        j = R.Journal(base_seq=0)
        j.append(0, *plans[0])
        with pytest.raises(ValueError, match="gap-free"):
            j.append(2, *plans[1])

    def test_tamper_detected(self):
        plans, _ = _stream(2, 3)
        j = R.Journal(base_seq=0)
        for s, p in enumerate(plans):
            j.append(s, *p)
        bad = j.entries[1].ops.copy()
        bad[0] = OP_NONE if bad[0] != OP_NONE else OP_FIND
        j.entries[1] = j.entries[1]._replace(ops=bad)
        with pytest.raises(ValueError, match="chain broken at entry 1"):
            j.verify()

    def test_snapshot_roundtrip_digest(self):
        eng = _mk_engine()
        state = jax.device_put(eng.init(CAP), eng.sharding)
        plans, _ = _stream(3, 2)
        state, _ = _run(eng, state, plans)
        snap = R.take_snapshot(state, eng.seq)
        back = R.snapshot_state(snap, eng.sharding)
        assert R.state_digest(back) == R.state_digest(state) == snap.digest

    def test_state_digest_moves_with_state(self):
        eng = _mk_engine()
        state = jax.device_put(eng.init(CAP), eng.sharding)
        d0 = R.state_digest(state)
        plans, _ = _stream(4, 1)
        state, _ = _run(eng, state, plans)
        assert R.state_digest(state) != d0


class TestRestoreCrashPoints:
    """Kill the run after every batch; snapshot + journal tail must rebuild
    the exact digest the uninterrupted run had at that point."""

    N = 6

    @pytest.fixture(scope="class")
    def baseline(self):
        plans, _ = _stream(10, self.N)
        eng = _mk_engine()
        state = jax.device_put(eng.init(CAP), eng.sharding)
        snap = R.take_snapshot(state, 0)
        j = R.Journal(base_seq=0)
        digests, metrics = [], []
        for s, (ops, keys, vals) in enumerate(plans):
            j.append(s, ops, keys, vals)
            state, _, _, _ = eng.step(state, jnp.asarray(ops),
                                      jnp.asarray(keys), jnp.asarray(vals))
            digests.append(R.state_digest(state))
            metrics.append({k: v.copy() for k, v in
                            eng.metrics(state).items()})
        assert j.verify()
        return snap, j, digests, metrics

    @pytest.mark.parametrize("crash_after", range(1, N + 1))
    def test_restore_at_every_crash_point(self, baseline, crash_after):
        snap, j, digests, metrics = baseline
        eng = _mk_engine()
        state, replayed = R.restore(eng, snap, j.entries[:crash_after])
        assert R.state_digest(state) == digests[crash_after - 1]
        assert eng.seq == crash_after
        assert replayed == sum(e.n_ops for e in j.entries[:crash_after])
        got = eng.metrics(state)
        want = metrics[crash_after - 1]
        assert set(got) == set(want)
        for k in want:
            assert (got[k] == want[k]).all(), k

    @pytest.mark.parametrize("mode", ["jnp", "interpret"])
    def test_restore_exec_mode_parity(self, baseline, mode):
        """Replaying the journal under a DIFFERENT exec mode lands on the
        same digest — recovery inherits the exec-mode parity contract."""
        snap, j, digests, _ = baseline
        eng = _mk_engine(exec_mode=mode)
        state, _ = R.restore(eng, snap, j.entries)
        assert R.state_digest(state) == digests[-1]

    def test_restore_rejects_misaligned_tail(self, baseline):
        snap, j, _, _ = baseline
        eng = _mk_engine()
        with pytest.raises(ValueError, match="replay expects seq"):
            R.restore(eng, snap, j.entries[1:])


class TestFaultPlan:
    def test_seed_determinism(self):
        a = R.make_fault_plan(7, 10, 4, LANES, n_faults=5)
        b = R.make_fault_plan(7, 10, 4, LANES, n_faults=5)
        assert a.faults == b.faults
        c = R.make_fault_plan(8, 10, 4, LANES, n_faults=5)
        assert a.faults != c.faults

    def test_step_zero_is_fault_free(self):
        p = R.make_fault_plan(0, 5, 2, LANES, n_faults=16)
        assert p.at(0) == []
        assert all(1 <= f.step < 5 for f in p.faults)

    def test_at_groups_by_step(self):
        p = R.FaultPlan(0, [R.Fault("stall", 2, ticks=1),
                            R.Fault("poison", 2, lane=0),
                            R.Fault("shard_drop", 3, shard=1)])
        assert len(p.at(2)) == 2 and len(p.at(3)) == 1 and p.at(1) == []

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown fault kinds"):
            R.make_fault_plan(0, 5, 2, LANES, kinds=("meteor",))
        with pytest.raises(ValueError, match="n_steps"):
            R.make_fault_plan(0, 1, 2, LANES)

    def test_default_seed_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_FAULTS", "42")
        assert R.default_seed(7) == 42
        monkeypatch.delenv("REPRO_FAULTS")
        assert R.default_seed(7) == 7


class TestFaultPrimitives:
    def test_poison_and_sanitize(self):
        ops = np.asarray([OP_INSERT, OP_FIND, OP_NONE, OP_DELETE], np.int32)
        wired = R.poison_ops(jnp.asarray(ops), 1)
        clean, poisoned = R.sanitize_ops(wired)
        assert poisoned.tolist() == [False, True, False, False]
        assert clean.tolist() == [OP_INSERT, OP_NONE, OP_NONE, OP_DELETE]
        # a clean plan sanitizes to itself
        clean2, poisoned2 = R.sanitize_ops(jnp.asarray(ops))
        assert not poisoned2.any() and (clean2 == ops).all()

    def test_shard_drop_kills_liveness(self):
        state = engine_mod.sharded_init(BACKEND, 2, CAP)
        assert R.state_alive(state, 2).tolist() == [True, True]
        dropped = R.inject_shard_drop(state, 1)
        assert R.state_alive(dropped, 2).tolist() == [True, False]
        # the healthy slice is untouched, bit for bit
        a = jax.tree.leaves(state)
        b = jax.tree.leaves(dropped)
        assert all((np.asarray(x[0]) == np.asarray(y[0])).all()
                   for x, y in zip(a, b))


def _fault_free_twin(plans):
    eng = _mk_engine()
    state = jax.device_put(eng.init(CAP), eng.sharding)
    state, outs = _run(eng, state, plans)
    return eng, state, outs


class TestResilientEngineSync:
    def test_shard_drop_recovers_bit_identical(self):
        plans, _ = _stream(20, 6)
        ref_eng, ref_state, ref_outs = _fault_free_twin(plans)

        eng = _mk_engine()
        plan = R.FaultPlan(0, [R.Fault("shard_drop", 3, shard=0)])
        reng = R.ResilientEngine(eng, snapshot_every=2, fault_plan=plan)
        state = jax.device_put(eng.init(CAP), eng.sharding)
        outs = []
        for ops, keys, vals in plans:
            state, res, ok, _ = reng.step(state, jnp.asarray(ops),
                                          jnp.asarray(keys),
                                          jnp.asarray(vals))
            outs.append((np.asarray(res).copy(), np.asarray(ok).copy()))

        for t, ((rv, rok), (fv, fok)) in enumerate(zip(outs, ref_outs)):
            assert (rv == fv).all() and (rok == fok).all(), f"step {t}"
        assert R.state_digest(state) == R.state_digest(ref_state)
        assert reng.metrics(state) == obs.merge_resilience(
            {k: int(np.sum(v)) for k, v in ref_eng.metrics(ref_state).items()},
            reng.tally)
        assert reng.tally["faults_injected"] == 1
        assert reng.tally["recoveries"] == 1
        assert reng.tally["replayed_ops"] > 0
        assert reng.journal.verify()
        assert reng.stats(state)["seq"] == len(plans)

    def test_seeded_plan_all_kinds_still_equal(self):
        """A REPRO_FAULTS-style seeded plan with every fault kind: results
        and final digest still equal the fault-free run (the CI chaos
        lane's contract, at unit scale)."""
        plans, _ = _stream(21, 8)
        _, ref_state, ref_outs = _fault_free_twin(plans)

        eng = _mk_engine()
        fplan = R.make_fault_plan(R.default_seed(3), len(plans), 1, LANES,
                                  n_faults=4)
        reng = R.ResilientEngine(eng, snapshot_every=2, fault_plan=fplan)
        state = jax.device_put(eng.init(CAP), eng.sharding)
        outs = []
        for ops, keys, vals in plans:
            state, res, ok, _ = reng.step(state, jnp.asarray(ops),
                                          jnp.asarray(keys),
                                          jnp.asarray(vals))
            outs.append((np.asarray(res).copy(), np.asarray(ok).copy()))
        for t, ((rv, rok), (fv, fok)) in enumerate(zip(outs, ref_outs)):
            assert (rv == fv).all() and (rok == fok).all(), f"step {t}"
        assert R.state_digest(state) == R.state_digest(ref_state)
        assert reng.tally["faults_injected"] == 4

    def test_poison_repaired_from_journaled_intent(self):
        plans, _ = _stream(22, 4)
        _, ref_state, ref_outs = _fault_free_twin(plans)

        eng = _mk_engine()
        plan = R.FaultPlan(0, [R.Fault("poison", 2, lane=3)])
        reng = R.ResilientEngine(eng, snapshot_every=4, fault_plan=plan)
        state = jax.device_put(eng.init(CAP), eng.sharding)
        state, outs = _run(reng, state, plans)
        for (rv, rok), (fv, fok) in zip(outs, ref_outs):
            assert (rv == fv).all() and (rok == fok).all()
        assert R.state_digest(state) == R.state_digest(ref_state)
        assert reng.tally["retries"] == 1
        assert reng.tally["recoveries"] == 0

    def test_stall_is_pure_latency(self):
        plans, _ = _stream(23, 4)
        _, ref_state, _ = _fault_free_twin(plans)
        eng = _mk_engine()
        plan = R.FaultPlan(0, [R.Fault("stall", 1, ticks=3),
                               R.Fault("stall", 2, ticks=2)])
        reng = R.ResilientEngine(eng, snapshot_every=4, fault_plan=plan)
        state = jax.device_put(eng.init(CAP), eng.sharding)
        state, _ = _run(reng, state, plans)
        assert R.state_digest(state) == R.state_digest(ref_state)
        assert reng.stall_ticks == 5
        assert reng.virtual_ticks == len(plans) + 5

    def test_metrics_view_is_schema_exact(self):
        plans, _ = _stream(24, 2)
        eng = _mk_engine()
        reng = R.ResilientEngine(eng, snapshot_every=2)
        state = jax.device_put(eng.init(CAP), eng.sharding)
        state, _ = _run(reng, state, plans)
        m = reng.metrics(state)
        assert set(m) == set(obs.METRICS_SCHEMA)
        assert all(m[k] == 0 for k in obs.RESILIENCE_SCHEMA)


class TestResilientEngineDegraded:
    def test_deferred_lanes_complete_with_fault_free_results(self):
        plans, _ = _stream(30, 6)
        _, ref_state, ref_outs = _fault_free_twin(plans)

        eng = _mk_engine()
        drop_at = 3
        plan = R.FaultPlan(0, [R.Fault("shard_drop", drop_at, shard=0)])
        # replay budget covers the whole tail at once: the rebuild and the
        # deferred catch-up complete inside the detecting step
        reng = R.ResilientEngine(eng, snapshot_every=2, fault_plan=plan,
                                 mode="degraded", replay_per_tick=64)
        state = jax.device_put(eng.init(CAP), eng.sharding)
        outs = []
        for ops, keys, vals in plans:
            state, res, ok, _ = reng.step(state, jnp.asarray(ops),
                                          jnp.asarray(keys),
                                          jnp.asarray(vals))
            outs.append((np.asarray(res).copy(), np.asarray(ok).copy()))

        # the detecting step deferred its (1-shard: ALL) lanes — callers saw
        # ok=False there; the true answers landed in completions and equal
        # the fault-free run's
        ops3 = plans[drop_at][0]
        fv, fok = ref_outs[drop_at]
        deferred = [(s, l) for (s, l) in reng.completions if s == drop_at]
        assert len(deferred) == int(np.sum(ops3 >= 0))
        for (s, lane), (cok, cval) in reng.completions.items():
            assert cok == bool(fok[lane]) and cval == int(fv[lane]), (s, lane)
        # non-faulted steps never diverged
        for t in range(len(plans)):
            if t == drop_at:
                continue
            rv, rok = outs[t]
            fvt, fokt = ref_outs[t]
            assert (rv == fvt).all() and (rok == fokt).all(), f"step {t}"
        assert reng.tally["recoveries"] == 1
        assert reng.quarantine is None

        # content equality (NOT digest: batch clocks shifted): probe every
        # key both runs touched and compare answers
        _, allkeys = _stream(30, 6)
        probe = np.asarray(allkeys[:LANES * 4], np.uint64)
        probe = np.pad(probe, (0, (-len(probe)) % LANES))
        ref_probe_eng, ref_probe_state, _ = _fault_free_twin(plans)
        for chunk in probe.reshape(-1, LANES):
            ops = np.where(chunk > 0, OP_FIND, OP_NONE).astype(np.int32)
            z = np.zeros(LANES, np.uint64)
            _, rv, rok, _ = reng.eng.step(state, jnp.asarray(ops),
                                          jnp.asarray(chunk), jnp.asarray(z))
            _, fv2, fok2, _ = ref_probe_eng.step(
                ref_probe_state, jnp.asarray(ops), jnp.asarray(chunk),
                jnp.asarray(z))
            assert (np.asarray(rok) == np.asarray(fok2)).all()
            okm = np.asarray(rok)
            assert (np.asarray(rv)[okm] == np.asarray(fv2)[okm]).all()
