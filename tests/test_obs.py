"""Observability-layer tests (`repro.store.obs` + friends).

The load-bearing contract: the `metrics()` pytree is DETERMINISTIC the same
way results are — bit-identical across every runnable exec mode
(jnp | interpret | pallas), across the fused and unfused tier probe paths,
and across shardings (the 1-device engine here; 8 shards in
tests/multidev/store_prog.py's METRICS-OK lane). Plus: the counters are
CORRECT on hand-built plans, the plane jits and carries across steps, the
exec dispatch meters are context-local and nestable, device phases name
the lowered step, host spans reach the profiler's trace and export as
Chrome-trace JSON, and `tools/bench_diff.py --assert-within` gates
regressions with the right exit codes.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.store import METRICS_SCHEMA, get_backend, make_plan
from repro.store import exec as exec_
from repro.store import obs
from repro.store.api import OP_DELETE, OP_FIND, OP_INSERT
from repro.store.tiers import unfused_twin

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OBS_BACKENDS = ("obs:fixed_hash", "obs:det_skiplist", "obs:hash+skiplist",
                "obs:tiered3", "obs:tiered3/lru", "obs:tiered3/size")


def churn_plans(n_plans=5, width=16, key_lo=1, key_hi=48, seed=0):
    rng = np.random.default_rng(seed)
    plans = []
    for _ in range(n_plans):
        ops = rng.integers(0, 3, width).astype(np.int32)
        keys = rng.integers(key_lo, key_hi, width).astype(np.uint64)
        vals = rng.integers(1, 1 << 20, width).astype(np.uint64)
        plans.append(make_plan(ops, keys, vals))
    return plans


def apply_stream(be, plans, mode, capacity=64, jit=False, **init_kw):
    st = be.init(capacity, **init_kw)
    with exec_.exec_mode(mode):
        step = jax.jit(be.apply) if jit else be.apply
        for p in plans:
            st, _ = step(st, p)
    return st


def as_ints(metrics):
    return {k: int(v) for k, v in metrics.items()}


# ---------------------------------------------------------------------------
# metrics plane: schema, correctness, jit carry
# ---------------------------------------------------------------------------

class TestMetricsPlane:
    def test_schema_complete_and_zeroed(self):
        be = get_backend("obs:fixed_hash")
        st = be.init(64)
        m = be.metrics(st)
        assert set(m) == set(METRICS_SCHEMA)
        assert all(int(v) == 0 for v in m.values())
        assert all(v.dtype == jnp.int64 for v in m.values())

    def test_unknown_metric_rejected(self):
        with obs.collect() as frame:
            with pytest.raises(ValueError, match="unknown metric"):
                frame.add("not_a_metric", 1)

    def test_record_noop_without_frame(self):
        # the thunk must NOT be evaluated when no frame is active — this is
        # the observability-off-costs-nothing contract
        evaluated = []
        obs.record("find_hits", lambda: evaluated.append(1) or 1)
        assert not evaluated
        with obs.collect() as frame:
            obs.record("find_hits", lambda: evaluated.append(1) or 1)
        assert evaluated and int(frame.acc["find_hits"]) == 1

    def test_innermost_frame_wins(self):
        with obs.collect() as outer:
            with obs.collect() as inner:
                obs.record("ops_find", 3)
            obs.record("ops_find", 2)
        assert int(inner.acc["ops_find"]) == 3
        assert int(outer.acc["ops_find"]) == 2

    def test_hand_built_plan_counters(self):
        be = get_backend("obs:tiered3/lru")
        st = be.init(64, hot_bucket=4, hot_frac=8)
        # insert 1, 2, 3 (new); re-insert 2 (existing); delete 3; find
        # 1 (hit), 2 (hit), 99 (miss)
        st, _ = be.apply(st, make_plan(
            [OP_INSERT] * 3, [1, 2, 3], [10, 20, 30]))
        st, _ = be.apply(st, make_plan(
            [OP_INSERT, OP_DELETE], [2, 3], [99, 0]))
        st, res = be.apply(st, make_plan(
            [OP_FIND] * 3, [1, 2, 99]))
        m = as_ints(be.metrics(st))
        assert m["ops_insert"] == 4 and m["ops_delete"] == 1
        assert m["ops_find"] == 3
        assert m["inserts_new"] == 3 and m["inserts_existing"] == 1
        assert m["deletes_hit"] == 1
        assert m["find_hits"] == 2 and m["find_misses"] == 1
        # all three finds answered hot (fresh small inserts stay hot)
        assert m["hot_hits"] + m["warm_hits"] + m["spill_hits"] == 2
        assert np.array_equal(np.asarray(res.ok), [True, True, False])

    def test_plan_counters_respect_mask_and_none(self):
        be = get_backend("obs:det_skiplist")
        st = be.init(64)
        plan = make_plan([OP_INSERT, OP_INSERT, -1, OP_FIND],
                         [5, 6, 7, 5], [1, 2, 3, 0],
                         mask=[True, False, True, True])
        st, _ = be.apply(st, plan)
        m = as_ints(be.metrics(st))
        assert m["ops_insert"] == 1          # masked + OP_NONE lanes ignored
        assert m["ops_find"] == 1 and m["find_hits"] == 1

    def test_metrics_jit_carry(self):
        be = get_backend("obs:tiered3/lru")
        plans = churn_plans()
        st_e = apply_stream(be, plans, "jnp", jit=False,
                            hot_bucket=4, hot_frac=8)
        st_j = apply_stream(be, plans, "jnp", jit=True,
                            hot_bucket=4, hot_frac=8)
        assert as_ints(be.metrics(st_e)) == as_ints(be.metrics(st_j))
        assert any(v for v in as_ints(be.metrics(st_j)).values())

    def test_movement_counters_match_stats(self):
        # the metrics plane's eviction/promotion counts must agree with the
        # tier state's own cumulative counters
        be = get_backend("obs:tiered3/lru")
        st = be.init(64, hot_bucket=4, hot_frac=8)
        for p in churn_plans(n_plans=8):
            st, _ = be.apply(st, p)
        m = as_ints(be.metrics(st))
        stats = {k: int(v) for k, v in be.stats(st).items()}
        assert m["evictions"] == stats["evictions"]
        assert m["promotions"] == stats["promotions"]

    def test_flush_records_demotions(self):
        be = get_backend("obs:tiered3/lru")
        st = be.init(64, hot_bucket=4, hot_frac=8)
        st, _ = be.apply(st, make_plan([OP_INSERT] * 4, [1, 2, 3, 4],
                                       [1, 2, 3, 4]))
        before = as_ints(be.metrics(st))["demotions"]
        st = be.flush(st)
        after = as_ints(be.metrics(st))["demotions"]
        assert after > before
        assert int(be.stats(st)["hot_size"]) == 0


# ---------------------------------------------------------------------------
# determinism: exec modes, fused vs unfused, engine vs direct replay
# ---------------------------------------------------------------------------

class TestMetricsParity:
    @pytest.mark.parametrize("name", OBS_BACKENDS)
    def test_bit_identical_across_exec_modes(self, name):
        be = get_backend(name)
        plans = churn_plans()
        kw = (dict(hot_bucket=4, hot_frac=8)
              if name.startswith("obs:tiered3")
              or name == "obs:hash+skiplist" else {})
        ref = None
        for mode in exec_.runnable_modes():
            st = apply_stream(be, plans, mode, jit=True, **kw)
            m = as_ints(be.metrics(st))
            if ref is None:
                ref = m
            else:
                assert m == ref, f"{name} metrics diverge in mode {mode}"

    @pytest.mark.parametrize("name", ["tiered3", "tiered3/lru",
                                      "tiered3/size"])
    def test_fused_vs_unfused_identical(self, name):
        plans = churn_plans()
        kw = dict(hot_bucket=4, hot_frac=8)
        fused = get_backend(f"obs:{name}")
        unf = obs.ObservedStore(unfused_twin(name))
        mf = as_ints(fused.metrics(apply_stream(fused, plans, "jnp",
                                                jit=True, **kw)))
        mu = as_ints(unf.metrics(apply_stream(unf, plans, "jnp", jit=True,
                                              **kw)))
        assert mf == mu

    def test_engine_matches_direct_replay(self):
        # METRICS-OK, 1-device form (8-shard form in multidev/store_prog.py):
        # the engine-carried plane == a direct observed instance replaying
        # the same stream, plus exact routed_ops/routed_bytes
        from repro.launch.mesh import make_mesh
        from repro.store.engine import StoreEngine

        lanes, steps = 16, 5
        mesh = make_mesh((1,), ("d",), devices=jax.devices()[:1])
        eng = StoreEngine(mesh, ("d",), lanes=lanes,
                          backend="obs:tiered3/lru")
        state = jax.device_put(eng.init(64, hot_bucket=4, hot_frac=8),
                               eng.sharding)
        be = get_backend("obs:tiered3/lru")
        st_direct = be.init(64, hot_bucket=4, hot_frac=8)

        rng = np.random.default_rng(3)
        total_valid = 0
        for _ in range(steps):
            ops = rng.integers(0, 3, lanes).astype(np.int32)
            keys = rng.integers(1, 48, lanes).astype(np.uint64)
            vals = rng.integers(1, 1 << 20, lanes).astype(np.uint64)
            state, _, _, dropped = eng.step(state, jnp.asarray(ops),
                                            jnp.asarray(keys),
                                            jnp.asarray(vals))
            assert int(dropped) == 0
            total_valid += int(np.sum(ops >= 0))
            # single shard: the routed plan is the original plan padded to
            # the engine pool; pad lanes are masked, so metrics match the
            # unpadded direct apply
            pool = 2 * lanes
            p_ops = np.full(pool, -1, np.int32)
            p_keys = np.zeros(pool, np.uint64)
            p_vals = np.zeros(pool, np.uint64)
            p_ops[:lanes], p_keys[:lanes], p_vals[:lanes] = ops, keys, vals
            st_direct, _ = be.apply(st_direct, make_plan(
                p_ops, p_keys, p_vals,
                mask=np.arange(pool) < lanes))

        m_eng = {k: int(v[0]) for k, v in eng.metrics(state).items()}
        m_dir = as_ints(be.metrics(st_direct))
        for k in METRICS_SCHEMA:
            if k in ("routed_ops", "routed_bytes"):
                continue
            assert m_eng[k] == m_dir[k], k
        assert m_eng["routed_ops"] == total_valid
        assert m_eng["routed_bytes"] == obs.ROUTED_OP_BYTES * total_valid

    @pytest.mark.slow
    def test_engine_metrics_multidevice(self, run_store_prog):
        # METRICS-OK, 8-shard form, and RECOVER-OK (a restored or
        # recovered shard's metrics plane): store_prog.py's "metrics" group
        out = run_store_prog("metrics")
        assert "METRICS-OK" in out
        assert "RECOVER-OK" in out

    def test_plain_backend_state_unchanged(self):
        # wrapping is opt-in: the un-prefixed backend's state pytree carries
        # no metrics and its apply records nothing
        be = get_backend("tiered3/lru")
        st = be.init(64, hot_bucket=4, hot_frac=8)
        assert not isinstance(st, obs.ObservedState)
        assert not hasattr(be, "metrics")


# ---------------------------------------------------------------------------
# exec dispatch meters: context-local + nestable (satellite fix)
# ---------------------------------------------------------------------------

class TestDispatchMeters:
    def test_nested_meters_compose(self):
        h = get_backend("fixed_hash").init(64)
        q = jnp.zeros((8,), jnp.uint64)
        with exec_.measure_dispatches() as outer:
            exec_.hash_find(h, q)
            with exec_.measure_dispatches() as inner:
                exec_.hash_find(h, q)
                exec_.hash_find(h, q)
            assert inner.n == 2
            exec_.hash_find(h, q)
        assert outer.n == 4      # inner activity counts toward the outer
        assert inner.n == 2      # ... without clobbering the inner total

    def test_meters_are_thread_local(self):
        h = get_backend("fixed_hash").init(64)
        q = jnp.zeros((8,), jnp.uint64)
        seen = {}

        def other():
            with exec_.measure_dispatches() as m:
                exec_.hash_find(h, q)
            seen["other"] = m.n

        with exec_.measure_dispatches() as mine:
            t = threading.Thread(target=other)
            t.start()
            t.join()
            exec_.hash_find(h, q)
        assert seen["other"] == 1
        assert mine.n == 1       # the other thread's probe never leaked in

    def test_reset_does_not_corrupt_meters(self):
        h = get_backend("fixed_hash").init(64)
        q = jnp.zeros((8,), jnp.uint64)
        with exec_.measure_dispatches() as m:
            exec_.hash_find(h, q)
            exec_.reset_dispatch_count()     # documented: meters unaffected
            exec_.hash_find(h, q)
        assert m.n == 2
        assert exec_.dispatch_count() == 1   # global restarted mid-block


# ---------------------------------------------------------------------------
# spans + chrome-trace export
# ---------------------------------------------------------------------------

def scope_names(lowered) -> set:
    """The `obs.*` phase names on the debug locations of a lowered step."""
    import re
    text = lowered.as_text(debug_info=True)
    return {part[len("obs."):]
            for loc in re.findall(r'loc\("([^"]*)"(?!:)', text)
            for part in loc.split("/") if part.startswith("obs.")}


def one_shard_engine(backend, lanes=16):
    from repro.launch.mesh import make_mesh
    from repro.store.engine import StoreEngine
    mesh = make_mesh((1,), ("d",), devices=jax.devices()[:1])
    # jnp: the Pallas modes add their kernels' own `obs.kernel.*` names
    return StoreEngine(mesh, ("d",), lanes=lanes, backend=backend,
                       exec_mode="jnp")


def engine_args(eng, capacity=64, seed=0):
    rng = np.random.default_rng(seed)
    state = jax.device_put(eng.init(capacity), eng.sharding)
    ops = jnp.asarray(rng.integers(0, 3, eng.lanes).astype(np.int32))
    keys = jnp.asarray(rng.integers(1, 48, eng.lanes).astype(np.uint64))
    return state, ops, keys, keys


class TestSpans:
    def test_span_noop_without_tracer(self):
        assert obs.current_tracer() is None
        with obs.span("scan"):       # must not raise or record anywhere
            pass

    def test_tracer_records_nested_spans(self):
        with obs.tracing() as tr:
            with obs.span("step", backend="x"):
                with obs.span("admit"):
                    pass
        names = [s.name for s in tr.spans]
        assert names == ["admit", "step"]      # inner closes first
        step = tr.spans[1]
        assert step.args == {"backend": "x"}
        assert step.dur_ns >= tr.spans[0].dur_ns

    def test_chrome_trace_structure(self):
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import trace_export
        with obs.tracing() as tr:
            with obs.span("step", lanes=4):
                pass
        payload = trace_export.to_chrome_trace(tr, meta={"k": 1})
        evs = [e for e in payload["traceEvents"] if e["ph"] == "X"]
        assert len(evs) == 1 and evs[0]["name"] == "step"
        assert evs[0]["ts"] >= 0 and evs[0]["dur"] >= 0
        assert evs[0]["args"] == {"lanes": 4}
        assert payload["otherData"] == {"k": 1}
        json.dumps(payload)      # must be JSON-serializable as-is

    def test_apply_emits_taxonomy_spans(self):
        # the tier stack's phases and the warm skiplist's passes name the
        # lowered HLO; lowering under a Tracer records nothing in it
        be = get_backend("obs:tiered3/lru")
        st = be.init(64, hot_bucket=4, hot_frac=8)
        plan = make_plan([OP_INSERT, OP_FIND], [1, 1], [7, 0])
        with obs.tracing() as tr, exec_.exec_mode("jnp"):
            names = scope_names(jax.jit(be.apply).lower(st, plan))
        assert {"insert", "delete", "find", "promote", "demote", "compact",
                "merge", "rebuild", "tombstone", "reclaim"} <= names
        assert names <= set(obs.SPAN_TAXONOMY)
        assert tr.spans == []

    @pytest.mark.parametrize("backend, present", [
        ("det_skiplist", {"route", "exchange", "find", "merge", "rebuild",
                          "tombstone", "range_delete", "reclaim"}),
        ("obs:tiered3/lru", {"route", "exchange", "find", "update", "merge",
                             "rebuild", "tombstone", "reclaim", "demote"}),
    ])
    def test_engine_step_scopes_in_taxonomy(self, backend, present):
        eng = one_shard_engine(backend)
        names = scope_names(eng._jit_step.lower(*engine_args(eng)))
        assert present <= names
        assert names <= set(obs.DEVICE_PHASES)

    def test_scope_rejects_unknown_phase(self):
        with pytest.raises(ValueError):
            obs.scope("not_a_phase")
        with pytest.raises(ValueError):      # a host phase is not a scope
            obs.scope("step")
        obs.scope("scan")                    # "scan" plays both roles

    def test_profiler_trace_numbers_engine_steps(self, tmp_path):
        # each StoreEngine.step is an `obs.step` host event on the
        # profiler's clock, numbered by the engine's seq
        from jax.profiler import ProfileData
        eng = one_shard_engine("det_skiplist")
        state, ops, keys, vals = engine_args(eng)
        state, *_ = eng.step(state, ops, keys, vals)        # compile first
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(3):
                state, *_ = eng.step(state, ops, keys, vals)
            jax.block_until_ready(state)
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        steps = sorted((e.start_ns, dict(e.stats))
                       for p in ProfileData.from_file(str(path)).planes
                       for line in p.lines for e in line.events
                       if e.name == "obs.step")
        assert [a["step_num"] for _, a in steps] == [1, 2, 3]
        assert all(a["traces"] == 1 and a["backend"] == "det_skiplist"
                   for _, a in steps)

    def test_retrace_counter(self):
        eng = one_shard_engine("det_skiplist")
        args = engine_args(eng)
        state, *_ = eng.step(*args)
        state, *_ = eng.step(state, *args[1:])
        assert eng.stats(state)["traces"] == 1     # same shapes: no retrace
        bigger = jax.device_put(eng.init(128), eng.sharding)
        state, *_ = eng.step(bigger, *args[1:])
        assert eng.stats(state)["traces"] == 2
        assert eng.stats(state)["seq"] == 3

    def test_demo_export_holds_host_spans_only(self):
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import trace_export
        tracer, _ = trace_export.record_demo_trace("obs:det_skiplist",
                                                   steps=2, lanes=16)
        assert [s.name for s in tracer.spans] == ["step", "step"]
        assert [s.args["step_num"] for s in tracer.spans] == [0, 1]
        payload = trace_export.to_chrome_trace(tracer)
        assert {e["name"] for e in payload["traceEvents"]
                if e["ph"] == "X"} == {"step"}


# ---------------------------------------------------------------------------
# bench_diff --assert-within (satellite gate)
# ---------------------------------------------------------------------------

class TestBenchDiffGate:
    def _artifact(self, tmp_path, name, us):
        payload = {"table": "t", "jax_backend": "cpu", "bench_iters": 5,
                   "warmup_discard": 2,
                   "rows": [{"name": r, "us_per_call": u}
                            for r, u in us.items()]}
        p = tmp_path / name
        p.write_text(json.dumps(payload))
        return str(p)

    def _run(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "bench_diff.py"),
             *args], capture_output=True, text=True)

    def test_within_threshold_passes(self, tmp_path):
        a = self._artifact(tmp_path, "a.json", {"r": 10.0, "s": 20.0})
        b = self._artifact(tmp_path, "b.json", {"r": 11.0, "s": 15.0})
        r = self._run("--assert-within", "25", a, b)
        assert r.returncode == 0, r.stderr
        assert "OK" in r.stdout

    def test_regression_fails(self, tmp_path):
        a = self._artifact(tmp_path, "a.json", {"r": 10.0, "s": 20.0})
        b = self._artifact(tmp_path, "b.json", {"r": 14.0, "s": 20.0})
        r = self._run("--assert-within", "25", a, b)
        assert r.returncode == 1
        assert "FAIL" in r.stderr and "r:" in r.stderr

    def test_improvement_and_missing_rows_pass(self, tmp_path):
        a = self._artifact(tmp_path, "a.json", {"r": 10.0, "gone": 5.0})
        b = self._artifact(tmp_path, "b.json", {"r": 2.0, "new": 9.0})
        assert self._run("--assert-within", "10", a, b).returncode == 0

    def test_metadata_mismatch_refuses_to_gate(self, tmp_path):
        a = self._artifact(tmp_path, "a.json", {"r": 10.0})
        payload = json.loads(open(a).read())
        payload["bench_iters"] = 3
        c = tmp_path / "c.json"
        c.write_text(json.dumps(payload))
        r = self._run("--assert-within", "10", a, str(c))
        assert r.returncode == 2
        # without the gate flag a metadata mismatch is only a warning
        assert self._run(a, str(c)).returncode == 0
