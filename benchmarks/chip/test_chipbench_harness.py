"""The chip benchmark's harness on the CPU, at tiny sizes.

Each traffic mix runs through `StoreEngine` and the comparison passes; it
fails on one corrupted lane, on each fault of the timed path that a cell
can have, and on the controls (the reference in the program's place with a
guarantee broken); every mix is deterministic in its seed.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness as H  # noqa: E402
import bench_reference as ref  # noqa: E402
import bench_traffic as traffic  # noqa: E402
from bench_system import EngineSystem, ModelSystem  # noqa: E402

TINY = dict(capacity_per_shard=1024, lanes_per_plan=64, records_per_shard=512)
SEED = 2 ** 31 + 12345
PLANS = 12
CELLS = [w["name"] for w in H.load_benchmark()["workloads"]
         if w["chips"] == 1]
MIXES = ("mixed-90-5-5", "ycsb-c-zipf")


def tiny_cfg(cell):
    _, cfg, _ = H.find_cell(H.load_benchmark(), cell)
    return {**cfg, **TINY}


@pytest.fixture(scope="module")
def engines():
    """One compiled StoreEngine per configuration, reused from an empty
    state by every test of the module."""
    import jax
    built = {}

    def get(cell):
        cfg = tiny_cfg(cell)
        key = cfg["backend"]
        if key not in built:
            built[key] = EngineSystem(cfg, jax.devices()[:1]).engine
        return EngineSystem(cfg, jax.devices()[:1], engine=built[key])
    return get


def run(cell, system, seed=SEED):
    import jax
    return H.run_cell(cell, seed, 60.0, False, devices=jax.devices()[:1],
                      sizes=TINY, system=system, max_plans=PLANS)


def checks(out):
    return {k: c["value"] for k, c in out["checks"].items()}


@pytest.mark.parametrize("cell", CELLS)
def test_mix_through_engine_is_correct(cell, engines):
    out = run(cell, engines(cell))
    assert out["correct"], out["checks"]
    assert out["attempted"] == PLANS * TINY["lanes_per_plan"]
    assert out["failed"] == 0
    assert out["info"]["live_records"] == TINY["records_per_shard"]
    assert set(out["metrics"]) == {"ops_per_s", "op_latency_p95_ms",
                                   "device_bytes_per_record", "setup_s"}
    assert list(out)[-1] == "checks"


def test_one_corrupted_lane_fails(engines):
    system = engines("skiplist-mixed")
    fetch = system.fetch
    calls = []

    def corrupt(out):
        res, ok, dropped = fetch(out)
        calls.append(1)
        if len(calls) == 20:     # one lane of one window plan
            res = res.copy()
            res[3] ^= np.uint64(1 << 40)
        return res, ok, dropped
    system.fetch = corrupt
    out = run("skiplist-mixed", system)
    assert not out["correct"]
    assert checks(out)["lanes_wrong"] == 1


def _state_unchanged(step):
    def f(state, ops, keys, vals):
        _, res, ok, dropped = step(state, ops, keys, vals)
        return state, res, ok, dropped
    return f


def _half_batch(step):
    def f(state, ops, keys, vals):
        import jax.numpy as jnp
        half = jnp.arange(ops.shape[0]) >= ops.shape[0] // 2
        return step(state, jnp.where(half, -1, ops), keys, vals)
    return f


def _answer_altered(step):
    def f(state, ops, keys, vals):
        state, res, ok, dropped = step(state, ops, keys, vals)
        return state, res.at[0].set(res[0] ^ 1), ok, dropped
    return f


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_timed_path_fails(cell, fault, engines):
    system = engines(cell)
    system.step_fn = fault(system.step_fn)
    out = run(cell, system)
    assert not out["correct"], out["checks"]
    assert checks(out)["lanes_wrong"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_32bit_values_fails(cell):
    out = run(cell, ModelSystem(ref.DictModel(value_bits=32)))
    assert not out["correct"]
    assert checks(out)["lanes_wrong"] > 0


def test_control_finds_before_deletes_fails():
    model = ref.DictModel(order=("insert", "find", "delete"))
    out = run("skiplist-mixed", ModelSystem(model))
    assert not out["correct"]
    assert checks(out)["lanes_wrong"] > 0


def test_reference_in_place_passes():
    out = run("skiplist-mixed", ModelSystem(ref.DictModel()))
    assert out["correct"], out["checks"]


def _plans(mix, seed, n=6, lanes=64, records=512):
    g = traffic.Generator(traffic.load_mix(mix), seed, lanes, records)
    return g.load_plans() + [g.next_plan() for _ in range(n)]


@pytest.mark.parametrize("mix", MIXES)
def test_generator_deterministic_in_seed(mix):
    a, b = _plans(mix, SEED), _plans(mix, SEED)
    c = _plans(mix, SEED + 1)
    for pa, pb in zip(a, b):
        for x, y in zip(pa, pb):
            assert np.array_equal(x, y)
    assert any(not np.array_equal(pa[1], pc[1]) for pa, pc in zip(a, c))


@pytest.mark.parametrize("mix", MIXES)
def test_generator_shares_and_keys(mix):
    m = traffic.load_mix(mix)
    plans = _plans(mix, -7, n=4, lanes=1000, records=4000)
    for ops, keys, _ in plans[4:]:
        want = traffic.lane_counts(m["shares"], 1000)
        got = tuple(int(np.sum(ops == op)) for op in
                    (traffic.OP_FIND, traffic.OP_INSERT, traffic.OP_DELETE))
        assert got == want
        assert np.all(keys != np.uint64(2 ** 64 - 1))
