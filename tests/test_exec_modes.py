"""Execution-layer parity: the layered-store correctness contract.

For EVERY registered backend (including the §IX tiered `hash+skiplist`
config), `apply` and `scan` results must be BIT-IDENTICAL across all
runnable `repro.store.exec` modes — pure-jnp reference, Pallas interpret,
and (on TPU) Pallas compiled. Mode choice is a performance knob only; this
suite is what makes that a contract instead of a hope.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (enables x64)
from repro.store import (OP_DELETE, OP_FIND, OP_INSERT, available_backends,
                         get_backend, make_plan)
from repro.store import exec as exec_

ALL_BACKENDS = available_backends()
MODES = exec_.runnable_modes()
KERNELIZED = ("det_skiplist", "fixed_hash", "hash+skiplist", "tiered3/lru",
              "twolevel_splitorder")


def _mixed_plans(seed=2, n_rounds=4, width=48, pool_size=64):
    """Overlapping insert/find/delete workload (same shape as
    test_store_api): duplicates in-batch, deletes colliding with inserts,
    a few masked lanes."""
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


def _run_apply(name, mode, plans, capacity=2048, **init_kw):
    be = get_backend(name)
    with exec_.exec_mode(mode):
        st = be.init(capacity, **init_kw)
        step = jax.jit(be.apply)      # traced in `mode`
        outs = []
        for p in plans:
            st, res = step(st, p)
            outs.append((np.asarray(res.ok), np.asarray(res.vals)))
        stats = {k: int(v) for k, v in be.stats(st).items()}
    return st, outs, stats


def test_modes_cover_platform():
    assert "jnp" in MODES and "interpret" in MODES
    # `pallas` (compiled) participates exactly when the platform runs it
    assert ("pallas" in MODES) == exec_.pallas_available()


@pytest.mark.parametrize("name", ALL_BACKENDS)
def test_apply_bit_identical_across_modes(name):
    plans = _mixed_plans()
    _, ref_outs, ref_stats = _run_apply(name, MODES[0], plans)
    for mode in MODES[1:]:
        _, outs, stats = _run_apply(name, mode, plans)
        assert stats == ref_stats, (name, mode)
        for rnd, ((ok_r, v_r), (ok, v)) in enumerate(zip(ref_outs, outs)):
            assert (ok_r == ok).all(), (name, mode, rnd, "ok")
            assert (v_r == v).all(), (name, mode, rnd, "vals")


@pytest.mark.parametrize("name", [n for n in ALL_BACKENDS
                                  if get_backend(n).ordered])
def test_scan_bit_identical_across_modes(name):
    rng = np.random.default_rng(4)
    ks = np.unique(rng.integers(1, 2**40, 50, dtype=np.uint64))
    plan = make_plan(np.full(len(ks), OP_INSERT, np.int32), ks, ks + 3)
    lo = jnp.asarray(np.array([0, int(ks[5])], np.uint64))
    hi = jnp.asarray(np.array([2**41, int(ks[30])], np.uint64))
    ref = None
    for mode in MODES:
        be = get_backend(name)
        with exec_.exec_mode(mode):
            st, _ = be.apply(be.init(512), plan)
            out = [np.asarray(a) for a in be.scan(st, lo, hi, 64)]
        if ref is None:
            ref = out
        else:
            for a, b in zip(ref, out):
                assert (a == b).all(), (name, mode)


@pytest.mark.parametrize("name", KERNELIZED)
def test_tiered_and_kernelized_via_jitted_apply(name):
    """The dispatch survives jit: one jitted apply per mode, same bits
    (the engine path exercises the same trace-time mode capture)."""
    plans = _mixed_plans(seed=6, n_rounds=2)
    be = get_backend(name)
    ref = None
    for mode in MODES:
        with exec_.exec_mode(mode):
            st = be.init(1024)
            step = jax.jit(be.apply)
            outs = []
            for p in plans:
                st, res = step(st, p)
                outs.append((np.asarray(res.ok), np.asarray(res.vals)))
        if ref is None:
            ref = outs
        else:
            for (ok_r, v_r), (ok, v) in zip(ref, outs):
                assert (ok_r == ok).all(), (name, mode)
                assert (v_r == v).all(), (name, mode)


def test_empty_query_batch_all_modes():
    """Zero-width query batches work in every mode: the kernel wrappers
    must match the jnp references' empty-batch contract instead of crashing
    on tile=0 (batch UPDATE primitives require width > 0 in every mode —
    that pre-dates the exec layer and is mode-independent)."""
    from repro.core.det_skiplist import skiplist_init
    from repro.core.hashtable import fixed_init
    none = jnp.zeros((0,), jnp.uint64)
    s = skiplist_init(128)
    h = fixed_init(16, 4)
    for mode in MODES:
        f, v, i = exec_.skiplist_find(s, none, mode)
        assert f.shape == v.shape == i.shape == (0,), mode
        f, v = exec_.hash_find(h, none, mode)
        assert f.shape == v.shape == (0,), mode


def test_mode_plumbing():
    assert exec_.get_mode() in exec_.MODES
    before = exec_.get_mode()
    with exec_.exec_mode("interpret"):
        assert exec_.get_mode() == "interpret"
        with exec_.exec_mode(None):          # None = keep current
            assert exec_.get_mode() == "interpret"
    assert exec_.get_mode() == before
    with pytest.raises(ValueError, match="unknown store exec mode"):
        exec_.set_mode("cuda")
    with pytest.raises(ValueError):
        with exec_.exec_mode("nope"):
            pass


@pytest.mark.slow
def test_sharded_tier_residency_multidevice(run_store_prog):
    """The tier stack's residency across modes, the blocked skiplist and
    the fused probe and apply paths, on the 8-device mesh: store_prog.py's
    "residency" group."""
    out = run_store_prog("residency")
    assert "RESIDENCY-OK" in out
    assert "BSKIP-OK" in out
    assert "FUSED-OK" in out
    assert "APPLY-OK" in out


def test_engine_exec_mode_single_device():
    """StoreEngine bakes the mode into its jitted step; results match the
    jnp engine bit-for-bit on a 1-device mesh (8-device parity runs in
    tests/multidev/store_prog.py)."""
    from repro.launch.mesh import make_mesh
    from repro.store.engine import StoreEngine
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    rng = np.random.default_rng(3)
    keys = rng.integers(1, 2**63, 32, dtype=np.uint64)
    ops = rng.choice([OP_FIND, OP_INSERT, OP_DELETE], 32,
                     p=[0.4, 0.5, 0.1]).astype(np.int32)
    outs = {}
    for mode in MODES:
        eng = StoreEngine(mesh, ("data",), 32, backend="hash+skiplist",
                          exec_mode=mode)
        assert eng.exec_mode == mode
        state = jax.device_put(eng.init(256), eng.sharding)
        put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)
        state, res, ok, dropped = eng.step(state, put(ops), put(keys),
                                           put(keys + 1))
        assert int(dropped) == 0
        outs[mode] = (np.asarray(ok), np.asarray(res))
    ref = outs[MODES[0]]
    for mode in MODES[1:]:
        assert (outs[mode][0] == ref[0]).all(), mode
        assert (outs[mode][1] == ref[1]).all(), mode
