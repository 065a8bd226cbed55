#!/usr/bin/env python3
"""Bring-up smoke run of the store on TPU: the main path at deployment size.

Drives the store through the entry points a user calls (`StoreEngine`,
`local_store_engine`) on the `paper-kvstore` deployment
(`src/repro/configs/paper_kvstore.py`: 65536 entries per shard, 4096 lanes
per plan), one process, one chip:

  1. `det_skiplist` and `obs:tiered3/lru` in the `jnp` exec mode: load 75%
     of capacity from a seeded key stream, run mixed FIND/INSERT/DELETE
     plans and one range step, read `stats()` (and `metrics()`), and check
     every result exactly against `DictModel`, a dict model of the store
     contract that shares no code with the store.
  2. The same stream in the `pallas` exec mode (the compiled kernels):
     results, stats, metrics and the final state bit-identical to `jnp`.
  3. Every store kernel's exec entry on the loaded states (hash probe,
     level-major and blocked walks, fused tier find and apply, pq pop,
     two-level split-order probe), `pallas` against `jnp`, bit for bit.
  4. The serving admission path: `local_store_engine` over `obs:pq`,
     inserts then bulk pops, both modes, checked against the model.

With `--chips 4` it runs only the mesh-sharded path: a 4-shard engine (one
shard per chip, `all_to_all` routing) against a 1-shard engine on the
first chip that holds 4x the capacity, on one seeded stream, bit for bit,
with no lane dropped.

Lines before the last are information, not metrics: backend, mode, keys
loaded, compile seconds, steady step wall time, peak device memory. The
last line of stdout is `{"ok": true, "device": {...}}`. A JAX that sees no
TPU, or any failed phase, exits non-zero without that line.

Run on a TPU host from the checkout root:
    python chip_smoke.py [--chips 4] [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402,F401  (enables x64)
from repro.configs.paper_kvstore import CONFIG  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.store import exec as X  # noqa: E402
from repro.store import get_backend, make_plan  # noqa: E402
from repro.store.api import (OP_DELETE, OP_FIND, OP_INSERT,  # noqa: E402
                             OP_POPMIN)
from repro.store.engine import StoreEngine, local_store_engine  # noqa: E402

LOAD_FRAC = 0.75
MIXED_PLANS = 4
RANGE_MAX_OUT = 16


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the reference: the store contract over a dict
# ---------------------------------------------------------------------------

class DictModel:
    """The `OpPlan` contract (`store/api.py`) over a Python dict, lane by
    lane: per plan INSERTs (insert-if-absent; ok, existed flag), then
    DELETEs, then POPMINs (the j-th pop lane takes the j-th smallest key;
    result = its value), then FINDs. First lane wins on duplicates."""

    def __init__(self):
        self.d = {}
        self.counts = dict.fromkeys(
            ("ops_find", "ops_insert", "ops_delete", "find_hits",
             "find_misses", "inserts_new", "inserts_existing",
             "deletes_hit", "pops", "pop_empty", "routed_ops"), 0)

    def apply(self, ops, keys, vals):
        n = len(ops)
        ok = np.zeros(n, bool)
        out = np.zeros(n, np.uint64)
        c = self.counts
        c["routed_ops"] += int((ops >= 0).sum())
        for i in np.flatnonzero(ops == OP_INSERT):
            k = int(keys[i])
            existed = k in self.d
            if not existed:
                self.d[k] = int(vals[i])
            ok[i] = True
            out[i] = existed
            c["ops_insert"] += 1
            c["inserts_existing" if existed else "inserts_new"] += 1
        for i in np.flatnonzero(ops == OP_DELETE):
            ok[i] = self.d.pop(int(keys[i]), None) is not None
            c["ops_delete"] += 1
            c["deletes_hit"] += int(ok[i])
        pops = np.flatnonzero(ops == OP_POPMIN)
        order = sorted(self.d)[:len(pops)]
        for i, k in zip(pops, order):
            ok[i] = True
            out[i] = self.d.pop(k)
        c["pops"] += len(order)
        c["pop_empty"] += len(pops) - len(order)
        for i in np.flatnonzero(ops == OP_FIND):
            k = int(keys[i])
            c["ops_find"] += 1
            if k in self.d:
                ok[i] = True
                out[i] = self.d[k]
                c["find_hits"] += 1
            else:
                c["find_misses"] += 1
        return ok, out

    def range_count(self, lo, hi):
        ks = np.array(sorted(self.d), np.uint64)
        return (np.searchsorted(ks, hi, side="left")
                - np.searchsorted(ks, lo, side="left")).astype(np.int64)


# ---------------------------------------------------------------------------
# the seeded stream
# ---------------------------------------------------------------------------

def make_stream(seed: int, capacity: int, lanes: int):
    """Load plans (75% of `capacity` distinct keys, all INSERT) and mixed
    plans (FIND 50% / INSERT 25% / DELETE 25%, keys 70% loaded, 30% not
    yet seen). Keys are uniform over the 64-bit space, so every shard of a
    top-bits partition owns an equal share."""
    rng = np.random.default_rng(seed)
    n_load = int(capacity * LOAD_FRAC) // lanes * lanes
    pool = np.unique(rng.integers(1, 2 ** 64 - 1, int(n_load * 1.6),
                                  dtype=np.uint64))
    pool = rng.permutation(pool)
    loaded, fresh = pool[:n_load], pool[n_load:]
    val = lambda k: k * np.uint64(0x9E3779B97F4A7C15) + np.uint64(seed)
    plans = [(np.full(lanes, OP_INSERT, np.int32), k, val(k))
             for k in loaded.reshape(-1, lanes)]
    mixed = []
    for _ in range(MIXED_PLANS):
        ops = rng.choice(np.array([OP_FIND, OP_INSERT, OP_DELETE], np.int32),
                         lanes, p=[0.5, 0.25, 0.25])
        keys = np.where(rng.random(lanes) < 0.7, rng.choice(loaded, lanes),
                        rng.choice(fresh, lanes))
        mixed.append((ops, keys, val(keys) ^ np.uint64(len(mixed) + 1)))
    return plans, mixed


def range_queries(model: DictModel, seed: int, lanes: int):
    rng = np.random.default_rng(seed + 1)
    ks = np.array(sorted(model.d), np.uint64)
    i = rng.integers(0, len(ks) - 1, lanes)
    j = np.minimum(i + rng.integers(1, 48, lanes), len(ks) - 1)
    return ks[i], ks[j]


# ---------------------------------------------------------------------------
# engine runs
# ---------------------------------------------------------------------------

def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "n/a")


def run_engine(eng, state, plans, label):
    """Step every plan; returns (state, [(res, ok)], compile_s, steady_s)."""
    outs, walls = [], []
    compile_s = None
    for ops, keys, vals in plans:
        args = [jax.device_put(jnp.asarray(a), eng.sharding)
                for a in (ops, keys, vals)]
        t0 = time.perf_counter()
        state, res, ok, dropped = eng.step(state, *args)
        jax.block_until_ready((state, res, ok, dropped))
        wall = time.perf_counter() - t0
        if compile_s is None:
            compile_s = wall
        else:
            walls.append(wall)
        check(int(dropped) == 0, f"{label}: {int(dropped)} lanes dropped")
        outs.append((jax.device_get(res), jax.device_get(ok)))
    steady = statistics.median(walls) if walls else float("nan")
    return state, outs, compile_s, steady


def check_against_model(model, plans, outs, label):
    for n, ((ops, keys, vals), (res, ok)) in enumerate(zip(plans, outs)):
        want_ok, want = model.apply(ops, keys, vals)
        bad = np.flatnonzero((ok != want_ok) | (res != want))
        check(bad.size == 0,
              f"{label}: plan {n}: {bad.size} lanes differ from the model "
              f"(first lane {bad[:1].tolist()})")


def smoke_backend(dev, backend, capacity, lanes, seed, modes):
    """The main path for one backend in each mode. Returns per-mode
    (results, stats, metrics, state leaves) for the cross-mode check, and
    the first mode's final state of shard 0."""

    load, mixed = make_stream(seed, capacity, lanes)
    mesh = make_mesh((1,), ("data",), devices=[dev])
    got = {}
    for mode in modes:
        label = f"{backend}/{mode}"
        eng = StoreEngine(mesh, ("data",), lanes, backend=backend,
                          exec_mode=mode)
        state = jax.device_put(eng.init(capacity), eng.sharding)
        model = DictModel()
        state, outs, compile_s, _ = run_engine(eng, state, load, label)
        check_against_model(model, load, outs, label + " load")
        state, mouts, _, steady = run_engine(eng, state, mixed, label)
        check_against_model(model, mixed, mouts, label + " mixed")

        lo, hi = range_queries(model, seed, lanes)
        rstep = eng.range_step(RANGE_MAX_OUT)
        put = lambda a: jax.device_put(jnp.asarray(a), eng.sharding)
        cnt = np.asarray(rstep(state, put(lo), put(hi),
                               put(np.ones(lanes, bool))))
        check(np.array_equal(cnt, model.range_count(lo, hi)),
              f"{label}: range counts differ from the model")

        stats = {k: np.asarray(v) for k, v in eng.stats(state).items()}
        check(int(stats["size"].sum()) == len(model.d),
              f"{label}: stats size {stats['size']} != {len(model.d)}")
        metrics = {}
        if backend.startswith("obs:"):
            metrics = eng.metrics(state)
            for k, v in model.counts.items():
                if k in ("pops", "pop_empty"):
                    continue
                check(int(metrics[k].sum()) == v,
                      f"{label}: metric {k} {metrics[k]} != {v}")
        log(f"{label}: capacity={capacity} lanes={lanes} "
            f"keys_loaded={len(load) * lanes} live={len(model.d)} "
            f"compile_s={compile_s:.3f} steady_step_s={steady:.6f} "
            f"peak_bytes={peak_bytes(dev)} results=model-exact")
        got[mode] = ([outs, mouts], cnt, stats, metrics,
                     jax.tree.leaves(jax.device_get(state)))
        if len(got) == 1:
            shard0 = jax.tree.map(lambda x: x[0], state)
    return got, shard0


def same_tree(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def check_modes_identical(got, label):
    ref_mode, ref = next(iter(got.items()))
    for mode, g in got.items():
        check(same_tree(ref, g),
              f"{label}: {mode} differs from {ref_mode} (results, stats, "
              f"metrics or state)")
    if len(got) > 1:
        log(f"{label}: {' == '.join(got)} bit-identical")


# ---------------------------------------------------------------------------
# kernel-level parity on loaded states
# ---------------------------------------------------------------------------

def kernel_parity(dev, sk, tr, capacity, lanes, seed, kernel_mode):
    """Every store kernel's exec entry, `kernel_mode` against `jnp`, on
    states loaded with the seeded stream: `sk` a det_skiplist state, `tr`
    a tiered3/lru one (from the engine runs, its spill tier empty), and,
    loaded here, a two-level split-order table and a tiered3/lru stack
    whose warm tier holds an eighth of the keys, so most spill to the runs
    of its cold tier."""
    load, mixed = make_stream(seed, capacity, lanes)

    def loaded(name, **kw):
        be = get_backend(name)
        step = jax.jit(be.apply)
        st = jax.device_put(be.init(**kw), dev)
        for ops, keys, vals in load + mixed[:1]:
            st, _ = step(st, make_plan(ops, keys, vals))
        return st

    so = loaded("twolevel_splitorder", capacity=capacity)
    sp = loaded("tiered3/lru", capacity=capacity // 8, spill_cap=capacity)
    check(int(sp.spill.n) > 0, "the spill tier of the kernel state is empty")
    rng = np.random.default_rng(seed + 2)
    q = jnp.asarray(np.concatenate([load[0][1], mixed[1][1]]))
    vals = jnp.asarray(np.concatenate([load[0][2], mixed[1][2]]))
    mask = jnp.asarray(rng.random(q.shape[0]) < 0.9)
    ranks = jnp.asarray(rng.permutation(q.shape[0]).astype(np.int32))
    calls = {   # name: (exec entry taking mode last, its arguments)
        "hash_probe": (lambda t, q, m: X.hash_find_cols(t.hot, q, m),
                       (tr, q)),
        "skiplist_search": (X.skiplist_find, (sk, q)),
        "bskiplist_walk": (X.bskiplist_find, (sk, q)),
        "pq_pop": (X.pq_pop, (sk, ranks, mask)),
        "tier_find": (lambda t, q, m: X.tier_find(
            t.hot, t.cold, t.spill, q, m), (tr, q)),
        "tier_find/block": (lambda t, q, m: X.tier_find(
            t.hot, t.cold, t.spill, q, m, warm_layout="block"), (tr, q)),
        "tier_apply": (lambda t, q, v, k, m: X.tier_apply(
            t.hot, t.hot_meta, t.clock, t.cold, t.spill, q, v, k, "lru",
            64, m), (tr, q, vals, mask)),
        "tier_apply/block": (lambda t, q, v, k, m: X.tier_apply(
            t.hot, t.hot_meta, t.clock, t.cold, t.spill, q, v, k, "lru",
            64, m, warm_layout="block"), (tr, q, vals, mask)),
        "tier_find/spill": (lambda t, q, m: X.tier_find(
            t.hot, t.cold, t.spill, q, m), (sp, q)),
        "tier_apply/spill": (lambda t, q, v, k, m: X.tier_apply(
            t.hot, t.hot_meta, t.clock, t.cold, t.spill, q, v, k, "lru",
            64, m), (sp, q, vals, mask)),
        "splitorder_probe": (X.twolevel_splitorder_find, (so, q)),
    }
    for name, (fn, args) in calls.items():
        run = jax.jit(fn, static_argnums=len(args))
        want = jax.device_get(run(*args, "jnp"))
        t0 = time.perf_counter()
        got = jax.device_get(run(*args, kernel_mode))
        wall = time.perf_counter() - t0
        check(same_tree(want, got),
              f"kernel {name}: {kernel_mode} differs from jnp")
        log(f"kernel {name}: {kernel_mode} == jnp on {q.shape[0]} queries "
            f"(compile+run s={wall:.3f})")


# ---------------------------------------------------------------------------
# the serving admission path
# ---------------------------------------------------------------------------

def smoke_pq(capacity, lanes, seed, modes):
    """`local_store_engine` over `obs:pq`: two insert plans, then bulk-pop
    plans of POPMIN lanes until the queue runs dry."""

    rng = np.random.default_rng(seed + 3)
    keys = np.unique(rng.integers(1, 2 ** 63, 3 * lanes, dtype=np.uint64))
    keys = rng.permutation(keys)[:2 * lanes]
    plans = [(np.full(lanes, OP_INSERT, np.int32), k, k ^ np.uint64(7))
             for k in keys.reshape(2, lanes)]
    zeros = np.zeros(lanes, np.uint64)
    plans += [(np.full(lanes, OP_POPMIN, np.int32), zeros, zeros)] * 3
    got = {}
    for mode in modes:
        label = f"obs:pq/{mode}"
        eng = local_store_engine("obs:pq", lanes, mode)
        state = jax.device_put(eng.init(capacity), eng.sharding)
        model = DictModel()
        state, outs, compile_s, steady = run_engine(eng, state, plans, label)
        check_against_model(model, plans, outs, label)
        metrics = eng.metrics(state)
        for k in ("pops", "pop_empty"):
            check(int(metrics[k].sum()) == model.counts[k],
                  f"{label}: metric {k} {metrics[k]} != {model.counts[k]}")
        log(f"{label}: local_store_engine capacity={capacity} lanes={lanes} "
            f"pops={model.counts['pops']} "
            f"pop_empty={model.counts['pop_empty']} "
            f"compile_s={compile_s:.3f} steady_step_s={steady:.6f} "
            f"results=model-exact")
        got[mode] = (outs, metrics, jax.tree.leaves(jax.device_get(state)))
    check_modes_identical(got, "obs:pq")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def smoke_one_chip(dev, capacity, lanes, seed, kernel_mode="pallas"):
    modes = ("jnp", kernel_mode)
    if kernel_mode == "pallas":
        check("pallas" in X.runnable_modes(), "pallas mode not runnable")
    states = []
    for backend in ("det_skiplist", "obs:tiered3/lru"):
        got, state = smoke_backend(dev, backend, capacity, lanes, seed,
                                   modes)
        check_modes_identical(got, backend)
        states.append(state)
    sk, observed = states
    kernel_parity(dev, sk, observed.inner, capacity, lanes, seed,
                  kernel_mode)
    smoke_pq(capacity, lanes, seed, modes)


def smoke_four_chips(devs, capacity, lanes, seed, backend):
    """4 shards (one per chip) against 1 shard of 4x the capacity on
    devs[0], on one seeded stream of 4 * lanes-wide plans."""

    n = len(devs)
    load, mixed = make_stream(seed, n * capacity, n * lanes)
    runs = {}
    for shards, lane_w, mesh_devs in ((n, lanes, devs),
                                      (1, n * lanes, devs[:1])):
        label = f"{backend}/{shards}-shard"
        mesh = make_mesh((shards,), ("data",), devices=mesh_devs)
        eng = StoreEngine(mesh, ("data",), lane_w, backend=backend,
                          exec_mode="jnp")
        state = jax.device_put(eng.init(n * capacity // shards),
                               eng.sharding)
        t0 = time.perf_counter()
        state, outs, compile_s, steady = run_engine(eng, state,
                                                    load + mixed, label)
        wall = time.perf_counter() - t0
        model = DictModel()
        check_against_model(model, load + mixed, outs, label)
        size = np.asarray(eng.stats(state)["size"])
        check(int(size.sum()) == len(model.d), f"{label}: stats size")
        log(f"{label}: capacity/shard={n * capacity // shards} "
            f"lanes/shard={lane_w} plans={len(load) + len(mixed)} "
            f"sizes={size.tolist()} dropped=0 compile_s={compile_s:.3f} "
            f"steady_step_s={steady:.6f} total_s={wall:.3f} "
            f"results=model-exact")
        runs[shards] = outs
    check(same_tree(runs[n], runs[1]),
          f"{backend}: {n}-shard results differ from the 1-shard run")
    log(f"{backend}: {n}-shard == 1-shard bit-identical, 0 lanes dropped")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX sees no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2

    log(f"compile cache: {use_compile_cache()}")
    cap, lanes = CONFIG.store_capacity, CONFIG.store_lanes
    log(f"devices: {len(devs)} x {devs[0].device_kind}; deployment "
        f"{CONFIG.name}: capacity {cap}/shard, {lanes} lanes/plan, "
        f"seed {args.seed}")
    t0 = time.perf_counter()
    if args.chips == 4:
        smoke_four_chips(devs[:4], cap, lanes, args.seed,
                         CONFIG.store_backend)
    else:
        smoke_one_chip(devs[0], cap, lanes, args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
