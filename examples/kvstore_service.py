"""The paper's own system, cluster-shaped: a sharded KV store over any backend.

One structure instance per mesh shard (= NUMA node), key space split by top
key bits, ops routed hierarchically with all_to_all (= the paper's lock-free
queues), results routed back. Runs on 8 fake devices.

The store is built through `repro.store.engine`, so the backend is a config
string: the deterministic skiplist, the two-level hash, the split-order
table, and the hierarchical hash+skiplist tier stack all serve the exact
same workload here — and the deterministic linearization makes their
find/insert/delete results bit-identical, which this example asserts.
The probe execution layer is a second config knob: the tiered backend is
re-run with its FIND phases on the Pallas kernels (interpret mode on CPU)
and must reproduce the jnp results bit-for-bit.

Run: PYTHONPATH=src python examples/kvstore_service.py [backend ...]
     (no args: run all of BACKENDS, cross-check, then cross-check exec modes)
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: F401,E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.store import OP_DELETE, OP_FIND, OP_INSERT, OP_POPK  # noqa: E402
from repro.store.engine import StoreEngine  # noqa: E402

AXES = ("pod", "data")
LANES = 32
BACKENDS = ("det_skiplist", "twolevel_hash", "splitorder", "hash+skiplist",
            "tiered3/lru", "pq")


def workload(n_rounds: int, total: int, seed: int = 0):
    """Deterministic op stream shared by every backend (vals = key + 1, so
    in-batch duplicate resolution cannot disagree between backends)."""
    rng = np.random.default_rng(seed)
    rounds = []
    keys = rng.integers(1, 2**63, total, dtype=np.uint64)
    rounds.append((np.full(total, OP_INSERT, np.int32), keys))
    for _ in range(n_rounds - 1):
        ops = rng.choice([OP_FIND, OP_DELETE, OP_INSERT], total,
                         p=[0.5, 0.25, 0.25]).astype(np.int32)
        k = keys.copy()
        fresh = ops == OP_INSERT
        k[fresh] = rng.integers(1, 2**63, int(fresh.sum()), dtype=np.uint64)
        rounds.append((ops, k))
        keys = k
    return rounds


def run_backend(name: str, rounds, exec_mode: str | None = None) -> list:
    mesh = make_mesh((2, 4), AXES)
    eng = StoreEngine(mesh, AXES, LANES, backend=name, pool_factor=4,
                      exec_mode=exec_mode)
    state = jax.device_put(eng.init(4096), eng.sharding)
    put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)

    outs = []
    for rnd, (ops, keys) in enumerate(rounds):
        state, res, ok, dropped = eng.step(state, put(ops), put(keys),
                                           put(keys + 1))
        assert int(dropped) == 0, f"{name}: routing drops"
        outs.append((np.asarray(ok), np.asarray(res)))
        finds = ops == OP_FIND
        if finds.any():
            hits = int(outs[-1][0][finds].sum())
            print(f"  [{name}] round {rnd}: finds hit {hits}/{int(finds.sum())}")

    stats = eng.stats(state)   # the Store.stats() accessor — no internals
    print(f"  [{name}] per-shard live sizes (top-3-bit key partition): "
          f"{stats['size']}")
    # "seq" is the engine's host step counter — a plain int, not a
    # per-shard plane, and it counts batches rather than structure totals
    extra = {k: np.sum(v) for k, v in stats.items()
             if k not in ("size", "capacity", "seq") and int(np.sum(v))}
    if extra:
        print(f"  [{name}] totals: " + ", ".join(
            f"{k}={int(v)}" for k, v in sorted(extra.items())))
    return outs


def main():
    use_compile_cache()
    backends = tuple(sys.argv[1:]) or BACKENDS
    rounds = workload(n_rounds=3, total=8 * LANES)
    results = {}
    for name in backends:
        print(f"backend: {name}")
        results[name] = run_backend(name, rounds)

    if len(results) > 1:
        ref_name, *others = list(results)
        ref = results[ref_name]
        for name in others:
            for r, ((ok_a, res_a), (ok_b, res_b)) in enumerate(
                    zip(ref, results[name])):
                assert (ok_a == ok_b).all(), (ref_name, name, r, "ok")
                assert (res_a == res_b).all(), (ref_name, name, r, "vals")
        print(f"all {len(results)} backends produced identical results "
              f"({len(rounds)} rounds x {8 * LANES} lanes)")

    # execution-layer parity: the tiered stack with its probes on the Pallas
    # kernels (interpret on CPU) must reproduce the jnp results bit-for-bit
    if "hash+skiplist" in results:
        kernelized = run_backend("hash+skiplist", rounds,
                                 exec_mode="interpret")
        for r, ((ok_a, res_a), (ok_b, res_b)) in enumerate(
                zip(results["hash+skiplist"], kernelized)):
            assert (ok_a == ok_b).all(), ("exec-mode", r, "ok")
            assert (res_a == res_b).all(), ("exec-mode", r, "vals")
        print("exec modes jnp and interpret produced identical results "
              "(hash+skiplist, kernelized hot-tier probe)")

    demo_pq_drain()


def demo_pq_drain():
    """Bulk-pop-k drain on the sharded `pq` backend: every shard is a
    per-NUMA priority queue (the relaxed-pq design — pop lanes carry a
    shard HINT in their key field), and one plan of OP_POPK lanes extracts
    each shard's k smallest keys in one dispatch. Drains the store to
    empty and checks each shard's pop stream comes out sorted."""
    print("backend: pq (sharded bulk-pop-k drain)")
    mesh = make_mesh((2, 4), AXES)
    eng = StoreEngine(mesh, AXES, LANES, backend="pq", pool_factor=4)
    state = jax.device_put(eng.init(4096), eng.sharding)
    put = lambda x: jax.device_put(jnp.asarray(x), eng.sharding)

    rng = np.random.default_rng(7)
    total = 8 * LANES
    keys = np.unique(rng.integers(1, 2**64, 2 * total,
                                  dtype=np.uint64))[:total]
    state, _, ok, dropped = eng.step(
        state, put(np.full(total, OP_INSERT, np.int32)), put(keys),
        put(keys + 1))
    assert int(dropped) == 0 and int(np.asarray(ok).sum()) == total

    # drain: every lane is OP_POPK; lane i hints shard i % 8, so each round
    # asks every shard for its LANES smallest live keys at once
    hints = (np.arange(total, dtype=np.uint64) % 8) << np.uint64(61)
    pops = np.full(total, OP_POPK, np.int32)
    drained = []                           # per round: 8 per-shard pop sets
    while True:
        state, res, ok, _ = eng.step(state, put(pops), put(hints),
                                     put(np.zeros(total, np.uint64)))
        ok, res = np.asarray(ok), np.asarray(res)
        if not ok.any():
            break
        drained.append([res[(np.arange(total) % 8 == s) & ok]
                        for s in range(8)])
    per_shard = [sum(len(r[s]) for r in drained) for s in range(8)]
    print(f"  [pq] drained {sum(per_shard)} keys in {len(drained)} bulk-pop "
          f"rounds; per-shard {per_shard}")
    # each round extracts a shard's smallest LIVE keys, so successive
    # rounds are strictly increasing blocks per shard — and the union is
    # exactly the inserted key set
    for s in range(8):
        for a, b in zip(drained, drained[1:]):
            assert not len(a[s]) or not len(b[s]) \
                or a[s].max() < b[s].min(), f"shard {s} pop order broken"
    got = sorted(k for r in drained for s in range(8) for k in r[s].tolist())
    assert got == sorted(keys.tolist())
    stats = eng.stats(state)
    print(f"  [pq] empty again (sizes {stats['size']}); pops="
          f"{int(stats['pops'].sum())} pop_empty="
          f"{int(stats['pop_empty'].sum())} — per-shard pop rounds strictly "
          f"increasing")


if __name__ == "__main__":
    main()
