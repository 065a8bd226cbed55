"""Recovery benchmark: what deterministic fault tolerance costs.

Three question rows, landing in ``BENCH_recovery.json``:

* **replay throughput** — ops/sec of `resilience.restore` (snapshot +
  journal tail through the normal engine step) as the journal tail grows;
  recovery time must scale linearly with journal length, and every replay
  must land on the SAME state digest (asserted, and the digest is recorded
  so two artifacts can be diffed for determinism, like BENCH_serve.json).
* **sync-recovery overhead** — wall time of a faulted run (one shard drop
  mid-stream, recovered synchronously) vs the fault-free twin, per exec
  mode, with the bit-identity of the recovered state asserted.
* **shed rate** — the cost of one `scheduler.cancel_class` RANGE_DELETE
  plan shedding an overload burst, and the fraction of the backlog it
  drops.

Deterministic by construction: the op stream, the fault plan (seeded via
`faults.default_seed`, so the CI chaos lane's ``REPRO_FAULTS`` reseeds it),
and therefore every digest and count are pure functions of the seeds.
CI gates two independent runs with tools/bench_diff.py --assert-within.
"""
from __future__ import annotations

import time
import zlib

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import Recorder, finish
from repro.launch.mesh import make_mesh
from repro.store import engine as engine_mod
from repro.store import exec as exec_
from repro.store import resilience as R
from repro.store.api import OP_DELETE, OP_FIND, OP_INSERT

BACKEND = "obs:det_skiplist"
LANES = 16
CAP = 512
SEED = 17
ITERS = 3
WARMUP = 1


def _fresh_engine(exec_mode=None):
    mesh = make_mesh((1,), ("local",), devices=jax.devices()[:1])
    return engine_mod.StoreEngine(mesh, ("local",), LANES, backend=BACKEND,
                                  pool_factor=1, exec_mode=exec_mode)


def _stream(n_steps: int):
    rng = np.random.default_rng(SEED)
    plans = []
    for t in range(n_steps):
        ops = rng.choice([OP_INSERT, OP_FIND, OP_DELETE], size=LANES,
                         p=[0.6, 0.3, 0.1]).astype(np.int32)
        keys = rng.integers(1, 1 << 48, LANES, dtype=np.uint64)
        plans.append((ops, keys, keys + np.uint64(t + 1)))
    return plans


def _journal(plans):
    """Run the stream once, journaling every plan; returns the restore
    inputs plus the live run's final digest."""
    eng = _fresh_engine()
    state = jax.device_put(eng.init(CAP), eng.sharding)
    snap = R.take_snapshot(state, 0)
    j = R.Journal(base_seq=0)
    for s, (ops, keys, vals) in enumerate(plans):
        j.append(s, ops, keys, vals)
        state, _, _, _ = eng.step(state, jnp.asarray(ops), jnp.asarray(keys),
                                  jnp.asarray(vals))
    return snap, j, R.state_digest(state)


def run(out_dir: str | None = None):
    fault_seed = R.default_seed(SEED)
    rec = Recorder("recovery", exec_modes=list(exec_.runnable_modes()),
                   bench_iters=ITERS, warmup_discard=WARMUP,
                   fault_seed=fault_seed)

    # --- replay throughput vs journal length --------------------------
    for n_entries in (8, 32):
        plans = _stream(n_entries)
        snap, j, want = _journal(plans)
        total_ops = sum(e.n_ops for e in j.entries)
        eng = _fresh_engine()     # one traced step reused by every replay
        walls = []
        for it in range(WARMUP + ITERS):
            t0 = time.perf_counter()
            state, replayed = R.restore(eng, snap, j.entries)
            jax.block_until_ready(jax.tree.leaves(state))
            walls.append(time.perf_counter() - t0)
            assert replayed == total_ops
            assert R.state_digest(state) == want, "replay digest drift"
        wall = float(np.median(walls[WARMUP:]))
        rec.record(f"recovery/replay/entries={n_entries}", wall / n_entries,
                   entries=n_entries, replayed_ops=total_ops,
                   ops_per_sec=total_ops / wall,
                   digest=zlib.crc32(want.encode()))

    # --- sync-recovery overhead per exec mode -------------------------
    n_steps = 12
    plans = _stream(n_steps)
    fplan = R.FaultPlan(fault_seed,
                        [R.Fault("shard_drop", n_steps // 2, shard=0)])
    ref_digest = None
    for mode in exec_.runnable_modes():
        def drive(fault_plan):
            eng = _fresh_engine(exec_mode=mode)
            reng = R.ResilientEngine(eng, snapshot_every=4,
                                     fault_plan=fault_plan)
            state = jax.device_put(eng.init(CAP), eng.sharding)
            t0 = time.perf_counter()
            for ops, keys, vals in plans:
                state, _, _, _ = reng.step(state, jnp.asarray(ops),
                                           jnp.asarray(keys),
                                           jnp.asarray(vals))
            jax.block_until_ready(jax.tree.leaves(state))
            return time.perf_counter() - t0, R.state_digest(state), reng

        drive(None)                      # warmup/trace
        base, base_digest, _ = drive(None)
        faulted, fault_digest, reng = drive(fplan)
        assert fault_digest == base_digest, "sync recovery not bit-identical"
        if ref_digest is None:
            ref_digest = base_digest
        assert base_digest == ref_digest, f"exec-mode divergence: {mode}"
        rec.record(f"recovery/sync/mode={mode}", faulted / n_steps,
                   steps=n_steps, overhead_pct=round(
                       100.0 * (faulted - base) / base, 1),
                   replayed_ops=reng.tally["replayed_ops"],
                   recoveries=reng.tally["recoveries"],
                   digest=zlib.crc32(base_digest.encode()), mode=mode)

    # --- shedding one overload burst ----------------------------------
    from repro.serving import scheduler as SCH
    n_bulk, n_urgent = 48, 8
    walls, outcome = [], None
    for it in range(WARMUP + ITERS):
        s = SCH.scheduler_init(max_pending=256)
        prios = np.concatenate([np.full(n_bulk, 2), np.full(n_urgent, 0)])
        for c in range(0, len(prios), LANES):
            chunk = prios[c:c + LANES]
            pad = LANES - len(chunk)
            s, _ = SCH.submit(
                s, jnp.asarray(np.concatenate([chunk, np.zeros(pad)]),
                               jnp.uint32),
                jnp.arange(c, c + LANES, dtype=jnp.int32),
                jnp.asarray([True] * len(chunk) + [False] * pad))
        t0 = time.perf_counter()
        s, cancelled = SCH.cancel_class(s, 2)
        walls.append(time.perf_counter() - t0)
        got = (cancelled, int(SCH.pending(s)))
        assert outcome in (None, got), "shed replay divergence"
        outcome = got
    assert outcome == (n_bulk, n_urgent)
    rec.record("recovery/shed/burst", float(np.median(walls[WARMUP:])),
               backlog=n_bulk + n_urgent, shed=outcome[0],
               shed_rate=round(outcome[0] / (n_bulk + n_urgent), 4),
               survivors=outcome[1])

    finish(rec, out_dir)
    return rec
