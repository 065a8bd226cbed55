"""The system under test, behind the few calls the timed loop makes.

`EngineSystem` is the deployed path: the program's `StoreEngine` over a
mesh of the cell's chips, built with the deployment's backend, lanes per
shard and capacity, and every other argument at the program's default
(exec mode, pool factor, fusion). The harness sets no environment variable
of the program.

`ModelSystem` puts a reference in the program's place: the controls of the
correctness comparison run through it.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def import_program():
    """The program's modules; importing `repro` turns on x64."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401
    from repro.launch.mesh import make_mesh
    from repro.store.engine import StoreEngine
    return make_mesh, StoreEngine


class EngineSystem:
    def __init__(self, cfg: dict, devices, engine=None):
        """`engine`: a `StoreEngine` built before for the same `cfg` and
        devices, to run it again from an empty state without compiling."""
        import jax
        make_mesh, StoreEngine = import_program()
        self.jax = jax
        self.devices = list(devices)
        self.n_shards = len(self.devices)
        if engine is None:
            mesh = make_mesh((self.n_shards,), ("data",),
                             devices=self.devices)
            engine = StoreEngine(mesh, ("data",), cfg["lanes_per_plan"],
                                 backend=cfg["backend"])
        self.engine = engine
        self.observed = cfg["backend"].startswith("obs:")
        self.state = jax.device_put(
            self.engine.init(cfg["capacity_per_shard"]),
            self.engine.sharding)
        self.step_fn = self.engine.step    # the timed call (tests wrap it)

    def put(self, ops, keys, vals):
        s = self.engine.sharding
        return tuple(self.jax.device_put(a, s) for a in (ops, keys, vals))

    def step(self, args):
        self.state, res, ok, dropped = self.step_fn(self.state, *args)
        return res, ok, dropped

    def fetch(self, out):
        res, ok, dropped = self.jax.device_get(out)
        return np.asarray(res), np.asarray(ok), int(dropped)

    def shard_sizes(self) -> np.ndarray:
        return np.asarray(self.engine.stats(self.state)["size"])

    def counters(self) -> dict | None:
        if not self.observed:
            return None
        return {k: int(np.sum(v))
                for k, v in self.engine.metrics(self.state).items()}

    def memory(self) -> dict:
        """Device memory on the cell's chips: `array_bytes`, the bytes of
        every live JAX array's shards there (the store's state, and
        anything else the process keeps on them); `bytes_in_use`, the
        allocator's count, which also holds the compiled programs' code;
        `peak_bytes`, the allocator's peak on the fullest chip."""
        devs = set(self.devices)
        arrays = sum(sh.data.nbytes for a in self.jax.live_arrays()
                     for sh in a.addressable_shards if sh.device in devs)
        stats = [d.memory_stats() or {} for d in self.devices]
        return {"array_bytes": arrays,
                "bytes_in_use": sum(int(s.get("bytes_in_use", 0))
                                    for s in stats),
                "peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                  for s in stats)}

    def release(self):
        for leaf in self.jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None


class ModelSystem:
    """A `bench_reference.DictModel` (of some variant) in the program's
    place, for the controls: same calls, host arrays, no device."""

    observed = False

    def __init__(self, model, n_shards: int = 1):
        self.model = model
        self.n_shards = n_shards

    def put(self, ops, keys, vals):
        return ops, keys, vals

    def step(self, args):
        ok, res = self.model.apply(*args)
        return res, ok, 0

    def fetch(self, out):
        return out

    def shard_sizes(self) -> np.ndarray:
        return self.model.shard_sizes(self.n_shards)

    def counters(self):
        return None

    def memory(self):
        return {"array_bytes": 0, "bytes_in_use": 0, "peak_bytes": 0}

    def release(self):
        pass


def cache_dir() -> str:
    """JAX's persistent compilation cache: one fixed directory inside the
    checkout, whatever the environment says (the path is part of the
    cache's key)."""
    return os.path.join(ROOT, ".jax_cache")
