"""Mesh-sharded storage engine over any registered `Store` backend.

Generalizes the paper's flagship experiment (§VI: 8 skiplists, one per NUMA
node, keys partitioned by top bits, lock-free queues routing each key to the
owner node) from "skiplist only" to ANY backend or tier stack: one backend
instance per mesh shard, hierarchical all_to_all routing (coarsest axis — the
DCI hop — first), the backend's `apply` executed locally, results routed back
to the requesting shard/lane.

Selection is by config string (`get_backend`): swapping `det_skiplist` for
`twolevel_hash`, `splitorder`, or a tier stack (`hash+skiplist`,
`tiered3/lru`, ...) changes one argument, nothing else — the routing,
sharding, and result plumbing are backend-agnostic, and each shard runs its
own full tier stack (hot table, warm skiplist, spill runs, and policy
state all shard on dim 0 like any other state leaf). The registered tier
stacks probe through the FUSED `exec.tier_find` path, so each shard's
local FIND chain is one kernel dispatch per plan regardless of tier depth
(docs/tiers.md); an unfused `TieredBackend(fused=False)` instance drops in
with bit-identical results and residency (the FUSED-OK multidev check).
Because the policies are deterministic and the linearization is
order-independent for distinct keys, per-shard tier residency is EXACTLY
what a single-device instance produces for that shard's sub-stream —
asserted by `tests/multidev/store_prog.py`. `core/ordered_sharded.py`
keeps its original API as thin wrappers over this module.
"""
from __future__ import annotations

import functools
import math
from contextlib import nullcontext
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.routing import (route_back, route_to_owners,
                                shard_linear_id)
from repro.launch.mesh import make_mesh
from repro.store import exec as exec_
from repro.store import obs
from repro.store.api import OpPlan, Store, get_backend


def resolve(backend) -> Store:
    """Accept a backend instance or a registry name."""
    return get_backend(backend) if isinstance(backend, str) else backend


def sharded_init(backend, n_shards: int, capacity_per_shard: int, **kw):
    """Backend state pytree with a leading shard dim (to be device_put with
    `store_sharding`). Python-int leaves (static knobs) are promoted to
    arrays so every leaf broadcasts."""
    be = resolve(backend)
    one = be.init(capacity_per_shard, **kw)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(jnp.asarray(x)[None],
                                   (n_shards,) + jnp.asarray(x).shape), one)


def store_sharding(mesh: Mesh, axis_names: Sequence[str]) -> NamedSharding:
    """State sharded on dim 0 over all routing axes; op streams likewise."""
    return NamedSharding(mesh, P(tuple(axis_names)))


def make_store_step(mesh: Mesh, axis_names: Sequence[str], lanes: int,
                    backend="det_skiplist", pool_factor: int = 2,
                    exec_mode: str | None = None):
    """Build the jit-able batched-op step for `backend`.

    Global inputs: ops[int32 S*lanes], keys[u64 S*lanes], vals[u64 S*lanes]
    sharded over the routing axes (S = total shards; each shard contributes
    `lanes` requests — "threads fill queues, then operate", §IX).
    Returns (state', results[u64 S*lanes], ok[bool S*lanes], dropped).

    `exec_mode` selects the probe execution layer (`repro.store.exec`:
    jnp | interpret | pallas; None = the module default) for the local
    `apply` only — routing, sharding, and result plumbing are identical in
    every mode, and so are the results (bit-identical by contract).
    """
    be = resolve(backend)
    mode = exec_.get_mode() if exec_mode is None else exec_mode
    axis_sizes = [mesh.shape[a] for a in axis_names]
    pool = lanes * pool_factor

    # per-shard routing counters land in the engine's OWN frame (opened
    # only around the route phase — the backend's apply opens its own
    # nested frame, so the two never double count) and are folded into the
    # observed state explicitly; with an un-observed backend the frame is
    # never opened and the records are no-ops
    observed = isinstance(be, obs.ObservedStore)

    def body(state, ops, keys, vals):
        sl = jax.tree.map(lambda x: x[0], state)   # this shard's instance
        valid = ops >= 0
        with (obs.collect() if observed else nullcontext(None)) as frame:
            with obs.scope("route"):
                rr = route_to_owners(keys, vals, ops, valid, axis_names,
                                     axis_sizes, pool)
            if observed:
                # ops this shard RECEIVED for local execution (valid routed
                # lanes in its pool) and the bytes they carried through the
                # all_to_all queues — per shard, like every other counter
                routed = jnp.sum(rr.valid & (rr.aux >= 0)).astype(jnp.int64)
                obs.record("routed_ops", routed)
                obs.record("routed_bytes", routed * obs.ROUTED_OP_BYTES)
        plan = OpPlan(ops=rr.aux, keys=rr.keys, vals=rr.vals, mask=rr.valid)
        with exec_.exec_mode(mode):   # baked in at trace time
            sl, res = be.apply(sl, plan)
        sl = obs.absorb_frame(sl, frame)
        with obs.scope("route"):
            resv, okb = route_back(res.vals, res.ok, rr.origin,
                                   rr.valid & (rr.aux >= 0), axis_names,
                                   axis_sizes, lanes)
        state2 = jax.tree.map(lambda a, b: b[None], state, sl)
        return state2, resv, okb, rr.dropped[None]   # [1]/shard -> [S] global

    spec1 = P(tuple(axis_names))
    # pallas_call has no shard_map replication rule: disable the check ONLY
    # when this backend actually traces one (results unchanged — parity is
    # tested); jnp-fallback backends keep the check in every mode
    check = mode == "jnp" or not getattr(be, "kernelized", False)
    step = jax.shard_map(body, mesh=mesh,
                         in_specs=(spec1, spec1, spec1, spec1),
                         out_specs=(spec1, spec1, spec1, spec1),
                         check_vma=check)

    def wrapped(state, ops, keys, vals):
        st, res, ok, dropped = step(state, ops, keys, vals)
        return st, res, ok, jnp.sum(dropped)

    return wrapped


def make_range_step(mesh: Mesh, axis_names: Sequence[str], lanes: int,
                    max_out: int, backend="det_skiplist",
                    pool_factor: int = 2):
    """Range counting over an ORDERED backend: [lo, hi) per lane. Ranges
    crossing shard boundaries are answered by every touched shard and summed
    on the way back (all_gather + psum: ranges are rare + wide, so two
    collectives beat per-key queues)."""
    be = resolve(backend)
    if not be.ordered:
        raise ValueError(f"backend {be.name!r} is unordered: range steps "
                         f"need an ordered backend or tier stack")
    axis_sizes = [mesh.shape[a] for a in axis_names]

    def body(state, los, his, valid):
        valid = valid.astype(jnp.int32)
        sl = jax.tree.map(lambda x: x[0], state)
        ls, hs, vs = los, his, valid
        for a in axis_names:
            ls = jax.lax.all_gather(ls, a, axis=0, tiled=True)
            hs = jax.lax.all_gather(hs, a, axis=0, tiled=True)
            vs = jax.lax.all_gather(vs, a, axis=0, tiled=True)
        cnt, _, _, _ = be.scan(sl, ls, hs, max_out)
        cnt = jnp.where(vs > 0, cnt, 0)
        for a in axis_names:
            cnt = jax.lax.psum(cnt, a)
        return jax.lax.dynamic_slice_in_dim(
            cnt, shard_linear_id(axis_names) * lanes, lanes)

    spec1 = P(tuple(axis_names))
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec1, spec1, spec1, spec1),
                         out_specs=spec1)


def sharded_stats(backend, state) -> dict:
    """Host-side per-shard `Store.stats`: dict of [S] numpy arrays."""
    be = resolve(backend)
    n_shards = jax.tree.leaves(state)[0].shape[0]
    per = [be.stats(jax.tree.map(lambda x: x[i], state))
           for i in range(n_shards)]
    return {k: np.asarray([np.asarray(jax.device_get(p[k])) for p in per])
            for k in per[0]}


def sharded_metrics(backend, state) -> dict:
    """Host-side per-shard metrics plane: dict of [S] numpy int64 arrays
    over `obs.METRICS_SCHEMA`. Requires an `obs:`-wrapped backend (whose
    sharded state carries the counters on dim 0 like every other leaf);
    per-shard values are bit-identical to a single-device observed instance
    replaying that shard's sub-stream — the METRICS-OK multidev contract."""
    be = resolve(backend)
    if not isinstance(be, obs.ObservedStore):
        raise ValueError(f"backend {be.name!r} carries no metrics plane; "
                         f"construct the engine with an 'obs:'-prefixed "
                         f"backend string (e.g. 'obs:tiered3/lru')")
    n_shards = jax.tree.leaves(state)[0].shape[0]
    per = [be.metrics(jax.tree.map(lambda x: x[i], state))
           for i in range(n_shards)]
    return {k: np.asarray([np.asarray(jax.device_get(p[k])) for p in per])
            for k in per[0]}


class StoreEngine:
    """Convenience bundle: backend + mesh + jitted step, one object.

    `backend` is a registry string (`api.available_backends()`: flat
    structures, or the `hash+skiplist` / `tiered3[/lru|/size]` tier
    stacks) or a `Store` instance; `exec_mode` bakes a probe execution
    mode (jnp | interpret | pallas, `repro.store.exec`) into the jitted
    step — None uses the process default (`REPRO_STORE_EXEC`).

    >>> eng = StoreEngine(mesh, ("pod", "data"), lanes=32,
    ...                   backend="hash+skiplist")
    >>> state = jax.device_put(eng.init(4096), eng.sharding)
    >>> state, res, ok, dropped = eng.step(state, ops, keys, vals)
    >>> eng.stats(state)["size"]        # per-shard live sizes
    """

    def __init__(self, mesh: Mesh, axis_names: Sequence[str], lanes: int,
                 backend="det_skiplist", pool_factor: int = 2,
                 exec_mode: str | None = None):
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.lanes = lanes
        self.backend = resolve(backend)
        self.exec_mode = exec_mode
        self.pool_factor = pool_factor
        self.n_shards = int(math.prod(mesh.shape[a] for a in self.axis_names))
        self.sharding = store_sharding(mesh, self.axis_names)
        step = make_store_step(mesh, self.axis_names, lanes,
                               backend=self.backend, pool_factor=pool_factor,
                               exec_mode=exec_mode)

        def counted(*args):
            # runs only while jax traces the step (a new shape or dtype):
            # a recompile shows in `traces`, at no cost per call
            self.traces += 1
            return step(*args)

        self.traces = 0
        self._jit_step = jax.jit(counted)
        # host-side step sequence number: incremented once per `step()` call,
        # surfaced in `stats()` and the "step" span. The resilience journal
        # keys its entries off this counter (`journal.py` restores it on
        # `restore`), and traces gain numbered steps. Deliberately NOT a
        # state leaf: engine state must stay leaf-for-leaf identical to a
        # broadcast backend state (the RESIDENCY-OK contract).
        self.seq = 0

    def step(self, state, ops, keys, vals):
        """One batched-op step inside the `"step"` host span: a
        `jax.profiler.StepTraceAnnotation("obs.step", step_num=seq)` on the
        profiler's clock, and a Tracer span when a `obs.tracing()` block is
        active. The span times the dispatch (the step returns before the
        device finishes) and carries `traces`, the engine's trace count
        before the call. Each call advances the host-side `seq` counter."""
        seq = self.seq
        self.seq += 1
        with obs.span("step", step_num=seq, backend=self.backend.name,
                      lanes=self.lanes, shards=self.n_shards,
                      traces=self.traces):
            return self._jit_step(state, ops, keys, vals)

    def init(self, capacity_per_shard: int, **kw):
        return sharded_init(self.backend, self.n_shards, capacity_per_shard,
                            **kw)

    def range_step(self, max_out: int, pool_factor: int = 2):
        return jax.jit(make_range_step(self.mesh, self.axis_names, self.lanes,
                                       max_out, backend=self.backend,
                                       pool_factor=pool_factor))

    def stats(self, state) -> dict:
        """Per-shard `STATS_SCHEMA` arrays plus the engine-level `"seq"`
        (host step counter — how many steps this engine has applied; the
        journal's next entry number) and `"traces"` (how many times the
        step was traced: 1 once warm, more after a recompile). Both are
        engine metadata, not part of `api.STATS_SCHEMA`: backend stats
        stay schema-exact."""
        out = sharded_stats(self.backend, state)
        out["seq"] = self.seq
        out["traces"] = self.traces
        return out

    def metrics(self, state) -> dict:
        """Per-shard metrics plane (`sharded_metrics`); raises unless the
        engine was built over an `obs:` backend."""
        return sharded_metrics(self.backend, state)


@functools.lru_cache(maxsize=None)
def local_store_engine(backend: str, lanes: int,
                       exec_mode: str | None = None) -> StoreEngine:
    """A cached 1-shard StoreEngine over the first local device — the
    serving layer's route into the Store API. Single-shard routing is the
    identity partition (`owner_of` -> shard 0 for every key), so a plan's
    lanes execute in their original order and pop lanes see the EXACT
    global pop-min; pool_factor=1 because the pooled plan is exactly the
    lane set. Cached by (backend string, lanes, exec_mode) so every
    scheduler/prefix-cache call reuses one traced step per configuration
    (flip modes at trace time by passing `exec_mode`, e.g. from
    `exec.get_mode()` inside an `exec.exec_mode(...)` block)."""
    mesh = make_mesh((1,), ("local",), devices=jax.devices()[:1])
    return StoreEngine(mesh, ("local",), lanes, backend=backend,
                       pool_factor=1, exec_mode=exec_mode)
