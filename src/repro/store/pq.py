"""Priority-queue `Store` backend over the deterministic skiplist.

"Practical Concurrent Priority Queues" (arXiv:1509.07053) builds pq
semantics on exactly the structure the paper gives us: a skiplist whose
minimum is the leftmost live terminal entry. This backend exposes that
through the Store contract as two lane ops:

  OP_POPMIN  extract-min; result vals = the popped entry's VALUE
  OP_POPK    extract-min; result vals = the popped entry's KEY

Both pop identically — all pop lanes of a plan share ONE rank pool in lane
order, so the j-th pop lane (counting POPMIN and POPK together) extracts
the j-th smallest live key and k pop lanes ARE a deterministic bulk-pop-k.
A pop lane's `keys` field is ignored here; under the sharded engine it is
the routing hint that selects WHICH shard's queue the lane pops — the
per-shard relaxed-pq design of 1509.07053 (a 1-shard mesh degenerates to
the exact global pop-min, which is how the serving scheduler runs it).

Pops execute as rank-select + lazy tombstones: `exec.pq_pop` (jnp |
Pallas interpret | pallas, bit-identical) locates the rank-th smallest
live key, `det_skiplist.pop_mark` commits the extraction through the same
DropKey/compaction path as deletes. FIND/INSERT/DELETE/RANGE_DELETE lanes
behave exactly as on `det_skiplist` (same primitives, same order), so the
cross-backend parity sweep covers `pq` unchanged; the full linearization
is INSERTS -> DELETES -> RANGE_DELETES -> POPS -> FINDS.

Registered as `pq` (and `obs:pq` via the observability prefix, which adds
the `pops` / `pop_empty` counters to the metrics plane; the same two ride
in `stats()` for un-observed states).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core import det_skiplist as dsl
from repro.core.bits import KEY_INF
from repro.store import exec as exec_
from repro.store import obs
from repro.store.api import (OP_POPK, OP_POPMIN, OP_RANGE_DELETE, OpPlan,
                             OpResults, register, uniform_stats)
from repro.store.backends import apply_linearized


class PQState(NamedTuple):
    """The pq backend's pytree: the skiplist heap + cumulative pop stats."""
    heap: dsl.DetSkiplist
    n_pops: jnp.ndarray       # scalar int64 — successful pop lanes
    n_pop_empty: jnp.ndarray  # scalar int64 — pop lanes that found it empty


class PQSkiplistBackend:
    name = "pq"
    ordered = True
    kernelized = True      # FIND -> kernels/skiplist_search, POP -> kernels/pq_pop

    def init(self, capacity: int, **kw) -> PQState:
        return PQState(heap=dsl.skiplist_init(capacity),
                       n_pops=jnp.zeros((), jnp.int64),
                       n_pop_empty=jnp.zeros((), jnp.int64))

    def apply(self, state: PQState, plan: OpPlan):
        valid = plan.mask & (plan.ops >= 0)
        is_pop = (plan.ops == OP_POPMIN) | (plan.ops == OP_POPK)
        pop_m = valid & is_pop
        rd_m = valid & (plan.ops == OP_RANGE_DELETE)

        def popping_find(heap, queries):
            # spliced between range-deletes and finds: `apply_linearized`
            # calls its find closure exactly once, after every update
            # phase, so committing the pops here keeps the linearization
            # INSERTS -> DELETES -> RANGE_DELETES -> POPS -> FINDS with
            # the insert/delete/range-delete half shared with det_skiplist
            ranks = jnp.cumsum(pop_m.astype(jnp.int32)) - 1
            with obs.scope("pop"):
                popped, pkeys, pidx = exec_.pq_pop(heap, ranks, pop_m)
                pvals = jnp.where(popped, heap.term_vals[pidx], jnp.uint64(0))
                heap = dsl.pop_mark(heap, pidx, popped)
            obs.record("pops", lambda: jnp.sum(popped))
            obs.record("pop_empty", lambda: jnp.sum(pop_m & ~popped))
            pop_state["heap"] = heap
            pop_state["res"] = (popped, pkeys, pvals)
            found, fvals, _ = exec_.skiplist_find(heap, queries)
            return found, fvals

        pop_state: dict = {}
        _, res = apply_linearized(
            state.heap, plan, dsl.insert_batch, dsl.delete_batch,
            popping_find, KEY_INF, range_delete_fn=dsl.range_delete_batch)
        heap = pop_state["heap"]
        popped, pkeys, pvals = pop_state["res"]

        # overlay the pop lanes onto the shared result encoding: ok = a
        # live entry was extracted; vals = its VALUE (POPMIN) or KEY (POPK)
        pres = jnp.where(popped,
                         jnp.where(plan.ops == OP_POPMIN, pvals, pkeys),
                         jnp.uint64(0))
        res = OpResults(ok=jnp.where(is_pop, popped, res.ok),
                        vals=jnp.where(is_pop & valid, pres, res.vals))
        n_pops = state.n_pops + jnp.sum(popped).astype(jnp.int64)
        n_empty = state.n_pop_empty + jnp.sum(pop_m & ~popped).astype(jnp.int64)
        heap = heap._replace(clock=heap.clock + 1)   # same batch clock as
        return PQState(heap=heap, n_pops=n_pops,     # det_skiplist.scan
                       n_pop_empty=n_empty), res

    def scan(self, state: PQState, lo, hi, max_out: int, as_of_batch=None):
        return dsl.range_query(state.heap, lo, hi, max_out,
                               as_of_batch=as_of_batch)

    def stats(self, state: PQState):
        return uniform_stats(
            size=state.heap.n_term - state.heap.n_marked,
            tombstones=state.heap.n_marked,
            capacity=state.heap.term_keys.shape[0],
            pops=state.n_pops,
            pop_empty=state.n_pop_empty)


PQ = register(PQSkiplistBackend())
