"""repro.store — unified storage-engine API over the concurrent structures.

The paper's closing proposal (§IX) is hierarchical composition of its
structures; this package is that composition layer. Module map:

api       the `Store` protocol (`init/apply/scan/stats`), the `OpPlan` /
          `OpResults` batch types, op codes, the uniform `STATS_SCHEMA`,
          and the string-keyed backend registry (`register`, `get_backend`,
          `available_backends`)
backends  adapters wrapping det_skiplist, rand_skiplist, fixed hash,
          two-level hash, split-order, and two-level split-order behind the
          protocol — all jit/shard_map-safe pytrees, all agreeing lane-for-
          lane on the INSERTS -> DELETES -> FINDS linearization
exec      the execution layer: FIND/probe phases dispatch through here to
          the pure-jnp references or the Pallas kernels
          (kernels/skiplist_search, kernels/hash_probe) — three modes
          (jnp | interpret | pallas), bit-identical results
pq        the priority-queue backend (`pq`): the deterministic skiplist as
          a mergeable heap — OP_POPMIN/OP_POPK bulk extraction (one rank
          pool per plan, kernelized rank-select + level walk), plus
          OP_RANGE_DELETE; the admission path of `repro.serving.scheduler`
tiers     the hierarchical tier stacks: `hash+skiplist` (hot fixed-hash
          over the ordered skiplist) and `tiered3[/lru|/size]` (a third
          append-only host-spill tier of sorted runs, plus pluggable
          deterministic hot-tier eviction policies — LRU-by-batch and
          size-aware), with batched spill/eviction/promotion/flush; the
          hot-tier probe is the kernelized fast path (docs/tiers.md)
engine    the mesh-sharded engine (hierarchical all_to_all routing + local
          apply) generalizing core/ordered_sharded.py to any backend;
          `StoreEngine` is the one-object convenience wrapper
obs       the observability layer: `obs:`-prefixed backends carry a
          deterministic jit-carried metrics plane (`METRICS_SCHEMA`,
          bit-identical across exec modes and shardings, like results),
          `scope` names device phases for `jax.profiler` traces, and
          `span`/`tracing` record host spans on the profiler's clock and
          into a Tracer exportable as Chrome-trace/Perfetto JSON
          (tools/trace_export.py, docs/observability.md)

The stack is three explicit layers: `core.layout` owns the flat-memory
shapes, `store.exec` owns probe execution over them, and this package's
backends/tiers/engine own semantics, composition, and sharding. Pick a
backend by config string (`configs/*.py: store_backend`) and an execution
mode by `store_exec`; adding a backend is a one-file drop-in that calls
`register`.
"""
from repro.store.api import (OP_DELETE, OP_FIND, OP_INSERT, OP_NONE, OP_POPK,
                             OP_POPMIN, OP_RANGE, OP_RANGE_DELETE,
                             STATS_SCHEMA, OpPlan, OpResults, Store,
                             available_backends, get_backend, make_plan,
                             register, uniform_stats)
from repro.store.obs import (METRICS_SCHEMA, SERVING_SCHEMA, SPAN_TAXONOMY,
                             ObservedStore, Tracer, current_tracer, scope,
                             span, tracing, uniform_serving_metrics)

__all__ = [
    "OP_DELETE", "OP_FIND", "OP_INSERT", "OP_NONE", "OP_POPK", "OP_POPMIN",
    "OP_RANGE", "OP_RANGE_DELETE",
    "STATS_SCHEMA", "OpPlan", "OpResults", "Store", "available_backends",
    "get_backend", "make_plan", "register", "uniform_stats",
    "METRICS_SCHEMA", "SERVING_SCHEMA", "SPAN_TAXONOMY", "ObservedStore",
    "Tracer", "current_tracer", "scope", "span", "tracing",
    "uniform_serving_metrics",
]
