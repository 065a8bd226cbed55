"""Deterministic store observability: in-array metrics + host trace spans.

The source paper's whole argument rests on *measured* memory behavior —
page faults, cache misses, remote-node accesses per NUMA hop. This module
is the reproduction's measurement substrate, in two planes:

**Plane 1 — in-array metrics.** `METRICS_SCHEMA` names deterministic int64
counters that are accumulated INSIDE `apply` (per-tier hit/miss, bucket
collisions, probe steps, eviction/demotion/promotion movement, spill-run
activity, per-shard routed ops/bytes) and carried in the state pytree
(`core.layout.metrics_plane`), so they jit, shard on dim 0, and checkpoint
like any other plane. Metrics are part of the determinism story, not a side
channel: every counter is computed on the u64 host path from probe INPUTS
and OUTPUTS (never inside a kernel body), so the `metrics()` pytree is held
to the SAME cross-exec-mode and cross-sharding bit-identity contract as
results — asserted by tests/test_obs.py and the METRICS-OK lane of
tests/multidev/store_prog.py.

Enable by wrapping any registered backend: ``get_backend("obs:tiered3/lru")``
returns an `ObservedStore` whose state is ``ObservedState(inner, metrics)``
and whose `metrics(state)` accessor returns the counter dict. Instrumented
modules (`store/backends.py`, `store/tiers.py`, `store/engine.py`) call
`record(name, thunk)` at the accumulation points; with NO collection frame
active (i.e. every un-wrapped backend) `record` returns before evaluating
the thunk, so an un-observed store traces no counter ops at all.

**Plane 2 — phase names, in two roles** (`SPAN_TAXONOMY` =
`DEVICE_PHASES` + `HOST_PHASES`; docs/observability.md lists what each
wraps and which benchmark metric reads it):

* Device phases — `scope(name)` — open `jax.named_scope("obs.<name>")`
  and nothing else. The name lands in the `op_name` of every HLO op
  traced inside, so a `jax.profiler` trace attributes device time to the
  phase; it costs nothing at run time and records nothing on the host.
  They cover the engine's routing ("route"), the exec dispatch layer
  ("find" / "update"), the tier stack's phases, and the skiplist's write
  passes ("merge", "rebuild", "tombstone", "range_delete", "reclaim";
  `core/det_skiplist.py` opens those scopes itself, since `core` does
  not import `repro.store`).
* Host phases — `span(name, **args)` — open a
  `jax.profiler.TraceAnnotation("obs.<name>")` (a
  `StepTraceAnnotation` when given `step_num`), so they sit on the
  profiler's clock beside the device ops, and record wall time + args
  into a context-local `Tracer` when one is installed (`tracing()`).
  They wrap host loops only: the engine step, eager scans, the serving
  loop, recoveries. `tools/trace_export.py` exports a Tracer as
  Chrome-trace JSON that loads in Perfetto.

The serving engine's host-side counters (`SERVING_SCHEMA`) share the same
closed-schema discipline via `uniform_serving_metrics`, so the serving
workload reports through one glossary.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.bits import EMPTY, KEY_INF
from repro.core.layout import hash_slot, metrics_plane

# ---------------------------------------------------------------------------
# metric schema (the glossary lives in docs/observability.md; names are
# gated there by tools/check_docs.py, like backend registry strings)
# ---------------------------------------------------------------------------

METRICS_SCHEMA = (
    # plan-level (recorded by the ObservedStore wrapper for EVERY backend,
    # derived from the plan and the results contract)
    "ops_find", "ops_insert", "ops_delete",
    "find_hits", "find_misses",
    "inserts_new", "inserts_existing", "deletes_hit",
    # probe cost (store/backends.py, store/tiers.py)
    "bucket_collisions", "warm_probe_steps", "spill_runs_searched",
    # tier residency + movement (store/tiers.py)
    "hot_hits", "warm_hits", "spill_hits",
    "evictions", "demotions", "promotions",
    "spill_appends", "tombstones_reclaimed",
    # priority-queue extraction (store/pq.py): successful pop lanes and
    # pop lanes that found the queue empty
    "pops", "pop_empty",
    # engine routing, per shard (store/engine.py)
    "routed_ops", "routed_bytes",
    # fault tolerance (store/resilience/, serving deadlines/overload).
    # These six are DELIBERATELY never accumulated inside a store state:
    # the journal contract says snapshot+replay reproduces the fault-free
    # state bit for bit, and counters *about* faults cannot live inside
    # the very plane a recovery must reproduce. They are tallied by the
    # resilience layer (ResilientEngine / the scheduler's resilience
    # record) and merged into the `metrics()` READ view by
    # `merge_resilience` — still deterministic (pure functions of the
    # seeded fault plan + trace), still schema-closed, still docs-gated.
    "faults_injected", "recoveries", "replayed_ops",
    "deadline_expired", "shed", "retries",
)

# the resilience-layer subset of METRICS_SCHEMA (glossary in
# docs/resilience.md): host-tallied, merged into metrics views
RESILIENCE_SCHEMA = ("faults_injected", "recoveries", "replayed_ops",
                     "deadline_expired", "shed", "retries")


def resilience_zero() -> Dict[str, int]:
    """A zeroed host-side resilience tally (plain ints — these counters
    never enter a jitted state; see the METRICS_SCHEMA note above)."""
    return {k: 0 for k in RESILIENCE_SCHEMA}


def merge_resilience(metrics: Dict[str, Any],
                     tally: Dict[str, int]) -> Dict[str, Any]:
    """A metrics view with a host-side resilience tally folded in (values
    added to the store plane's zeros; non-resilience keys pass through)."""
    unknown = set(tally) - set(RESILIENCE_SCHEMA)
    if unknown:
        raise ValueError(f"resilience tally keys {sorted(unknown)} not in "
                         f"obs.RESILIENCE_SCHEMA")
    return {k: (v + tally[k] if k in tally else v)
            for k, v in metrics.items()}

# host-side serving-engine counters (serving/engine.py `Engine.metrics()`)
SERVING_SCHEMA = ("ring_depth", "prefix_hits", "prefix_lookups",
                  "prefix_hit_rate", "batch_fill", "decode_steps",
                  "decode_tokens")

# phase names (docs/observability.md lists what each phase wraps). Device
# phases are named scopes on traced code (`scope`); host phases are
# profiler annotations + Tracer spans around host work (`span`). "scan" is
# both: a host span when called eagerly, a scope when traced.
DEVICE_PHASES = ("route", "find", "update", "insert", "delete", "pop",
                 "demote", "promote", "compact", "flush",
                 # the deterministic skiplist's passes (core/det_skiplist.py)
                 "merge", "rebuild", "tombstone", "range_delete", "reclaim")
HOST_PHASES = ("step", "scan", "admit", "prefill", "decode",
               # fault tolerance (store/resilience/restore.py): wraps a
               # quarantined shard's snapshot+journal rebuild — args carry
               # the shard id, journal replay length, and recovery mode
               "recover")
SPAN_TAXONOMY = DEVICE_PHASES + HOST_PHASES

# bytes one routed op carries through the engine's all_to_all queues:
# key u64 + val u64 + op i32 + origin i32 (core/routing.py lane payload)
ROUTED_OP_BYTES = 24


def metrics_zero() -> Dict[str, jnp.ndarray]:
    """A zeroed metrics plane (`core.layout.metrics_plane` over the schema)."""
    return metrics_plane(METRICS_SCHEMA)


def uniform_serving_metrics(**counters) -> Dict[str, Any]:
    """Pad host-side serving counters to the closed `SERVING_SCHEMA` key set
    (the serving analogue of `api.uniform_stats`; unknown keys error so the
    schema stays closed and the docs glossary stays exhaustive)."""
    unknown = set(counters) - set(SERVING_SCHEMA)
    if unknown:
        raise ValueError(f"serving metric keys {sorted(unknown)} not in "
                         f"SERVING_SCHEMA; extend obs.SERVING_SCHEMA")
    return {k: counters.get(k, 0) for k in SERVING_SCHEMA}


# ---------------------------------------------------------------------------
# collection frames (context-local, nestable: records go to the INNERMOST
# frame, so an engine-level frame and a backend-level frame never double
# count — the engine absorbs its own frame explicitly)
# ---------------------------------------------------------------------------

class MetricsFrame:
    """Trace-time accumulator: metric name -> traced int64 scalar."""

    __slots__ = ("acc",)

    def __init__(self):
        self.acc: Dict[str, jnp.ndarray] = {}

    def add(self, name: str, value) -> None:
        if name not in METRICS_SCHEMA:
            raise ValueError(f"unknown metric {name!r}; extend "
                             f"obs.METRICS_SCHEMA (and its docs glossary)")
        v = jnp.asarray(value).astype(jnp.int64)
        self.acc[name] = self.acc[name] + v if name in self.acc else v


_FRAMES: ContextVar[tuple] = ContextVar("repro_obs_frames", default=())


@contextmanager
def collect():
    """Open a metrics frame: `record` calls inside the block accumulate into
    it. Frames nest; each `record` lands in the innermost frame only."""
    frame = MetricsFrame()
    token = _FRAMES.set(_FRAMES.get() + (frame,))
    try:
        yield frame
    finally:
        _FRAMES.reset(token)


def collecting() -> bool:
    """True iff a metrics frame is active (instrumentation sites can use it
    to skip expensive derivations, though `record` thunks already do)."""
    return bool(_FRAMES.get())


def record(name: str, value) -> None:
    """Accumulate `value` into the innermost active frame. `value` may be a
    zero-arg thunk, evaluated ONLY when a frame is active — the disabled
    path is a dict lookup and a return, so un-observed stores pay nothing
    (neither trace-time compute nor extra ops in the jaxpr)."""
    frames = _FRAMES.get()
    if not frames:
        return
    frames[-1].add(name, value() if callable(value) else value)


def merge_metrics(metrics: Dict[str, jnp.ndarray],
                  frame: MetricsFrame) -> Dict[str, jnp.ndarray]:
    """metrics plane + one frame's accumulations (schema-complete result)."""
    return {k: (metrics[k] + frame.acc[k]) if k in frame.acc else metrics[k]
            for k in METRICS_SCHEMA}


# ---------------------------------------------------------------------------
# shared metric derivations (used by backends.py and tiers.py so the flat
# fixed-hash backend and the tier stack's hot tier agree on definitions)
# ---------------------------------------------------------------------------

def bucket_collision_count(table, queries) -> jnp.ndarray:
    """Bucket collisions of one probe phase: over every probed lane (query
    != EMPTY/KEY_INF sentinel), the non-empty cells of the lane's bucket
    row that are NOT the queried key — the constant-cost analogue of the
    paper's probe-chain length. Pure function of (table state, queries), so
    identical in every exec mode by construction."""
    rows = table.keys[hash_slot(queries, table.num_slots)]      # [K, B]
    live = (queries != EMPTY) & (queries != KEY_INF)
    coll = (rows != EMPTY) & (rows != queries[:, None])
    return jnp.sum(coll & live[:, None]).astype(jnp.int64)


# ---------------------------------------------------------------------------
# the ObservedStore wrapper: any registered backend + a metrics plane
# ---------------------------------------------------------------------------

class ObservedState(NamedTuple):
    """An observed backend's state: the wrapped backend's pytree plus the
    jit-carried metrics plane."""
    inner: Any
    metrics: Dict[str, jnp.ndarray]


class ObservedStore:
    """`Store` adapter adding the in-array metrics plane to any backend.

    Constructed via the registry prefix — ``get_backend("obs:<name>")`` —
    or directly over a backend instance (e.g. an unfused
    `TieredBackend(fused=False)` twin, which is how the fused-vs-unfused
    metric parity is asserted). `apply` opens a collection frame around the
    inner apply, records the plan-level counters itself, and folds
    everything into `state.metrics`; `scan`/`stats` proxy through
    (`scan` is read-only — a pure function cannot return new counters, so
    it contributes spans only)."""

    def __init__(self, inner):
        self.inner = inner
        self.name = f"obs:{inner.name}"
        self.ordered = inner.ordered
        self.kernelized = getattr(inner, "kernelized", False)

    def init(self, capacity: int, **kw) -> ObservedState:
        return ObservedState(inner=self.inner.init(capacity, **kw),
                             metrics=metrics_zero())

    def apply(self, state: ObservedState, plan):
        with collect() as frame:
            inner2, res = self.inner.apply(state.inner, plan)
            _record_plan_metrics(plan, res)
        return (ObservedState(inner=inner2,
                              metrics=merge_metrics(state.metrics, frame)),
                res)

    def scan(self, state: ObservedState, lo, hi, max_out: int, **kw):
        # **kw forwards backend-specific scan options (e.g. the ordered
        # skiplist backends' snapshot `as_of_batch=`) untouched. Inside a
        # trace (a range step) "scan" names the device ops; called eagerly
        # it is a host span.
        traced = isinstance(lo, jax.core.Tracer)
        with (scope("scan") if traced
              else span("scan", backend=self.inner.name)):
            return self.inner.scan(state.inner, lo, hi, max_out, **kw)

    def stats(self, state: ObservedState):
        return self.inner.stats(state.inner)

    def metrics(self, state: ObservedState) -> Dict[str, jnp.ndarray]:
        """The accumulated metrics plane (schema-complete dict of int64
        scalars; untouched counters are zero, like `uniform_stats`)."""
        return dict(state.metrics)

    def flush(self, state: ObservedState) -> ObservedState:
        """Proxy of a tier stack's bulk demotion — instrumented too, so
        flush-driven demotions/spill appends land in the same counters."""
        with collect() as frame:
            inner2 = self.inner.flush(state.inner)
        return ObservedState(inner=inner2,
                             metrics=merge_metrics(state.metrics, frame))


def _record_plan_metrics(plan, res) -> None:
    """The backend-generic counters, derived from the plan and the shared
    `OpResults` encoding (FIND -> (hit, val); INSERT -> (applied, existed);
    DELETE -> (removed, 0)) — bit-identical across modes because results
    are."""
    from repro.store.api import OP_DELETE, OP_FIND, OP_INSERT
    valid = plan.mask & (plan.ops >= 0)
    find_m = valid & (plan.ops == OP_FIND)
    ins_m = valid & (plan.ops == OP_INSERT)
    del_m = valid & (plan.ops == OP_DELETE)
    record("ops_find", lambda: jnp.sum(find_m))
    record("ops_insert", lambda: jnp.sum(ins_m))
    record("ops_delete", lambda: jnp.sum(del_m))
    record("find_hits", lambda: jnp.sum(find_m & res.ok))
    record("find_misses", lambda: jnp.sum(find_m & ~res.ok))
    existed = res.vals != 0
    record("inserts_new", lambda: jnp.sum(ins_m & res.ok & ~existed))
    record("inserts_existing", lambda: jnp.sum(ins_m & res.ok & existed))
    record("deletes_hit", lambda: jnp.sum(del_m & res.ok))


def absorb_frame(state, frame) -> Any:
    """Fold an EXTERNAL frame's counters (e.g. the engine's routing frame)
    into an observed state; a no-op for un-observed states, so callers can
    stay backend-agnostic."""
    if isinstance(state, ObservedState) and frame is not None and frame.acc:
        return ObservedState(inner=state.inner,
                             metrics=merge_metrics(state.metrics, frame))
    return state


# ---------------------------------------------------------------------------
# plane 2: host trace spans
# ---------------------------------------------------------------------------

class Span(NamedTuple):
    name: str
    ts_ns: int          # absolute perf_counter_ns at entry
    dur_ns: int
    args: Dict[str, Any]


class Tracer:
    """Span sink: append-only list of `Span`s plus the recording epoch
    (t0_ns), which `tools/trace_export.py` subtracts so trace timestamps
    start near zero."""

    def __init__(self):
        self.t0_ns = time.perf_counter_ns()
        self.spans: list[Span] = []

    def add(self, name: str, ts_ns: int, dur_ns: int,
            args: Dict[str, Any]) -> None:
        self.spans.append(Span(name=name, ts_ns=ts_ns, dur_ns=dur_ns,
                               args=args))


_TRACER: ContextVar[Tracer | None] = ContextVar("repro_obs_tracer",
                                                default=None)


@contextmanager
def tracing(tracer: Tracer | None = None):
    """Install a Tracer for the block (context-local; nested `tracing`
    blocks shadow the outer tracer). Yields the tracer."""
    tr = tracer if tracer is not None else Tracer()
    token = _TRACER.set(tr)
    try:
        yield tr
    finally:
        _TRACER.reset(token)


def current_tracer() -> Tracer | None:
    return _TRACER.get()


def scope(name: str):
    """A device phase: `jax.named_scope("obs.<name>")`, which puts the name
    on the `op_name` of every op traced inside (how a `jax.profiler` trace
    attributes device time). Nothing is recorded on the host: around
    traced code a wall clock would time lowering, once per compile.
    `name` is one of `DEVICE_PHASES`, or "scan" (traced in a range step)."""
    if name not in DEVICE_PHASES + ("scan",):
        raise ValueError(f"{name!r} is not a device phase; extend "
                         f"obs.DEVICE_PHASES (and its docs table)")
    return jax.named_scope(f"obs.{name}")


@contextmanager
def span(name: str, *, step_num: int | None = None, **args):
    """A host phase: a `jax.profiler.TraceAnnotation("obs.<name>", **args)`
    — a `StepTraceAnnotation` numbered `step_num` when one is given — so
    the span sits on the profiler's clock beside the device ops; when a
    Tracer is installed (`tracing()`), also records wall time + args for
    the Chrome-trace export. Wrap host work only (never traced code): the
    annotation is a no-op unless a profiler trace is running, and without
    a tracer the Tracer side costs one contextvar read."""
    tr = _TRACER.get()
    t0 = time.perf_counter_ns() if tr is not None else 0
    if step_num is None:
        ann = jax.profiler.TraceAnnotation(f"obs.{name}", **args)
    else:
        ann = jax.profiler.StepTraceAnnotation(f"obs.{name}",
                                               step_num=step_num, **args)
    with ann:
        try:
            yield
        finally:
            if tr is not None:
                if step_num is not None:
                    args = {**args, "step_num": step_num}
                tr.add(name, t0, time.perf_counter_ns() - t0, args)
