"""Execution-mode dispatch: the layer between layouts and the Store API.

Every backend's FIND/probe phase calls through here, so the probe
implementation is swappable without touching backend or tier logic. Three
interchangeable modes, selected by config string
(`configs/*.py: store_exec`, or the `REPRO_STORE_EXEC` env default):

  jnp        pure-jnp reference probes (`core.det_skiplist.find_batch`,
             `core.hashtable.fixed_find`, ...) — the portable baseline and
             the default
  interpret  Pallas kernels in interpreter mode — the kernel bodies execute
             on CPU; what CI runs
  pallas     Pallas kernels compiled for the TPU. Every store kernel
             compiles for v5e (tests/test_chip_compile.py) and matches jnp
             bit for bit on one v5e chip (chip_smoke.py); whether it is
             faster than jnp there has not been measured

The correctness contract is BIT-IDENTICAL results across all three modes
for every backend (asserted by tests/test_exec_modes.py): the kernels
consume the same `core.layout` shapes the references do and use the same
comparisons, so parity is by construction, and mode choice is purely a
performance knob.

Kernelized probes: the deterministic skiplist search
(`kernels.skiplist_search`; its block-major B-skiplist twin
`kernels.bskiplist_walk`, dispatched by `bskiplist_find` — lane-width fat
nodes, one whole-block compare per step), the fixed-hash bucket probe
(`kernels.hash_probe` — also the §IX hot-tier fast path), the FUSED
tier-stack find and apply (`kernels.tier_find`, `kernels.tier_apply`),
the two-level split-order per-table searchsorted
(`kernels.splitorder_probe`), and the priority-queue pop rank-select
(`kernels.pq_pop`, dispatched by `pq_pop` — live-prefix binary search +
the shared `level_walk` descent). Probes whose access pattern defeats the
static-shape or VMEM premise (the randomized skiplist's MAX_GAP-padded
walk, ONE-level split-order's searchsorted over the full array — the
global array does not fit VMEM, which is why only the two-level variant
kernelizes — and the two-level hash table's pooled L2 indirection), and
the unfused chain's standalone spill probe and hot-tier update, have no
kernel: they run their jnp reference in the jnp and interpret modes and
REFUSE the pallas mode, so a pallas request never runs a reference in
silence. They are still routed through this module so a future kernel is
a one-function change.

The mode is read at TRACE time: `StoreEngine`/`make_store_step` bake it
into the jitted step via `exec_mode(...)`, so two engines with different
modes coexist; flipping the module default after a step is traced does not
retrace it.

Every entry here counts as ONE dispatch (`dispatch_count()` /
`measure_dispatches()`), split by kind: probes ("probe" — the read-only
FIND/membership launches) and updates ("update" — the write-path launches:
`hot_update`, the fused `tier_apply`). The counter ticks when the entry is
TRACED, which is exactly once per launch in the compiled step — the unit
the fused tier find/apply kernels exist to minimize. Benchmarks and the
fused-path tests read it to report dispatches per plan, probe and update
halves separately. Meters are CONTEXT-LOCAL and NESTABLE (see
`measure_dispatches`); each dispatch also opens the device phase
`obs.scope("find")` or `obs.scope("update")`, so a `jax.profiler` trace
attributes the entry's device ops to its phase.
"""
from __future__ import annotations

import functools
import os
from contextlib import contextmanager
from contextvars import ContextVar

MODES = ("jnp", "interpret", "pallas")


def _check(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(f"unknown store exec mode {mode!r}; one of {MODES}")
    return mode


# REPRO_STORE_EXEC: the process-wide default execution mode, read ONCE at
# import ("jnp" | "interpret" | "pallas"; default "jnp"). CI re-runs the
# kernel suites with REPRO_STORE_EXEC=interpret; `set_mode`/`exec_mode()`
# override it per call site, and `StoreEngine(exec_mode=...)` bakes an
# explicit mode into its jitted step regardless of this default.
_mode = _check(os.environ.get("REPRO_STORE_EXEC", "jnp"))


def get_mode() -> str:
    return _mode


def set_mode(mode: str) -> None:
    global _mode
    _mode = _check(mode)


@contextmanager
def exec_mode(mode: str | None):
    """Scoped mode override (None = keep the current mode). Wrap the TRACE
    of a jitted step to bake the mode in."""
    global _mode
    prev = _mode
    if mode is not None:
        _mode = _check(mode)
    try:
        yield
    finally:
        _mode = prev


def _resolve(mode: str | None) -> str:
    return _check(mode) if mode is not None else _mode


def _reference_only(name: str, mode: str | None) -> None:
    """Gate of the probes that have no kernel: they refuse `pallas`."""
    if _resolve(mode) == "pallas":
        raise NotImplementedError(
            f"store.exec.{name} has no Pallas kernel; exec mode 'pallas' "
            f"would run its jnp reference — use exec mode 'jnp'")


_PALLAS_OK: bool | None = None


def pallas_available() -> bool:
    """True iff COMPILED Pallas kernels run on the current jax backend.
    Off TPU (CPU/GPU get interpret mode only) this is False. On a TPU it
    launches a real store kernel — the hash probe over one full query tile
    — checks it against the jnp reference, and returns True; a kernel that
    fails to compile, run or match RAISES there, since a TPU that cannot
    run the kernels is a fault to see, not a mode to drop. Checked once
    per process; tests and benchmarks use it to scope the `pallas` mode."""
    global _PALLAS_OK
    if _PALLAS_OK is None:
        import jax
        if jax.default_backend() != "tpu":
            _PALLAS_OK = False
        else:
            _check_pallas_kernel()
            _PALLAS_OK = True
    return _PALLAS_OK


def _check_pallas_kernel() -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.core import hashtable as ht
    from repro.kernels.hash_probe.ops import fixed_hash_find_cols
    from repro.kernels.rows import MAX_TILE
    keys = jnp.arange(1, 2 * MAX_TILE + 1, 2, dtype=jnp.uint64)
    h, _, _ = ht.fixed_insert(ht.fixed_init(256, 8), keys[: MAX_TILE // 2],
                              keys[: MAX_TILE // 2])
    got = fixed_hash_find_cols(h, keys, interpret=False)
    want = ht.fixed_find_cols(h, keys)
    for g, w in zip(got, want):
        if not np.array_equal(np.asarray(g), np.asarray(w)):
            raise RuntimeError("compiled hash_probe kernel disagrees with "
                               "its jnp reference on this TPU")


def runnable_modes() -> tuple:
    """The execution modes that can actually run here — all three on a TPU
    (`pallas_available` raises there rather than drop one), `jnp` and
    `interpret` elsewhere. What parity tests and benchmarks iterate
    over."""
    return MODES if pallas_available() else tuple(m for m in MODES
                                                  if m != "pallas")


# ---------------------------------------------------------------------------
# dispatch accounting (context-local, nestable)
# ---------------------------------------------------------------------------

# dispatches split by KIND: "probe" (read-only FIND/membership launches)
# and "update" (write-path launches: the hot-tier insert prologue, the
# fused tier-apply). The split is what lets the fused-vs-unfused bench
# rows report dispatches_per_apply for each half of an apply.
DISPATCH_KINDS = ("probe", "update")

_n_dispatch = 0
_n_by_kind = {"probe": 0, "update": 0}

# the active meter stack lives in a ContextVar, so meters are CONTEXT-LOCAL:
# concurrent traces (threads, async tasks) each see only their own probes,
# and nested `measure_dispatches()` blocks compose instead of sharing one
# global start offset
_METERS: ContextVar[tuple] = ContextVar("repro_exec_meters", default=())


def _bump(kind: str = "probe") -> None:
    global _n_dispatch
    _n_dispatch += 1
    _n_by_kind[kind] += 1
    for meter in _METERS.get():
        meter._n += 1
        if kind == "probe":
            meter._probe += 1
        else:
            meter._update += 1


def dispatch_count(kind: str | None = None) -> int:
    """Cumulative dispatches issued through this module in this process
    (counted at trace time — one tick = one launch in the traced step).
    `kind=None` returns the total; `"probe"` / `"update"` return one half
    of the split (probe = FIND/membership launches, update = write-path
    launches). Monotone; see `reset_dispatch_count` for the reset
    semantics. For scoped counts prefer `measure_dispatches`."""
    if kind is None:
        return _n_dispatch
    if kind not in DISPATCH_KINDS:
        raise ValueError(f"unknown dispatch kind {kind!r}; "
                         f"one of {DISPATCH_KINDS}")
    return _n_by_kind[kind]


def reset_dispatch_count() -> None:
    """Zero the process-cumulative `dispatch_count()` (total and both
    kinds). Reset semantics: only the global totals are affected — active
    `measure_dispatches` meters count INCREMENTS (not offsets against the
    global), so a reset inside a measured block neither corrupts nor
    rewinds any meter."""
    global _n_dispatch
    _n_dispatch = 0
    for k in DISPATCH_KINDS:
        _n_by_kind[k] = 0


class DispatchMeter:
    """Live dispatch counter for one `measure_dispatches` block. `n` is
    valid DURING the block (live count so far) and after it (final count),
    with the probe/update split exposed as `.probe` / `.update`
    (`n == probe + update`); every dispatch traced in the block ticks this
    meter AND any enclosing ones, so nested blocks see their own totals
    and outer blocks include inner activity."""

    __slots__ = ("_n", "_probe", "_update")

    def __init__(self):
        self._n = 0
        self._probe = 0
        self._update = 0

    @property
    def n(self) -> int:
        return self._n

    @property
    def probe(self) -> int:
        return self._probe

    @property
    def update(self) -> int:
        return self._update


@contextmanager
def measure_dispatches():
    """Count the probe dispatches traced inside the block:

    >>> with measure_dispatches() as m:
    ...     backend.apply(state, plan)        # or jax.make_jaxpr(...)
    >>> m.n                                   # dispatches per plan

    Context-local and nestable: an inner `with measure_dispatches()` block
    keeps its own total while still contributing to the outer meter, and
    meters in other threads/contexts never observe this block's probes.
    """
    meter = DispatchMeter()
    token = _METERS.set(_METERS.get() + (meter,))
    try:
        yield meter
    finally:
        _METERS.reset(token)


def _probe(fn):
    """Shared probe-entry decorator: one dispatch tick + the
    `obs.scope("find")` device phase per entry."""
    from repro.store import obs

    @functools.wraps(fn)
    def wrapped(*args, **kw):
        _bump("probe")
        with obs.scope("find"):
            return fn(*args, **kw)
    return wrapped


def _update(fn):
    """Write-path twin of `_probe`: one "update"-kind dispatch tick + the
    `obs.scope("update")` device phase per entry. Update dispatches are the
    half of an apply the fused tier-apply kernel collapses; the split
    counters are what the fused-vs-unfused bench rows report."""
    from repro.store import obs

    @functools.wraps(fn)
    def wrapped(*args, **kw):
        _bump("update")
        with obs.scope("update"):
            return fn(*args, **kw)
    return wrapped


# ---------------------------------------------------------------------------
# kernelized probes
# ---------------------------------------------------------------------------

@_probe
def skiplist_find(s, queries, mode: str | None = None):
    """Deterministic-skiplist FIND: (found[Q], vals[Q], term_idx[Q])."""
    m = _resolve(mode)
    if m == "jnp":
        from repro.core import det_skiplist as dsl
        return dsl.find_batch(s, queries)
    from repro.kernels.skiplist_search.ops import skiplist_find as sk_find
    return sk_find(s, queries, interpret=(m == "interpret"))


@_probe
def bskiplist_find(s, queries, mode: str | None = None):
    """Deterministic-skiplist FIND through the block-major B-skiplist view
    (`core.layout.bskiplist_layout`): same (found[Q], vals[Q], term_idx[Q])
    contract and bit-identical found/vals as `skiplist_find`, but the walk
    compares one lane-width fat node (128 sorted keys) per step instead of
    a fan-out-4 gather — `tiered3/b128`'s warm probe. The layout, like the
    mode, is a probe-execution knob over unchanged state."""
    m = _resolve(mode)
    if m == "jnp":
        from repro.core import det_skiplist as dsl
        return dsl.find_batch_blocked(s, queries)
    from repro.kernels.bskiplist_walk.ops import bskiplist_find as bsk_find
    return bsk_find(s, queries, interpret=(m == "interpret"))


@_probe
def pq_pop(s, ranks, mask, mode: str | None = None):
    """Priority-queue rank-select on a DetSkiplist: the rank-th smallest
    live key per lane. Returns (found[K], keys[K] u64, idx[K] i32) — a pure
    read; the pq backend commits the extraction with `pop_mark`. Both paths
    apply identical not-found masking (keys=KEY_INF, idx=0), so results are
    bit-identical across modes including the miss lanes."""
    m = _resolve(mode)
    if m == "jnp":
        from repro.core import det_skiplist as dsl
        return dsl.pop_rank_select(s, ranks, mask)
    from repro.kernels.pq_pop.ops import pq_pop_ranks
    return pq_pop_ranks(s, ranks, mask, interpret=(m == "interpret"))


@_probe
def hash_find(h, queries, mode: str | None = None):
    """Fixed-slot hash probe: (found[Q], vals[Q]). The §IX hot-tier path."""
    m = _resolve(mode)
    if m == "jnp":
        from repro.core import hashtable as ht
        return ht.fixed_find(h, queries)
    from repro.kernels.hash_probe.ops import fixed_hash_find
    return fixed_hash_find(h, queries, interpret=(m == "interpret"))


@_probe
def hash_find_cols(h, queries, mode: str | None = None):
    """Fixed-slot hash probe that also reports the hit column:
    (found[Q], vals[Q], col[Q] i32). This is the policy-aware form of the
    hot-tier probe: the column is what lets an eviction policy refresh its
    per-entry metadata plane (`core.layout.policy_arrays`) after a hit —
    LRU-by-batch stamps the batch clock at [slot, col]. Both the jnp
    reference and the Pallas kernel derive the column with the same
    first-match argmax over the bucket row, so metadata stays bit-identical
    across modes (col of a miss is unspecified; callers mask by `found`)."""
    m = _resolve(mode)
    if m == "jnp":
        from repro.core import hashtable as ht
        return ht.fixed_find_cols(h, queries)
    from repro.kernels.hash_probe.ops import fixed_hash_find_cols
    return fixed_hash_find_cols(h, queries, interpret=(m == "interpret"))


# ---------------------------------------------------------------------------
# reference-only probes (routed here so kernelizing one is a local change)
# ---------------------------------------------------------------------------

@_probe
def rand_skiplist_find(s, queries, mode: str | None = None):
    """Randomized-skiplist FIND — jnp only, refuses pallas (the
    MAX_GAP-padded walk has no static-shape kernel win; see
    docs/store_layers.md)."""
    _reference_only("rand_skiplist_find", mode)
    from repro.core import rand_skiplist as rsl
    return rsl.find_batch(s, queries)


@_probe
def twolevel_hash_find(h, queries, mode: str | None = None):
    """Two-level hash FIND — jnp only, refuses pallas (pooled L2
    indirection)."""
    _reference_only("twolevel_hash_find", mode)
    from repro.core import hashtable as ht
    return ht.twolevel_find(h, queries)


@_probe
def splitorder_find(h, queries, mode: str | None = None):
    """ONE-level split-order FIND — jnp only, refuses pallas: its
    searchsorted runs over the single global [C] array, which does not fit
    VMEM at production capacity (the two-level variant is the kernelized
    one)."""
    _reference_only("splitorder_find", mode)
    from repro.core import splitorder as so
    return so.splitorder_find(h, queries)


@_probe
def twolevel_splitorder_find(h, queries, mode: str | None = None):
    """Two-level split-order FIND: per-table searchsorted over the
    [T, C2] two-level layout (`kernels.splitorder_probe` under
    interpret/pallas — each probe touches one small table row, so the
    whole plane stack is VMEM-resident, unlike the one-level variant)."""
    m = _resolve(mode)
    if m == "jnp":
        from repro.core import splitorder as so
        return so.twolevel_splitorder_find(h, queries)
    from repro.kernels.splitorder_probe.ops import twolevel_splitorder_probe
    return twolevel_splitorder_probe(h, queries, interpret=(m == "interpret"))


@_probe
def spill_find(sp, queries, mode: str | None = None):
    """Cold spill-tier membership probe: (found[Q], vals[Q]). jnp only,
    refuses pallas — since the fused tier find, a per-run binary search over the
    `run_offsets` boundaries (`kernels.tier_find.ref.spill_find_runs`,
    O(runs * log run-len); the old flat masked compare is gone from every
    path). Standalone spill probes only run on the UNFUSED chain — the
    fused path folds this search into the single `tier_find` dispatch —
    so the cold tier keeps no dedicated kernel of its own."""
    _reference_only("spill_find", mode)
    from repro.store.tiers import spill_find_ref
    return spill_find_ref(sp, queries)


@_probe
def tier_find(hot, cold, spill, queries, mode: str | None = None,
              warm_layout: str = "level"):
    """FUSED tier-stack FIND — the whole hot -> warm -> cold chain as ONE
    dispatch per plan (`kernels.tier_find`): VMEM bucket probe, warm
    skiplist walk (level-major fan-out-4, or the block-major B-skiplist
    walk when `warm_layout="block"` — same results, fewer steps), per-run
    searchsorted over the spill boundaries. Returns
    ((hot found, vals, col), (warm found, vals), (spill found, vals)) with
    miss FALL-THROUGH applied: a warm hit only counts on a hot miss, a
    spill hit only on a hot+warm miss (under single-tier residency the
    masking never changes a result — it encodes the fall-through contract).
    `spill=None` (2-tier stacks) yields all-miss spill results. The hot
    `col` feeds the LRU policy's stamp refresh, same as `hash_find_cols`.
    Bit-identical to the unfused three-dispatch chain in every mode."""
    m = _resolve(mode)
    if m == "jnp":
        from repro.kernels.tier_find.ref import tier_find_ref
        hot_r, warm_r, sp_r = tier_find_ref(hot, cold, spill, queries,
                                            warm_layout=warm_layout)
    else:
        from repro.kernels.tier_find.ops import tier_find_fused
        hot_r, warm_r, sp_r = tier_find_fused(hot, cold, spill, queries,
                                              warm_layout=warm_layout,
                                              interpret=(m == "interpret"))
    import jax.numpy as jnp
    f_hot, v_hot, c_hot = hot_r
    f_warm, v_warm = warm_r
    f_sp, v_sp = sp_r
    f_warm = f_warm & ~f_hot
    f_sp = f_sp & ~f_hot & ~f_warm
    # a masked-off lane's value stays zero (the shared miss convention)
    return ((f_hot, v_hot, c_hot),
            (f_warm, jnp.where(f_warm, v_warm, jnp.uint64(0))),
            (f_sp, jnp.where(f_sp, v_sp, jnp.uint64(0))))


# ---------------------------------------------------------------------------
# update dispatches (the write half of an apply)
# ---------------------------------------------------------------------------

@_update
def hot_update(hot, meta, clock, keys, vals, mask, policy, max_evict,
               mode: str | None = None):
    """Hot-tier insert prologue as ONE counted update dispatch — the
    UNFUSED write path (membership probes already ran). jnp only, refuses
    pallas: the sort/scatter prologue (`bucket_insert_plan` + victim
    selection) is gather/scatter-bound with no kernel win of its own; the
    fused `tier_apply` is the kernelized form. Returns
    (hot', meta', ins[K], exists[K], ev_key[K], ev_val[K], ev_mask[K]);
    for `policy == "none"` the eviction lanes are all-miss zeros and meta
    passes through unchanged."""
    _reference_only("hot_update", mode)
    import jax.numpy as jnp
    from repro.kernels.tier_apply.ref import hot_insert_evict
    if policy == "none":
        from repro.core import hashtable as ht
        hot2, ins, exists = ht.fixed_insert(hot, keys, vals, mask)
        k = keys.shape[0]
        return (hot2, meta, ins, exists,
                jnp.zeros((k,), jnp.uint64), jnp.zeros((k,), jnp.uint64),
                jnp.zeros((k,), bool))
    return hot_insert_evict(hot, meta, clock, keys, vals, mask,
                            policy, max_evict)


@_update
def tier_apply(hot, meta, clock, cold, spill, keys, vals, mask, policy,
               max_evict, mode: str | None = None,
               warm_layout: str = "level"):
    """FUSED tier-stack APPLY prologue — membership probes + the hot-tier
    insert plan + victim selection as ONE dispatch per plan
    (`kernels.tier_apply`): the `tier_find` probe chain (bucket probe,
    warm walk in the selected `warm_layout` — level-major or the blocked
    B-skiplist — per-run spill search with the `run_offsets` plane
    scalar-prefetched so spill chunks stream through VMEM), then the
    sorted insert prologue (dup/exists/candidate lanes, nth-empty column,
    eviction-rank victim selection off the policy metadata plane) inside
    the same launch; the u64 scatters commit in the glue. Returns
    (hot', meta', in_warm[K], in_spill[K], ins[K], exists[K],
    ev_key[K], ev_val[K], ev_mask[K]) — `in_warm`/`in_spill` carry the
    same fall-through masking as `tier_find`, so the caller's demote
    routing sees identical lanes fused and unfused. `spill=None` (2-tier
    stacks) yields all-miss spill lanes. Bit-identical to the unfused
    probes + `hot_update` chain in every mode."""
    m = _resolve(mode)
    if m == "jnp":
        from repro.kernels.tier_apply.ref import tier_apply_ref
        return tier_apply_ref(hot, meta, clock, cold, spill, keys, vals,
                              mask, policy, max_evict,
                              warm_layout=warm_layout)
    from repro.kernels.tier_apply.ops import tier_apply_fused
    return tier_apply_fused(hot, meta, clock, cold, spill, keys, vals,
                            mask, policy, max_evict,
                            warm_layout=warm_layout,
                            interpret=(m == "interpret"))
