"""jit'd wrapper: DetSkiplist state -> shared level-major layout
(`repro.core.layout.skiplist_layout`) -> batched Pallas search.

`skiplist_find` is the unjitted entry the `repro.store.exec` dispatch layer
calls from inside already-jitted store steps; `skiplist_search` keeps the
standalone jitted contract of `core.det_skiplist.find_batch`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.bits import KEY_INF
from repro.core.layout import skiplist_layout, split_u64
from repro.kernels.rows import query_tiles
from repro.kernels.skiplist_search.kernel import skiplist_search_tiles


def stack_levels(s):
    """DetSkiplist -> ([L, C1] hi, lo, child) padded with +inf sentinels.
    (Compatibility veneer over `core.layout.skiplist_layout`.)"""
    lay = skiplist_layout(s)
    return lay.lvl_hi, lay.lvl_lo, lay.lvl_child


def skiplist_find(s, queries, *, tile: int | None = None,
                  interpret: bool = True):
    """Batched Find on a DetSkiplist via the Pallas kernel — same contract as
    core.det_skiplist.find_batch: (found bool[T], vals u64[T], idx int32[T]).
    Not jitted: callable from inside jitted/shard_mapped store steps."""
    t = queries.shape[0]
    tp, tile = query_tiles(t, tile)
    qp = jnp.pad(queries, (0, tp - t), constant_values=KEY_INF)
    qh, ql = split_u64(qp)
    lay = skiplist_layout(s)
    # named scope: visible as obs.kernel.skiplist_search in jax.profiler
    # timelines / lowered HLO (span taxonomy in store/obs.py)
    with jax.named_scope("obs.kernel.skiplist_search"):
        found, idx = skiplist_search_tiles(
            qh, ql, lay.lvl_hi, lay.lvl_lo, lay.lvl_child,
            lay.term_hi, lay.term_lo, lay.term_mark,
            tile=tile, interpret=interpret)
    found = found[:t].astype(bool) & (queries != KEY_INF)
    idx = idx[:t]
    vals = jnp.where(found, s.term_vals[jnp.clip(idx, 0, s.capacity - 1)],
                     jnp.uint64(0))
    return found, vals, idx


@partial(jax.jit, static_argnames=("tile", "interpret"))
def skiplist_search(s, queries, *, tile: int | None = None,
                    interpret: bool = True):
    """Jitted standalone form of `skiplist_find`."""
    return skiplist_find(s, queries, tile=tile, interpret=interpret)
