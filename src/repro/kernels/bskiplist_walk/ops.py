"""jit'd wrapper: DetSkiplist state -> shared block-major layout
(`repro.core.layout.bskiplist_layout`) -> batched Pallas B-skiplist search.

`bskiplist_find` is the unjitted entry the `repro.store.exec` dispatch
layer calls from inside already-jitted store steps; `bskiplist_search`
keeps the standalone jitted contract of `core.det_skiplist.find_batch`.
Same contract as `kernels.skiplist_search.ops` — the two walks are
interchangeable probe implementations over the same state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.bits import KEY_INF
from repro.core.layout import BSKIP_BLOCK, bskiplist_layout, split_u64
from repro.kernels.bskiplist_walk.kernel import bskiplist_walk_tiles
from repro.kernels.rows import query_tiles


def bskiplist_find(s, queries, *, block: int = BSKIP_BLOCK,
                   tile: int | None = None, interpret: bool = True):
    """Batched Find on a DetSkiplist via the blocked Pallas kernel — same
    contract as core.det_skiplist.find_batch: (found bool[T], vals u64[T],
    idx int32[T]). Not jitted: callable from inside jitted/shard_mapped
    store steps."""
    t = queries.shape[0]
    tp, tile = query_tiles(t, tile)
    qp = jnp.pad(queries, (0, tp - t), constant_values=KEY_INF)
    qh, ql = split_u64(qp)
    lay = bskiplist_layout(s, block)
    # named scope: visible as obs.kernel.bskiplist_walk in jax.profiler
    # timelines / lowered HLO (span taxonomy in store/obs.py)
    with jax.named_scope("obs.kernel.bskiplist_walk"):
        found, idx = bskiplist_walk_tiles(
            qh, ql, lay.blk_hi, lay.blk_lo,
            lay.term_hi, lay.term_lo, lay.term_mark,
            block=block, tile=tile, interpret=interpret)
    found = found[:t].astype(bool) & (queries != KEY_INF)
    idx = idx[:t]
    vals = jnp.where(found, s.term_vals[jnp.clip(idx, 0, s.capacity - 1)],
                     jnp.uint64(0))
    return found, vals, idx


@partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def bskiplist_search(s, queries, *, block: int = BSKIP_BLOCK,
                     tile: int | None = None, interpret: bool = True):
    """Jitted standalone form of `bskiplist_find`."""
    return bskiplist_find(s, queries, block=block, tile=tile,
                          interpret=interpret)
