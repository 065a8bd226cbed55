"""jit'd wrapper: DetSkiplist state -> shared level-major layout
(`repro.core.layout.skiplist_layout`) -> batched Pallas pop rank-select.

`pq_pop_ranks` is the unjitted entry the `repro.store.exec` dispatch layer
calls from inside already-jitted store steps; it matches the contract of
`core.det_skiplist.pop_rank_select` bit for bit (same live-prefix formula,
same found/KEY_INF/idx=0 masking of not-found lanes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bits import KEY_INF
from repro.core.layout import skiplist_layout
from repro.kernels.pq_pop.kernel import pq_pop_tiles
from repro.kernels.rows import query_tiles


def pq_pop_ranks(s, ranks, mask, *, tile: int | None = None,
                 interpret: bool = True):
    """Rank-select the rank-th smallest live key per lane on a DetSkiplist
    via the Pallas kernel — same contract as det_skiplist.pop_rank_select:
    (found bool[K], keys u64[K], idx int32[K]). Not jitted: callable from
    inside jitted/shard_mapped store steps."""
    t = ranks.shape[0]
    tp, tile = query_tiles(t, tile)
    rp = jnp.pad(jnp.asarray(ranks, jnp.int32), (0, tp - t),
                 constant_values=-1)
    mp = jnp.pad(jnp.asarray(mask, bool), (0, tp - t)).astype(jnp.int32)
    lay = skiplist_layout(s)
    # named scope: visible as obs.kernel.pq_pop in jax.profiler timelines /
    # lowered HLO (span taxonomy in store/obs.py)
    with jax.named_scope("obs.kernel.pq_pop"):
        found, idx = pq_pop_tiles(
            rp, mp, lay.lvl_hi, lay.lvl_lo, lay.lvl_child,
            lay.term_hi, lay.term_lo, lay.term_mark,
            tile=tile, interpret=interpret)
    found = found[:t].astype(bool)
    idx = jnp.where(found, jnp.clip(idx[:t], 0, s.capacity - 1), 0)
    keys = jnp.where(found, s.term_keys[idx], KEY_INF)
    return found, keys, idx
