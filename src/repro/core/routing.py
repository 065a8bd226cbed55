"""Hierarchical NUMA->mesh key routing (paper §I, §VI, §VII).

The paper's pattern: partition the key space by top key bits, one structure
instance per NUMA node; per-thread lock-free queues carry each key to a
thread pinned on the owner node; all structure memory accesses stay local.
"Hierarchical usage of concurrent data structures ... reduces memory accesses
from remote NUMA nodes."

Mesh adaptation: NUMA node -> mesh shard; the queue hop -> `all_to_all`
inside `shard_map`; the hierarchy (socket -> node) -> routing one mesh axis
at a time, coarsest (slowest link) first: on the multi-pod mesh that is the
"pod" axis (DCI) then the "data" axis (ICI) — two-stage all-to-all, exactly
the paper's proposal of hierarchical structure usage. MoE expert dispatch
reuses this module with expert-id in place of key bits.

Everything here runs INSIDE a shard_map body. Buckets are capacity-bounded
(static shapes); overflow lanes are dropped and *counted* — the bounded
analogue of the paper's unbounded queues, with the drop count surfaced so
capacity factors can be tuned (and asserted zero in tests).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.bits import KEY_INF


class RouteResult(NamedTuple):
    keys: jnp.ndarray      # [P] routed keys (KEY_INF padding)
    vals: jnp.ndarray      # [P] routed payloads
    aux: jnp.ndarray       # [P] routed aux (e.g. op codes), int32
    origin: jnp.ndarray    # [P] uint64 packed (src_shard << 32 | src_lane)
    valid: jnp.ndarray     # [P] bool
    dropped: jnp.ndarray   # scalar int32 — capacity overflow count (telemetry)


def owner_of(keys: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Owner shard from the top key bits (paper: 3 MSBs -> 8 skiplists)."""
    b = int(math.log2(n_shards))
    if b == 0:
        return jnp.zeros(keys.shape, jnp.int32)
    return (keys >> jnp.uint64(64 - b)).astype(jnp.int32)


def bucketize(dest: jnp.ndarray, valid: jnp.ndarray, payloads: Sequence[jnp.ndarray],
              n_dest: int, capacity: int):
    """Group lanes by destination with per-destination capacity.

    Returns ([n_dest, capacity] buffers..., valid[n_dest, capacity], dropped).
    Deterministic: lanes sort stably by dest, overflow drops highest ranks.
    """
    sort_key = jnp.where(valid, dest, n_dest)   # invalid lanes park at n_dest
    order = jnp.argsort(sort_key, stable=True)
    sd = sort_key[order]                        # sorted — safe for searchsorted
    sv = valid[order]
    run_start = jnp.searchsorted(sd, sd, side="left").astype(jnp.int32)
    rank = jnp.arange(dest.shape[0], dtype=jnp.int32) - run_start
    keep = sv & (rank < capacity) & (sd < n_dest)
    dropped = jnp.sum(sv & ~keep, dtype=jnp.int32)
    slot = jnp.where(keep, sd * capacity + rank, n_dest * capacity)
    out = []
    for p in payloads:
        buf = jnp.zeros((n_dest * capacity,) + p.shape[1:], p.dtype)
        buf = buf.at[slot].set(p[order], mode="drop")
        out.append(buf.reshape((n_dest, capacity) + p.shape[1:]))
    vbuf = jnp.zeros((n_dest * capacity,), bool).at[slot].set(keep, mode="drop")
    return out, vbuf.reshape(n_dest, capacity), dropped


def _a2a(x: jnp.ndarray, name: str) -> jnp.ndarray:
    """all_to_all with bool transport (collectives move numeric payloads)."""
    if x.dtype == jnp.bool_:
        return jax.lax.all_to_all(x.astype(jnp.uint8), name, 0, 0,
                                  tiled=False).astype(bool)
    return jax.lax.all_to_all(x, name, 0, 0, tiled=False)


def shard_linear_id(axis_names: Sequence[str]) -> jnp.ndarray:
    """Flat shard id over the routing axes (row-major, coarsest first)."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name).astype(jnp.int32)
    return idx


def route_to_owners(keys: jnp.ndarray, vals: jnp.ndarray, aux: jnp.ndarray,
                    valid: jnp.ndarray, axis_names: Sequence[str],
                    axis_sizes: Sequence[int], pool: int) -> RouteResult:
    """Route (key, val, aux) to owner shards, one mesh axis per stage,
    coarsest first (pod -> data): the hierarchical NUMA route.

    Must run inside shard_map over (at least) `axis_names`. `pool` is the
    per-shard item budget after every stage (static).
    """
    n_shards = int(math.prod(axis_sizes))
    me = shard_linear_id(axis_names)
    lane = jnp.arange(keys.shape[0], dtype=jnp.uint32)
    origin = (me.astype(jnp.uint64) << jnp.uint64(32)) | lane.astype(jnp.uint64)

    dropped = jnp.int32(0)
    # digit weights, coarsest first: owner = d0 * (s1*s2..) + d1 * (s2..) + ...
    weights = []
    rem = n_shards
    for s in axis_sizes:
        rem //= s
        weights.append(rem)

    for name, size, w in zip(axis_names, axis_sizes, weights):
        owner = owner_of(keys, n_shards)
        digit = (owner // w) % size
        cap = max(1, -(-pool // size))
        (k_b, v_b, a_b, o_b), val_b, drop = bucketize(
            digit, valid, [keys, vals, aux, origin], size, cap)
        dropped = dropped + drop
        # the queue hop: chunk i -> shard with digit i on this axis
        k_b, v_b, a_b, o_b, val_b = (_a2a(k_b, name), _a2a(v_b, name),
                                     _a2a(a_b, name), _a2a(o_b, name),
                                     _a2a(val_b, name))
        flat = lambda x: x.reshape((size * cap,) + x.shape[2:])
        keys, vals, aux, origin, valid = map(flat, (k_b, v_b, a_b, o_b, val_b))
        # re-pack to the pool budget (compact valid lanes first)
        keys, vals, aux, origin, valid, drop2 = _compact(
            [keys, vals, aux, origin], valid, pool)
        dropped = dropped + drop2
    keys = jnp.where(valid, keys, KEY_INF)
    return RouteResult(keys=keys, vals=vals, aux=aux, origin=origin,
                       valid=valid, dropped=dropped)


def _compact(payloads: Sequence[jnp.ndarray], valid: jnp.ndarray, out_size: int):
    """Compact valid lanes to a prefix of a fixed-size pool. Returns
    (*payloads, valid, dropped) — overflow is counted, never silent."""
    rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
    keep = valid & (rank < out_size)
    dropped = jnp.sum(valid & ~keep, dtype=jnp.int32)
    slot = jnp.where(keep, rank, out_size)
    outs = []
    for p in payloads:
        buf = jnp.zeros((out_size,) + p.shape[1:], p.dtype)
        outs.append(buf.at[slot].set(p, mode="drop"))
    vout = jnp.zeros((out_size,), bool).at[slot].set(keep, mode="drop")
    return (*outs, vout, dropped)


def route_back(results: jnp.ndarray, found: jnp.ndarray, origin: jnp.ndarray,
               valid: jnp.ndarray, axis_names: Sequence[str],
               axis_sizes: Sequence[int], lanes_out: int):
    """Send per-op results back to their source shard + lane.

    Returns (results[lanes_out], found[lanes_out]) scattered into the original
    lane positions. Reverse hop order (finest axis first) — the return queue.
    """
    src = (origin >> jnp.uint64(32)).astype(jnp.int32)
    lane = (origin & jnp.uint64(0xFFFFFFFF)).astype(jnp.int32)
    pool = results.shape[0]

    weights = []
    rem = int(math.prod(axis_sizes))
    for s in axis_sizes:
        rem //= s
        weights.append(rem)

    for name, size, w in zip(reversed(axis_names), reversed(axis_sizes),
                             reversed(weights)):
        digit = (src // w) % size
        cap = max(1, -(-pool // size))
        (r_b, f_b, s_b, l_b), val_b, _ = bucketize(
            digit, valid, [results, found.astype(jnp.int32), src, lane], size, cap)
        r_b, f_b, s_b, l_b, val_b = (_a2a(r_b, name), _a2a(f_b, name),
                                     _a2a(s_b, name), _a2a(l_b, name),
                                     _a2a(val_b, name))
        flat = lambda x: x.reshape((size * cap,) + x.shape[2:])
        results, found_i, src, lane, valid = (flat(r_b), flat(f_b), flat(s_b),
                                              flat(l_b), flat(val_b))
        found = found_i.astype(bool)
        results, found_i2, src, lane, valid, _ = _compact(
            [results, found.astype(jnp.int32), src, lane], valid, pool)
        found = found_i2.astype(bool)

    # scatter into original lanes
    slot = jnp.where(valid, lane, lanes_out)
    out_r = jnp.zeros((lanes_out,) + results.shape[1:], results.dtype
                      ).at[slot].set(results, mode="drop")
    out_f = jnp.zeros((lanes_out,), bool).at[slot].set(found & valid, mode="drop")
    return out_r, out_f
