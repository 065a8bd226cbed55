"""Mixture-of-Experts FFN with the paper's routing as the dispatch layer.

Token -> expert dispatch IS the paper's key -> NUMA-owner routing (§I/§VI):
the expert id plays the role of the key's top bits, experts are the "NUMA
domains" sharded over the model axis, and the two dispatch implementations
mirror the paper's two memory regimes:

  * "replicated_psum"  — activations replicated over the model axis; every
    expert shard computes its experts for all tokens it can see, partial
    outputs are psum-combined. No all_to_all; collective = one psum of the
    output. The remote-access-heavy baseline.
  * "routed_a2a"       — tokens bucketized by owner shard (capacity-bounded,
    deterministic linearization — core.routing.bucketize) and moved with
    all_to_all over the model axis, computed NUMA-locally, moved back.
    The paper's design; collective = 2 x all_to_all of only the routed
    tokens (top-k/E of the psum bytes). See EXPERIMENTS.md §Perf.

Router: softmax top-k, optional probability renormalization (qwen3), plus a
load-balancing auxiliary loss (Switch-style).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.routing import bucketize
from repro.models.common import cast, dense_init


def init_moe(key, cfg):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, e, cfg.param_dtype, scale=0.02),
        "wi": (jax.random.normal(ks[1], (e, d, f), jnp.float32) / jnp.sqrt(d)
               ).astype(cfg.param_dtype),
        "wu": (jax.random.normal(ks[2], (e, d, f), jnp.float32) / jnp.sqrt(d)
               ).astype(cfg.param_dtype),
        "wd": (jax.random.normal(ks[3], (e, f, d), jnp.float32) / jnp.sqrt(f)
               ).astype(cfg.param_dtype),
    }
    if cfg.n_shared_experts:
        from repro.models.mlp import init_mlp
        p["shared"] = init_mlp(ks[4], d, cfg.d_expert * cfg.n_shared_experts,
                               cfg.param_dtype)
    return p


def router_probs(p, cfg, x):
    """x: [T, D] -> (weights [T, k], experts [T, k], aux_loss scalar)."""
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.n_experts_active
    w, idx = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # Switch aux loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    dispatch = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    f_e = jnp.mean(dispatch, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return w, idx.astype(jnp.int32), aux


def _expert_ffn(wi, wu, wd, xe, compute_dtype):
    """xe: [E_local, C, D] bucketed tokens -> [E_local, C, D]."""
    g = jnp.einsum("ecd,edf->ecf", xe, cast(wi, compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, cast(wu, compute_dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, cast(wd, compute_dtype))


def moe_dense_ffn(p, cfg, x2d):
    """Reference dispatch (tiny/smoke scale): bucketize into [E, C, D] on one
    shard, no collectives. Returns (y2d, aux)."""
    t, d = x2d.shape
    k = cfg.n_experts_active
    e = cfg.n_experts
    w, idx, aux = router_probs(p, cfg, x2d)
    # flatten (token, choice) pairs -> bucketize by expert
    flat_dest = idx.reshape(-1)
    flat_w = w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    cap = max(1, int(2 * t * k / e) + 8)
    (tok_b, w_b), valid, dropped = bucketize(
        flat_dest, jnp.ones_like(flat_dest, bool),
        [flat_tok, flat_w.astype(jnp.float32)], e, cap)
    xe = jnp.where(valid[..., None], x2d[tok_b], 0)          # [E, C, D]
    ye = _expert_ffn(p["wi"], p["wu"], p["wd"], xe, cfg.compute_dtype)
    ye = ye * w_b[..., None].astype(ye.dtype)
    y = jnp.zeros_like(x2d).at[jnp.where(valid, tok_b, t).reshape(-1)].add(
        ye.reshape(e * cap, d), mode="drop")
    if cfg.n_shared_experts:
        from repro.models.mlp import mlp
        y = y + mlp(p["shared"], x2d, cfg.compute_dtype)
    return y, aux


def moe_replicated_psum(p, cfg, x2d, axis: str):
    """EP over `axis` (model): experts sharded, tokens replicated, psum
    combine. Runs inside shard_map: p['wi'] etc. arrive [E_local, D, F]."""
    t, d = x2d.shape
    e_local = p["wi"].shape[0]
    size = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis).astype(jnp.int32)
    w, idx, aux = router_probs(p, cfg, x2d)      # router replicated
    k = cfg.n_experts_active
    flat_dest = idx.reshape(-1)
    flat_w = w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    mine = (flat_dest // e_local) == me
    local_e = flat_dest % e_local
    cap = max(1, int(2 * t * k / cfg.n_experts) + 8)
    (tok_b, w_b), valid, dropped = bucketize(
        local_e, mine, [flat_tok, flat_w.astype(jnp.float32)], e_local, cap)
    xe = jnp.where(valid[..., None], x2d[tok_b], 0)
    ye = _expert_ffn(p["wi"], p["wu"], p["wd"], xe, cfg.compute_dtype)
    ye = ye * w_b[..., None].astype(ye.dtype)
    y = jnp.zeros_like(x2d).at[jnp.where(valid, tok_b, t).reshape(-1)].add(
        ye.reshape(-1, d), mode="drop")
    # f32 reduction (bf16 all-reduce promotion crashes XLA:CPU; f32 accumulate
    # is also the numerically-right choice for a 16-way combine)
    y = jax.lax.psum(y.astype(jnp.float32), axis).astype(y.dtype)
    # (shared expert is applied OUTSIDE the manual region — blocks._ffn_apply)
    return y, jnp.float32(aux)


def moe_routed_a2a(p, cfg, x2d, axis: str, capacity_factor: float | None = None):
    """The paper's routing: tokens sharded over `axis` (sequence-split),
    bucketized by owner shard, all_to_all out, expert FFN NUMA-locally,
    all_to_all back. Collective bytes ~ top-k routed tokens only."""
    t, d = x2d.shape                              # t = local tokens
    if capacity_factor is None:
        capacity_factor = getattr(cfg, "moe_capacity_factor", 2.0)
    e_local = p["wi"].shape[0]
    size = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis).astype(jnp.int32)
    w, idx, aux = router_probs(p, cfg, x2d)
    k = cfg.n_experts_active
    flat_dest = idx.reshape(-1)                   # global expert id
    flat_w = w.reshape(-1).astype(jnp.float32)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    owner = flat_dest // e_local                  # owner shard on `axis`

    cap = max(1, int(capacity_factor * t * k / size) + 8)
    (x_b, w_b, tok_b, e_b), valid, dropped = bucketize(
        owner, jnp.ones_like(owner, bool),
        [x2d[flat_tok].astype(cfg.compute_dtype), flat_w, flat_tok, flat_dest],
        size, cap)
    # out: [size, cap, ...] -> exchange (the queue hop to the owner NUMA node)
    a2a = lambda v: jax.lax.all_to_all(v, axis, 0, 0, tiled=False)
    x_r = a2a(x_b)
    w_r = a2a(w_b)
    e_r = a2a(e_b)
    val_r = a2a(valid.astype(jnp.uint8)).astype(bool)

    # local expert compute: bucketize arrivals by local expert
    xf = x_r.reshape(size * cap, d)
    ef = (e_r % e_local).reshape(-1)
    vf = val_r.reshape(-1)
    cap2 = max(1, int(capacity_factor * size * cap / max(e_local, 1)) + 8)
    (pos_b,), valid2, dropped2 = bucketize(
        ef, vf, [jnp.arange(size * cap, dtype=jnp.int32)], e_local, cap2)
    xe = jnp.where(valid2[..., None], xf[pos_b], 0)
    ye = _expert_ffn(p["wi"], p["wu"], p["wd"], xe, cfg.compute_dtype)
    yf = jnp.zeros_like(xf).at[
        jnp.where(valid2, pos_b, size * cap).reshape(-1)].set(
        ye.reshape(-1, d), mode="drop")

    # route back (reverse hop) and weighted-combine at the source
    y_r = a2a(yf.reshape(size, cap, d))
    w_back = w_b                                  # weights never left home order
    tok_back = tok_b
    val_back = valid
    y = jnp.zeros((t, d), y_r.dtype).at[
        jnp.where(val_back, tok_back, t).reshape(-1)].add(
        (y_r * w_back[..., None].astype(y_r.dtype)).reshape(-1, d), mode="drop")
    # (shared expert is applied OUTSIDE the manual region — blocks._ffn_apply)
    return y.astype(jnp.dtype(cfg.compute_dtype)), jnp.float32(aux)
