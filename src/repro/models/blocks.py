"""Decoder blocks, one per architecture family, with a uniform scan-friendly
signature: every layer of an arch shares one block structure (heterogeneous
patterns — xlstm's 7:1 mLSTM:sLSTM, hymba's global-attention layers — are
expressed as a fixed period of positions, scanned over groups).

Block kinds: dense | moe | mlstm | slstm | hymba
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as att
from repro.models import ssm
from repro.models.common import cast, rms_norm
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe


def block_kinds(cfg) -> list[str]:
    """The per-period list of block kinds for this config."""
    if cfg.block_pattern == "xlstm":
        p = cfg.slstm_every or 8
        return ["mlstm"] * (p - 1) + ["slstm"]
    if cfg.block_pattern == "hybrid":
        return ["hymba"]
    if cfg.is_moe:
        return ["moe"]
    return ["dense"]


def layer_windows(cfg) -> jnp.ndarray:
    """Per-layer sliding-window sizes (0 = global), [n_layers] int32.

    hymba: full attention on layer 0, the middle layer and the last layer;
    sliding window elsewhere (arXiv:2411.13676)."""
    n = cfg.n_layers
    if cfg.sliding_window <= 0:
        return jnp.zeros((n,), jnp.int32)
    w = jnp.full((n,), cfg.sliding_window, jnp.int32)
    for g in (0, n // 2, n - 1):
        w = w.at[g].set(0)
    return w


def init_block(key, cfg, kind: str):
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.ones((cfg.d_model,), cfg.param_dtype)}
    if kind in ("dense", "moe", "hymba"):
        p["attn"] = (att.init_mla(ks[0], cfg) if cfg.attn_type == "mla"
                     else att.init_gqa(ks[0], cfg))
        p["ln2"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
        if kind == "moe":
            p["ffn"] = init_moe(ks[1], cfg)
        else:
            p["ffn"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.param_dtype)
        if kind == "hymba":
            p["mamba"] = ssm.init_mamba(ks[2], cfg)
            p["attn_ln"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
            p["mamba_ln"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
    elif kind == "mlstm":
        p["cell"] = ssm.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["cell"] = ssm.init_slstm(ks[0], cfg)
        p["ln2"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
        p["ffn"] = init_mlp(ks[1], cfg.d_model,
                            max(cfg.d_ff, 4 * cfg.d_model // 3), cfg.param_dtype)
    return p


def _ffn_apply(p, cfg, x):
    """MLP or MoE on [B, S, D]; returns (y, aux)."""
    if cfg.is_moe and "router" in p:
        from repro.models import moe as moe_mod
        b, s, d = x.shape
        x2 = x.reshape(b * s, d)
        impl = cfg.moe_impl
        if impl == "dense":
            y2, aux = moe_mod.moe_dense_ffn(p, cfg, x2)
        else:
            y2, aux = _moe_sharded(p, cfg, x2, impl)
            if cfg.n_shared_experts:
                # shared expert runs under GSPMD auto-sharding (its weights
                # are TP-sharded like a dense MLP; no manual collectives)
                y2 = y2 + mlp(p["shared"], x2, cfg.compute_dtype)
        return y2.reshape(b, s, d).astype(x.dtype), aux
    return mlp(p, x, cfg.compute_dtype), jnp.float32(0.0)


def _moe_sharded(p, cfg, x2d, impl: str):
    """Nested shard_map over the model axis (GSPMD auto elsewhere)."""
    from jax.sharding import PartitionSpec as P
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import current_mesh, mesh_cfg

    mesh = current_mesh()
    if mesh is None:  # single-device smoke: fall back to reference dispatch
        return moe_mod.moe_dense_ffn(p, cfg, x2d)
    mc = mesh_cfg()
    tp = mc["tp_axis"]
    dp = tuple(mc["dp_axes"])
    # fully-manual region over (dp..., tp): GSPMD makes zero resharding
    # decisions inside the dispatch (its gather-resharding fallback emits
    # invalid programs on some (arch x mesh) combos — observed llama4@16x16)
    manual = set(dp) | {tp}

    def _mean_aux(aux):
        for a in manual:
            aux = jax.lax.pmean(aux, a)
        return aux

    tok_spec = P((*dp, tp), None)           # tokens split over all axes
    if impl == "routed_a2a":
        def fn(pp, xx):
            y, aux = moe_mod.moe_routed_a2a(pp, cfg, xx, tp)
            return y, _mean_aux(aux)
        in_specs = (_expert_specs(p, tp), tok_spec)
        out_specs = (tok_spec, P())
    else:
        def fn(pp, xx):
            y, aux = moe_mod.moe_replicated_psum(pp, cfg, xx, tp)
            return y, _mean_aux(aux)
        in_specs = (_expert_specs(p, tp), P(tuple(dp), None))
        out_specs = (P(tuple(dp), None), P())
    y2, aux = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, axis_names=manual,
                            check_vma=False)(p, x2d)
    return y2, aux


def _expert_specs(p, tp):
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        if name in ("wi", "wu", "wd") and leaf.ndim == 3:
            return P(tp, None, None)       # experts over the model axis
        return P(*([None] * leaf.ndim))
    return jax.tree_util.tree_map_with_path(spec, p)


# ---------------------------------------------------------------------------
# forward / prefill / decode per block
# ---------------------------------------------------------------------------

def block_forward(p, cfg, kind, x, positions, window):
    """x: [B,S,D] -> (x', aux)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("dense", "moe"):
        a = (att.mla_forward(p["attn"], cfg, h, positions, window)
             if cfg.attn_type == "mla"
             else att.gqa_forward(p["attn"], cfg, h, positions, window))
        x = x + a
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        f, aux = _ffn_apply(p["ffn"], cfg, h2)
        return x + f, aux
    if kind == "hymba":
        a = att.gqa_forward(p["attn"], cfg, h, positions, window)
        m, _ = ssm.mamba_forward(p["mamba"], cfg, h)
        a = rms_norm(a, p["attn_ln"], cfg.norm_eps)
        m = rms_norm(m, p["mamba_ln"], cfg.norm_eps)
        x = x + 0.5 * (a + m)
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        f, aux = _ffn_apply(p["ffn"], cfg, h2)
        return x + f, aux
    if kind == "mlstm":
        y, _ = ssm.mlstm_forward(p["cell"], cfg, h)
        return x + y, jnp.float32(0.0)
    if kind == "slstm":
        y, _ = ssm.slstm_forward(p["cell"], cfg, h)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp(p["ffn"], h2, cfg.compute_dtype), jnp.float32(0.0)
    raise ValueError(kind)


def block_init_cache(cfg, kind, batch: int, cache_len: int):
    ct = jnp.dtype(cfg.compute_dtype)
    kt = jnp.dtype(cfg.kv_cache_dtype)
    if kind in ("dense", "moe"):
        if cfg.attn_type == "mla":
            return {"ckv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), kt),
                    "kpe": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), kt)}
        dh = cfg.resolved_head_dim
        return {"k": jnp.zeros((batch, cache_len, cfg.n_kv_heads, dh), kt),
                "v": jnp.zeros((batch, cache_len, cfg.n_kv_heads, dh), kt)}
    if kind == "hymba":
        dh = cfg.resolved_head_dim
        di = cfg.ssm_expand * cfg.d_model
        return {"k": jnp.zeros((batch, cache_len, cfg.n_kv_heads, dh), kt),
                "v": jnp.zeros((batch, cache_len, cfg.n_kv_heads, dh), kt),
                "conv": jnp.zeros((batch, cfg.ssm_conv - 1, di), ct),
                "ssm": jnp.zeros((batch, di, cfg.ssm_state), jnp.float32)}
    if kind == "mlstm":
        st = ssm.mlstm_init_state(cfg, batch)
        return st
    if kind == "slstm":
        return ssm.slstm_init_state(cfg, batch)
    raise ValueError(kind)


def block_prefill(p, cfg, kind, x, positions, cache_len, window, past=None):
    """Returns (x', cache, aux). `past`: roped prefix KV (dense/GQA only) —
    prefix-cache reuse skips recomputing the shared pages."""
    b = x.shape[0]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("dense", "moe"):
        if cfg.attn_type == "mla":
            a, cache = att.mla_prefill(p["attn"], cfg, h, positions, cache_len, window)
        else:
            a, cache = att.gqa_prefill(p["attn"], cfg, h, positions, cache_len,
                                       window, past=past)
        x = x + a
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        f, aux = _ffn_apply(p["ffn"], cfg, h2)
        return x + f, cache, aux
    if kind == "hymba":
        a, kv = att.gqa_prefill(p["attn"], cfg, h, positions, cache_len, window)
        st0 = {"conv": jnp.zeros((b, cfg.ssm_conv - 1,
                                  cfg.ssm_expand * cfg.d_model), h.dtype),
               "ssm": jnp.zeros((b, cfg.ssm_expand * cfg.d_model,
                                 cfg.ssm_state), jnp.float32)}
        m, st = ssm.mamba_forward(p["mamba"], cfg, h, st0)
        a = rms_norm(a, p["attn_ln"], cfg.norm_eps)
        m = rms_norm(m, p["mamba_ln"], cfg.norm_eps)
        x = x + 0.5 * (a + m)
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        f, aux = _ffn_apply(p["ffn"], cfg, h2)
        return x + f, {**kv, **st}, aux
    if kind == "mlstm":
        y, st = ssm.mlstm_forward(p["cell"], cfg, h,
                                  ssm.mlstm_init_state(cfg, b))
        return x + y, st, jnp.float32(0.0)
    if kind == "slstm":
        y, st = ssm.slstm_forward(p["cell"], cfg, h, ssm.slstm_init_state(cfg, b))
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp(p["ffn"], h2, cfg.compute_dtype), st, jnp.float32(0.0)
    raise ValueError(kind)


def block_decode(p, cfg, kind, x, pos, cache, window):
    """x: [B,1,D]; returns (x', cache')."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("dense", "moe"):
        if cfg.attn_type == "mla":
            a, cache = att.mla_decode(p["attn"], cfg, h, pos, cache, window)
        else:
            a, cache = att.gqa_decode(p["attn"], cfg, h, pos, cache, window)
        x = x + a
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe and "router" in p["ffn"]:
            from repro.models.moe import moe_dense_ffn
            b, s, d = h2.shape
            f, _ = moe_dense_ffn(p["ffn"], cfg, h2.reshape(b, d))
            f = f.reshape(b, 1, d).astype(x.dtype)
        else:
            f = mlp(p["ffn"], h2, cfg.compute_dtype)
        return x + f, cache
    if kind == "hymba":
        kv = {"k": cache["k"], "v": cache["v"]}
        a, kv = att.gqa_decode(p["attn"], cfg, h, pos, kv, window)
        st = {"conv": cache["conv"], "ssm": cache["ssm"]}
        m, st = ssm.mamba_decode(p["mamba"], cfg, h, st)
        a = rms_norm(a, p["attn_ln"], cfg.norm_eps)
        m = rms_norm(m, p["mamba_ln"], cfg.norm_eps)
        x = x + 0.5 * (a + m)
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp(p["ffn"], h2, cfg.compute_dtype), {**kv, **st}
    if kind == "mlstm":
        y, st = ssm.mlstm_decode(p["cell"], cfg, h, cache)
        return x + y, st
    if kind == "slstm":
        y, st = ssm.slstm_decode(p["cell"], cfg, h, cache)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp(p["ffn"], h2, cfg.compute_dtype), st
    raise ValueError(kind)
