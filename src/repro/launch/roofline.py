import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# a CPU-only tool: on a host with a TPU it must not take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
# (must run in the dry-run's 512-device environment)

"""Roofline analysis from the compiled dry-run artifacts.

Terms per (arch x shape x mesh), all in seconds-per-step per device:
  compute term    = HLO_FLOPs_per_dev / peak_FLOPs      (197 TF/s bf16, v5e)
  memory term     = HLO_bytes_per_dev / HBM_bw          (819 GB/s)
  collective term = collective_bytes_per_dev / link_bw  (~50 GB/s/link ICI)

Scan-body correction (measured: XLA cost_analysis counts a scan body ONCE,
not x trip count): we lower each cell twice more with n_layers = period and
2 x period; the difference isolates the per-layer-group cost and the affine
extrapolation  total = base + n_groups * group  recovers true per-step
totals (collective bytes parsed from HLO text get the same treatment; the
optimizer/head live in `base`). Microbatch probes run mb=1 with the full
batch in one body, so totals need no mb factor.

MODEL_FLOPS = 6*N*D (train, D = tokens incl. frontend) or 2*N_active*B
(decode) — the ratio MODEL_FLOPS / HLO_FLOPs_total flags remat/redundancy
waste (>1/3 of compute non-useful is a §Perf target).
"""
import argparse
import json
import math

import jax

import repro  # noqa: F401
from repro.configs import ALL, get_config
from repro.configs.base import SHAPES, cells_for
from repro.launch.dryrun import MICROBATCHES, lower_cell
from repro.models.blocks import block_kinds

HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9}


def count_params(cfg, active_only: bool = False) -> float:
    """Analytic parameter count (embedding + blocks + head)."""
    d = cfg.d_model
    if cfg.family == "kvstore":
        return 0.0
    vp = cfg.padded_vocab
    emb = vp * d * (cfg.n_codebooks or 1)
    head = 0 if cfg.tie_embeddings else vp * d * (cfg.n_codebooks or 1)
    kinds = block_kinds(cfg)
    per_period = 0.0
    dh = cfg.resolved_head_dim
    for kind in kinds:
        p = 0.0
        if kind in ("dense", "moe", "hymba"):
            if cfg.attn_type == "mla":
                qd = cfg.qk_nope_dim + cfg.qk_rope_dim
                p += (d * cfg.q_lora_rank + cfg.q_lora_rank * cfg.n_heads * qd
                      + d * cfg.kv_lora_rank
                      + cfg.kv_lora_rank * cfg.n_heads
                      * (cfg.qk_nope_dim + cfg.v_head_dim)
                      + d * cfg.qk_rope_dim + cfg.n_heads * cfg.v_head_dim * d)
            else:
                p += d * (cfg.n_heads + 2 * cfg.n_kv_heads) * dh
                p += cfg.n_heads * dh * d
            if kind == "moe":
                e = cfg.n_experts_active if active_only else cfg.n_experts
                p += e * 3 * d * cfg.d_expert + d * cfg.n_experts
                p += cfg.n_shared_experts * 3 * d * cfg.d_expert
            else:
                p += 3 * d * cfg.d_ff
            if kind == "hymba":
                di = cfg.ssm_expand * d
                p += d * 2 * di + di * (1 + 2 * cfg.ssm_state) + di * d
        elif kind == "mlstm":
            di = cfg.ssm_expand * d
            p += d * 2 * di + 3 * di * di + di * 2 * cfg.n_heads + di * d
        elif kind == "slstm":
            p += d * 4 * d + d * 4 * d + d * d
            p += 3 * d * max(cfg.d_ff, 4 * d // 3)
        per_period += p
    n_groups = cfg.n_layers // len(kinds)
    return emb + head + per_period * n_groups


def model_flops(cfg, shape) -> float:
    n_act = count_params(cfg, active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    return 2.0 * n_act * shape.global_batch        # decode: one token/req


def _extract(rep):
    return (rep["flops"], rep["bytes_accessed"],
            rep["collective_bytes_total"])


def analyze_cell(arch: str, shape_name: str, multi_pod: bool,
                 full_report: dict | None = None,
                 overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    if full_report is None:
        full_report = lower_cell(arch, shape_name, multi_pod,
                                 overrides=overrides)
    out = dict(full_report)

    if cfg.family == "kvstore":
        flops_t, bytes_t, coll_t = _extract(full_report)
    else:
        period = len(block_kinds(cfg))
        # UNROLLED probes: scan_layers off + inner chunk scans disabled so
        # every op is counted x its true trip count (see §Method notes)
        unroll = {"scan_layers": False, "attn_block_q": 1 << 30,
                  "scan_chunk": 1 << 30, "remat": False}
        ov = dict(overrides or {})
        f1 = lower_cell(arch, shape_name, multi_pod, microbatches=1,
                        donate=False,
                        overrides={**ov, **unroll, "n_layers": period})
        f2 = lower_cell(arch, shape_name, multi_pod, microbatches=1,
                        donate=False,
                        overrides={**ov, **unroll, "n_layers": 2 * period})
        g = [b - a for a, b in zip(_extract(f1), _extract(f2))]
        base = [a - d for a, d in zip(_extract(f1), g)]
        ng = cfg.n_layers // period
        flops_t, bytes_t, coll_t = [max(b, 0) + ng * max(dd, 0)
                                    for b, dd in zip(base, g)]
        # remat recompute: the production step rematerializes each layer
        # group in the backward -> +1 forward pass of the group compute
        if shape.kind == "train" and cfg.remat:
            # fwd ~= 1/3 of fwd+bwd group flops
            flops_t = flops_t + ng * max(g[0], 0) / 3.0
        # sLSTM's time recurrence is a lax.scan the probes cannot unroll
        # (sequential): add its per-token flops analytically
        if cfg.block_pattern == "xlstm" and shape.kind != "decode":
            d = cfg.d_model
            tokens = shape.global_batch * shape.seq_len
            n_slstm = cfg.n_layers // (cfg.slstm_every or 8)
            mult = 3.0 if shape.kind == "train" else 1.0
            missing = (tokens - shape.global_batch) * 18 * d * d * mult
            flops_t += n_slstm * missing / full_report["devices"]

    terms = {
        "compute_s": flops_t / HW["peak_flops"],
        "memory_s": bytes_t / HW["hbm_bw"],
        "collective_s": coll_t / HW["link_bw"],
    }
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_total = flops_t * full_report["devices"]
    out.update({
        "flops_per_dev_corrected": flops_t,
        "bytes_per_dev_corrected": bytes_t,
        "collective_bytes_per_dev_corrected": coll_t,
        "terms": terms,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_total": hlo_total,
        "useful_ratio": (mf / hlo_total) if hlo_total else 0.0,
        "step_time_lb_s": max(terms.values()),
        "roofline_fraction": (mf / HW["peak_flops"] / full_report["devices"]
                              / max(terms.values())) if max(terms.values()) else 0.0,
    })
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="reports/roofline")
    ap.add_argument("--dryrun-dir", default="reports/dryrun")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    cells = []
    if args.all:
        for arch in ALL:
            names = (["train_4k"] if arch == "paper-kvstore" else cells_for(arch))
            for sh in names:
                cells.append((arch, sh))
    else:
        cells.append((args.arch, args.shape))

    for arch, sh in cells:
        tag = f"{arch}__{sh}__{'2x16x16' if args.multi_pod else '16x16'}"
        full = None
        fp = os.path.join(args.dryrun_dir, tag + ".json")
        if os.path.exists(fp):
            with open(fp) as f:
                full = json.load(f)
        try:
            rep = analyze_cell(arch, sh, args.multi_pod, full_report=full)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rep, f, indent=1)
            t = rep["terms"]
            print(f"{tag:58s} comp={t['compute_s']*1e3:8.2f}ms "
                  f"mem={t['memory_s']*1e3:8.2f}ms coll={t['collective_s']*1e3:8.2f}ms "
                  f"dom={rep['dominant'][:-2]:10s} useful={rep['useful_ratio']:.2f} "
                  f"roofline={rep['roofline_fraction']:.3f}", flush=True)
        except Exception as e:
            print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
