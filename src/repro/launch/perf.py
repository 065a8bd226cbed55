import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# a CPU-only tool: on a host with a TPU it must not take the chip
os.environ["JAX_PLATFORMS"] = "cpu"

"""§Perf hillclimb driver: evaluate config variants against a cell's baseline
roofline terms (hypothesis -> change -> re-lower -> before/after).

  PYTHONPATH=src python -m repro.launch.perf --arch llama3-405b \
      --shape train_4k --variants flash remat_off mb32

Known variants (composable, comma-free names):
  flash      attn_impl=xla_flash     (online-softmax double loop: removes
                                      [.., Sk]-wide score traffic)
  kvfp8      kv_cache_dtype=float8   (halves decode cache bytes vs bf16)
  moe_psum   moe_impl=replicated_psum (the remote-heavy MoE baseline)
  moe_a2a    moe_impl=routed_a2a      (the paper's routing)
  remat_off  remat=False
  mb<k>      microbatches=k
  bq<k>      attn_block_q=k
  chunk<k>   scan_chunk=k
"""
import argparse
import json
import re

import repro  # noqa: F401
from repro.launch.roofline import analyze_cell


def parse_variant(v: str):
    if v == "flash":
        return {"attn_impl": "xla_flash"}, None
    if v == "kvfp8":
        return {"kv_cache_dtype": "float8_e4m3fn"}, None
    if v == "moe_psum":
        return {"moe_impl": "replicated_psum"}, None
    if v == "moe_a2a":
        return {"moe_impl": "routed_a2a"}, None
    if v == "remat_off":
        return {"remat": False}, None
    m = re.fullmatch(r"mb(\d+)", v)
    if m:
        return {}, int(m.group(1))
    m = re.fullmatch(r"bq(\d+)", v)
    if m:
        return {"attn_block_q": int(m.group(1))}, None
    m = re.fullmatch(r"chunk(\d+)", v)
    if m:
        return {"scan_chunk": int(m.group(1))}, None
    if v == "ssmbf16":
        return {"ssm_scan_dtype": "bfloat16"}, None
    m = re.fullmatch(r"cf(\d+)", v)   # cf125 -> capacity factor 1.25
    if m:
        return {"moe_capacity_factor": int(m.group(1)) / 100.0}, None
    if v == "seq2d":
        return {"decode_shard": "seq2d"}, None
    if v == "podcomp":
        return {"pod_compress": True}, None
    raise SystemExit(f"unknown variant {v}")


def run_variant(arch, shape, multi_pod, overrides, microbatches):
    from repro.launch.dryrun import lower_cell
    full = lower_cell(arch, shape, multi_pod, microbatches=microbatches,
                      overrides=overrides or None)
    return analyze_cell(arch, shape, multi_pod, full_report=full,
                        overrides=overrides or None)


def fmt(rep):
    t = rep["terms"]
    mem = rep.get("memory", {})
    return (f"comp={t['compute_s']*1e3:9.2f}ms mem={t['memory_s']*1e3:9.2f}ms "
            f"coll={t['collective_s']*1e3:9.2f}ms dom={rep['dominant'][:-2]:10s} "
            f"useful={rep['useful_ratio']:.2f} roofline={rep['roofline_fraction']:.3f} "
            f"tempGiB={mem.get('temp_bytes', 0)/2**30:.1f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variants", nargs="+", default=[])
    ap.add_argument("--combine", nargs="*", default=None,
                    help="additionally evaluate all listed variants together")
    ap.add_argument("--out", default="reports/perf")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    tag = f"{args.arch}__{args.shape}__{'2x16x16' if args.multi_pod else '16x16'}"

    base = run_variant(args.arch, args.shape, args.multi_pod, {}, None)
    print(f"BASE      {tag}\n          {fmt(base)}", flush=True)
    results = {"baseline": base}
    for v in args.variants:
        ov, mb = parse_variant(v)
        rep = run_variant(args.arch, args.shape, args.multi_pod, ov, mb)
        results[v] = rep
        dom = base["dominant"]
        delta = (1 - rep["terms"][dom] / base["terms"][dom]) * 100
        print(f"VAR {v:10s} {fmt(rep)}\n          baseline-dominant({dom[:-2]}) "
              f"delta: {delta:+.1f}%", flush=True)
    if args.combine:
        ov_all, mb_all = {}, None
        for v in args.combine:
            ov, mb = parse_variant(v)
            ov_all.update(ov)
            mb_all = mb or mb_all
        rep = run_variant(args.arch, args.shape, args.multi_pod, ov_all, mb_all)
        results["+".join(args.combine)] = rep
        print(f"COMBINED  {fmt(rep)}", flush=True)
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
