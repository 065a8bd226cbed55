import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a CPU-only tool: on a host with a TPU it must not take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both MUST precede every other import — jax locks the device count and
#   platform on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost/collective data for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out reports/]

No arrays are ever materialized: params/optimizer/caches enter as
ShapeDtypeStructs with NamedShardings (jax.eval_shape over the init fns) and
jit(...).lower(...).compile() proves the distribution is coherent.
"""
import argparse
import json
import math
import re
import sys
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro  # noqa: F401  (x64)
from repro.configs import ALL, ARCHS, get_config
from repro.configs.base import SHAPES, cells_for
from repro.launch.mesh import dp_axes_of, make_production_mesh, n_devices
from repro.models import model as M
from repro.models.blocks import block_kinds
from repro.optim.adamw import adamw_init
from repro.parallel.sharding import param_spec, use_mesh
from repro.train.step import make_serve_decode, make_serve_prefill, make_train_step

# per-cell tuning (microbatches bound activation memory; these are the
# baseline settings — §Perf iterates them)
MICROBATCHES = {
    "llama3-405b": 16, "qwen1.5-110b": 8, "qwen3-moe-235b-a22b": 8,
    "llama4-scout-17b-a16e": 4, "llava-next-mistral-7b": 2,
}

COLLECTIVE_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*\(")
SHAPE_RE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16}


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-buffer bytes of every collective op (per-device program)."""
    out = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "= " not in line:
            continue
        kind = m.group(1)
        lhs = line.split("= ", 1)[1] if " = " in line else line
        sm = SHAPE_RE.search(lhs)
        if not sm:
            continue
        dt, dims = sm.group(1), sm.group(2)
        size = DTYPE_BYTES.get(dt, 4)
        for d in dims.split(","):
            if d:
                size *= int(d)
        e = out.setdefault(kind, {"count": 0, "bytes": 0})
        e["count"] += 1
        e["bytes"] += size
    return out


def shaped(tree, spec_fn, mesh):
    """eval_shape pytree -> ShapeDtypeStructs with NamedShardings."""
    def attach(path, leaf):
        sp = spec_fn(path, leaf)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, sp))
    return jax.tree_util.tree_map_with_path(attach, tree)


def _pathstr(path):
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _maybe_axes(dim, axes, mesh):
    if not axes:
        return None
    n = math.prod(mesh.shape[a] for a in axes)
    return axes if (n and dim % n == 0) else None


def param_specs_fn(mesh, dp_axes):
    def fn(path, leaf):
        ps = _pathstr(path)
        shape = leaf.shape
        if "blocks" in ps and len(shape) >= 1:
            inner = shape[1:]
            sp = param_spec(ps, inner, mesh, dp_axes, "model")
            return P(None, *sp)
        return param_spec(ps, shape, mesh, dp_axes, "model")
    return fn


def opt_specs_fn(mesh, dp_axes):
    pfn = param_specs_fn(mesh, dp_axes)

    def fn(path, leaf):
        ps = _pathstr(path)
        if ps.endswith("step") or leaf.ndim == 0:
            return P()
        # moments under "adam/m" / "adam/v" mirror the param tree paths
        return pfn(path[2:] if len(path) > 2 else path, leaf)
    return fn


def batch_specs_fn(mesh, dp_axes):
    def fn(path, leaf):
        dp = _maybe_axes(leaf.shape[0], dp_axes, mesh)
        return P(dp, *([None] * (leaf.ndim - 1)))
    return fn


def cache_specs_fn(cfg, mesh, dp_axes, batch):
    """Contiguous decode caches: batch over dp when divisible, the big
    context dim (seq / di / dk) over the model axis when divisible.

    decode_shard="seq2d" (§Perf lever): batch replicated, the cache seq dim
    sharded over (dp..., model) jointly — weights stay stationary and the
    per-step collectives shrink to partial-softmax stats."""
    seq2d = getattr(cfg, "decode_shard", "batch") == "seq2d"
    seq_axes = (*dp_axes, "model") if seq2d else ("model",)

    def fn(path, leaf):
        ps = _pathstr(path)
        name = ps.split("/")[-1]
        shape = leaf.shape  # leading ng stack dim
        dpax = None if seq2d else _maybe_axes(batch, dp_axes, mesh)
        rest = [None] * (leaf.ndim - 2)
        if name in ("k", "v") and leaf.ndim == 5:        # [ng,B,S,Hkv,Dh]
            mod = _maybe_axes(shape[2], seq_axes, mesh)
            return P(None, dpax, mod, None, None)
        if name in ("ckv", "kpe") and leaf.ndim == 4:    # [ng,B,S,r]
            mod = _maybe_axes(shape[2], seq_axes, mesh)
            return P(None, dpax, mod, None)
        if name == "c" and leaf.ndim == 5:               # mlstm [ng,B,H,dk,dv]
            mod = _maybe_axes(shape[4], ("model",), mesh)
            return P(None, dpax, None, None, mod)
        if name in ("ssm",) and leaf.ndim == 4:          # [ng,B,di,n]
            mod = _maybe_axes(shape[2], ("model",), mesh)
            return P(None, dpax, mod, None)
        if name in ("conv",) and leaf.ndim == 4:         # [ng,B,K-1,di]
            mod = _maybe_axes(shape[3], ("model",), mesh)
            return P(None, dpax, None, mod)
        if leaf.ndim >= 3:                               # slstm [ng,B,d] etc.
            mod = _maybe_axes(shape[-1], ("model",), mesh)
            return P(None, dpax, *([None] * (leaf.ndim - 3)), mod)
        return P(*([None] * leaf.ndim))
    return fn


def make_inputs_train(cfg, shape, mesh, dp_axes):
    b, s = shape.global_batch, shape.seq_len
    bs = batch_specs_fn(mesh, dp_axes)
    dp = _maybe_axes(b, dp_axes, mesh)
    f32 = jnp.float32
    if cfg.n_codebooks:
        batch = {
            "tokens": jax.ShapeDtypeStruct((b, cfg.n_codebooks, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, cfg.n_codebooks, s), jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct((b, s), f32),
        }
    else:
        ft = cfg.frontend_tokens
        batch = {
            "tokens": jax.ShapeDtypeStruct((b, s - ft), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct((b, s), f32),
        }
        if ft:
            batch["prefix_embeds"] = jax.ShapeDtypeStruct((b, ft, cfg.d_model), f32)
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                    sharding=NamedSharding(mesh, bs((), v)))
            for k, v in batch.items()}


def input_specs(arch: str, shape_name: str, mesh):
    """Public entry: ShapeDtypeStruct stand-ins for every model input of the
    given cell (weak-type-correct, shardable, no device allocation)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    dp_axes = dp_axes_of(mesh)
    return make_inputs_train(cfg, shape, mesh, dp_axes)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               microbatches: int | None = None, donate: bool = True,
               overrides: dict | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_axes = dp_axes_of(mesh)
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    report = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "devices": n_devices(mesh)}

    with use_mesh(mesh, dp_axes=dp_axes, tp_axis="model"):
        if cfg.family == "kvstore":
            from repro.store.engine import make_store_step, sharded_init
            lanes = cfg.store_lanes
            nsh = n_devices(mesh)
            report["store_backend"] = cfg.store_backend
            report["store_exec"] = cfg.store_exec
            state = jax.eval_shape(partial(sharded_init, cfg.store_backend,
                                           nsh, cfg.store_capacity))
            sp = P(tuple(mesh.axis_names))
            state = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(
                    mesh, P(tuple(mesh.axis_names), *([None] * (l.ndim - 1))))), state)
            stream = lambda dt: jax.ShapeDtypeStruct(
                (nsh * lanes,), dt, sharding=NamedSharding(mesh, sp))
            step = make_store_step(mesh, tuple(mesh.axis_names), lanes,
                                   backend=cfg.store_backend,
                                   exec_mode=cfg.store_exec)
            lowered = jax.jit(step).lower(state, stream(jnp.int32),
                                          stream(jnp.uint64), stream(jnp.uint64))
        elif shape.kind == "train":
            mb = microbatches or MICROBATCHES.get(arch, 1)
            report["microbatches"] = mb
            pfn = param_specs_fn(mesh, dp_axes)
            params = shaped(jax.eval_shape(
                partial(M.init_params, jax.random.PRNGKey(0), cfg)), pfn, mesh)
            opt = shaped(jax.eval_shape(lambda p: {"adam": adamw_init(p)},
                                        params), opt_specs_fn(mesh, dp_axes), mesh)
            batch = make_inputs_train(cfg, shape, mesh, dp_axes)
            use_comp = getattr(cfg, "pod_compress", False) and "pod" in mesh.axis_names
            if use_comp:
                from repro.optim.compress import compress_state_init
                res = shaped(jax.eval_shape(compress_state_init, params),
                             param_specs_fn(mesh, dp_axes), mesh)
                opt = {**opt, "residuals": res}
                step = make_train_step(cfg, microbatches=mb, pod_compress=True,
                                       mesh=mesh)
            else:
                step = make_train_step(cfg, microbatches=mb)
            lowered = jax.jit(
                step, donate_argnums=(0, 1) if donate else ()).lower(
                params, opt, batch)
        elif shape.kind == "prefill":
            pfn = param_specs_fn(mesh, dp_axes)
            params = shaped(jax.eval_shape(
                partial(M.init_params, jax.random.PRNGKey(0), cfg)), pfn, mesh)
            batch = make_inputs_train(cfg, shape, mesh, dp_axes)
            batch.pop("labels")
            batch.pop("loss_mask")
            step = make_serve_prefill(cfg, cache_len=shape.seq_len)
            lowered = jax.jit(step).lower(params, batch)
        else:  # decode
            b, s = shape.global_batch, shape.seq_len
            pfn = param_specs_fn(mesh, dp_axes)
            params = shaped(jax.eval_shape(
                partial(M.init_params, jax.random.PRNGKey(0), cfg)), pfn, mesh)
            caches = jax.eval_shape(partial(M.init_caches, None, cfg, b, s))
            caches = shaped(caches, cache_specs_fn(cfg, mesh, dp_axes, b), mesh)
            dp = (None if getattr(cfg, "decode_shard", "batch") == "seq2d"
                  else _maybe_axes(b, dp_axes, mesh))
            tok_shape = ((b, cfg.n_codebooks, 1) if cfg.n_codebooks else (b, 1))
            token = jax.ShapeDtypeStruct(tok_shape, jnp.int32,
                                         sharding=NamedSharding(
                                             mesh, P(dp, *([None] * (len(tok_shape) - 1)))))
            pos = jax.ShapeDtypeStruct((b,), jnp.int32,
                                       sharding=NamedSharding(mesh, P(dp)))
            step = make_serve_decode(cfg)
            lowered = jax.jit(
                step, donate_argnums=(3,) if donate else ()).lower(
                params, token, pos, caches)

        compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        report["flops"] = float(ca.get("flops", 0.0))
        report["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        if ma is not None:
            report["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
        report["collectives"] = parse_collective_bytes(compiled.as_text())
        report["collective_bytes_total"] = sum(
            v["bytes"] for v in report["collectives"].values())
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in ALL:
            names = (["train_4k"] if arch == "paper-kvstore"
                     else cells_for(arch))
            for sh in names:
                cells.append((arch, sh))
    else:
        cells.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch, sh in cells:
        for mp in meshes:
            tag = f"{arch}__{sh}__{'2x16x16' if mp else '16x16'}"
            try:
                rep = lower_cell(arch, sh, mp, microbatches=args.microbatches)
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(rep, f, indent=1)
                mem = rep.get("memory", {})
                per_dev = (mem.get("argument_bytes", 0)
                           + mem.get("temp_bytes", 0)) / rep["devices"]
                print(f"OK   {tag:60s} flops={rep['flops']:.3e} "
                      f"coll={rep['collective_bytes_total']:.3e}B "
                      f"mem/dev~{per_dev/2**30:.2f}GiB", flush=True)
            except Exception as e:
                failures += 1
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc(limit=3)
    print(f"done: {len(cells) * len(meshes) - failures} ok, {failures} failed")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
