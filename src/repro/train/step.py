"""Train / serve step builders — the functions the dry-run lowers and the
train loop jits.

train_step features:
  * microbatch gradient accumulation (lax.scan — bounds activation memory)
  * remat per layer group (model-level flag)
  * optional int8 error-feedback gradient compression on the pod (DCI) axis
    via a shard_map over ("pod",) with intra-pod axes on GSPMD auto
  * AdamW with ZeRO-sharded moments (sharding inherited from params)

serve_prefill / serve_decode lower the inference cells; decode carries the
contiguous KV caches (seq dim shardable over the model axis).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.optim.adamw import adamw_update
from repro.optim.compress import pod_allreduce_compressed
from repro.optim.schedule import cosine_with_warmup

AUX_WEIGHT = 0.01


def loss_fn(params, cfg, batch):
    logits, aux = M.forward(params, cfg, batch["tokens"],
                            prefix_embeds=batch.get("prefix_embeds"))
    loss = M.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    return loss + AUX_WEIGHT * aux, (loss, aux)


def _split_micro(batch, n):
    def f(x):
        return x.reshape((n, x.shape[0] // n) + x.shape[1:])
    return jax.tree.map(f, batch)


def grads_of(params, cfg, batch, microbatches: int = 1):
    """Accumulated grads + metrics over microbatches (sequential scan)."""
    if microbatches == 1:
        (_, (loss, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, cfg, batch)
        return grads, loss, aux
    mb = _split_micro(batch, microbatches)

    def body(carry, mbatch):
        acc, loss_acc, aux_acc = carry
        (_, (loss, aux)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, cfg, mbatch)
        acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
        return (acc, loss_acc + loss, aux_acc + aux), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (grads, loss, aux), _ = jax.lax.scan(body, (zero, 0.0, jnp.float32(0)), mb)
    inv = 1.0 / microbatches
    return (jax.tree.map(lambda g: g * inv, grads), loss * inv, aux * inv)


def make_train_step(cfg, *, lr_peak=3e-4, warmup=100, total_steps=10000,
                    microbatches: int = 1, pod_compress: bool = False,
                    mesh=None, pod_axis: str = "pod"):
    """Returns train_step(params, opt_state, batch) -> (params', opt', metrics).

    With pod_compress, batches stay pod-local (the batch dim's pod shard) and
    gradients cross the DCI as int8 + error feedback; opt_state carries the
    residuals.
    """

    def apply_update(params, opt_state, grads, loss, aux):
        lr = cosine_with_warmup(opt_state["adam"]["step"] + 1, peak_lr=lr_peak,
                                warmup_steps=warmup, total_steps=total_steps)
        new_p, new_adam, gn = adamw_update(grads, opt_state["adam"], params, lr)
        metrics = {"loss": loss, "aux": aux, "grad_norm": gn, "lr": lr}
        return new_p, {**opt_state, "adam": new_adam}, metrics

    if not pod_compress:
        def train_step(params, opt_state, batch):
            grads, loss, aux = grads_of(params, cfg, batch, microbatches)
            return apply_update(params, opt_state, grads, loss, aux)
        return train_step

    assert mesh is not None and pod_axis in mesh.axis_names
    from jax.sharding import PartitionSpec as P

    def train_step(params, opt_state, batch):
        # partial-manual: only the pod axis is manual, intra-pod axes stay
        # on GSPMD auto
        def per_pod(params, residuals, batch):
            grads, loss, aux = grads_of(params, cfg, batch, microbatches)
            grads, residuals = pod_allreduce_compressed(grads, residuals,
                                                        pod_axis)
            loss = jax.lax.pmean(loss, pod_axis)
            aux = jax.lax.pmean(aux, pod_axis)
            return grads, residuals, loss, aux

        specs_p = jax.tree.map(lambda _: P(), params)
        batch_specs = jax.tree.map(lambda _: P(pod_axis), batch)
        grads, residuals, loss, aux = jax.shard_map(
            per_pod, mesh=mesh,
            in_specs=(specs_p, specs_p, batch_specs),
            out_specs=(specs_p, specs_p, P(), P()),
            axis_names={pod_axis}, check_vma=False,
        )(params, opt_state["residuals"], batch)
        params, opt_state, metrics = apply_update(params, opt_state, grads,
                                                  loss, aux)
        return params, {**opt_state, "residuals": residuals}, metrics

    return train_step


def make_serve_prefill(cfg, cache_len: int):
    def serve_prefill(params, batch):
        logits, caches, _ = M.prefill(params, cfg, batch["tokens"], cache_len,
                                      prefix_embeds=batch.get("prefix_embeds"))
        return logits[:, -1], caches
    return serve_prefill


def make_serve_decode(cfg):
    def serve_decode(params, token, pos, caches):
        logits, caches = M.decode_step(params, cfg, token, pos, caches)
        return logits[:, 0] if logits.ndim == 3 else logits[:, 0], caches
    return serve_decode
