"""Pipeline parallelism: GPipe-style fill-drain schedule over a mesh axis.

Each device on the `stage` axis holds one stage's parameters; activations
flow stage-to-stage with `collective_permute` (the ICI-neighbor hop), one
microbatch injected per tick — n_micro + n_stages - 1 ticks total, bubble
fraction (S-1)/(T+S-1) as usual. Composes under jit with the other axes on
GSPMD auto (pass `mesh` with extra axes and keep them out of `axis`).

This is the PP primitive (deliverable: DP/TP/PP/EP/SP support); the default
production configs prefer DP×TP(+EP) — PP becomes profitable past the HBM
cliff (see llama3-405b train temp-memory in §Dry-run).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, mesh: Mesh, axis: str = "stage"):
    """Build a pipelined apply: (stage_params, micro_x) -> micro_y.

    stage_params: pytree with leading dim = n_stages (sharded over `axis`).
    micro_x: [n_micro, ...] microbatch stream (replicated).
    stage_fn(params_slice, x) -> y, same shape as x.
    Returns ys [n_micro, ...] (outputs of the LAST stage, in order).
    """
    n_stages = mesh.shape[axis]

    def body(params, xs):
        params = jax.tree.map(lambda a: a[0], params)   # my stage's params
        sid = jax.lax.axis_index(axis)
        n_micro = xs.shape[0]
        ticks = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        buf = jnp.zeros_like(xs[0])
        ys = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, ys = carry
            # stage 0 injects microbatch t (older stages are processing t-sid)
            inj = jax.lax.dynamic_index_in_dim(xs, jnp.minimum(t, n_micro - 1),
                                               axis=0, keepdims=False)
            x_in = jnp.where(sid == 0, inj, buf)
            y = stage_fn(params, x_in)
            # last stage commits its result for microbatch t - (S-1)
            out_idx = t - (n_stages - 1)
            ok = (sid == n_stages - 1) & (out_idx >= 0)
            ys = jax.lax.cond(
                ok,
                lambda ys: jax.lax.dynamic_update_index_in_dim(
                    ys, y, jnp.maximum(out_idx, 0), axis=0),
                lambda ys: ys, ys)
            # shift activations one stage forward
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, ys

        _, ys = jax.lax.fori_loop(0, ticks, tick, (buf, ys))
        # broadcast the last stage's outputs to every stage (so out_specs can
        # be replicated); sum works because other stages contributed zeros
        ys = jax.lax.psum(jnp.where(sid == n_stages - 1, ys, 0), axis)
        return ys

    pspec = jax.tree_util.Partial  # noqa: F841 (doc aid)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis), P()),
                         out_specs=P(),
                         check_vma=False)
