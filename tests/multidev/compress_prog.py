"""8-device validation of int8 error-feedback pod-axis gradient compression:
(a) compressed training tracks uncompressed losses, (b) residuals carry the
quantization error, (c) the lowered HLO actually moves int8 over the pod axis.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import repro  # noqa: F401,E402
from repro.configs import get_reduced  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.data.pipeline import synth_batch  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim.adamw import adamw_init  # noqa: E402
from repro.optim.compress import compress_state_init  # noqa: E402
from repro.train.step import make_train_step  # noqa: E402


def main() -> int:
    cfg = get_reduced("qwen3-1.7b")
    mesh = make_mesh((2, 4), ("pod", "data"))
    shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
    params = M.init_params(jax.random.PRNGKey(0), cfg)

    plain = jax.jit(make_train_step(cfg))
    comp = jax.jit(make_train_step(cfg, pod_compress=True, mesh=mesh))

    opt_a = {"adam": adamw_init(params)}
    opt_b = {"adam": adamw_init(params),
             "residuals": compress_state_init(params)}
    pa, pb = params, params
    losses_a, losses_b = [], []
    for step in range(4):
        batch = synth_batch(cfg, shape, 11, step)
        pa, opt_a, ma = plain(pa, opt_a, batch)
        pb, opt_b, mb = comp(pb, opt_b, batch)
        losses_a.append(float(ma["loss"]))
        losses_b.append(float(mb["loss"]))
    print("plain:", [f"{x:.4f}" for x in losses_a])
    print("comp: ", [f"{x:.4f}" for x in losses_b])
    # int8 quantization error must stay small at loss level
    for a, b in zip(losses_a, losses_b):
        assert abs(a - b) < 0.05 * max(abs(a), 1), (a, b)
    rn = sum(float(jnp.sum(jnp.abs(r)))
             for r in jax.tree.leaves(opt_b["residuals"]))
    assert rn > 0, "error feedback residuals never populated"
    # the pod exchange must be int8 on the wire
    batch = synth_batch(cfg, shape, 11, 0)
    txt = jax.jit(make_train_step(cfg, pod_compress=True, mesh=mesh)
                  ).lower(pb, opt_b, batch).compile().as_text()
    assert any("s8[" in l and "all-gather" in l for l in txt.splitlines()), \
        "no int8 all-gather found in HLO"
    print("COMPRESS-OK residual_norm=%.3f" % rn)
    return 0


if __name__ == "__main__":
    sys.exit(main())
