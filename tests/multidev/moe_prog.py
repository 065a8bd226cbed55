"""8-device MoE dispatch equivalence: the three dispatch implementations
(reference dense, replicated+psum, the paper's routed all_to_all) must agree
on the same inputs/weights. Also exercises forward+backward under jit."""
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
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.blocks import _moe_sharded  # noqa: E402
from repro.parallel.sharding import use_mesh  # noqa: E402


def main() -> int:
    cfg = get_reduced("qwen3-moe-235b-a22b")  # 8 experts top-2
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe(key, cfg)
    t, d = 64, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (t, d), jnp.float32) * 0.5
    x = x.astype(jnp.dtype(cfg.compute_dtype))

    y_ref, aux_ref = moe_mod.moe_dense_ffn(p, cfg, x)

    with use_mesh(mesh, dp_axes=("data",), tp_axis="model"):
        for impl in ("replicated_psum", "routed_a2a"):
            y, aux = jax.jit(lambda p, x, impl=impl:
                             _moe_sharded(p, cfg, x, impl))(p, x)
            err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                        - y_ref.astype(jnp.float32))))
            print(f"{impl}: max|dy|={err:.5f} aux_err="
                  f"{abs(float(aux) - float(aux_ref)):.6f}")
            assert err < 0.05, (impl, err)

        # backward through the routed path
        def loss(p, x):
            y, aux = _moe_sharded(p, cfg, x, "routed_a2a")
            return jnp.sum(y.astype(jnp.float32) ** 2) + aux

        g = jax.jit(jax.grad(loss))(p, x)
        gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
        assert np.isfinite(gn) and gn > 0
        print(f"routed_a2a grad |sum|={gn:.3f}")
    print("MOE-OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
