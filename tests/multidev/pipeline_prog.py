"""4-stage pipeline parallelism vs sequential reference (8 devices: the
mesh keeps a spare axis on auto to prove PP composes with DP)."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import repro  # noqa: F401,E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.parallel.pipeline import pipeline_apply  # noqa: E402


def main() -> int:
    mesh = make_mesh((4,), ("stage",), devices=jax.devices()[:4])
    S, M, B, D = 4, 6, 2, 16
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((S, D, D)) / np.sqrt(D), jnp.float32)
    xs = jnp.asarray(rng.standard_normal((M, B, D)), jnp.float32)

    def stage_fn(wi, x):
        return jnp.tanh(x @ wi)

    piped = jax.jit(pipeline_apply(stage_fn, mesh, "stage"))
    ys = piped(w, xs)

    # sequential reference
    ref = xs
    for i in range(S):
        ref = jnp.tanh(ref @ w[i])
    err = float(jnp.max(jnp.abs(ys - ref)))
    print("pipeline err:", err)
    assert err < 1e-5
    print("PIPELINE-OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
