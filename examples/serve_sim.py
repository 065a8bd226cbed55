"""End-to-end serving driver: continuous batching with paged KV cache,
skiplist scheduler and ring-queue arrivals on a reduced qwen3 model.

Run: PYTHONPATH=src python examples/serve_sim.py
"""
import time

import numpy as np
import jax

import repro  # noqa: F401
from repro.configs import get_reduced
from repro.launch.cache import use_compile_cache
from repro.models import model as M
from repro.serving.engine import Engine, Request


def main():
    use_compile_cache()
    cfg = get_reduced("qwen3-1.7b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_reqs=4, num_pages=64, page_size=8,
                 max_pages_per_req=8)
    rng = np.random.default_rng(0)
    for i in range(10):
        eng.submit(Request(req_id=i,
                           prompt=rng.integers(1, cfg.vocab_size, 8),
                           max_new=12, priority=i % 3))
    t0 = time.perf_counter()
    outs = eng.run(max_steps=128)
    dt = time.perf_counter() - t0
    toks = sum(len(v) for v in outs.values())
    print(f"served {len(outs)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s on 1 CPU core)")
    print(f"pool fully recycled: {int(eng.kv.pool.num_free())} pages free")
    print("sample output:", outs[0])


if __name__ == "__main__":
    main()
