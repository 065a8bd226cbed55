"""Compile rehearsal: every store kernel, compiled for a described TPU v5e.

Interpret mode runs the kernel bodies on the CPU and cannot see what the
TPU's compiler refuses (unaligned tiles, gathers Mosaic does not lower,
too much VMEM). Here each store kernel is lowered and compiled by the
installed TPU compiler for one chip of a described `v5e:2x2` topology, at
the widths of the `paper-kvstore` deployment (65536 entries per shard, a
4096-lane plan routed into an 8192-lane pool). Nothing runs: a compile
that passes is not a chip run. Whole-step compiles take minutes and stay
in `chip_smoke.py`.

The topology is described inside a module-scoped fixture, never at import
time: the TPU library can be loaded by one process at a time, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

import repro  # noqa: F401  (enables x64)
from repro.configs.paper_kvstore import CONFIG

CAP = CONFIG.store_capacity
POOL = 2 * CONFIG.store_lanes          # StoreEngine's default pool_factor


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(make, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(make))


def _cases(dev):
    """kernel name -> (function of device values, argument shapes)."""
    from repro.core import det_skiplist as dsl
    from repro.kernels.bskiplist_walk.ops import bskiplist_find
    from repro.kernels.hash_probe.ops import fixed_hash_find_cols
    from repro.kernels.pq_pop.ops import pq_pop_ranks
    from repro.kernels.skiplist_search.ops import skiplist_find
    from repro.kernels.splitorder_probe.ops import twolevel_splitorder_probe
    from repro.kernels.tier_apply.ops import tier_apply_fused
    from repro.kernels.tier_find.ops import tier_find_fused
    from repro.store import get_backend

    tiers = get_backend("tiered3/lru")
    tier = _shapes(lambda: tiers.init(CAP), dev)
    sk = _shapes(lambda: dsl.skiplist_init(CAP), dev)
    so = _shapes(lambda: get_backend("twolevel_splitorder").init(CAP), dev)
    q = jax.ShapeDtypeStruct((POOL,), jnp.uint64, sharding=dev)
    ranks = jax.ShapeDtypeStruct((POOL,), jnp.int32, sharding=dev)
    mask = jax.ShapeDtypeStruct((POOL,), jnp.bool_, sharding=dev)

    def apply(layout):
        return lambda t, k, v, m: tier_apply_fused(
            t.hot, t.hot_meta, t.clock, t.cold, t.spill, k, v, m, "lru", 64,
            warm_layout=layout, interpret=False)

    def find(layout):
        return lambda t, k: tier_find_fused(
            t.hot, t.cold, t.spill, k, warm_layout=layout, interpret=False)

    return {
        "hash_probe": (lambda t, k: fixed_hash_find_cols(
            t.hot, k, interpret=False), (tier, q)),
        "skiplist_search": (lambda s, k: skiplist_find(
            s, k, interpret=False), (sk, q)),
        "bskiplist_walk": (lambda s, k: bskiplist_find(
            s, k, interpret=False), (sk, q)),
        "pq_pop": (lambda s, r, m: pq_pop_ranks(
            s, r, m, interpret=False), (sk, ranks, mask)),
        "tier_find": (find("level"), (tier, q)),
        "tier_find/block": (find("block"), (tier, q)),
        "tier_apply": (apply("level"), (tier, q, q, mask)),
        "tier_apply/block": (apply("block"), (tier, q, q, mask)),
        "splitorder_probe": (lambda h, k: twolevel_splitorder_probe(
            h, k, interpret=False), (so, q)),
    }


KERNELS = ("hash_probe", "skiplist_search", "bskiplist_walk", "pq_pop",
           "tier_find", "tier_find/block", "tier_apply", "tier_apply/block",
           "splitorder_probe")


@pytest.fixture(scope="module")
def cases(one_chip):
    return _cases(one_chip)


@pytest.mark.parametrize("kernel", KERNELS)
def test_store_kernel_compiles_for_v5e(cases, kernel):
    fn, args = cases[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
