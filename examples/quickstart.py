"""Quickstart: the paper's data structures in five minutes.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.core.det_skiplist import (delete_batch, find_batch, insert_batch,
                                     range_query, skiplist_init)
from repro.core.ringqueue import pop_batch, push_batch, queue_init
from repro.core.splitorder import (splitorder_find, splitorder_init,
                                   splitorder_insert)
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    print("== deterministic 1-2-3-4 skiplist (paper §II) ==")
    s = skiplist_init(capacity=1024)
    keys = jnp.asarray(np.random.default_rng(0).integers(1, 10_000, 200,
                                                         dtype=np.uint64))
    s, inserted, existed = insert_batch(s, keys, keys * jnp.uint64(10))
    print(f"inserted {int(inserted.sum())} keys "
          f"({int(existed.sum())} in-batch duplicates)")
    found, vals, _ = find_batch(s, keys[:8])
    print("find:", np.asarray(found), "->", np.asarray(vals))
    cnt, rk, _, valid = range_query(s, jnp.asarray([100], jnp.uint64),
                                    jnp.asarray([1000], jnp.uint64), 16)
    print(f"range [100,1000): {int(cnt[0])} keys, first few:",
          np.asarray(rk[0])[np.asarray(valid[0])][:5])
    s, deleted = delete_batch(s, keys[:50])
    print(f"deleted {int(deleted.sum())} (lazy tombstones; compaction at 25%)")

    print("\n== lock-free block queue (paper §III) ==")
    q = queue_init(max_blocks=8, block_size=16)
    q, ok = push_batch(q, jnp.arange(40, dtype=jnp.uint64),
                       jnp.ones((40,), bool))
    q, out, got = pop_batch(q, 10)
    print("FIFO pop:", np.asarray(out))
    print("block recycles so far:", int(np.asarray(q.recycles).sum()))

    print("\n== split-order hash (paper §VII): resize w/o movement ==")
    h = splitorder_init(512, seed_slots=4, max_load=4)
    h, _, _ = splitorder_insert(h, keys, keys)
    print(f"slots grew 4 -> {int(h.n_slots)} with zero data migration")
    f, v = splitorder_find(h, keys[:5])
    print("find:", np.asarray(f))


if __name__ == "__main__":
    main()
