"""repro.kernels — Pallas TPU kernels for the compute hot spots.

Module map (each kernel is a package of three files — `kernel.py` the
Pallas body + pallas_call wrapper, `ref.py` a pure-jnp oracle over the SAME
layout, `ops.py` the state -> layout -> kernel adapter):

flash_attention   tiled softmax(QK^T)V with online renormalization
paged_attention   decode attention over block-paged KV cache pages
selective_scan    chunked SSM recurrence (Mamba-style selective scan)
skiplist_search   batched deterministic-skiplist FIND: the 1-2-3-4
                  criterion's fixed L-level, fan-out-4 walk over the
                  level-major layout (`core.layout.skiplist_layout`)
bskiplist_walk    batched B-skiplist FIND over the block-major layout
                  (`core.layout.bskiplist_layout`): 128-key lane-width fat
                  nodes, ONE whole-block `key_lt` compare + reduction per
                  descent step — same found/idx contract as
                  skiplist_search in ceil(log128 C) steps instead of the
                  fan-out-4 walk's num_levels
hash_probe        batched fixed-hash bucket probe over the bucket-major
                  layout (`core.layout.bucket_layout`) — the §IX hot-tier
                  fast path
pq_pop            batched priority-queue pop: live-prefix rank-select over
                  the terminal level + the shared skiplist_search
                  `level_walk` descent (the `pq` backend's POPMIN/POPK)
tier_find         fused tier-stack FIND: hot bucket probe + warm level
                  walk + per-run spill binary search in ONE dispatch,
                  bodies shared with hash_probe / skiplist_search
tier_apply        fused tier-stack APPLY prologue: the tier_find probes
                  + the hot-insert linearization and eviction-policy
                  victim selection in ONE dispatch, lanes scanned in
                  sorted order with the reference's cumsums as running
                  counts
rows              the shared per-query row-walk primitives: Mosaic lowers
                  no data-dependent vector gather, so the store kernels
                  load whole 128-lane rows at a data-dependent row index
                  and reduce each compare to a scalar
splitorder_probe  two-level split-ordered hash probe (recursive-split
                  bucket directory + sorted-segment search)

The store kernels (skiplist_search, hash_probe) are never called directly
by backends: `repro.store.exec` dispatches between them and their jnp
references by execution mode (jnp | interpret | pallas), with bit-identical
results guaranteed by tests/test_exec_modes.py. All kernels validate in
interpret mode on CPU (tests/test_kernels.py); the store kernels compile
for a described v5e in tests/test_chip_compile.py and match jnp on the
chip in chip_smoke.py.

Add a kernel ONLY for a hot spot the paper itself optimizes; keep the
ref/ops/kernel split so the oracle and the layout adapter stay testable
without TPU hardware. See docs/store_layers.md for the layout/execution/
store layering.
"""
