"""The plain reference: the store's `OpPlan` contract over a Python dict.

Copied from `chip_smoke.py`'s `DictModel` (FIND / INSERT / DELETE lanes
only: no mix here sends pops or ranges) and kept here, so that no change to
the program can change the yardstick. It imports nothing of the program.

Contract (`store/api.py`): per plan all INSERTs apply first
(insert-if-absent; result = (applied, existed flag)), then all DELETEs
(result = (removed, 0)), then all FINDs observe the post-update state
(result = (hit, stored value)); on in-plan duplicates the first lane wins.

`value_bits` and `order` exist for the controls only: a reference that keeps
32-bit values, or that answers FINDs before DELETEs, breaks a guarantee the
configurations state, and the comparison has to call it wrong.
"""
from __future__ import annotations

import numpy as np

OP_FIND, OP_INSERT, OP_DELETE = 0, 1, 2
COUNTERS = ("ops_find", "ops_insert", "ops_delete", "find_hits",
            "find_misses", "inserts_new", "inserts_existing", "deletes_hit")


class DictModel:
    def __init__(self, value_bits: int = 64,
                 order: tuple = ("insert", "delete", "find")):
        self.d = {}
        self.mask = (1 << value_bits) - 1
        self.order = order
        self.counts = dict.fromkeys(COUNTERS, 0)

    def apply(self, ops, keys, vals):
        """One plan; returns (ok bool[n], res uint64[n])."""
        n = len(ops)
        ok = np.zeros(n, bool)
        out = np.zeros(n, np.uint64)
        for phase in self.order:
            getattr(self, "_" + phase)(ops, keys, vals, ok, out)
        return ok, out

    def _insert(self, ops, keys, vals, ok, out):
        c, d = self.counts, self.d
        idx = np.flatnonzero(ops == OP_INSERT)
        for i, k, v in zip(idx.tolist(), keys[idx].tolist(),
                           vals[idx].tolist()):
            existed = k in d
            if not existed:
                d[k] = v & self.mask
            ok[i] = True
            out[i] = existed
            c["inserts_existing" if existed else "inserts_new"] += 1
        c["ops_insert"] += idx.size

    def _delete(self, ops, keys, vals, ok, out):
        c, d = self.counts, self.d
        idx = np.flatnonzero(ops == OP_DELETE)
        hit = [d.pop(k, None) is not None for k in keys[idx].tolist()]
        ok[idx] = hit
        c["ops_delete"] += idx.size
        c["deletes_hit"] += sum(hit)

    def _find(self, ops, keys, vals, ok, out):
        c, d = self.counts, self.d
        idx = np.flatnonzero(ops == OP_FIND)
        got = [d.get(k) for k in keys[idx].tolist()]
        hit = np.array([g is not None for g in got], bool)
        ok[idx] = hit
        out[idx] = np.array([0 if g is None else g for g in got], np.uint64)
        c["ops_find"] += idx.size
        c["find_hits"] += int(hit.sum())
        c["find_misses"] += idx.size - int(hit.sum())

    def shard_sizes(self, n_shards: int) -> np.ndarray:
        """Live keys per owner shard, routed by the top log2(n) key bits."""
        if n_shards == 1:
            return np.array([len(self.d)])
        bits = n_shards.bit_length() - 1
        keys = np.fromiter(self.d.keys(), np.uint64, len(self.d))
        owner = (keys >> np.uint64(64 - bits)).astype(np.int64)
        return np.bincount(owner, minlength=n_shards)


def lanes_wrong(model: DictModel, plans, results) -> tuple[int, int]:
    """Replay `plans` through `model`; count the lanes whose (ok, value)
    differs from `results` and the plans holding such a lane."""
    wrong = bad_plans = 0
    for (ops, keys, vals), (ok, res) in zip(plans, results):
        want_ok, want = model.apply(ops, keys, vals)
        n = int(np.count_nonzero((ok != want_ok) | (res != want)))
        wrong += n
        bad_plans += n > 0
    return wrong, bad_plans
