"""The one traffic generator: reads a mix's parameters from `traffic/<name>.json`.

Every mix is a closed loop: one op outstanding per client, one client per
lane, so a plan holds one op per client and the next plan is issued when
the previous plan's results are on the host. What a plan holds depends only
on the seed and the plan's index, never on timing.

A mix file gives:

  shares       {"find", "insert", "delete"}: the share of a plan's lanes of
               each op; counts are rounded, FIND takes the remainder
  find_keys    {"from": "live" | "loaded", "distribution": "uniform" |
               "zipfian", "theta", "scramble"}: "live" draws from the keys
               live when the plan is issued, "loaded" from the records of
               the load phase
  delete_keys  {"from": "live", "distribution": "uniform"}: distinct live keys
  insert_keys  "fresh": keys never drawn before
  warmup_plans plans of the mix run after the load and before the window

Keys are uniform over [1, 2**64 - 2]: every u64 but the store's +inf
sentinel (`core/bits.py` KEY_INF) and 0, so every shard of a top-bits
partition owns an equal share. The load phase inserts `records` fresh keys
in plans of pure INSERTs.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

OP_FIND, OP_INSERT, OP_DELETE = 0, 1, 2     # store/api.py op codes
KEY_LO, KEY_HI = 1, 2 ** 64 - 1             # integers() excludes KEY_HI
VAL_MUL = np.uint64(0x9E3779B97F4A7C15)

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: only closed-loop mixes are generated")
    return mix


def seed_rng(seed: int) -> np.random.Generator:
    """Any whole number is a seed; negative ones fold into 64 bits."""
    return np.random.default_rng(np.random.SeedSequence(seed % 2 ** 64))


def lane_counts(shares: dict, lanes: int) -> tuple[int, int, int]:
    """(FIND, INSERT, DELETE) lanes of a plan."""
    n_ins = int(round(shares.get("insert", 0.0) * lanes))
    n_del = int(round(shares.get("delete", 0.0) * lanes))
    n_find = lanes - n_ins - n_del
    if n_find < 0 or abs(n_find - shares.get("find", 0.0) * lanes) > 1:
        raise ValueError(f"shares {shares} do not add up to 1")
    return n_find, n_ins, n_del


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


class Generator:
    """The op stream of one mix at one size, drawn from `seed`.

    `load_plans()` gives the plans that load `records` keys;
    `next_plan()` gives the mix's plans, one per call, as
    (ops int32[lanes], keys uint64[lanes], vals uint64[lanes])."""

    def __init__(self, mix: dict, seed: int, lanes: int, records: int):
        if records % lanes:
            raise ValueError(f"records {records} is not a whole number of "
                             f"{lanes}-lane plans")
        self.mix = mix
        self.lanes = lanes
        self.records = records
        self.seed = seed
        self.rng = seed_rng(seed)
        self.counts = lane_counts(mix["shares"], lanes)
        self.live = self._fresh(records)
        self.loaded = self.live
        fk = mix["find_keys"]
        self.find_from = fk["from"]
        self.zipf = None
        if fk["distribution"] == "zipfian":
            self.zipf = zipf_cdf(records, float(fk["theta"]))
            self.rank_to_key = (self.rng.permutation(records)
                                if fk.get("scramble", False)
                                else np.arange(records))
        elif fk["distribution"] != "uniform":
            raise ValueError(f"unknown find distribution {fk['distribution']!r}")
        if mix["delete_keys"] != {"from": "live", "distribution": "uniform"}:
            raise ValueError(f"unknown delete keys {mix['delete_keys']!r}")
        if mix["insert_keys"] != "fresh":
            raise ValueError(f"unknown insert keys {mix['insert_keys']!r}")

    def _fresh(self, n: int) -> np.ndarray:
        """n new keys. 64-bit draws: a repeat among a run's ~10^7 keys has
        odds ~10^-5, and the reference answers a repeated key like any
        other."""
        return self.rng.integers(KEY_LO, KEY_HI, n, dtype=np.uint64)

    def value(self, keys: np.ndarray) -> np.ndarray:
        return keys * VAL_MUL + np.uint64(self.seed % 2 ** 64)

    def load_plans(self) -> list:
        ops = np.full(self.lanes, OP_INSERT, np.int32)
        return [(ops.copy(), k, self.value(k))
                for k in self.loaded.reshape(-1, self.lanes)]

    def _find_keys(self, n: int) -> np.ndarray:
        if self.find_from == "loaded":
            if self.zipf is None:
                return self.loaded[self.rng.integers(0, self.records, n)]
            rank = np.searchsorted(self.zipf, self.rng.random(n), side="right")
            rank = np.minimum(rank, self.records - 1)
            return self.loaded[self.rank_to_key[rank]]
        if self.zipf is not None:
            raise ValueError("zipfian finds draw from the loaded records")
        return self.live[self.rng.integers(0, self.live.size, n)]

    def next_plan(self):
        n_find, n_ins, n_del = self.counts
        find = self._find_keys(n_find)
        d_idx = self.rng.choice(self.live.size, n_del, replace=False)
        dele = self.live[d_idx]
        ins = self._fresh(n_ins)
        if n_del or n_ins:
            self.live = np.concatenate([np.delete(self.live, d_idx), ins])
        ops = np.concatenate([np.full(n_find, OP_FIND, np.int32),
                              np.full(n_ins, OP_INSERT, np.int32),
                              np.full(n_del, OP_DELETE, np.int32)])
        keys = np.concatenate([find, ins, dele])
        vals = np.concatenate([np.zeros(n_find, np.uint64), self.value(ins),
                               np.zeros(n_del, np.uint64)])
        perm = self.rng.permutation(self.lanes)
        return ops[perm], keys[perm], vals[perm]
