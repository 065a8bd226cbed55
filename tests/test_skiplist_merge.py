"""The skiplist merge's destinations against the binary-search formula.

`insert_batch` moves stored entry i to i + #{j : newk[j] < term_keys[i]}.
It counts that as a prefix count over C of the new keys' insertion points;
`_insert_batch_ref` below keeps the formula it replaced, C binary searches
into the sorted new keys, and every case compares the two states field by
field, bit for bit, at every batch of an operation sequence.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401  (enables x64)
from repro.core.bits import KEY_INF, dup_in_run
from repro.core.det_skiplist import (_rebuild_levels, check_invariants,
                                     delete_batch, insert_batch,
                                     skiplist_init)

K = 16
INF = 2**64 - 1


def _insert_batch_ref(s, keys, vals, mask):
    """`insert_batch` with the merge's old destinations: `dest_old` from
    searchsorted(newk, term_keys), `dest_new` from searchsorted(term_keys,
    newk)."""
    K = keys.shape[0]
    C = s.capacity
    mask = mask & (keys != KEY_INF)
    order = jnp.argsort(keys, stable=True)
    sk, sv, sm = keys[order], vals[order], mask[order]
    same = jnp.concatenate([jnp.zeros((1,), bool), sk[1:] == sk[:-1]])
    dup = dup_in_run(same, sm)
    pos = jnp.searchsorted(s.term_keys, sk).astype(jnp.int32)
    posc = jnp.clip(pos, 0, C - 1)
    match = sm & (pos < C) & (s.term_keys[posc] == sk)
    revive = match & s.term_mark[posc] & ~dup
    exists = match & ~s.term_mark[posc]
    rpos = jnp.where(revive, posc, C)
    term_mark = s.term_mark.at[rpos].set(False, mode="drop")
    term_vals = s.term_vals.at[rpos].set(sv, mode="drop")
    term_stamp = s.term_stamp.at[rpos].set(s.clock, mode="drop")
    n_marked = s.n_marked - jnp.sum(revive).astype(jnp.int32)
    new = sm & ~match & ~dup
    rank = jnp.cumsum(new.astype(jnp.int32)) - 1
    new = new & (s.n_term + rank < C)
    n_new = jnp.sum(new).astype(jnp.int32)
    crank = jnp.where(new, rank, K)
    newk = jnp.full((K,), KEY_INF).at[crank].set(sk, mode="drop")
    newv = jnp.zeros((K,), jnp.uint64).at[crank].set(sv, mode="drop")
    old_idx = jnp.arange(C, dtype=jnp.int32)
    dest_old = old_idx + jnp.searchsorted(newk, s.term_keys, side="left").astype(jnp.int32)
    dest_old = jnp.where(old_idx < s.n_term, dest_old, C)
    dest_new = (jnp.searchsorted(s.term_keys, newk, side="left").astype(jnp.int32)
                + jnp.arange(K, dtype=jnp.int32))
    dest_new = jnp.where(jnp.arange(K) < n_new, dest_new, C)
    tk = jnp.full((C,), KEY_INF).at[dest_old].set(s.term_keys, mode="drop")
    tk = tk.at[dest_new].set(newk, mode="drop")
    tv = jnp.zeros((C,), jnp.uint64).at[dest_old].set(term_vals, mode="drop")
    tv = tv.at[dest_new].set(newv, mode="drop")
    tm = jnp.zeros((C,), bool).at[dest_old].set(term_mark, mode="drop")
    ts = jnp.zeros((C,), jnp.int32).at[dest_old].set(term_stamp, mode="drop")
    ts = ts.at[dest_new].set(s.clock, mode="drop")
    s2 = s._replace(term_keys=tk, term_vals=tv, term_mark=tm, term_stamp=ts,
                    n_term=s.n_term + n_new, n_marked=n_marked)
    inv = jnp.zeros((K,), jnp.int32).at[order].set(jnp.arange(K, dtype=jnp.int32))
    return _rebuild_levels(s2), (new | revive)[inv], (exists | dup)[inv]


def _rng_keys(seed, n, lo=1, hi=2**64 - 2):
    return list(np.random.default_rng(seed).integers(lo, hi, n, dtype=np.uint64,
                                                     endpoint=True))


def _spread(n, step=1000, start=1000):
    return [start + step * i for i in range(n)]


# Each case: (capacity, [("ins", keys, mask) | ("del", keys)]); a batch is
# padded to K lanes with masked KEY_INF, and "ins" values are key + 7.
CASES = {
    "capacity_below_k": (8, [("ins", _spread(3), None),
                             ("ins", _rng_keys(1, K), None)]),
    "capacity_equal_k": (K, [("ins", _spread(5), None),
                             ("ins", _rng_keys(2, K), None)]),
    "capacity_above_k": (64, [("ins", _rng_keys(3, K), None),
                              ("ins", _rng_keys(4, K), None),
                              ("del", _rng_keys(3, 6)),
                              ("ins", _rng_keys(5, K), None)]),
    "empty_skiplist": (32, [("ins", _rng_keys(6, K), None)]),
    "no_new_keys": (32, [("ins", _spread(10), None),
                         ("ins", [INF] * K, None),
                         ("ins", _rng_keys(7, K), [False] * K),
                         ("ins", [INF] * 8 + _rng_keys(8, 8),
                          [True] * 8 + [False] * 8)]),
    "in_batch_duplicates": (64, [("ins", _spread(6), None),
                                 ("ins", [5, 5, 2000, 5, 9, 9, 9, 2500, 2500,
                                          1, 1, 3000, 3000, 7, 5, 9],
                                  [False, True, True, True, True, False, True,
                                   True, True, True, True, True, False, True,
                                   True, True])]),
    "revive_marked": (64, [("ins", _spread(12), None),
                           ("del", _spread(12)[::3]),
                           ("ins", _spread(12)[::3] + [1500, 4500, 0, 9500]
                            + _spread(12)[1:5], None),
                           ("del", _spread(12)[1::4]),
                           ("ins", _spread(12)[1::4] + [2500, 2600], None)]),
    "overflow_at_capacity": (K, [("ins", _spread(12), None),
                                 ("ins", _rng_keys(9, K), None),
                                 ("ins", _rng_keys(10, K), None)]),
    "extreme_keys": (32, [("ins", [1, 2**63, 2**64 - 3], None),
                          ("ins", [0, 2**64 - 2, 2, 2**63 + 1, 2**63 - 1],
                           None),
                          ("ins", [0, 2**64 - 2, INF], None)]),
    "one_gap": (64, [("ins", [100, 200, 300], None),
                     ("ins", list(range(150, 150 + K)), None),
                     ("ins", [50, 51, 250, 251, 252, 253, 350, 351, 352,
                              170, 180, 190], None)]),
}


def _lanes(xs, fill):
    a = np.full((K,), fill, dtype=bool if type(fill) is bool else np.uint64)
    a[:len(xs)] = np.asarray(xs, dtype=a.dtype)
    return jnp.asarray(a)


def _assert_same(a, b, where):
    fa, fb = jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, x), y in zip(fa, fb):
        x, y = np.asarray(x), np.asarray(y)
        name = f"{where}{jax.tree_util.keystr(path)}"
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert np.array_equal(x, y), name


@pytest.mark.parametrize("case", sorted(CASES))
def test_merge_matches_binary_search_formula(case):
    cap, steps = CASES[case]
    new_ins, ref_ins = jax.jit(insert_batch), jax.jit(_insert_batch_ref)
    s = skiplist_init(cap)
    n_new = []
    for i, step in enumerate(steps):
        if step[0] == "del":
            s, _ = delete_batch(s, _lanes(step[1], INF), compact_num=1,
                                compact_den=1)
            continue
        keys = _lanes(step[1], INF)
        mask = _lanes(step[2] if step[2] is not None
                      else [True] * len(step[1]), False)
        vals = keys + jnp.uint64(7)
        got = new_ins(s, keys, vals, mask)
        want = ref_ins(s, keys, vals, mask)
        _assert_same(got, want, f"{case} batch {i}: ")
        n_new.append(int(got[0].n_term) - int(s.n_term))
        s = got[0]
        assert all(v == 0 for v in check_invariants(s).values())
    assert sum(n_new) > 0 or case == "no_new_keys"
    if case == "no_new_keys":
        assert n_new[1:] == [0, 0, 0]
    if case in ("capacity_below_k", "overflow_at_capacity"):
        assert int(s.n_term) == cap
    if case == "revive_marked":
        assert int(s.n_marked) == 0


def test_merge_lowers_without_a_search_over_capacity():
    """No `while` of the lowered merge carries a [C] int32 array: the C
    binary searches into the new keys (a `while` over C int32 bounds) are
    gone. The K searches into term_keys carry term_keys itself (u64)."""
    C, Kb = 2**16, 8192
    s = jax.eval_shape(lambda: skiplist_init(C))
    k = jax.ShapeDtypeStruct((Kb,), jnp.uint64)
    text = jax.jit(insert_batch).lower(s, k, k).as_text()
    carries = [line.split(") : ", 1)[1] for line in text.splitlines()
               if re.search(r"stablehlo\.while\(", line)]
    assert carries, "no while loop found: the lowering's text changed"
    assert not any(f"tensor<{C}xi32>" in c for c in carries), carries
