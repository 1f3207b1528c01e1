"""Ops-layer kernels vs. brute-force numpy oracles."""

import numpy as np
import jax.numpy as jnp
import jax
import pytest

from deneva_tpu.ops import (
    bucket_hash, combine_key, Zipfian, last_writer,
    access_incidence, earlier_edges, greedy_first_fit,
    wavefront_levels, precedence_levels, key_overlap,
)


def test_bucket_hash_range_and_independence():
    keys = jnp.arange(10000, dtype=jnp.int32)
    ident = combine_key(3, keys)
    h0 = np.asarray(bucket_hash(ident, 1024, family=0))
    h1 = np.asarray(bucket_hash(ident, 1024, family=1))
    assert h0.min() >= 0 and h0.max() < 1024
    # families disagree on most keys
    assert (h0 == h1).mean() < 0.01
    # roughly uniform occupancy
    counts = np.bincount(h0, minlength=1024)
    assert counts.max() < 40

def test_combine_key_separates_tables():
    keys = jnp.arange(1000, dtype=jnp.int32)
    a = np.asarray(bucket_hash(combine_key(0, keys), 4096))
    b = np.asarray(bucket_hash(combine_key(1, keys), 4096))
    assert (a == b).mean() < 0.01


def test_zipfian_uniform_theta0():
    z = Zipfian(1000, 0.0)
    s = np.asarray(z.sample(jax.random.PRNGKey(0), (20000,)))
    assert s.min() >= 0 and s.max() < 1000
    assert abs(s.mean() - 499.5) < 15

def test_zipfian_skew():
    z = Zipfian(1 << 20, 0.9)
    s = np.asarray(z.sample(jax.random.PRNGKey(1), (50000,)))
    assert s.min() >= 0 and s.max() < (1 << 20)
    # theta=0.9 at n=2^20: ~8% of mass on the 10 hottest keys (zeta math)
    assert (s < 10).mean() > 0.06
    assert (s == 0).mean() > 0.015


def test_hotset_two_tier_split():
    from deneva_tpu.ops import HotSet
    h = HotSet(n=1 << 20, hot_max=100, access_perc=0.3)
    s = np.asarray(h.sample(jax.random.PRNGKey(2), (40000,)))
    assert s.min() >= 0 and s.max() < (1 << 20)
    hot_frac = (s < 100).mean()
    assert abs(hot_frac - 0.3) < 0.02          # ACCESS_PERC of accesses...
    hot = s[s < 100]
    assert np.bincount(hot, minlength=100).min() > 0  # ...uniform over DATA_PERC keys


def _overlap_case(case: str, a: int, b: int = 48, seed: int = 7):
    """(tables, keys, U mask, W mask) of a random epoch: padded slots,
    inactive txns, pure reads, blind writes and RMW lanes everywhere;
    ``case`` adds what its name says."""
    rng = np.random.default_rng([seed, a, len(case)])
    keys = rng.integers(0, 60, (b, a))
    tables = np.zeros((b, a), np.int64)
    is_w = rng.random((b, a)) < 0.4
    is_r = ~is_w | (rng.random((b, a)) < 0.3)
    if case == "rmw":                 # every lane reads AND writes
        is_r[:], is_w[:] = True, True
    if case == "repeat":              # one key in every slot of a txn
        keys[::2] = keys[::2, :1]
    if case == "tables":              # the same keys in three tables
        keys = rng.integers(0, 12, (b, a))
        tables = rng.integers(0, 3, (b, a))
    valid = rng.random((b, a)) < 0.8
    active = rng.random(b) < 0.85
    v = valid & active[:, None]
    return tables, keys, v & (is_r | is_w), v & is_w


@pytest.mark.parametrize("a", [1, 10, 16])
@pytest.mark.parametrize("case", ["padded", "rmw", "repeat", "tables"])
def test_key_overlap_is_the_set_intersection(case, a):
    """`key_overlap` against Python sets of (table, key): txn i's masked
    U slots meet txn j's masked W slots."""
    tables, keys, um, wm = _overlap_case(case, a)
    ident = combine_key(jnp.asarray(tables, jnp.int32),
                        jnp.asarray(keys, jnp.int32))
    got = np.asarray(jax.jit(key_overlap)(ident, jnp.asarray(um),
                                          jnp.asarray(wm)))
    b = keys.shape[0]
    sets = lambda m: [{(int(t), int(k)) for t, k, on  # noqa: E731
                       in zip(tables[i], keys[i], m[i]) if on}
                      for i in range(b)]
    us, ws = sets(um), sets(wm)
    want = np.array([[bool(us[i] & ws[j]) for j in range(b)]
                     for i in range(b)])
    assert (got == want).all()
    assert want.any() and not want.all()
    assert got.dtype == np.bool_ and got.shape == (b, b)


def test_last_writer_oracle():
    rng = np.random.default_rng(0)
    n, cap = 256, 32
    slots = rng.integers(0, cap + 1, n).astype(np.int32)
    order = rng.integers(0, 50, n).astype(np.int32)
    mask = rng.random(n) < 0.8
    got = np.asarray(last_writer(jnp.asarray(slots), jnp.asarray(order),
                                 jnp.asarray(mask), cap))
    # oracle: per slot, winner = max order, tie -> highest index
    for s in range(cap + 1):
        idx = [i for i in range(n) if slots[i] == s and mask[i]]
        winners = [i for i in idx if got[i]]
        if not idx:
            assert not winners
            continue
        assert len(winners) == 1
        w = winners[0]
        best = max(order[i] for i in idx)
        assert order[w] == best
        assert w == max(i for i in idx if order[i] == best)
    # masked-out entries never win
    assert not got[~mask].any()


def _bruteforce_conflict(keysets_a, keysets_b):
    b = len(keysets_a)
    c = np.zeros((b, b), bool)
    for i in range(b):
        for j in range(b):
            c[i, j] = bool(keysets_a[i] & keysets_b[j])
    return c

def test_key_overlap_of_a_set_with_itself():
    rng = np.random.default_rng(2)
    b, a = 32, 6
    keys = rng.integers(0, 500, (b, a)).astype(np.int32)
    valid = jnp.asarray(rng.random((b, a)) < 0.9)
    ident = combine_key(0, jnp.asarray(keys))
    got = np.asarray(key_overlap(ident, valid, valid))
    sets = [set(keys[i][np.asarray(valid[i])].tolist()) for i in range(b)]
    want = _bruteforce_conflict(sets, sets)
    assert (got == want).all()


def test_access_incidence_counts_the_lanes_of_each_bucket():
    rng = np.random.default_rng(4)
    b, a, k = 32, 6, 64
    buckets = rng.integers(0, k, (b, a)).astype(np.int32)
    valid = rng.random((b, a)) < 0.8
    got = np.asarray(access_incidence(jnp.asarray(buckets),
                                      jnp.asarray(valid), k), np.float32)
    want = np.zeros((b, k), np.float32)
    for i in range(b):
        np.add.at(want[i], buckets[i][valid[i]], 1)
    assert got.shape == (b, k) and (got == want).all()


def _greedy_oracle(conflict, rank, active):
    b = len(rank)
    order = sorted(range(b), key=lambda i: (rank[i], i))
    win = np.zeros(b, bool)
    for i in order:
        if not active[i]:
            continue
        blocked = any(win[j] and conflict[i, j] for j in range(b) if j != i)
        win[i] = not blocked
    return win

def test_greedy_first_fit_oracle():
    rng = np.random.default_rng(3)
    b = 64
    conflict = rng.random((b, b)) < 0.08
    conflict = conflict | conflict.T
    np.fill_diagonal(conflict, True)
    rank = rng.integers(0, 20, b).astype(np.int32)
    active = rng.random(b) < 0.9
    e = earlier_edges(jnp.asarray(conflict), jnp.asarray(rank),
                      jnp.asarray(active))
    win, lose, und = (np.asarray(x) for x in
                      greedy_first_fit(e, jnp.asarray(active), rounds=b))
    assert not und.any()
    want = _greedy_oracle(conflict, rank, active)
    want &= active
    assert (win == want).all()
    assert (lose == (active & ~want)).all()

def test_greedy_first_fit_round_cap_defers_safely():
    # a chain 0-1-2-...-n: each conflicts with predecessor; few rounds
    b = 32
    conflict = np.zeros((b, b), bool)
    for i in range(1, b):
        conflict[i, i - 1] = conflict[i - 1, i] = True
    rank = np.arange(b, dtype=np.int32)
    active = np.ones(b, bool)
    e = earlier_edges(jnp.asarray(conflict), jnp.asarray(rank), jnp.asarray(active))
    win, lose, und = (np.asarray(x) for x in
                      greedy_first_fit(e, jnp.asarray(active), rounds=4))
    # decided prefix follows alternating pattern; nothing both win&lose
    assert not (win & lose).any()
    dec = win | lose
    assert dec[:4].all()
    # undecided tail exists and no undecided txn is marked winner
    assert und.any() and not (und & win).any()
    # winners among decided = even positions
    for i in range(b):
        if dec[i]:
            assert win[i] == (i % 2 == 0)


def test_wavefront_levels_chain():
    b = 16
    conflict = np.zeros((b, b), bool)
    for i in range(1, b):
        conflict[i, i - 1] = conflict[i - 1, i] = True
    rank = np.arange(b, dtype=np.int32)
    active = np.ones(b, bool)
    e = earlier_edges(jnp.asarray(conflict), jnp.asarray(rank), jnp.asarray(active))
    lv, ovf = (np.asarray(x) for x in wavefront_levels(e, max_level=20))
    assert (lv == np.arange(b)).all()
    assert not ovf.any()
    lv, ovf = (np.asarray(x) for x in wavefront_levels(e, max_level=5))
    assert ovf.sum() == b - 6


def test_precedence_levels_cycle_detection():
    b = 8
    p = np.zeros((b, b), bool)
    # chain 0->1->2, cycle 3<->4, node 5 downstream of cycle, 6,7 free
    p[0, 1] = p[1, 2] = True
    p[3, 4] = p[4, 3] = True
    p[4, 5] = True
    active = np.ones(b, bool)
    lv, unstable = (np.asarray(x) for x in
                    precedence_levels(jnp.asarray(p), jnp.asarray(active), rounds=16))
    assert lv[0] == 0 and lv[1] == 1 and lv[2] == 2
    assert not unstable[[0, 1, 2, 6, 7]].any()
    assert unstable[3] and unstable[4] and unstable[5]


@pytest.mark.slow
def test_seg_scan_matches_serial_reference():
    """The Kogge-Stone segmented scan must be exact for any associative
    combine — including an unflagged first lane and additive combines
    (regression: an earlier fill treated 0 as a combine identity)."""
    import jax.numpy as jnp
    from deneva_tpu.ops.forward import _seg_scan

    rng = np.random.default_rng(0)
    combs = {"max": max, "left": lambda a, b: a, "add": lambda a, b: a + b}
    jcombs = {"max": jnp.maximum, "left": lambda a, b: a,
              "add": lambda a, b: a + b}
    for trial in range(25):
        n = int(rng.integers(1, 50))
        f = rng.random(n) < 0.25          # flags[0] frequently False
        v = rng.integers(-9, 9, n)
        for name in combs:
            got = np.asarray(_seg_scan(jnp.asarray(f),
                                       jnp.asarray(v, jnp.int32),
                                       jcombs[name]))
            ref = np.empty(n, np.int64)
            for i in range(n):
                acc = int(v[i])
                j = i
                while not f[j] and j > 0:
                    j -= 1
                    acc = combs[name](int(v[j]), acc)
                ref[i] = acc
            assert (got == ref).all(), (trial, name)


# ---- in-batch read forwarding (ops/forward.py) -------------------------

def test_last_earlier_writer_basic():
    from deneva_tpu.ops import last_earlier_writer
    # txn0 writes k5; txn1 reads k5; txn2 writes k5; txn3 reads k5, k9
    keys = jnp.array([[5], [5], [5], [5]], jnp.int32)
    keys = jnp.concatenate([keys, jnp.array([[1], [2], [3], [9]], jnp.int32)], 1)
    is_w = jnp.array([[True, False], [False, False],
                      [True, False], [False, False]])
    valid = jnp.ones((4, 2), bool)
    rank = jnp.array([0, 1, 2, 3], jnp.int32)
    fwd = np.asarray(last_earlier_writer(keys, rank, is_w, valid))
    assert fwd[1, 0] == 0     # txn1 reads txn0's write of k5
    assert fwd[3, 0] == 2     # txn3 reads txn2's (later) write of k5
    assert fwd[0, 0] == -1    # first writer has no predecessor
    assert fwd[3, 1] == -1    # k9 never written


def test_last_earlier_writer_same_rank_not_own_write():
    from deneva_tpu.ops import last_earlier_writer
    # one txn reads k7 in lane 0 and writes k7 in lane 1: the read must
    # NOT see its own write (serial semantics: reads before writes)
    keys = jnp.full((1, 2), 7, jnp.int32)
    is_w = jnp.array([[False, True]])
    valid = jnp.ones((1, 2), bool)
    fwd = np.asarray(last_earlier_writer(keys, jnp.array([4], jnp.int32),
                                         is_w, valid))
    assert fwd[0, 0] == -1


@pytest.mark.slow
def test_last_earlier_writer_matches_serial_reference():
    from deneva_tpu.ops import last_earlier_writer
    rng = np.random.default_rng(11)
    B, A, K = 64, 6, 13
    keys = rng.integers(0, K, (B, A)).astype(np.int32)
    is_w = rng.random((B, A)) < 0.5
    valid = rng.random((B, A)) < 0.9
    rank = np.argsort(rng.random(B)).astype(np.int32)  # unique, shuffled
    got = np.asarray(last_earlier_writer(
        jnp.asarray(keys), jnp.asarray(rank), jnp.asarray(is_w),
        jnp.asarray(valid)))
    # serial reference: walk txns in rank order
    last_w = {}
    exp = np.full((B, A), -1, np.int32)
    for i in np.argsort(rank):
        for a in range(A):
            if valid[i, a]:
                exp[i, a] = last_w.get(keys[i, a], -1)
        for a in range(A):
            if valid[i, a] and is_w[i, a]:
                k = keys[i, a]
                last_w[k] = max(last_w.get(k, -1), int(rank[i]))
    # compare only on valid lanes (invalid lanes are unspecified)
    assert (got[valid] == exp[valid]).all()


def test_forward_execute_mono_scatter_matches_legacy():
    """The monotone pre-sorted scatter (mono=True, the hot-path default)
    must be bit-identical to the legacy trash-steered scatter on both
    table state and checksum — winners' values land, losers' duplicate
    rewrites are idempotent, pre-first-winner lanes drop."""
    from deneva_tpu.ops import forward_plan_flat
    from deneva_tpu.workloads.ycsb import _forward_execute_f0

    rng = np.random.default_rng(11)
    n, tab = 4096, 512
    keys = rng.integers(0, 200, n).astype(np.int32)   # heavy duplication
    keys[rng.random(n) < 0.05] = np.iinfo(np.int32).max  # invalid lanes
    rank = np.repeat(np.arange(n // 4, dtype=np.int32), 4)
    w = rng.random(n) < 0.5
    w &= keys != np.iinfo(np.int32).max
    p = forward_plan_flat(jnp.asarray(keys), jnp.asarray(rank),
                          jnp.asarray(w))
    big = jnp.int32(np.iinfo(np.int32).max)
    slots = jnp.where(p.keys != big, p.keys, tab)     # identity index
    f0 = jnp.asarray(rng.integers(0, 2**32, tab + 1, dtype=np.uint32))
    a_f0, a_cks, a_w, a_l, a_r, _ = _forward_execute_f0(
        f0, p, slots, tab, mono=False)
    b_f0, b_cks, b_w, b_l, b_r, _ = _forward_execute_f0(
        f0, p, slots, tab, mono=True)
    # trash slot may differ (legacy parks losers there); data rows must not
    np.testing.assert_array_equal(np.asarray(a_f0)[:tab],
                                  np.asarray(b_f0)[:tab])
    assert int(a_cks) == int(b_cks) and int(a_w) == int(b_w)
    # fingerprint mode hands the scatter and the gather every lane, in
    # both forms
    assert int(a_l) == int(b_l) == int(a_r) == int(b_r) == n


def test_forward_execute_mono_scatter_matches_legacy_full_row():
    from deneva_tpu.ops import forward_plan_flat
    from deneva_tpu.workloads.ycsb import _forward_execute_f0

    rng = np.random.default_rng(12)
    n, tab, width = 1024, 128, 24
    keys = rng.integers(0, 64, n).astype(np.int32)
    rank = np.repeat(np.arange(n // 2, dtype=np.int32), 2)
    w = rng.random(n) < 0.5
    p = forward_plan_flat(jnp.asarray(keys), jnp.asarray(rank),
                          jnp.asarray(w))
    slots = p.keys
    f0 = jnp.asarray(rng.integers(0, 256, (tab + 1, width), dtype=np.uint8))
    a_f0, a_cks, _, a_l, *_ = _forward_execute_f0(f0, p, slots, tab,
                                                  mono=False)
    b_f0, b_cks, _, b_l, *_ = _forward_execute_f0(f0, p, slots, tab,
                                                  mono=True)
    np.testing.assert_array_equal(np.asarray(a_f0)[:tab],
                                  np.asarray(b_f0)[:tab])
    assert int(a_cks) == int(b_cks)
    # full rows: mono goes through `scatter_winner_rows`, which leaves
    # the trash row alone and is handed fewer lanes than there are
    np.testing.assert_array_equal(np.asarray(b_f0)[tab], np.asarray(f0)[tab])
    assert int(a_l) == n and 0 < int(b_l) <= n


@pytest.mark.parametrize("write_frac", [0.5, 0.02, 0.0])
def test_mono_scatter_indices_keep_the_sorted_promise(write_frac):
    """`indices_are_sorted=True` is a promise: the CPU backend ignores
    it, the TPU's scatter relies on it (a -1 sentinel for the lanes
    before the first winner wraps to the last row, put the largest index
    first, and lost every write on the chip).  The index vector must be
    non-decreasing and within [0, n_rows]; lanes before the first winner
    repeat the first winner's write; with no winner every lane is out of
    range and the table is untouched, pad rows included."""
    from deneva_tpu.ops import forward_plan_flat
    from deneva_tpu.workloads.ycsb import (_forward_execute_f0,
                                           _mono_winner_lanes)

    rng = np.random.default_rng(13)
    n, tab, rows = 2048, 300, 320           # rows > tab: trash + pad rows
    big = np.iinfo(np.int32).max
    keys = rng.integers(0, tab, n).astype(np.int32)
    keys[rng.random(n) < 0.05] = big
    rank = np.repeat(np.arange(n // 4, dtype=np.int32), 4)
    w = (rng.random(n) < write_frac) & (keys != big)
    w[keys == keys[keys != big].min()] = False   # leading lanes only read
    p = forward_plan_flat(jnp.asarray(keys), jnp.asarray(rank),
                          jnp.asarray(w))
    slots = jnp.where(p.keys != big, p.keys, tab)
    wslot, wkey, wrank = (np.asarray(x) for x in
                          _mono_winner_lanes(p, slots, rows))
    win = np.asarray(p.win)
    assert (np.diff(wslot) >= 0).all()
    assert wslot.min() >= 0 and wslot.max() <= rows
    if win.any():
        first = int(np.argmax(win))
        assert first > 0                    # the case under test exists
        assert (wslot[:first] == wslot[first]).all()
        assert (wkey[:first] == wkey[first]).all()
        assert (wrank[:first] == wrank[first]).all()
        assert wslot.max() < tab
    else:
        assert (wslot == rows).all()
    f0 = jnp.asarray(rng.integers(0, 2**32, rows, dtype=np.uint32))
    a_f0, a_cks, *_ = _forward_execute_f0(f0, p, slots, tab, mono=False)
    b_f0, b_cks, *_ = _forward_execute_f0(f0, p, slots, tab, mono=True)
    np.testing.assert_array_equal(np.asarray(a_f0)[:tab],
                                  np.asarray(b_f0)[:tab])
    # mono never touches the trash slot or a pad row
    np.testing.assert_array_equal(np.asarray(b_f0)[tab:],
                                  np.asarray(f0)[tab:])
    assert int(a_cks) == int(b_cks)


# ---- scatter_winner_rows: only the final writers reach the row scatter ---

def _winner_case(pattern: str, n: int, cap: int, chunk: int, edge: int,
                 rng):
    """(slots, win) for one epoch of n lanes over `cap` rows; `cap` is
    the trash slot.  Winners hold distinct slots (one final writer a
    row) unless the pattern says otherwise.  `edge`: the most chunks the
    loop takes before the one sorted scatter is cheaper."""
    slots = rng.choice(cap, size=n, replace=False).astype(np.int32)
    win = np.zeros(n, bool)
    count = {"none": 0, "all": n, "two_chunks": 2 * chunk,
             "two_chunks_and_one": 2 * chunk + 1, "edge": edge * chunk,
             "edge_and_one": edge * chunk + 1}.get(pattern)
    if count is not None:
        win[rng.choice(n, count, replace=False)] = True
    elif pattern == "behind_losers":
        win[-5:] = True
    elif pattern == "trash_and_twins":
        # `last_writer` lets lanes aimed at the trash slot "win", and
        # `level_exec` hands over one txn's duplicate lanes of one slot
        # (identical values); a miss may also carry a negative slot
        win[rng.random(n) < 0.3] = True
        slots[::7] = cap
        slots[1::2] = slots[::2]
        slots[3] = -1
    else:
        raise AssertionError(pattern)
    return slots, win


# (table, pattern, which branch must run).  `small`: a pass over the
# column is cheap, so past a few chunks the one sorted scatter of all n
# lanes runs; `large`: the loop at any count, all 64 chunks included
_WINNER_CASES = [
    ("small", "none", "loop"), ("small", "all", "whole"),
    ("small", "edge", "loop"), ("small", "edge_and_one", "whole"),
    ("small", "behind_losers", "loop"), ("small", "trash_and_twins", "whole"),
    ("large", "none", "loop"), ("large", "all", "loop"),
    ("large", "two_chunks", "loop"), ("large", "two_chunks_and_one", "loop"),
    ("large", "behind_losers", "loop"), ("large", "trash_and_twins", "loop"),
]


@pytest.mark.parametrize("in_slot_order", [True, False],
                         ids=["slot_order", "any_order"])
@pytest.mark.parametrize("table,pattern,branch", _WINNER_CASES,
                         ids=[f"{t}-{p}" for t, p, _ in _WINNER_CASES])
def test_scatter_winner_rows_matches_the_trash_steered_scatter(
        table, pattern, branch, in_slot_order):
    """The winners-only row scatter against the legacy one (every lane
    issued, losers steered to the trash row): the table below `capacity`
    bit for bit, the trash row and the padding rows untouched, and the
    lanes it reports = what its two branches issue — whole chunks
    covering the winners (no winner: none at all), or all n lanes with
    the sorted promise."""
    from deneva_tpu.ops import scatter as sc
    from deneva_tpu.workloads.ycsb import _field_bytes

    rng = np.random.default_rng(list(f"{pattern}/{table}".encode()))
    n, width = 1024, 24
    cap = 2_000 if table == "small" else 150_000
    rows = cap + 9                          # trash row + 8 padding rows
    chunk = -(-n // sc._CHUNKS)
    edge = min((rows + 6 * n - 1) // (chunk * sc._ROWS_PER_LANE),
               n // chunk)
    slots, win = _winner_case(pattern, n, cap, chunk, edge, rng)
    keys = slots.astype(np.int32) * 3 + 1   # a function of the slot
    order = rng.integers(0, 1000, n).astype(np.int32)
    if pattern == "trash_and_twins":
        order[1::2] = order[::2]            # twins carry one value
    if in_slot_order:
        o = np.argsort(np.where(slots < 0, cap, slots), kind="stable")
        slots, win, keys, order = slots[o], win[o], keys[o], order[o]
    col = jnp.asarray(rng.integers(0, 256, (rows, width), dtype=np.uint8))
    value = lambda k, o: _field_bytes(k, o, width)      # noqa: E731
    got, lanes, _groups, read = jax.jit(sc.scatter_winner_rows,
                                        static_argnums=(4, 5))(
        col, jnp.asarray(slots), jnp.asarray(win),
        (jnp.asarray(keys), jnp.asarray(order)), value, cap,
        col[cap].sum(dtype=jnp.uint32))
    assert int(read) == int(np.asarray(col)[cap].sum())  # handed back
    steer = np.where(win & (slots >= 0), slots, cap)
    want = col.at[jnp.asarray(steer)].set(value(jnp.asarray(keys),
                                                jnp.asarray(order)))
    np.testing.assert_array_equal(np.asarray(got)[:cap],
                                  np.asarray(want)[:cap])
    np.testing.assert_array_equal(np.asarray(got)[cap:],
                                  np.asarray(col)[cap:])
    cnt = int((win & (slots >= 0) & (slots < cap)).sum())
    trips = -(-cnt // chunk)
    assert 0 < edge and (edge < n // chunk) == (table == "small")
    assert (trips <= edge) == (branch == "loop")
    assert int(lanes) == (trips * chunk if branch == "loop" else n)


@pytest.mark.parametrize("write_frac", [0.5, 0.02, 0.0, 1.0])
def test_winner_scatter_indices_keep_the_sorted_promise(write_frac):
    """`indices_are_sorted=True` is a promise (PR 22: a -1 sentinel wraps
    to the last row, put the largest index first, and the chip lost
    every write).  What `compact_winners` hands XLA is non-decreasing
    and within [0, n_rows], as a whole and chunk by chunk, with no
    winner (every lane out of range) and with every write lane a
    winner; the winners' slots come first, ascending, each with its own
    (key, rank)."""
    from deneva_tpu.ops import compact_winners, forward_plan_flat
    from deneva_tpu.ops import scatter as sc

    rng = np.random.default_rng(13)
    n, tab, rows = 2048, 5000, 5016
    big = np.iinfo(np.int32).max
    if write_frac == 1.0:
        keys = rng.permutation(tab)[:n].astype(np.int32)   # all distinct
    else:
        keys = rng.integers(0, tab, n).astype(np.int32)
        keys[rng.random(n) < 0.05] = big
    rank = np.repeat(np.arange(n // 4, dtype=np.int32), 4)
    w = (rng.random(n) < write_frac) & (keys != big)
    p = forward_plan_flat(jnp.asarray(keys), jnp.asarray(rank),
                          jnp.asarray(w))
    slots = jnp.where(p.keys != big, p.keys, tab)
    idx, (ck, cr), cnt = compact_winners(slots, p.win, (p.keys, p.rank),
                                         tab, rows)
    idx, ck, cr, cnt = np.asarray(idx), np.asarray(ck), np.asarray(cr), \
        int(cnt)
    win = np.asarray(p.win)
    assert cnt == win.sum()
    if write_frac == 1.0:
        assert cnt == n                     # every lane a winner
    assert (np.diff(idx) >= 0).all()
    assert idx.min() >= 0 and idx.max() <= rows
    chunk = -(-n // sc._CHUNKS)
    for at in range(0, n, chunk):
        part = idx[at:at + chunk]
        assert (np.diff(part) >= 0).all() and part.max() <= rows
    assert (idx[cnt:] == rows).all() and (idx[:cnt] < tab).all()
    np.testing.assert_array_equal(idx[:cnt], np.asarray(slots)[win])
    np.testing.assert_array_equal(ck[:cnt], np.asarray(p.keys)[win])
    np.testing.assert_array_equal(cr[:cnt], np.asarray(p.rank)[win])
