"""Storage layer tests (SURVEY §1 L7): catalog parsing against the
reference's actual schema grammar, device tables, indexes."""

import jax.numpy as jnp
import numpy as np
import pytest

from deneva_tpu.storage import (Catalog, DenseIndex, DeviceTable, HashIndex,
                                SortedIndex, parse_schema)

YCSB_SCHEMA = """\
//size, type, name
TABLE=MAIN_TABLE
\t100,string,F0
\t100,string,F1

INDEX=MAIN_INDEX
\tMAIN_TABLE,0
"""

TPCC_FRAGMENT = """\
TABLE=DISTRICT
\t8,int64_t,D_ID
\t8,int64_t,D_W_ID
\t8,double,D_TAX
\t8,int64_t,D_NEXT_O_ID
"""


def test_parse_schema_ycsb():
    cat = parse_schema(YCSB_SCHEMA)
    t = cat.table("MAIN_TABLE")
    assert [c.name for c in t.columns] == ["F0", "F1"]
    assert t.columns[0].ctype == "string" and t.columns[0].size == 100
    assert t.tuple_size == 200
    assert cat.indexes["MAIN_INDEX"].table == "MAIN_TABLE"


def test_parse_schema_mixed_types_and_spaces():
    # the reference files mix tabs and spaces (PPS_schema.txt line 2)
    cat = parse_schema(TPCC_FRAGMENT.replace("\t8,int64_t,D_W_ID", "  8,int64_t,D_W_ID"))
    t = cat.table("DISTRICT")
    assert t.column("D_TAX").ctype == "double"
    assert t.column("D_NEXT_O_ID").index == 3


def test_device_table_gather_scatter_roundtrip():
    cat = parse_schema(TPCC_FRAGMENT)
    tab = DeviceTable.create(cat.table("DISTRICT"), capacity=16)
    slots = jnp.array([0, 3, 7])
    tab = tab.scatter(slots, {"D_NEXT_O_ID": jnp.array([10, 11, 12]),
                              "D_TAX": jnp.array([0.1, 0.2, 0.3])})
    out = tab.gather(slots, ("D_NEXT_O_ID", "D_TAX"))
    np.testing.assert_array_equal(out["D_NEXT_O_ID"], [10, 11, 12])
    np.testing.assert_allclose(out["D_TAX"], [0.1, 0.2, 0.3], rtol=1e-6)


def test_device_table_masked_scatter_goes_to_trash():
    cat = parse_schema(TPCC_FRAGMENT)
    tab = DeviceTable.create(cat.table("DISTRICT"), capacity=8)
    tab = tab.scatter(jnp.array([2, 2]), {"D_ID": jnp.array([5, 9])},
                      mask=jnp.array([False, True]))
    assert int(tab.columns["D_ID"][2]) == 9  # only the unmasked write landed


def test_device_table_scatter_add_duplicates_exact():
    cat = parse_schema(TPCC_FRAGMENT)
    tab = DeviceTable.create(cat.table("DISTRICT"), capacity=8)
    # ten concurrent increments of the same district counter
    tab = tab.scatter_add(jnp.zeros(10, jnp.int32),
                          {"D_NEXT_O_ID": jnp.ones(10, jnp.int32)})
    assert int(tab.columns["D_NEXT_O_ID"][0]) == 10


def test_device_table_append_prefix_sum_and_overflow():
    cat = parse_schema(TPCC_FRAGMENT)
    tab = DeviceTable.create(cat.table("DISTRICT"), capacity=4)
    mask = jnp.array([True, False, True, True])
    tab, slots = tab.append({"D_ID": jnp.array([1, 2, 3, 4])}, mask)
    np.testing.assert_array_equal(slots, [0, 4, 1, 2])  # masked row -> trash(4)
    assert int(tab.row_cnt) == 3
    # overflow: only one slot left
    tab, slots2 = tab.append({"D_ID": jnp.array([7, 8])}, jnp.array([True, True]))
    assert int(slots2[0]) == 3 and int(slots2[1]) == 4  # second insert dropped
    assert int(tab.row_cnt) == 4


APPEND_SCHEMA = """\
TABLE=RING
\t8,int64_t,A
\t8,double,B
\t24,string,C
\t10,string,D
"""

# (ring, capacity, lanes a call, live lanes of each call): which lanes are
# live is drawn; the counts place the cursor
APPEND_CASES = {
    "ring_wrap_inside_one_call": (True, 40, 16, [16, 16, 11, 0, 7]),
    "ring_call_ends_on_capacity": (True, 48, 16, [16, 9, 7, 16, 5, 16]),
    "ring_second_wrap": (True, 24, 16, [16, 16, 16, 3, 16, 16]),
    "ring_clamped_first_window": (True, 40, 16, [13, 13, 5, 16, 16, 1]),
    "ring_lanes_equal_capacity": (True, 16, 16, [5, 16, 9, 16, 0, 2]),
    "ring_random_masks": (True, 37, 8, [3, 8, 0, 5, 8, 8, 1, 7, 8, 6]),
    "ring_empty_masks": (True, 40, 16, [0, 0]),
    "ring_more_lanes_than_rows": (True, 8, 16, [3, 5, 0, 8, 2]),
    "table_fills_and_drops": (False, 40, 16, [16, 7, 0, 16, 16, 4]),
    "table_ends_on_capacity": (False, 32, 16, [16, 16, 16]),
    "table_clamped_window": (False, 40, 16, [13, 13, 5, 16]),
    "table_more_lanes_than_rows": (False, 8, 16, [3, 4, 6, 0]),
}


@pytest.mark.parametrize("case", APPEND_CASES)
def test_device_table_append_matches_the_slot_model(case):
    """`DeviceTable.append` against numpy's ``slots = (row_cnt + rank) %
    capacity``: the windows (a wrap inside a call, a call that ends on
    ``capacity``, a first window that `dynamic_update_slice` would clamp)
    and the scatter kept for a call of more lanes than rows write the
    same rows, return the same slots and cursor, leave the trash and pad
    rows zero, and count their lanes where the stats carry the
    counters."""
    import jax
    ring, cap, n, lives = APPEND_CASES[case]
    tab = DeviceTable.create(parse_schema(APPEND_SCHEMA).table("RING"), cap,
                             full_row=True, ring=ring)
    assert tab.columns["C"].shape[1:] == (24,)
    model = {c: np.zeros(v.shape, v.dtype) for c, v in tab.columns.items()}
    rng = np.random.default_rng(len(case))
    zero = jnp.zeros((), jnp.uint32)

    @jax.jit
    def step(tab, rows, mask):
        stats = {"append_window_lanes": zero, "append_scatter_lanes": zero}
        tab, slots = tab.append(rows, mask, stats=stats)
        return tab, slots, stats

    cnt = 0
    for k in lives:
        mask = np.zeros(n, bool)
        mask[rng.choice(n, size=k, replace=False)] = True
        rows = {"A": rng.integers(1, 1 << 30, n).astype(np.int32),
                "B": rng.random(n).astype(np.float32) + 1,
                "C": rng.integers(1, 256, (n, 24)).astype(np.uint8),
                "D": rng.integers(1, 256, (n, 10)).astype(np.uint8)}
        tab, slots, stats = step(tab, rows, mask)
        want = cnt + np.cumsum(mask) - mask
        if ring:
            want = np.where(mask, want % cap, cap)
            cnt += k
        else:
            want = np.where(mask & (want < cap), want, cap)
            cnt = min(cnt + k, cap)
        np.testing.assert_array_equal(slots, want)
        assert int(tab.row_cnt) == cnt
        written = want != cap
        for c in model:
            model[c][want[written]] = rows[c][written]
            # every row, the trash slot and the pad rows among them
            np.testing.assert_array_equal(np.asarray(tab.columns[c]),
                                          model[c], err_msg=c)
            assert not model[c][cap:].any()
        window, scatter = (0, n) if n > cap else (int(written.sum()), 0)
        assert (int(stats["append_window_lanes"]),
                int(stats["append_scatter_lanes"])) == (window, scatter)
    # a dict without the counters is left alone
    stats = {}
    tab.append(rows, mask, stats=stats)
    assert stats == {}


def test_compact_live_keeps_lane_order_for_every_mask_of_twelve_lanes():
    """`storage.table._compact_live` — the shifts by one bit of the
    move a round — on all 4,096 masks of 12 lanes at once: the live
    lanes arrive at the front in lane order, none lost to another on
    the way, whatever the widths of the columns beside them."""
    import jax
    from deneva_tpu.storage.table import _compact_live
    n = 12
    masks = (np.arange(1 << n)[:, None] >> np.arange(n)) & 1
    rows = {"A": jnp.arange(1, n + 1, dtype=jnp.int32),
            "B": jnp.arange(1, n + 1, dtype=jnp.float32) / 7,
            "C": (jnp.arange(n * 10).reshape(n, 10) % 251 + 1
                  ).astype(jnp.uint8)}
    out = jax.vmap(lambda m: _compact_live(rows, m))(
        jnp.asarray(masks, jnp.int32))
    for m, ids in zip(masks, np.asarray(out["A"])):     # every mask
        live = np.flatnonzero(m) + 1
        assert (ids[:len(live)] == live).all()
    for c, v in rows.items():       # every column, every 37th mask
        v, got = np.asarray(v), np.asarray(out[c])
        assert got.dtype == v.dtype and got.shape == (1 << n,) + v.shape
        for m, g in zip(masks[::37], got[::37]):
            np.testing.assert_array_equal(g[:m.sum()], v[m.astype(bool)])


def test_dense_index():
    idx = DenseIndex(base=100, stride=1, size=50, miss_slot=999)
    out = idx.lookup(jnp.array([100, 149, 150, 99, 7]))
    np.testing.assert_array_equal(out, [0, 49, 999, 999, 999])


def test_hash_index_roundtrip_and_misses():
    rng = np.random.default_rng(0)
    keys = rng.choice(1_000_000, size=5000, replace=False).astype(np.int32)
    slots = np.arange(5000, dtype=np.int32)
    idx = HashIndex.build(keys, slots, miss_slot=12345)
    out = np.asarray(idx.lookup(jnp.asarray(keys)))
    np.testing.assert_array_equal(out, slots)
    # misses
    miss_keys = np.array([1_000_001, 2_000_000], np.int32)
    out = np.asarray(idx.lookup(jnp.asarray(miss_keys)))
    np.testing.assert_array_equal(out, [12345, 12345])


def test_hash_index_rejects_duplicates():
    with pytest.raises(ValueError):
        HashIndex.build(np.array([5, 5], np.int32), np.array([0, 1], np.int32),
                        miss_slot=0)


def test_sorted_index_lookup_and_misses():
    keys = np.array([40, 10, 30, 20], np.int32)
    slots = np.array([4, 1, 3, 2], np.int32)
    idx = SortedIndex.build(keys, slots, miss_slot=99)
    out = np.asarray(idx.lookup(jnp.array([10, 20, 30, 40, 25, 5, 50])))
    np.testing.assert_array_equal(out, [1, 2, 3, 4, 99, 99, 99])


def test_sorted_index_nonunique_first_and_count():
    # nonunique keys: reference index_btree via itemid_t chains
    keys = np.array([7, 7, 7, 9], np.int32)
    slots = np.array([0, 1, 2, 3], np.int32)
    idx = SortedIndex.build(keys, slots, miss_slot=-1)
    assert int(idx.lookup(jnp.array(7))) == 0  # stable: first inserted
    np.testing.assert_array_equal(
        np.asarray(idx.lookup_count(jnp.array([7, 9, 8]))), [3, 1, 0])


def test_sorted_index_range_scan_padded():
    keys = np.arange(0, 100, 10, dtype=np.int32)          # 0,10,...,90
    slots = np.arange(10, dtype=np.int32)
    idx = SortedIndex.build(keys, slots, miss_slot=-1)
    s, ok = idx.range_slots(jnp.array([35]), width=4)     # keys 40,50,60,70
    np.testing.assert_array_equal(np.asarray(s)[0], [4, 5, 6, 7])
    assert bool(np.all(np.asarray(ok)[0]))
    # past-the-end padding
    s, ok = idx.range_slots(jnp.array([85]), width=4)     # only 90 remains
    np.testing.assert_array_equal(np.asarray(ok)[0], [True, False, False, False])
    np.testing.assert_array_equal(np.asarray(s)[0], [9, -1, -1, -1])


def test_sorted_index_empty_returns_misses():
    idx = SortedIndex.build(np.array([], np.int32), np.array([], np.int32),
                            miss_slot=99)
    np.testing.assert_array_equal(np.asarray(idx.lookup(jnp.array([1, 2]))),
                                  [99, 99])
    np.testing.assert_array_equal(np.asarray(idx.lookup_count(jnp.array([1]))),
                                  [0])
    s, ok = idx.range_slots(jnp.array([0]), width=3)
    np.testing.assert_array_equal(np.asarray(s)[0], [99, 99, 99])
    assert not np.any(np.asarray(ok))
    s, ok = idx.range_between(jnp.array([0]), jnp.array([5]), width=3)
    assert not np.any(np.asarray(ok))


def test_sorted_index_range_between():
    keys = np.arange(0, 100, 10, dtype=np.int32)
    slots = np.arange(10, dtype=np.int32)
    idx = SortedIndex.build(keys, slots, miss_slot=-1)
    s, ok = idx.range_between(jnp.array([20]), jnp.array([45]), width=8)
    np.testing.assert_array_equal(np.asarray(ok)[0],
                                  [True, True, True, False, False, False, False, False])
    np.testing.assert_array_equal(np.asarray(s)[0][:3], [2, 3, 4])


def test_dynamic_sorted_index_insert_merge():
    """Dynamic ordered index (VERDICT r3 next #9, the index_btree insert
    analogue): batched merge-inserts keep probes exact — verified
    against a numpy model across several insert epochs."""
    import jax.numpy as jnp

    from deneva_tpu.storage.index import DynamicSortedIndex

    rng = np.random.default_rng(11)
    idx = DynamicSortedIndex.build(np.asarray([5, 9], np.int32),
                                   np.asarray([50, 90], np.int32),
                                   miss_slot=999, cap=64)
    model: list[tuple[int, int]] = [(5, 50), (9, 90)]
    slot = 100
    for _ in range(4):
        ks = rng.integers(0, 40, size=8).astype(np.int32)
        ss = np.arange(slot, slot + 8, dtype=np.int32)
        slot += 8
        mask = rng.random(8) < 0.75
        idx = idx.insert(jnp.asarray(ks), jnp.asarray(ss),
                         jnp.asarray(mask))
        model += [(int(k), int(s)) for k, s, m in zip(ks, ss, mask) if m]
    model.sort(key=lambda e: e[0])
    # lookup: first slot of each present key; misses -> miss_slot
    for q in range(42):
        want = next((s for k, s in model if k == q), 999)
        got = int(np.asarray(idx.lookup(jnp.asarray([q], jnp.int32)))[0])
        if any(k == q for k, _ in model):
            assert got in [s for k, s in model if k == q], q
        else:
            assert got == 999, q
        cnt = int(np.asarray(idx.lookup_count(
            jnp.asarray([q], jnp.int32)))[0])
        assert cnt == sum(1 for k, _ in model if k == q), q
    # range scan returns exactly the in-range slots, ascending by key
    slots, ok = idx.range_between(jnp.asarray([10], jnp.int32),
                                  jnp.asarray([30], jnp.int32), 64)
    got = sorted(np.asarray(slots)[0][np.asarray(ok)[0]].tolist())
    want = sorted(s for k, s in model if 10 <= k <= 30)
    assert got == want
    assert not bool(np.asarray(idx.overflowed()))


def test_dynamic_sorted_index_overflow_flag():
    from deneva_tpu.storage.index import DynamicSortedIndex
    import jax.numpy as jnp

    idx = DynamicSortedIndex.build(np.zeros(0, np.int32),
                                   np.zeros(0, np.int32),
                                   miss_slot=7, cap=4)
    ks = jnp.asarray([3, 1, 2, 5, 4, 0], jnp.int32)
    idx = idx.insert(ks, jnp.arange(6, dtype=jnp.int32),
                     jnp.ones(6, bool))
    assert bool(np.asarray(idx.overflowed()))
    # the smallest cap keys survive; the dropped tail reads as misses
    assert (np.asarray(idx.keys) == [0, 1, 2, 3]).all()
    assert int(np.asarray(idx.lookup(jnp.asarray([5], jnp.int32)))[0]) == 7


def test_mc_layout_roundtrip_and_geometry():
    """to_mc_layout permutes rows owner-major: block d holds exactly the
    anchors ≡ d (mod D) in anchor order, data is preserved, and pad rows
    are zero (the block-local trash)."""
    from deneva_tpu.storage.table import (fill_columns, mc_block_geometry,
                                          to_mc_layout)

    schema = parse_schema("TABLE=T\n\t8,int64_t,V\n")
    cap, R, D = 24 * 5, 5, 4            # 24 anchors x 5 rows, 4 blocks
    tab = DeviceTable.create(schema.table("T"), cap)
    vals = np.arange(cap, dtype=np.int32) * 7 + 3
    tab = fill_columns(tab, cap, {"V": vals})
    mc = to_mc_layout(tab, D, anchor_rows=R)
    local_rows, lb = mc_block_geometry(cap, R, D)
    assert local_rows == (24 // D) * R and mc.mc_parts == D
    col = np.asarray(mc.columns["V"])
    assert col.shape[0] == D * lb
    for d in range(D):
        block = col[d * lb:(d + 1) * lb]
        anchors = [d + D * j for j in range(24 // D)]
        expect = np.concatenate(
            [vals[a * R:(a + 1) * R] for a in anchors])
        assert (block[:local_rows] == expect).all(), d
        assert (block[local_rows:] == 0).all(), d   # block trash/pad
