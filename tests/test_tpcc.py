"""TPC-C workload: loader invariants, generation distributions, money
conservation, D_NEXT_O_ID / order-insert consistency (the reference's
consistency oracle is `YCSB_ABORT_MODE`-style spot checks; here we assert
TPC-C's actual audit invariants over the device tables)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deneva_tpu.config import Config
from deneva_tpu.engine import Engine
from deneva_tpu.workloads import get_workload
from deneva_tpu.workloads.tpcc import TPCC_NEW_ORDER, TPCC_PAYMENT


def tpcc_cfg(**kw):
    base = dict(workload="TPCC", num_wh=2, cust_per_dist=120,
                max_items=200, max_items_per_txn=5, max_accesses=8,
                epoch_batch=64, conflict_buckets=1024,
                max_txn_in_flight=256, insert_table_cap=1 << 14,
                warmup_secs=0.0, done_secs=0.2)
    base.update(kw)
    from deneva_tpu.config import WorkloadKind, CCAlg
    base["workload"] = WorkloadKind(base["workload"])
    if "cc_alg" in base:
        base["cc_alg"] = CCAlg(base["cc_alg"])
    return Config(**base)


def run_epochs(cfg, n=25, seed=0):
    eng = Engine(cfg, get_workload(cfg))
    state = eng.init_state(seed)
    state = eng.jit_run(state, n)
    return jax.device_get(state)


def test_loader_shapes_and_invariants():
    cfg = tpcc_cfg()
    wl = get_workload(cfg)
    db = wl.load()
    assert set(db) == {"WAREHOUSE", "DISTRICT", "CUSTOMER", "HISTORY",
                       "NEW-ORDER", "ORDER", "ORDER-LINE", "ITEM", "STOCK"}
    assert int(db["DISTRICT"].row_cnt) == 2 * 10
    next_o = db["DISTRICT"].host_column("D_NEXT_O_ID")
    assert (next_o == 3001).all()
    cw = db["CUSTOMER"].host_column("C_W_ID")
    assert cw.min() == 0 and cw.max() == 1
    sq = db["STOCK"].host_column("S_QUANTITY")
    assert sq.min() >= 10 and sq.max() <= 100


@pytest.mark.slow
def test_generation_distributions():
    cfg = tpcc_cfg(perc_payment=0.5)
    wl = get_workload(cfg)
    q = jax.device_get(wl.generate(jax.random.PRNGKey(0), 4096))
    pay = q.txn_type == TPCC_PAYMENT
    assert 0.4 < pay.mean() < 0.6
    assert q.w_id.min() >= 0 and q.w_id.max() < cfg.num_wh
    assert q.d_id.max() < 10
    assert (q.c_id < cfg.cust_per_dist).all()
    # remote payment customer ~15% (tpcc_query.cpp:168-186)
    rem = (q.c_w_id != q.w_id)[pay]
    assert 0.08 < rem.mean() < 0.25
    no = ~pay
    assert q.ol_cnt[no].min() >= 5 and q.ol_cnt[no].max() <= 5
    # valid items are within cnt and distinct
    for i in np.where(no)[0][:50]:
        v = q.item_valid[i]
        ids = q.items[i][v]
        assert len(set(ids.tolist())) == len(ids)


@pytest.mark.parametrize("alg", ["NOCC", "OCC", "TPU_BATCH", "CALVIN",
                                 "NO_WAIT", "MVCC"])
@pytest.mark.slow
def test_tpcc_runs_and_commits(alg):
    cfg = tpcc_cfg(cc_alg=alg)
    state = run_epochs(cfg)
    commits = int(state.stats["total_txn_commit_cnt"])
    assert commits > 0
    if alg in ("CALVIN", "TPU_BATCH"):
        assert int(state.stats["total_txn_abort_cnt"]) == 0


def test_dynamic_order_index_tracks_inserted_orders():
    """--tpcc_order_index: the dynamic ordered index (index_btree insert
    analogue) stays exact under the NewOrder insert stream — every ORDER
    ring row is findable by its composite key at its ring slot, and a
    district range scan walks its o_ids like the reference's leaf walk."""
    cfg = tpcc_cfg(cc_alg="TPU_BATCH", tpcc_order_index=True,
                   insert_table_cap=1 << 14)
    wl = get_workload(cfg)
    eng = Engine(cfg, wl)
    state = eng.jit_run(eng.init_state(0), 20)
    db = jax.device_get(state.db)
    idx = db["ORDER_IDX"]
    n_ord = int(db["ORDER"].row_cnt)
    assert 0 < n_ord < cfg.insert_table_cap and not bool(
        np.asarray(idx.overflowed()))
    o_w = np.asarray(db["ORDER"].columns["O_W_ID"])[:n_ord]
    o_d = np.asarray(db["ORDER"].columns["O_D_ID"])[:n_ord]
    o_id = np.asarray(db["ORDER"].columns["O_ID"])[:n_ord]
    keys = (o_w * wl.n_dist + o_d).astype(np.int64) * (1 << 21) + o_id
    import jax.numpy as jnp
    got = np.asarray(idx.lookup(jnp.asarray(keys.astype(np.int32))))
    assert (got == np.arange(n_ord)).all()   # ring slot = insert order
    # district leaf walk: range over one district == its sorted o_ids
    dk = int(o_w[0]) * wl.n_dist + int(o_d[0])
    lo = np.int32(dk * (1 << 21))
    hi = np.int32(dk * (1 << 21) + (1 << 21) - 1)
    slots, ok = idx.range_between(jnp.asarray([lo]), jnp.asarray([hi]),
                                  256)
    walk = np.asarray(slots)[0][np.asarray(ok)[0]]
    mine = np.where((o_w == o_w[0]) & (o_d == o_d[0]))[0]
    assert sorted(walk.tolist()) == sorted(mine.tolist())
    assert (np.diff(o_id[walk]) >= 1).all()   # ascending o_id walk


def test_mvcc_reads_byte_match_serial_oracle():
    """MVCC value fidelity for TPC-C (VERDICT r3 next #7): every value a
    committed txn READ must byte-match serial execution.  TPC-C's
    executor gathers are structurally protected — pure reads target
    load-immutable columns (W_TAX/D_TAX/C_DISCOUNT), RMW reads
    (D_NEXT_O_ID, S_QUANTITY) are only allowed at the latest version
    (MVCC aborts a stale RMW, cc/timestamp.py), and read-only txns read
    their serialization point (the epoch snapshot) — so no version-value
    ring is needed.  PROOF, not assertion: the ORDER table records
    exactly the committed NewOrders, so the cumulative read checksum is
    recomputable in closed form from the immutable columns — one
    divergent byte in any committed gather breaks the equality."""
    cfg = tpcc_cfg(cc_alg="MVCC", num_wh=2, epoch_batch=64,
                   max_txn_in_flight=256, perc_payment=0.4)
    wl = get_workload(cfg)
    eng = Engine(cfg, wl)
    s0 = eng.init_state(1)
    d0 = jax.device_get(s0.db)
    state = eng.jit_run(s0, 25)
    d1 = jax.device_get(state.db)
    got = int(state.stats["read_checksum"])

    n_ord = int(d1["ORDER"].row_cnt)
    assert 0 < n_ord < cfg.insert_table_cap, "need commits, no ring wrap"
    o_w = np.asarray(d1["ORDER"].columns["O_W_ID"])[:n_ord]
    o_d = np.asarray(d1["ORDER"].columns["O_D_ID"])[:n_ord]
    o_c = np.asarray(d1["ORDER"].columns["O_C_ID"])[:n_ord]
    w_tax = d0["WAREHOUSE"].host_column("W_TAX")
    d_tax = d0["DISTRICT"].host_column("D_TAX")
    c_disc = d0["CUSTOMER"].host_column("C_DISCOUNT")
    # mirror the executor's f32 arithmetic lane-for-lane (tpcc.py
    # _exec_neworder): (w_tax + d_tax + c_disc) * 1000 -> uint32
    dslot = o_w * wl.n_dist + o_d
    cslot = dslot * cfg.cust_per_dist + o_c
    lanes = ((w_tax[o_w].astype(np.float32)
              + d_tax[dslot].astype(np.float32)
              + c_disc[cslot].astype(np.float32)) * np.float32(1000)
             ).astype(np.uint32)
    ref = int(lanes.sum(dtype=np.uint32))
    assert got == ref


@pytest.mark.slow
def test_money_conservation_and_order_consistency():
    """TPC-C audit: sum(D_YTD)+sum(W_YTD) grows by exactly 2x the committed
    payment amounts; orders inserted == sum of D_NEXT_O_ID advances."""
    cfg = tpcc_cfg(cc_alg="TPU_BATCH", perc_payment=0.5)
    wl = get_workload(cfg)
    eng = Engine(cfg, wl)
    state = eng.init_state(0)
    d0 = jax.device_get(state.db)
    state = eng.jit_run(state, 30)
    d1 = jax.device_get(state.db)

    h = d1["HISTORY"]
    n_hist = int(h.row_cnt)
    assert n_hist < cfg.insert_table_cap, "ring wrapped; test invalid"
    paid = np.asarray(h.columns["H_AMOUNT"])[:n_hist].sum()

    dytd = (d1["DISTRICT"].host_column("D_YTD").astype(np.float64).sum()
            - d0["DISTRICT"].host_column("D_YTD").astype(np.float64).sum())
    wytd = (d1["WAREHOUSE"].host_column("W_YTD").astype(np.float64).sum()
            - d0["WAREHOUSE"].host_column("W_YTD").astype(np.float64).sum())
    assert n_hist > 0
    np.testing.assert_allclose(dytd, paid, rtol=1e-5)
    np.testing.assert_allclose(wytd, paid, rtol=1e-5)

    # customer balance decreased by total paid
    bal = (d0["CUSTOMER"].host_column("C_BALANCE").astype(np.float64).sum()
           - d1["CUSTOMER"].host_column("C_BALANCE").astype(np.float64).sum())
    np.testing.assert_allclose(bal, paid, rtol=1e-5)

    # order-id accounting: next_o_id advances == ORDER rows == NEW-ORDER rows
    adv = int((d1["DISTRICT"].host_column("D_NEXT_O_ID")
               - d0["DISTRICT"].host_column("D_NEXT_O_ID")).sum())
    assert adv == int(d1["ORDER"].row_cnt) == int(d1["NEW-ORDER"].row_cnt)
    assert adv > 0

    # per-district order ids are exactly [3001, 3001+adv_d) with no dups
    n_ord = int(d1["ORDER"].row_cnt)
    o_d = np.asarray(d1["ORDER"].columns["O_D_ID"])[:n_ord]
    o_w = np.asarray(d1["ORDER"].columns["O_W_ID"])[:n_ord]
    o_id = np.asarray(d1["ORDER"].columns["O_ID"])[:n_ord]
    next_o = d1["DISTRICT"].host_column("D_NEXT_O_ID")
    for w in range(cfg.num_wh):
        for d in range(10):
            ids = np.sort(o_id[(o_w == w) & (o_d == d)])
            hi = next_o[w * 10 + d]
            assert (ids == np.arange(3001, hi)).all(), (w, d)

    # order lines reference real orders; avg just under ol_cnt because
    # duplicate sampled items are invalidated rather than resampled
    n_ol = int(d1["ORDER-LINE"].row_cnt)
    assert n_ol >= n_ord * 4


@pytest.mark.slow
def test_order_free_exemption_commit_rate():
    """Warehouse/district/customer accesses are order_free (commutative
    scatter-adds + immutable-column reads), so the deterministic
    backends must not chain on them: with every txn hitting one of 2
    warehouses, defers may come only from stock-row collisions —
    row-level conflict declaration would defer nearly everything here."""
    for alg in ("TPU_BATCH", "CALVIN"):
        # max_items large enough that NURand stock collisions are rare;
        # warehouse/district contention stays maximal (2 warehouses)
        cfg = tpcc_cfg(cc_alg=alg, num_wh=2, perc_payment=0.5,
                       max_items=4096)
        state = run_epochs(cfg, n=30)
        commits = int(state.stats["total_txn_commit_cnt"])
        defers = int(state.stats["defer_cnt"])
        assert commits > 0
        assert defers < max(commits // 10, 5), (alg, commits, defers)


@pytest.mark.slow
def test_stock_quantity_rule():
    """S_QUANTITY stays in (0, 101): the new_order_8 replenish rule."""
    cfg = tpcc_cfg(cc_alg="TPU_BATCH", perc_payment=0.0, num_wh=1,
                   max_items=50)
    state = run_epochs(cfg, n=40)
    sq = np.asarray(state.db["STOCK"].columns["S_QUANTITY"])[:50]
    assert sq.min() > -10 and sq.max() <= 101
    assert int(state.stats["total_txn_commit_cnt"]) > 0
    rc = np.asarray(state.db["STOCK"].columns["S_REMOTE_CNT"])[:50]
    assert (rc == 0).all()  # single warehouse -> no remote supplies


def test_ring_append_wraps():
    from deneva_tpu.storage.catalog import parse_schema
    from deneva_tpu.storage.table import DeviceTable
    cat = parse_schema("TABLE=T\n\t8,int64_t,A\n")
    t = DeviceTable.create(cat.table("T"), 8, ring=True)
    for i in range(3):
        t, slots = t.append({"A": jnp.arange(5) + i * 5},
                            jnp.ones(5, bool))
    assert int(t.row_cnt) == 15
    vals = np.sort(np.asarray(t.columns["A"])[:8])
    np.testing.assert_array_equal(vals, np.arange(7, 15))


def test_lastname_index_matches_closed_form():
    """The CUSTOMER_LAST probe path (hash index + postings walk,
    index_hash.cpp:68-100) resolves exactly the customer the arithmetic
    closed form picks when per-lastname counts are uniform — the index is
    the measured path (default on), the closed form the oracle."""
    cfg = tpcc_cfg()                      # cpd=120 -> names=120, uniform
    assert cfg.tpcc_by_last_index
    wl_idx = get_workload(cfg)
    wl_arith = get_workload(cfg.replace(tpcc_by_last_index=False))
    rng = jax.random.PRNGKey(11)
    q1 = wl_idx.generate(rng, 256)
    q2 = wl_arith.generate(rng, 256)
    for f in ("txn_type", "w_id", "d_id", "c_id", "c_w_id", "c_d_id"):
        assert (np.asarray(getattr(q1, f)) ==
                np.asarray(getattr(q2, f))).all(), f


def test_lastname_index_irregular_counts():
    """cust_per_dist=1500 with 1000 lastnames: lastnames < 500 have two
    customers, the rest one — the index returns the true middle of the
    actual run (closed-form arithmetic assumes uniform counts and cannot;
    this is the case that justifies the index machinery)."""
    cfg = tpcc_cfg(cust_per_dist=1500)
    wl = get_workload(cfg)
    L = jnp.asarray([0, 499, 500, 999], jnp.int32)
    mid = np.asarray(wl._lastname_middle(
        jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32), L))
    # count 2 -> postings [L, L+1000], middle idx 1; count 1 -> [L]
    assert mid.tolist() == [1000, 1499, 500, 999]


@pytest.mark.slow
def test_escrow_ablation_flag():
    """--escrow_order_free=false makes the deterministic backends see the
    full RW-sets (no commutativity exemption): still correct, strictly
    more chaining — the ablation that separates algorithm win from
    annotation win in BASELINE.md."""
    cfg = tpcc_cfg(cc_alg="TPU_BATCH", num_wh=2)
    st_on = run_epochs(cfg, n=15).stats
    st_off = run_epochs(cfg.replace(escrow_order_free=False), n=15).stats
    on_c = int(st_on["total_txn_commit_cnt"])
    off_c = int(st_off["total_txn_commit_cnt"])
    assert on_c > 0 and off_c > 0
    # 2 warehouses, payments serialize on warehouse rows: ablation defers
    assert off_c <= on_c


def test_full_schema_mode():
    """TPCC_FULL_SCHEMA (reference benchmarks/TPCC_full_schema.txt): all
    reference columns materialize, loader fills them, and the full-spec
    stock bookkeeping (S_YTD += qty, S_ORDER_CNT++) runs; short-schema
    semantics (commit counts, invariants) are unchanged."""
    cfg = tpcc_cfg(cc_alg="TPU_BATCH", tpcc_full_schema=True)
    wl = get_workload(cfg)
    db = wl.load()
    assert "C_DATA" in db["CUSTOMER"].columns
    assert "S_DIST_07" in db["STOCK"].columns
    assert int(np.asarray(db["CUSTOMER"].columns["C_DATA"][:5]).sum()) != 0
    state = run_epochs(cfg, n=15)
    stats = {k: np.asarray(v) for k, v in state.stats.items()}
    assert int(stats["total_txn_commit_cnt"]) > 0
    # full-spec bookkeeping moved: every committed neworder item adds
    s_ytd = np.asarray(state.db["STOCK"].columns["S_YTD"])
    s_ocnt = np.asarray(state.db["STOCK"].columns["S_ORDER_CNT"])
    assert s_ytd.sum() > 0 and s_ocnt.sum() > 0
    # short-schema run at same seed: identical commit decisions
    s_short = run_epochs(tpcc_cfg(cc_alg="TPU_BATCH"), n=15)
    short_stats = {k: np.asarray(v) for k, v in s_short.stats.items()}
    assert int(short_stats["total_txn_commit_cnt"]) == \
        int(stats["total_txn_commit_cnt"])


def test_full_width_rows_load_and_append_bytes():
    """``sim_full_row`` with the full schema: every string is its bytes at
    the schema's width — STOCK 338 B and CUSTOMER 695 B a row as the
    schema file counts them (8 B numbers; 314 / 651 B at the program's 4 B),
    the ten S_DIST_xx one array of (row, district) cells — the loader
    fills them from (row, column), NewOrder copies the stock row's cell
    of its district into the line, Payment appends H_DATA's bytes, and
    no masked lane leaves anything in a trash row."""
    from deneva_tpu.workloads.tpcc import (_FULL_EXTRA, _SCHEMA_COLS, S_DIST,
                                           _field_bytes)
    cfg = tpcc_cfg(tpcc_full_schema=True, sim_full_row=True,
                   cc_alg="TPU_BATCH", perc_payment=0.5).validate()
    wl = get_workload(cfg)
    db = jax.device_get(wl.load())
    widths = {t: {cn: sz for cn, ct, sz in ex if ct == "string"}
              for t, ex in _FULL_EXTRA.items()}
    for t, cols in widths.items():
        for cn, sz in cols.items():
            if t == "STOCK" and cn.startswith("S_DIST_"):
                assert cn not in db[t].columns
                continue
            col = db[t].columns[cn]
            assert col.dtype == np.uint8 and col.shape[1] == sz, (t, cn)
    row_bytes = {t: 8 * len(_SCHEMA_COLS[t]) + sum(
        sz for _cn, _ct, sz in _FULL_EXTRA.get(t, ())) for t in _SCHEMA_COLS}
    assert row_bytes["STOCK"] == 338 and row_bytes["CUSTOMER"] == 695
    held = {t: sum(int(np.prod(v.shape[1:])) * v.dtype.itemsize
                   for cn, v in db[t].columns.items() if cn != S_DIST)
            + (240 if t == "STOCK" else 0) for t in db}
    assert held["STOCK"] == 314 and held["CUSTOMER"] == 651
    assert held["ORDER-LINE"] == 60 and held["HISTORY"] == 52
    # the loader's bytes: column j of a table holds (row, j + 1)
    n = wl.n_stock_loc
    cells = db["STOCK"].columns[S_DIST]
    assert cells.shape == (db["STOCK"].columns["S_I_ID"].shape[0] * 10, 24)
    for row, d in ((0, 0), (7, 3), (n - 1, 9)):
        assert (cells[row * 10 + d] == np.asarray(
            _field_bytes(jnp.uint32(row), d + 1, 24))).all()
    assert not cells[n * 10:].any()
    c_data = db["CUSTOMER"].columns["C_DATA"]
    assert (c_data[5] == np.asarray(_field_bytes(jnp.uint32(5), 13, 500))
            ).all() and not c_data[wl.n_cust_loc:].any()

    state = run_epochs(cfg, n=20)
    db = state.db
    n_ord = int(db["ORDER"].row_cnt)
    n_ol = int(db["ORDER-LINE"].row_cnt)
    n_hist = int(db["HISTORY"].row_cnt)
    assert n_ord > 0 and n_ol > n_ord and n_hist > 0
    ol = {k: np.asarray(v) for k, v in db["ORDER-LINE"].columns.items()}
    cells = np.asarray(db["STOCK"].columns[S_DIST])
    srow = ol["OL_SUPPLY_W_ID"][:n_ol] * cfg.max_items + ol["OL_I_ID"][:n_ol]
    assert (ol["OL_DIST_INFO"][:n_ol]
            == cells[srow * 10 + ol["OL_D_ID"][:n_ol]]).all()
    assert ol["OL_DIST_INFO"][:n_ol].any(axis=1).all()
    h = {k: np.asarray(v) for k, v in db["HISTORY"].columns.items()}
    assert (h["H_DATA"][:n_hist] == np.asarray(_field_bytes(
        jnp.asarray(h["H_C_ID"][:n_hist]), jnp.asarray(h["H_W_ID"][:n_hist]),
        24))).all()
    # masked lanes write zeros: every trash and pad row is as loaded
    for t in ("HISTORY", "ORDER", "NEW-ORDER", "ORDER-LINE", "STOCK",
              "CUSTOMER", "DISTRICT", "WAREHOUSE"):
        cap = db[t].capacity
        for cn, v in db[t].columns.items():
            lo = cap * 10 if cn == S_DIST else cap
            assert not np.asarray(v)[lo:].any(), (t, cn)


def test_sim_full_row_validates_for_tpcc_with_the_full_schema_only():
    ok = tpcc_cfg(tpcc_full_schema=True, sim_full_row=True).validate()
    assert ok.sim_full_row and get_workload(ok).full_row
    with pytest.raises(ValueError, match="tpcc_full_schema"):
        tpcc_cfg(sim_full_row=True).validate()
    with pytest.raises(ValueError, match="one device"):
        tpcc_cfg(tpcc_full_schema=True, sim_full_row=True,
                 device_parts=2).validate()
    # PPS holds full-width rows too, on one device (PR 38: this was the
    # refusal of PPS, turned into its opposite)
    pps = tpcc_cfg(workload="PPS", sim_full_row=True, max_accesses=21)
    assert get_workload(pps.validate()).full_row
    assert get_workload(pps).load()["PARTS"].columns["FIELDS"].shape[1] == 100
    with pytest.raises(ValueError, match="one device"):
        tpcc_cfg(workload="PPS", sim_full_row=True, max_accesses=21,
                 device_parts=2).validate()


def test_served_rings_wrap_and_the_serial_reference_follows(tmp_path,
                                                            monkeypatch):
    """The benchmark's toy TPC-C launch with rings of 256 orders (epochs
    of 128 lanes: every append takes the windows of
    `storage/table.DeviceTable.append`): HISTORY, ORDER, NEW-ORDER and
    ORDER-LINE each wrap at least once, inside a call where the cursor
    falls so, and `benchmark/references/tpcc_serial.py` — numpy, a slot
    rule of its own — reproduces every leaf.  The benchmark's timed
    launch wraps its 2^21-order rings where its check, which reads the
    verify launch's first 0.75 s, does not look."""
    import importlib.util
    import os
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")

    def script(rel):
        spec = importlib.util.spec_from_file_location(
            "wrap_" + os.path.basename(rel)[:-3], os.path.join(bench, rel))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    run, ref = script("run.py"), script("references/tpcc_serial.py")
    cap = 256
    cell = run.load_cell("tpcc_fullschema_tpubatch.mixed")
    cell["config_file"]["fields"].update(
        num_wh=4, cust_per_dist=64, max_items=128, epoch_batch=128,
        pipeline_epochs=4, max_txn_in_flight=4096, client_batch_size=128,
        insert_table_cap=cap)
    cell["traffic_file"].update(warmup_secs=0.5, ring_txns=1 << 13)
    monkeypatch.setattr(run, "SERVER_PLATFORM", "cpu")
    res, fields, log, _ = run.logged_launch(cell, 3_000_000_019,
                                            str(tmp_path))
    info = res["server"]["info"]
    checks, notes = ref.verify(log, fields, info)
    assert [n for n, v, lim in checks if v > lim] == [], (checks, notes)
    sz = ref.Sizes(fields)
    tab, _ = ref.replay(log, sz)
    assert ref.digests(ref.columns(sz, tab)) == info["column_digests"]
    assert len(tab.history) > cap and len(tab.orders) > cap
    assert sum(len(ln) for ln in tab.lines) > cap * sz.ipt
    s = res["server"]["summary"]
    # every lane went through a window, and the window's rows are what
    # its commits insert: 1 a Payment, 2 + its valid lines a NewOrder
    # (write_cnt: 6 a Payment, 2 + its lines a NewOrder)
    assert s["append_scatter_lane_cnt"] == 0
    assert s["append_window_lane_cnt"] == \
        s["write_cnt"] - 5 * s["tpcc_payment_commit_cnt"] > 0
