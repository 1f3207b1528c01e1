"""The TPC-C deployment of the benchmark (PR 36), on the CPU at a toy size:
`tpcc-fullschema-tpubatch` x `mixed` = `tpcc_fullschema_tpubatch.mixed`.

* the plain reference (`benchmark/references/tpcc_serial.py`) against the
  served program on seeded traffic — 4 warehouses, 64 customers a
  district, 128 items, full schema with full-width rows — leaf by leaf;
  at that size most epochs hold chains deeper than `exec_subrounds`, so
  the launch DEFERS thousands of transactions and the reference has to
  hold them back by the rule;
* one broken guarantee at a time on the reference's side (a lost stock
  write, a skipped HISTORY row, a swapped pair of same-district
  NewOrders, a deferred transaction committed at once): each FAILS the
  comparison, and `benchmark/control.py` drives the cell unedited;
* the generator's ring decodes through the program's `from_wire` to
  valid queries with the source's shares;
* the roofline's bytes function on a hand-counted epoch;
* the contract's three functions on the tree with the new deployment;
* one served rehearsal of the whole run (`run_cell`): only the chip gate
  fails.
"""

import json
import os

import numpy as np
import pytest

from bench_contract import (check_accepted, check_benchmark, check_per_layer,
                            load_json)
from conftest import ROOT, load_script

CELL = "tpcc_fullschema_tpubatch.mixed"
_TOY = dict(num_wh=4, cust_per_dist=64, max_items=128, epoch_batch=128,
            pipeline_epochs=4, max_txn_in_flight=4096, client_batch_size=128,
            insert_table_cap=1 << 14)
SEED = 3_000_000_019


@pytest.fixture(scope="module")
def tpcc_serial():
    return load_script("references/tpcc_serial.py")


@pytest.fixture(scope="module")
def tpcc_gen():
    return load_script("generators/tpcc.py")


def _toy_cell(bench_run):
    cell = bench_run.load_cell(CELL)
    cell["config_file"]["fields"].update(_TOY)
    cell["traffic_file"].update(warmup_secs=0.5, ring_txns=1 << 13)
    return cell


@pytest.fixture
def cpu_server(bench_run, monkeypatch):
    """The server on the CPU, and serving 3 s past the clients' window
    (`test_bench_rehearsal.py`'s fixture)."""
    monkeypatch.setattr(bench_run, "SERVER_PLATFORM", "cpu")
    monkeypatch.setattr(bench_run, "SERVE_PAST_WINDOW_S", 3.0)


@pytest.fixture(scope="module")
def launched(bench_run, tmp_path_factory):
    """ONE verify launch of the toy cell: (launch, fields, log)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(bench_run, "SERVER_PLATFORM", "cpu")
    try:
        res, fields, log, verdicts = bench_run.logged_launch(
            _toy_cell(bench_run), SEED, str(tmp_path_factory.mktemp("tpcc")))
    finally:
        mp.undo()
    assert verdicts is None and log
    return res, fields, log


def _failed(checks):
    return sorted(n for n, v, lim in checks if v > lim)


def test_the_serial_reference_reproduces_every_leaf_of_the_nine_tables(
        launched, tpcc_serial):
    res, fields, log = launched
    info = res["server"]["info"]
    checks, notes = tpcc_serial.verify(log, fields, info)
    assert _failed(checks) == [], (checks, notes)
    assert {n for n, _, _ in checks} == {
        "digest_mismatch", "commit_count_gap", "order_id_gaps",
        "float_headroom_violations", "logged_epochs_missing"}
    # leaf by leaf: the full schema's columns at their widths, the one
    # S_DIST array, the four rings and every cursor
    sz = tpcc_serial.Sizes(fields)
    tab, _ = tpcc_serial.replay(log, sz)
    ours = tpcc_serial.digests(tpcc_serial.columns(sz, tab))
    assert ours == info["column_digests"] and len(ours) == 92
    assert {"STOCK.columns.S_DIST", "CUSTOMER.columns.C_DATA",
            "ORDER-LINE.columns.OL_DIST_INFO", "HISTORY.row_cnt"} <= set(ours)
    # both kinds ran, levels chained, and the launch deferred: the
    # reference held the deferred transactions back by the rule
    assert notes["commits"] == info["run_commit_cnt"] > 1000
    assert notes["deferred"] > 0 and tab.history and tab.orders
    s = res["server"]["summary"]
    assert s["level_pass_cnt"] > s["stage_epoch_cnt"] > 0
    assert s["write_scatter_lane_cnt"] == s["level_pass_cnt"] * (
        7 * _TOY["epoch_batch"] + 3 * _TOY["epoch_batch"] * 15)


@pytest.mark.parametrize("fault,table", [
    (dict(skipped_history=True), "HISTORY"),
    (dict(swapped_neworders=True), "ORDER"),
    (dict(defers_commit=True), ""),
    ("drop_key", "STOCK.columns.S_QUANTITY"),
], ids=["skipped_history_row", "swapped_same_district_neworders",
        "deferred_txn_committed_at_once", "lost_stock_write"])
def test_one_broken_guarantee_fails_the_comparison(fault, table, launched,
                                                   tpcc_serial):
    res, fields, log = launched
    kw = dict(fault=fault)
    if fault == "drop_key":
        # as `benchmark/control.py` names it: the item of the last valid
        # line the log holds
        for _e, keys, types, active in tpcc_serial.read_log(log):
            lanes = np.flatnonzero(
                (active[:, None] & (types == tpcc_serial.WRITE)).ravel())
            if len(lanes):
                last = int(keys.ravel()[lanes[-1]])
        kw = dict(drop_key=last)
    checks, notes = tpcc_serial.verify(log, fields, res["server"]["info"],
                                       **kw)
    assert "digest_mismatch" in _failed(checks), (checks, notes)
    assert any(table in n for n in notes["first_differing"]), notes
    if fault == "drop_key":
        assert notes["first_differing"] == ["STOCK.columns.S_QUANTITY"]
    if fault == dict(defers_commit=True):
        assert "commit_count_gap" in _failed(checks)


def test_the_control_drives_the_tpcc_cell_unedited(bench_run, cpu_server,
                                                   capfd):
    control = load_script("control.py")
    rc = control.main(["--workload", CELL, "--seeds", "11"],
                      run=bench_run, cell=_toy_cell(bench_run))
    out = json.loads([ln for ln in capfd.readouterr().out.splitlines()
                      if ln.startswith("{")][-1])
    assert rc == 0 and out["control_ok"] and out["sound_failed"] == [], out
    assert "digest_mismatch" in out["lost_write_failed"]


def test_the_ring_decodes_to_valid_queries_with_the_sources_shares(tpcc_gen):
    from deneva_tpu.config import Config
    from deneva_tpu.workloads import get_workload
    fields = load_json(ROOT, "benchmark", "configs",
                       "tpcc-fullschema-tpubatch.json")["fields"]
    n = 1 << 16
    spec = dict(seed=2_147_483_901, fields={**fields,
                                            "client_batch_size": 1024},
                traffic=dict(perc_payment=0.5, ring_txns=n))
    ring = tpcc_gen.make_ring(spec, 0)
    assert len(ring) == n // 1024
    again = tpcc_gen.make_ring(spec, 0)
    other = tpcc_gen.make_ring(spec, 1)
    assert all((a == b).all() for x, y in zip(ring, again)
               for a, b in zip(x, y))
    assert any((a != b).any() for a, b in zip(ring[0], other[0]))
    keys, types, scal = (np.concatenate([b[i] for b in ring])
                         for i in range(3))
    # through the program's own decoder (at the ring's shapes the small
    # workload object reads nothing but max_items_per_txn)
    wl = get_workload(Config.from_args(
        ["--workload=TPCC", "--num_wh=4", "--max_accesses=18",
         "--tpcc_by_last_index=false"]))
    q = wl.from_wire(keys, types, scal)
    pay = np.asarray(q.txn_type) == 0
    w, d, c = (np.asarray(x) for x in (q.w_id, q.d_id, q.c_id))
    n_wh, cpd, n_items = (fields[k] for k in ("num_wh", "cust_per_dist",
                                              "max_items"))
    assert ((0 <= w) & (w < n_wh) & (0 <= d) & (d < 10)
            & (0 <= c) & (c < cpd)).all()
    cw, cd = np.asarray(q.c_w_id), np.asarray(q.c_d_id)
    assert ((0 <= cw) & (cw < n_wh) & (0 <= cd) & (cd < 10)).all()
    assert (cw[~pay] == w[~pay]).all() and (cd[~pay] == d[~pay]).all()
    amount = np.asarray(q.h_amount)[pay]
    assert (amount == np.round(amount)).all() and amount.min() >= 1 \
        and amount.max() <= 5000
    valid = np.asarray(q.item_valid)
    items, sup, qty = (np.asarray(x) for x in (q.items, q.supply_w,
                                               q.quantity))
    assert not valid[pay].any()
    assert (valid.sum(axis=1)[~pay] == np.asarray(q.ol_cnt)[~pay]).all()
    assert ((0 <= items) & (items < n_items) & (0 <= sup)
            & (sup < n_wh)).all()
    assert ((qty[valid] >= 1) & (qty[valid] <= 10)).all()
    # no item twice in a transaction
    srt = np.sort(np.where(valid, items, -1 - np.arange(15)), axis=1)
    assert not (srt[:, 1:] == srt[:, :-1]).any()
    # the source's shares, within three standard deviations and a bit
    n_pay, n_new = pay.sum(), (~pay).sum()
    assert abs(pay.mean() - 0.5) < 0.01
    assert abs((cw[pay] != w[pay]).mean() - 0.15) < 0.01
    assert abs(valid.sum() / n_new - 10.0) < 0.1
    assert abs((sup[valid] != np.broadcast_to(w[:, None], sup.shape)[valid]
                ).mean() - 0.01) < 0.002
    # by last name: c_id = name + 1000 (the middle of three), name by
    # NURand(255): those ids lie in [1000, 2000) for 60% of Payments and
    # for as many of the rest as of NewOrder's customers, drawn by id
    # from the same NURand(1023)
    by_id = ((c[~pay] >= 1000) & (c[~pay] < 2000)).mean()
    mid = ((c[pay] >= 1000) & (c[pay] < 2000)).mean()
    assert abs(mid - (0.6 + 0.4 * by_id)) < 0.015, (mid, by_id, n_pay)


def test_the_rooflines_bytes_on_a_hand_counted_epoch():
    m = load_script("metrics/tpcc_epoch_hbm_roofline.py")
    # 1 Payment: 5 accumulators read and written + a 52 B HISTORY row
    assert m.tpcc_epoch_bytes(1, 0, 0) == 5 * 8 + 52 == 92
    # 1 NewOrder without lines: 3 reads, D_NEXT_O_ID both ways, 32 + 12
    assert m.tpcc_epoch_bytes(0, 1, 0) == 12 + 8 + 32 + 12 == 64
    # 1 line: I_PRICE, 4 stock counters both ways, 24 B S_DIST, 60 B row
    assert m.tpcc_epoch_bytes(0, 0, 1) == 4 + 32 + 24 + 60 == 120
    # an epoch of 3 Payments and 2 NewOrders of 5 and 7 lines
    assert m.tpcc_epoch_bytes(3, 2, 12) == 3 * 92 + 2 * 64 + 12 * 120
    peaks = load_script("peaks.py")
    ctx = dict(trace=dict(epochs=10, group_busy_s=0.05), peaks=peaks,
               server=dict(info=dict(kind="TPU v5 lite"), summary=dict(
                   stage_epoch_cnt=100.0, tpcc_payment_commit_cnt=300.0,
                   tpcc_new_order_commit_cnt=200.0,
                   write_cnt=300.0 * 6 + 200 * 2 + 1200)))
    want = 100 * (3 * 92 + 2 * 64 + 12 * 120) / (0.005 * 819e9)
    assert m.read(ctx) == pytest.approx(want)
    # nothing to read: no trace; a parent that prints no per-type counts
    assert m.read({**ctx, "trace": None}) is None
    del ctx["server"]["summary"]["tpcc_payment_commit_cnt"]
    assert m.read(ctx) is None


@pytest.mark.parametrize("name,key", [
    ("exec.levels_per_epoch", "level_pass_cnt"),
    ("cc.level_defers_per_txn", "defer_cnt")])
def test_the_counter_readers_read_the_window_and_nothing_on_a_parent(name,
                                                                     key):
    m = load_script(f"metrics/{name}.py")
    summ = dict(stage_epoch_cnt=50.0, total_txn_commit_cnt=4000.0,
                level_pass_cnt=110.0, defer_cnt=8.0)
    want = 110 / 50 if key == "level_pass_cnt" else 8 / 4000
    assert m.read(dict(server=dict(summary=summ))) == pytest.approx(want)
    del summ[key]
    assert m.read(dict(server=dict(summary=summ))) is None


@pytest.mark.parametrize("check", [check_benchmark, check_per_layer,
                                   check_accepted],
                         ids=lambda f: f.__name__)
def test_the_contract_holds_on_the_tree_with_the_tpcc_deployment(check):
    check(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert sorted(m["name"] for m in mine) == [
        "cc.level_defers_per_txn", "exec.levels_per_epoch",
        "phase.levels_ms_per_epoch", "phase.oid_ms_per_epoch",
        "tpcc.validate_ms_per_epoch", "tpcc_epoch_hbm_roofline"]
    assert all(m["workloads"] == [CELL] for m in mine)
    conf = load_json(ROOT, "benchmark", "configs",
                     "tpcc-fullschema-tpubatch.json")
    assert conf["fields"]["num_wh"] == 128 and "num_wh" not in conf["reduced"]
    assert set(conf["shapes"]) == {"tpcc_full_schema", "sim_full_row",
                                   "cust_per_dist", "max_items",
                                   "max_items_per_txn", "wh_update"}
    assert conf["guarantees"]["aborts"].startswith("none")


def test_a_whole_run_rehearses_and_only_the_chip_gate_fails(
        bench_run, cpu_server, capfd):
    with pytest.raises(bench_run.RunFailed, match="no TPU was found"):
        bench_run.run_cell(_toy_cell(bench_run), SEED + 2, 1.0, trace=False)
    out = capfd.readouterr().out
    for name in ("digest_mismatch", "commit_count_gap", "order_id_gaps",
                 "float_headroom_violations"):
        assert f"[check] reference.{name} value=0 limit=0 ok" in out
    assert "[check] timed.deterministic_aborts value=0 limit=0 ok" in out
    failed = sorted(ln.split()[1] for ln in out.splitlines()
                    if ln.startswith("[check] ") and ln.endswith("FAILED"))
    assert failed == ["timed.server_not_on_tpu", "verify.server_not_on_tpu"]
    assert '"correct"' not in out           # no result line without a chip
    assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic",
                                       "mixed.json"))
