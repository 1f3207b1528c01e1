"""The PPS deployment of the benchmark (PR 38), on the CPU at a toy size:
`pps-fullrow-tpubatch` x `lookup_order_update` =
`pps_fullrow_tpubatch.lookup_order_update`.

* the plain reference (`benchmark/references/pps_serial.py`) against the
  served program on seeded traffic — 200 parts, 40 products and
  suppliers of ten parts each, full-width rows — leaf by leaf, with the
  commit count, the count of lanes sent back and the checksum of what
  the committed reads returned; at that size every epoch rewrites most
  products, so the launch defers thousands of walks on stale
  reconnaissance and the reference has to hold them back by the rule;
* one broken guarantee at a time on the reference's side (a walk that
  commits on a stale part set, a look-up on the wrong side of an order's
  decrement, a lost mapping write, a lost decrement): each FAILS its own
  check, and `benchmark/control.py` drives the cell unedited;
* the generator's ring decodes through the program's `from_wire` to
  valid queries with the traffic file's shares;
* the roofline's bytes function on a hand-counted epoch, each new reader
  on a reduced trace's numbers and on a parent's lines;
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
from conftest import BENCH, ROOT, load_script

CELL = "pps_fullrow_tpubatch.lookup_order_update"
_TOY = dict(pps_parts_cnt=200, pps_products_cnt=40, pps_suppliers_cnt=40,
            epoch_batch=128, pipeline_epochs=4, max_txn_in_flight=4096,
            client_batch_size=128)
SEED = 3_000_000_019
CHECKS = {"digest_mismatch", "commit_count_gap", "defer_count_gap",
          "read_checksum_mismatch", "stale_recon_commits",
          "logged_epochs_missing"}


@pytest.fixture(scope="module")
def pps_serial():
    return load_script("references/pps_serial.py")


@pytest.fixture(scope="module")
def pps_gen():
    return load_script("generators/pps.py")


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
            _toy_cell(bench_run), SEED, str(tmp_path_factory.mktemp("pps")))
    finally:
        mp.undo()
    assert verdicts is None and log
    return res, fields, log


def _failed(checks):
    return sorted(n for n, v, lim in checks if v > lim)


def test_the_serial_reference_reproduces_tables_counts_and_reads(
        launched, pps_serial):
    res, fields, log = launched
    info = res["server"]["info"]
    checks, notes = pps_serial.verify(log, fields, info)
    assert _failed(checks) == [], (checks, notes)
    assert {n for n, _, _ in checks} == CHECKS
    # leaf by leaf: the three anchor tables at their row widths (the ten
    # strings of a row as one leaf), the two mappings, every cursor
    sz = pps_serial.Sizes(fields)
    tab, _ = pps_serial.replay(log, sz)
    ours = pps_serial.digests(pps_serial.columns(sz, tab))
    assert ours == info["column_digests"] and len(ours) == 16
    assert {"PARTS.columns.FIELDS", "PARTS.columns.PART_AMOUNT",
            "USES.columns.PART_KEY", "SUPPLIES.row_cnt"} <= set(ours)
    assert not any("PRODUCT_PART" in n for n in ours)
    # the mechanism ran: walks waited on stale reconnaissance, levels
    # chained, the mapping was rewritten, and the committed reads — a
    # third of the transactions, which change no digest — were checked
    assert notes["commits"] == info["run_commit_cnt"] > 1000
    assert notes["deferred"] == info["run_defer_cnt"] \
        >= notes["recon_deferred"] > 100
    assert notes["read_checksum"] == info["read_checksum"] > 0
    assert (tab.uses != pps_serial.SerialTables(sz).uses).any()
    s = res["server"]["summary"]
    assert s["level_pass_cnt"] > s["stage_epoch_cnt"] > 0
    assert 0 < s["recon_defer_cnt"] <= s["defer_cnt"]
    assert s["total_txn_abort_cnt"] == 0
    b, per = _TOY["epoch_batch"], 10
    assert s["write_scatter_lane_cnt"] == s["level_pass_cnt"] * b * (per + 2)
    assert s["read_gather_lane_cnt"] == s["level_pass_cnt"] * b * (per + 3)
    assert s["pps_lookup_commit_cnt"] + s["pps_order_commit_cnt"] \
        + s["pps_update_commit_cnt"] == s["total_txn_commit_cnt"]
    assert s["pps_lookup_commit_cnt"] == s["pps_getpartbyproduct_commit_cnt"]


@pytest.mark.parametrize("fault,own,leaf", [
    (dict(stale_commits=True), "stale_recon_commits", None),
    (dict(swapped_lookup_order=True), "read_checksum_mismatch", None),
    (dict(lost_mapping_write=True), "digest_mismatch",
     "USES.columns.PART_KEY"),
    ("drop_key", "digest_mismatch", "PARTS.columns.PART_AMOUNT"),
], ids=["walk_commits_on_a_stale_part_set",
        "lookup_on_the_wrong_side_of_an_orders_decrement",
        "lost_mapping_write", "lost_decrement"])
def test_one_broken_guarantee_fails_its_own_check(fault, own, leaf, launched,
                                                  pps_serial):
    res, fields, log = launched
    kw = dict(fault=fault)
    if fault == "drop_key":
        # as `benchmark/control.py` names it: the product of the last
        # ORDERPRODUCT lane the log holds
        for _e, keys, types, active in pps_serial.read_log(log):
            lanes = np.flatnonzero(
                (active[:, None] & (types == pps_serial.WRITE)).ravel())
            if len(lanes):
                last = int(keys.ravel()[lanes[-1]])
        kw = dict(drop_key=last)
    checks, notes = pps_serial.verify(log, fields, res["server"]["info"],
                                      **kw)
    assert own in _failed(checks), (checks, notes)
    if leaf:
        # the lost write's own leaf — and, where a later walk read what
        # it left behind, the reads' checksum and the parts it touched
        assert leaf in notes["first_differing"] and set(_failed(checks)) \
            <= {"digest_mismatch", "read_checksum_mismatch"}
    if own == "read_checksum_mismatch":
        # a read-only transaction out of place changes no table and no
        # count: nothing but the reads' checksum can see it
        assert _failed(checks) == ["read_checksum_mismatch"]
    if own == "stale_recon_commits":
        assert {"digest_mismatch", "defer_count_gap"} <= set(_failed(checks))


def test_the_control_drives_the_pps_cell_unedited(bench_run, cpu_server,
                                                  capfd):
    control = load_script("control.py")
    rc = control.main(["--workload", CELL, "--seeds", "11"],
                      run=bench_run, cell=_toy_cell(bench_run))
    out = json.loads([ln for ln in capfd.readouterr().out.splitlines()
                      if ln.startswith("{")][-1])
    assert rc == 0 and out["control_ok"] and out["sound_failed"] == [], out
    assert "digest_mismatch" in out["lost_write_failed"]


def test_the_ring_decodes_to_valid_queries_with_the_traffics_shares(pps_gen):
    from deneva_tpu.config import Config
    from deneva_tpu.workloads import get_workload
    fields = load_json(ROOT, "benchmark", "configs",
                       "pps-fullrow-tpubatch.json")["fields"]
    traffic = load_json(ROOT, "benchmark", "traffic",
                        "lookup_order_update.json")
    pps_gen.check(traffic)
    n = 1 << 16
    spec = dict(seed=2_147_483_901, fields=fields,
                traffic={**traffic, "ring_txns": n})
    ring = pps_gen.make_ring(spec, 0)
    assert len(ring) == n // 1024
    again = pps_gen.make_ring(spec, 0)
    other = pps_gen.make_ring(spec, 1)
    assert all((a == b).all() for x, y in zip(ring, again)
               for a, b in zip(x, y))
    assert (ring[0][2] != other[0][2]).any()
    keys, types, scal = (np.concatenate([b[i] for b in ring])
                         for i in range(3))
    assert keys.shape == types.shape == (n, 1) and not keys.any()
    # through the program's own decoder and wire
    wl = get_workload(Config.from_args(
        [f"--{k}={v}" for k, v in {**fields,
                                   **pps_gen.server_fields(traffic)}.items()]))
    q = wl.from_wire(keys, types, scal)
    k2, t2, s2 = wl.to_wire(q)
    assert (s2 == scal).all() and k2.shape == keys.shape \
        and t2.dtype == types.dtype
    kind = np.asarray(q.txn_type)
    share = np.bincount(kind, minlength=8) / n
    want = [traffic[k] for k in pps_gen.MIX]
    assert np.abs(share - want).max() < 0.01 and share[want == 0].sum() == 0
    for v, hi in ((q.part_key, 10000), (q.product_key, 1000),
                  (q.supplier_key, 1000)):
        v = np.asarray(v)
        assert v.min() == 0 and v.max() == hi - 1
        assert np.abs(np.bincount(v * 10 // hi) / n - 0.1).max() < 0.01
    # what the generator refuses
    for bad in ({**traffic, "arrival": "poisson"},
                {**traffic, "perc_updatepart": 0.5},
                {k: v for k, v in traffic.items() if k != "perc_getparts"}):
        with pytest.raises(ValueError):
            pps_gen.check(bad)


def test_the_rooflines_bytes_on_a_hand_counted_epoch():
    m = load_script("metrics/pps_epoch_hbm_roofline.py")
    # 1 look-up: ten mapping rows of 8 B, ten whole part rows of 108 B
    assert m.pps_epoch_bytes(1, 0, 0) == 10 * (8 + 108) == 1160
    # 1 order: the same mapping rows, PART_AMOUNT of ten parts both ways
    assert m.pps_epoch_bytes(0, 1, 0) == 10 * (8 + 8) == 160
    # 1 update: PART_KEY of one mapping row
    assert m.pps_epoch_bytes(0, 0, 1) == 4
    assert m.pps_epoch_bytes(3, 2, 5, per=4) == 3 * 4 * 116 + 2 * 4 * 16 + 20
    peaks = load_script("peaks.py")
    ctx = dict(trace=dict(epochs=10, group_busy_s=0.02), peaks=peaks,
               fields=dict(pps_parts_per=10),
               server=dict(info=dict(kind="TPU v5 lite"), summary=dict(
                   stage_epoch_cnt=100.0, pps_lookup_commit_cnt=31000.0,
                   pps_order_commit_cnt=30000.0,
                   pps_update_commit_cnt=32000.0)))
    want = 100 * (310 * 1160 + 300 * 160 + 320 * 4) / (0.002 * 819e9)
    assert m.read(ctx) == pytest.approx(want)
    # nothing to read: no trace; a parent that prints no such counts
    assert m.read({**ctx, "trace": None}) is None
    del ctx["server"]["summary"]["pps_lookup_commit_cnt"]
    assert m.read(ctx) is None


@pytest.mark.parametrize("name,want,gone", [
    ("pps.levels_per_epoch", 340 / 50, "level_pass_cnt"),
    ("cc.recon_defers_per_txn", 4800 / 48000, "recon_defer_cnt"),
    ("cc.pps_level_defers_per_txn", 12 / 48000, "recon_defer_cnt")])
def test_the_counter_readers_read_the_window_and_nothing_on_a_parent(
        name, want, gone):
    m = load_script(f"metrics/{name}.py")
    summ = dict(stage_epoch_cnt=50.0, total_txn_commit_cnt=48000.0,
                level_pass_cnt=340.0, defer_cnt=4812.0,
                recon_defer_cnt=4800.0)
    assert m.read(dict(server=dict(summary=summ))) == pytest.approx(want)
    del summ[gone]
    assert m.read(dict(server=dict(summary=summ))) is None


@pytest.mark.parametrize("name,want", [
    ("phase.recon_ms_per_epoch", 1e3 * 0.016 / 320),
    ("pps.validate_ms_per_epoch", 1e3 * 0.192 / 320)])
def test_the_trace_readers_on_a_reduced_trace_and_on_a_parents(
        bench_run, tmp_path, name, want):
    read = bench_run.load_by_name("metrics", name).read

    def ctx(d, phase):
        os.makedirs(d / "timed")
        (d / "timed" / "phase_reduce.json").write_text(json.dumps(phase))
        return dict(server={"summary": {}}, trace={"epochs": 320.0},
                    fields={"log_dir": str(d / "tlog"),
                            "pipeline_epochs": 8})
    phase = dict(groups=40.0, epochs=320.0, group_s=0.9,
                 phase_s=dict(plan=0.01, validate=0.192, read=0.3,
                              write=0.3, other=0.098),
                 scope_s={"ep.recon": 0.016, "ep.levels": 0.02})
    assert read(ctx(tmp_path / "a", phase)) == pytest.approx(want)
    # the recorded chip trace of an older program: it has phases and no
    # `ep.recon` scope, so the reconnaissance reader has nothing to read
    old = load_json(BENCH, "testdata", "tiny_scoped_expected.json")
    got = read(ctx(tmp_path / "b", old))
    if name == "phase.recon_ms_per_epoch":
        assert got is None
    else:
        assert got == pytest.approx(
            1e3 * old["phase_s"]["validate"] / old["epochs"])
    assert read(ctx(tmp_path / "c", {})) is None    # a scope-less parent
    assert read(dict(trace=None)) is None           # an untraced run


@pytest.mark.parametrize("check", [check_benchmark, check_per_layer,
                                   check_accepted],
                         ids=lambda f: f.__name__)
def test_the_contract_holds_on_the_tree_with_the_pps_deployment(check):
    check(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert sorted(m["name"] for m in mine) == [
        "cc.pps_level_defers_per_txn", "cc.recon_defers_per_txn",
        "phase.recon_ms_per_epoch", "pps.levels_per_epoch",
        "pps.validate_ms_per_epoch", "pps_epoch_hbm_roofline"]
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "served_txn_per_s" for m in mine)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        "pps-fullrow-tpubatch", 1, "lookup_order_update")
    conf = load_json(ROOT, "benchmark", "configs",
                     "pps-fullrow-tpubatch.json")
    entry = {c["name"]: c for c in bench["configs"]}["pps-fullrow-tpubatch"]
    assert entry["reduced"] == list(conf["reduced"]) \
        == ["node_cnt", "max_txn_in_flight"]
    assert set(conf["shapes"]) == {"sim_full_row", "pps_parts_cnt",
                                   "pps_products_cnt", "pps_suppliers_cnt",
                                   "pps_parts_per"}
    assert (conf["fields"]["pps_parts_cnt"], conf["fields"]["max_accesses"],
            conf["reference"]) == (10000, 21, "pps_serial")
    assert conf["guarantees"]["aborts"].startswith("none")
    assert "read" in conf["guarantees"]["isolation"]


def test_a_whole_run_rehearses_and_only_the_chip_gate_fails(
        bench_run, cpu_server, capfd):
    with pytest.raises(bench_run.RunFailed, match="no TPU was found"):
        bench_run.run_cell(_toy_cell(bench_run), SEED + 2, 1.0, trace=False)
    out = capfd.readouterr().out
    for name in CHECKS:
        assert f"[check] reference.{name} value=0 limit=0 ok" in out
    assert "[check] timed.deterministic_aborts value=0 limit=0 ok" in out
    failed = sorted(ln.split()[1] for ln in out.splitlines()
                    if ln.startswith("[check] ") and ln.endswith("FAILED"))
    assert failed == ["timed.server_not_on_tpu", "verify.server_not_on_tpu"]
    assert '"correct"' not in out           # no result line without a chip
