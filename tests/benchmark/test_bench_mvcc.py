"""The MVCC deployment of the benchmark (PR 43), on the CPU at a toy size:
`ycsb-fullrow-mvcc` x `medium` = `ycsb_fullrow_mvcc.medium`.

* the plain reference (`benchmark/references/ycsb_mvto.py`) against the
  served program on seeded traffic — 16,384 full-width rows, a history
  three deep, epochs of 64, theta 0.8, so that readers wait behind
  writers of their epoch and come back with the timestamp they were born
  with — every check 0 AND the mechanism seen: reads served an old
  version, transactions that waited, read-only commits;
* one broken guarantee at a time: a stale read served the live bytes, a
  lost write, and hand-built histories (a reader that should have
  waited, a late write under a committed read, a read from beyond the
  retained history, a commit of an inactive lane) each FAIL their own
  check, and `benchmark/control.py` drives the cell unedited;
* the reference's decoder against `ycsb_serial.read_log` on one log;
* the roofline's bytes function on a hand-counted epoch, each new reader
  on a window's numbers, on a reduced trace and on a parent's lines;
* the contract's three functions on the tree with the new deployment;
* one served rehearsal of the whole run (`run_cell`): only the chip gate
  fails.
"""

import json
import os
import struct

import numpy as np
import pytest

from bench_contract import (check_accepted, check_benchmark, check_per_layer,
                            load_json)
from conftest import BENCH, ROOT, load_script

CELL = "ycsb_fullrow_mvcc.medium"
CONFIG = "ycsb-fullrow-mvcc"
_TOY = dict(synth_table_size=16384, mvcc_his_len=3, epoch_batch=64,
            pipeline_epochs=2, max_txn_in_flight=2048, client_batch_size=64,
            conflict_buckets=512)
SEED = 3_000_000_019
CHECKS = {"digest_mismatch", "commit_count_gap", "read_checksum_mismatch",
          "mvto_rule_violations", "logged_epochs_missing"}
NEW_METRICS = ["cc.mvcc_abort_rate", "cc.mvcc_old_version_reads_per_txn",
               "cc.mvcc_retries_per_txn", "cc.mvcc_ro_commit_share",
               "cc.mvcc_waits_per_txn", "mvcc.validate_ms_per_epoch",
               "mvcc_epoch_hbm_roofline", "phase.version_ms_per_epoch"]


@pytest.fixture(scope="module")
def mvto():
    return load_script("references/ycsb_mvto.py")


def _toy_cell(bench_run, **over):
    cell = bench_run.load_cell(CELL)
    cell["config_file"]["fields"].update(_TOY, **over)
    cell["traffic_file"].update(zipf_theta=0.8, warmup_secs=0.5,
                                ring_txns=1 << 13)
    return cell


@pytest.fixture
def cpu_server(bench_run, monkeypatch):
    """The server on the CPU, and serving 3 s past the clients' window
    (`test_bench_rehearsal.py`'s fixture)."""
    monkeypatch.setattr(bench_run, "SERVER_PLATFORM", "cpu")
    monkeypatch.setattr(bench_run, "SERVE_PAST_WINDOW_S", 3.0)


@pytest.fixture(scope="module")
def launched(bench_run, tmp_path_factory):
    """ONE verify launch of the toy cell: (launch, fields, log, replayed
    commit masks)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(bench_run, "SERVER_PLATFORM", "cpu")
    try:
        res, fields, log, verdicts = bench_run.logged_launch(
            _toy_cell(bench_run), SEED, str(tmp_path_factory.mktemp("mvcc")))
    finally:
        mp.undo()
    assert verdicts and log
    return res, fields, log, verdicts


def _failed(checks):
    return sorted(n for n, v, lim in checks if v > lim)


def test_the_reference_holds_tables_rings_counts_and_every_read(launched,
                                                                mvto):
    res, fields, log, verdicts = launched
    info = res["server"]["info"]
    checks, notes = mvto.verify(log, fields, info, verdicts)
    assert _failed(checks) == [], (checks, notes)
    assert {n for n, _, _ in checks} == CHECKS
    # the mechanism ran, and the reference saw it: reads served a version
    # other than the live one, transactions that came back with the
    # timestamp they were born with, read-only commits — counted from
    # the log and the masks alone, and equal to the program's own counts
    assert notes["old_version_reads"] == info[
        "run_mvcc_old_version_read_cnt"] > 0
    assert notes["read_only_commits"] == info["run_mvcc_ro_commit_cnt"] > 100
    assert 0 < notes["waited"] <= info["run_mvcc_wait_cnt"] \
        == info["run_defer_cnt"]
    assert notes["commits"] == info["run_commit_cnt"] > 1000
    assert notes["read_checksum"] == info["read_checksum"] > 0
    assert notes["committed_reads"] > 5 * notes["commits"]
    # leaf by leaf: the table's ten columns, its cursor, the version ring
    h = mvto.History(log, _TOY["synth_table_size"], verdicts)
    ours = {n: mvto._sha(v) for n, v in mvto.leaves(
        h, _TOY["synth_table_size"], 100, _TOY["mvcc_his_len"]).items()}
    assert ours == info["column_digests"] and len(ours) == 12
    assert mvto.RING in ours
    ring = mvto.ring_leaf(h, _TOY["synth_table_size"], _TOY["mvcc_his_len"])
    assert (ring.reshape(-1, 3)[_TOY["synth_table_size"]:] == 0).all()
    assert (ring.reshape(-1, 3) > 0).all(axis=1).any()      # a full ring
    s = res["server"]["summary"]
    assert s["mvcc_wait_cnt"] == s["defer_cnt"] > 0
    assert s["mvcc_ro_commit_cnt"] == s["ycsb_ro_commit_cnt"] > 0
    assert 0 < s["mvcc_history_abort_cnt"] <= s["total_txn_abort_cnt"]
    assert s["ycsb_ro_abort_cnt"] == 0      # a read-only one never aborts


@pytest.mark.parametrize("kw,own", [
    (dict(fault=dict(stale_reads_live=True)), "read_checksum_mismatch"),
    ("drop_key", "digest_mismatch"),
    ("every_active_lane_commits", "mvto_rule_violations"),
], ids=["stale_read_served_the_live_bytes", "lost_write",
        "illegal_verdicts"])
def test_one_broken_guarantee_fails_its_own_check(kw, own, launched, mvto):
    res, fields, log, verdicts = launched
    if kw == "drop_key":
        # as `benchmark/control.py` names it: the last committed write
        for e, keys, types, _a in mvto.read_log(log):
            lanes = np.flatnonzero(
                (verdicts[e][:, None] & (types == mvto.WRITE)).ravel())
            if len(lanes):
                last = int(keys.ravel()[lanes[-1]])
        kw = dict(drop_key=last)
    elif kw == "every_active_lane_commits":
        verdicts = {e: a for e, _k, _t, a in mvto.read_log(log)}
        kw = {}
    checks, notes = mvto.verify(log, fields, res["server"]["info"],
                                verdicts, **kw)
    assert own in _failed(checks), (checks, notes)
    if own == "read_checksum_mismatch":
        # bytes of the wrong version change no table, no count, no rule:
        # nothing but the reads' checksum can see them
        assert _failed(checks) == ["read_checksum_mismatch"]
    if own == "digest_mismatch":
        assert notes["first_differing"] == ["MAIN_TABLE.columns.F0"]


# ---- hand-built histories --------------------------------------------------

def _record(epoch, ts, keys, types, active):
    """One record of the command log, as the server frames it."""
    ts, keys = np.asarray(ts, np.int64), np.asarray(keys, np.int32)
    types = np.asarray(types, np.int8)
    n, w = keys.shape
    blob = struct.pack("<qI", epoch, n) + ts.tobytes() \
        + struct.pack("<III", n, w, 0) + np.zeros(n, np.int64).tobytes() \
        + keys.tobytes() + types.tobytes()
    bits = np.packbits(np.asarray(active, bool)).tobytes()
    return struct.pack("<IqII", 0xDE7E7A10, epoch, len(blob),
                       len(bits)) + blob + bits


R, W = 1, 2
K, M = 7, 9         # the contended key; where the reader writes
# (epochs as [(ts, [keys], [types])...], committed masks, violations)
HISTORIES = {
    "sound_reader_waits_then_reads_the_writers_version": (
        [[(5, [K, K], [W, W]), (6, [K, M], [R, W])],
         [(6, [K, M], [R, W])]],
        [[True, False], [True]], 0),
    "reader_commits_beside_an_earlier_writer_of_its_epoch": (
        [[(5, [K, K], [W, W]), (6, [K, M], [R, W])]],
        [[True, True]], 1),
    "late_write_under_a_committed_read": (
        [[(6, [K, M], [R, W])], [(5, [K, K], [W, W])]],
        [[True], [True]], 1),
    "read_three_overwrites_back_with_a_history_of_two": (
        [[(10, [K, K], [W, W])], [(20, [K, K], [W, W])],
         [(30, [K, K], [W, W])], [(5, [K, M], [R, W])]],
        [[True], [True], [True], [True]], 1),
    "read_of_a_version_its_own_epoch_overwrote": (
        # 5 and 7 commit in one epoch: the row retains 7's version only,
        # and the reader at 6, back in a later epoch, is owed 5's
        [[(5, [K, K], [W, W]), (7, [K, K], [W, W])], [(6, [K, M], [R, W])]],
        [[True, True], [True]], 1),
    "commit_of_an_inactive_lane": (
        [[(5, [K, K], [W, W]), (0, [0, 0], [0, 0])]],
        [[True, True]], 1),
}


@pytest.mark.parametrize("name", sorted(HISTORIES))
def test_a_hand_built_history_counts_its_violation(name, mvto):
    epochs, commits, want = HISTORIES[name]
    log = b"".join(_record(
        e, [t for t, _, _ in txns], [k for _, k, _ in txns],
        [ty for _, _, ty in txns], [t > 0 for t, _, _ in txns])
        for e, txns in enumerate(epochs))
    verdicts = {e: np.asarray(c) for e, c in enumerate(commits)}
    fields = dict(synth_table_size=64, tup_size=100, mvcc_his_len=2,
                  sim_full_row="true")
    checks, notes = mvto.verify(log, fields, dict(run_commit_cnt=0),
                                verdicts)
    got = {n: v for n, v, _ in checks}
    assert got["mvto_rule_violations"] == want, notes
    assert got["logged_epochs_missing"] == 0
    if name.startswith("sound"):
        # the reader came back with its timestamp and read version 5
        assert notes["waited"] == 1 and notes["old_version_reads"] == 0
        h = mvto.History(log, 64, verdicts)
        assert mvto.select_versions(h, 2)["version"].tolist() == [5]


def test_the_decoder_yields_what_ycsb_serials_does_and_the_timestamps(
        launched, mvto, serial):
    _res, _fields, log, _v = launched
    ours = list(mvto.read_records(log))
    theirs = list(serial.read_log(log))
    assert len(ours) == len(theirs) > 10
    for (e, ts, k, t, a), (e2, k2, t2, a2) in zip(ours, theirs):
        assert e == e2 and (k == k2).all() and (t == t2).all() \
            and (a == a2).all()
        assert ts.dtype == np.int64 and len(ts) == len(k)
        assert (ts[a] >= 1).all()
    assert [r[0] for r in mvto.read_log(log)] == [r[0] for r in theirs]
    assert mvto.WRITE == serial.WRITE
    # a torn tail ends the walk, as it does `ycsb_serial`'s
    assert len(list(mvto.read_records(log[:-5]))) == len(ours) - 1


def test_the_control_drives_the_mvcc_cell_unedited(bench_run, cpu_server,
                                                   capfd):
    control = load_script("control.py")
    rc = control.main(["--workload", CELL, "--seeds", "11"],
                      run=bench_run, cell=_toy_cell(bench_run))
    out = json.loads([ln for ln in capfd.readouterr().out.splitlines()
                      if ln.startswith("{")][-1])
    assert rc == 0 and out["control_ok"] and out["sound_failed"] == [], out
    assert "digest_mismatch" in out["lost_write_failed"]
    assert "mvto_rule_violations" in out["illegal_verdict_failed"]


# ---- the readers ----------------------------------------------------------

def test_the_rooflines_bytes_on_a_hand_counted_epoch():
    m = load_script("metrics/mvcc_epoch_hbm_roofline.py")
    # 1 read: its field and its row's ring of ten timestamps
    assert m.mvcc_epoch_bytes(1, 0) == 100 + 40 == 140
    # 1 write: its field, the ring read, one timestamp written
    assert m.mvcc_epoch_bytes(0, 1) == 100 + 40 + 4 == 144
    assert m.mvcc_epoch_bytes(7, 3, row_bytes=50, his_len=4) \
        == 7 * 66 + 3 * 70
    peaks = load_script("peaks.py")
    ctx = dict(trace=dict(epochs=10, group_busy_s=0.03), peaks=peaks,
               fields=dict(req_per_query=10, tup_size=100, mvcc_his_len=10),
               server=dict(info=dict(kind="TPU v5 lite"), summary=dict(
                   stage_epoch_cnt=100.0, total_txn_commit_cnt=100000.0,
                   write_cnt=250000.0)))
    # 750,000 reads and 250,000 writes over 100 epochs of 3 ms
    want = 100 * (7500 * 140 + 2500 * 144) / (0.003 * 819e9)
    assert m.read(ctx) == pytest.approx(want) and 0 < want < 100
    # nothing to read: no trace; another schema's fields
    assert m.read({**ctx, "trace": None}) is None
    assert m.read({**ctx, "fields": dict(req_per_query=10)}) is None


@pytest.mark.parametrize("name,want,gone", [
    ("cc.mvcc_old_version_reads_per_txn", 90 / 48000,
     "mvcc_old_version_read_cnt"),
    ("cc.mvcc_waits_per_txn", 1200 / 48000, "mvcc_wait_cnt"),
    ("cc.mvcc_ro_commit_share", 100 * 24600 / 48000, "mvcc_ro_commit_cnt"),
    ("cc.mvcc_abort_rate", 2.5, "abort_rate"),
    ("cc.mvcc_retries_per_txn", 0.03, "txn_retries_mean")])
def test_the_counter_readers_read_the_window_and_nothing_on_a_parent(
        name, want, gone):
    m = load_script(f"metrics/{name}.py")
    summ = dict(total_txn_commit_cnt=48000.0, abort_rate=0.025,
                txn_retries_mean=0.03, mvcc_old_version_read_cnt=90.0,
                mvcc_wait_cnt=1200.0, mvcc_ro_commit_cnt=24600.0,
                mvcc_history_abort_cnt=700.0)
    assert m.read(dict(server=dict(summary=summ))) == pytest.approx(want)
    del summ[gone]
    assert m.read(dict(server=dict(summary=summ))) is None


@pytest.mark.parametrize("name,want", [
    ("phase.version_ms_per_epoch", 1e3 * 0.48 / 320),
    ("mvcc.validate_ms_per_epoch", 1e3 * 0.192 / 320)])
def test_the_trace_readers_on_a_reduced_trace_and_on_a_parents(
        bench_run, tmp_path, name, want):
    read = bench_run.load_by_name("metrics", name).read

    def ctx(d, phase):
        os.makedirs(d / "timed")
        (d / "timed" / "phase_reduce.json").write_text(json.dumps(phase))
        return dict(server={"summary": {}}, trace={"epochs": 320.0},
                    fields={"log_dir": str(d / "tlog"),
                            "pipeline_epochs": 32})
    phase = dict(groups=10.0, epochs=320.0, group_s=0.9,
                 phase_s=dict(plan=0.0, validate=0.192, read=0.05,
                              write=0.05, other=0.608),
                 scope_s={"ep.version": 0.48})
    assert read(ctx(tmp_path / "a", phase)) == pytest.approx(want)
    # the recorded chip trace of an older program: it has phases and no
    # `ep.version` scope, so the ring's reader has nothing to read
    old = load_json(BENCH, "testdata", "tiny_scoped_expected.json")
    got = read(ctx(tmp_path / "b", old))
    if name == "phase.version_ms_per_epoch":
        assert got is None
    else:
        assert got == pytest.approx(
            1e3 * old["phase_s"]["validate"] / old["epochs"])
    assert read(ctx(tmp_path / "c", {})) is None    # a scope-less parent
    assert read(dict(trace=None)) is None           # an untraced run


# ---- the contract -----------------------------------------------------------

@pytest.mark.parametrize("check", [check_benchmark, check_per_layer,
                                   check_accepted],
                         ids=lambda f: f.__name__)
def test_the_contract_holds_on_the_tree_with_the_mvcc_deployment(check):
    check(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # new entries list the new cell and nothing else; no accepted entry
    # took it into its list
    assert sorted(m["name"] for m in mine) == NEW_METRICS
    assert all(m["workloads"] == [CELL]
               and m["layer"] == "CC and executor kernels" for m in mine)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["chips"], cell["traffic"]) == (CONFIG, 1,
                                                                "medium")
    conf = load_json(ROOT, "benchmark", "configs", CONFIG + ".json")
    occ = load_json(ROOT, "benchmark", "configs", "ycsb-fullrow-occ.json")
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == list(conf["reduced"]) \
        == ["synth_table_size", "node_cnt", "max_txn_in_flight"]
    assert "ycsb_skew, MVCC" in entry["source"] \
        and "HIS_RECYCLE_LEN" in entry["source"]
    # the OCC file's deployment under another backend: the same fields
    # but the backend and its history depth, which is a shape
    f = dict(conf["fields"])
    assert (f.pop("cc_alg"), f.pop("mvcc_his_len")) == ("MVCC", 10)
    assert f == {k: v for k, v in occ["fields"].items() if k != "cc_alg"}
    assert conf["shapes"] == {**occ["shapes"], "mvcc_his_len": 10}
    assert (conf["reference"], conf["verdicts"]) == ("ycsb_mvto", "replay")
    g = conf["guarantees"]
    assert "multi-version timestamp ordering" in g["isolation"] \
        and "retained history" in g["isolation"]
    assert not g["aborts"].startswith("none") and "read-only" in g["aborts"]
    told = " ".join(conf["assumed"])
    assert "DEPARTURE 1" in told and "DEPARTURE 2" in told \
        and "HIS_RECYCLE_LEN" in told


def test_a_whole_run_rehearses_and_only_the_chip_gate_fails(
        bench_run, cpu_server, capfd):
    with pytest.raises(bench_run.RunFailed, match="no TPU was found"):
        # (512 in flight: the second client's first block is in the
        # fifth epoch, so both are acked however slowly a loaded machine
        # serves the verify launch's 0.75 s)
        bench_run.run_cell(_toy_cell(bench_run, max_txn_in_flight=512),
                           SEED + 2, 1.0, trace=False)
    out = capfd.readouterr().out
    for name in CHECKS:
        assert f"[check] reference.{name} value=0 limit=0 ok" in out
    failed = sorted(ln.split()[1] for ln in out.splitlines()
                    if ln.startswith("[check] ") and ln.endswith("FAILED"))
    assert failed == ["timed.server_not_on_tpu", "verify.server_not_on_tpu"]
    assert '"correct"' not in out           # no result line without a chip
    assert "old_version_reads" in out       # the reference's notes are said
