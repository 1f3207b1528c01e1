"""The WAIT_DIE deployment of the benchmark (PR 46), on the CPU at a toy
size: `ycsb-fullrow-waitdie` x `medium` = `ycsb_fullrow_waitdie.medium`.

* the plain reference (`benchmark/references/ycsb_2pl.py`) against the
  served program on seeded traffic — 512 full-width rows, epochs of 64,
  theta 0.8, so that lanes die AND older lanes wait behind younger
  winners — every check 0 and both mechanisms seen, in the reference's
  own count and in the program's counters;
* one broken guarantee at a time: the age test inverted, a lost write,
  illegal verdicts and a budget the program did not have each FAIL their
  own check, and `benchmark/control.py` drives the cell unedited;
* a hand-made epoch in which rank order and timestamp order differ pins
  commit / wait / die lane by lane, under both rules;
* the roofline's bytes function on a hand-counted epoch, each new reader
  on the toy launch's numbers and on a result without its key;
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
from conftest import ROOT, load_script

CELL = "ycsb_fullrow_waitdie.medium"
CONFIG = "ycsb-fullrow-waitdie"
_TOY = dict(synth_table_size=512, epoch_batch=64, pipeline_epochs=2,
            max_txn_in_flight=2048, client_batch_size=64,
            conflict_buckets=512)
SEED = 3_000_000_019
CHECKS = {"digest_mismatch", "commit_count_gap", "lock_rule_violations",
          "ungranted_winners_gap", "wait_count_gap", "die_count_gap",
          "birth_ts_changed", "read_checksum_mismatch",
          "logged_epochs_missing"}
NEW_METRICS = ["cc.lock_die_rate", "cc.lock_leftovers_per_txn",
               "cc.lock_retries_per_txn", "cc.lock_waits_per_txn",
               "twopl.validate_ms_per_epoch", "twopl_epoch_hbm_roofline"]


@pytest.fixture(scope="module")
def twopl():
    return load_script("references/ycsb_2pl.py")


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
            _toy_cell(bench_run), SEED, str(tmp_path_factory.mktemp("wd")))
    finally:
        mp.undo()
    assert verdicts and log
    return res, fields, log, verdicts


def _failed(checks):
    return sorted(n for n, v, lim in checks if v > lim)


def test_the_reference_holds_every_verdict_wait_death_and_read(launched,
                                                               twopl):
    res, fields, log, verdicts = launched
    info = res["server"]["info"]
    checks, notes = twopl.verify(log, fields, info, verdicts)
    assert _failed(checks) == [], (checks, notes)
    assert {n for n, _, _ in checks} == CHECKS
    # both mechanisms ran, and the reference saw them: counted from the
    # log alone, and equal to the program's own counters
    assert notes["deaths"] == info["run_lock_die_cnt"] \
        == info["run_abort_cnt"] > 100
    assert notes["waits"] == info["run_lock_wait_cnt"] > 10
    assert notes["leftovers"] == info["run_lock_leftover_cnt"] == 0
    assert info["run_defer_cnt"] == notes["waits"] + notes["leftovers"]
    assert notes["epochs_with_deaths"] > notes["epochs_with_waits"] > 0
    assert notes["commits"] == notes["granted"] == info["run_commit_cnt"] \
        > 500
    assert notes["read_checksum"] == info["read_checksum"] > 0
    assert notes["committed_reads"] > 5 * notes["commits"]
    # losers came back, every one with the timestamp it was born with
    assert notes["returning_lanes"] > notes["deaths"] // 2
    assert len(info["column_digests"]) == 11
    s = res["server"]["summary"]
    assert s["lock_die_cnt"] == s["total_txn_abort_cnt"] > 0
    assert s["lock_wait_cnt"] + s["lock_leftover_cnt"] == s["defer_cnt"] > 0
    assert s["lock_forced_restart_cnt"] >= 0


@pytest.mark.parametrize("kw,own", [
    (dict(fault=dict(younger_waits=True)), "wait_count_gap"),
    ("drop_key", "digest_mismatch"),
    ("every_active_lane_commits", "lock_rule_violations"),
    ("a_budget_of_two_rounds", "ungranted_winners_gap"),
    ("a_program_that_counts_nothing", "die_count_gap"),
], ids=["age_test_inverted", "lost_write", "illegal_verdicts",
        "a_budget_the_program_did_not_have", "the_parents_program"])
def test_one_broken_guarantee_fails_its_own_check(kw, own, launched, twopl):
    res, fields, log, verdicts = launched
    info = res["server"]["info"]
    if kw == "drop_key":
        # as `benchmark/control.py` names it: the last committed write —
        # of a rank above 0 (rank 0's bytes are the load's: ROADMAP D0 t)
        for e, keys, types, _a in twopl.read_log(log):
            lanes = np.flatnonzero(
                (verdicts[e][:, None] & (types == twopl.WRITE)).ravel())
            lanes = lanes[lanes >= keys.shape[1]]
            if len(lanes):
                last = int(keys.ravel()[lanes[-1]])
        kw = dict(drop_key=last)
    elif kw == "every_active_lane_commits":
        verdicts = {e: a for e, _k, _t, a in twopl.read_log(log)}
        kw = {}
    elif kw == "a_budget_of_two_rounds":
        fields = {**fields, "sweep_rounds": 2}
        kw = {}
    elif kw == "a_program_that_counts_nothing":
        info = {k: v for k, v in info.items() if "lock" not in k}
        kw = {}
    checks, notes = twopl.verify(log, fields, info, verdicts, **kw)
    assert own in _failed(checks), (checks, notes)
    if own == "wait_count_gap":
        # the split is held through the counters: nothing else moves
        assert _failed(checks) == ["die_count_gap", "wait_count_gap"]
    if own == "digest_mismatch":
        assert _failed(checks) == ["digest_mismatch"]
        assert notes["first_differing"] == ["MAIN_TABLE.columns.F0"]
    if own == "die_count_gap":
        assert _failed(checks) == ["die_count_gap", "ungranted_winners_gap",
                                   "wait_count_gap"]


# ---- a hand-made epoch ------------------------------------------------------

def _record(epoch, ts, tags, keys, types, active):
    """One record of the command log, as the server frames it."""
    ts, keys = np.asarray(ts, np.int64), np.asarray(keys, np.int32)
    types = np.asarray(types, np.int8)
    n, w = keys.shape
    blob = struct.pack("<qI", epoch, n) + ts.tobytes() \
        + struct.pack("<III", n, w, 0) \
        + np.asarray(tags, np.int64).tobytes() \
        + keys.tobytes() + types.tobytes()
    bits = np.packbits(np.asarray(active, bool)).tobytes()
    return struct.pack("<IqII", 0xDE7E7A10, epoch, len(blob),
                       len(bits)) + blob + bits


R, W = 1, 2
C, WT, D = "commit", "wait", "die"
# rank = position; (birth ts, keys, types, fate under WAIT_DIE)
EPOCH = [
    (50, [7, 7], [W, W], C),    # first on key 7: granted
    (10, [7, 0], [W, 0], WT),   # older than its one owner (50)
    (90, [7, 0], [W, 0], D),    # younger
    (5, [7, 0], [R, 0], WT),    # a reader behind the writer, older
    (60, [9, 0], [R, 0], C),    # first on key 9, shared
    (70, [9, 0], [W, 0], D),    # a writer behind an OLDER reader: 70 > 60
    (20, [9, 0], [R, 0], C),    # shared with lane 4: lane 5 holds nothing
    (1, [9, 7], [W, R], WT),    # owners 0, 4 and 6: older than every one
    (30, [9, 0], [W, 0], D),    # owners 4 (60) and 6 (20): not older than 6
    (40, [3, 3], [R, W], C),    # its own repeated key is no conflict
    (0, [0, 0], [0, 0], None),  # an empty slot
]


@pytest.mark.parametrize("rule", ["WAIT_DIE", "NO_WAIT", "younger_waits"])
def test_a_hand_made_epoch_pins_commit_wait_and_die_lane_by_lane(rule,
                                                                 twopl):
    ts = np.asarray([t for t, _, _, _ in EPOCH], np.int64)
    keys = np.asarray([k for _, k, _, _ in EPOCH], np.int32)
    types = np.asarray([t for _, _, t, _ in EPOCH], np.int8)
    active = np.asarray([f is not None for _, _, _, f in EPOCH])
    name = {twopl.COMMIT: C, twopl.WAIT: WT, twopl.DIE: D, 0: None}
    fate = [name[f] for f in twopl.lock_table(
        ts, keys, types, active, wait_die=rule != "NO_WAIT",
        younger_waits=rule == "younger_waits").tolist()]
    want = [f for _, _, _, f in EPOCH]
    if rule == "NO_WAIT":
        want = [D if f == WT else f for f in want]
    elif rule == "younger_waits":
        want = [{WT: D, D: WT}.get(f, f) for f in want]
    assert fate == want
    if rule == "younger_waits":
        return
    # the whole comparison on this one epoch: the counts, the commits,
    # and the bytes the three committed reads returned (the state the
    # epoch BEGAN with: lane 9 reads key 3 before its own write)
    log = _record(0, ts, np.arange(100, 111), keys, types, active)
    commit = np.asarray([f == C for f in want])
    info = dict(run_commit_cnt=4, run_lock_leftover_cnt=0,
                run_lock_wait_cnt=want.count(WT),
                run_lock_die_cnt=want.count(D))
    fields = dict(synth_table_size=64, tup_size=100, sim_full_row="true",
                  cc_alg=rule, sweep_rounds=24)
    checks, notes = twopl.verify(log, fields, info, {0: commit})
    got = {n: v for n, v, _ in checks}
    assert [n for n in sorted(CHECKS) if got[n]] == [
        "digest_mismatch", "read_checksum_mismatch"]    # (no chip said any)
    reads = twopl.field_bytes(np.asarray([9, 9, 3]), 0, 100)
    assert notes["read_checksum"] == int(reads.sum())
    assert (notes["waits"], notes["deaths"]) == (want.count(WT),
                                                 want.count(D))
    # lane 8 commits too: it conflicts with the winners 4 and 6
    commit[8] = True
    checks, _ = twopl.verify(log, fields, {**info, "run_commit_cnt": 5},
                             {0: commit})
    assert dict((n, v) for n, v, _ in checks)["lock_rule_violations"] == 1


def test_a_transaction_that_comes_back_with_another_timestamp_is_counted(
        twopl):
    lane = ([7, 0], [W, 0])
    log = _record(0, [50, 60], [100, 101], [lane[0]] * 2, [lane[1]] * 2,
                  [True, True]) \
        + _record(1, [61, 70], [101, 102], [lane[0]] * 2, [lane[1]] * 2,
                  [True, True]) \
        + _record(2, [70, 71], [102, 100], [lane[0]] * 2, [lane[1]] * 2,
                  [True, True])
    commits = {e: np.asarray([True, False]) for e in range(3)}
    fields = dict(synth_table_size=64, tup_size=100, sim_full_row="true",
                  cc_alg="WAIT_DIE", sweep_rounds=24)
    checks, notes = twopl.verify(log, fields, dict(run_commit_cnt=3),
                                 commits)
    got = {n: v for n, v, _ in checks}
    # tag 101 died at 60 and came back at 61; tag 102 kept its 70; tag
    # 100 committed in epoch 0, so its tag in epoch 2 names another one
    assert got["birth_ts_changed"] == 1 and got["commit_count_gap"] == 0
    assert notes["returning_lanes"] == 2 and notes["transactions"] == 4
    assert got["lock_rule_violations"] == 0


def test_the_decoder_yields_what_ycsb_serials_does_with_tags_and_timestamps(
        launched, twopl, serial):
    _res, _fields, log, _v = launched
    ours = list(twopl.read_records(log))
    theirs = list(serial.read_log(log))
    assert len(ours) == len(theirs) > 10
    for (e, ts, tags, k, t, a), (e2, k2, t2, a2) in zip(ours, theirs):
        assert e == e2 and (k == k2).all() and (t == t2).all() \
            and (a == a2).all()
        assert len(ts) == len(tags) == len(k) and (ts[a] >= 1).all()
        assert len(set(tags[a].tolist())) == a.sum()
    assert [r[0] for r in twopl.read_log(log)] == [r[0] for r in theirs]
    assert twopl.WRITE == serial.WRITE
    assert len(list(twopl.read_records(log[:-5]))) == len(ours) - 1


def test_the_control_drives_the_waitdie_cell_unedited(bench_run, cpu_server,
                                                      capfd):
    control = load_script("control.py")
    rc = control.main(["--workload", CELL, "--seeds", "11"],
                      run=bench_run, cell=_toy_cell(bench_run))
    out = json.loads([ln for ln in capfd.readouterr().out.splitlines()
                      if ln.startswith("{")][-1])
    assert out["sound_failed"] == [], out
    assert "lock_rule_violations" in out["illegal_verdict_failed"]
    # (the control's lost write is a no-op where the log's last writer
    # has rank 0, whose bytes are the load's: ROADMAP D0 t)
    assert rc == 0 and out["control_ok"] \
        and "digest_mismatch" in out["lost_write_failed"], out


# ---- the readers ----------------------------------------------------------

def test_the_rooflines_bytes_on_a_hand_counted_epoch():
    m = load_script("metrics/twopl_epoch_hbm_roofline.py")
    assert m.twopl_epoch_bytes(1, 0, 0) == 100          # a read: its field
    assert m.twopl_epoch_bytes(0, 1, 0) == 200          # a row out, a row in
    assert m.twopl_epoch_bytes(0, 0, 1) == 5            # a key and a flag
    assert m.twopl_epoch_bytes(7, 3, 20, row_bytes=50) == 350 + 300 + 100
    peaks = load_script("peaks.py")
    ctx = dict(trace=dict(epochs=10, group_busy_s=0.006), peaks=peaks,
               fields=dict(req_per_query=10, tup_size=100, epoch_batch=1024),
               server=dict(info=dict(kind="TPU v5 lite"), summary=dict(
                   stage_epoch_cnt=100.0, total_txn_commit_cnt=40000.0,
                   write_cnt=100000.0)))
    # 3,000 reads and 1,000 written rows an epoch of 0.6 ms, 10,240 lanes
    want = 100 * (3000 * 100 + 1000 * 200 + 10240 * 5) / (0.0006 * 819e9)
    assert m.read(ctx) == pytest.approx(want) and 0 < want < 100
    # nothing to read: no trace; another schema's fields
    assert m.read({**ctx, "trace": None}) is None
    assert m.read({**ctx, "fields": dict(req_per_query=10)}) is None


def test_every_new_reader_reads_the_toy_launch_and_nothing_without_its_key(
        launched, bench_run, tmp_path):
    res, fields, _log, _v = launched
    s = res["server"]["summary"]
    os.makedirs(tmp_path / "timed")
    (tmp_path / "timed" / "phase_reduce.json").write_text(json.dumps(dict(
        groups=10.0, epochs=20.0, group_s=0.02, scope_s={},
        phase_s=dict(plan=0.001, validate=0.004, read=0.005, write=0.005,
                     other=0.005))))
    ctx = dict(server=dict(res["server"],
                           info={**res["server"]["info"],
                                 "kind": "TPU v5 lite"}),
               trace=dict(epochs=20.0, group_busy_s=0.02),
               peaks=load_script("peaks.py"),
               fields={**fields, "log_dir": str(tmp_path / "tlog")})
    want = {
        "cc.lock_die_rate": 100 * s["lock_die_cnt"] / (
            s["total_txn_commit_cnt"] + s["lock_die_cnt"]),
        "cc.lock_waits_per_txn": s["lock_wait_cnt"]
        / s["total_txn_commit_cnt"],
        "cc.lock_leftovers_per_txn": 0.0,
        "cc.lock_retries_per_txn": s["txn_retries_mean"],
        "twopl.validate_ms_per_epoch": 1e3 * 0.004 / 20,
    }
    gone = {"cc.lock_die_rate": "lock_die_cnt",
            "cc.lock_waits_per_txn": "lock_wait_cnt",
            "cc.lock_leftovers_per_txn": "lock_leftover_cnt",
            "cc.lock_retries_per_txn": "txn_retries_mean",
            "twopl_epoch_hbm_roofline": "write_cnt"}
    for name in NEW_METRICS:
        read = bench_run.load_by_name("metrics", name).read
        got = read(ctx)
        assert got is not None and got >= 0, name
        if name in want:
            assert got == pytest.approx(want[name]), name
        else:
            assert 0 < got < 100, name          # the roofline, toy numbers
        # a result without its key (the parent's lines; an untraced run)
        if name in gone:
            less = {k: v for k, v in s.items() if k != gone[name]}
            assert read({**ctx, "server": dict(ctx["server"],
                                               summary=less)}) is None, name
        else:
            assert read({**ctx, "trace": None}) is None, name
    assert want["cc.lock_die_rate"] > 10 and want["cc.lock_waits_per_txn"] > 0


# ---- the contract -----------------------------------------------------------

@pytest.mark.parametrize("check", [check_benchmark, check_per_layer,
                                   check_accepted],
                         ids=lambda f: f.__name__)
def test_the_contract_holds_on_the_tree_with_the_waitdie_deployment(check):
    check(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # new entries list the new cell and nothing else; no accepted entry
    # took it into its list
    assert sorted(m["name"] for m in mine) == NEW_METRICS
    assert all(m["workloads"] == [CELL]
               and m["layer"] == "CC and executor kernels" for m in mine)
    assert {m["name"]: m["moves"] for m in mine} == {
        "twopl.validate_ms_per_epoch": "served_txn_per_s",
        "cc.lock_die_rate": "served_txn_per_s",
        "cc.lock_waits_per_txn": "ack_latency_p99_ms",
        "cc.lock_retries_per_txn": "ack_latency_p99_ms",
        "cc.lock_leftovers_per_txn": "served_txn_per_s",
        "twopl_epoch_hbm_roofline": "served_txn_per_s"}
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["chips"], cell["traffic"]) == (CONFIG, 1,
                                                                "medium")
    conf = load_json(ROOT, "benchmark", "configs", CONFIG + ".json")
    occ = load_json(ROOT, "benchmark", "configs", "ycsb-fullrow-occ.json")
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == list(conf["reduced"]) \
        == ["synth_table_size", "node_cnt", "max_txn_in_flight"]
    assert "ycsb_skew, WAIT_DIE" in entry["source"] \
        and "row_lock.cpp" in entry["source"]
    # the OCC file's deployment under another backend: the same fields
    # but the backend, its isolation level (a shape) and the program's
    # two budgets, which the reference reads there
    f = dict(conf["fields"])
    assert (f.pop("cc_alg"), f.pop("isolation_level"), f.pop("sweep_rounds"),
            f.pop("defer_rounds_max")) == ("WAIT_DIE", "SERIALIZABLE", 24, 8)
    assert f == {k: v for k, v in occ["fields"].items() if k != "cc_alg"}
    assert conf["shapes"] == {**occ["shapes"],
                              "isolation_level": "SERIALIZABLE"}
    assert (conf["reference"], conf["verdicts"]) == ("ycsb_2pl", "replay")
    g = conf["guarantees"]
    assert "strict two-phase locking" in g["isolation"]
    assert not g["aborts"].startswith("none") \
        and "TIMESTAMP IT WAS BORN WITH" in g["aborts"]
    told = " ".join(conf["assumed"])
    assert "DEPARTURE 1" in told and "DEPARTURE 2" in told \
        and "sweep_rounds" in told and "defer_rounds_max" in told
    # the reference imports nothing of the program
    with open(os.path.join(ROOT, "benchmark", "references",
                           "ycsb_2pl.py")) as fh:
        src = fh.read()
    assert "import deneva_tpu" not in src and "from deneva_tpu" not in src \
        and "import jax" not in src


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
    assert "epochs_with_waits" in out       # the reference's notes are said
