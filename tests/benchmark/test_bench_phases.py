"""`benchmark/phase_reduce.py` and the readers of the metrics it and the
server's stage clock feed: the wire-format walk on hand-made bytes, the
whole reduction on a small trace recorded on the chip with the scopes
and the host spans in it (`benchmark/testdata/tiny_scoped.xplane.pb`: a
toy-sized `ycsb_fullrow_occ.medium`, 0.05 s of its served window on one
v5e, my chip run, PR 25), and each reader's arithmetic on a hand-made
context — None, never an error, where there is nothing to read."""

import json
import os
import shutil

import pytest

from conftest import BENCH, ROOT, load_script

SCOPED = os.path.join(BENCH, "testdata", "tiny_scoped.xplane.pb")
UNSCOPED = os.path.join(BENCH, "testdata", "tiny.xplane.pb")   # PR 24's
NEW = ["host.busy_share", "host.device_wait_share",
       "host.admit_ms_per_epoch", "host.retire_ms_per_epoch",
       "server.queue_wait_ms", "server.pipeline_ms",
       "group.verdict_lag_ms", "phase.plan_ms_per_epoch",
       "phase.read_ms_per_epoch", "phase.write_ms_per_epoch",
       "phase.other_ms_per_epoch", "phase.validate_ms_per_epoch"]


@pytest.fixture(scope="module")
def pr():
    return load_script("phase_reduce.py")


# ---- protobuf wire format ------------------------------------------------

def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _ld(no: int, payload: bytes) -> bytes:
    return _varint(no << 3 | 2) + _varint(len(payload)) + payload


def test_wire_walk_reads_varints_and_nested_messages_and_skips_fixed(pr):
    msg = (_varint(1 << 3 | 0) + _varint(300)            # 1: varint
           + _varint(9 << 3 | 1) + b"\x00" * 8           # 9: fixed64
           + _ld(2, b"name") + _varint(7 << 3 | 5) + b"\x00" * 4
           + _ld(3, _ld(1, b"inner") + _varint(2 << 3) + _varint(5)))
    got = [(k, v if isinstance(v, int) else bytes(v))
           for k, v in pr.fields(memoryview(msg))]
    assert got[0] == (1, 300) and got[1] == (2, b"name")
    assert got[2][0] == 3
    assert [(k, v if isinstance(v, int) else bytes(v))
            for k, v in pr.fields(memoryview(got[2][1]))] == \
        [(1, b"inner"), (2, 5)]
    assert bytes(pr.first(memoryview(msg), 2)) == b"name"
    assert pr.first(memoryview(msg), 4) is None
    with pytest.raises(ValueError, match="wire type"):
        list(pr.fields(memoryview(_varint(1 << 3 | 3))))


def _xspace(module_name: str, instrs: list) -> bytes:
    """An XSpace whose `/host:metadata` plane holds one HLO module of
    one computation: [(name, opcode, op_name, [operand names])]."""
    ids = {row[0]: n + 1 for n, row in enumerate(instrs)}
    comp = b"".join(
        _ld(2, _ld(1, name.encode()) + _ld(2, opcode.encode())
            + (_ld(7, _ld(1, b"type") + _ld(2, op.encode())) if op else b"")
            + _varint(35 << 3) + _varint(ids[name])
            + (_ld(36, b"".join(_varint(ids[o]) for o in operands))
               if operands else b""))
        for name, opcode, op, operands in instrs)
    hlo = _ld(1, _ld(1, b"m") + _ld(3, _ld(1, b"c0") + comp))
    em = _ld(2, module_name.encode()) + _ld(5, _ld(6, hlo))
    meta = _ld(2, b"/host:metadata") + _ld(4, _varint(1 << 3) + _varint(7)
                                           + _ld(2, em))
    return _ld(1, _ld(2, b"/device:TPU:0")) + _ld(1, meta)


BODY = "jit(group)/while/body/closed_call/"
HLO = [
    ("fusion.65", "fusion", BODY + "ep.write/scatter", []),
    ("sort.38", "sort", BODY + "ep.plan/sort", []),
    ("slice.2", "fusion", "jit(group)/while/body/dynamic_slice", []),
    # what the chip's compiler makes of a scatter-add: a sort, tuple
    # reads and a kernel with no op_name, then a reshape that has one
    ("sort.58", "sort", "sort", []),
    ("gte.781", "get-tuple-element", "", ["sort.58"]),
    ("fusion.89", "fusion", "", ["gte.781"]),
    ("reshape.289", "reshape", BODY + "ep.validate/scatter-add",
     ["fusion.89"]),
    # a relayout copy of the table feeds the loop: nobody's
    ("copy.86", "copy", "", []),
    ("tuple.9", "tuple", "", ["copy.86"]),
    ("while.49", "while", "", ["tuple.9"]),
    ("gte.9", "get-tuple-element", BODY + "ep.read/gather", ["while.49"]),
]


def test_scopes_come_from_the_group_modules_hlo_in_the_metadata_plane(pr):
    got = pr.hlo_scopes(_xspace("jit_group(123)", HLO))
    assert got["fusion.65"] == "ep.write" and got["sort.38"] == "ep.plan"
    # an op of the program outside every scope stays outside
    assert got["slice.2"] == "unscoped"
    # another program's module is not read
    assert pr.hlo_scopes(_xspace("jit_load(9)", HLO)) == {}
    assert pr.hlo_scopes(b"") == {}


def test_an_op_the_compiler_made_takes_its_consumers_scope(pr):
    got = pr.hlo_scopes(_xspace("jit_group(123)", HLO))
    assert got["sort.58"] == got["gte.781"] == got["fusion.89"] \
        == "ep.validate"
    # ... but never through a loop: the table's relayout copy is
    # nobody's, whatever reads the loop's results
    assert got["copy.86"] == got["tuple.9"] == got["while.49"] == "unscoped"


@pytest.mark.parametrize("op_name, want", [
    ("jit(group)/while/body/closed_call/ep.write/scatter", "ep.write"),
    ("jit(group)/while/body/ep.levels/while/body/ep.read/gather", "ep.read"),
    ("jit(group)/grp.pack/reduce_sum", "grp.pack"),
    ("jit(group)/while/body/dynamic_slice", "unscoped"),
    ("", "unscoped"),
])
def test_an_op_belongs_to_its_innermost_scope(pr, op_name, want):
    assert pr.scope_of(op_name) == want


# ---- the recorded chip trace ----------------------------------------------

@pytest.fixture(scope="module")
def reduced(pr):
    with open(SCOPED, "rb") as f:
        scopes = pr.hlo_scopes(f.read())
    return pr.reduce(pr.tr.load(SCOPED), scopes, 4), scopes


def test_the_recorded_chip_trace_reduces_to_its_known_numbers(reduced, pr):
    got, scopes = reduced
    pr_phases = pr.PHASES
    want = json.load(open(os.path.join(BENCH, "testdata",
                                       "tiny_scoped_expected.json")))
    assert got["groups"] == want["groups"] > 0
    assert got["epochs"] == want["groups"] * 4
    assert got["group_s"] == pytest.approx(want["group_s"])
    for ph, s in want["phase_s"].items():
        assert got["phase_s"][ph] == pytest.approx(s, abs=1e-12)
    # the phases sum to the group programs' device time, nothing lost
    assert sum(got["phase_s"].values()) == pytest.approx(got["group_s"])
    assert sum(got["scope_s"].values()) == pytest.approx(got["group_s"])
    assert got["scope_s"]["no_op"] >= 0
    assert set(got["scope_s"]) >= {"ep.validate", "ep.read", "ep.write",
                                   "ep.stats", "grp.pack", "unscoped"}
    assert got["phase_s"]["validate"] > got["phase_s"]["write"] > 0
    # what no phase claimed is listed by HLO name, largest first
    others = got["other_ops"]
    assert others == sorted(others, key=lambda r: -r[1])
    assert all(scopes[n] not in pr_phases for n, _ in others)


def test_the_same_group_time_as_the_accepted_reduction(reduced,
                                                       trace_reduce):
    """`phase.*` must sum to `group.device_ms_per_epoch`: both reducers
    count the same whole executions."""
    got, _ = reduced
    old = trace_reduce.reduce(trace_reduce.load(SCOPED), 0.05, 4)
    assert got["groups"] == old["groups"]
    assert got["group_s"] == pytest.approx(old["group_busy_s"])
    # and the idle gaps are named by the loop's stages now
    assert all(n.startswith("srv.") for n, _ in
               old["breakdown"]["idle_gaps"])
    assert "srv.group" not in {n for n, _ in old["breakdown"]["idle_gaps"]}


def test_every_whole_execution_is_paired_with_its_host_spans(reduced):
    got, _ = reduced
    want = json.load(open(os.path.join(BENCH, "testdata",
                                       "tiny_scoped_expected.json")))
    lag = got["lag"]
    assert lag["with_dispatch"] == got["groups"]   # one dispatch each
    # the toy's host runs ~7 ms behind its device, so the last whole
    # execution's retirement may fall past the window's end
    assert got["groups"] - 1 <= lag["pairs"] <= got["groups"]
    assert lag["pairs"] == want["lag"]["pairs"]
    for k in ("median_s", "mean_s", "max_s"):
        assert lag[k] == pytest.approx(want["lag"][k])
    assert 0 < lag["median_s"] <= lag["max_s"] < 0.05


def test_a_trace_from_before_the_scopes_gives_nothing_and_no_error(pr):
    with open(UNSCOPED, "rb") as f:
        scopes = pr.hlo_scopes(f.read())
    assert scopes and set(scopes.values()) == {"unscoped"}
    assert pr.reduce(pr.tr.load(UNSCOPED), scopes, 4) == {}


# ---- the child, once per run ----------------------------------------------

def _run_dir(tmp_path, trace_file):
    d = tmp_path / "timed" / "trace" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    shutil.copy(trace_file, d / "host.xplane.pb")
    return dict(trace={"epochs": 24.0}, server={"summary": {}},
                fields={"log_dir": str(tmp_path / "tlog"),
                        "pipeline_epochs": 4})


def test_the_reduction_is_made_once_and_kept_beside_the_trace(
        pr, tmp_path, reduced):
    ctx = _run_dir(tmp_path, SCOPED)
    got = pr.cached(ctx)
    assert got["groups"] == reduced[0]["groups"]
    assert got["phase_s"] == pytest.approx(reduced[0]["phase_s"])
    kept = tmp_path / "timed" / "phase_reduce.json"
    assert json.loads(kept.read_text()) == got
    kept.write_text('{"groups": 1}')          # a second reader: no child
    assert pr.cached(ctx) == {"groups": 1}


def test_an_untraced_run_or_a_lost_trace_reads_as_nothing(pr, tmp_path,
                                                          capfd):
    assert pr.cached(dict(trace=None)) == {}
    ctx = dict(trace={"epochs": 1.0}, fields={
        "log_dir": str(tmp_path / "tlog"), "pipeline_epochs": 4})
    os.makedirs(tmp_path / "timed")
    assert pr.cached(ctx) == {}               # no trace directory
    assert "phase_reduce: exit code" in capfd.readouterr().err


# ---- the readers -----------------------------------------------------------

SUMMARY = dict(
    total_runtime=40.0, total_txn_commit_cnt=80_000_000.0,
    stage_wall_time=40.0, stage_drain_time=1.0, stage_admit_time=8.0,
    stage_collect_time=0.5, stage_feed_time=0.1, stage_dispatch_time=0.4,
    stage_retire_wait_time=24.0, stage_retire_time=5.0,
    stage_other_time=1.0, stage_epoch_cnt=5000.0,
    queue_txn_mean=1_000_000.0, pipeline_time_mean=0.444)
PHASE = dict(groups=10.0, epochs=320.0, group_s=2.2,
             phase_s=dict(plan=0.064, validate=0.192, read=0.544,
                          write=1.28, other=0.12),
             lag=dict(pairs=10, median_s=0.0021, mean_s=0.0027,
                      max_s=0.009))
WANT = {
    # every stage but the two waits, over the wall
    "host.busy_share": 100.0 * (1.0 + 8.0 + 0.1 + 0.4 + 5.0 + 1.0) / 40.0,
    "host.device_wait_share": 60.0,
    "host.admit_ms_per_epoch": 1.6,
    "host.retire_ms_per_epoch": 1.0,
    "server.queue_wait_ms": 500.0,            # 1M waiting / 2M per s
    "server.pipeline_ms": 444.0,
    "group.verdict_lag_ms": 2.1,
    "phase.plan_ms_per_epoch": 0.2,
    "phase.validate_ms_per_epoch": 0.6,
    "phase.read_ms_per_epoch": 1.7,
    "phase.write_ms_per_epoch": 4.0,
    "phase.other_ms_per_epoch": 0.375,
}


def _ctx(tmp_path, summary, phase):
    os.makedirs(tmp_path / "timed", exist_ok=True)
    (tmp_path / "timed" / "phase_reduce.json").write_text(
        json.dumps(phase))
    return dict(server={"summary": summary}, trace={"epochs": 320.0},
                fields={"log_dir": str(tmp_path / "tlog"),
                        "pipeline_epochs": 32})


@pytest.mark.parametrize("name", NEW)
def test_reader_arithmetic(bench_run, tmp_path, name):
    read = bench_run.load_by_name("metrics", name).read
    assert read(_ctx(tmp_path, SUMMARY, PHASE)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_parents_lines_and_says_none(
        bench_run, tmp_path, name):
    """The parent commit's server prints no stage keys and its trace
    holds no scopes and no spans: None, not an error."""
    read = bench_run.load_by_name("metrics", name).read
    old = dict(total_runtime=40.0, total_txn_commit_cnt=8e7,
               worker_idle_time=6.0, worker_process_time=18.0)
    assert read(_ctx(tmp_path, old, {})) is None


def test_busy_wait_and_collect_shares_make_the_whole_window(bench_run,
                                                            tmp_path):
    ctx = _ctx(tmp_path, SUMMARY, PHASE)
    busy, wait = (bench_run.load_by_name("metrics", n).read(ctx) for n in
                  ("host.busy_share", "host.device_wait_share"))
    collect = 100.0 * SUMMARY["stage_collect_time"] / 40.0
    assert busy + wait + collect == pytest.approx(100.0)
    ph = [bench_run.load_by_name("metrics", f"phase.{p}_ms_per_epoch")
          .read(ctx) for p in ("plan", "validate", "read", "write", "other")]
    assert sum(ph) == pytest.approx(1e3 * PHASE["group_s"] / PHASE["epochs"])


def test_every_new_metric_is_declared_after_the_accepted_ones():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:9] == [
        "client.sent_txn_per_s", "transport.bytes_per_txn",
        "host.idle_share", "group.txn_per_epoch",
        "group.device_ms_per_epoch", "epoch_group_hbm_roofline",
        "cc.retries_per_txn", "cc.abort_rate", "device.idle_share"]
    assert names[9:] == NEW
    layers = {m["layer"] for m in bench["per_layer"][:9]}
    for m in bench["per_layer"][9:]:
        assert m["layer"] in layers and m["better"] == "lower"
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    assert bench["per_layer"][-1]["workloads"] == ["ycsb_fullrow_occ.medium"]
