"""The four CPU readers of the server host loop (PR 40) and the
operator's tool that prints the stage clock of an untraced run.

`host.cpu_ms_per_epoch`, `host.admit_cpu_ms_per_epoch`,
`host.retire_cpu_ms_per_epoch` and `host.offcpu_share` read the stage
clock's `stage_<stage>_cpu_time` keys; where a program prints none its
CPU is taken as its wall (`benchmark/stage_cpu.py`), so the parent's
lines, and `test_bench_phases.SUMMARY` as it stands, read the wall
values and an off-CPU share of 0.0."""

import importlib.util
import json
import os

import pytest

from bench_contract import (check_accepted, check_benchmark,
                            check_per_layer, load_json)
from conftest import ROOT
from test_bench_phases import SUMMARY
from test_bench_rehearsal import CELLS, _toy_cell

NEW = ("host.cpu_ms_per_epoch", "host.admit_cpu_ms_per_epoch",
       "host.retire_cpu_ms_per_epoch", "host.offcpu_share")
# the canned window of `test_bench_phases` (40 s, 5,000 epochs) with the
# CPU the thread burnt in each stage: admit and retire half off the CPU
CPU = dict(stage_drain_cpu_time=0.9, stage_admit_cpu_time=4.0,
           stage_collect_cpu_time=0.01, stage_feed_cpu_time=0.1,
           stage_dispatch_cpu_time=0.3, stage_retire_wait_cpu_time=0.04,
           stage_retire_cpu_time=2.5, stage_other_cpu_time=0.9,
           process_cpu_time=61.0)
WANT = {
    "host.cpu_ms_per_epoch": 1e3 * 8.75 / 5000,
    "host.admit_cpu_ms_per_epoch": 0.8,
    "host.retire_cpu_ms_per_epoch": 0.5,
    # the six working stages: 15.5 s of wall, 8.7 s of CPU, of 40 s
    "host.offcpu_share": 100.0 * (15.5 - 8.7) / 40.0,
}
# the same window from a program that prints no CPU reading
WALL = {
    "host.cpu_ms_per_epoch": 1e3 * 40.0 / 5000,
    "host.admit_cpu_ms_per_epoch": 1.6,       # host.admit_ms_per_epoch
    "host.retire_cpu_ms_per_epoch": 1.0,      # host.retire_ms_per_epoch
    "host.offcpu_share": 0.0,
}


def _read(bench_run, name, summary):
    return bench_run.load_by_name("metrics", name).read(
        dict(server={"summary": summary}))


@pytest.mark.parametrize("name", NEW)
def test_reader_arithmetic_on_a_summary_with_cpu_keys(bench_run, name):
    assert _read(bench_run, name, dict(SUMMARY, **CPU)) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_without_a_cpu_reading_the_cpu_is_taken_as_the_wall(bench_run,
                                                            name):
    assert not [k for k in SUMMARY if "cpu" in k]     # as it stands
    assert _read(bench_run, name, SUMMARY) == pytest.approx(WALL[name])
    # one stage's CPU printed, the others' not: each by the same rule
    part = dict(SUMMARY, stage_admit_cpu_time=4.0)
    want = {"host.cpu_ms_per_epoch": 1e3 * 36.0 / 5000,
            "host.admit_cpu_ms_per_epoch": 0.8,
            "host.retire_cpu_ms_per_epoch": 1.0,
            "host.offcpu_share": 10.0}[name]
    assert _read(bench_run, name, part) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("window", [
    dict(stage_epoch_cnt=0.0), dict(stage_epoch_cnt=None)],
    ids=["no_epochs", "no_key"])
def test_no_window_reads_none(bench_run, name, window):
    summ = dict(SUMMARY, **CPU, **window)
    if window["stage_epoch_cnt"] is None:
        del summ["stage_epoch_cnt"]
    assert _read(bench_run, name, summ) is None
    # lines of a program with no stage clock at all
    assert _read(bench_run, name, dict(total_runtime=40.0)) is None


def test_the_twins_never_read_over_the_accepted_wall_metrics(bench_run):
    summ = dict(SUMMARY, **CPU)
    for twin, wall in (("host.admit_cpu_ms_per_epoch",
                        "host.admit_ms_per_epoch"),
                       ("host.retire_cpu_ms_per_epoch",
                        "host.retire_ms_per_epoch")):
        assert _read(bench_run, twin, summ) <= _read(bench_run, wall, summ)
    # busy share = the working stages' wall; off-CPU is a part of it
    assert _read(bench_run, "host.offcpu_share", summ) <= \
        _read(bench_run, "host.busy_share", summ)


def test_the_four_entries_are_appended_and_list_no_cells():
    for check in (check_benchmark, check_per_layer, check_accepted):
        check(ROOT)
    entries = {m["name"]: m for m in
               load_json(ROOT, "BENCHMARK.json")["per_layer"]}
    names = list(entries)
    # appended: in this order, after everything the parent declared
    assert tuple(n for n in names if n in NEW) == NEW
    assert names.index(NEW[0]) > names.index("exec.narrow_pass_share")
    wall = entries["host.admit_ms_per_epoch"]
    for name in NEW:
        m = entries[name]
        assert "workloads" not in m           # the clock serves every cell
        assert {k: m[k] for k in ("layer", "source", "moves", "better")} \
            == {k: wall[k] for k in ("layer", "source", "moves", "better")}
        assert m["unit"] == ("%" if name.endswith("share") else "ms/epoch")


# ---- the operator's tool, rehearsed on the CPU ---------------------------

@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "tools_stage_record", os.path.join(ROOT, "tools", "stage_record.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_tool_prints_an_untraced_runs_stage_clock(tool, bench_run,
                                                      monkeypatch, capfd):
    """`tools/stage_record.py` on the toy hot cell with the server on
    the CPU: the whole run fails its chip gate as `run.py`'s does (no
    result line), and the tool still prints what both timed launches'
    closing lines read."""
    monkeypatch.setattr(bench_run, "SERVER_PLATFORM", "cpu")
    monkeypatch.setattr(bench_run, "SERVE_PAST_WINDOW_S", 3.0)
    timed_phase = bench_run.timed_phase
    rc = tool.main(["--workload", CELLS[0], "--seed", "3000000040",
                    "--seconds", "1.0", "--launches", "2"],
                   run=bench_run, cell=_toy_cell(bench_run, CELLS[0]))
    assert bench_run.timed_phase is timed_phase       # put back
    cap = capfd.readouterr()
    assert rc == 1 and "no TPU was found" in cap.err
    assert '"correct"' not in cap.out
    reps = [json.loads(ln.split(" ", 1)[1]) for ln in cap.out.splitlines()
            if ln.startswith("[stage_record] ")]
    assert [r["seed"] for r in reps] == [3000000040, 3000000041]
    # the further launch brings its own end-to-end numbers
    assert reps[0]["end_to_end"] == {} and set(reps[1]["end_to_end"]) == {
        m["name"] for m in load_json(ROOT, "BENCHMARK.json")["end_to_end"]}
    for r in reps:
        assert r["epochs"] > 0 and r["window_s"] > 0.5
        st = r["ms_per_epoch"]
        assert set(st) == {"drain", "admit", "collect", "feed", "dispatch",
                           "retire_wait", "retire", "other"}
        assert all(0 <= v["cpu_ms"] <= v["wall_ms"] + 1e-3
                   for v in st.values())
        # the stages partition the window: their walls an epoch make it
        assert r["wall_ms_per_epoch"] * r["epochs"] == pytest.approx(
            1e3 * r["window_s"], rel=0.02)
        assert 0 < r["cpu_ms_per_epoch"] <= r["wall_ms_per_epoch"]
        assert 0 <= r["offcpu_share"] <= 100
        assert 0 < r["process_cores"] <= r["stage_record"]["cpus"]
        rec = r["stage_record"]
        assert rec["dropped"] == 0 and rec["intervals"] > 0
        assert 0.4 < r["window_at_s"] < 5         # the toy's warm-up: 0.5 s
        for row in rec["longest"] + rec["longest_work"]:
            assert len(row["acks_k"]) == 3 and row["barrier_s"] == \
                pytest.approx(row["at_s"] + rec["t_start"] - rec["t_meas"]
                              + r["window_at_s"], abs=2e-3)
        assert sum(r["acks_by_s"]) > 0
    table = [ln for ln in cap.out.splitlines() if ln.startswith("[stage] ")]
    assert sum("host.offcpu_share=" in ln for ln in table) == 2
    assert sum(ln.startswith("[stage]   retire_wait*") for ln in table) >= 2
