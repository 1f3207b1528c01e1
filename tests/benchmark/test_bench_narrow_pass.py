"""`exec.narrow_pass_share` (PR 39): the window's `narrow_pass_cnt` over
its `level_pass_cnt` from the server's closing line — how much of
`engine/epoch.run_levels`' work ran under the batch's width.  The
parent's line (passes counted, none of them narrow) reads 0.0, a program
that runs no level pass reads 0.0 too (the entry lists no cells: the
accepted tests of the TPC-C and the PPS deployment pin which entries may
list theirs), no measured window reads nothing; the line parser carries
the key through to the reader unedited; and the contract's three
functions hold on the tree."""

import pytest

from bench_contract import (check_accepted, check_benchmark, check_per_layer,
                            load_json)
from conftest import ROOT

NAME = "exec.narrow_pass_share"
SUMMARY = ("node 0 (server): [summary] total_runtime=40,epoch_cnt=17500,"
           "total_txn_commit_cnt=16350000,level_pass_cnt=38912,"
           "narrow_pass_cnt=22896,write_scatter_lane_cnt=1008730112,"
           "stage_epoch_cnt=16000,stage_wall_time=40")


@pytest.mark.parametrize("summary,want", [
    (dict(level_pass_cnt=38912.0, narrow_pass_cnt=22896.0,
          stage_epoch_cnt=16000.0), 22896 / 38912),
    (dict(level_pass_cnt=16000.0, narrow_pass_cnt=0.0,
          stage_epoch_cnt=16000.0), 0.0),
    # the parent under this PR's benchmark files: passes, none narrow
    (dict(level_pass_cnt=38912.0, stage_epoch_cnt=16000.0), 0.0),
    # a forwarding or a sweep backend: no level pass, so none narrow
    (dict(stage_epoch_cnt=11008.0, write_scatter_lane_cnt=333_250_560.0),
     0.0),
    (dict(level_pass_cnt=0.0, narrow_pass_cnt=0.0, stage_epoch_cnt=8.0),
     0.0),
    # no measured window
    (dict(level_pass_cnt=0.0, narrow_pass_cnt=0.0, stage_epoch_cnt=0.0),
     None),
    (dict(level_pass_cnt=12.0, narrow_pass_cnt=4.0), None),
], ids=["window", "no_pass_narrow", "parent", "no_level_passes",
        "passes_counted_none_ran", "empty_window", "no_window"])
def test_narrow_pass_share_reader(bench_run, summary, want):
    read = bench_run.load_by_name("metrics", NAME).read
    got = read(dict(server={"summary": summary}))
    assert got == (None if want is None else pytest.approx(want))


def test_narrow_pass_share_reads_the_servers_closing_line(bench_run):
    srv = bench_run.parse_server(
        '[device] node=0 {"platform": "tpu"}\n' + SUMMARY)
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server=srv)) == pytest.approx(22896 / 38912)


@pytest.mark.parametrize("check", [check_benchmark, check_per_layer,
                                   check_accepted],
                         ids=lambda f: f.__name__)
def test_the_contract_holds_on_the_tree_with_the_new_entry(check):
    check(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == dict(
        name=NAME, unit="share", better="higher", source="program_counter",
        layer="CC and executor kernels", moves="served_txn_per_s")


@pytest.mark.parametrize("cell", [
    "tpcc_fullschema_tpubatch.mixed",
    "pps_fullrow_tpubatch.lookup_order_update",
    "ycsb_fullrow_tpubatch.hot"])
def test_every_served_cell_reports_it_in_a_traced_run(bench_run, cell):
    """`compute_metrics` asks the reader in every cell (no `workloads`
    key); what it answers there is the reader test's, above."""
    c = bench_run.load_cell(cell)
    asked = [m["name"] for m in c["bench"]["per_layer"]
             if "workloads" not in m or cell in m["workloads"]]
    assert NAME in asked
