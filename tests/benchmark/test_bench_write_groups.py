"""`exec.write_groups_per_epoch` (PR 48): the window's
`write_row_group_cnt` — tile groups the row write's kernel writes back
for the epoch's winners — over the window's epochs from the server's
closing line.  A YCSB program that prints no such counter (the parent:
XLA's scatter wrote its rows one by one) reads nothing; a workload whose
executor never calls the row write reads 0.0 (the entry lists no cells,
so every served cell reports it); the line parser carries the key
through to the reader unedited; and the contract's three functions hold
on the tree."""

import pytest

from bench_contract import (check_accepted, check_benchmark, check_per_layer,
                            load_json)
from conftest import ROOT

NAME = "exec.write_groups_per_epoch"
SUMMARY = ("node 0 (server): [summary] total_runtime=40,epoch_cnt=24000,"
           "write_cnt=1802000000,write_scatter_lane_cnt=675840000,"
           "write_row_group_cnt=598400000,read_gather_lane_cnt=2027520000,"
           "stage_epoch_cnt=22000,stage_wall_time=40")
YCSB, TPCC = dict(workload="YCSB", cc_alg="TPU_BATCH"), dict(workload="TPCC")


@pytest.mark.parametrize("fields,summary,want", [
    (YCSB, dict(write_row_group_cnt=598_400_000.0, stage_epoch_cnt=22_000.0,
                epoch_cnt=24_000.0), 27_200.0),
    (dict(workload="YCSB", cc_alg="MVCC"),
     dict(write_row_group_cnt=0.0, stage_epoch_cnt=20_000.0), 0.0),
    # the parent: stage keys and the lane counter, not the groups
    (YCSB, dict(stage_epoch_cnt=21_888.0,
                write_scatter_lane_cnt=663_666_688.0), None),
    # (a configuration that names no workload runs YCSB)
    (dict(cc_alg="OCC"), dict(stage_epoch_cnt=32_000.0), None),
    # an executor that never calls the row write writes no group
    (TPCC, dict(stage_epoch_cnt=13_000.0,
                write_scatter_lane_cnt=723_000_000.0), 0.0),
    (dict(workload="PPS"), dict(stage_epoch_cnt=25_000.0), 0.0),
    # no measured window divides by nothing
    (YCSB, dict(write_row_group_cnt=0.0, stage_epoch_cnt=0.0), None),
    (YCSB, dict(write_row_group_cnt=12.0), None),
    (TPCC, dict(write_scatter_lane_cnt=12.0), None),
], ids=["window", "no_groups", "parent", "parent_default_workload",
        "tpcc_never_calls_it", "pps_never_calls_it", "empty_window",
        "no_window", "no_window_no_row_write"])
def test_write_groups_reader(bench_run, fields, summary, want):
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server={"summary": summary}, fields=fields)) == want


def test_write_groups_reads_the_servers_closing_line(bench_run):
    srv = bench_run.parse_server(
        '[device] node=0 {"platform": "tpu"}\n' + SUMMARY)
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server=srv, fields=YCSB)) == pytest.approx(27_200.0)
    lanes = bench_run.load_by_name("metrics",
                                   "exec.write_lanes_per_epoch").read
    # groups <= lanes: a lane opens at most one group
    assert read(dict(server=srv, fields=YCSB)) <= lanes(
        dict(server=srv, fields=YCSB))


@pytest.mark.parametrize("check", [check_benchmark, check_per_layer,
                                   check_accepted],
                         ids=lambda f: f.__name__)
def test_the_contract_holds_on_the_tree_with_the_new_entry(check):
    check(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == dict(
        name=NAME, unit="groups/epoch", better="lower",
        source="program_counter", layer="CC and executor kernels",
        moves="served_txn_per_s")


def test_every_served_cell_reports_it_in_a_traced_run(bench_run):
    """`compute_metrics` asks the reader in every cell (no `workloads`
    key), and the cell's merged fields tell it whether the executor
    writes rows through the kernel's function."""
    bench = load_json(ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        c = bench_run.load_cell(w["name"])
        asked = [m["name"] for m in c["bench"]["per_layer"]
                 if "workloads" not in m or w["name"] in m["workloads"]]
        assert NAME in asked
        assert (c["config_file"]["fields"]["workload"] == "YCSB") == \
            w["name"].startswith("ycsb_")
