"""`exec.ring_push_lanes_per_epoch` (PR 45): the window's
`ring_push_lane_cnt` — lanes the MVCC epoch hands its version ring's row
write, the epoch's winners in whole chunks — over the window's epochs
from the server's closing line.  An MVCC program that prints no such
counter (the parent: its push was handed every lane by construction, and
counted none) reads nothing; a program that holds no version ring reads
0.0 (the entry lists no cells — the accepted test of the MVCC deployment
pins which entries may list its cell — so every served cell reports it);
the line parser carries the key through to the reader unedited; and the
contract's three functions hold on the tree."""

import pytest

from bench_contract import (check_accepted, check_benchmark, check_per_layer,
                            load_json)
from conftest import ROOT

NAME = "exec.ring_push_lanes_per_epoch"
CELL = "ycsb_fullrow_mvcc.medium"
SUMMARY = ("node 0 (server): [summary] total_runtime=40,epoch_cnt=22000,"
           "write_cnt=51540000,write_scatter_lane_cnt=54400000,"
           "mvcc_wait_cnt=208000,ring_push_lane_cnt=54400000,"
           "stage_epoch_cnt=20000,stage_wall_time=40")
MVCC, OCC = dict(cc_alg="MVCC"), dict(cc_alg="OCC")


@pytest.mark.parametrize("fields,summary,want", [
    (MVCC, dict(ring_push_lane_cnt=54_400_000.0, stage_epoch_cnt=20_000.0,
                epoch_cnt=22_000.0), 2_720.0),
    (MVCC, dict(ring_push_lane_cnt=0.0, stage_epoch_cnt=20_000.0), 0.0),
    # the parent: stage keys and the table's lane counter, not the ring's
    (MVCC, dict(stage_epoch_cnt=10_496.0,
                write_scatter_lane_cnt=27_069_440.0), None),
    # a program with no version ring hands it no lane
    (OCC, dict(stage_epoch_cnt=32_000.0,
               write_scatter_lane_cnt=82_000_000.0), 0.0),
    (dict(cc_alg="TPU_BATCH"), dict(stage_epoch_cnt=11_008.0), 0.0),
    # no measured window divides by nothing
    (MVCC, dict(ring_push_lane_cnt=0.0, stage_epoch_cnt=0.0), None),
    (MVCC, dict(ring_push_lane_cnt=12.0), None),
    (OCC, dict(write_scatter_lane_cnt=12.0), None),
], ids=["window", "no_lanes", "parent", "occ_has_no_ring",
        "tpu_batch_has_no_ring", "empty_window", "no_window",
        "no_window_no_ring"])
def test_ring_push_lanes_reader(bench_run, fields, summary, want):
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server={"summary": summary}, fields=fields)) == want


def test_ring_push_lanes_reads_the_servers_closing_line(bench_run):
    srv = bench_run.parse_server(
        '[device] node=0 {"platform": "tpu"}\n' + SUMMARY)
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server=srv, fields=MVCC)) == pytest.approx(2_720.0)


@pytest.mark.parametrize("check", [check_benchmark, check_per_layer,
                                   check_accepted],
                         ids=lambda f: f.__name__)
def test_the_contract_holds_on_the_tree_with_the_new_entry(check):
    check(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == dict(
        name=NAME, unit="lanes/epoch", better="lower",
        source="program_counter", layer="CC and executor kernels",
        moves="served_txn_per_s")


def test_every_served_cell_reports_it_in_a_traced_run(bench_run):
    """`compute_metrics` asks the reader in every cell (no `workloads`
    key), and the cell's merged fields tell it whether a ring is there."""
    bench = load_json(ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        c = bench_run.load_cell(w["name"])
        asked = [m["name"] for m in c["bench"]["per_layer"]
                 if "workloads" not in m or w["name"] in m["workloads"]]
        assert NAME in asked
        alg = c["config_file"]["fields"]["cc_alg"]
        assert (alg == "MVCC") == (w["name"] == CELL)
