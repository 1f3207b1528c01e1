"""`exchange.defer_skip_share` (PR 41): 100 x (1 - the window's
`mc_defer_pass_cnt` over its shard-epochs, `stage_epoch_cnt` x
`mesh_shards`) from the server's closing line — how often a shard of
`YCSBWorkload.execute_mc` saw from its owner counts that no block of its
slice could overflow and left the capacity-defer pass out.  No pass run
reads 100; the parent's line (a mesh, no such counter) reads nothing, a
server without a mesh reads nothing, no measured window reads nothing;
the line parser carries the keys through to the reader unedited; the
entry lists the four-chip cell alone; and the contract's three functions
hold on the tree."""

import pytest

from bench_contract import (DP4, check_accepted, check_benchmark,
                            check_per_layer, load_json)
from conftest import ROOT

NAME = "exchange.defer_skip_share"
SUMMARY = ("node 0 (server): [summary] total_runtime=40,epoch_cnt=23200,"
           "total_txn_commit_cnt=301206000,defer_cnt=1536,"
           "mc_defer_pass_cnt=1840,stage_epoch_cnt=18400,"
           "stage_wall_time=40,mesh_shards=4,mesh_a2a_bytes=2211840")


@pytest.mark.parametrize("summary,want", [
    # the cell as it is served: no slice's block can overflow
    (dict(mc_defer_pass_cnt=0.0, stage_epoch_cnt=18400.0, mesh_shards=4.0),
     100.0),
    # one shard-epoch in forty ran the pass
    (dict(mc_defer_pass_cnt=1840.0, stage_epoch_cnt=18400.0,
          mesh_shards=4.0), 97.5),
    # every shard in every epoch: the parent's work, counted
    (dict(mc_defer_pass_cnt=73600.0, stage_epoch_cnt=18400.0,
          mesh_shards=4.0), 0.0),
    # the parent under this PR's benchmark files: a mesh, no counter
    (dict(stage_epoch_cnt=18400.0, mesh_shards=4.0, defer_cnt=0.0), None),
    # one device: no mesh, no exchange
    (dict(stage_epoch_cnt=10240.0, write_scatter_lane_cnt=3.1e8), None),
    (dict(mc_defer_pass_cnt=0.0, stage_epoch_cnt=10240.0), None),
    # no measured window
    (dict(mc_defer_pass_cnt=0.0, stage_epoch_cnt=0.0, mesh_shards=4.0),
     None),
    (dict(mc_defer_pass_cnt=12.0, mesh_shards=4.0), None),
], ids=["no_pass_run", "some_passes", "every_pass", "parent", "one_device",
        "no_mesh_shards", "empty_window", "no_window"])
def test_defer_skip_share_reader(bench_run, summary, want):
    read = bench_run.load_by_name("metrics", NAME).read
    got = read(dict(server={"summary": summary}))
    assert got == (None if want is None else pytest.approx(want))


def test_defer_skip_share_reads_the_servers_closing_line(bench_run):
    srv = bench_run.parse_server(
        '[device] node=0 {"platform": "tpu"}\n' + SUMMARY)
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server=srv)) == pytest.approx(97.5)


@pytest.mark.parametrize("check", [check_benchmark, check_per_layer,
                                   check_accepted],
                         ids=lambda f: f.__name__)
def test_the_contract_holds_on_the_tree_with_the_new_entry(check):
    check(ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == dict(
        name=NAME, unit="%", better="higher", source="program_counter",
        layer="CC and executor kernels", moves="served_txn_per_s",
        workloads=[DP4])


def test_only_the_four_chip_cell_reports_it(bench_run):
    bench = load_json(ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        c = bench_run.load_cell(w["name"])
        asked = [m["name"] for m in c["bench"]["per_layer"]
                 if "workloads" not in m or w["name"] in m["workloads"]]
        assert (NAME in asked) == (w["name"] == DP4), w["name"]
