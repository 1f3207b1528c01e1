"""`exec.read_lanes_per_epoch` (PR 30): window lanes over window epochs
from the server's closing line, None where the program prints no such
counter (the parent commit's), and the line parser carries the key
through to the reader unedited."""

import pytest

NAME = "exec.read_lanes_per_epoch"
SUMMARY = ("node 0 (server): [summary] total_runtime=40,epoch_cnt=12096,"
           "write_cnt=443000000,write_scatter_lane_cnt=333250560,"
           "read_gather_lane_cnt=817344000,stage_epoch_cnt=11008,"
           "stage_wall_time=40")


@pytest.mark.parametrize("summary,want", [
    (dict(read_gather_lane_cnt=817_344_000.0, stage_epoch_cnt=11008.0,
          epoch_cnt=12096.0), 74_250.0),
    (dict(read_gather_lane_cnt=0.0, stage_epoch_cnt=11008.0), 0.0),
    # the parent: stage keys and the write's counter, not the read's
    (dict(stage_epoch_cnt=5760.0, write_scatter_lane_cnt=176_947_200.0),
     None),
    # an empty window divides by nothing
    (dict(read_gather_lane_cnt=0.0, stage_epoch_cnt=0.0), None),
], ids=["window", "no_lanes", "parent", "empty_window"])
def test_read_lanes_reader(bench_run, summary, want):
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server={"summary": summary})) == want


def test_read_lanes_reads_the_servers_closing_line(bench_run):
    srv = bench_run.parse_server(
        '[device] node=0 {"platform": "tpu"}\n' + SUMMARY)
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server=srv)) == pytest.approx(74_250.0)
