"""`exec.write_lanes_per_epoch` (PR 26): window lanes over window
epochs from the server's closing line, None where the program prints no
such counter (the parent commit's), and the line parser carries the key
through to the reader unedited."""

import pytest

NAME = "exec.write_lanes_per_epoch"
SUMMARY = ("node 0 (server): [summary] total_runtime=40,epoch_cnt=6336,"
           "write_cnt=235929600,write_scatter_lane_cnt=176947200,"
           "stage_epoch_cnt=5760,stage_wall_time=40")


@pytest.mark.parametrize("summary,want", [
    (dict(write_scatter_lane_cnt=176_947_200.0, stage_epoch_cnt=5760.0,
          epoch_cnt=6336.0), 30_720.0),
    (dict(write_scatter_lane_cnt=0.0, stage_epoch_cnt=5760.0), 0.0),
    # the parent: stage keys, no lane counter
    (dict(stage_epoch_cnt=5760.0, write_cnt=235_929_600.0), None),
    # an empty window divides by nothing
    (dict(write_scatter_lane_cnt=0.0, stage_epoch_cnt=0.0), None),
], ids=["window", "no_lanes", "parent", "empty_window"])
def test_write_lanes_reader(bench_run, summary, want):
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server={"summary": summary})) == want


def test_write_lanes_reads_the_servers_closing_line(bench_run):
    srv = bench_run.parse_server(
        '[device] node=0 {"platform": "tpu"}\n' + SUMMARY)
    read = bench_run.load_by_name("metrics", NAME).read
    assert read(dict(server=srv)) == pytest.approx(30_720.0)
