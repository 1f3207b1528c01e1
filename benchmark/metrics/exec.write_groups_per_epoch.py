"""Tile groups the row write's kernel writes back, per epoch of the
measured window: the server's `write_row_group_cnt` (device counter
`write_row_groups`, counted from the compacted slots in
`deneva_tpu/ops/scatter.scatter_winner_rows`: 32 rows of the byte column
are one group, and a group goes back once a call of
`write_rows_by_group`, whatever its winners) over the window's epochs
(`stage_epoch_cnt`; `epoch_cnt` is the whole run's).  Over
`exec.write_lanes_per_epoch` it is the share of handed lanes that opened
a group of their own (the rest merged into a neighbour's, or were a
chunk's padding); times a group's 4 KB read and 4 KB written it is the
kernel's HBM traffic.  The entry lists no cells, so every served cell
reports it: a workload whose executor never calls the row write (TPC-C,
PPS: any `workload` but YCSB) writes no group and reads 0.0, as does a
YCSB cell whose calls are shorter than the kernel takes (the counter is
there and stays 0: the medium cells and the four-chip cell, whose rows
XLA's scatter writes);
a YCSB program that prints no such key (the parent: XLA's scatter wrote
its rows one by one) has nothing to read: None, as has a run with no
measured window."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt"):
        return None
    if "write_row_group_cnt" not in s:
        return None if ctx["fields"].get("workload", "YCSB") == "YCSB" \
            else 0.0
    return s["write_row_group_cnt"] / s["stage_epoch_cnt"]
