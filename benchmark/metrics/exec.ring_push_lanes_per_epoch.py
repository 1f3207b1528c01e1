"""Lanes the MVCC epoch program hands its version ring's row write, per
epoch of the measured window: the server's `ring_push_lane_cnt` (device
counter `ring_push_lanes`, `deneva_tpu/storage/table.VersionRing.push_rows`
through `deneva_tpu/ops/scatter.scatter_winner_rows`) over the window's
epochs (`stage_epoch_cnt`; `epoch_cnt` is the whole run's).  Against the
epoch's lane count (txns x requests) it says how far the push engages on
the winners alone; beside `exec.write_lanes_per_epoch` it reads the same
winners in the same chunks.  The entry lists no cells, so every served
cell reports it: a program that holds no version ring (any `cc_alg` but
MVCC) pushes no lane and reads 0.0; an MVCC program that prints no such
key (the parent: its push was handed every lane by construction and
counted none) has nothing to read: None, as has a run with no measured
window."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt"):
        return None
    if "ring_push_lane_cnt" not in s:
        return None if ctx["fields"].get("cc_alg") == "MVCC" else 0.0
    return s["ring_push_lane_cnt"] / s["stage_epoch_cnt"]
