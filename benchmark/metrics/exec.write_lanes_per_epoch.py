"""Lanes the epoch program hands its row scatter, per epoch of the
measured window: the server's `write_scatter_lane_cnt` (device counter
`write_scatter_lanes`, `deneva_tpu/ops/scatter.scatter_winner_rows`) over
the window's epochs (`stage_epoch_cnt`; `epoch_cnt` is the whole run's).
Against the epoch's lane count (txns x requests) it says how far the
winner compaction engages; the parent prints no such key: None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt") or "write_scatter_lane_cnt" not in s:
        return None
    return s["write_scatter_lane_cnt"] / s["stage_epoch_cnt"]
