"""Lanes the epoch program hands its row gather, per epoch of the
measured window: the server's `read_gather_lane_cnt` (device counter
`read_gather_lanes`, `deneva_tpu/ops/gather.checksum_needed_rows`) over
the window's epochs (`stage_epoch_cnt`; `epoch_cnt` is the whole run's).
Against the epoch's lane count (txns x requests; over several chips the
sum of the shards' plans) it says how far gathering only the reads that
nothing forwards to engages; the parent prints no such key: None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt") or "read_gather_lane_cnt" not in s:
        return None
    return s["read_gather_lane_cnt"] / s["stage_epoch_cnt"]
