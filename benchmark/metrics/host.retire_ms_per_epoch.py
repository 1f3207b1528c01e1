"""Host milliseconds per epoch retiring verdicts — ack splits, `CL_RSP`
sends, retry routing; the wait for the device excluded: the window's
`stage_retire_time` of the server's stage clock
(`deneva_tpu/runtime/stages.py`) over the window's epochs."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt") or "stage_retire_time" not in s:
        return None
    return 1e3 * s["stage_retire_time"] / s["stage_epoch_cnt"]
