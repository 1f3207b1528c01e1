"""Host milliseconds per epoch assembling contributions (admission, its
inner drains included): the window's `stage_admit_time` of the server's
stage clock (`deneva_tpu/runtime/stages.py`) over the window's epochs."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt") or "stage_admit_time" not in s:
        return None
    return 1e3 * s["stage_admit_time"] / s["stage_epoch_cnt"]
