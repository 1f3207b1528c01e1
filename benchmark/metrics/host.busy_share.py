"""Share of the measured window the server's dispatch thread WORKS: the
window's wall less the two waits of its stage clock
(`deneva_tpu/runtime/stages.py`, whose stages partition the wall) —
`retire_wait` (blocked on the device's verdicts) and `collect` (blocked
on the peers' blobs).  At 100% the host binds."""


def read(ctx):
    s = ctx["server"]["summary"]
    wall = s.get("stage_wall_time")
    if not wall or "stage_retire_wait_time" not in s:
        return None
    waits = s["stage_retire_wait_time"] + s.get("stage_collect_time", 0.0)
    return 100.0 * (wall - waits) / wall
