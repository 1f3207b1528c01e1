"""Share of the measured window the server's dispatch thread spends
blocked on the device's verdicts (`stage_retire_wait_time` of its stage
clock over the window's wall).  It falls as the device gets faster; at 0
the host binds."""


def read(ctx):
    s = ctx["server"]["summary"]
    wall = s.get("stage_wall_time")
    if not wall or "stage_retire_wait_time" not in s:
        return None
    return 100.0 * s["stage_retire_wait_time"] / wall
