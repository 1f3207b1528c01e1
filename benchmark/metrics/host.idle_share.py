"""Share of the server dispatch loop's time spent waiting for input
(`worker_idle_time` against idle + process; whole run)."""


def read(ctx):
    s = ctx["server"]["summary"]
    tot = s.get("worker_idle_time", 0.0) + s.get("worker_process_time", 0.0)
    return 100.0 * s["worker_idle_time"] / tot if tot > 0 else None
