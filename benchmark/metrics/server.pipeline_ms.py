"""How long a dispatch group spends in the device pipeline: from the
start of its `srv.dispatch` to the end of its `srv.retire`, mean over
the window's groups (`pipeline_time_mean`)."""


def read(ctx):
    v = ctx["server"]["summary"].get("pipeline_time_mean")
    return None if v is None else 1e3 * v
