"""Transactions acknowledged to the clients inside THEIR measured window
(`warmup_secs` after the start barrier, `--seconds` long), per second of
it: all the work and all the time of the window, on the clients' clocks."""


def read(ctx):
    return sum(c["win_acked"] for c in ctx["clients"]) / ctx["seconds"]
