"""Aborts over commits + aborts in the server's window."""


def read(ctx):
    s = ctx["server"]["summary"]
    return 100.0 * s["abort_rate"] if "abort_rate" in s else None
