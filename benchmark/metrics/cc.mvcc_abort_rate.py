"""Aborts over commits + aborts in the server's window, under MVCC: a
read-write transaction sent back with a fresh timestamp because a write
of it lies under a committed read or write of a later timestamp, or a
read of it needs a version its row no longer retains (the server's
`abort_rate`; a waiting transaction is no abort, a read-only one never
aborts)."""


def read(ctx):
    s = ctx["server"]["summary"]
    return 100.0 * s["abort_rate"] if "abort_rate" in s else None
