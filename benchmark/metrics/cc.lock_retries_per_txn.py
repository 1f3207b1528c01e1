"""Mean restarts per transaction retired in the server's window under
two-phase locking (`txn_retries_mean`: a transaction that died, or a
waiter past the host's defer budget, re-enters a later epoch after its
back-off WITH the timestamp it was born with; waits are not restarts)."""


def read(ctx):
    return ctx["server"]["summary"].get("txn_retries_mean")
