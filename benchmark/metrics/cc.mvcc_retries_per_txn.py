"""Mean restarts per transaction retired in the server's window under
MVCC (`txn_retries_mean`: an aborted transaction re-enters a later epoch
with a fresh timestamp; waits are not restarts)."""


def read(ctx):
    return ctx["server"]["summary"].get("txn_retries_mean")
