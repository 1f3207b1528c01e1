"""Mean retries per transaction retired in the server's window (a
validating backend's aborted transactions re-enter later epochs)."""


def read(ctx):
    return ctx["server"]["summary"].get("txn_retries_mean")
