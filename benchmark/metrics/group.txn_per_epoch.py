"""Committed transactions per epoch the device ran (whole run): how many
of an epoch's `epoch_batch` lanes carried a transaction."""


def read(ctx):
    s, info = ctx["server"]["summary"], ctx["server"]["info"]
    return info["run_commit_cnt"] / s["epoch_cnt"] if s.get("epoch_cnt") \
        else None
