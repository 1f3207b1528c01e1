"""Capacity defers per committed transaction in the server's window:
`defer_cnt` (transactions an epoch sent back because a lane of theirs
fell past its (slice, owner) block of the exchange,
`ops.mc_plan_defer`'s rule) over `total_txn_commit_cnt`.  A deferred
transaction waits for a later epoch: it is neither aborted nor acked.
The plain reference commits every logged lane, so a run that defers in
its verify launch reads `correct` false (PERF.md section 7)."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "defer_cnt" not in s or not s.get("total_txn_commit_cnt"):
        return None
    return s["defer_cnt"] / s["total_txn_commit_cnt"]
