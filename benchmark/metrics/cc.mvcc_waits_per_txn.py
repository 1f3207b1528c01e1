"""Waits per committed transaction of the server's window under MVCC:
`mvcc_wait_cnt` (transactions deferred behind an earlier-stamped writer
of a key they read in their epoch, `cc/timestamp.validate_mvcc`: they
come back with the timestamp they were born with) over
`total_txn_commit_cnt`.  A program that counts none (the parent):
None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "mvcc_wait_cnt" not in s or not s.get("total_txn_commit_cnt"):
        return None
    return s["mvcc_wait_cnt"] / s["total_txn_commit_cnt"]
