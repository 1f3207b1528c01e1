"""Level defers per committed transaction in the server's window:
`defer_cnt` (transactions an epoch sent back because their chain of
conflicts was deeper than `exec_subrounds`, `cc/calvin.validate_calvin`)
over `total_txn_commit_cnt`.  A deferred transaction waits for a later
epoch: it is neither aborted nor acked.  (`cc.defers_per_txn` reads the
same counter where a mesh's exchange capacity is what defers, and lists
that cell.)"""


def read(ctx):
    s = ctx["server"]["summary"]
    if "defer_cnt" not in s or not s.get("total_txn_commit_cnt"):
        return None
    return s["defer_cnt"] / s["total_txn_commit_cnt"]
