"""Level defers per committed transaction in the server's window of a PPS
cell: `defer_cnt` less `recon_defer_cnt` — the lanes an epoch sent back
because their chain of conflicts was deeper than `exec_subrounds`
(`cc/calvin.validate_calvin`), not those whose reconnaissance was stale
(`cc.recon_defers_per_txn`) — over `total_txn_commit_cnt`.  A deferred
transaction waits for a later epoch: it is neither aborted nor acked.  A
program that does not tell the two apart: None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "recon_defer_cnt" not in s or "defer_cnt" not in s \
            or not s.get("total_txn_commit_cnt"):
        return None
    return (s["defer_cnt"] - s["recon_defer_cnt"]) \
        / s["total_txn_commit_cnt"]
