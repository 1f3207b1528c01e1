"""Waits per committed transaction of the server's window under
WAIT_DIE: `lock_wait_cnt` (device counter `lock_wait`,
`cc/twopl.validate_wait_die`: lanes refused a lock whose birth timestamp
is below every owner's — deferred, not aborted, back the epoch after
their group retires with the timestamp they were born with) over
`total_txn_commit_cnt`.  A wait needs an OLDER lane ranked behind a
younger winner, which only the retry queue's order produces.  A program
that counts none (the parent): None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "lock_wait_cnt" not in s or not s.get("total_txn_commit_cnt"):
        return None
    return s["lock_wait_cnt"] / s["total_txn_commit_cnt"]
