"""Sweep-budget leftovers per committed transaction of the server's
window under two-phase locking: `lock_leftover_cnt` (device counter
`lock_leftover`, `cc/twopl.py`: lanes `ops.greedy_first_fit` left
undecided after `sweep_rounds` rounds — their verdict rests on a longer
chain of earlier verdicts than the budget follows — deferred, neither
granted nor refused) over `total_txn_commit_cnt`.  0.0 where the budget
reaches every chain.  A program that counts none (the parent): None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "lock_leftover_cnt" not in s or not s.get("total_txn_commit_cnt"):
        return None
    return s["lock_leftover_cnt"] / s["total_txn_commit_cnt"]
