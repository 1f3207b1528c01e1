"""Deaths over commits + deaths in the server's window, under two-phase
locking: `lock_die_cnt` (device counter `lock_die`,
`cc/twopl.validate_wait_die`: lanes refused a lock that an earlier-ranked
winner of their epoch holds and NOT older than every such owner; under
NO_WAIT every refused lane) over `total_txn_commit_cnt` + `lock_die_cnt`.
A lane that dies restarts after a back-off with the timestamp it was
born with.  A waiter the host's defer budget sends back
(`lock_forced_restart_cnt`) is no death of the rule and is not counted.
A program that counts none (the parent): None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "lock_die_cnt" not in s or not s.get("total_txn_commit_cnt"):
        return None
    return 100.0 * s["lock_die_cnt"] / (s["total_txn_commit_cnt"]
                                        + s["lock_die_cnt"])
